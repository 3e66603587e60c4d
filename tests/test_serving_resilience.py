"""Serving resilience layer: lifecycle, tick supervision, drain, reload, shed.

The serving counterpart of tests/test_resilience.py. The load-bearing
invariants, each proven by injecting the fault and watching the blast
radius:

- a fault in one decode tick fails ONLY the slots it poisons (retryable
  error to those clients) — the scheduler thread, the queue, and every
  other request survive untouched (byte-identical to single-request
  ``generate()``);
- the breaker trips the engine into DEGRADED and rebuilds the jitted step
  after N consecutive faults; a clean tick closes it back to READY;
- drain stops admission (retryable 503s), finishes in-flight generations
  up to the deadline, then force-finishes — no handle ever hangs;
- hot reload swaps checkpoints between ticks without retiring a slot, and
  a corrupt/mismatched artifact is rejected with the engine READY on the
  old weights;
- infeasible deadlines shed at admission instead of timing out mid-queue.

Fast deterministic cases run in the quick lane; the full chaos scenario
(decode faults + NaN windows + mid-load SIGTERM over HTTP) carries the
``chaos`` marker: ``make serve-chaos``.
"""
import http.client
import json
import signal
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from zero_transformer_tpu.checkpoint import export_params_msgpack
from zero_transformer_tpu.config import model_config
from zero_transformer_tpu.inference.generate import decode_model, generate
from zero_transformer_tpu.inference.sampling import SamplingConfig
from zero_transformer_tpu.models import Transformer
from zero_transformer_tpu.obs.spans import NAME
from zero_transformer_tpu.serving import (
    DEGRADED,
    DRAINING,
    READY,
    STARTING,
    STOPPED,
    ReloadError,
    ServeFault,
    ServingChaosMonkey,
    ServingEngine,
    ServingServer,
    run_server,
)
from zero_transformer_tpu.serving.resilience import (
    CircuitBreaker,
    ItlEwma,
    Lifecycle,
    infeasible_deadline,
)

CACHE_LEN = 32
SAMPLING = SamplingConfig(temperature=0.9, top_k=20)


@pytest.fixture(scope="module")
def cfg():
    return model_config("test", dropout=0.0, compute_dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    model = Transformer(cfg)
    return model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]


@pytest.fixture(scope="module")
def params2(cfg):
    """A second, differently-initialized tree with the same structure —
    the hot-reload artifact."""
    model = Transformer(cfg)
    return model.init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]


@pytest.fixture(scope="module")
def reference(cfg, params):
    model = decode_model(cfg, CACHE_LEN)

    def run(prompt, seed, max_new=8, p=params):
        toks = generate(
            model, p, jnp.asarray([prompt], jnp.int32), max_new,
            jax.random.PRNGKey(seed), SAMPLING,
        )
        return jax.device_get(toks)[0].tolist()

    return run


def make_engine(cfg, params, clock=None, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("cache_len", CACHE_LEN)
    kw.setdefault("sampling", SAMPLING)
    if clock is not None:
        kw["clock"] = clock
    return ServingEngine(cfg, params, **kw)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class ByteTokenizer:
    eos_token_id = None

    def encode(self, text):
        return list(text.encode("utf-8"))

    def decode(self, ids, **kw):
        return bytes(int(i) % 256 for i in ids).decode("utf-8", errors="replace")


# ----------------------------------------------------------------- lifecycle


def test_lifecycle_state_machine():
    clock = FakeClock()
    lc = Lifecycle(clock)
    assert lc.state == STARTING
    clock.t = 2.0
    assert lc.uptime_s == 2.0
    assert lc.to(READY)
    assert lc.to(DEGRADED) and lc.to(READY, reason="recovered")
    assert lc.to(DRAINING)
    assert not lc.to(READY)  # draining never returns to traffic
    assert not lc.to(DEGRADED)
    assert lc.to(STOPPED)
    assert not lc.to(READY)  # terminal
    assert [s for s, _, _ in lc.history] == [
        STARTING, READY, DEGRADED, READY, DRAINING, STOPPED,
    ]


def test_circuit_breaker_threshold_and_cooldown():
    br = CircuitBreaker(threshold=3, cooldown=2)
    assert not br.record_fault() and not br.record_fault()
    assert br.record_fault()  # 3rd consecutive opens it
    assert br.open and br.trips == 1
    assert not br.record_clean()  # cooldown=2: one clean tick isn't enough
    assert br.record_clean() and not br.open
    # a fault mid-cooldown resets the clean streak
    br2 = CircuitBreaker(threshold=1, cooldown=2)
    assert br2.record_fault() and br2.open
    assert not br2.record_clean()
    br2.record_fault()
    assert not br2.record_clean() and br2.open


def test_run_marks_ready_and_stop_marks_stopped(cfg, params):
    engine = make_engine(cfg, params)
    assert engine.lifecycle.state == STARTING
    stop = threading.Event()
    thread = threading.Thread(target=engine.run, args=(stop,), daemon=True)
    thread.start()
    give_up = time.monotonic() + 30
    while engine.lifecycle.state != READY and time.monotonic() < give_up:
        time.sleep(0.005)
    assert engine.lifecycle.state == READY
    stop.set()
    thread.join(timeout=30)
    assert engine.lifecycle.state == STOPPED


# --------------------------------------------------------- tick supervision


def test_tick_fault_fails_only_active_slots(cfg, params, reference):
    """One poisoned tick: the two decoding requests fail RETRYABLY, the
    queued request survives, admits afterwards, and its trajectory is
    byte-identical to single-request generate() — the scheduler never
    died."""
    chaos = ServingChaosMonkey([ServeFault("tick_fault", step=2, duration=1)])
    engine = make_engine(cfg, params, n_slots=2, chaos=chaos)
    a = engine.submit([1, 2], max_new_tokens=8, seed=0)
    b = engine.submit([3, 4], max_new_tokens=8, seed=1)
    queued = engine.submit([5, 6], max_new_tokens=8, seed=7)
    engine.run_until_idle()
    assert a.status == "failed" and a.retryable and "retryable" in a.error
    assert b.status == "failed" and b.retryable
    assert queued.status == "done"
    assert queued.tokens == reference([5, 6], 7)
    assert engine.stats["tick_faults"] == 1
    assert engine.stats["breaker_trips"] == 0  # one fault < threshold
    # blocked consumers unblocked (terminal events delivered)
    assert a.result(timeout=1) == a.tokens


def test_breaker_trips_rebuilds_and_recovers(cfg, params, reference):
    """Three consecutive faulted ticks open the breaker: DEGRADED, jitted
    step rebuilt, then the next clean tick closes it back to READY and the
    engine serves byte-identical output again."""
    chaos = ServingChaosMonkey([ServeFault("tick_fault", step=1, duration=3)])
    engine = make_engine(cfg, params, n_slots=1, chaos=chaos)
    victims = [engine.submit([i + 1], max_new_tokens=4, seed=i) for i in range(3)]
    engine.step()  # tick 0: clean (admits first victim)
    for _ in range(3):  # ticks 1-3: faulted
        engine.step()
    assert engine.lifecycle.state == DEGRADED
    assert engine.stats["breaker_trips"] == 1
    assert engine._breaker.open
    assert all(v.status == "failed" and v.retryable for v in victims)
    after = engine.submit([9, 9], max_new_tokens=8, seed=5)
    engine.run_until_idle()
    assert engine.lifecycle.state == READY  # clean tick closed the breaker
    assert not engine._breaker.open
    assert after.status == "done" and after.tokens == reference([9, 9], 5)


def test_degraded_idle_engine_self_probes_back_to_ready(cfg, params):
    """An idle DEGRADED engine must close its own breaker: a load balancer
    honoring the 503 sends no traffic, so the engine self-probes with an
    empty fused tick instead of staying DEGRADED forever."""
    chaos = ServingChaosMonkey([ServeFault("tick_fault", step=1, duration=3)])
    engine = make_engine(cfg, params, n_slots=1, chaos=chaos)
    for i in range(3):
        engine.submit([i + 1], max_new_tokens=4, seed=i)
    for _ in range(4):  # tick 0 clean, ticks 1-3 faulted -> breaker opens
        engine.step()
    assert engine.lifecycle.state == DEGRADED
    assert engine.queue_depth == 0 and engine.active_count == 0  # starved
    assert engine.step() is False  # the probe tick reports idle...
    assert engine.lifecycle.state == READY  # ...but proved the engine clean
    assert not engine._breaker.open


def test_breaker_escalates_after_max_rebuilds(cfg, params):
    """A fault that survives every rebuild is structural: the supervised
    tick must stop eating it and escalate out of run() so the replica dies
    loudly (bounded recovery, like the training supervisor's restart
    budget)."""
    chaos = ServingChaosMonkey([ServeFault("tick_fault", step=0, duration=10_000)])
    engine = make_engine(
        cfg, params, n_slots=1, chaos=chaos,
        breaker_threshold=2, max_rebuilds=1,
    )
    for i in range(8):
        engine.submit([i + 1], max_new_tokens=4, seed=i)
    with pytest.raises(RuntimeError, match="rebuilds"):
        engine.run(threading.Event())
    # the abort failed everything outstanding and the engine is dead
    assert engine.lifecycle.state == STOPPED
    late = engine.submit([1], max_new_tokens=2)
    assert late.status == "failed"


def test_nan_logits_retire_only_poisoned_slot(cfg, params, reference):
    """NaN logits in slot 0 retire ONLY slot 0 (retryable error); its
    neighbor's trajectory is byte-identical to an undisturbed run — the
    per-tick guard reuses the training anomaly predicate without a second
    host sync."""
    chaos = ServingChaosMonkey(
        [ServeFault("nan_logits", step=2, duration=1, slots=[0])]
    )
    engine = make_engine(cfg, params, n_slots=2, chaos=chaos)
    poisoned = engine.submit([5, 6], max_new_tokens=8, seed=0)
    neighbor = engine.submit([7, 8], max_new_tokens=8, seed=1)
    engine.run_until_idle()
    assert poisoned.status == "failed" and poisoned.retryable
    assert "non-finite" in poisoned.error
    assert 0 < len(poisoned.tokens) < 8  # partial output delivered
    assert neighbor.status == "done"
    assert neighbor.tokens == reference([7, 8], 1)
    assert engine.stats["poisoned_slots"] == 1
    assert engine.stats["tick_faults"] == 0  # guard path, not fault path
    assert engine.lifecycle.state != DEGRADED  # slot-level, not engine-level


# ---------------------------------------------------------------- draining


def test_drain_under_load(cfg, params, reference):
    """begin_drain: the queued request is rejected retryably AT ONCE, new
    submits bounce with Retry-After, the in-flight generation runs to
    completion (byte-identical), then the engine is STOPPED."""
    engine = make_engine(cfg, params, n_slots=1)
    hog = engine.submit([1, 2, 3], max_new_tokens=8, seed=0)
    queued = engine.submit([4, 5], max_new_tokens=4, seed=1)
    engine.step()  # hog admits
    assert engine.begin_drain(deadline_s=60.0)
    assert not engine.begin_drain(deadline_s=60.0)  # idempotent
    assert queued.status == "rejected" and queued.retryable
    assert "draining" in queued.error and queued.retry_after >= 1.0
    late = engine.submit([6], max_new_tokens=2, seed=2)
    assert late.status == "rejected" and late.retryable
    assert engine.stats["rejected_draining"] == 2
    while not engine.poll_drain():
        engine.step()
    assert hog.status == "done" and hog.tokens == reference([1, 2, 3], 0)
    assert engine.lifecycle.state == STOPPED
    assert engine.drain_latency_s is not None
    assert engine.stats["drain_forced"] == 0


def test_drain_deadline_force_finishes(cfg, params):
    """Past the drain deadline the remaining generation is force-finished
    retryably — the process gets to exit instead of hanging on one slow
    request; the handle still reaches a terminal event."""
    clock = FakeClock()
    engine = make_engine(cfg, params, n_slots=1, clock=clock)
    hog = engine.submit([1, 2], max_new_tokens=30, seed=0)
    engine.step()
    engine.begin_drain(deadline_s=5.0)
    engine.step()
    assert not engine.poll_drain()  # deadline not reached, hog still going
    clock.t = 10.0
    assert engine.poll_drain()
    assert hog.status == "failed" and hog.retryable
    assert "drain deadline" in hog.error
    assert engine.stats["drain_forced"] == 1
    assert engine.lifecycle.state == STOPPED
    assert hog.result(timeout=1) == hog.tokens  # no hang


def test_scheduler_thread_drains_and_exits(cfg, params):
    """The run() loop itself completes a drain: scheduler thread exits on
    its own (the serve_forever SIGTERM path rides on this)."""
    engine = make_engine(cfg, params, n_slots=1)
    stop = threading.Event()
    thread = threading.Thread(target=engine.run, args=(stop,), daemon=True)
    thread.start()
    handle = engine.submit([1, 2], max_new_tokens=6, seed=0)
    give_up = time.monotonic() + 30
    while handle.status == "queued" and time.monotonic() < give_up:
        time.sleep(0.005)
    engine.begin_drain(deadline_s=30.0)
    thread.join(timeout=60)
    assert not thread.is_alive()
    assert handle.status == "done" and len(handle.tokens) == 6
    assert engine.lifecycle.state == STOPPED


# --------------------------------------------------------------- hot reload


def test_hot_reload_swaps_without_retiring_slots(cfg, params, params2, reference):
    """Reload mid-generation: the active slot is never retired (its
    generation completes at full length), the swap lands between ticks,
    and post-reload requests decode with the NEW weights."""
    engine = make_engine(cfg, params, n_slots=1)
    mid = engine.submit([1, 2], max_new_tokens=10, seed=0)
    for _ in range(3):
        engine.step()
    assert mid.status == "running"
    engine.reload_params(params2)
    engine.run_until_idle()
    assert mid.status == "done" and len(mid.tokens) == 10  # slot survived
    assert engine.stats["reloads"] == 1
    assert engine.wait_reload(timeout=0.1)
    fresh = engine.submit([5, 6, 7], max_new_tokens=8, seed=3)
    engine.run_until_idle()
    assert fresh.status == "done"
    assert fresh.tokens == reference([5, 6, 7], 3, p=params2)
    assert fresh.tokens != reference([5, 6, 7], 3)  # weights really swapped


def test_reload_rejects_mismatched_and_corrupt(cfg, params, reference):
    """A wrong-model or corrupt artifact raises ReloadError; the engine
    stays READY on the old weights and keeps producing byte-identical
    output."""
    engine = make_engine(cfg, params, n_slots=1)
    stop = threading.Event()
    thread = threading.Thread(target=engine.run, args=(stop,), daemon=True)
    thread.start()
    try:
        with pytest.raises(ReloadError, match="mismatch"):
            engine.reload_params({"bogus": jnp.zeros((2, 2), jnp.float32)})
        wrong_shape = jax.tree.map(lambda x: jnp.zeros((1,) + x.shape, x.dtype), params)
        with pytest.raises(ReloadError, match="mismatch"):
            engine.reload_params(wrong_shape)

        def corrupt_loader():
            raise OSError("truncated msgpack")

        with pytest.raises(ReloadError, match="failed to load"):
            engine.reload_params(corrupt_loader)
        assert engine.stats["reloads_rejected"] == 3
        assert engine.stats["reloads"] == 0
        assert engine.lifecycle.state == READY  # never left
        handle = engine.submit([3, 7, 11], max_new_tokens=8, seed=0)
        assert handle.result(timeout=60) == reference([3, 7, 11], 0)
    finally:
        stop.set()
        thread.join(timeout=30)


@pytest.fixture(scope="module")
def cfg_bf16():
    """float32 weights multiplied in bfloat16, as ``serve`` loads a
    training checkpoint: the engine holds the matmul leaves converted."""
    return model_config("test", dropout=0.0, compute_dtype="bfloat16")


def _bytes(tree, dtype=None):
    return sum(
        x.nbytes for x in jax.tree.leaves(tree) if dtype is None or x.dtype == dtype
    )


def test_float32_checkpoint_reloads_into_an_engine_that_holds_bfloat16(
    cfg_bf16, params, params2
):
    """The reload is validated against the SOURCE form the engine was built
    from, not against what it holds: a float32 artifact swaps in, is held
    converted like the tree it replaces, the in-flight stream survives, and
    a fresh stream equals ``generate()`` under the new float32 weights."""
    engine = make_engine(cfg_bf16, params, n_slots=1)
    held = jax.tree.leaves(engine.params)
    assert {x.dtype for x in held} == {jnp.dtype("bfloat16"), jnp.dtype("float32")}
    mid = engine.submit([1, 2], max_new_tokens=10, seed=0)
    for _ in range(3):
        engine.step()
    assert mid.status == "running"
    engine.reload_params(params2)
    engine.run_until_idle()
    assert mid.status == "done" and len(mid.tokens) == 10
    assert engine.stats["reloads"] == 1 and engine.stats["reloads_rejected"] == 0
    assert [x.dtype for x in jax.tree.leaves(engine.params)] == [x.dtype for x in held]
    fresh = engine.submit([5, 6, 7], max_new_tokens=8, seed=3)
    engine.run_until_idle()
    model = decode_model(cfg_bf16, CACHE_LEN)

    def want(p):
        toks = generate(
            model, p, jnp.asarray([[5, 6, 7]], jnp.int32), 8,
            jax.random.PRNGKey(3), SAMPLING,
        )
        return jax.device_get(toks)[0].tolist()

    assert fresh.tokens == want(params2)
    assert fresh.tokens != want(params)  # weights really swapped


def test_reload_refuses_a_candidate_of_another_source_dtype(cfg_bf16, params):
    """What the engine HOLDS is no valid artifact: a tree already rounded to
    bfloat16 differs from the float32 source form and is refused, the first
    differing leaf named."""
    engine = make_engine(cfg_bf16, params, n_slots=1)
    rounded = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    with pytest.raises(ReloadError) as err:
        engine.reload_params(rounded)
    assert "['blocks']['attn']['key']['kernel']" in str(err.value)
    assert "float32" in str(err.value) and "bfloat16" in str(err.value)
    with pytest.raises(ReloadError, match="mismatch"):
        engine.reload_params(engine.params)
    assert engine.stats["reloads_rejected"] == 2 and engine.stats["reloads"] == 0


def test_prepare_weights_span_and_byte_gauges(cfg, cfg_bf16, params, params2):
    """``engine/prepare_weights`` is in the ring at start and at each
    reload; ``metrics_snapshot()`` says what is held and how much of it a
    conversion made (nothing, for a model served in its compute dtype:
    the engine then holds the very arrays it was given)."""
    engine = make_engine(cfg_bf16, params, n_slots=1)

    def spans():
        return [s for s in engine.tracer.by_track("engine") if s[NAME] == "prepare_weights"]

    assert len(spans()) == 1
    snap = engine.metrics_snapshot()
    assert snap["weights_bytes_held"] == _bytes(engine.params)
    assert snap["weights_bytes_converted_at_load"] == _bytes(engine.params, jnp.bfloat16)
    assert 0 < snap["weights_bytes_converted_at_load"] < snap["weights_bytes_held"]
    assert snap["weights_bytes_held"] < _bytes(params)
    engine.reload_params(params2)
    assert len(spans()) == 2  # made on the reload's thread, before the swap
    engine.step()
    assert engine.metrics_snapshot()["weights_bytes_held"] == snap["weights_bytes_held"]

    same = make_engine(cfg, params, n_slots=1)  # float32 compute: nothing to convert
    assert same.metrics_snapshot()["weights_bytes_converted_at_load"] == 0
    assert same.metrics_snapshot()["weights_bytes_held"] == _bytes(params)
    for a, b in zip(jax.tree.leaves(same.params), jax.tree.leaves(params)):
        assert a is b


def test_chaos_corrupt_reload_artifact_rejected(cfg, params, params2):
    """The chaos corrupt_reload fault mangles a VALID artifact between load
    and validation — the reject path the acceptance bar names."""
    chaos = ServingChaosMonkey([ServeFault("corrupt_reload", step=0)])
    engine = make_engine(cfg, params, n_slots=1, chaos=chaos)
    with pytest.raises(ReloadError, match="mismatch"):
        engine.reload_params(params2)
    assert engine.stats["reloads_rejected"] == 1
    # the fault is one-shot: the retry goes through clean
    engine.reload_params(params2)
    engine.step()
    assert engine.stats["reloads"] == 1


# ------------------------------------------------------------ load shedding


def test_infeasible_deadline_sheds_at_admission(cfg, params):
    """With a measured ITL, a deadline that provably cannot be met is shed
    as a fast retryable rejection instead of expiring mid-queue; feasible
    deadlines still admit."""
    clock = FakeClock()
    engine = make_engine(cfg, params, n_slots=1, clock=clock, shed_warmup=4)
    for _ in range(8):  # seed the EWMA: 0.1 s/token measured
        engine._itl_ewma.update(0.1)
    doomed = engine.submit([1, 2], max_new_tokens=20, seed=0, deadline=1.0)
    assert doomed.status == "rejected" and doomed.retryable
    assert "shed" in doomed.error
    assert engine.stats["shed_infeasible"] == 1
    feasible = engine.submit([1, 2], max_new_tokens=20, seed=0, deadline=100.0)
    assert feasible.status == "queued"
    engine.run_until_idle()
    assert feasible.status == "done"


def test_shed_is_inert_before_warmup(cfg, params):
    """A cold engine has no ITL evidence — nothing sheds, whatever the
    deadline (the guard must be provable, not a guess)."""
    clock = FakeClock()
    engine = make_engine(cfg, params, n_slots=1, clock=clock)
    tight = engine.submit([1], max_new_tokens=20, seed=0, deadline=0.001)
    assert tight.status == "queued"  # admitted; deadline enforcement owns it
    assert engine.stats["shed_infeasible"] == 0


def test_infeasible_deadline_math():
    itl = ItlEwma(decay=0.9, warmup=2)
    assert not infeasible_deadline(1.0, 0.0, 100, 0, 1, itl)  # cold: inert
    itl.update(0.05)
    itl.update(0.05)
    # 100 tokens * 50ms = 5s floor; deadline in 1s is provably infeasible
    assert infeasible_deadline(1.0, 0.0, 100, 0, 1, itl)
    assert not infeasible_deadline(10.0, 0.0, 100, 0, 1, itl)
    # queue depth pushes the bound out
    assert infeasible_deadline(6.0, 0.0, 100, 30, 1, itl)


# ----------------------------------------------------------------- HTTP API


def _get(conn, path):
    conn.request("GET", path)
    resp = conn.getresponse()
    return resp, json.loads(resp.read())


def test_healthz_lifecycle_codes_and_body(cfg, params):
    """503 (not 200) whenever the engine is not READY — starting, draining,
    stopped — with the lifecycle fields in the body."""
    engine = make_engine(cfg, params)
    server = ServingServer(engine, ByteTokenizer(), port=0)
    server.start(start_scheduler=False)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=30)
        resp, body = _get(conn, "/healthz")
        assert resp.status == 503 and body["state"] == "starting"
        for key in ("state", "uptime_s", "reloads", "breaker_open"):
            assert key in body, key
        server.start_scheduler()
        give_up = time.monotonic() + 30
        while engine.lifecycle.state != READY and time.monotonic() < give_up:
            time.sleep(0.005)
        resp, body = _get(conn, "/healthz")
        assert resp.status == 200 and body["status"] == "ok"
        assert body["state"] == "ready" and body["breaker_open"] is False
        conn.close()
    finally:
        server.stop()
    # draining answers 503: on a server whose scheduler never runs, the
    # drain can't complete underneath the probe (an IDLE engine drains to
    # STOPPED instantly — also a 503, but a different state string)
    engine2 = make_engine(cfg, params)
    server2 = ServingServer(engine2, ByteTokenizer(), port=0)
    server2.start(start_scheduler=False)
    try:
        engine2.begin_drain(deadline_s=30.0)
        conn = http.client.HTTPConnection("127.0.0.1", server2.port, timeout=30)
        resp, body = _get(conn, "/healthz")
        assert resp.status == 503 and body["state"] == "draining"
        conn.close()
    finally:
        server2.stop()


def test_oversized_body_413(cfg, params):
    engine = make_engine(cfg, params)
    server = ServingServer(engine, ByteTokenizer(), port=0, max_body_bytes=512)
    server.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=30)
        conn.request(
            "POST", "/generate", b'{"prompt": "' + b"x" * 4096 + b'"}',
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        assert resp.status == 413
        assert "exceeds" in json.loads(resp.read())["error"]
        conn.close()
    finally:
        server.stop()


def test_draining_maps_to_503_with_retry_after(cfg, params):
    # scheduler deliberately NOT started: an idle engine's drain completes
    # instantly (STOPPED -> the dead-engine 503), and this test pins the
    # DRAINING rejection contract specifically
    engine = make_engine(cfg, params)
    server = ServingServer(engine, ByteTokenizer(), port=0)
    server.start(start_scheduler=False)
    try:
        engine.begin_drain(deadline_s=30.0)
        conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=30)
        conn.request("POST", "/generate", json.dumps({"prompt": "ab"}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 503
        assert int(resp.getheader("Retry-After")) >= 1
        assert "draining" in json.loads(resp.read())["error"]
        conn.close()
    finally:
        server.stop()


def test_admin_reload_endpoint(cfg, params, params2, tmp_path):
    """POST /admin/reload: a good artifact swaps (200, reloads=1) without
    retiring anything; a corrupt artifact is 409 with the engine READY."""
    from zero_transformer_tpu.parallel.sharding import unbox

    good = export_params_msgpack(unbox(params2), tmp_path / "good.msgpack")
    corrupt = tmp_path / "corrupt.msgpack"
    corrupt.write_bytes(good.read_bytes()[: good.stat().st_size // 2])
    engine = make_engine(cfg, params)
    server = ServingServer(engine, ByteTokenizer(), port=0)
    server.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=60)
        conn.request("POST", "/admin/reload",
                     json.dumps({"params": str(good)}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200, body
        assert body["reloaded"] is True and body["reloads"] == 1
        conn.request("POST", "/admin/reload",
                     json.dumps({"params": str(corrupt)}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 409
        assert body["state"] == "ready" and body["reloads"] == 1
        resp, health = _get(conn, "/healthz")
        assert resp.status == 200  # still serving on the good weights
        conn.close()
    finally:
        server.stop()


def test_metrics_exports_resilience_counters(cfg, params):
    engine = make_engine(cfg, params)
    engine.submit([1, 2], max_new_tokens=4, seed=0)
    engine.run_until_idle()
    snap = engine.metrics_snapshot()
    for key in (
        "state", "uptime_s", "breaker_open", "itl_ewma_ms",
        "tick_faults", "poisoned_slots", "breaker_trips", "shed_infeasible",
        "rejected_draining", "drain_forced", "reloads", "reloads_rejected",
    ):
        assert key in snap, key


def test_resilience_events_land_in_metrics_timeline(cfg, params, tmp_path):
    """Breaker trips / poisoned slots / reload / drain emit
    MetricsLogger.event() entries — the same JSONL timeline PR 2
    established for training incidents."""
    from zero_transformer_tpu.utils.monitoring import MetricsLogger

    metrics = MetricsLogger(directory=tmp_path)
    chaos = ServingChaosMonkey(
        [ServeFault("nan_logits", step=2, duration=1, slots=[0])]
    )
    engine = make_engine(cfg, params, n_slots=1, chaos=chaos, metrics=metrics)
    engine.submit([1, 2], max_new_tokens=8, seed=0)
    engine.run_until_idle()
    engine.begin_drain(deadline_s=10.0)
    while not engine.poll_drain():
        engine.step()
    metrics.close()
    events = [
        json.loads(line)["event"]
        for line in (tmp_path / "metrics.jsonl").read_text().splitlines()
        if "event" in json.loads(line)
    ]
    assert "poisoned_slots" in events
    assert "drain_begin" in events and "drain_done" in events


# ------------------------------------------------------------- chaos proof


@pytest.mark.chaos
def test_serving_chaos_end_to_end(cfg, params, reference):
    """The acceptance-bar scenario over the real HTTP server: decode faults
    + NaN-logit windows + a mid-load SIGTERM. No in-flight request hangs
    (every handle reaches a terminal event), the server drains and the
    scheduler exits cleanly, and every request untouched by a fault is
    byte-identical to an undisturbed run with the same seed."""
    prompts = [[3 + i, 7, 11 + i] for i in range(10)]
    refs = {i: reference(p, i, max_new=12) for i, p in enumerate(prompts)}

    chaos = ServingChaosMonkey([
        ServeFault("tick_fault", step=8, duration=1),
        ServeFault("nan_logits", step=16, duration=1, slots=[0]),
        ServeFault("sigterm", step=24),
    ])
    engine = make_engine(cfg, params, n_slots=2, chaos=chaos, max_queue=64)
    server = ServingServer(engine, ByteTokenizer(), port=0)
    old_term = signal.getsignal(signal.SIGTERM)
    old_hup = signal.getsignal(signal.SIGHUP)
    server.install_signal_handlers(drain_deadline_s=30.0)
    server.start()
    results = {}
    lock = threading.Lock()

    def client(i):
        conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=120)
        try:
            conn.request(
                "POST", "/generate",
                json.dumps({"tokens": prompts[i], "max_new_tokens": 12,
                            "seed": i, "stream": False}),
                {"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            doc = json.loads(resp.read())
            with lock:
                results[i] = (resp.status, doc)
        except Exception as exc:  # connection torn down mid-drain: terminal too
            with lock:
                results[i] = (None, {"status": "connection_error", "error": repr(exc)})
        finally:
            conn.close()

    try:
        threads = [
            threading.Thread(target=client, args=(i,), daemon=True)
            for i in range(len(prompts))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads), "a client hung"

        # SIGTERM fired mid-load -> the handler drained the engine and shut
        # the server down; the scheduler thread must have exited cleanly
        give_up = time.monotonic() + 60
        while engine.lifecycle.state != STOPPED and time.monotonic() < give_up:
            time.sleep(0.02)
        assert engine.lifecycle.state == STOPPED
        server._scheduler.join(timeout=30)
        assert not server._scheduler.is_alive()
        assert engine.active_count == 0 and engine.queue_depth == 0

        assert chaos.fired_log, "no fault fired"
        statuses = [doc.get("status") for _, doc in results.values()]
        completed = [
            i for i, (code, doc) in results.items()
            if code == 200 and doc.get("status") == "done"
        ]
        # every request reached a terminal outcome (done / failed /
        # rejected / connection closed by drain) — none hung, none vanished
        assert len(results) == len(prompts)
        # the byte-identical bar: untouched (completed) requests match the
        # undisturbed run exactly
        assert completed, f"nothing completed: {statuses}"
        for i in completed:
            assert results[i][1]["tokens"] == refs[i], f"request {i} garbled"
    finally:
        server.stop()
        signal.signal(signal.SIGTERM, old_term)
        signal.signal(signal.SIGHUP, old_hup)

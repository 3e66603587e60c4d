"""Continuous-batching serving engine: scheduler state machine + parity.

The load-bearing invariant is REQUEST ISOLATION: a request admitted into a
slot must produce the same token trajectory as single-request ``generate()``
with the same seed, whatever its neighbors do — admissions, retirements,
cancellations, and deadline expiries in other slots must never perturb it.
Everything runs the ``test`` zoo model on CPU; the fake-clock tests drive
``step()`` by hand so deadline semantics are deterministic.
"""
import dataclasses
import http.client
import inspect
import json

import jax
import jax.numpy as jnp
import pytest

from zero_transformer_tpu.config import ServingConfig, model_config
from zero_transformer_tpu.inference.generate import decode_model, generate
from zero_transformer_tpu.inference.sampling import SamplingConfig
from zero_transformer_tpu.models import Transformer
from zero_transformer_tpu.serving import ServingEngine, StreamDecoder, run_server

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
def reference(cfg, params):
    """Single-request ``generate()`` tokens for (prompt, seed, max_new)."""
    model = decode_model(cfg, CACHE_LEN)

    def run(prompt, seed, max_new=8):
        toks = generate(
            model, params, jnp.asarray([prompt], jnp.int32), max_new,
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
    """Manually-advanced monotonic clock for deadline tests."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# ------------------------------------------- one engine, one set of defaults

# ServingConfig's name for a constructor parameter, where the two differ
_ENGINE_PARAM = {"slots": "n_slots"}
_SHARED_FIELDS = [
    f.name for f in dataclasses.fields(ServingConfig)
    if _ENGINE_PARAM.get(f.name, f.name)
    in inspect.signature(ServingEngine.__init__).parameters
]


@pytest.mark.parametrize("field", _SHARED_FIELDS)
def test_engine_defaults_are_serving_configs(field):
    """What ``ServingEngine(cfg, params)`` builds is what ``serve --server``
    builds from ``ServingConfig()``: every parameter the constructor shares
    with the config has the config's default, so a test that passes none
    runs the engine users run."""
    param = inspect.signature(ServingEngine.__init__).parameters[
        _ENGINE_PARAM.get(field, field)
    ]
    assert param.default == getattr(ServingConfig(), field)
    assert {"prefill_chunk", "prefix_cache_chunks", "page_size"} <= set(_SHARED_FIELDS)


def _refused_by_the_cli(cfg, params):
    from zero_transformer_tpu import serve

    serve.main(["--model", "test", "--params", "unread.msgpack", "--server",
                "--prefill-chunk", "0"])


@pytest.mark.parametrize("build", [
    lambda cfg, params: make_engine(cfg, params, kv_layout="slab"),
    lambda cfg, params: make_engine(cfg, params, fused_tail=False),
    lambda cfg, params: make_engine(cfg, params, prefill_chunk=0),
    _refused_by_the_cli,
], ids=["kv_layout=slab", "fused_tail=False", "prefill_chunk=0",
        "serve --prefill-chunk 0"])
def test_removed_paths_are_refused_by_name(cfg, params, build, capsys):
    """The slab layout, the defused tail and one-shot prefill are gone: a
    caller that still asks for one is told so, not served by another path.
    (The keyword names stay accepted at their only remaining values because
    the benchmark's traffic files pass them.)"""
    with pytest.raises((ValueError, SystemExit)) as refusal:
        build(cfg, params)
    said = str(refusal.value) + capsys.readouterr().err
    assert "was removed" in said
    make_engine(cfg, params, kv_layout="paged", fused_tail=True,
                max_prefill_buckets=8)


# --------------------------------------------------------------- state machine


def test_slot_exhaustion_queues_then_completes(cfg, params, reference):
    """5 requests into 2 slots: the overflow queues, every request still
    finishes with its exact single-request trajectory, and occupancy peaks
    at (not above) the slot count."""
    prompts = [[3 + i, 7, 11 + i] for i in range(5)]
    engine = make_engine(cfg, params, n_slots=2)
    handles = [
        engine.submit(p, max_new_tokens=8, seed=i) for i, p in enumerate(prompts)
    ]
    assert engine.queue_depth == 5  # nothing admits until a tick runs
    engine.run_until_idle()
    for i, (p, h) in enumerate(zip(prompts, handles)):
        assert h.status == "done"
        assert h.tokens == reference(p, i)
    snap = engine.metrics_snapshot()
    assert snap["peak_occupancy"] == 2
    assert snap["completed"] == 5
    assert snap["peak_queue_depth"] == 5


def test_interleaved_admission_preserves_outputs(cfg, params, reference):
    """Mid-flight admissions (the continuous-batching case) must not
    perturb running requests: interleave submits with ticks and compare
    every trajectory to the single-request baseline."""
    engine = make_engine(cfg, params, n_slots=2)
    first = [engine.submit([10, 20, 30], max_new_tokens=8, seed=0),
             engine.submit([40, 50], max_new_tokens=8, seed=1)]
    for _ in range(3):  # partially decode the first wave
        engine.step()
    late = [engine.submit([60, 61, 62, 63], max_new_tokens=8, seed=2),
            engine.submit([70], max_new_tokens=8, seed=3)]
    engine.run_until_idle()
    expect = [([10, 20, 30], 0), ([40, 50], 1), ([60, 61, 62, 63], 2), ([70], 3)]
    for h, (p, s) in zip(first + late, expect):
        assert h.status == "done"
        assert h.tokens == reference(p, s)


def test_deadline_expiry_in_queue(cfg, params):
    clock = FakeClock()
    engine = make_engine(cfg, params, n_slots=1, clock=clock)
    hog = engine.submit([1, 2, 3], max_new_tokens=12, seed=0)
    doomed = engine.submit([4, 5, 6], max_new_tokens=4, seed=1, deadline=5.0)
    engine.step()  # hog admits; doomed waits
    clock.t = 10.0  # deadline passes while queued
    engine.run_until_idle()
    assert hog.status == "done" and len(hog.tokens) == 12
    assert doomed.status == "expired" and doomed.tokens == []
    assert "queue" in doomed.error
    assert engine.stats["expired_queued"] == 1


def test_queued_deadline_expires_while_all_slots_busy(cfg, params):
    """A queued request's deadline (and a queued cancel) must be honored on
    the NEXT TICK even when no slot frees — not deferred until admission
    finally pops it. Regression: the sweep used to live inside _admit's
    free-slot loop, so a busy engine held expired requests (and their
    blocked result() callers) hostage to the longest running generation."""
    clock = FakeClock()
    engine = make_engine(cfg, params, n_slots=1, clock=clock)
    hog = engine.submit([1, 2, 3], max_new_tokens=12, seed=0)
    doomed = engine.submit([4, 5, 6], max_new_tokens=4, seed=1, deadline=5.0)
    axed = engine.submit([7, 8], max_new_tokens=4, seed=2)
    engine.step()  # hog admits and holds the only slot
    clock.t = 10.0
    axed.cancel()
    engine.step()  # hog still decoding — the sweep alone must finish both
    assert hog.status == "running"
    assert doomed.status == "expired" and "queue" in doomed.error
    assert axed.status == "cancelled"
    assert engine.stats["expired_queued"] == 1
    assert engine.stats["cancelled"] == 1
    engine.run_until_idle()
    assert hog.status == "done" and len(hog.tokens) == 12


def test_deadline_expiry_mid_decode(cfg, params):
    clock = FakeClock()
    engine = make_engine(cfg, params, n_slots=2, clock=clock)
    doomed = engine.submit([1, 2, 3], max_new_tokens=20, seed=0, deadline=5.0)
    safe = engine.submit([4, 5, 6], max_new_tokens=20, seed=1)
    for _ in range(3):
        engine.step()
    assert doomed.status == "running" and len(doomed.tokens) == 3
    clock.t = 6.0  # expire mid-decode
    engine.run_until_idle()
    assert doomed.status == "expired" and len(doomed.tokens) == 3
    assert "mid-decode" in doomed.error
    assert safe.status == "done" and len(safe.tokens) == 20
    assert engine.stats["expired_decoding"] == 1


def test_cancellation_frees_slot_for_queued_request(cfg, params, reference):
    engine = make_engine(cfg, params, n_slots=1)
    hog = engine.submit([9, 9, 9], max_new_tokens=30, seed=0)
    waiting = engine.submit([5, 6], max_new_tokens=8, seed=7)
    for _ in range(2):
        engine.step()
    assert hog.status == "running" and waiting.status == "queued"
    hog.cancel()
    engine.run_until_idle()
    assert hog.status == "cancelled" and len(hog.tokens) == 2
    assert engine.stats["cancelled"] == 1
    # the freed slot served the queued request, unperturbed
    assert waiting.status == "done"
    assert waiting.tokens == reference([5, 6], 7)


def test_cancel_while_queued_never_admits(cfg, params):
    engine = make_engine(cfg, params, n_slots=1)
    hog = engine.submit([1], max_new_tokens=4, seed=0)
    queued = engine.submit([2], max_new_tokens=4, seed=1)
    queued.cancel()
    engine.run_until_idle()
    assert hog.status == "done"
    assert queued.status == "cancelled" and queued.tokens == []


def test_queue_full_rejects_with_backpressure(cfg, params):
    engine = make_engine(cfg, params, n_slots=1, max_queue=2)
    ok = [engine.submit([1], max_new_tokens=2, seed=i) for i in range(2)]
    rejected = engine.submit([2], max_new_tokens=2, seed=9)
    assert rejected.status == "rejected" and "queue full" in rejected.error
    assert engine.stats["rejected_queue_full"] == 1
    engine.run_until_idle()
    assert all(h.status == "done" for h in ok)


def test_invalid_requests_reject_at_submit(cfg, params):
    engine = make_engine(cfg, params)
    empty = engine.submit([], max_new_tokens=4)
    assert empty.status == "rejected" and "empty" in empty.error
    too_long = engine.submit([1] * 30, max_new_tokens=20)
    assert too_long.status == "rejected" and "cache_len" in too_long.error
    assert engine.stats["rejected_invalid"] == 2


def test_result_blocks_until_done_and_stream_yields_all(cfg, params, reference):
    """The thread-facing consumer API, driven from a scheduler thread."""
    import threading

    engine = make_engine(cfg, params)
    stop = threading.Event()
    thread = threading.Thread(target=engine.run, args=(stop,), daemon=True)
    thread.start()
    try:
        handle = engine.submit([11, 12, 13], max_new_tokens=8, seed=4)
        streamed = list(handle.stream(timeout=60))
        assert streamed == handle.result(timeout=1)
        assert streamed == reference([11, 12, 13], 4)
    finally:
        stop.set()
        thread.join(timeout=10)


def test_int8_kv_cache_parity(params):
    """The slot cache inherits int8-KV quantization from init_cache; the
    engine must stay token-identical to generate() under the same cfg."""
    qcfg = model_config(
        "test", dropout=0.0, compute_dtype="float32", kv_cache_dtype="int8"
    )
    model = decode_model(qcfg, CACHE_LEN)
    ref = jax.device_get(
        generate(model, params, jnp.asarray([[7, 8, 9]], jnp.int32), 8,
                 jax.random.PRNGKey(3), SAMPLING)
    )[0].tolist()
    engine = make_engine(qcfg, params, n_slots=2)
    handle = engine.submit([7, 8, 9], max_new_tokens=8, seed=3)
    engine.run_until_idle()
    assert handle.status == "done" and handle.tokens == ref


def test_scheduler_crash_fails_outstanding_requests_loudly(cfg, params):
    """A step() exception must not strand clients: every queued and active
    handle finishes as ``failed`` (unblocking result()/stream() waiters)
    and the exception re-raises out of run() instead of dying silently."""
    import threading

    engine = make_engine(cfg, params, n_slots=1)
    running = engine.submit([1, 2], max_new_tokens=8, seed=0)
    queued = engine.submit([3, 4], max_new_tokens=8, seed=1)
    engine.step()  # admit the first request
    assert running.status == "running"

    real_step = engine.step
    calls = {"n": 0}

    def dying_step():
        calls["n"] += 1
        if calls["n"] > 1:
            raise RuntimeError("boom")
        return real_step()

    engine.step = dying_step
    with pytest.raises(RuntimeError, match="boom"):
        engine.run(threading.Event())
    assert running.status == "failed" and "boom" in running.error
    assert queued.status == "failed"
    # blocked consumers unblock immediately (no TimeoutError)
    assert running.result(timeout=1) == running.tokens
    # and the dead engine fails NEW submits fast instead of queueing them
    # onto a queue no thread will ever drain
    late = engine.submit([5, 6], max_new_tokens=4, seed=2)
    assert late.status == "failed" and "boom" in late.error


def test_percentiles_nearest_rank():
    """p50 of an odd sample list is the true median — int(round()) banker's
    rounding regressed it to the 2nd-smallest of 5."""
    from zero_transformer_tpu.serving.engine import _percentiles

    assert _percentiles([1, 2, 3, 4, 5])["p50"] == 3
    assert _percentiles([5, 1])["p50"] == 1
    assert _percentiles([7.0])["p99"] == 7.0
    assert _percentiles([])["p90"] == 0.0


def test_graceful_stop_fails_outstanding_requests(cfg, params):
    """stop() mid-decode must not strand blocked consumers: run() aborts
    whatever is still queued or in a slot on the way out."""
    import threading

    engine = make_engine(cfg, params, n_slots=1)
    hog = engine.submit([1, 2], max_new_tokens=30, seed=0)
    queued = engine.submit([3], max_new_tokens=4, seed=1)
    stop = threading.Event()
    thread = threading.Thread(target=engine.run, args=(stop,), daemon=True)
    thread.start()
    import time as time_mod

    give_up = time_mod.monotonic() + 30
    while hog.status == "queued" and time_mod.monotonic() < give_up:
        time_mod.sleep(0.005)  # let the hog admit
    stop.set()
    thread.join(timeout=30)
    assert hog.status in ("failed", "done")  # done iff it finished pre-stop
    assert queued.status in ("failed", "done")
    # a dead (stopped) engine fails fresh submits fast
    late = engine.submit([5], max_new_tokens=2, seed=2)
    assert late.status == "failed" and "stopped" in late.error


def test_metrics_snapshot_schema(cfg, params):
    engine = make_engine(cfg, params)
    engine.submit([1, 2], max_new_tokens=4, seed=0)
    engine.run_until_idle()
    snap = engine.metrics_snapshot()
    for key in (
        "tokens_per_sec", "slot_occupancy", "queue_depth",
        "ttft_ms_p50", "ttft_ms_p90", "ttft_ms_p99",
        "itl_ms_p50", "itl_ms_p90", "itl_ms_p99",
        "submitted", "completed", "tokens_out", "peak_occupancy",
    ):
        assert key in snap, key
    assert snap["completed"] == 1 and snap["tokens_out"] == 4


# --------------------------------------------------------------------- detok


class ByteTokenizer:
    """Token id == byte value: multi-byte UTF-8 chars genuinely span
    tokens, exactly the hazard StreamDecoder exists for."""

    eos_token_id = 0

    def encode(self, text):
        return list(text.encode("utf-8"))

    def decode(self, ids, **kw):
        return bytes(ids).decode("utf-8", errors="replace")


def test_stream_decoder_holds_incomplete_multibyte():
    dec = StreamDecoder(ByteTokenizer())
    tokens = list("héllo".encode("utf-8"))  # é = 0xC3 0xA9
    pieces = [dec.push(t) for t in tokens]
    assert pieces[1] is None  # 0xC3 alone would decode to U+FFFD
    assert "".join(p for p in pieces if p) == "héllo"
    assert dec.flush() is None


def test_stream_decoder_flush_emits_tail():
    dec = StreamDecoder(ByteTokenizer())
    assert dec.push(0xC3) is None
    assert dec.flush() == "�"  # genuinely truncated stream: tail surfaces


# ------------------------------------------------------------------- server


def test_http_server_end_to_end(cfg, params):
    """Full admit→prefill→decode→stream→retire lifecycle over HTTP: SSE
    stream, non-streaming JSON, /healthz, /metrics, and 400 backpressure
    mapping — on an ephemeral port, fully on CPU."""
    engine = make_engine(cfg, params)
    server = run_server(engine, ByteTokenizer(), port=0, background=True)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=60)

        def post(body):
            conn.request("POST", "/generate", json.dumps(body),
                         {"Content-Type": "application/json"})
            return conn.getresponse()

        # non-streaming JSON
        resp = post({"prompt": "ab", "max_new_tokens": 6, "seed": 1,
                     "stream": False})
        assert resp.status == 200
        doc = json.loads(resp.read())
        assert doc["status"] == "done" and len(doc["tokens"]) == 6

        # SSE stream: events concatenate to the final text
        resp = post({"tokens": [65, 66, 67], "max_new_tokens": 6, "seed": 2})
        assert resp.status == 200
        assert resp.getheader("Content-Type") == "text/event-stream"
        events = [
            json.loads(line[len(b"data: "):])
            for line in resp.read().split(b"\n\n")
            if line.startswith(b"data: ")
        ]
        assert events[-1]["done"] and events[-1]["status"] == "done"
        assert "".join(e["text"] for e in events[:-1]) == events[-1]["text"]

        # invalid request maps to 400, not a stream
        resp = post({"tokens": [], "max_new_tokens": 4})
        assert resp.status == 400 and "empty" in json.loads(resp.read())["error"]

        # ill-TYPED field values are also the client's fault: 400 with the
        # field named, never a dropped connection
        resp = post({"prompt": "ab", "timeout": "abc"})
        assert resp.status == 400
        assert "bad request field" in json.loads(resp.read())["error"]

        # valid JSON that is not an object: 400, not a handler traceback
        resp = post([1, 2, 3])
        assert resp.status == 400
        assert "JSON object" in json.loads(resp.read())["error"]

        conn.request("GET", "/healthz")
        health = json.loads(conn.getresponse().read())
        assert health["status"] == "ok" and health["slots"] == 2

        conn.request("GET", "/metrics")
        metrics = json.loads(conn.getresponse().read())
        assert metrics["completed"] == 2 and "ttft_ms_p50" in metrics
        conn.close()
    finally:
        server.stop()

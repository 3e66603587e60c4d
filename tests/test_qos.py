"""Overload isolation plane: QoS classes, fair admission, brownout.

Three tiers of evidence, cheapest first:

- **pure logic** (no jax, no sockets): token buckets, the DWRR class
  queue's fairness proportions and floor gating, reservation arithmetic,
  and the brownout controller's hysteresis ladder;
- **real engine** (test zoo model, CPU): per-tenant quota isolation,
  queue-full shedding that evicts a LOWER class, gold preemption of a
  running batch stream, slot-reservation floors, the brownout rungs'
  admission effects, per-class histogram exposition, and the stalled-SSE
  client's bounded emit buffer (chaos ``slow_client``) with neighbor
  byte-parity;
- **router** (real replica fleet): the dict SLO config carrying qos +
  brownout blocks, per-class objective binding to class-suffixed
  histogram families, the fleet brownout controller pushing rungs to
  replicas and fully reverting, fleet-level tenant quotas, and
  tenant-affinity routing.

The multi-tenant flood proof (one tenant floods a 2-replica fleet; the
gold tenant's latency and ``dropped_streams`` are pinned) is
slow+chaos-marked: ``make tenant-chaos``.
"""
import http.client
import json
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from zero_transformer_tpu.config import model_config
from zero_transformer_tpu.inference.generate import decode_model, generate
from zero_transformer_tpu.inference.sampling import SamplingConfig
from zero_transformer_tpu.models import Transformer
from zero_transformer_tpu.obs.fleet import TenantLedger
from zero_transformer_tpu.serving import (
    BROWNOUT_RUNGS,
    BrownoutController,
    ClassQueue,
    QosPolicy,
    RouterServer,
    ServeFault,
    ServingChaosMonkey,
    ServingEngine,
    ServingServer,
    TokenBucket,
    rung_at_least,
)
from zero_transformer_tpu.serving.qos import TenantBuckets, reserved_above

REPO = Path(__file__).resolve().parent.parent
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
    model = decode_model(cfg, CACHE_LEN)

    def run(prompt, seed, max_new=8):
        toks = generate(
            model, params, jnp.asarray([prompt], jnp.int32), max_new,
            jax.random.PRNGKey(seed), SAMPLING,
        )
        return jax.device_get(toks)[0].tolist()

    return run


def make_engine(cfg, params, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("cache_len", CACHE_LEN)
    kw.setdefault("sampling", SAMPLING)
    return ServingEngine(cfg, params, **kw)


class ByteTokenizer:
    eos_token_id = None

    def encode(self, text):
        return list(text.encode("utf-8"))

    def decode(self, ids, **kw):
        return bytes(int(i) % 256 for i in ids).decode("utf-8", errors="replace")


def _wait(pred, timeout=10.0, interval=0.01, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


def _get(port, path, headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    try:
        conn.request("GET", path, headers=headers or {})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read() or b"{}")
    finally:
        conn.close()


def _post(port, path, body, headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request("POST", path, json.dumps(body),
                     {"Content-Type": "application/json", **(headers or {})})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read() or b"{}"), dict(
            resp.getheaders()
        )
    finally:
        conn.close()


# ---------------------------------------------------------------- pure logic


def test_token_bucket_charge_refill_and_wait():
    b = TokenBucket(rate=10.0, burst=20.0)
    assert b.take(20.0, now=0.0) == 0.0           # full burst admits
    wait = b.take(5.0, now=0.0)                    # empty: must wait
    assert wait == pytest.approx(0.5)              # 5 tokens at 10/s
    assert b.take(5.0, now=1.0) == 0.0             # refilled 10 in 1 s
    # scale multiplies capacity (the router's fleet-level bucket)
    fleet = TokenBucket(rate=10.0, burst=20.0)
    assert fleet.take(40.0, now=0.0, scale=2.0) == 0.0
    # inf burst (the inert default) never waits
    assert TokenBucket(rate=float("inf"), burst=float("inf")).take(
        1e12, now=0.0
    ) == 0.0


def test_qos_policy_defaults_are_inert_and_config_file_parses():
    # the policy-less default: no floors, unbounded buckets — an engine
    # without a qos config must behave exactly as before this plane existed
    p = QosPolicy.from_config(None)
    assert p.names() == ("gold", "standard", "batch")
    assert p.default_class == "standard"
    for cls in p.classes.values():
        assert cls.slot_floor == 0 and cls.page_floor_frac == 0.0
        assert cls.rate == float("inf")
    # unknown / missing class names degrade to default service, never a 400
    assert p.normalize("GOLD") == "gold"
    assert p.normalize("bogus") == "standard"
    assert p.normalize(None) == "standard"
    assert p.rank("gold") == 0 and p.rank("batch") == 2
    # the committed config carries real floors and quotas
    doc = json.loads((REPO / "configs" / "slo_default.json").read_text())
    q = QosPolicy.from_config(doc["qos"])
    assert q.classes["gold"].slot_floor == 1
    assert q.classes["gold"].page_floor_frac == 0.25
    assert q.classes["batch"].brownout_max_new_tokens == 16
    assert q.classes["gold"].retry_after_s < q.classes["batch"].retry_after_s


def test_qos_policy_rejects_bad_config():
    with pytest.raises(ValueError, match="unknown keys"):
        QosPolicy.from_config({"classes": {"gold": {"oops": 1}}})
    with pytest.raises(ValueError, match="weight"):
        QosPolicy.from_config({"classes": {"gold": {"weight": 0}}})
    with pytest.raises(ValueError, match="default_class"):
        QosPolicy.from_config({"default_class": "bogus"})


def test_class_queue_dwrr_fairness_and_floors():
    policy = QosPolicy.from_config(None)  # weights 8 : 4 : 1

    class Item:
        def __init__(self, qos, cost=10):
            self.qos, self.cost = qos, cost

    q = ClassQueue(policy, cost=lambda h: h.cost, class_of=lambda h: h.qos)
    for _ in range(40):
        q.append(Item("gold"))
        q.append(Item("standard"))
        q.append(Item("batch"))
    assert len(q) == 120
    assert q.counts() == {"gold": 40, "standard": 40, "batch": 40}
    served = [q.popleft().qos for _ in range(26)]
    # weighted-fair service: proportions track 8:4:1, and the heaviest
    # class cannot be starved out of its share by the others' backlog
    assert 14 <= served.count("gold") <= 18, served
    assert 6 <= served.count("standard") <= 10, served
    assert 1 <= served.count("batch") <= 4, served
    # floor gating: an ineligible class is skipped WITHOUT burning its
    # deficit — the next eligible pop still follows the weights
    nxt = q.popleft(eligible=lambda c: c != "gold")
    assert nxt.qos in ("standard", "batch")
    assert q.popleft(eligible=lambda c: False) is None
    # queue-full shed victim: lowest class, never at-or-above the bar
    victim = q.pop_lowest_class(above_rank=policy.rank("standard"))
    assert victim.qos == "batch"
    assert q.pop_lowest_class(above_rank=policy.rank("batch")) is None
    assert q.best_waiting_rank() == 0
    # appendleft is a refund: the item comes back out first for its class
    head = Item("gold", cost=1)
    q.appendleft(head)
    assert q.popleft(eligible=lambda c: c == "gold") is head


def test_reserved_above_arithmetic():
    policy = QosPolicy.from_config(
        {"classes": {"gold": {"slot_floor": 2}, "standard": {"slot_floor": 1}}}
    )
    floors = {n: c.slot_floor for n, c in policy.classes.items()}
    # batch sees both unmet floors; gold sees none (nothing outranks it)
    assert reserved_above(policy, "batch", floors, {}) == 3
    assert reserved_above(policy, "gold", floors, {}) == 0
    # a higher class already running inside its floor releases that much
    assert reserved_above(policy, "batch", floors, {"gold": 1}) == 2
    assert reserved_above(policy, "batch", floors, {"gold": 5}) == 1


def test_brownout_controller_hysteresis_and_force():
    bo = BrownoutController(calm_evals=3)
    assert bo.rung == "normal"
    assert bo.observe(True) == ("normal", "no_spec")
    assert bo.observe(True) == ("no_spec", "shrink_batch")
    assert bo.observe(True) == ("shrink_batch", "suspend_batch")
    assert bo.observe(True) is None  # already at the top
    # one calm blip mid-overload changes nothing; calm_evals consecutive
    # calm evaluations step down ONE rung (and reset the streak)
    assert bo.observe(False) is None
    assert bo.observe(True) is None  # hot again: streak resets
    for _ in range(2):
        assert bo.observe(False) is None
    assert bo.observe(False) == ("suspend_batch", "shrink_batch")
    for _ in range(8):
        bo.observe(False)
    assert bo.rung == "normal"  # sustained calm fully reverts
    assert bo.force("suspend_batch") == ("normal", "suspend_batch")
    assert bo.force("suspend_batch") is None  # idempotent
    with pytest.raises(ValueError):
        bo.force("bogus")
    snap = bo.snapshot()
    assert snap["rung"] == "suspend_batch" and snap["rungs"] == list(
        BROWNOUT_RUNGS
    )
    assert rung_at_least("shrink_batch", "no_spec")
    assert not rung_at_least("no_spec", "shrink_batch")
    assert rung_at_least("bogus", "normal")  # unknown compares as normal


def test_tenant_ledger_eviction_callback_and_lru_preference():
    evicted = []
    ledger = TenantLedger(capacity=2, on_evict=evicted.append)
    ledger.record("idle", {"tokens_out": 1})
    ledger.record("active", {"tokens_out": 1})
    ledger.record("active", {"tokens_out": 1})  # touch: active moves to MRU
    ledger.record("new", {"tokens_out": 1})     # capacity: IDLE is evicted
    assert evicted == ["idle"]
    assert ledger.evictions == 1
    assert set(ledger.snapshot()) == {"active", "new"}


# ------------------------------------------------------------- engine plane


def test_engine_tenant_quota_is_per_tenant(cfg, params):
    """A flooding tenant exhausts ITS OWN bucket: the rejection is
    retryable with a class-aware Retry-After, and another tenant's bucket
    is untouched."""
    engine = make_engine(
        cfg, params, qos={"classes": {"standard": {"rate": 1.0, "burst": 10.0}}}
    )
    ok = engine.submit([1, 2, 3], max_new_tokens=5, seed=0, tenant="flood")
    broke = engine.submit([1, 2, 3], max_new_tokens=5, seed=0, tenant="flood")
    other = engine.submit([1, 2, 3], max_new_tokens=5, seed=1, tenant="calm")
    assert ok.status == "queued" and other.status == "queued"
    assert broke.status == "rejected" and broke.retryable
    assert "quota" in broke.error
    assert broke.retry_after >= 1.0  # at least the class retry hint
    assert engine.stats["rejected_quota"] == 1
    engine.run_until_idle()
    assert ok.status == "done" and other.status == "done"


def test_engine_queue_full_sheds_lower_class(cfg, params):
    """At queue capacity a HIGHER-class arrival evicts the lowest-class
    waiter (retryably) instead of being turned away; an equal-class
    arrival still gets the classic queue-full rejection."""
    engine = make_engine(cfg, params, n_slots=1, max_queue=2,
                         qos={"classes": {}})
    waiters = [
        engine.submit([1, 2 + i], max_new_tokens=4, seed=i, qos="batch")
        for i in range(3)
    ]
    assert waiters[2].status == "rejected"  # queue full among equals
    assert engine.stats["rejected_queue_full"] == 1
    gold = engine.submit([1, 9], max_new_tokens=4, seed=9, qos="gold")
    assert gold.status == "queued"
    shed = [w for w in waiters[:2] if w.status == "rejected"]
    assert len(shed) == 1 and shed[0].retryable
    assert "shed" in shed[0].error
    assert engine.stats["shed_lower_class"] == 1
    engine.run_until_idle()
    assert gold.status == "done"


def test_engine_preempts_running_batch_for_waiting_gold(cfg, params):
    """With every slot busy on lower-class work, a waiting gold request
    preempts one victim (retryable terminal) instead of queueing behind
    it; gold never waits on batch."""
    engine = make_engine(cfg, params, n_slots=1, qos={"classes": {}})
    batch = engine.submit([2, 3], max_new_tokens=24, seed=0, qos="batch")
    for _ in range(3):
        engine.step()
    assert batch.status == "running"
    gold = engine.submit([2, 4], max_new_tokens=4, seed=1, qos="gold")
    engine.run_until_idle()
    assert gold.status == "done"
    assert batch.status == "failed" and batch.retryable
    assert "preempted" in batch.error
    assert engine.stats["preempted_for_class"] == 1
    # gold-for-gold never preempts: same-class contention just queues
    g1 = engine.submit([2, 5], max_new_tokens=24, seed=2, qos="gold")
    for _ in range(3):
        engine.step()
    g2 = engine.submit([2, 6], max_new_tokens=4, seed=3, qos="gold")
    engine.run_until_idle()
    assert g1.status == "done" and g2.status == "done"
    assert engine.stats["preempted_for_class"] == 1  # unchanged


def test_engine_slot_floor_reserves_capacity_for_gold(cfg, params):
    """A gold slot floor keeps batch from ever filling the last slot:
    batch runs one-at-a-time through 2 slots, and a gold arrival admits
    immediately into the reserved slot."""
    engine = make_engine(
        cfg, params, n_slots=2,
        qos={"classes": {"gold": {"slot_floor": 1}}},
    )
    waiters = [
        engine.submit([3, 5 + i], max_new_tokens=12, seed=i, qos="batch")
        for i in range(3)
    ]
    peak_batch = 0
    for _ in range(6):
        engine.step()
        active = [
            a.handle.request.qos
            for a in engine._active
            if a is not None
        ]
        peak_batch = max(peak_batch, active.count("batch"))
    assert peak_batch == 1  # the floor held a slot open throughout
    gold = engine.submit([3, 9], max_new_tokens=4, seed=9, qos="gold")
    engine.step()
    assert gold.status == "running"  # straight into the reserved slot
    engine.run_until_idle()
    assert gold.status == "done"
    assert all(w.status == "done" for w in waiters)


def test_engine_brownout_rungs_and_full_revert(cfg, params):
    """Every rung changes admission the way it advertises, transitions
    are counted + flight-recorded, and ``normal`` restores the exact
    pre-brownout behavior."""
    engine = make_engine(cfg, params, qos={"classes": {}})
    assert engine.brownout_rung == "normal" and engine._spec_enabled
    info = engine.set_brownout("no_spec")
    assert info == {"rung": "no_spec", "previous": "normal"}
    assert not engine._spec_enabled
    engine.set_brownout("shrink_batch")
    clamped = engine.submit([1, 2], max_new_tokens=24, seed=0, qos="batch")
    assert clamped.request.max_new_tokens == 16  # the class's brownout cap
    gold_uncapped = engine.submit([1, 3], max_new_tokens=24, seed=0,
                                  qos="gold")
    assert gold_uncapped.request.max_new_tokens == 24
    engine.set_brownout("suspend_batch")
    suspended = engine.submit([1, 4], max_new_tokens=4, seed=0, qos="batch")
    assert suspended.status == "rejected" and suspended.retryable
    assert "brownout" in suspended.error
    assert engine.stats["rejected_brownout"] == 1
    still_gold = engine.submit([1, 5], max_new_tokens=4, seed=0, qos="gold")
    assert still_gold.status == "queued"
    # full revert: batch admits again, spec re-enables, no clamp
    engine.set_brownout("normal")
    assert engine._spec_enabled
    back = engine.submit([1, 6], max_new_tokens=24, seed=0, qos="batch")
    assert back.status == "queued"
    assert back.request.max_new_tokens == 24
    assert engine.stats["brownout_transitions"] == 4
    assert engine.set_brownout("normal") == {"rung": "normal",
                                             "previous": "normal"}
    assert engine.stats["brownout_transitions"] == 4  # idempotent no-op
    with pytest.raises(ValueError):
        engine.set_brownout("bogus")
    engine.run_until_idle()
    snap = engine.metrics_snapshot()
    assert snap["brownout_rung"] == "normal"


def test_engine_per_class_histograms_and_new_exports(cfg, params):
    engine = make_engine(cfg, params, qos={"classes": {}})
    for i, q in enumerate(("gold", "batch", None)):
        engine.submit([3 + i, 7], max_new_tokens=4, seed=i, qos=q)
    engine.run_until_idle()
    text = engine.prometheus_text()
    for family in (
        "serve_ttft_seconds_gold", "serve_ttft_seconds_standard",
        "serve_ttft_seconds_batch", "serve_itl_seconds_gold",
        "serve_brownout_rung", "serve_rejected_quota",
        "serve_shed_lower_class", "serve_preempted_for_class",
        "serve_stalled_streams",
    ):
        assert family in text, family
    # the classless request landed in the default class's stream
    assert 'serve_ttft_seconds_standard_count 1' in text
    snap = engine.metrics_snapshot()
    for key in ("rejected_quota", "rejected_brownout", "shed_lower_class",
                "preempted_for_class", "brownout_transitions",
                "stalled_streams"):
        assert snap[key] == 0
    assert snap["queue_by_class"] == {"gold": 0, "standard": 0, "batch": 0}


def test_shed_ewma_stays_cold_across_breaker_rebuild(cfg, params):
    """Cold-start pin (satellite): the deadline shedder must be inert on
    an uninitialized ITL estimate — at engine start AND after a breaker
    rebuild, which must preserve (not reset) the warm estimate."""
    engine = make_engine(cfg, params, n_slots=1, shed_warmup=4)
    # fresh engine: no ITL evidence, nothing sheds however tight the ask
    tight = engine.submit([1], max_new_tokens=20, seed=0, deadline=0.001)
    assert tight.status == "queued"
    assert engine.stats["shed_infeasible"] == 0
    engine.run_until_idle()
    # warm the estimate, then force the breaker's device-state rebuild:
    # the EWMA is HOST state and must survive (a rebuild that zeroed it
    # would re-open the cold-start window after every trip)
    for _ in range(8):
        engine._itl_ewma.update(0.1)
    assert engine._itl_ewma.warm
    before = engine._itl_ewma.value
    engine._rebuild_device_state()
    assert engine._itl_ewma.warm and engine._itl_ewma.value == before
    doomed = engine.submit([1, 2], max_new_tokens=20, seed=0, deadline=0.5)
    assert doomed.status == "rejected" and "shed" in doomed.error


@pytest.mark.chaos
def test_slow_client_chaos_bounds_emit_buffer(cfg, params, reference):
    """Chaos ``slow_client``: an SSE consumer stalls mid-stream. The
    stalled stream's emit buffer hits its bound and the stream finishes
    RETRYABLY (slot released, done event delivered); a concurrent healthy
    stream is byte-identical to the undisturbed run."""
    chaos = ServingChaosMonkey([
        ServeFault("slow_client", step=2, duration=2.0),
    ])
    engine = make_engine(cfg, params, n_slots=2, chaos=chaos,
                         emit_buffer_max=3)
    server = ServingServer(engine, ByteTokenizer(), port=0)
    server.start()
    results = {}

    def client(i):
        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=60)
        try:
            conn.request(
                "POST", "/generate",
                json.dumps({"tokens": [3 + i, 7, 11], "max_new_tokens": 24,
                            "seed": i, "stream": True}),
                {"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            toks, done = [], None
            while True:
                line = resp.readline()
                if not line:
                    break
                if not line.startswith(b"data: "):
                    continue
                event = json.loads(line[6:])
                if event.get("done"):
                    done = event
                    break
                if "token" in event:
                    toks.append(event["token"])
            results[i] = (toks, done)
        finally:
            conn.close()

    try:
        # client 0 arrives first — the chaos fault stalls ITS pump after
        # 2 delivered events; client 1 streams unperturbed alongside
        t0 = threading.Thread(target=client, args=(0,))
        t0.start()
        # ... and the second only once the one-shot fault has fired on the
        # first: admitted in the same tick, the two pumps race for it, and
        # on a loaded machine the wrong stream stalled one run in three
        _wait(lambda: chaos.fired_log, msg="the stall on client 0")
        t1 = threading.Thread(target=client, args=(1,))
        t1.start()
        t0.join(60)
        t1.join(60)
        stalled_toks, stalled_done = results[0]
        assert stalled_done is not None, "stalled stream must still terminate"
        assert stalled_done["status"] == "failed"
        assert stalled_done["retryable"] is True
        assert "stalled" in stalled_done["error"]
        assert engine.stats["stalled_streams"] == 1
        assert chaos.fired_log  # the fault actually fired
        # neighbor isolation: byte-identical to the undisturbed trajectory
        healthy_toks, healthy_done = results[1]
        assert healthy_done["status"] == "done"
        assert healthy_toks == reference([4, 7, 11], 1, max_new=24)
    finally:
        server.stop()


# ------------------------------------------------------------- router plane


def _make_replica(cfg, params, **engine_kw):
    engine_kw.setdefault("n_slots", 2)
    engine_kw.setdefault("cache_len", CACHE_LEN)
    engine_kw.setdefault("sampling", SamplingConfig(greedy=True))
    engine = ServingEngine(cfg, params, **engine_kw)
    server = ServingServer(engine, ByteTokenizer(), port=0)
    server.start()
    return server


def test_router_dict_slo_config_binds_per_class_objectives(cfg, params):
    """The config-file dict shape wires all three planes at once: the
    objective list (including per-class ones bound to class-suffixed
    histogram families), the QoS policy, and the brownout controller."""
    doc = json.loads((REPO / "configs" / "slo_default.json").read_text())
    t = [0.0]
    router = RouterServer(["127.0.0.1:9"], clock=lambda: t[0], slo=doc)
    router._httpd.server_close()  # never started; just release the socket
    assert router.qos.classes["gold"].slot_floor == 1
    assert router.brownout.calm_evals == 3
    assert router._brownout_protected == ("gold", "standard")
    assert set(router.slo._objectives) >= {"ttft_p99_gold", "itl_p99_gold"}
    # feed the aggregator a real engine's exposition carrying gold-only
    # traffic: the gold objective sees samples from the class-suffixed
    # family while the classless family feeds the fleet-wide objective
    engine = make_engine(cfg, params, qos={"classes": {}})
    engine.submit([3, 7], max_new_tokens=4, seed=0, qos="gold")
    engine.run_until_idle()
    router.aggregator.update("r1", "decode", engine.prometheus_text())
    t[0] += 1.0
    snap = router.evaluate_slo()
    gold = snap["objectives"]["ttft_p99_gold"]
    assert gold["qos_class"] == "gold"
    assert gold["total"] > 0  # the class-suffixed family reached the SLO
    # a plain objective list still works and leaves the inert policy
    plain = RouterServer(["127.0.0.1:9"], slo=doc["objectives"])
    plain._httpd.server_close()
    assert plain.qos.classes["gold"].slot_floor == 0


def test_router_brownout_propagates_and_reverts(cfg, params):
    """Hot per-class evaluations walk the fleet up the rung ladder and
    PUSH each rung to every replica; sustained calm walks it all the way
    back. Rungs are visible on /healthz at both tiers, every transition
    is a flight event, and the final rung rejects batch at the router."""
    replica = _make_replica(cfg, params)
    doc = json.loads((REPO / "configs" / "slo_default.json").read_text())
    router = RouterServer(
        [f"http://127.0.0.1:{replica.port}"], probe_interval=0.05, slo=doc,
        # obs loop off: the ladder is driven BY HAND below, and a live
        # loop's calm real evaluations would walk it back mid-assertion
        metrics_scrape_interval=0.0,
    )
    router.start()
    try:
        _wait(lambda: len(router.registry.routable()) == 1, timeout=15,
              msg="replica routable")
        hot = {"objectives": {"ttft_p99_gold": {
            "qos_class": "gold", "state": "fast_burn"}}}
        calm = {"objectives": {"ttft_p99_gold": {
            "qos_class": "gold", "state": "ok"}}}
        for _ in range(3):
            router.brownout_tick(hot)
        assert router.brownout.rung == "suspend_batch"
        _wait(
            lambda: replica.engine.brownout_rung == "suspend_batch",
            msg="rung pushed to replica",
        )
        code, health = _get(router.port, "/healthz")
        assert health["brownout_rung"] == "suspend_batch"
        # the final rung suspends batch AT THE FRONT DOOR, gold still flows
        code, body, headers = _post(
            router.port, "/generate",
            {"tokens": [3, 7], "max_new_tokens": 4, "seed": 0,
             "stream": False},
            headers={"X-QoS-Class": "batch"},
        )
        assert code == 503 and "brownout" in body["error"]
        assert int(headers.get("Retry-After", 0)) >= 1
        code, body, _ = _post(
            router.port, "/generate",
            {"tokens": [3, 7], "max_new_tokens": 4, "seed": 0,
             "stream": False},
            headers={"X-QoS-Class": "gold"},
        )
        assert code == 200 and body["status"] == "done"
        assert router.stats["rejected_brownout"] == 1
        # sustained calm fully reverts, and the revert propagates too
        for _ in range(12):
            router.brownout_tick(calm)
        assert router.brownout.rung == "normal"
        _wait(lambda: replica.engine.brownout_rung == "normal",
              msg="revert pushed to replica")
        code, body, _ = _post(
            router.port, "/generate",
            {"tokens": [3, 7], "max_new_tokens": 4, "seed": 0,
             "stream": False},
            headers={"X-QoS-Class": "batch"},
        )
        assert code == 200 and body["status"] == "done"
        assert router.stats["brownout_transitions"] == 6
        event_names = [e[1] for e in router.flight.events()]
        assert "fleet_brownout" in event_names
        # operator override via the router admin surface
        code, snap, _ = _post(router.port, "/admin/brownout",
                              {"rung": "no_spec"})
        assert code == 200 and snap["rung"] == "no_spec"
        _wait(lambda: replica.engine.brownout_rung == "no_spec",
              msg="forced rung pushed")
        code, _, _ = _post(router.port, "/admin/brownout", {"rung": "bogus"})
        assert code == 400
    finally:
        router.stop()
        replica.stop()


def test_router_fleet_tenant_quota_and_affinity(cfg, params):
    """The router's fleet-level bucket rejects a flooding tenant with 429
    + Retry-After before any replica sees the request, and a tenant's
    requests stick to one replica (tenant affinity)."""
    replica = _make_replica(cfg, params)
    doc = {
        "qos": {"classes": {"standard": {"rate": 1.0, "burst": 8.0}}},
        "objectives": json.loads(
            (REPO / "configs" / "slo_default.json").read_text()
        )["objectives"],
    }
    router = RouterServer(
        [f"http://127.0.0.1:{replica.port}"], probe_interval=0.05, slo=doc,
    )
    router.start()
    try:
        _wait(lambda: len(router.registry.routable()) == 1, timeout=15,
              msg="replica routable")
        body = {"tokens": [3, 7], "max_new_tokens": 4, "seed": 0,
                "stream": False}
        code, doc1, _ = _post(router.port, "/generate", body,
                              headers={"X-Tenant-Key": "flood"})
        assert code == 200, doc1
        code, doc2, headers = _post(router.port, "/generate", body,
                                    headers={"X-Tenant-Key": "flood"})
        assert code == 429 and "quota" in doc2["error"]
        assert int(headers.get("Retry-After", 0)) >= 1
        # another tenant's bucket is untouched
        code, doc3, _ = _post(router.port, "/generate", body,
                              headers={"X-Tenant-Key": "calm"})
        assert code == 200, doc3
        assert router.stats["rejected_quota"] == 1
        assert router.stats["tenant_affinity_hits"] >= 0
        assert router._tenant_affinity_lookup("calm") == replica_id(router)
        snap = router.metrics_snapshot()
        assert snap["brownout_rung"] == "normal"
        assert "gold" in snap["qos_classes"]
    finally:
        router.stop()
        replica.stop()


def replica_id(router):
    return next(iter(router.registry.replicas))


# ----------------------------------------------------- multi-tenant flood


@pytest.mark.slow
@pytest.mark.chaos
def test_tenant_flood_isolation_two_replica_fleet(cfg, params):
    """The acceptance-bar scenario: one tenant floods a 2-replica fleet
    with batch work while a gold tenant runs a steady trickle. The gold
    tenant's requests ALL complete, ``dropped_streams`` stays 0, every
    shed/suspended flood request ends retryably with a Retry-After, and
    the flood's damage is visible in the isolation counters."""
    qos = {
        "classes": {
            "gold": {"slot_floor": 1, "page_floor_frac": 0.25},
            "batch": {"rate": 20.0, "burst": 40.0},
        }
    }
    replicas = [_make_replica(cfg, params, qos=qos) for _ in range(2)]
    doc = json.loads((REPO / "configs" / "slo_default.json").read_text())
    doc["qos"]["classes"]["batch"].update(rate=20.0, burst=40.0)
    router = RouterServer(
        [f"http://127.0.0.1:{s.port}" for s in replicas],
        probe_interval=0.05, max_attempts=2, slo=doc,
    )
    router.start()
    try:
        _wait(lambda: len(router.registry.routable()) == 2, timeout=20,
              msg="fleet ready")
        stop = threading.Event()
        flood_codes = []
        flood_lock = threading.Lock()

        def flood():
            while not stop.is_set():
                try:
                    code, body, headers = _post(
                        router.port, "/generate",
                        {"tokens": [9, 9, 9], "max_new_tokens": 16,
                         "seed": 0, "stream": False},
                        headers={"X-Tenant-Key": "flooder",
                                 "X-QoS-Class": "batch"},
                    )
                    with flood_lock:
                        flood_codes.append((code, body, headers))
                except OSError:
                    pass

        threads = [threading.Thread(target=flood, daemon=True)
                   for _ in range(4)]
        for t in threads:
            t.start()
        gold_results = []
        for i in range(8):
            code, body, _ = _post(
                router.port, "/generate",
                {"tokens": [3, 5, 7 + i], "max_new_tokens": 8, "seed": i,
                 "stream": False},
                headers={"X-Tenant-Key": "vip", "X-QoS-Class": "gold"},
            )
            gold_results.append((code, body))
        stop.set()
        for t in threads:
            t.join(30)
        # EVERY gold request completed despite the flood
        assert all(
            code == 200 and body.get("status") == "done"
            for code, body in gold_results
        ), [c for c, _ in gold_results]
        # the flood was actually throttled — and every rejection honest:
        # retryable semantics with a Retry-After the client can obey
        rejected = [(c, b, h) for c, b, h in flood_codes if c != 200]
        assert rejected, "flood never hit a limit — not a flood"
        for code, body, headers in rejected:
            assert code in (429, 503), (code, body)
            assert int(headers.get("Retry-After", 0)) >= 1
        assert router.stats["dropped_streams"] == 0
        # isolation machinery engaged somewhere in the stack
        engine_stats = [s.engine.stats for s in replicas]
        engaged = (
            router.stats["rejected_quota"]
            + sum(st["rejected_quota"] for st in engine_stats)
            + sum(st["shed_lower_class"] for st in engine_stats)
            + sum(st["preempted_for_class"] for st in engine_stats)
            + sum(st["rejected_queue_full"] for st in engine_stats)
        )
        assert engaged > 0
        # the gold tenant's class-suffixed histograms carried its samples
        text = "".join(s.engine.prometheus_text() for s in replicas)
        assert "serve_ttft_seconds_gold_count" in text
    finally:
        router.stop()
        for s in replicas:
            s.stop()

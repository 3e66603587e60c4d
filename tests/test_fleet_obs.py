"""Fleet observability plane (ISSUE 15): cross-process trace stitching,
fleet metrics aggregation, the SLO engine, and the per-request cost ledger.

The load-bearing claims:

- a DISAGGREGATED request (prefill replica -> page ship -> decode replica
  -> attach) and a LIVE MIGRATION each produce ONE merged Perfetto trace
  with per-process tracks — >= 95% of the client-observed wall latency
  covered, zero orphan spans, hop ordering consistent after clock-offset
  correction;
- the router's fleet_* rollups are pin-equal to the per-replica scrapes
  they fold (counters summed, histograms bucket-merged, MAX_GAUGES maxed);
- an induced fast burn fires the existing machinery within one evaluation:
  a FlightRecorder dump carrying the fleet snapshot and an autoscaler
  up-signal — with dropped_streams == 0 throughout;
- every terminated stream carries a complete cost ledger whose counters
  cross-check against the engine's stats;
- the satellites: span-ring overflow warns once and exports
  ``obs_spans_dropped``; FlightRecorder rotates its dump directory.
"""
import http.client
import json
import logging
import threading
import time
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp

from zero_transformer_tpu import obs
from zero_transformer_tpu.config import model_config
from zero_transformer_tpu.inference.generate import decode_model, generate
from zero_transformer_tpu.inference.sampling import SamplingConfig
from zero_transformer_tpu.models import Transformer
from zero_transformer_tpu.obs.fleet import (
    ENGINE_LEDGER_KEYS,
    FLEET_OBS_REQUIRED_KEYS,
    LEDGER_KEYS,
    FleetAggregator,
    estimate_clock_offset,
    parse_exposition,
)
from zero_transformer_tpu.obs.slo import Objective, parse_slo_config
from zero_transformer_tpu.serving import (
    RouterServer,
    ServingEngine,
    ServingServer,
)

CACHE_LEN = 48
SAMPLING = SamplingConfig(temperature=0.9, top_k=20)


@pytest.fixture(scope="module")
def cfg():
    return model_config("test", dropout=0.0, compute_dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]


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
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("page_size", 4)
    return ServingEngine(cfg, params, **kw)


class _Tok:
    eos_token_id = None

    def encode(self, text):
        return [1 + (b % 250) for b in text.encode()]

    def decode(self, ids, **kw):
        return "".join(f"<{t}>" for t in ids)

    def convert_ids_to_tokens(self, ids):
        return [f"<{t}>" for t in ids]

    def convert_tokens_to_string(self, toks):
        return "".join(toks)


def _server(cfg, params, role, **kw):
    engine = make_engine(cfg, params, role=role, **kw)
    server = ServingServer(engine, _Tok(), port=0)
    server.start()
    return engine, server


def _sse(port, path, body, timeout=240.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path, json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if "text/event-stream" not in (resp.getheader("Content-Type") or ""):
            return resp.status, [], json.loads(resp.read() or b"{}")
        ids, done = [], None
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
                ids.append(int(event["token"]))
        return resp.status, ids, done
    finally:
        conn.close()


def _wait(pred, timeout=120.0, interval=0.02, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


def _prompt(length, offset=0):
    return [(3 + offset + i) % 250 + 1 for i in range(length)]


def _assert_stitched(router, rid, want_processes):
    """The acceptance bar, executable: ONE merged doc, >=95% coverage,
    zero orphans, hop ordering consistent after clock correction, and the
    expected process tracks present."""
    doc = router.merged_trace(rid)
    check = doc["otherData"]["stitch"]
    assert check["coverage"] >= 0.95, check
    assert check["orphans"] == 0, check
    assert check["hops_ordered"], check
    procs = {
        e["args"]["name"]
        for e in doc["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    for want in want_processes:
        assert any(want in p for p in procs), (want, procs)
    # the request's spans really span processes (per-process pids)
    pids = {
        e["pid"] for e in doc["traceEvents"]
        if e.get("ph") == "X" and e.get("cat") == rid
    }
    assert len(pids) >= len(want_processes), (pids, procs)
    return doc, check


# ------------------------------------------------- stitching: disagg + migrate


def test_disagg_request_produces_one_merged_trace(cfg, params, reference):
    """Prefill replica -> page ship -> decode replica -> attach: ONE merged
    Perfetto trace with router/prefill/decode tracks (satellite + tentpole
    acceptance: the trace nobody could read before)."""
    ed, sd = _server(cfg, params, "decode")
    ep, sp = _server(cfg, params, "prefill")
    router = RouterServer(
        [f"127.0.0.1:{sp.port}", f"127.0.0.1:{sd.port}"],
        probe_interval=0.05, chunk_tokens=8, stream_timeout=240.0,
        metrics_scrape_interval=0.0,
    )
    try:
        router.start()
        assert router.wait_ready(30)
        _wait(
            lambda: any(
                r.role == "prefill" for r in router.registry.routable()
            ),
            msg="role scrape",
        )
        prompt = _prompt(13)
        status, ids, done = _sse(
            router.port, "/generate",
            {"tokens": prompt, "max_new_tokens": 8, "seed": 3,
             "request_id": "disagg-trace-1"},
        )
        assert done and done.get("status") == "done", done
        assert ids == reference(prompt, seed=3, max_new=8)
        assert router.stats["disagg_dispatches"] == 1
        doc, check = _assert_stitched(
            router, "disagg-trace-1", ("router", "prefill", "decode")
        )
        # the phase split is readable: the prefill replica's tree has a
        # prefill span, the decode replica's tree decodes, hop attrs order
        # prefill (0) before attach (1)
        names = {
            (e["args"].get("hop") if e.get("args") else None, e["name"])
            for e in doc["traceEvents"]
            if e.get("ph") == "X" and e.get("cat") == "disagg-trace-1"
        }
        hops = {h for h, _ in names if h is not None}
        assert {0, 1} <= hops, names
        assert router.stats["dropped_streams"] == 0
        # the complete ledger: engine counters + fleet fields, migrations
        # == 1 (the page ship), 2 replicas crossed, zero replayed tokens
        ledger = done["ledger"]
        assert set(LEDGER_KEYS) <= set(ledger)
        assert ledger["migrations"] == 1
        assert ledger["replicas_crossed"] == 2
        assert ledger["attach_hops"] == 1
        assert ledger["resume_replayed_tokens"] == 0
        assert ledger["tokens_out"] == len(ids)
        assert ledger["prefill_chunks"] >= 1  # paid on the prefill replica
    finally:
        router.stop()
        sd.stop()
        sp.stop()


def test_migrated_stream_produces_one_merged_trace(cfg, params, reference):
    """/admin/migrate mid-stream: the merged trace covers both replicas'
    span trees plus the router's relay/attach hops — no inter-hop gap
    unaccounted past the 5% bar, zero orphans."""
    e1, s1 = _server(cfg, params, "mixed")
    e2, s2 = _server(cfg, params, "mixed")
    router = RouterServer(
        [f"127.0.0.1:{s1.port}", f"127.0.0.1:{s2.port}"],
        probe_interval=0.05, chunk_tokens=8, stream_timeout=240.0,
        metrics_scrape_interval=0.0,
    )
    try:
        router.start()
        assert router.wait_ready(30)
        prompt = _prompt(13)
        expect = reference(prompt, seed=7, max_new=24)
        got = {}

        def client():
            got["r"] = _sse(
                router.port, "/generate",
                {"tokens": prompt, "max_new_tokens": 24, "seed": 7,
                 "request_id": "mig-trace-1"},
            )

        t = threading.Thread(target=client, daemon=True)
        t.start()
        src = {}

        def find_src():
            for e, s, other in ((e1, s1, s2), (e2, s2, s1)):
                for act in e._active:
                    if (
                        act is not None
                        and act.handle.rid == "mig-trace-1"
                        and len(act.handle.tokens) >= 3
                    ):
                        src["server"], src["target"] = s, other
                        return True
            return False

        _wait(find_src, msg="stream decoding on a replica")
        conn = http.client.HTTPConnection(
            "127.0.0.1", src["server"].port, timeout=30
        )
        conn.request(
            "POST", "/admin/migrate",
            json.dumps({"request_id": "mig-trace-1",
                        "target": f"http://127.0.0.1:{src['target'].port}"}),
            {"Content-Type": "application/json"},
        )
        assert conn.getresponse().status == 202
        conn.close()
        t.join(timeout=240)
        assert not t.is_alive(), "migrated stream hung"
        _, ids, done = got["r"]
        assert done and done.get("status") == "done", done
        assert ids == expect
        assert router.stats["migration_resumes"] == 1
        assert router.stats["dropped_streams"] == 0
        _assert_stitched(router, "mig-trace-1", ("router", "mixed"))
        # the cumulative ledger crossed the migration: one page crossing,
        # both replicas, zero replay, every token accounted
        ledger = done["ledger"]
        assert ledger["migrations"] == 1
        assert ledger["replicas_crossed"] == 2
        assert ledger["resume_replayed_tokens"] == 0
        assert ledger["tokens_out"] == len(ids)
        # the per-request /admin/trace endpoint serves the same doc
        conn = http.client.HTTPConnection("127.0.0.1", router.port, timeout=30)
        conn.request("GET", "/admin/trace?request_id=mig-trace-1")
        resp = conn.getresponse()
        assert resp.status == 200
        doc = json.loads(resp.read())
        conn.close()
        assert doc["otherData"]["stitch"]["coverage"] >= 0.95
    finally:
        router.stop()
        s1.stop()
        s2.stop()


# ------------------------------------------------------- metrics aggregation


def test_fleet_rollups_pin_equal_to_per_replica_scrapes(cfg, params):
    """The aggregation semantics, pinned: per-role sums of fleet_* equal
    the per-replica scrapes they fold (counters summed, histogram
    bucket/count merged, MAX_GAUGES maxed)."""
    e1 = make_engine(cfg, params)
    e2 = make_engine(cfg, params)
    for i in range(3):
        e1.submit(_prompt(5, i), max_new_tokens=4, seed=i)
    e1.run_until_idle()
    for i in range(2):
        e2.submit(_prompt(5, 10 + i), max_new_tokens=4, seed=i)
    e2.run_until_idle()
    agg = FleetAggregator()
    agg.update("r1", "mixed", e1.prometheus_text())
    agg.update("r2", "decode", e2.prometheus_text())

    text = agg.render()
    fams = parse_exposition(text)
    # counters: per-role series sum to the engines' own stats
    samples = fams["fleet_serve_completed_total"]["samples"]
    role_sum = sum(v for labels, v in samples if "replica" not in labels)
    assert role_sum == e1.stats["completed"] + e2.stats["completed"] == 5
    per_replica = {
        labels["replica"]: v for labels, v in samples if "replica" in labels
    }
    assert per_replica == {"r1": 3.0, "r2": 2.0}
    # role labels are carried (one series per role)
    roles = {
        labels["role"] for labels, _ in samples if "replica" not in labels
    }
    assert roles == {"mixed", "decode"}
    # histograms: bucket-merged count equals the sum of observations
    hist = agg.merged_histogram("serve_ttft_seconds")
    assert hist["count"] == len(e1._h_ttft) + len(e2._h_ttft) == 5
    assert hist["buckets"][-1][1] == 5  # +Inf cumulative == count
    # MAX_GAUGES: uptime is the max, not the sum
    up = [
        v for labels, v in fams["fleet_serve_uptime_seconds"]["samples"]
        if "replica" not in labels and labels.get("role") == "mixed"
    ]
    assert up and up[0] <= max(
        e1.lifecycle.uptime_s, e2.lifecycle.uptime_s
    ) + 1.0
    # dropping a replica removes its contribution
    agg.drop("r2")
    fams2 = parse_exposition(agg.render())
    total = sum(
        v for labels, v in fams2["fleet_serve_completed_total"]["samples"]
        if "replica" not in labels
    )
    assert total == 3


def test_good_total_below_reads_cumulative_buckets():
    agg = FleetAggregator()
    text = (
        "# TYPE serve_ttft_seconds histogram\n"
        'serve_ttft_seconds_bucket{le="0.1"} 7\n'
        'serve_ttft_seconds_bucket{le="1"} 9\n'
        'serve_ttft_seconds_bucket{le="+Inf"} 10\n'
        "serve_ttft_seconds_sum 4.2\n"
        "serve_ttft_seconds_count 10\n"
    )
    agg.update("r1", "mixed", text)
    agg.update("r2", "mixed", text)
    assert agg.good_total_below("serve_ttft_seconds", 0.1) == (14.0, 20.0)
    assert agg.good_total_below("serve_ttft_seconds", 1.0) == (18.0, 20.0)
    # a threshold BETWEEN bounds rounds UP to the covering bound (the
    # histogram cannot split a bucket; rounding down would damn good
    # observations inside the straddling bucket)
    assert agg.good_total_below("serve_ttft_seconds", 0.5) == (18.0, 20.0)
    # past the top finite bound: everything in +Inf stays bad
    assert agg.good_total_below("serve_ttft_seconds", 5.0) == (18.0, 20.0)
    assert agg.good_total_below("serve_nonexistent", 0.1) is None


def test_clock_offset_estimation_prefers_tight_round_trips():
    # remote clock 100s ahead, measured through a 10ms round trip
    off, rtt, at = estimate_clock_offset(100.105, t0=0.1, t1=0.11)
    assert off == pytest.approx(100.0)
    assert rtt == pytest.approx(0.01)
    # a looser round trip does NOT displace the tight estimate...
    off2, rtt2, _ = estimate_clock_offset(
        107.0, t0=5.0, t1=6.0, prev=(off, rtt, at), now=6.0
    )
    assert (off2, rtt2) == (off, rtt)
    # ...until the tight one ages out (clock drift wins eventually)
    off3, rtt3, _ = estimate_clock_offset(
        107.5, t0=50.0, t1=51.0, prev=(off, rtt, at), now=51.0,
        max_age_s=30.0,
    )
    assert off3 == pytest.approx(107.5 - 50.5)


# ------------------------------------------------------------------ SLO engine


class _SpyScaler:
    def __init__(self):
        self.spawned = 0

    def spawn(self):
        self.spawned += 1
        return f"127.0.0.1:{9000 + self.spawned}"

    def retire(self, url):
        pass


def _ttft_text(good, bad):
    total = good + bad
    return (
        "# TYPE serve_ttft_seconds histogram\n"
        f'serve_ttft_seconds_bucket{{le="0.1"}} {good}\n'
        f'serve_ttft_seconds_bucket{{le="+Inf"}} {total}\n'
        f"serve_ttft_seconds_sum 1.0\n"
        f"serve_ttft_seconds_count {total}\n"
    )


def test_slo_fast_burn_fires_dump_and_autoscaler_up_signal(tmp_path):
    """Induced fast burn (chaos latency injection shape: TTFT samples past
    the threshold flood the aggregated histogram) -> within ONE evaluation
    the flight recorder dumps the fleet snapshot and the autoscaler gets
    an up-signal. dropped_streams stays 0 throughout."""
    t = [0.0]
    router = RouterServer(
        ["127.0.0.1:9"],
        clock=lambda: t[0],
        obs_dir=str(tmp_path),
        scaler=_SpyScaler(),
        autoscale_interval=0.0,  # loop off; ticks driven by hand
        scale_patience=1,
        max_replicas=4,
        slo=[Objective(
            name="ttft_p99", metric="ttft_p99", target=0.99,
            threshold_s=0.1, short_window_s=5.0, long_window_s=30.0,
            fast_burn=4.0,
        )],
    )
    try:
        router.start(probe=False)  # HTTP only; probes/evals driven by hand
        # a routable replica (hand-fed probe; no threads started)
        router.registry.observe_probe(
            "127.0.0.1:9", ok=True, body={"state": "ready"},
        )
        # healthy traffic: all TTFTs under the threshold
        for _ in range(6):
            t[0] += 1.0
            router.aggregator.update(
                "127.0.0.1:9", "mixed", _ttft_text(good=10 * int(t[0]), bad=0)
            )
            snap = router.evaluate_slo()
        assert snap["verdict"] == "ok"
        assert router.consume_slo_hot() is False
        # chaos latency injection: every new request blows the threshold
        good = 10 * int(t[0])
        for i in range(2):
            t[0] += 1.0
            router.aggregator.update(
                "127.0.0.1:9", "mixed",
                _ttft_text(good=good, bad=10 * (i + 1)),
            )
            snap = router.evaluate_slo()
        assert snap["verdict"] == "violated"
        assert snap["objectives"]["ttft_p99"]["state"] == "fast_burn"
        assert router.stats["slo_fast_burns"] == 1
        # the existing machinery fired: a flight dump with the fleet inside
        dumps = list((tmp_path / "flightrec").glob("*slo_fast_burn*"))
        assert dumps, "fast burn must dump the flight recorder"
        doc = json.loads(dumps[0].read_text())
        assert doc["extra"]["objective"] == "ttft_p99"
        assert "registry" in doc["extra"] and "slo" in doc["extra"]
        # ...and the autoscaler consumes the up-signal on its next tick
        router._autoscale_tick()
        assert router.scaler.spawned == 1
        assert router.consume_slo_hot() is False  # consumed, not sticky
        assert router.stats["dropped_streams"] == 0
        # /metrics carries the slo_* families
        text = router.metrics.render()
        assert 'slo_budget_remaining{objective="ttft_p99"}' in text
        assert "slo_violated 1" in text
    finally:
        router.stop()


def test_slo_zero_kind_and_config_parsing():
    objs = parse_slo_config(json.loads(
        (Path(__file__).resolve().parent.parent / "configs"
         / "slo_default.json").read_text()
    ))
    assert {o.name for o in objs} == {
        "ttft_p99", "itl_p99", "availability", "dropped_streams",
        "ttft_p99_gold", "itl_p99_gold",
    }
    assert next(o for o in objs if o.name == "dropped_streams").kind == "zero"
    # per-class objectives (PR 18) bind to one class's histogram stream
    assert next(o for o in objs if o.name == "ttft_p99_gold").qos_class == "gold"
    assert next(o for o in objs if o.name == "ttft_p99").qos_class is None
    with pytest.raises(ValueError, match="unknown keys"):
        parse_slo_config([{"name": "x", "metric": "ttft_p99", "oops": 1}])
    with pytest.raises(ValueError, match="unknown metric"):
        parse_slo_config([{"name": "x", "metric": "nope"}])


def test_slo_dropped_streams_zero_objective():
    t = [0.0]
    router = RouterServer(
        ["127.0.0.1:9"], clock=lambda: t[0],
        slo=[Objective(
            name="dropped_streams", metric="dropped_streams", kind="zero",
            target=0.999999, short_window_s=5.0, long_window_s=30.0,
            fast_burn=1.0,
        )],
    )
    try:
        router.start(probe=False)  # HTTP only; evaluations driven by hand
        for _ in range(3):
            t[0] += 1.0
            router.stats["streams"] += 5
            snap = router.evaluate_slo()
        assert snap["verdict"] == "ok"
        t[0] += 1.0
        router.stats["dropped_streams"] += 1  # the unforgivable event
        snap = router.evaluate_slo()
        assert snap["verdict"] == "violated"
        assert snap["objectives"]["dropped_streams"]["budget_remaining"] == 0.0
    finally:
        router.stop()


# ------------------------------------------------------------- cost ledger


def test_engine_ledger_cross_checks_against_stats(cfg, params):
    """Ledger counters summed over requests equal the engine's own stats —
    the ledger is an attribution of the stats, not a second opinion."""
    engine = make_engine(cfg, params)
    handles = [
        engine.submit(_prompt(9, i), max_new_tokens=6, seed=i)
        for i in range(3)
    ]
    engine.run_until_idle()
    assert all(h.status == "done" for h in handles)
    led = [h.ledger_snapshot() for h in handles]
    for snap in led:
        assert set(ENGINE_LEDGER_KEYS) <= set(snap)
        assert snap["queue_ms"] >= 0 and snap["decode_ms"] >= 0
        assert snap["pages_held_ticks"] > 0  # paged engine holds pages
        assert snap["migrations"] == 0
    assert sum(s["tokens_out"] for s in led) == engine.stats["tokens_out"]
    assert sum(s["prefill_chunks"] for s in led) == engine.stats["prefill_chunks"]
    # decode ticks: every emitted token cost at least one held tick
    for s in led:
        assert s["decode_ticks"] >= s["tokens_out"] > 0


def test_migration_export_carries_live_wall_time(cfg, params):
    """A mid-decode export ships the SOURCE hop's decode_ms (the handle is
    live, so the snapshot must account wall time to now — regression: it
    shipped decode_ms=0 and the cumulative split lost the source hop)."""
    engine = make_engine(cfg, params)
    shipped = []
    engine.page_shipper = lambda payload, target, on_done: (
        shipped.append(payload), on_done("sink")  # fail it; payload captured
    )
    handle = engine.submit(_prompt(9), max_new_tokens=16, seed=0)
    while len(handle.tokens) < 3:
        engine.step()
    assert engine.request_migration(handle.rid, "http://sink")
    engine.step()
    assert shipped, "export never reached the shipper"
    led = shipped[0]["ledger"]
    assert led["decode_ms"] > 0.0, led  # source decode time carried
    assert led["tokens_out"] >= 3


def test_speculative_ledger_attributes_drafts(cfg, params):
    engine = make_engine(
        cfg, params, draft_k=4, sampling=SamplingConfig(greedy=True),
    )
    handles = [
        engine.submit(_prompt(9, i), max_new_tokens=8, seed=i)
        for i in range(2)
    ]
    engine.run_until_idle()
    assert all(h.status == "done" for h in handles)
    drafted = sum(h.ledger["draft_tokens"] for h in handles)
    accepted = sum(h.ledger["accepted_tokens"] for h in handles)
    assert drafted == engine.stats["draft_tokens"] > 0
    assert accepted == engine.stats["accepted_tokens"]


def test_http_done_event_carries_ledger_and_tenant_rollup(cfg, params):
    """Every terminated stream carries the schema-pinned ledger; the
    router rolls it up under the tenant key."""
    from zero_transformer_tpu.serving import run_server

    engine = make_engine(cfg, params)
    server = run_server(engine, _Tok(), port=0, background=True)
    router = RouterServer(
        [f"127.0.0.1:{server.port}"], probe_interval=0.05,
        chunk_tokens=8, stream_timeout=240.0, metrics_scrape_interval=0.0,
    )
    try:
        router.start()
        assert router.wait_ready(30)
        conn = http.client.HTTPConnection("127.0.0.1", router.port, timeout=240)
        conn.request(
            "POST", "/generate",
            json.dumps({"tokens": _prompt(9), "max_new_tokens": 4,
                        "stream": False}),
            {"Content-Type": "application/json", "X-Tenant-Key": "acme"},
        )
        doc = json.loads(conn.getresponse().read())
        conn.close()
        assert doc["status"] == "done"
        missing = FLEET_OBS_REQUIRED_KEYS["ledger"] - set(doc["ledger"])
        assert not missing, sorted(missing)
        assert doc["ledger"]["tokens_out"] == len(doc["tokens"])
        assert doc["ledger"]["replicas_crossed"] == 1
        # SSE path, tenant via body field
        status, ids, done = _sse(
            router.port, "/generate",
            {"tokens": _prompt(9, 3), "max_new_tokens": 4, "tenant": "acme"},
        )
        assert done["status"] == "done"
        assert set(LEDGER_KEYS) <= set(done["ledger"])
        tenants = router.tenants.snapshot()
        assert "acme" in tenants and tenants["acme"]["requests"] == 2
        assert tenants["acme"]["tokens_out"] == doc["ledger"]["tokens_out"] + len(ids)
        # per-tenant families render on /metrics
        text = router.metrics.render()
        assert 'router_tenant_requests_total{tenant="acme"} 2' in text
    finally:
        router.stop()
        server.stop()


def test_tenant_ledger_is_bounded_lru():
    tl = obs.TenantLedger(capacity=3)
    for i in range(5):
        tl.record(f"t{i}", {"tokens_out": 1})
    snap = tl.snapshot()
    assert len(snap) == 3
    assert "t4" in snap and "t0" not in snap  # least-recent evicted
    assert tl.totals()["tokens_out"] == 3.0
    # true LRU: an ACTIVE tenant survives a key-churn flood (recording
    # refreshes recency; a one-off key is what gets evicted)
    tl = obs.TenantLedger(capacity=3)
    tl.record("prod", {"tokens_out": 10})
    for i in range(10):
        tl.record(f"oneoff{i}", {"tokens_out": 1})
        tl.record("prod", {"tokens_out": 10})
    snap = tl.snapshot()
    assert "prod" in snap
    assert snap["prod"]["tokens_out"] == 110.0  # never evicted/reset


# --------------------------------------------------------------- satellites


def test_tracer_overflow_warns_once_and_counts(caplog):
    tr = obs.Tracer(capacity=4)
    with caplog.at_level(logging.WARNING, logger="zero_transformer_tpu"):
        for i in range(10):
            tr.add("s", "t", float(i), float(i) + 0.5)
    warnings = [r for r in caplog.records if "span ring overflowed" in r.message]
    assert len(warnings) == 1, "overflow must warn exactly once"
    assert tr.dropped == 6


def test_engine_exports_obs_spans_dropped(cfg, params):
    engine = make_engine(cfg, params, trace_capacity=4)
    for i in range(3):
        engine.submit(_prompt(5, i), max_new_tokens=4, seed=i)
    engine.run_until_idle()
    text = engine.prometheus_text()
    assert "obs_spans_dropped" in text
    assert engine.tracer.dropped > 0  # 3 request trees overflow capacity 4
    assert f"obs_spans_dropped {engine.tracer.dropped}" in text


def test_flight_recorder_rotates_dumps_newest_survives(tmp_path):
    fr = obs.FlightRecorder(directory=str(tmp_path), max_dumps=3)
    fr.tick({"tick": 1})
    paths = [fr.dump(f"reason{i}") for i in range(7)]
    assert all(p is not None for p in paths)
    remaining = sorted(Path(p).name for p in paths if Path(p).exists())
    assert len(remaining) == 3
    # the NEWEST dump always survives; the oldest were deleted
    assert Path(paths[-1]).exists()
    assert not Path(paths[0]).exists()
    assert [Path(p).name for p in fr.dumps] == remaining


def test_flight_recorder_default_rotation_bound():
    fr = obs.FlightRecorder(directory=None)
    assert fr.max_dumps == 64

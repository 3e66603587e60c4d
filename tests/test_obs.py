"""Unified observability layer (ISSUE 7): span lifecycle parity, Prometheus
exposition conformance, X-Request-Id round trip, flight-recorder dumps, and
on-demand profiling.

The load-bearing invariants:

- every ADMITTED request's span tree is complete and well-nested — root
  ``request`` span covering contiguous ``queue``/``prefill``/``decode``
  children accounting for >=95% of its measured wall latency — for every
  terminal outcome (done, shed, expired, cancelled, tick-faulted);
- ``/metrics`` text exposition parses under the Prometheus 0.0.4 grammar
  while the engine is actively serving (histogram buckets cumulative,
  ``+Inf`` == count), and the scrape never perturbs in-flight requests;
- a breaker-open fires a flight-recorder dump whose ring contains the
  faulting ticks — the post-mortem exists without verbose logging;
- profile captures ride the admin lifecycle (202 accepted, 409 while
  draining).
"""
import http.client
import json
import re
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from zero_transformer_tpu import obs
from zero_transformer_tpu.config import model_config
from zero_transformer_tpu.inference.sampling import SamplingConfig
from zero_transformer_tpu.models import Transformer
from zero_transformer_tpu.serving import (
    ServeFault,
    ServingChaosMonkey,
    ServingEngine,
    run_server,
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


def make_engine(cfg, params, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("cache_len", CACHE_LEN)
    kw.setdefault("page_size", 8)  # its tests pass chunks of 8
    kw.setdefault("sampling", SAMPLING)
    return ServingEngine(cfg, params, **kw)


class ByteTok:
    eos_token_id = None

    def encode(self, text):
        return [ord(c) % 250 + 1 for c in text] or [1]

    def decode(self, toks, **kw):
        return "".join(chr(97 + (t % 26)) for t in toks)


# ------------------------------------------------------------ metric types


def test_histogram_observe_quantile_monotone():
    h = obs.Histogram("h_seconds", "t", buckets=(0.001, 0.01, 0.1, 1.0))
    assert h.quantile(0.5) == 0.0  # empty
    for v in (0.0005, 0.002, 0.003, 0.05, 0.5, 3.0):
        h.observe(v)
    qs = [h.quantile(q) for q in (0.1, 0.5, 0.9, 0.99, 1.0)]
    assert qs == sorted(qs), qs  # monotone in q
    assert len(h) == 6 and h.count == 6
    assert h.sum == pytest.approx(3.5555)
    # overflow clamps at the top finite bound, never extrapolates
    assert h.quantile(1.0) == 1.0


def test_histogram_rejects_bad_buckets():
    with pytest.raises(ValueError):
        obs.Histogram("x", "t", buckets=())
    with pytest.raises(ValueError):
        obs.Histogram("x", "t", buckets=(0.1, 0.01))


def test_registry_get_or_create_and_type_conflict():
    reg = obs.Registry()
    c1 = reg.counter("reqs", "h")
    assert reg.counter("reqs", "h") is c1  # idempotent wiring
    with pytest.raises(ValueError):
        reg.gauge("reqs", "h")  # one name, two meanings = scrape bug
    with pytest.raises(ValueError):
        c1.inc(-1)  # counters only go up
    # the two func flavors share one class — the type check must still hold
    reg.counter_func("fn_metric", "h", lambda: 1)
    with pytest.raises(ValueError):
        reg.gauge_func("fn_metric", "h", lambda: 2)


def test_exposition_format_counters_gauges_histograms_labels():
    reg = obs.Registry()
    reg.counter("a_reqs", "count").inc(3)
    reg.gauge("b_depth", 'weird "help"\nline').set(2.5)
    reg.histogram("c_seconds", "lat", buckets=(0.1, 1.0)).observe(0.05)
    reg.gauge_func("d_hbm", "per device",
                   lambda: [({"device": "0"}, 1.0), ({"device": "1"}, 2.0)])
    text = reg.render()
    assert 'c_seconds_bucket{le="0.1"} 1' in text
    assert 'c_seconds_bucket{le="+Inf"} 1' in text
    assert "a_reqs_total 3" in text
    assert 'd_hbm{device="1"} 2' in text
    # HELP text escapes the newline so the line-oriented grammar survives
    assert '# HELP b_depth weird "help"\\nline' in text


EXPOSITION_LINE = re.compile(
    r"^(?:"
    r"# (?:HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .+"
    r"|"
    r'[a-zA-Z_:][a-zA-Z0-9_:]*(?:\{(?:[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*",?)+\})?'
    r" (?:NaN|[+-]Inf|[-+]?[0-9]*\.?[0-9]+(?:[eE][-+]?[0-9]+)?)"
    r")$"
)


def _assert_conformant(text: str) -> None:
    """Every line matches the 0.0.4 exposition grammar; every histogram's
    bucket counts are cumulative and ``+Inf`` equals ``_count``."""
    assert text.endswith("\n")
    buckets: dict = {}
    counts: dict = {}
    for line in text.splitlines():
        assert EXPOSITION_LINE.match(line), f"malformed exposition line: {line!r}"
        if "_bucket{" in line:
            name = line.split("_bucket{", 1)[0]
            le = re.search(r'le="([^"]+)"', line).group(1)
            buckets.setdefault(name, []).append((le, float(line.rsplit(" ", 1)[1])))
        elif re.match(r"^[a-zA-Z_:][a-zA-Z0-9_:]*_count \d", line):
            counts[line.split("_count ", 1)[0]] = float(line.rsplit(" ", 1)[1])
    assert buckets, "no histograms rendered"
    for name, series in buckets.items():
        values = [v for _, v in series]
        assert values == sorted(values), f"{name} buckets not cumulative"
        assert series[-1][0] == "+Inf"
        assert values[-1] == counts[name], f"{name} +Inf != _count"


def test_engine_prometheus_text_conformance(cfg, params):
    engine = make_engine(cfg, params)
    for i in range(3):
        engine.submit([1 + i, 2, 3], max_new_tokens=4, seed=i)
    engine.run_until_idle()
    text = engine.prometheus_text()
    _assert_conformant(text)
    assert "serve_completed_total 3" in text
    assert "serve_ttft_seconds_count 3" in text


# ------------------------------------------------------------- span tracing


def test_tracer_ring_bounds_and_drop_count():
    tr = obs.Tracer(capacity=4)
    for i in range(10):
        tr.add("s", "t", float(i), float(i) + 0.5)
    assert len(tr) == 4 and tr.dropped == 6
    doc = tr.chrome_trace()
    assert doc["otherData"]["dropped_spans"] == 6
    names = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(names) == 4
    disabled = obs.Tracer(enabled=False)
    disabled.add("s", "t", 0.0, 1.0)
    assert len(disabled) == 0


def test_tracer_jsonl_is_incremental(tmp_path):
    tr = obs.Tracer()
    tr.add("a", "t", 0.0, 1.0)
    path = tmp_path / "spans.jsonl"
    assert tr.write_jsonl(path) == 1
    assert tr.write_jsonl(path) == 0  # nothing new
    tr.add("b", "t", 1.0, 2.0, {"k": 1})
    assert tr.write_jsonl(path) == 1
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert [l["name"] for l in lines] == ["a", "b"]
    assert lines[1]["attrs"] == {"k": 1}


def _assert_complete_tree(spans, handle, outcome):
    """The acceptance bar: a complete, well-nested span tree whose children
    account for >=95% of the request's measured wall latency."""
    tree = obs.span_tree(spans, handle.rid)
    assert tree, f"no span tree for {handle.rid} ({outcome})"
    root = tree["root"]
    r0, r1 = root[obs.spans.T0], root[obs.spans.T1]
    assert root[obs.spans.ATTRS]["outcome"] == outcome
    assert r0 == handle.submitted_at and r1 == handle.finished_at
    for child in tree["children"]:
        assert child[obs.spans.T0] >= r0 - 1e-9, "child escapes root (left)"
        assert child[obs.spans.T1] <= r1 + 1e-9, "child escapes root (right)"
    assert obs.coverage_fraction(tree) >= 0.95
    names = {c[obs.spans.NAME] for c in tree["children"]}
    assert "queue" in names


def test_span_tree_complete_for_done_cancel_expire(cfg, params):
    """finish / cancel / queue-expiry outcomes all leave complete trees."""
    engine = make_engine(cfg, params, n_slots=2, prefill_chunk=8)
    done = [engine.submit([1, 2, 3], max_new_tokens=4, seed=i) for i in range(2)]
    # a third request queued behind the two slots, cancelled before admission
    cancelled = engine.submit([4, 5], max_new_tokens=4, seed=9)
    cancelled.cancel()
    # and one whose deadline has already passed when the scheduler sees it
    expired = engine.submit([6, 7], max_new_tokens=4, seed=10, timeout=0.0)
    engine.run_until_idle()
    spans = engine.tracer.spans()
    for h in done:
        assert h.status == "done"
        _assert_complete_tree(spans, h, "done")
        names = {c[obs.spans.NAME]
                 for c in obs.span_tree(spans, h.rid)["children"]}
        assert {"queue", "prefill", "decode"} <= names
    assert cancelled.status == "cancelled"
    _assert_complete_tree(spans, cancelled, "cancelled")
    assert expired.status == "expired"
    _assert_complete_tree(spans, expired, "expired")


def test_span_tree_complete_for_shed_and_reject(cfg, params):
    """Admission-time terminal outcomes (deadline shed, invalid reject)
    still get a root + queue tree — correlation ids must resolve even for
    requests that never touched a slot."""
    engine = make_engine(cfg, params)
    # warm the ITL EWMA so the shedder has evidence
    for _ in range(8):
        engine._itl_ewma.update(0.05)
    shed = engine.submit([1, 2], max_new_tokens=20, timeout=0.001)
    assert shed.status == "rejected" and "shed" in shed.error
    invalid = engine.submit([], max_new_tokens=4)
    assert invalid.status == "rejected"
    spans = engine.tracer.spans()
    _assert_complete_tree(spans, shed, "rejected")
    _assert_complete_tree(spans, invalid, "rejected")


def test_span_tree_complete_for_tick_fault(cfg, params):
    """A supervised decode-tick fault fails its slots retryably — and their
    span trees still close, outcome=failed, fault attribution intact."""
    chaos = ServingChaosMonkey([
        ServeFault("tick_fault", step=1, duration=1),
    ])
    engine = make_engine(cfg, params, chaos=chaos, prefill_chunk=8)
    handles = [engine.submit([1 + i, 2], max_new_tokens=6, seed=i)
               for i in range(2)]
    engine.run_until_idle()
    statuses = sorted(h.status for h in handles)
    assert "failed" in statuses  # the fault really fired
    spans = engine.tracer.spans()
    for h in handles:
        _assert_complete_tree(spans, h, h.status)
    # the engine-track timeline recorded phases around the fault
    engine_names = {s[obs.spans.NAME] for s in engine.tracer.by_track("engine")}
    assert "tick" in engine_names and "decode_step" in engine_names


def test_perfetto_export_has_thread_metadata(cfg, params, tmp_path):
    engine = make_engine(cfg, params)
    engine.submit([1, 2, 3], max_new_tokens=4, seed=0)
    engine.run_until_idle()
    path = engine.export_trace(str(tmp_path / "t.trace.json"))
    doc = json.loads((tmp_path / "t.trace.json").read_text())
    assert path and doc["traceEvents"]
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    tracks = {m["args"]["name"] for m in metas}
    assert "engine" in tracks
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert all(e["dur"] >= 0 for e in xs)


# ------------------------------------------------------- HTTP: ids + scrape


def test_request_id_roundtrip_http_sse(cfg, params):
    """Inbound X-Request-Id is honored end-to-end (header + SSE done event);
    without one, the engine generates an id at admission and returns it the
    same two ways — non-stream JSON responses carry it too."""
    engine = make_engine(cfg, params, prefill_chunk=8)
    server = run_server(engine, ByteTok(), port=0, background=True)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=60)
        conn.request(
            "POST", "/generate",
            json.dumps({"prompt": "hello", "max_new_tokens": 4}),
            {"Content-Type": "application/json", "X-Request-Id": "corr-123"},
        )
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("X-Request-Id") == "corr-123"
        body = resp.read().decode()
        done = json.loads(body.strip().splitlines()[-1][len("data: "):])
        assert done["done"] is True and done["request_id"] == "corr-123"
        # generated id: header and body agree, and it resolves to a span tree
        conn.request(
            "POST", "/generate",
            json.dumps({"prompt": "yo", "max_new_tokens": 2, "stream": False}),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        rid = resp.getheader("X-Request-Id")
        doc = json.loads(resp.read())
        assert rid and doc["request_id"] == rid
        assert obs.span_tree(engine.tracer.spans(), rid)
        # hostile ids (body field — http.client refuses to SEND a bad
        # header, but a raw-socket client wouldn't): CR/LF and non-ASCII
        # must never reach the response header (response splitting /
        # UnicodeEncodeError in send_header)
        conn.request(
            "POST", "/generate",
            json.dumps({"prompt": "x", "max_new_tokens": 2, "stream": False,
                        "request_id": "evil\r\nSet-Cookie: pwned=1"}),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        rid = resp.getheader("X-Request-Id")
        resp.read()
        assert resp.getheader("Set-Cookie") is None
        assert "\r" not in rid and "\n" not in rid and " " not in rid
        conn.request(
            "POST", "/generate",
            json.dumps({"prompt": "x", "max_new_tokens": 2, "stream": False,
                        "request_id": "☃☃"}),  # sanitizes to empty
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        rid = resp.getheader("X-Request-Id")
        assert rid and rid.isascii()  # fell back to a generated id
        assert json.loads(resp.read())["request_id"] == rid
        conn.close()
    finally:
        server.stop()


def test_metrics_scrape_conformant_while_serving(cfg, params):
    """Prometheus text scrape (content-negotiated) DURING live traffic:
    format conforms, JSON default stays, and the scraped requests finish
    normally — exposition never perturbs the tick loop."""
    engine = make_engine(cfg, params, prefill_chunk=8)
    server = run_server(engine, ByteTok(), port=0, background=True)
    try:
        results = []

        def client(i):
            c = http.client.HTTPConnection("127.0.0.1", server.port, timeout=60)
            c.request(
                "POST", "/generate",
                json.dumps({"prompt": "x" * (3 + i), "max_new_tokens": 12,
                            "stream": False}),
                {"Content-Type": "application/json"},
            )
            r = c.getresponse()
            results.append((r.status, json.loads(r.read())["status"]))
            c.close()

        workers = [threading.Thread(target=client, args=(i,)) for i in range(4)]
        for w in workers:
            w.start()
        texts = []
        scrape = http.client.HTTPConnection("127.0.0.1", server.port, timeout=60)
        while any(w.is_alive() for w in workers):
            scrape.request("GET", "/metrics",
                           headers={"Accept": "text/plain;version=0.0.4"})
            r = scrape.getresponse()
            assert "text/plain; version=0.0.4" in r.getheader("Content-Type")
            texts.append(r.read().decode())
            time.sleep(0.02)
        for w in workers:
            w.join(timeout=60)
        # also via ?format= and the JSON default
        scrape.request("GET", "/metrics?format=prometheus")
        r = scrape.getresponse()
        texts.append(r.read().decode())
        scrape.request("GET", "/metrics")
        r = scrape.getresponse()
        assert "application/json" in r.getheader("Content-Type")
        snap = json.loads(r.read())
        scrape.close()
        assert snap["completed"] == 4
        assert all(s == (200, "done") for s in results), results
        for text in texts[-3:]:
            _assert_conformant(text)
        assert "serve_completed_total 4" in texts[-1]
    finally:
        server.stop()


# ------------------------------------------------------------ flight recorder


@pytest.mark.chaos
def test_flight_recorder_dumps_on_breaker_open(cfg, params, tmp_path):
    """Three consecutive injected tick faults trip the breaker — the dump
    must appear in the obs dir with the faulting ticks and the breaker_trip
    event inside, without any verbose logging enabled."""
    chaos = ServingChaosMonkey([
        ServeFault("tick_fault", step=2, duration=3),
    ])
    engine = make_engine(
        cfg, params, chaos=chaos, prefill_chunk=8,
        breaker_threshold=3, obs_dir=str(tmp_path),
    )
    # enough offered load that every faulting tick has active slots — the
    # breaker counts CONSECUTIVE faulted ticks, and an idle tick between
    # faults would reset nothing yet never trip
    for i in range(8):
        engine.submit([1 + i, 2, 3], max_new_tokens=16, seed=i)
    engine.run_until_idle()
    assert engine.stats["breaker_trips"] >= 1
    dumps = [p for p in engine.flight.dumps if "breaker_open" in p]
    assert dumps, engine.flight.dumps
    doc = json.loads(open(dumps[0]).read())
    assert doc["reason"] == "breaker_open"
    fault_ticks = [t for t in doc["ticks"] if t.get("fault")]
    assert len(fault_ticks) >= 3, "faulting ticks missing from the ring"
    assert any(e["event"] == "breaker_trip" for e in doc["events"])
    assert any(e["event"] == "tick_fault" for e in doc["events"])
    assert doc.get("spans"), "span tail missing from the dump"


def test_flight_recorder_dumps_on_drain(cfg, params, tmp_path):
    engine = make_engine(cfg, params, obs_dir=str(tmp_path))
    engine.submit([1, 2], max_new_tokens=3, seed=0)
    stop = threading.Event()
    t = threading.Thread(target=engine.run, args=(stop,), daemon=True)
    t.start()
    time.sleep(0.2)
    engine.begin_drain(deadline_s=30)
    t.join(timeout=60)
    assert engine.lifecycle.state == "stopped"
    assert any("drain" in p for p in engine.flight.dumps)
    # the drain path also exports the Perfetto trace + span log
    assert (tmp_path / "trace_serve.json").exists()
    assert (tmp_path / "spans.jsonl").exists()


def test_flight_recorder_no_dir_is_silent_noop():
    fr = obs.FlightRecorder(directory=None)
    fr.tick({"tick": 1})
    fr.event("boom", detail="x")
    assert fr.dump("anything") is None
    assert len(fr.ticks()) == 1 and len(fr.events()) == 1


# ----------------------------------------------------------------- profiling


def test_parse_profile_window():
    assert obs.parse_profile_window("100:20") == (100, 20)
    for bad in ("x:y", "100", "0:5", "5:0", ":"):
        with pytest.raises(ValueError):
            obs.parse_profile_window(bad)


def test_profile_capture_over_http_and_draining_409(cfg, params, tmp_path):
    """The full 202 -> capture -> on-disk artifact -> drain-409 lifecycle,
    with a real jax.profiler capture. It was the slow lane's while the
    window opened with Python tracing on (~20 s on CPU); through
    ``obs.profiling.start_trace`` it takes two (PR 24)."""
    engine = make_engine(cfg, params, obs_dir=str(tmp_path), prefill_chunk=8)
    server = run_server(engine, ByteTok(), port=0, background=True)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=60)
        conn.request("POST", "/admin/profile", json.dumps({"ticks": 2}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        doc = json.loads(resp.read())
        assert resp.status == 202 and doc["accepted"] and doc["ticks"] == 2
        # a second request while the first is pending/active conflicts
        conn.request("POST", "/admin/profile", json.dumps({"ticks": 2}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 409
        resp.read()
        # traffic drives ticks; the capture must complete and land on disk
        conn.request(
            "POST", "/generate",
            json.dumps({"prompt": "abc", "max_new_tokens": 8, "stream": False}),
            {"Content-Type": "application/json"},
        )
        conn.getresponse().read()
        deadline = time.time() + 30
        while engine.profile_active and time.time() < deadline:
            time.sleep(0.05)
        assert engine.profiles_completed, "capture never finished"
        assert (tmp_path / "profiles").exists()
        # draining: new captures are rejected with 409
        engine.begin_drain(deadline_s=30)
        conn.request("POST", "/admin/profile", json.dumps({"ticks": 1}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 409
        body = json.loads(resp.read())
        assert "drain" in body["error"]
        conn.close()
    finally:
        server.stop()


def test_profile_request_refusals(cfg, params, tmp_path):
    """The staging-side contract, without ever touching jax.profiler (the
    scheduler never runs, so the staged capture never starts): no obs dir
    -> refuse; concurrent capture -> refuse; draining -> refuse."""
    engine = make_engine(cfg, params)  # no obs_dir
    with pytest.raises(RuntimeError, match="obs"):
        engine.request_profile(2)
    staged = make_engine(cfg, params, obs_dir=str(tmp_path))
    info = staged.request_profile(3)
    assert info["ticks"] == 3 and "profiles" in info["path"]
    with pytest.raises(RuntimeError, match="in progress"):
        staged.request_profile(2)
    draining = make_engine(cfg, params, obs_dir=str(tmp_path / "d"))
    draining.begin_drain(deadline_s=1.0)
    with pytest.raises(RuntimeError, match="drain"):
        draining.request_profile(2)


# ------------------------------------------------------------- training side


def test_hbm_device_stats_shape():
    stats = obs.hbm_device_stats()
    if stats is None:  # CPU backend exposes no memory stats — the honest None
        assert obs.hbm_used_gb() is None
        return
    assert stats["max_gb"] == max(stats["per_device_gb"])
    assert stats["mean_gb"] == pytest.approx(
        sum(stats["per_device_gb"]) / len(stats["per_device_gb"])
    )


def test_trainer_emits_step_spans_and_trace(tmp_path, devices):
    """A tiny end-to-end train run records the per-phase step timeline
    (data_fetch / dispatch / device_sync / checkpoint_save) and exports the
    Perfetto trace + spans.jsonl beside metrics.jsonl on close."""
    from zero_transformer_tpu.config import (
        CheckpointConfig,
        Config,
        DataConfig,
        MeshConfig,
        ModelConfig,
        OptimizerConfig,
        TrainingConfig,
    )
    from zero_transformer_tpu.training.trainer import Trainer

    cfg = Config(
        model=ModelConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                          max_seq_len=16, dropout=0.0),
        mesh=MeshConfig(zero_stage=1),
        optimizer=OptimizerConfig(peak_learning_rate=1e-2, warmup_steps=2,
                                  total_steps=10),
        training=TrainingConfig(batch_size=8, train_context=16, total_steps=10,
                                evaluation_frequency=0,
                                maximum_evaluation_steps=1,
                                log_frequency=5, seed=0),
        data=DataConfig(source="synthetic", max_context=16),
        checkpoint=CheckpointConfig(directory=str(tmp_path / "run"),
                                    save_frequency=5, async_save=False),
    )
    trainer = Trainer(cfg)
    trainer.train()
    trainer.close()
    names = {s[obs.spans.NAME] for s in trainer.tracer.by_track("train")}
    assert {"data_fetch", "dispatch", "device_sync", "checkpoint_save"} <= names
    run_dir = tmp_path / "run"
    assert (run_dir / "trace_train.json").exists()
    assert (run_dir / "spans.jsonl").exists()
    assert (run_dir / "metrics.jsonl").exists()  # the obs exports sit beside it
    doc = json.loads((run_dir / "trace_train.json").read_text())
    assert any(e.get("name") == "data_fetch" for e in doc["traceEvents"])
    # flight ring carried the log-point step summaries
    assert any(t[1].get("step") for t in trainer.flight.ticks())

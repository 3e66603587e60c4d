"""The chunk tick's readers (ISSUE 35): CPU, one file.

``benchmark/chunk_ticks.py`` and the seven readers beside it are held to a
ring and to a capture written out by hand, to returning None where their
inputs are missing (the parent's spans, an untraced run), and to a real run of
a tiny engine: the admission's spans nest as ``docs/OBSERVABILITY.md`` says,
the capture's chunk ticks are the ring's, and the gaps classified are
``arith.gaps_ms``'s. Their entries in ``BENCHMARK.json`` are held to the
benchmark's own rules.
"""
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import arith, chunk_ticks, harness, host_trace  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CHUNK_METRICS = ("chunk_tick_ms_p50", "chunk_tick_host_ms_p50", "install_ms_p50",
                 "chunk_tick_launches_p50", "chunk_tick_device_idle_ms_p50",
                 "itl_chunk_gap_share", "itl_decode_only_p95_ms")


def test_entries_are_ready_to_append():
    # declared in this order, found by name wherever later entries stand
    entries = [m for m in BENCH["per_layer"] if m["name"] in CHUNK_METRICS]
    assert tuple(m["name"] for m in entries) == CHUNK_METRICS
    layers = {m["layer"] for m in BENCH["per_layer"] if m["name"] not in CHUNK_METRICS}
    itl = next(m for m in BENCH["end_to_end"] if m["name"] == "itl_p95_ms")
    for m in entries:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert re.match(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$", m["name"])
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"]) and m["better"] == "lower"
        # serving cells, each of which reports the metric it moves; a cell
        # that a later PR adds appends itself to both lists, or to neither
        assert m["moves"] == "itl_p95_ms" and m["workloads"]
        assert set(m["workloads"]) <= set(itl["workloads"])
        assert m["layer"] in layers
        assert m["layer"] == ("Device" if m["source"] == "device_trace" else "Serving engine")
        assert hasattr(harness.load_reader(m["name"]), "read")
    assert {m["name"] for m in entries if m["source"] == "device_trace"} == {
        "chunk_tick_launches_p50", "chunk_tick_device_idle_ms_p50"}


@pytest.mark.parametrize("name", CHUNK_METRICS)
def test_reader_returns_none_without_its_inputs(name, monkeypatch):
    monkeypatch.setattr(host_trace, "newest_xplane", lambda root=None: None)
    read = harness.load_reader(name).read
    assert read({}) is None
    # the spans of a program whose ``tick`` notes no ``chunks`` and which has
    # no ``install`` (PR 35's parent), traced and with its requests' records
    old_spans = [(0, "engine", "tick", 1.0, 1.07, {"tick": 3}),
                 (1, "engine", "prefill", 1.001, 1.02, {"tick": 3}),
                 (2, "engine", "prefill_chunk", 1.002, 1.004, {"tick": 3}),
                 (3, "engine", "decode_step", 1.02, 1.069, {"tick": 3}),
                 (4, "engine", "device_wait", 1.03, 1.068, {"tick": 3})]
    assert read({"spans": old_spans, "t0": 0.0, "t_end": 9.0, "trace": {"events": {0: []}},
                 "chips": 1, "records": [{"token_times": [1.0, 1.07, 1.14]}]}) is None


# ------------------------------------------------------ a ring written by hand


def _chunk_ring():
    """The ring of five ticks, written by hand (seconds on one clock), and the
    token times of four requests. Tick 0 lies before the window; tick 1 is a
    plain decode tick; tick 2 a chunk tick of 16 ms with two dispatches
    (install 5 ms after the first, a chunk_wait of 1 ms before the second,
    device_wait 3 ms: host 12); tick 3 a prefill-only tick (install 2 ms);
    tick 4 a chunk tick of 10 ms (install 3, device_wait 2: host 8)."""
    def sp(name, s, e, tick, **attrs):
        return (0, "engine", name, s, e, {"tick": tick, **attrs})

    spans = [
        sp("tick", 1.0, 1.02, 0, chunks=1), sp("install", 1.001, 1.009, 0, slots=1),
        sp("decode_step", 1.01, 1.019, 0),
        sp("tick", 10.0, 10.005, 1), sp("decode_step", 10.0005, 10.0048, 1),
        sp("device_wait", 10.001, 10.0045, 1),
        sp("tick", 10.005, 10.021, 2, chunks=2), sp("prefill", 10.0055, 10.0155, 2),
        sp("prefill_chunk", 10.0056, 10.0076, 2, slots=2, rows=2),
        sp("install", 10.0077, 10.0127, 2, slots=1),
        sp("chunk_wait", 10.0128, 10.0138, 2),
        sp("prefill_chunk", 10.0139, 10.0154, 2, slots=1, rows=2),
        sp("decode_step", 10.016, 10.0205, 2), sp("device_wait", 10.017, 10.020, 2),
        sp("tick", 12.0, 12.004, 3, chunks=1, phase="prefill_only"),
        sp("prefill", 12.0002, 12.0038, 3), sp("prefill_chunk", 12.0003, 12.0009, 3),
        sp("install", 12.001, 12.003, 3, slots=1),
        sp("tick", 14.0, 14.010, 4, chunks=1), sp("prefill", 14.0005, 14.0065, 4),
        sp("prefill_chunk", 14.0006, 14.0026, 4, slots=1, rows=2),
        sp("install", 14.0027, 14.0057, 4, slots=1),
        sp("decode_step", 14.007, 14.0095, 4), sp("device_wait", 14.0075, 14.0095, 4),
        (0, "req-1", "queue", 10.0, 10.1, None),
    ]
    records = [
        # 5.9 ms plain; 15.8 ms over tick 2 (its decode_step ends 10.0205); 9.3 ms plain
        {"token_times": [9.999, 10.0049, 10.0207, 10.030]},
        # the consumer stamped the token before AFTER tick 2 had started (10.005):
        # by the tick's start this 15.7 ms gap would pass for a plain one
        {"token_times": [10.0051, 10.0208]},
        # 14.7 ms over tick 4 (decode_step ends 14.0095); 5.0 ms plain
        {"token_times": [13.995, 14.0097, 14.0147]},
        {"token_times": [20.0]},  # one token: no gap
    ]
    return {"spans": spans, "records": records, "t0": 5.0, "t_end": 30.0}


def test_readers_on_a_ring_written_by_hand(capsys):
    ctx = _chunk_ring()
    read = {name: harness.load_reader(name).read for name in CHUNK_METRICS}
    ticks = chunk_ticks.ring_ticks(ctx)
    assert [p["tick"] for p in ticks] == [2, 4] and [p["chunks"] for p in ticks] == [2, 1]
    assert [p["wait_s"] for p in ticks] == pytest.approx([4e-3, 2e-3])
    assert read["chunk_tick_ms_p50"](ctx) == pytest.approx(13.0)       # (16 + 10) / 2
    assert read["chunk_tick_host_ms_p50"](ctx) == pytest.approx(10.0)  # (12 + 8) / 2
    assert read["install_ms_p50"](ctx) == pytest.approx(3.0)           # of 5, 2, 3
    # six gaps, three of them over a chunk tick; the others 5.0, 5.9, 9.3 ms
    assert len(chunk_ticks.gap_instants(ctx["records"])) == 6 == len(
        arith.gaps_ms([r["token_times"] for r in ctx["records"]]))
    made, others = chunk_ticks.split_gaps(ctx)
    assert sorted(made) == pytest.approx([14.7, 15.7, 15.8])
    assert sorted(others) == pytest.approx([5.0, 5.9, 9.3])
    assert read["itl_chunk_gap_share"](ctx) == pytest.approx(50.0)
    assert read["itl_decode_only_p95_ms"](ctx) == pytest.approx(5.9 + 0.9 * (9.3 - 5.9))
    err = capsys.readouterr().err
    assert "of the window 2 (chunks a tick: 1 x 1, 2 x 1)" in err
    assert "prefill span p50 8.000" in err and "install p50 3.000 over 3 spans" in err
    assert "gaps 6 (arith.gaps_ms: 6;" in err and "6 of them" in err and "3 hold a chunk tick" in err
    # a traced run's ring is read up to the instant its capture OPENED (13.0:
    # tick 4 and the third request's gaps come after it)
    traced = dict(ctx, traced=(13.0, 16.0))
    assert chunk_ticks.window(ctx) == (5.0, 30.0) and chunk_ticks.window(traced) == (5.0, 13.0)
    assert read["chunk_tick_ms_p50"](traced) == pytest.approx(16.0)
    assert read["chunk_tick_host_ms_p50"](traced) == pytest.approx(12.0)
    assert read["install_ms_p50"](traced) == pytest.approx(3.5)         # of 5, 2
    assert read["itl_chunk_gap_share"](traced) == pytest.approx(50.0)  # 15.8, 15.7 of four
    assert read["itl_decode_only_p95_ms"](traced) == pytest.approx(5.9 + 0.95 * (9.3 - 5.9))
    assert "gaps 6 (arith.gaps_ms: 6;" in capsys.readouterr().err
    # an untraced run has no device side to read
    assert read["chunk_tick_launches_p50"](ctx) is None
    assert read["chunk_tick_device_idle_ms_p50"](dict(ctx, trace=None)) is None


# --------------------------------------------------- a capture written by hand


def _chunk_timeline(tiny=(3, 5)):
    """A capture written by hand, host clock 1.3 ms ahead of the device's: a
    plain tick, then for each entry ``n`` of ``tiny`` a chunk tick and a plain
    tick. A plain tick: schedule 0.2 ms, dispatch 0.5, device_wait until 0.3
    after the decode program (enqueued 0.1 after the dispatch returned, 10 ms
    long) ends, emit 0.2. A chunk tick, from its start in ms: schedule 0.2;
    prefill [0.2, 6.2] holding prefill_chunk [0.3, 1.3] (the chunk program is
    enqueued at 1.0, starts 0.05 later and runs 4 ms) and install [1.4, 6.0]
    (``n`` tiny programs enqueued from 2.0 on, 0.01 ms each once the chunk
    program has ended at 5.05); decode_step [6.3, 17.25] = dispatch 0.5 +
    device_wait (the decode program, enqueued at 6.9, runs [6.95, 16.95]);
    emit 0.2: 17.45 ms, 2 + n launches, the device idle 1.05 + (1.9 - 0.01 n)
    + 0.5 of it."""
    off, ms = 1.3e-3, 1e-3
    host, modules, ops = {}, [], []
    run_ids = iter(range(100, 1000))

    def add(name, s, e, **stats):
        host.setdefault(name, []).append((s, e, stats))

    def launch(name, enqueue, start, seconds):
        rid = next(run_ids)
        add(host_trace.ENQUEUE, enqueue, enqueue + 0.02 * ms, run_id=rid)
        modules.append((name, start - off, start + seconds - off, rid))
        ops.append((start - off, start + seconds - off))

    def plain(t, tick):
        add("engine/schedule", t, t + 0.2 * ms, tick=tick)
        add("engine/dispatch", t + 0.2 * ms, t + 0.7 * ms, tick=tick)
        launch("jit__fused_step_impl(123)", t + 0.8 * ms, t + 0.85 * ms, 10 * ms)
        done = t + 11.15 * ms
        add("engine/device_wait", t + 0.7 * ms, done, tick=tick)
        add("engine/decode_step", t + 0.2 * ms, done, tick=tick)
        add("engine/emit", done, done + 0.2 * ms, tick=tick)
        add("engine/tick", t, done + 0.2 * ms, tick=tick)
        return done + 0.2 * ms

    def chunk(t, tick, n):
        add("engine/schedule", t, t + 0.2 * ms, tick=tick)
        add("engine/prefill", t + 0.2 * ms, t + 6.2 * ms, tick=tick)
        add("engine/prefill_chunk", t + 0.3 * ms, t + 1.3 * ms, tick=tick)
        launch("jit__paged_chunk_prefill_impl(9)", t + 1.0 * ms, t + 1.05 * ms, 4 * ms)
        add("engine/install", t + 1.4 * ms, t + 6.0 * ms, tick=tick)
        for k in range(n):
            launch("jit_convert_element_type(5)", t + (2.0 + 0.5 * k) * ms,
                   t + (5.05 + 0.01 * k) * ms, 0.01 * ms)
        add("engine/dispatch", t + 6.3 * ms, t + 6.8 * ms, tick=tick)
        launch("jit__fused_step_impl(123)", t + 6.9 * ms, t + 6.95 * ms, 10 * ms)
        add("engine/device_wait", t + 6.8 * ms, t + 17.25 * ms, tick=tick)
        add("engine/decode_step", t + 6.3 * ms, t + 17.25 * ms, tick=tick)
        add("engine/emit", t + 17.25 * ms, t + 17.45 * ms, tick=tick)
        add("engine/tick", t, t + 17.45 * ms, tick=tick)
        return t + 17.45 * ms

    t = plain(0.100, 7)
    for i, n in enumerate(tiny):
        t = chunk(t, 8 + 2 * i, n)
        t = plain(t, 9 + 2 * i)
    for evs in host.values():
        evs.sort(key=lambda ev: ev[0])
    ops.sort()
    return {"host": host, "modules": {0: modules}, "ops": {0: ops},
            "extent": (ops[0][0], ops[-1][1])}, off


def test_device_side_readers_on_a_capture_written_by_hand(monkeypatch, capsys):
    loaded, off = _chunk_timeline()
    found = host_trace.offset(loaded)
    assert found["consistent"] and abs(found["offset_s"] - off) <= found["error_s"] + 1e-12
    # the capture's chunk ticks are those that hold a prefill_chunk and a decode_step
    ticks = chunk_ticks.capture_ticks(loaded)
    assert [stats["tick"] for *_, stats in ticks] == [8, 10]
    per_tick = chunk_ticks.launches(loaded, found["offset_s"], ticks)
    assert per_tick == [
        {"jit__paged_chunk_prefill_impl": 1, "jit_convert_element_type": 3, "jit__fused_step_impl": 1},
        {"jit__paged_chunk_prefill_impl": 1, "jit_convert_element_type": 5, "jit__fused_step_impl": 1}]
    idle = chunk_ticks.idle_in_ticks(loaded, found["offset_s"], ticks, 1)
    assert [sum(b - a for a, b in gaps) for gaps in idle] == pytest.approx([3.42e-3, 3.40e-3])

    monkeypatch.setattr(host_trace, "load", lambda path=None: loaded)
    ctx = dict(_chunk_ring(), trace={"events": {}}, chips=1)
    assert harness.load_reader("chunk_tick_launches_p50").read(ctx) == pytest.approx(6.0)  # of 5, 7
    assert harness.load_reader("chunk_tick_device_idle_ms_p50").read(ctx) == pytest.approx(3.41)
    err = capsys.readouterr().err
    assert "jit_convert_element_type 5, jit__paged_chunk_prefill_impl 1" in err
    assert "engine/install" in err and "engine/device_wait" in err
    # a program whose ring holds no chunk tick (the parent) reads nothing
    # though its capture holds the same annotations; nor does an untraced run
    old = dict(ctx, spans=[sp for sp in ctx["spans"] if not (sp[5] or {}).get("chunks")])
    for name in ("chunk_tick_launches_p50", "chunk_tick_device_idle_ms_p50"):
        assert harness.load_reader(name).read(old) is None
        assert harness.load_reader(name).read(dict(ctx, trace=None)) is None
    # and without a launch both clocks saw there is no offset to shift by
    no_enqueue = dict(loaded, host={k: v for k, v in loaded["host"].items()
                                    if k != host_trace.ENQUEUE})
    monkeypatch.setattr(host_trace, "load", lambda path=None: no_enqueue)
    assert harness.load_reader("chunk_tick_device_idle_ms_p50").read(ctx) is None


# ------------------------------------------------------------ a real engine run


def test_readers_on_a_real_engine_run(tmp_path):
    """A tiny engine, every program compiled first, then a window under a
    capture: three requests at once (two dispatches in one prefill-only tick:
    three slots, two rows a dispatch), then one more every third tick while
    the others decode, so that some ticks run a chunk program AND a decode
    step; the tokens are stamped as the benchmark's consumer stamps them."""
    import jax

    from benchmark import weights
    from zero_transformer_tpu.obs import profiling

    config = {"name": "tiny", "reference": "benchmark/reference/gpt_alibi.py", "model": {
        "d_model": 128, "n_layers": 2, "n_heads": 4, "head_dim": 32, "d_ff": 512,
        "vocab_size": 256, "max_seq_len": 128, "position": "alibi", "norm": "layernorm",
        "activation": "gelu", "tie_embeddings": True, "param_dtype": "float32",
        "compute_dtype": "bfloat16"}}
    mix = json.loads((ROOT / "benchmark/traffic/alpaca_open_poisson.json").read_text())
    mix["engine"].update({"n_slots": 4, "cache_len": 128, "prefill_chunk": 16, "page_size": 4})
    cell = {"name": "tiny_serve", "chips": 1, "config": config, "traffic": mix}
    drv = harness.load_driver("serve_open_loop")
    ref = harness.load_reference(config)
    params = weights.build(ref.leaf_table(config["model"]), weights.seed_key(3, "weights"),
                           jax.numpy.float32)
    engine = drv.build_engine(cell, params, ROOT / ".bench_out" / "tiny_chunk_ticks")
    warm = [engine.submit([5 + i, 6, 7, 8], max_new_tokens=6, seed=i) for i in range(3)]
    engine.run_until_idle()
    assert all(h.status == "done" for h in warm)

    handles, records = [], []

    def submit(n):
        for _ in range(n):
            handles.append(engine.submit([9 + len(handles), 6, 7, 8], max_new_tokens=8,
                                         seed=len(handles)))
            records.append({"token_times": []})

    def collect():
        for h, rec in zip(handles, records):
            while (ev := h.next_event(timeout=0)) is not None:
                if ev[0] == "token":
                    rec["token_times"].append(engine.now())

    t0 = engine.now()
    profiling.start_trace(tmp_path)
    try:
        submit(3)
        for i in range(200):
            busy = engine.step()
            collect()
            if i % 3 == 2 and len(handles) < 8:
                submit(1)
            elif not busy and engine.queue_depth == 0:
                break
    finally:
        jax.profiler.stop_trace()
    assert len(handles) == 8 and all(h.status == "done" for h in handles)
    ctx = {"spans": engine.tracer.spans(), "t0": t0, "t_end": engine.now(), "records": records}

    per: dict = {}
    for _, track, name, s, e, a in ctx["spans"]:
        if track == "engine" and s >= t0 and a and "tick" in a:
            per.setdefault(a["tick"], {}).setdefault(name, []).append((s, e, a))
    installs = 0
    for spans in per.values():
        (ts, te, attrs), = spans["tick"]
        # ``chunks`` on a tick is its count of prefill_chunk spans, 0 not noted
        assert attrs.get("chunks", 0) == len(spans.get("prefill_chunk", ()))
        assert ("chunks" in attrs) == ("prefill" in spans)
        chunks = spans.get("prefill_chunk", [])
        for s, e, a in spans.get("install", ()):
            installs += 1
            (ps, pe, _), = spans["prefill"]
            # inside the tick's prefill, after a dispatch, over none
            assert ps <= s and e <= pe and a["slots"] >= 1 and "shipped" not in a
            assert any(ce <= s for _, ce, _ in chunks)
            assert not any(cs < e and ce > s for cs, ce, _ in chunks)
        for s, e, _ in spans.get("chunk_wait", ()):
            (ps, pe, _), = spans["prefill"]
            # the wait for the chunk program before, ahead of the next dispatch
            assert ps <= s and e <= pe and any(e <= cs for cs, _, _ in chunks)
    assert installs >= 6  # 8 requests, the first three installed by two dispatches
    assert any("chunk_wait" in spans for spans in per.values())

    # host + wait is the tick, to the span overhead; install lies in prefill
    chunk = chunk_ticks.ring_ticks(ctx)
    assert len(chunk) >= 3 and all(0 <= p["wait_s"] < p["end"] - p["start"] for p in chunk)
    assert all(p["prefill_s"] < p["end"] - p["start"] for p in chunk)
    tick = harness.load_reader("chunk_tick_ms_p50").read(ctx)
    host = harness.load_reader("chunk_tick_host_ms_p50").read(ctx)
    wait = arith.percentile([p["wait_s"] * 1e3 for p in chunk], 50)
    assert 0 < host < tick and host + wait == pytest.approx(tick, rel=0.5)
    prefill = arith.percentile([p["prefill_s"] * 1e3 for p in chunk], 50)
    assert 0 < harness.load_reader("install_ms_p50").read(ctx) <= prefill

    # the capture's chunk ticks, told by the annotations they hold, are the ring's
    loaded = host_trace.load(host_trace.newest_xplane(tmp_path))
    in_capture = [stats["tick"] for *_, stats in chunk_ticks.capture_ticks(loaded)]
    assert in_capture == [p["tick"] for p in chunk]
    assert "engine/install" in loaded["host"] and "engine/chunk_wait" in loaded["host"]

    # every gap itl_p95_ms reads is classified, once
    every = arith.gaps_ms([r["token_times"] for r in records])
    made, others = chunk_ticks.split_gaps(ctx)
    assert len(every) == 8 * 7 == len(made) + len(others) and made and others
    assert sorted(made + others) == pytest.approx(sorted(every))
    share = harness.load_reader("itl_chunk_gap_share").read(ctx)
    assert share == pytest.approx(100.0 * len(made) / len(every))
    assert harness.load_reader("itl_decode_only_p95_ms").read(ctx) == pytest.approx(
        arith.percentile(others, 95))

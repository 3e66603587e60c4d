"""The host side of a trace (ISSUE 24): CPU, one file.

``benchmark/host_trace.py`` is held to a recorded chip trace worked out by
hand (``small_trace.host_by_hand.json``) and to a timeline written out in the
test; the six new per-layer readers are held to what they read and to
returning None where their inputs are missing; the flash kernels are held to
their names.
"""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import harness, host_trace  # noqa: E402

HERE = Path(__file__).resolve().parent
FIXTURE = HERE / "small_trace.xplane.pb"
BY_HAND = json.loads((HERE / "small_trace.host_by_hand.json").read_text())
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW_METRICS = ("engine_host_ms_per_tick", "engine_device_wait_ms_p50",
               "decode_program_ms_p50", "prefill_program_ms_p50",
               "device_idle_attributed.serve", "flash_attention_roofline")
NS = 1e-9


@pytest.fixture(scope="module")
def recorded():
    return host_trace.load(FIXTURE)


# ------------------------------------------------- the recorded chip trace


def test_recorded_trace_pairs_launches_and_bounds_the_offset(recorded):
    launches = BY_HAND["launches_ns"]
    modules = recorded["modules"][0]
    assert [rid for *_, rid in modules] == [4, 5, 6]
    for name, s, e, rid in modules:
        assert name.startswith("jit__lambda(")
        assert [s, e] == pytest.approx([t * NS for t in launches[str(rid)]["module"]], abs=NS)
    assert host_trace.program_durations(recorded, r"^jit__lambda\b") == pytest.approx(
        [(b - a) * NS for a, b in (v["module"] for v in launches.values())], abs=NS)
    assert host_trace.programs(recorded) == {"jit__lambda": [3, pytest.approx(sum(
        (b - a) * NS for a, b in (v["module"] for v in launches.values())), abs=3 * NS)]}
    found = host_trace.offset(recorded)
    by_hand = BY_HAND["offset_ns"]
    assert found["pairs"] == 3 and found["upper_from"] == host_trace.COMPLETE
    assert found["consistent"]
    assert found["low_s"] == pytest.approx(by_hand["low"] * NS, abs=NS)
    assert found["high_s"] == pytest.approx(by_hand["high"] * NS, abs=NS)
    assert found["offset_s"] == pytest.approx(by_hand["midpoint"] * NS, abs=NS)
    assert found["error_s"] == pytest.approx(by_hand["half_width"] * NS, abs=NS)
    # the device "starts" each program over a millisecond before the host
    # enqueued it: uncorrected, half of every gap would change hands
    assert 1.0e-3 < found["low_s"] < found["high_s"] < 2.0e-3


def test_recorded_trace_splits_the_gaps_between_launches_by_hand(recorded):
    names = BY_HAND["names"]
    spans = {n: recorded["host"][n] for n in names}
    gaps = [tuple(t * NS for t in g["device"]) for g in BY_HAND["gaps_between_launches_ns"]]
    # the gaps between the launches are among the device's idle gaps
    idle = host_trace.idle_gaps(recorded, 1)
    for g in gaps:
        assert any(a == pytest.approx(g[0], abs=NS) and b == pytest.approx(g[1], abs=NS)
                   for a, b in idle)
    labelled = host_trace.label_gaps(gaps, spans, BY_HAND["offset_ns"]["midpoint"] * NS)
    totals = BY_HAND["totals_ns"]
    assert labelled["idle_s"] == pytest.approx(totals["idle"] * NS, abs=2 * NS)
    assert set(labelled["by_name"]) == set(names) | {host_trace.UNATTRIBUTED}
    for name, sec in labelled["by_name"].items():
        assert sec == pytest.approx(totals[name] * NS, abs=3 * NS), name
    by_start = sorted(labelled["gaps"])
    for (at, dur, mine), hand in zip(by_start, BY_HAND["gaps_between_launches_ns"]):
        assert dur == pytest.approx(hand["length"] * NS, abs=NS)
        assert {k: round(v / NS) for k, v in mine.items()} == pytest.approx(hand["split"], abs=2)
    assert host_trace.attributed_percent(labelled) == pytest.approx(
        BY_HAND["attributed_percent"], abs=1e-3)


def test_recorded_trace_holds_no_program_span_so_the_readers_say_so(recorded, monkeypatch, capsys):
    """A capture of a program without the live spans (the parent commit's):
    no ``engine/`` annotation, so nothing is attributed, and said so."""
    assert host_trace.annotations(recorded) == {}
    monkeypatch.setattr(host_trace, "newest_xplane", lambda root=None: FIXTURE)
    ctx = {"trace": {"events": {}}, "chips": 1}
    assert harness.load_reader("device_idle_attributed.serve").read(ctx) is None
    assert "offset unknown" in capsys.readouterr().err
    # the device's programs are there whatever the program records: none is
    # the engine's, so these two find nothing either
    assert harness.load_reader("decode_program_ms_p50").read(ctx) is None
    assert harness.load_reader("prefill_program_ms_p50").read(ctx) is None


# ----------------------------------------------- a timeline written by hand


def _timeline():
    """Two engine ticks on a host clock that runs 1.3 ms ahead of the
    device's. Each tick: schedule 0.2 ms, dispatch 0.5 ms, device_wait until
    0.3 ms after the program ends, emit 0.2 ms; 1 ms of idle between the
    ticks. As on the chip, the runtime enqueues the program on a thread of
    its own 0.1 ms AFTER the dispatch has returned, inside the wait; the
    decode program starts 0.05 ms after its enqueue and takes 10 ms."""
    off = 1.3e-3
    host, modules, ops = {}, [], []

    def add(name, s, e, **stats):
        host.setdefault(name, []).append((s, e, stats))

    t = 0.100
    for tick in (7, 8):
        start = t
        add("engine/schedule", t, t + 0.2e-3, tick=tick)
        t += 0.2e-3
        add("engine/dispatch", t, t + 0.5e-3, tick=tick)
        enqueue = t + 0.6e-3
        add(host_trace.ENQUEUE, enqueue, enqueue + 0.05e-3, run_id=tick)
        dev0 = enqueue + 0.05e-3 - off
        modules.append(("jit__fused_step_impl(123)", dev0, dev0 + 10e-3, tick))
        ops.append((dev0, dev0 + 4e-3))
        ops.append((dev0 + 4e-3, dev0 + 10e-3))
        t += 0.5e-3
        done = dev0 + 10e-3 + off + 0.3e-3
        add("engine/device_wait", t, done, tick=tick)
        add("engine/decode_step", t - 0.5e-3, done, tick=tick)
        add("engine/emit", done, done + 0.2e-3, tick=tick)
        t = done + 0.2e-3
        add("engine/tick", start, t, tick=tick)
        add("engine/idle", t, t + 1e-3)
        t += 1e-3
    extent = (ops[0][0], ops[-1][1])
    return {"host": host, "modules": {0: modules}, "ops": {0: ops}, "extent": extent}, off


def test_offset_from_the_programs_own_wait_and_gaps_innermost_first():
    loaded, off = _timeline()
    found = host_trace.offset(loaded)
    # no completion callback in a level-1 capture: the upper bound is the end
    # of the wait in which each enqueue fell (bounding by the wait's START
    # would pair the second wait with the first program, 13 ms too loose)
    assert found["upper_from"] == "engine/device_wait" and found["pairs"] == 2
    assert found["low_s"] == pytest.approx(off - 0.05e-3)
    assert found["high_s"] == pytest.approx(off + 0.3e-3)
    assert abs(found["offset_s"] - off) <= found["error_s"] + 1e-12

    spans = host_trace.annotations(loaded, "engine/")
    assert set(spans) == {"engine/tick", "engine/schedule", "engine/dispatch", "engine/idle",
                          "engine/device_wait", "engine/decode_step", "engine/emit"}
    gaps = host_trace.idle_gaps(loaded, 1)
    assert len(gaps) == 1  # between the two programs; the extent is the ops' own
    labelled = host_trace.label_gaps(gaps, spans, off)
    # with the true offset the one gap reads: the tail of the first wait
    # 0.3, emit 0.2, idle 1.0, schedule 0.2, dispatch 0.5, and 0.15 of the
    # second wait up to the launch
    want = {"engine/device_wait": 0.45e-3, "engine/emit": 0.2e-3, "engine/idle": 1.0e-3,
            "engine/schedule": 0.2e-3, "engine/dispatch": 0.5e-3}
    assert labelled["by_name"] == pytest.approx(want, abs=1e-9)
    assert labelled["idle_s"] == pytest.approx(2.35e-3)
    assert host_trace.attributed_percent(labelled) == pytest.approx(100.0)
    # the parents (tick, decode_step) cover the same instants and get none:
    # innermost first
    assert "engine/tick" not in labelled["by_name"]
    # uncorrected, over a millisecond of the gap changes hands
    wrong = host_trace.label_gaps(gaps, spans, 0.0)["by_name"]
    assert wrong.get("engine/device_wait", 0.0) > 1.0e-3
    # what no span covers is unattributed
    bare = {k: v for k, v in spans.items() if k in ("engine/emit", "engine/schedule")}
    part = host_trace.label_gaps(gaps, bare, off)
    assert part["by_name"][host_trace.UNATTRIBUTED] == pytest.approx(1.95e-3)
    assert host_trace.attributed_percent(part) == pytest.approx(100.0 * 0.4 / 2.35)

    lines = []

    class Out:
        def write(self, text):
            lines.append(text)

    host_trace.report(found, labelled, out=Out())
    text = "".join(lines)
    assert "host-device offset" in text and "engine/idle" in text and "gap of 2.350 ms" in text


def test_offset_needs_a_launch_on_both_clocks():
    loaded, _ = _timeline()
    no_enqueue = dict(loaded, host={k: v for k, v in loaded["host"].items()
                                    if k != host_trace.ENQUEUE})
    assert host_trace.offset(no_enqueue) is None
    no_wait = dict(loaded, host={k: v for k, v in loaded["host"].items()
                                 if k != "engine/device_wait"})
    assert host_trace.offset(no_wait) is None
    assert host_trace.load(HERE / "no_such.xplane.pb") is None
    assert host_trace.newest_xplane(HERE / "no_such_dir") is None


# --------------------------------------------------------------- the readers


@pytest.mark.parametrize("appended", [
    [],
    # ``BENCHMARK.json`` grows by appending: a later PR's metric after them,
    # and its cell in their lists, are not what this test judges
    [{"name": "later_metric", "unit": "%", "better": "higher", "source": "device_trace",
      "layer": "Kernels", "moves": "itl_p95_ms", "workloads": ["later_cell"]}],
])
def test_new_metrics_are_declared_with_their_cells_and_readers(appended):
    per_layer = [dict(m, workloads=m["workloads"] + ["later_cell"]) if appended else m
                 for m in BENCH["per_layer"]] + appended
    declared = {m["name"]: m for m in per_layer}
    # the six are there by name, in the order PR 24 gave them, wherever they stand
    assert [m["name"] for m in per_layer if m["name"] in NEW_METRICS] == list(NEW_METRICS)
    for name in NEW_METRICS:
        assert declared[name]["workloads"] and hasattr(harness.load_reader(name), "read")
    assert "train_1_3b_1chip" in declared["flash_attention_roofline"]["workloads"]
    assert declared["flash_attention_roofline"]["unit"] == "%"


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_returns_none_without_its_inputs(name, monkeypatch):
    monkeypatch.setattr(host_trace, "newest_xplane", lambda root=None: None)
    read = harness.load_reader(name).read
    assert read({}) is None
    # a context of the other kind of cell, and of a program that records no
    # ``device_wait`` (the parent commit's spans)
    old_spans = [(0, "engine", "tick", 1.0, 1.07, {"tick": 3}),
                 (1, "engine", "decode_step", 1.0, 1.069, {"tick": 3})]
    assert read({"spans": old_spans, "t0": 0.0, "t_end": 9.0, "trace": {"events": {0: []}},
                 "chips": 1}) is None


def test_tick_readers_split_a_tick_into_host_and_wait():
    spans = []
    for i, (tick_ms, wait_ms) in enumerate([(70.0, 66.0), (72.0, 66.5), (160.0, 150.0)]):
        t = 10.0 + i
        spans += [(0, "engine", "tick", t, t + tick_ms * 1e-3, {"tick": i}),
                  (0, "engine", "decode_step", t + 1e-3, t + tick_ms * 1e-3 - 1e-3, {"tick": i}),
                  (0, "engine", "device_wait", t + 2e-3, t + 2e-3 + wait_ms * 1e-3, {"tick": i})]
    # a prefill-only tick has no decode_step, one before the window is outside
    spans += [(0, "engine", "tick", 20.0, 20.004, {"tick": 9, "phase": "prefill_only"}),
              (0, "engine", "tick", 1.0, 1.5, {"tick": 0}),
              (0, "req-1", "queue", 10.0, 10.1, None)]
    ctx = {"spans": spans, "t0": 5.0, "t_end": 30.0}
    host = harness.load_reader("engine_host_ms_per_tick").read(ctx)
    wait = harness.load_reader("engine_device_wait_ms_p50").read(ctx)
    tick = harness.load_reader("engine_tick_ms_p50").read(ctx)
    assert host == pytest.approx(5.5) and wait == pytest.approx(66.5)
    assert tick == pytest.approx(72.0) and host + wait == pytest.approx(tick)


def test_tick_readers_on_a_real_engine_run():
    """The ring of a tiny engine run, read as the benchmark reads it: host +
    wait is the tick, to the span overhead."""
    import jax

    from benchmark import weights

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
    engine = drv.build_engine(cell, params, ROOT / ".bench_out" / "tiny_host_trace")
    handles = [engine.submit([5 + i, 6, 7, 8], max_new_tokens=6, seed=i) for i in range(3)]
    t0 = engine.now()
    engine.run_until_idle()
    assert all(h.status == "done" for h in handles)
    ctx = {"spans": engine.tracer.spans(), "t0": t0, "t_end": engine.now()}
    host = harness.load_reader("engine_host_ms_per_tick").read(ctx)
    wait = harness.load_reader("engine_device_wait_ms_p50").read(ctx)
    tick = harness.load_reader("engine_tick_ms_p50").read(ctx)
    assert host > 0 and wait > 0
    ticks = harness.load_reader("engine_host_ms_per_tick").decode_ticks(ctx)
    assert len(ticks) >= 6 and all(0 < w < t for t, w in ticks)
    assert sorted((t - w) * 1e3 for t, w in ticks)[len(ticks) // 2] == pytest.approx(host, rel=0.5)
    assert tick > wait


def test_flash_roofline_counts_the_causal_half_per_call():
    reader = harness.load_reader("flash_attention_roofline")
    peak = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    # ISSUE 24's sizes: 4 x 16 x 1024^2 x 128 -> 87 / 131 / 174 us a call
    least = {}
    for kernel in reader.KERNELS:
        ops, byts = reader.call_ops_bytes(kernel, 4, 16, 1024, 128)
        least[kernel] = max(ops / peak["flops_per_s"], byts / peak["bytes_per_s"])
    assert least["flash_fwd"] == pytest.approx(87.2e-6, rel=0.01)
    assert least["flash_bwd_dq"] == pytest.approx(130.8e-6, rel=0.01)
    assert least["flash_bwd_dkv"] == pytest.approx(174.4e-6, rel=0.01)
    # the names as the chip's trace has them (my chip run, PR 24): JAX wraps
    # the kernel's name in the transformations that made the call
    events = [
        ("%jvp_flash_fwd_.1 = (bf16[4,16,1024,128]{3,2,1,0}, f32[4,16,1024,1]) custom-call(...)", 0.0, 400e-6),
        ("%checkpoint_jvp_flash_fwd_.7 = (bf16[4,16,1024,128]) custom-call(...)", 1e-3, 1e-3 + 400e-6),
        ("%transpose_jvp_flash_bwd_dq__.1 = bf16[4,16,1024,128] custom-call(...)", 2e-3, 2e-3 + 300e-6),
        ("%transpose_jvp_flash_bwd_dkv__.1 = (bf16[4,16,1024,128]) custom-call(...)", 3e-3, 3e-3 + 480e-6),
        ("%fusion.9 = bf16[4,1024,2048] fusion(%jvp_flash_fwd_.1)", 4e-3, 5e-3),
        ("%paged_attention.3 = bf16[16,12,128] custom-call(...)", 5e-3, 6e-3),
    ]
    ctx = {"trace": {"events": {0: events}}, "rows_per_micro": 4, "seq_len": 1024,
           "model": {"n_heads": 16, "head_dim": 128}, "peak": peak}
    want = 100.0 * (2 * least["flash_fwd"] + least["flash_bwd_dq"] + least["flash_bwd_dkv"]) \
        / (400e-6 + 400e-6 + 300e-6 + 480e-6)
    assert reader.read(ctx) == pytest.approx(want)
    assert 5.0 < want < 100.0
    # a trace without the names (the parent commit's ``attn.38``) reads nothing
    unnamed = [("%attn.38 = (bf16[4,16,1024,128]) custom-call(...)", 0.0, 400e-6)]
    assert reader.read(dict(ctx, trace={"events": {0: unnamed}})) is None


def test_flash_kernels_carry_their_names():
    """The jaxpr of a flash forward and backward holds three ``pallas_call``s
    named ``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``: the names the
    roofline reader looks for in a trace."""
    import jax
    import jax.numpy as jnp

    from zero_transformer_tpu.ops.pallas import flash

    def names(jaxpr, out):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(eqn.params["name"])
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) else [value]:
                    inner = getattr(sub, "jaxpr", sub)
                    inner = getattr(inner, "jaxpr", inner)
                    if hasattr(inner, "eqns"):
                        names(inner, out)
        return out

    q = jnp.ones((1, 128, 2, 128), jnp.float32)

    def loss(q, k, v):
        return flash.flash_attention(q, k, v, causal=True, alibi=True, interpret=True).sum()

    forward = names(jax.make_jaxpr(loss)(q, q, q).jaxpr, [])
    both = names(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q).jaxpr, [])
    assert forward == ["flash_fwd"]
    assert sorted(both) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    reader = harness.load_reader("flash_attention_roofline")
    assert set(both) == set(reader.KERNELS)

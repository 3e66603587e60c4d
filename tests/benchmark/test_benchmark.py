"""The benchmark's own tests: CPU, tiny model, one file.

What the chip measures is never asserted here; what is asserted is that the
files resolve, the generator and the arithmetic do what they say, the trace
reduction reads a recorded chip trace as worked out by hand, the harness
refuses to run without a TPU, and ``correct`` comes out false for the control
and for each planted fault.
"""
import json
import math
import os
import re
import subprocess
import sys
import zlib
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import arith, harness, trace, traffic  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

TINY_MODEL = {
    "d_model": 128, "n_layers": 2, "n_heads": 4, "head_dim": 32, "d_ff": 512,
    "vocab_size": 256, "max_seq_len": 128, "position": "alibi", "norm": "layernorm",
    "activation": "gelu", "tie_embeddings": True, "param_dtype": "float32",
    "compute_dtype": "bfloat16",
}
TINY_CONFIG = {"name": "tiny", "reference": "benchmark/reference/gpt_alibi.py",
               "model": TINY_MODEL}


def test_benchmark_json_names_units_and_files_resolve():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for entry in BENCH["configs"] + BENCH["workloads"] + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for cell in BENCH["workloads"]:
        loaded = harness.load_cell(cell["name"])  # config + traffic files by name
        assert hasattr(harness.load_driver(loaded["traffic"]["kind"]), "calibrate")
        ref = harness.load_reference(loaded["config"])  # the family, by the config's key
        model = loaded["config"]["model"]
        # held and active parameters, both stated and both the family's: what
        # memory holds is the tree, what the FLOP arithmetic multiplies by is
        # what a token passes through (shared layers and sparse experts make
        # the two differ, either way)
        assert loaded["config"]["active_parameters"] == ref.active_params(model)
        assert loaded["config"]["parameters"] == sum(
            math.prod(shape) for shape, _ in ref.leaf_table(model).values())
        assert set(ref.leaf_table(model)) and ref.attention_flops_per_position(model) > 0
        optimizer = loaded["traffic"].get("overrides", {}).get("optimizer.optimizer")
        if optimizer:
            assert hasattr(harness.load_optimizer(optimizer), "first_gradient_norms")
        assert any(m["name"] != "setup_s" for m in loaded["end_to_end"])
        assert loaded["per_layer"]
        mine = {m["name"] for m in loaded["end_to_end"]}
        for m in loaded["per_layer"]:
            assert hasattr(harness.load_reader(m["name"]), "read")
            assert m["moves"] in mine, (cell["name"], m["name"])
        assert any("mfu" in m["name"] for m in loaded["per_layer"])
    peaks = json.loads((ROOT / "benchmark" / "peaks.json").read_text())
    assert all(p["source"] and p["flops_per_s"] and p["bytes_per_s"] for p in peaks.values())
    with pytest.raises(SystemExit):
        arith.load_peak("TPU v99", ROOT / "benchmark" / "peaks.json")


# a head width that is not d_model / n_heads: 3 heads of 48 on a stream of 64
ODD_HEADS = {"name": "odd_heads", "reference": "benchmark/reference/gpt_alibi.py",
             "model": dict(TINY_MODEL, d_model=64, n_heads=3, head_dim=48, d_ff=256)}


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]] + ["odd_heads"])
def test_configuration_builds_the_programs_tree(name):
    """What a run relies on, at full size and without memory: the keys of
    ``model`` that are ``ModelConfig`` fields build one, and the abstract
    parameter tree of that model is the family's ``leaf_table``."""
    entry = next((c for c in BENCH["configs"] if c["name"] == name), None)
    config = json.loads((ROOT / entry["file"]).read_text()) if entry else ODD_HEADS
    harness.check_configuration(config)
    assert harness.model_config(config).head_width == config["model"]["head_dim"]


@pytest.mark.parametrize("leaf", ["blocks/attn/out/kernel", "ln_f/scale"])
def test_configuration_whose_table_and_tree_differ_is_refused(monkeypatch, leaf):
    ref = harness.load_reference(ODD_HEADS)
    table = ref.leaf_table(ODD_HEADS["model"])
    shape, init = table[leaf]
    altered = dict(table, **{leaf: (shape[:-1] + (shape[-1] + 1,), init)})
    monkeypatch.setattr(ref, "leaf_table", lambda model: altered)
    with pytest.raises(SystemExit, match=leaf):
        harness.check_configuration(ODD_HEADS)
    # ... and a leaf the program does not have
    monkeypatch.setattr(ref, "leaf_table", lambda model: dict(table, extra=((3,), "ones")))
    with pytest.raises(SystemExit, match="extra"):
        harness.check_configuration(ODD_HEADS)


def test_traffic_same_seed_same_requests_other_seed_other_order():
    spec = json.loads((ROOT / "benchmark/traffic/alpaca_open_poisson.json").read_text())
    a = traffic.open_loop_requests(spec, 2**31 + 7, 20.0, 50304)
    b = traffic.open_loop_requests(spec, 2**31 + 7, 20.0, 50304)
    c = traffic.open_loop_requests(spec, 8, 20.0, 50304)
    assert a == b and a != c
    assert len(a) == round(spec["rate_per_s"] * 20.0)
    assert all(0.0 <= r.due_s < 20.0 for r in a)
    assert [r.due_s for r in a] == sorted(r.due_s for r in a)
    sizes = lambda rs: sorted((len(r.prompt), r.max_new_tokens) for r in rs)  # noqa: E731
    assert sizes(a) == sizes(c)  # the seed changes the ids, never the work
    assert [r.due_s for r in a] == [r.due_s for r in c]
    lo, hi = spec["prompt_len"]["min"], spec["prompt_len"]["max"]
    assert all(lo <= len(r.prompt) <= hi for r in a)
    # a longer window sees the same requests, and more of them
    longer = traffic.open_loop_requests(spec, 8, 40.0, 50304)
    assert [len(r.prompt) for r in longer[: len(c)]] == [len(r.prompt) for r in c]
    many = traffic.open_loop_requests(dict(spec, rate_per_s=400.0), 8, 40.0, 50304)
    mean = lambda xs: sum(xs) / len(xs)  # noqa: E731
    assert mean([len(r.prompt) for r in many]) == pytest.approx(spec["prompt_len"]["mean"], rel=0.03)
    assert mean([r.max_new_tokens for r in many]) == pytest.approx(spec["output_len"]["mean"], rel=0.03)
    x = traffic.train_batch(3, 0, 2, 2, 16, 256)
    assert (x == traffic.train_batch(3, 0, 2, 2, 16, 256)).all()
    assert (x != traffic.train_batch(3, 1, 2, 2, 16, 256)).any()
    assert len({tuple(r) for r in x.reshape(-1, 16)}) == 4


def test_a_stall_in_the_window_moves_every_end_to_end_metric():
    # 100 requests, a token every 10 ms, first token 50 ms after due
    due = [0.1 * i for i in range(100)]
    times = [[d + 0.05 + 0.01 * k for k in range(20)] for d in due]
    ttft = arith.ttft_ms(due, [t[0] for t in times])
    assert arith.percentile(ttft, 95) == pytest.approx(50.0)
    assert arith.percentile(arith.gaps_ms(times), 95) == pytest.approx(10.0)
    n = arith.tokens_in_window(times, 0.0, 10.0)
    assert arith.rate(n, 10.0) == pytest.approx(198.0, abs=0.25)  # the last two requests run past the close
    # a 1 s stall at t = 5 s: everything due or in flight then comes 1 s late
    stalled = [[t + 1.0 if t >= 5.0 else t for t in ts] for ts in times]
    assert arith.percentile(arith.ttft_ms(due, [t[0] for t in stalled]), 95) > 1000.0
    assert max(arith.gaps_ms(stalled)) == pytest.approx(1010.0)
    assert arith.percentile(arith.gaps_ms(stalled), 99.95) > 500.0
    assert arith.tokens_in_window(stalled, 0.0, 10.0) < n
    # a failed request is the worst, and counts
    assert arith.percentile(arith.ttft_ms([0.0] * 10, [0.05] * 9 + [None]), 95) == float("inf")
    # whole steps: the window is first fetch to last sync, stall included
    steps = [(0.0, 1.0), (1.0, 2.0), (2.5, 3.5)]
    t0, t1 = arith.whole_step_window(steps)
    assert arith.rate(3 * 1000, t1 - t0) == pytest.approx(3000 / 3.5)
    assert arith.spread([1.0, 1.01, 1.02, 1.03, 1.04, 1.05]) == pytest.approx(0.0341, abs=1e-3)


def test_interval_arithmetic_of_the_trace_reduction():
    assert trace.union_seconds([(0, 1), (0.5, 2), (3, 4)]) == pytest.approx(3.0)
    own = dict(trace.self_times([("while", 0, 10), ("a", 1, 3), ("b", 4, 5), ("c", 11, 12)]))
    assert own == {"while": 7.0, "a": 2.0, "b": 1.0, "c": 1.0}
    planes = {"devices": {0: [("a", 1.0, 2.0), ("b", 2.5, 3.0)]}, "extent": (0.0, 4.0)}
    red = trace.reduce(planes, 1)
    assert red["busy_s"] == pytest.approx(1.5) and red["window_s"] == pytest.approx(4.0)
    with pytest.raises(SystemExit):
        trace.reduce({"devices": {}, "extent": (0.0, 1.0)}, 1)


def test_recorded_chip_trace_gives_the_idle_share_worked_out_by_hand():
    path = Path(__file__).parent / "small_trace.xplane.pb"
    by_hand = json.loads((Path(__file__).parent / "small_trace.by_hand.json").read_text())
    red = trace.reduce(trace.read_planes(path), 1)
    assert red["busy_s"] == pytest.approx(by_hand["busy_s"], rel=1e-6)
    assert red["window_s"] == pytest.approx(by_hand["window_s"], rel=1e-6)
    idle = harness.load_reader("device_idle_share.train").read({"trace": red})
    assert idle == pytest.approx(by_hand["idle_share_percent"], rel=1e-6)
    assert 0.0 < idle < 100.0


def test_run_exits_nonzero_and_prints_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", BENCH["workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert not out.stdout.strip()
    assert "TPU" in out.stderr


# ------------------------------------------------- ``correct``: control, faults


def _tiny_train_cell():
    job = json.loads((ROOT / "benchmark/traffic/pretrain_ctx1k_64k.json").read_text())
    job["overrides"].update({
        "training.batch_size": 2, "training.gradient_accumulation_steps": 4,
        "training.train_context": 64, "model.loss_chunk": 16,
    })
    job["reference"]["rows_per_block"] = 2; job["reference"]["loss_rows_per_block"] = 2
    # tiny-size limits: three times what a sound run reads here (seed 5)
    job["limits"] = {"loss_rel_gap": 1e-4, "grad_leaf_gap": 3e-3, "change_leaf_gap": 5e-2}
    return {"name": "tiny_train", "chips": 1, "config": dict(TINY_CONFIG),
            "traffic": job, "end_to_end": [], "per_layer": []}


@pytest.fixture(scope="module")
def train_driver():
    import jax

    return harness.load_driver("train_job"), jax.devices()[:1]


def _break_step(how):
    def wrap(trainer):
        real = trainer.train_step

        def unchanged(state, batch, rng):
            new, metrics = real(state, batch, rng)
            return new.replace(params=state.params), metrics

        def half(state, batch, rng):
            return real(state, batch[: batch.shape[0] // 2], rng)

        trainer.train_step = {"state_unchanged": unchanged, "half_batch": half}[how]
        return trainer

    return wrap


@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_batch"])
def test_train_run_is_correct_only_with_the_timed_path_sound(train_driver, monkeypatch, fault):
    drv, devices = train_driver
    if fault:
        build = drv.build_trainer
        monkeypatch.setattr(
            drv, "build_trainer", lambda *a, **k: _break_step(fault)(build(*a, **k)))
    res = drv.run(_tiny_train_cell(), devices, seed=5, seconds=0.2, trace=False)
    assert res["correct"] is (fault is None), res["compared"]
    assert res["metrics"]["train_tokens_per_s_chip"]["value"] > 0
    assert "compared" in res


def test_train_control_in_lower_precision_comes_out_not_correct(train_driver):
    drv, _ = train_driver
    cell = _tiny_train_cell()
    reference = drv.run_reference(cell, 5, "f32")
    control = drv.run_reference(cell, 5, "fp8")
    compared = drv.compare(control, reference, cell["traffic"]["limits"])
    assert not harness.decide(compared), compared
    same = drv.compare(reference, reference, cell["traffic"]["limits"])
    assert harness.decide(same)


def _tiny_serve_cell():
    mix = json.loads((ROOT / "benchmark/traffic/alpaca_open_poisson.json").read_text())
    mix["engine"].update({"n_slots": 4, "cache_len": 128, "prefill_chunk": 16, "page_size": 4})
    mix["prompt_len"] = {"mean": 24, "min": 4, "max": 64}
    mix["output_len"] = {"mean": 12, "min": 4, "max": 32}
    mix["warmup"] = [[64, 4], [20, 4], [5, 6]]
    mix["rate_per_s"] = 10.0
    mix["reference"]["pad_to"] = 32
    mix["reference"]["sample"] = 20
    mix["limits"] = {"served_logit_gap_max": 0.1}  # tiny size: sound reads 0.0, a flipped token 1.6
    return {"name": "tiny_serve", "chips": 1, "config": dict(TINY_CONFIG),
            "traffic": mix, "end_to_end": [], "per_layer": []}


def _alter(monkeypatch, which):
    """The served token altered where it is produced: every one, or (about) a
    tenth of them, the 3rd, 13th, 23rd ... the engine emits."""
    from zero_transformer_tpu.serving.engine import RequestHandle

    emit, count = RequestHandle._emit, [0]

    def altered(self, token, now):
        count[0] += 1
        hit = which == "token_altered" or count[0] % 10 == 3
        return emit(self, int(token) ^ 1 if hit else token, now)

    monkeypatch.setattr(RequestHandle, "_emit", altered)


@pytest.mark.parametrize("fault, limits", [
    (None, {"served_logit_gap_max": 0.1}),
    ("token_altered", {"served_logit_gap_max": 0.1}),
    # a mix may hold the 90th percentile over all judged tokens instead of (or
    # beside) the widest gap: sound reads 0.0, a tenth of the tokens altered 1.0 and more
    (None, {"served_logit_gap_p90": 0.1}),
    ("tenth_altered", {"served_logit_gap_p90": 0.1}),
])
def test_serve_run_is_correct_only_with_served_tokens_unaltered(monkeypatch, fault, limits):
    import jax

    if fault:
        _alter(monkeypatch, fault)
    # two layers at GPT-2's init all but copy the input token; four times the
    # spread makes the layers, and so the precision, decide the next token
    ref = harness.load_reference(TINY_CONFIG)
    table = ref.leaf_table
    monkeypatch.setattr(ref, "leaf_table", lambda model: {
        path: (shape, init if init == "ones" else 4.0 * init)
        for path, (shape, init) in table(model).items()})
    drv = harness.load_driver("serve_open_loop")
    cell = _tiny_serve_cell()
    cell["traffic"]["limits"] = limits
    res = drv.run(cell, jax.devices()[:1], seed=5, seconds=2.0, trace=False,
                  control_modes=("fp8", "whole_tree"))
    assert res["failed"] == 0 and res["attempted"] == 20
    assert res["correct"] is (fault is None), res["compared"]
    # only what the mix names is compared; every statistic is in the line
    assert {k for k in res["compared"] if k.startswith("served_logit_gap")} == set(limits)
    stats = res["served_logit_gap"]
    assert stats["tokens"] == res["compared"][next(iter(limits))]["tokens"] > 100
    assert 0.0 <= stats["mean"] <= stats["p90"] + stats["max"] and stats["p90"] <= stats["max"]
    # gpt_alibi's reference ran in blocks; the whole tree on the same served
    # tokens reads the same, to float32 rounding of logits of size 1
    assert res["control_whole_tree"]["max"] == pytest.approx(stats["max"], abs=1e-4)
    if fault is None:
        # the control: what fp8 puts first lies below the reference's best
        assert res["control_fp8"]["max"] > 3 * max(stats["max"], 1e-3)
        assert res["control_fp8"]["mean"] > 3 * max(stats["mean"], 1e-4)
    if fault == "tenth_altered":
        assert stats["not_first"] >= 0.1 and stats["p90"] > 3 * limits["served_logit_gap_p90"]


def test_serve_mfu_of_a_throughput_cell_is_serve_mfu():
    # two ticks of 10 ms in the window, one request of 3 prompt and 2 served
    # tokens (4 positions): 4 x (2 x 100 + 8 x 2.5) = 880 operations over 20 ms
    ctx = {"spans": [(0, "engine", "tick", 1.00, 1.01, None),
                     (1, "engine", "tick", 1.02, 1.03, None)],
           "records": [{"prompt": [1, 2, 3], "tokens": [4, 5], "token_times": [1.01, 1.03]}],
           "t0": 0.5, "t_end": 2.0, "active_params": 100, "attention_flops_per_position": 8,
           "peak": {"flops_per_s": 1e6}, "chips": 1}
    read = harness.load_reader("serve_mfu.throughput").read
    assert read(ctx) == harness.load_reader("serve_mfu").read(ctx)
    assert read(ctx) == pytest.approx(100.0 * 4 * arith.forward_flops(100, 8, 2.5) / (0.02 * 1e6))
    assert read({}) is None
    declared = next(m for m in BENCH["per_layer"] if m["name"] == "serve_mfu.throughput")
    assert declared["moves"] == "serve_tokens_per_s"
    throughput = next(m for m in BENCH["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert set(declared["workloads"]) <= set(throughput["workloads"])


def test_a_mix_that_names_no_gap_statistic_is_refused():
    drv = harness.load_driver("serve_open_loop")
    assert drv.gap_statistics([]) == {"tokens": 0, "max": None, "p90": None, "mean": None,
                                      "not_first": None}
    got = drv.gap_statistics([0.0] * 17 + [0.5, 1.0, 2.0])
    assert got["tokens"] == 20 and got["max"] == 2.0 and got["not_first"] == 0.15
    assert got["mean"] == pytest.approx(0.175) and got["p90"] == pytest.approx(0.55)
    assert set(drv.GAP_STATISTICS) <= set(got)


# ------------------------------------- the reference in blocks (ISSUE 30, A.4)


def test_leaf_maker_gives_builds_leaves_bit_for_bit_a_layer_at_a_time():
    import jax
    import numpy as np

    from benchmark import weights

    ref = harness.load_reference(TINY_CONFIG)
    table = dict(ref.leaf_table(TINY_MODEL), odd=((5, 3, 7), 0.3))
    key = weights.seed_key(2**31 + 11, "weights")
    whole = weights.flatten(weights.build(table, key))
    make = weights.leaf_maker(table, key)
    assert all(np.array_equal(leaf, whole[path]) for path, leaf in make(tuple(table)).items())
    stacked = tuple(p for p, (shape, _) in table.items() if shape[0] in (2, 5) and len(shape) > 1)
    assert len(stacked) == 9
    for l in range(2):
        got = make(stacked, l)
        assert set(got) == set(stacked)
        assert all(np.array_equal(leaf, whole[path][l]) for path, leaf in got.items()), l
    assert np.array_equal(make(("odd",), 4)["odd"], whole["odd"][4])
    # past 2**32 values the counter carries into its high word: the last
    # layer of a leaf that could never be made whole, against jax's own bits
    shape, l = (70000, 70001), 69999
    k = jax.random.key(7)
    mine = weights.make_leaf(k, "a", shape, 1.0, layer=l)
    flat = np.arange(l * shape[1], (l + 1) * shape[1], dtype=np.uint64)
    k1, k2 = jax.random.key_data(jax.random.fold_in(k, zlib.crc32(b"a") & 0x7FFFFFFF))
    b1, b2 = weights.threefry2x32_p.bind(k1, k2, jax.numpy.asarray(flat >> 32, "uint32"),
                                         jax.numpy.asarray(flat & 0xFFFFFFFF, "uint32"))
    by_hand = ((np.asarray(b1 ^ b2) >> 9) | 0x3F800000).view(np.float32) - np.float32(1.0)
    assert mine.shape == (70001,)
    uniform = np.float32(2.0) * by_hand - np.float32(1.0)
    assert np.allclose(np.asarray(jax.scipy.special.erf(mine / np.sqrt(2))), uniform, atol=1e-5)


@pytest.mark.parametrize("mode", ["f32", "bf16"])
def test_gpt_alibi_in_blocks_is_its_whole_tree_and_asks_for_one_layer_at_a_time(mode):
    import jax
    import numpy as np

    from benchmark import weights

    ref = harness.load_reference(TINY_CONFIG)
    table = ref.leaf_table(TINY_MODEL)
    key = weights.seed_key(9, "weights")
    tokens = jax.numpy.asarray(
        np.random.default_rng(0).integers(0, TINY_MODEL["vocab_size"], size=(2, 48)), "int32")
    asked = []
    make = weights.leaf_maker(table, key)

    def spy(paths, layer=None):
        asked.append((tuple(paths), layer))
        return make(paths, layer)

    with jax.default_matmul_precision("highest"):
        whole = ref.logits(weights.build(table, key), tokens, TINY_MODEL, mode)
        blocks = ref.logits_by_blocks(spy, tokens, TINY_MODEL, mode)
    # the same float32 arithmetic in the same order, the layers under a scan
    # or one program each: logits of size 1 (std 0.3) agree to a few ulps
    assert np.abs(np.asarray(whole)).max() > 0.5
    assert np.allclose(np.asarray(blocks), np.asarray(whole), rtol=0, atol=2e-6)
    # no request spans two layers: stacked leaves are only ever asked for by
    # layer, each layer's together and once, in order; what is asked for
    # whole is the table (for the lookup and, tied, with the final norm for
    # the head)
    stacked = {p for p in table if p.startswith("blocks/")}
    assert all((layer is not None) == bool(set(paths) & stacked) for paths, layer in asked)
    assert [(set(paths), layer) for paths, layer in asked if layer is not None] == \
        [(stacked, l) for l in range(TINY_MODEL["n_layers"])]
    assert [paths for paths, layer in asked if layer is None] == \
        [("wte/embedding",), ("ln_f/scale", "wte/embedding")]

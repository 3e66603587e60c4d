"""The benchmark's own tests: CPU, tiny model, one file.

What the chip measures is never asserted here; what is asserted is that the
files resolve, the generator and the arithmetic do what they say, the trace
reduction reads a recorded chip trace as worked out by hand, the harness
refuses to run without a TPU, and ``correct`` comes out false for the control
and for each planted fault.
"""
import json
import os
import re
import subprocess
import sys
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
        assert ref.active_params(model) == loaded["config"]["parameters"]
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
    for cfg in BENCH["configs"]:
        model = json.loads((ROOT / cfg["file"]).read_text())["model"]
        assert model["d_model"] == model["n_heads"] * model["head_dim"]
    peaks = json.loads((ROOT / "benchmark" / "peaks.json").read_text())
    assert all(p["source"] and p["flops_per_s"] and p["bytes_per_s"] for p in peaks.values())
    with pytest.raises(SystemExit):
        arith.load_peak("TPU v99", ROOT / "benchmark" / "peaks.json")


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


@pytest.mark.parametrize("fault", [None, "token_altered"])
def test_serve_run_is_correct_only_with_served_tokens_unaltered(monkeypatch, fault):
    import jax

    from zero_transformer_tpu.serving.engine import RequestHandle

    if fault:
        emit = RequestHandle._emit
        monkeypatch.setattr(
            RequestHandle, "_emit", lambda self, token, now: emit(self, int(token) ^ 1, now))
    # two layers at GPT-2's init all but copy the input token; four times the
    # spread makes the layers, and so the precision, decide the next token
    ref = harness.load_reference(TINY_CONFIG)
    table = ref.leaf_table
    monkeypatch.setattr(ref, "leaf_table", lambda model: {
        path: (shape, init if init == "ones" else 4.0 * init)
        for path, (shape, init) in table(model).items()})
    drv = harness.load_driver("serve_open_loop")
    res = drv.run(_tiny_serve_cell(), jax.devices()[:1], seed=5, seconds=2.0, trace=False,
                  control_modes=("fp8",))
    assert res["failed"] == 0 and res["attempted"] == 20
    assert res["correct"] is (fault is None), res["compared"]
    if fault is None:
        # the control: what fp8 puts first lies below the reference's best
        assert res["control_fp8"] > 3 * max(res["compared"]["served_logit_gap_max"]["value"], 1e-3)

"""The granite-4.0-h-micro configuration's benchmark files, on the CPU: the
configuration states every published key and cuts nothing, the family's leaf
table is the program's tree at full size (abstract: nothing is allocated),
the operations and bytes the new readers divide by are right by hand counts,
the cell is as ISSUE 33 names it, the block-wise reference is its whole-tree
twin, and each new reader reads what the program writes, nothing where it
writes nothing, and 100% where the events take exactly the least time."""
import json
import math
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import arith, harness, host_trace, ssm_ticks, traffic, weights  # noqa: E402

CELL = "serve_granite4h_micro_chat"
TINY = {"d_model": 64, "n_layers": 8, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
        "d_ff": 128, "vocab_size": 256, "max_seq_len": 64,
        "layer_pattern": ["mamba", "mamba", "attention", "mamba"], "mamba_heads": 4,
        "mamba_head_dim": 32, "mamba_state": 16, "mamba_conv": 4, "attention_scale": 0.0625,
        "embedding_multiplier": 12.0, "residual_multiplier": 0.22, "logits_scaling": 8.0,
        "scan_layers": True, "norm_eps": 1e-5, "param_dtype": "float32"}
PEAK = {"flops_per_s": 197e12, "bytes_per_s": 819e9}

@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(CELL)


@pytest.fixture(scope="module")
def ref():
    return ssm_ticks.family()


def test_configuration_states_every_published_key_and_cuts_nothing(cell):
    config, model = cell["config"], cell["config"]["model"]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "granite_4_0_h_micro")
    assert entry["reduced"] == config["reduced"] == []
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json"
    published = {"hidden_size": 2048, "intermediate_size": 8192, "shared_intermediate_size": 8192,
                 "num_hidden_layers": 40, "num_attention_heads": 32, "num_key_value_heads": 8,
                 "vocab_size": 100352, "mamba_n_heads": 64, "mamba_d_head": 64,
                 "mamba_d_state": 128, "mamba_n_groups": 1, "mamba_d_conv": 4,
                 "mamba_expand": 2, "mamba_chunk_size": 256, "mamba_conv_bias": True,
                 "mamba_proj_bias": False, "attention_bias": False,
                 "attention_multiplier": 0.015625, "embedding_multiplier": 12,
                 "residual_multiplier": 0.22, "logits_scaling": 8, "rms_norm_eps": 1e-05,
                 "position_embedding_type": "nope", "num_local_experts": 0,
                 "num_experts_per_tok": 0, "tie_word_embeddings": True,
                 "max_position_embeddings": 131072, "model_type": "granitemoehybrid"}
    assert {k: config[k] for k in published} == published
    assert (config["hidden_act"], config["normalization_function"], config["rope_scaling"],
            config["rope_theta"]) == ("silu", "rmsnorm", None, 10000)
    kinds = config["layer_types"]
    assert len(kinds) == 40 and [i for i, k in enumerate(kinds) if k == "attention"] == [5, 15, 25, 35]
    # ... and the same sizes under the program's keys: nothing cut
    assert (model["d_model"], model["n_layers"], model["n_heads"], model["n_kv_heads"],
            model["head_dim"], model["d_ff"], model["vocab_size"]) == \
        (2048, 40, 32, 8, 64, 8192, 100352)
    assert model["layer_pattern"] * 4 == kinds
    assert (model["mamba_heads"], model["mamba_head_dim"], model["mamba_state"],
            model["mamba_conv"], model["mamba_chunk"]) == (64, 64, 128, 4, 256)
    assert model["mamba_heads"] * model["mamba_head_dim"] == 2 * model["d_model"]
    assert (model["position"], model["attention_scale"], model["embedding_multiplier"],
            model["residual_multiplier"], model["logits_scaling"]) == ("none", 1 / 64, 12.0, 0.22, 8.0)
    assert model["max_seq_len"] == cell["traffic"]["engine"]["cache_len"] == 512
    assert {"state_dtypes", "init"} <= set(config["assumed"])
    assert all(isinstance(v, str) for v in config["assumed"].values())


def test_leaf_table_is_the_programs_tree_at_full_size(cell, ref):
    config, model = cell["config"], cell["config"]["model"]
    harness.check_configuration(config)  # the abstract tree: nothing is allocated
    table = ref.leaf_table(model)
    held = sum(math.prod(s) for s, _ in table.values())
    cfg = harness.model_config(config)
    assert held == cfg.num_params == config["parameters"] == 3_191_396_096
    # scanned over the four periods: block j of every period one stacked leaf
    layers = ref.layers(model)
    assert len(layers) == 40 and layers[5] == ("periods/block_5", "attention", 0)
    assert layers[39] == ("periods/block_9", "mamba", 3)
    assert table["periods/block_0/mamba/in_proj/kernel"][0] == (4, 2048, 8512)
    assert table["periods/block_5/attn/key/kernel"][0] == (4, 2048, 512)
    assert table["periods/block_9/mamba/conv_kernel"] == ((4, 4, 4352), 0.5)
    assert table["periods/block_1/mamba/A_log"] == ((4, 64), 1.0)
    # the tied table small and the residual projections undivided: at the
    # usual 0.02 and 0.02 / sqrt(80) the tied head reads the fed token back
    # and no gap statistic can tell one arithmetic from another (leaf_table)
    assert table["wte/embedding"] == ((100352, 2048), 0.004)
    assert table["periods/block_1/mamba/out_proj/kernel"][1] == 0.02
    assert "lm_head/kernel" not in table  # tied
    # what a token is multiplied by: every matrix once, the table as the head
    mamba = 2048 * 8512 + 4096 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    mlp = 3 * 2048 * 8192
    active = 36 * (mamba + mlp) + 4 * (attn + mlp) + 100352 * 2048
    assert ref.active_params(model) == config["active_parameters"] == active == 3_190_292_480
    assert cfg.params_per_token - active == 36 * (4352 * 5 + 192 + 4096) + 40 * 4096 + 2048
    assert ref.attention_flops_per_position(model) == 4 * 4 * 32 * 64
    from zero_transformer_tpu.analysis.memory import kv_bytes_per_token, state_bytes_per_slot

    assert kv_bytes_per_token(cfg) == 4 * 2 * 512 * 2 == 8192
    assert state_bytes_per_slot(cfg) == config["state_bytes_per_slot"] == \
        ref.state_bytes_per_slot(model) == 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2) == 76_437_504


def test_operations_and_bytes_against_hand_counts(ref, cell):
    # a row's state: 4 heads x 32 x 16 float32, in and out; x, D x, y (128
    # each), B and C (16 each), the decay (4); five operations a state value
    state = 4 * 32 * 16
    assert ref.state_update_ops_bytes(TINY, rows=3) == (
        5.0 * 3 * state, 3 * (2 * state + 3 * 128 + 2 * 16 + 4) * 4.0)
    mamba = 64 * (128 + 160 + 4) + 128 * 64
    attn = 2 * 64 * 64 + 2 * 64 * 32
    fixed = 6 * mamba + 2 * attn + 8 * 3 * 64 * 128 + 64 * 256
    assert ref.active_params(TINY) == fixed
    assert ref.decode_read_bytes(TINY, rows=0, live_positions=0) == fixed * 2.0
    slot = 6 * (state * 4 + 3 * 160 * 2)
    assert ref.state_bytes_per_slot(TINY) == slot
    assert ref.decode_read_bytes(TINY, rows=3, live_positions=10) == \
        fixed * 2.0 + 2 * 3 * slot + 2 * 2 * 2 * 16 * 2 * 10
    # at the published size a call moves 2 x 2.1 MB a decoding row
    ops, byts = ref.state_update_ops_bytes(cell["config"]["model"], rows=32)
    assert byts == 32 * (2 * 2_097_152 + (3 * 4096 + 2 * 128 + 64) * 4)
    assert arith.roofline_seconds(ops, byts, PEAK)[1] == "bandwidth"


def test_cell_and_traffic_are_as_the_issue_names_them(cell):
    mix = cell["traffic"]
    assert cell["chips"] == 1 and mix["kind"] == "serve_open_loop"
    chat = json.loads((ROOT / "benchmark/traffic/alpaca_open_poisson.json").read_text())
    assert mix["prompt_len"] == chat["prompt_len"] == {"mean": 19.31, "min": 4, "max": 1024}
    assert mix["output_len"] == chat["output_len"] == {"mean": 58.45, "min": 4, "max": 1024}
    assert mix["engine"] == {"n_slots": 32, "cache_len": 512, "max_queue": 64,
                             "prefill_chunk": 64, "prefix_cache_chunks": 256, "page_size": 16,
                             "page_pool_tokens": 16384, "draft_k": 0, "trace_capacity": 131072}
    assert mix["params_dtype"] == "bfloat16" and mix["sampling"]["greedy"]
    assert (mix["drain_seconds"], mix["trace_seconds"], mix["trace_at_fraction"]) == (60, 3.0, 0.4)
    assert mix["reference"] == {"sample": 8, "pad_to": 256, "precision": "f32"}
    assert mix["warmup"] == chat["warmup"]
    assert set(mix["limits"]) - {"set_from"} <= {"served_logit_gap_max", "served_logit_gap_p90"}
    # the same one trace as the two chat cells: a higher rate sees the same
    # requests, and more of them
    reqs = traffic.open_loop_requests(mix, 2**31 + 9, 51.0, 100352)
    base = traffic.open_loop_requests(chat, 2**31 + 9, 51.0, 100352)
    n = min(len(reqs), len(base))
    assert [(len(r.prompt), r.max_new_tokens) for r in reqs[:n]] == \
        [(len(r.prompt), r.max_new_tokens) for r in base[:n]]
    assert max(len(r.prompt) + r.max_new_tokens for r in reqs) <= mix["engine"]["cache_len"]
    assert len(reqs) == round(mix["rate_per_s"] * 51)
    names = {m["name"] for m in cell["per_layer"]}
    assert {"ssm_state_update_roofline", "ssm_decode_bandwidth_share", "state_rows_in_use_p50",
            "serve_mfu", "engine_tick_ms_p50", "engine_host_ms_per_tick",
            "engine_device_wait_ms_p50", "queue_wait_ms_p95", "ttft_p95_ms",
            "decode_program_ms_p50", "prefill_program_ms_p50", "device_idle_share.serve",
            "device_idle_attributed.serve"} <= names
    # full-head bytes would read four times what 8 K/V heads hold
    assert "paged_attention_roofline" not in names
    assert {m["name"] for m in cell["end_to_end"]} >= {"itl_p95_ms", "setup_s"}
    # the cell's own entries, found by name: later PRs append configurations,
    # cells and metrics wherever the list ends
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["config"] == "granite_4_0_h_micro"
    new = {m["name"]: m for m in bench["per_layer"] if m["name"] in
           {"ssm_state_update_roofline", "ssm_decode_bandwidth_share", "state_rows_in_use_p50"}}
    assert len(new) == 3
    assert all(m["workloads"] == [CELL] for m in new.values())
    assert new["state_rows_in_use_p50"]["moves"] == "serve_tokens_per_s"


@pytest.mark.parametrize("mode", ["f32", "fp8", "state_bf16"])
def test_reference_in_blocks_is_its_whole_tree(ref, mode):
    """A layer's leaves asked for together (one period's slice of a stacked
    leaf) and never the tree: the same logits."""
    import jax
    import jax.numpy as jnp

    tiny = TINY
    table, key = ref.leaf_table(tiny), weights.seed_key(2**31 + 11, "weights")
    params = weights.build(table, key)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 40), 0, 256)
    asked = []
    maker = weights.leaf_maker(table, key)

    def make(paths, layer=None):
        asked.append((tuple(paths), layer))
        return maker(paths, layer)

    with jax.default_matmul_precision("highest"):
        whole = ref.logits(params, toks, tiny, mode)
        blocks = ref.logits_by_blocks(make, toks, tiny, mode)
    # (a kept value that lies on a bfloat16 rounding's edge falls either way
    # with the last float32 bit, so that control's two paths differ by more)
    assert float(jnp.max(jnp.abs(whole - blocks))) < (1e-5 if mode == "state_bf16" else 2e-6)
    assert [layer for _, layer in asked] == [None, 0, 0, 0, 0, 1, 1, 1, 1, None]
    assert all(p.startswith("periods/block_2/") for p in asked[3][0])
    if mode != "f32":  # a control is far from the reference, each in its way
        with jax.default_matmul_precision("highest"):
            off = float(jnp.max(jnp.abs(whole - ref.logits(params, toks, tiny, "f32"))))
        assert off > (1e-3 if mode == "fp8" else 1e-5)


# ---- the readers, on a context made by hand --------------------------------


def _ctx(step_attrs, records=None, **over):
    """Spans at 10.0, 10.1, ... inside a capture [9, 20], and requests
    decoding throughout: prompts of 10 tokens, one token out before 10.0."""
    spans = [(i, "engine", "decode_step", 10.0 + 0.1 * i, 10.05 + 0.1 * i, attrs)
             for i, attrs in enumerate(step_attrs)]
    if records is None:
        records = [{"prefill_done_at": 9.0, "finished_at": 30.0, "prompt": (1,) * 10,
                    "token_times": [9.5]} for _ in range(3)]
    ctx = {"model": TINY, "spans": spans, "records": records, "traced": (9.0, 20.0),
           "peak": PEAK, "t0": 9.0, "t_end": 20.0,
           "mix": {"engine": {"n_slots": 4}}}
    ctx.update(over)
    return ctx


STEP = {"active": 3, "state_rows": 3, "state_bytes": 2 * 3 * 52992, "state_rows_in_use": 3}


def test_decode_ticks_reads_the_engines_spans_and_the_records():
    ticks = ssm_ticks.decode_ticks(_ctx([STEP, {"active": 1}]))
    # three requests decode, each 10 + 1 cached positions
    assert ticks == [{"rows": 3, "live": 33}]
    assert ssm_ticks.decode_ticks(_ctx([{"active": 1}])) == []  # the parent's spans
    assert ssm_ticks.decode_ticks({}) == []
    assert ssm_ticks.decode_ticks(_ctx([STEP], model={"d_model": 64})) == []
    assert ssm_ticks.decode_ticks(_ctx([STEP]), within=(11.0, 12.0)) == []


def _with_capture(monkeypatch, programs):
    monkeypatch.setattr(host_trace, "load", lambda path=None: {"modules": {0: programs}})


def test_readers_read_100_where_the_events_take_the_least_time(monkeypatch, ref):
    ctx = _ctx([STEP])
    _with_capture(monkeypatch, [("jit__fused_step_impl(1)", 10.0, 10.01, 1)])
    call = arith.roofline_seconds(*ref.state_update_ops_bytes(TINY, rows=3), PEAK)[0]
    kernel = [(f"%ssm_state_update.{i} = (f32[6,4,4,32,16]{{4,3,2,1,0}}, f32[4,4,32]) custom-call(...)",
               10.001 + i * 1e-4, 10.001 + i * 1e-4 + call) for i in range(6)]
    other = [("%paged_attention.3 = bf16[4,1,4,16] custom-call(...)", 10.004, 10.005)]
    read = harness.load_reader("ssm_state_update_roofline").read
    assert read(dict(ctx, trace={"events": {0: kernel + other}})) == pytest.approx(100.0)
    slow = [(n, s, s + 2 * (e - s)) for n, s, e in kernel]
    assert read(dict(ctx, trace={"events": {0: slow}})) == pytest.approx(50.0)
    assert read(dict(ctx, trace={"events": {0: other}})) is None  # another kernel's name
    # the accepted full-head reader does not take this kernel's events
    import re

    paged = harness.load_reader("paged_attention_roofline").KERNEL
    assert not any(re.search(paged, n) for n, _, _ in kernel)

    # the whole decode program against the bytes it must move
    must = ref.decode_read_bytes(TINY, rows=3, live_positions=33) / PEAK["bytes_per_s"]
    read = harness.load_reader("ssm_decode_bandwidth_share").read
    _with_capture(monkeypatch, [("jit__fused_step_impl(1)", 10.0, 10.0 + must, 1)])
    assert read(dict(ctx, trace={"events": {0: kernel}})) == pytest.approx(100.0)
    _with_capture(monkeypatch, [("jit__fused_step_impl(1)", 10.0, 10.0 + 2 * must, 1),
                                ("jit__paged_chunk_prefill_impl(2)", 10.5, 10.6, 2)])
    assert read(dict(ctx, trace={"events": {0: kernel}})) == pytest.approx(50.0)

    # the slots in use (decoding or mid-prefill): the median of 3, 3 and 1
    # over 4 slots, whatever the rows that decode
    read = harness.load_reader("state_rows_in_use_p50").read
    assert read(_ctx([STEP, STEP, dict(STEP, state_rows_in_use=1)])) == pytest.approx(75.0)
    assert read(_ctx([STEP, STEP, dict(STEP, state_rows=1)])) == pytest.approx(75.0)
    assert read(_ctx([dict(STEP, state_rows_in_use=2)], t0=10.5)) is None  # before the window


@pytest.mark.parametrize("name", ["ssm_state_update_roofline", "ssm_decode_bandwidth_share",
                                  "state_rows_in_use_p50"])
def test_new_readers_give_nothing_and_raise_nothing_without_their_inputs(monkeypatch, name):
    """An untraced run, a run with no capture on disk, a program whose spans
    carry no state counters (the parent): None, never an error."""
    read = harness.load_reader(name).read
    monkeypatch.setattr(host_trace, "newest_xplane", lambda root=None: None)
    assert read({}) is None
    assert read(_ctx([{"active": 2}])) is None
    assert read(_ctx([{"active": 2, "loops": 1, "pages_in_use": 7}])) is None
    if name != "state_rows_in_use_p50":
        assert read(_ctx([STEP])) is None  # counters, and no capture to time them by
        assert read(_ctx([STEP], trace=None)) is None

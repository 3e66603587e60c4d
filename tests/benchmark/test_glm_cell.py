"""The GLM-4.7-Flash configuration's benchmark files, on the CPU: the
configuration states the published widths and cuts the depth alone, the
family's leaf table is the program's tree at full size (abstract: nothing is
allocated), the operations and bytes the new readers divide by are right by
hand counts, the block-wise reference is its whole-tree twin, and each new
reader reads what the program writes, nothing where it writes nothing, and
at most 100% where the events take exactly the least time."""
import json
import math
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import arith, harness, host_trace, moe_ticks, traffic, weights  # noqa: E402

CELL = "serve_glm47_flash_conv"
TINY = {"d_model": 64, "n_layers": 3, "moe_dense_layers": 1, "n_heads": 4, "head_dim": 20,
        "d_ff": 128, "vocab_size": 256, "max_seq_len": 64, "rope_theta": 1e6,
        "kv_lora_rank": 16, "q_lora_rank": 24, "qk_nope_head_dim": 12,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "n_experts": 8, "moe_top_k": 2,
        "moe_d_ff": 32, "moe_shared_experts": 1, "moe_routed_scale": 1.8,
        "scan_layers": False, "norm_eps": 1e-5, "param_dtype": "float32"}
PEAK = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(CELL)


@pytest.fixture(scope="module")
def ref():
    return moe_ticks.family()


def test_configuration_states_the_published_widths_and_cuts_the_depth_alone(cell):
    config, model = cell["config"], cell["config"]["model"]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "glm_4_7_flash")
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers", "num_nextn_predict_layers"]
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json"
    published = {"hidden_size": 2048, "intermediate_size": 10240, "moe_intermediate_size": 1536,
                 "num_attention_heads": 20, "num_key_value_heads": 20, "n_routed_experts": 64,
                 "n_shared_experts": 1, "num_experts_per_tok": 4, "routed_scaling_factor": 1.8,
                 "first_k_dense_replace": 1, "q_lora_rank": 768, "kv_lora_rank": 512,
                 "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
                 "vocab_size": 154880, "rope_theta": 1000000, "rms_norm_eps": 1e-05,
                 "max_position_embeddings": 202752, "norm_topk_prob": True, "n_group": 1,
                 "topk_group": 1, "topk_method": "noaux_tc", "tie_word_embeddings": False,
                 "model_type": "glm4_moe_lite"}
    assert {k: config[k] for k in published} == published
    assert (config["num_hidden_layers"], config["num_nextn_predict_layers"]) == (7, 0)
    assert config["published"]["num_hidden_layers"] == 47 and "eight" in config["deployment"]
    # ... and the same sizes under the program's keys: no width, expert or
    # row of the vocabulary cut
    assert (model["d_model"], model["n_heads"], model["head_dim"], model["d_ff"],
            model["n_experts"], model["moe_top_k"], model["moe_d_ff"], model["vocab_size"],
            model["kv_lora_rank"], model["q_lora_rank"], model["v_head_dim"]) == \
        (2048, 20, 256, 10240, 64, 4, 1536, 154880, 512, 768, 256)
    assert model["head_dim"] == model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    assert (model["n_layers"], model["moe_dense_layers"], model["init_depth"]) == (7, 1, 47)
    assert config["assumed"] and all(isinstance(v, str) for v in config["assumed"].values())


def test_leaf_table_is_the_programs_tree_at_full_size(cell, ref):
    config, model = cell["config"], cell["config"]["model"]
    harness.check_configuration(config)  # the abstract tree: nothing is allocated
    table = ref.leaf_table(model)
    held = sum(math.prod(s) for s, _ in table.values())
    cfg = harness.model_config(config)
    assert held == cfg.num_params == config["parameters"] == 4_530_936_960
    # the stack is unrolled: the dense block, then six routed ones, every
    # leaf a buffer of its own (a grouped matmul takes it without a copy)
    assert ref.layers(model) == [("block_0", "dense", None)] + [
        (f"block_{i}", "moe", None) for i in range(1, 7)]
    assert table["block_0/mlp/wi/kernel"][0] == (2048, 10240)
    assert table["block_6/moe/wi"][0] == (64, 2048, 1536)
    assert table["block_1/moe/router_bias"] == ((64,), 0.05)
    assert table["block_1/attn/out/kernel"][1] == pytest.approx(0.02 / math.sqrt(94))
    # what a token is multiplied by: 5.5 times under what memory holds
    attn = 2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 + 5120 * 2048
    expert = 3 * 2048 * 1536
    active = 7 * attn + 3 * 2048 * 10240 + 6 * (2048 * 64 + 5 * expert) + 2048 * 154880
    assert ref.active_params(model) == config["active_parameters"] == active == 816_316_416
    assert cfg.params_per_token - active == 7 * (2 * 2048 + 768 + 512) + 2048 + 6 * 64
    assert ref.attention_flops_per_position(model) == 7 * 20 * (256 + 256) * 2
    from zero_transformer_tpu.analysis.memory import kv_bytes_per_token

    assert kv_bytes_per_token(cfg) == 7 * 640 * 2  # the PADDED row the pool holds


def test_operations_and_bytes_against_hand_counts(ref):
    # one expert: three 64 x 32 matrices; a (row, choice) pair is three matmuls
    assert ref.expert_ops_bytes(TINY, rows=5, touched=3) == (
        2.0 * 3 * 64 * 32 * 5 * 2, (3 * 3 * 64 * 32 + 2 * 5 * 2 * 64) * 2.0)
    # 4 heads score over 16 + 8 lanes and sum 16; a cached row is 24 values
    assert ref.latent_decode_ops_bytes(TINY, live_positions=100, rows=2) == (
        2.0 * 4 * (24 + 16) * 100, (100 * 24 + 2 * 4 * (24 + 16)) * 2.0)
    attn = 64 * 24 + 24 * 80 + 64 * 24 + 16 * 112 + 64 * 64
    fixed = 3 * attn + 3 * 64 * 128 + 2 * (64 * 8 + 3 * 64 * 32) + 64 * 256
    assert ref.decode_read_bytes(TINY, touched=0, live_positions=0) == fixed * 2.0
    assert ref.decode_read_bytes(TINY, touched=5, live_positions=10) == \
        (fixed + 5 * 3 * 64 * 32 + 3 * 10 * 24) * 2.0


def test_cell_and_traffic_are_as_the_issue_names_them(cell):
    mix = cell["traffic"]
    assert cell["chips"] == 1 and mix["kind"] == "serve_open_loop"
    assert mix["prompt_len"] == {"mean": 1155, "min": 16, "max": 4096}
    assert mix["output_len"] == {"mean": 211, "min": 4, "max": 1024}
    assert mix["engine"] == {"n_slots": 16, "cache_len": 5120, "max_queue": 64,
                             "prefill_chunk": 64, "prefix_cache_chunks": 256, "page_size": 16,
                             "page_pool_tokens": 81920, "draft_k": 0, "trace_capacity": 131072}
    assert mix["reference"]["pad_to"] % moe_ticks.family().ROWS == 0
    reqs = traffic.open_loop_requests(mix, 2**31 + 9, 51.0, 154880)
    assert max(len(r.prompt) + r.max_new_tokens for r in reqs) <= mix["engine"]["cache_len"]
    assert len(reqs) == round(mix["rate_per_s"] * 51)
    names = {m["name"] for m in cell["per_layer"]}
    assert {"moe_expert_roofline", "latent_attention_roofline", "moe_decode_bandwidth_share",
            "expert_load_max_over_mean", "serve_mfu", "decode_program_ms_p50"} <= names
    # full-head bytes would read several hundred percent on latent pages,
    # and the looped family's byte count is not this one's
    assert not names & {"paged_attention_roofline", "decode_bandwidth_share"}
    # tokens delivered inside the window depend on where it closes in the
    # one trace (PERF.md section 7 (i)): not judged here, nor what moves it
    assert {m["name"] for m in cell["end_to_end"]} == {"itl_p95_ms", "setup_s"}
    assert {m["moves"] for m in cell["per_layer"]} == {"itl_p95_ms"}


@pytest.mark.parametrize("mode", ["f32", "fp8"])
def test_reference_in_blocks_is_its_whole_tree(ref, monkeypatch, mode):
    """Rows 8 at a time, keys padded to ``max_seq_len``, a layer's leaves
    asked for together and never the tree: the same logits."""
    import jax
    import jax.numpy as jnp

    table, key = ref.leaf_table(TINY), weights.seed_key(2**31 + 11, "weights")
    params = weights.build(table, key)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 40), 0, 256)
    asked = []
    maker = weights.leaf_maker(table, key)

    def make(paths, layer=None):
        asked.append((tuple(paths), layer))
        return maker(paths, layer)

    monkeypatch.setattr(ref, "ROWS", 8)
    with jax.default_matmul_precision("highest"):
        whole = ref.logits(params, toks, TINY, mode)
        blocks = ref.logits_by_blocks(make, toks, TINY, mode)
    assert float(jnp.max(jnp.abs(whole - blocks))) < 2e-6
    assert [layer for _, layer in asked] == [None] * 5
    assert all(p.startswith("block_2/") for p in asked[3][0])
    with pytest.raises(SystemExit, match="rows in 8s"):
        ref.logits_by_blocks(make, toks[:, :37], TINY, mode)


# ---- the readers, on a context made by hand --------------------------------


def _ctx(step_attrs, records=None, **over):
    spans = [(i, "engine", "decode_step", 10.0 + 0.1 * i, 10.05 + 0.1 * i, dict(a, tick=i))
             for i, a in enumerate(step_attrs)]
    ctx = {"spans": spans, "model": dict(TINY), "peak": PEAK, "t0": 5.0, "t_end": 20.0,
           "traced": (9.0, 11.0), "trace": {"events": {0: []}},
           "records": records if records is not None else [
               {"prompt": [1] * 30, "prefill_done_at": 8.0, "finished_at": None,
                "token_times": [8.0, 9.5]}]}
    ctx.update(over)
    return ctx


STEP = {"active": 3, "experts_touched": 7, "moe_routed": 12, "moe_load_max": 5}


def test_decode_ticks_reads_the_engines_counters_and_the_records():
    ticks = moe_ticks.decode_ticks(_ctx([STEP, {"active": 1}]))
    # 12 pairs over 2 routed layers x 2 choices: 3 rows; 30 + 2 cached positions
    assert ticks == [{"rows": 3.0, "touched": 7, "routed": 12, "load_max": 5, "live": 32}]
    assert moe_ticks.decode_ticks(_ctx([{"active": 1}])) == []  # the parent's spans
    assert moe_ticks.decode_ticks({}) == []
    assert moe_ticks.decode_ticks(_ctx([STEP], model={"d_model": 64})) == []


def test_load_reader_is_the_busiest_over_what_an_even_router_would_give():
    mod = harness.load_reader("expert_load_max_over_mean")
    # one row picks its 2 of 8 experts: the busiest holds 1, whatever the
    # router; with every expert in every choice there is no room for chance
    assert mod.expected_max(1, 8, 2) == 1.0 and mod.expected_max(5, 8, 8) == 5.0
    three, five = mod.expected_max(3, 8, 2), mod.expected_max(5, 8, 2)
    assert 1.0 < three < five < 5.0
    # 5 + 9 rows on the busiest experts of 2 routed layers, over the
    # expected maxima of ticks of 3 and 5 rows
    got = mod.read(_ctx([STEP, dict(STEP, moe_routed=20, moe_load_max=9)]))
    assert got == pytest.approx((5 + 9) / (2 * three + 2 * five))
    # as even as chance reads 1, at any number of rows
    even = [dict(STEP, moe_routed=4 * n, moe_load_max=2 * mod.expected_max(n, 8, 2))
            for n in (1, 3, 5)]
    assert mod.read(_ctx(even)) == pytest.approx(1.0)
    assert mod.read(_ctx([{"active": 2}])) is None and mod.read({}) is None


def _with_capture(monkeypatch, programs):
    monkeypatch.setattr(host_trace, "load", lambda path=None: {"modules": {0: programs}})


def test_rooflines_read_100_where_the_events_take_the_least_time(monkeypatch, ref):
    ctx = _ctx([STEP])
    # a capture with one decode program and one prefill program
    _with_capture(monkeypatch, [("jit__fused_step_impl(1)", 10.0, 10.01, 1),
                                ("jit__paged_chunk_prefill_impl(2)", 10.5, 10.6, 2)])
    ops, byts = ref.expert_ops_bytes(TINY, rows=3 * 2, touched=7)
    least = arith.roofline_seconds(ops, byts, PEAK)[0]
    third = least / 3
    events = [(f"%ragged-dot-none.{i} = bf16[6,32]{{1,0}} custom-call(...)",
               10.001 + i * 1e-4, 10.001 + i * 1e-4 + third) for i in range(3)]
    # the prefill program's grouped matmuls are no decode tick's
    events.append(("%ragged-dot-none = bf16[512,32]{1,0} custom-call(...)", 10.5, 10.55))
    ctx["trace"] = {"events": {0: events}}
    assert harness.load_reader("moe_expert_roofline").read(ctx) == pytest.approx(100.0)
    slow = [(n, s, s + 2 * (e - s)) for n, s, e in events]
    assert harness.load_reader("moe_expert_roofline").read(
        dict(ctx, trace={"events": {0: slow}})) == pytest.approx(50.0)

    # the chunk-prefill program's grouped matmuls: 4 slots x 8 positions
    # whoever prefills, 2 routed layers, 11 (layer, expert) pairs touched
    mix = {"engine": {"n_slots": 4, "prefill_chunk": 8}}
    pre = dict(_ctx([dict(STEP, prefill_experts_touched=11), STEP]), mix=mix)
    ops, byts = ref.expert_ops_bytes(TINY, rows=4 * 8 * 2, touched=11)
    least = arith.roofline_seconds(ops, byts, PEAK)[0]
    chunk = [("%ragged-dot-none = bf16[512,32]{1,0} custom-call(...)", 10.5, 10.5 + least),
             events[0]]  # a decode program's are no chunk's
    read = harness.load_reader("moe_prefill_expert_roofline").read
    assert read(dict(pre, trace={"events": {0: chunk}})) == pytest.approx(100.0)
    slow = [(n, s, s + 4 * (e - s)) for n, s, e in chunk]
    assert read(dict(pre, trace={"events": {0: slow}})) == pytest.approx(25.0)
    assert read(dict(ctx, mix=mix, trace={"events": {0: chunk}})) is None  # no count: the parent

    # the latent kernel: three calls (one a layer), each at its least time
    ops, byts = ref.latent_decode_ops_bytes(TINY, live_positions=32, rows=3.0)
    call = arith.roofline_seconds(ops, byts, PEAK)[0]
    kernel = [(f"%latent_paged_attention.{i} = f32[4,8,16]{{2,1,0}} custom-call(...)",
               10.002 + i * 1e-3, 10.002 + i * 1e-3 + call) for i in range(3)]
    other = [("%paged_attention.3 = bf16[4,1,4,16] custom-call(...)", 10.004, 10.005)]
    read = harness.load_reader("latent_attention_roofline").read
    assert read(dict(ctx, trace={"events": {0: kernel + other}})) == pytest.approx(100.0)
    assert read(dict(ctx, trace={"events": {0: other}})) is None  # another kernel's name
    # and the accepted full-head reader does not take the latent kernel's events
    import re

    paged = harness.load_reader("paged_attention_roofline").KERNEL
    assert not any(re.search(paged, n) for n, _, _ in kernel)

    # the whole decode program against the bytes it must read
    must = ref.decode_read_bytes(TINY, touched=7, live_positions=32) / PEAK["bytes_per_s"]
    _with_capture(monkeypatch, [("jit__fused_step_impl(1)", 10.0, 10.0 + 4 * must, 1)])
    assert harness.load_reader("moe_decode_bandwidth_share").read(ctx) == pytest.approx(25.0)


@pytest.mark.parametrize("name", ["moe_expert_roofline", "latent_attention_roofline",
                                  "moe_decode_bandwidth_share", "expert_load_max_over_mean",
                                  "moe_prefill_expert_roofline"])
def test_new_readers_give_nothing_and_raise_nothing_without_their_inputs(monkeypatch, name):
    """An untraced run, a run with no capture on disk, a program whose
    spans carry no expert counters (the parent): None, never an error."""
    read = harness.load_reader(name).read
    monkeypatch.setattr(host_trace, "newest_xplane", lambda root=None: None)
    assert read({}) is None
    assert read(_ctx([{"active": 2}])) is None
    if name != "expert_load_max_over_mean":
        step = dict(STEP, prefill_experts_touched=9)
        mix = {"engine": {"n_slots": 4, "prefill_chunk": 8}}
        assert read(_ctx([step], mix=mix)) is None  # counters, and no capture to time them by
        assert read(_ctx([step], mix=mix, trace=None)) is None

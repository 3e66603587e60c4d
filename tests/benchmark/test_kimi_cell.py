"""The Kimi-Linear-48B-A3B configuration's benchmark files, on the CPU: the
configuration states every published key and cuts depth, held experts and
vocabulary as its deployment says, the family's leaf table is the program's
tree at full size (abstract: nothing is allocated), the operations and bytes
the new readers divide by are right by hand counts, the cell is as ISSUE 41
names it, the block-wise reference is its whole-tree twin, and each new
reader reads what the program writes, nothing where it writes nothing, and
100% where the events take exactly the least time."""
import json
import math
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import arith, harness, host_trace, kda_ticks, traffic, weights  # noqa: E402

CELL = "serve_kimi_linear_reason"
TINY = {"d_model": 64, "n_layers": 8, "n_heads": 4, "head_dim": 24, "d_ff": 128,
        "vocab_size": 256, "max_seq_len": 64, "layer_pattern": ["kda", "kda", "kda", "attention"],
        "kda_heads": 2, "kda_head_dim": 16, "kda_conv": 4, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "n_experts": 16,
        "moe_experts_held": 4, "moe_top_k": 4, "moe_d_ff": 32, "moe_shared_experts": 1,
        "moe_routed_scale": 2.446, "moe_dense_layers": 1, "norm_eps": 1e-5,
        "scan_layers": False, "param_dtype": "float32"}
PEAK = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(CELL)


@pytest.fixture(scope="module")
def ref():
    return kda_ticks.family()


def test_configuration_states_every_published_key_and_its_cut(cell):
    config, model = cell["config"], cell["config"]["model"]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "kimi_linear_48b_a3b")
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers", "num_experts",
                                                      "vocab_size"]
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json"
    published = {"first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
                 "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
                 "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear",
                 "moe_intermediate_size": 1024, "moe_layer_freq": 1, "moe_renormalize": True,
                 "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
                 "num_expert_group": 1, "num_experts_per_token": 8, "num_key_value_heads": 32,
                 "num_nextn_predict_layers": 0, "num_shared_experts": 1, "q_lora_rank": None,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
                 "rope_scaling": None, "rope_theta": 10000, "routed_scaling_factor": 2.446,
                 "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True,
                 "v_head_dim": 128}
    assert {k: config[k] for k in published} == published
    linear = config["linear_attn_config"]
    assert (linear["num_heads"], linear["head_dim"], linear["short_conv_kernel_size"]) == \
        (32, 128, 4)
    assert linear["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert len(linear["kda_layers"]) == 20
    # the cut: the first 8 layers, 32 of 256 experts held, 1/8 of the vocabulary
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == \
        (8, 32, 20480)
    assert config["published"] == {"num_hidden_layers": 27, "num_experts": 256,
                                   "vocab_size": 163840}
    kinds = ["attention" if i + 1 in linear["full_attn_layers"] else "kda" for i in range(8)]
    assert model["layer_pattern"] * 2 == kinds
    # ... and the same sizes under the program's keys: no width cut
    assert (model["d_model"], model["n_layers"], model["n_heads"], model["head_dim"],
            model["d_ff"], model["vocab_size"]) == (2304, 8, 32, 192, 9216, 20480)
    assert (model["kda_heads"], model["kda_head_dim"], model["kda_conv"]) == (32, 128, 4)
    assert (model["kv_lora_rank"], model["q_lora_rank"], model["qk_nope_head_dim"],
            model["qk_rope_head_dim"], model["v_head_dim"], model["position"]) == \
        (512, None, 128, 64, 128, "none")
    assert (model["n_experts"], model["moe_experts_held"], model["moe_top_k"],
            model["moe_d_ff"], model["moe_shared_experts"], model["moe_routed_scale"],
            model["moe_dense_layers"]) == (256, 32, 8, 1024, 1, 2.446, 1)
    assert model["max_seq_len"] == cell["traffic"]["engine"]["cache_len"] == 3072
    assert {"conv", "q_scale", "output_gate", "state_dtypes", "init"} <= set(config["assumed"])


def test_leaf_table_is_the_programs_tree_at_full_size(cell, ref):
    config, model = cell["config"], cell["config"]["model"]
    harness.check_configuration(config)  # the abstract tree: nothing is allocated
    table = ref.leaf_table(model)
    held = sum(math.prod(s) for s, _ in table.values())
    cfg = harness.model_config(config)
    assert held == cfg.num_params == config["parameters"] == 2_092_550_080
    assert ref.layers(model)[0] == ("block_0", ("kda", "dense"))
    assert ref.layers(model)[7] == ("block_7", ("attention", "moe"))
    assert table["block_1/moe/wi"][0] == (32, 2304, 1024)
    assert table["block_1/moe/router"][0] == (2304, 256)
    assert table["block_1/moe/router_bias"] == ((256,), 0.05)
    assert table["block_3/attn/q/kernel"][0] == (2304, 32 * 192)
    assert table["block_2/kda/dt_bias"] == ((4096,), 1.0)
    assert table["block_2/kda/o_proj/kernel"][1] == pytest.approx(0.02 / math.sqrt(54))
    assert table["lm_head/kernel"][0] == (2304, 20480)
    assert ref.active_params(model) == config["active_parameters"] == 509_100_032
    assert ref.attention_flops_per_position(model) == 2 * 2 * 32 * (128 + 64 + 128)
    from zero_transformer_tpu.analysis.memory import kv_bytes_per_token, state_bytes_per_slot

    assert kv_bytes_per_token(cfg) == 2 * 640 * 2
    assert state_bytes_per_slot(cfg) == config["state_bytes_per_slot"] == \
        ref.state_bytes_per_slot(model) == 6 * (32 * 128 * 128 * 4 + 3 * 3 * 4096 * 2)


def test_operations_and_bytes_against_hand_counts(ref, cell):
    # a row's state: 2 heads x 16 x 16 float32, in and out; q, k, v, the
    # decay and o (32 each) and beta (2); seven operations a state value
    state = 2 * 16 * 16
    assert ref.state_update_ops_bytes(TINY, rows=3) == (
        7.0 * 3 * state, 3 * (2 * state + 5 * 32 + 2) * 4.0)
    kda = 4 * 64 * 32 + 2 * (64 * 16 + 16 * 32) + 64 * 2
    mla = 64 * 4 * 24 + 64 * 24 + 16 * 4 * 32 + 4 * 16 * 64
    fixed = 6 * kda + 2 * mla + 3 * 64 * 128 + 7 * (64 * 16 + 3 * 64 * 32) + 64 * 256
    assert ref.active_params(TINY) == fixed + 7 * 3 * 64 * 32  # one held expert a token
    slot = 6 * (state * 4 + 3 * 3 * 32 * 2)
    assert ref.state_bytes_per_slot(TINY) == slot
    assert ref.decode_read_bytes(TINY, rows=0, touched=0, live_positions=0) == fixed * 2.0
    assert ref.decode_read_bytes(TINY, rows=3, touched=5, live_positions=10) == \
        (fixed + 5 * 3 * 64 * 32 + 2 * 10 * 24) * 2.0 + 2 * 3 * slot
    # at the published size a call moves 2 x 2.1 MB a decoding row
    ops, byts = ref.state_update_ops_bytes(cell["config"]["model"], rows=100)
    assert byts == 100 * (2 * 2_097_152 + (5 * 4096 + 32) * 4)
    assert arith.roofline_seconds(ops, byts, PEAK)[1] == "bandwidth"


def test_cell_and_traffic_are_as_the_issue_names_them(cell):
    mix = cell["traffic"]
    assert cell["chips"] == 1 and mix["kind"] == "serve_open_loop"
    assert mix["prompt_len"] == {"mean": 256, "min": 16, "max": 1024}
    assert mix["output_len"] == {"mean": 512, "min": 32, "max": 2048}
    assert mix["engine"] == {"n_slots": 128, "cache_len": 3072, "max_queue": 128,
                             "prefill_chunk": 64, "prefix_cache_chunks": 256, "page_size": 16,
                             "page_pool_tokens": 393216, "draft_k": 0, "trace_capacity": 131072}
    assert mix["params_dtype"] == "bfloat16" and mix["sampling"]["greedy"]
    assert set(mix["limits"]) - {"set_from"} == {"served_logit_gap_p90"}
    reqs = traffic.open_loop_requests(mix, 2**31 + 9, 51.0, 20480)
    assert max(len(r.prompt) + r.max_new_tokens for r in reqs) <= mix["engine"]["cache_len"]
    assert max(t for r in reqs for t in r.prompt) < 20480
    assert len(reqs) == round(mix["rate_per_s"] * 51)
    names = {m["name"] for m in cell["per_layer"]}
    assert {"kda_state_update_roofline", "kda_decode_bandwidth_share", "serve_mfu",
            "engine_tick_ms_p50", "engine_host_ms_per_tick", "engine_device_wait_ms_p50",
            "queue_wait_ms_p95", "ttft_p95_ms", "decode_program_ms_p50",
            "prefill_program_ms_p50", "device_idle_share.serve",
            "device_idle_attributed.serve"} <= names
    # the family-pinned readers count another family's layers
    assert not names & {"ssm_state_update_roofline", "ssm_decode_bandwidth_share",
                        "moe_decode_bandwidth_share", "moe_expert_roofline",
                        "latent_attention_roofline", "paged_attention_roofline"}
    assert {m["name"] for m in cell["end_to_end"]} >= {"itl_p95_ms", "setup_s"}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["config"] == "kimi_linear_48b_a3b"
    new = {m["name"]: m for m in bench["per_layer"]
           if m["name"] in {"kda_state_update_roofline", "kda_decode_bandwidth_share"}}
    assert len(new) == 2 and all(m["workloads"] == [CELL] for m in new.values())
    assert all(m["moves"] == "itl_p95_ms" for m in new.values())


@pytest.mark.parametrize("mode", ["f32", "fp8", "state_bf16"])
def test_reference_in_blocks_is_its_whole_tree(ref, mode):
    """A layer's leaves asked for together and never the tree: the same
    logits."""
    import jax
    import jax.numpy as jnp

    table, key = ref.leaf_table(TINY), weights.seed_key(2**31 + 13, "weights")
    params = weights.build(table, key)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 40), 0, 256)
    asked = []
    maker = weights.leaf_maker(table, key)

    def make(paths, layer=None):
        asked.append((tuple(paths), layer))
        return maker(paths, layer)

    with jax.default_matmul_precision("highest"):
        whole = ref.logits(params, toks, TINY, mode)
        blocks = ref.logits_by_blocks(make, toks, TINY, mode)
    # (a kept value on a bfloat16 rounding's edge falls either way with the
    # last float32 bit of the two paths, and the delta rule feeds the state
    # back into every later step's correction: that control's two paths
    # differ by up to 2e-3 at 40 positions, as much as half its distance
    # from the reference; it is a CPU control, never a limit on the chip)
    assert float(jnp.max(jnp.abs(whole - blocks))) < (5e-3 if mode == "state_bf16" else 2e-6)
    # a routed layer's experts an expert at a time, its other leaves together
    held = TINY["moe_experts_held"]
    assert [layer for _, layer in asked] == [None, None] + [None, 0, 1, 2, 3] * 7 + [None]
    assert asked[3][0] == ("block_1/moe/wi", "block_1/moe/gate", "block_1/moe/wo")
    assert all(p.startswith("block_1/") and p not in asked[3][0] for p in asked[2][0])
    assert len({paths for paths, layer in asked if layer == held - 1}) == 7
    if mode != "f32":  # a control is far from the reference, each in its way
        with jax.default_matmul_precision("highest"):
            off = float(jnp.max(jnp.abs(whole - ref.logits(params, toks, TINY, "f32"))))
        assert off > 1e-3


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
           "peak": PEAK, "t0": 9.0, "t_end": 20.0, "mix": {"engine": {"n_slots": 4}}}
    ctx.update(over)
    return ctx


STEP = {"active": 3, "state_rows": 3, "experts_touched": 5, "moe_routed": 84,
        "moe_load_max": 9}


def test_decode_ticks_reads_the_engines_spans_and_the_records():
    ticks = kda_ticks.decode_ticks(_ctx([STEP, {"active": 1}]))
    # three requests decode, each 10 + 1 cached positions
    assert ticks == [{"rows": 3, "touched": 5, "live": 33}]
    assert kda_ticks.decode_ticks(_ctx([{"active": 1}])) == []  # no state counters
    assert kda_ticks.decode_ticks({}) == []
    assert kda_ticks.decode_ticks(_ctx([STEP], model={"d_model": 64})) == []  # no KDA
    assert kda_ticks.decode_ticks(_ctx([STEP], traced=None)) == []


def _with_capture(monkeypatch, programs):
    monkeypatch.setattr(host_trace, "load", lambda path=None: {"modules": {0: programs}})


def test_readers_read_100_where_the_events_take_the_least_time(monkeypatch, ref):
    ctx = _ctx([STEP])
    _with_capture(monkeypatch, [("jit__fused_step_impl(1)", 10.0, 10.01, 1)])
    call = arith.roofline_seconds(*ref.state_update_ops_bytes(TINY, rows=3), PEAK)[0]
    kernel = [(f"%kda_state_update.{i} = (f32[6,4,2,16,16]{{4,3,2,1,0}}, f32[4,2,16]) "
               "custom-call(...)", 10.001 + i * 1e-4, 10.001 + i * 1e-4 + call)
              for i in range(6)]
    other = [("%ssm_state_update.3 = f32[4,4,32] custom-call(...)", 10.004, 10.005)]
    read = harness.load_reader("kda_state_update_roofline").read
    assert read(dict(ctx, trace={"events": {0: kernel + other}})) == pytest.approx(100.0)
    slow = [(n, s, s + 2 * (e - s)) for n, s, e in kernel]
    assert read(dict(ctx, trace={"events": {0: slow}})) == pytest.approx(50.0)
    assert read(dict(ctx, trace={"events": {0: other}})) is None  # another kernel's name
    import re

    ssm = harness.load_reader("ssm_state_update_roofline").KERNEL
    assert not any(re.search(ssm, n) for n, _, _ in kernel)

    must = ref.decode_read_bytes(TINY, rows=3, touched=5, live_positions=33) / PEAK["bytes_per_s"]
    read = harness.load_reader("kda_decode_bandwidth_share").read
    _with_capture(monkeypatch, [("jit__fused_step_impl(1)", 10.0, 10.0 + must, 1)])
    assert read(dict(ctx, trace={"events": {0: kernel}})) == pytest.approx(100.0)
    _with_capture(monkeypatch, [("jit__fused_step_impl(1)", 10.0, 10.0 + 2 * must, 1),
                                ("jit__paged_chunk_prefill_impl(2)", 10.5, 10.6, 2)])
    assert read(dict(ctx, trace={"events": {0: kernel}})) == pytest.approx(50.0)


@pytest.mark.parametrize("name", ["kda_state_update_roofline", "kda_decode_bandwidth_share"])
def test_new_readers_give_nothing_and_raise_nothing_without_their_inputs(monkeypatch, name):
    """An untraced run, a run with no capture on disk, a program whose spans
    carry no state counters: None, never an error."""
    read = harness.load_reader(name).read
    monkeypatch.setattr(host_trace, "newest_xplane", lambda root=None: None)
    assert read({}) is None
    assert read(_ctx([{"active": 2}])) is None
    assert read(_ctx([{"active": 2, "loops": 1, "pages_in_use": 7}])) is None
    assert read(_ctx([STEP])) is None  # counters, and no capture to time them by
    assert read(_ctx([STEP], trace=None)) is None

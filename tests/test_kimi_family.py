"""The Kimi-Linear family (Kimi Delta Attention mixers among latent-attention
layers with a direct query projection and no rotary, routed expert layers
after a leading dense one, an expert-parallel share of the experts held) at
a small size, held to its plain reference ``benchmark/reference/
kimi_linear.py`` on seeded weights from the family's own ``leaf_table``: the
full forward, the chunked (WY) and one-step forms of the delta rule against
the sequential recurrence, the Pallas step against its ``jax.numpy`` twin,
the state and the latent pages through slab and paged caches, and chunked
prefill + decode in the serving engine with the state and the latent pages
in ONE cache tree.

Tolerances. Everything here runs in float32 at ``highest`` against a float32
reference whose sums differ only in their order (the chunked form's
cumulative decays and its triangular solve against a product of steps):
logits of deviation 0.16 agree to 7e-7 (the full forward, 37 positions over
five chunks of 8); ``TOL`` 5e-6 is seven times what is seen. The same
forward in bfloat16 misses by 1e-2 and more, and the state held in bfloat16
between steps by more than ten tolerances
(``test_state_controls_are_far_outside``); the recurrence's forms are held
to 2e-5 on unit-scale inputs, where a bfloat16 state misses by 1e-3."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark import harness, weights  # noqa: E402
from zero_transformer_tpu.config import model_config  # noqa: E402
from zero_transformer_tpu.inference import SamplingConfig  # noqa: E402
from zero_transformer_tpu.inference.generate import decode_model, init_cache  # noqa: E402
from zero_transformer_tpu.models import Transformer, kda  # noqa: E402
from zero_transformer_tpu.ops.pallas import kda_update as ku  # noqa: E402

TOL = 5e-6
FORM_TOL = 2e-5
REF = harness.load_reference({"reference": "benchmark/reference/kimi_linear.py"})
KEYS = ("vocab_size", "d_model", "n_heads", "n_layers", "max_seq_len", "head_dim", "d_ff",
        "layer_pattern", "kda_heads", "kda_head_dim", "kda_conv", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "n_experts", "moe_experts_held",
        "moe_top_k", "moe_d_ff", "moe_shared_experts", "moe_routed_scale",
        "moe_dense_layers", "norm_eps", "scan_layers", "param_dtype")


def _model_group(cfg) -> dict:
    """The reference's ``model`` group from a ``ModelConfig``."""
    return {k: getattr(cfg, k) for k in KEYS}


def _family(**over):
    cfg = model_config("kimi_linear_test", param_dtype="float32",
                       compute_dtype="float32", **over)
    model = _model_group(cfg)
    table = REF.leaf_table(model)
    return cfg, model, table, weights.build(table, weights.seed_key(2**31 + 41, "weights"))


@pytest.fixture(scope="module")
def family():
    return _family()


def _reference(params, tokens, model, mode="f32"):
    with jax.default_matmul_precision("highest"):
        return REF.logits(params, jnp.asarray(tokens), model, mode)


# ---- (a) the full forward ---------------------------------------------------


def test_leaf_table_is_the_programs_tree_and_counts_agree(family):
    cfg, model, table, _ = family
    from zero_transformer_tpu.parallel.sharding import unbox

    abstract = jax.eval_shape(
        lambda: Transformer(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    weights.check_tree(table, unbox(abstract))
    held = sum(int(np.prod(s)) for s, _ in table.values())
    assert held == cfg.num_params == 379_388
    assert [(cfg.mixer_kind(i), cfg.mlp_kind(i)) for i in range(5)] == [
        ("kda", "dense"), ("kda", "moe"), ("kda", "moe"), ("attention", "moe"), ("kda", "moe")]
    assert cfg.layers_of("kda") == 6 and cfg.kv_entries == 2 and cfg.recurrent_kind == "kda"
    # 4 of 16 experts held: the expert stacks hold 4, the router routes over 16
    assert table["block_1/moe/wi"][0] == (4, 64, 32)
    assert table["block_1/moe/router"][0] == (64, 16)
    # what a token is multiplied by on this share: top 4 of 16, so one held
    # expert on average; the program also counts what is elementwise
    kda_small = 3 * 32 * 4 + 2 + 32 + 16
    small = 6 * kda_small + 2 * 16 + 8 * 2 * 64 + 7 * 16 + 64
    assert REF.active_params(model) == cfg.params_per_token - small


def test_full_forward_matches_the_reference(family, monkeypatch):
    cfg, model, _, params = family
    monkeypatch.setattr(kda, "CHUNK", 8)  # 37 positions cross five chunks
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 37), 0, 256)
    with jax.default_matmul_precision("highest"):
        got = Transformer(cfg).apply({"params": params}, toks)
    assert float(jnp.max(jnp.abs(got - _reference(params, toks, model)))) < TOL


def test_bfloat16_would_fail_the_tolerance(family):
    cfg, model, _, params = family
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 37), 0, 256)
    low = Transformer(dataclasses.replace(cfg, compute_dtype="bfloat16"))
    got = low.apply({"params": params}, toks).astype(jnp.float32)
    assert float(jnp.max(jnp.abs(got - _reference(params, toks, model)))) > 100 * TOL


@pytest.mark.parametrize("mode", ["bf16", "fp8", "state_bf16"])
def test_the_references_controls_fail_the_tolerance(family, mode):
    """Matmul operands rounded to bfloat16 or float8, and (``state_bf16``)
    the recurrent state rounded to bfloat16 after every position with the
    matmuls left in float32: each is far outside the tolerance, so a
    program that narrowed either would fail the tests above."""
    _, model, _, params = family
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 37), 0, 256)
    ctrl = _reference(params, toks, model, mode)
    assert float(jnp.max(jnp.abs(ctrl - _reference(params, toks, model)))) > 10 * TOL


@pytest.mark.parametrize("field,value", [("position", "rope"), ("q_lora_rank", 8),
                                         ("moe_experts_held", 16)])
def test_mla_without_rotary_or_q_rank_and_the_share_are_read(family, field, value):
    """MLA with a direct query projection and NO rotary: rotating the rope
    lanes moves the logits far outside the tolerance; a q bottleneck is
    another tree (the reference's has none); holding every expert is
    another share."""
    cfg, model, _, params = family
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0, 256)
    other = dataclasses.replace(cfg, **{field: value})
    if field == "position":
        with jax.default_matmul_precision("highest"):
            got = Transformer(other).apply({"params": params}, toks)
        assert float(jnp.max(jnp.abs(got - _reference(params, toks, model)))) > 100 * TOL
        return
    from zero_transformer_tpu.parallel.sharding import unbox

    abstract = unbox(jax.eval_shape(
        lambda: Transformer(other).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )["params"])
    with pytest.raises(SystemExit, match="do not match"):
        weights.check_tree(REF.leaf_table(model), abstract)


# ---- (b) the recurrence's three forms ---------------------------------------


def _kda_inputs(T, seed=0, B=2, H=3, D=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = kda.l2_normalize(jax.random.normal(ks[0], (B, T, H, D))) * D ** -0.5
    k = kda.l2_normalize(jax.random.normal(ks[1], (B, T, H, D)))
    v = jax.random.normal(ks[2], (B, T, H, D))
    # decays a channel from about exp(-5) to 0.99 a step
    g = -jnp.exp(jax.random.normal(ks[3], (B, T, H, D)) - 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    s0 = jax.random.normal(ks[5], (B, H, D, D))
    return q, k, v, g, beta, s0


def _sequential(q, k, v, g, beta, s0, state_dtype=jnp.float32):
    """The recurrence a position at a time, by hand: decay, then the delta
    rule's correction, then the read."""
    s, os_ = s0, []
    for t in range(q.shape[1]):
        s = jnp.exp(g[:, t])[..., None] * s
        u = beta[:, t, :, None] * (v[:, t] - jnp.einsum("bhc,bhcd->bhd", k[:, t], s))
        s = (s + k[:, t, ..., None] * u[..., None, :]).astype(state_dtype).astype(jnp.float32)
        os_.append(jnp.einsum("bhc,bhcd->bhd", q[:, t], s))
    return jnp.stack(os_, axis=1), s


@pytest.mark.parametrize("chunks", [1, 2, 5])
def test_chunked_form_is_the_sequential_recurrence(chunks):
    """Over 1, 2 and 5 chunks (the last one ragged), from a state that is
    not zero: the triangular solve is the delta rule's corrections."""
    args = _kda_inputs(T=8 * chunks - (3 if chunks > 1 else 0), seed=chunks)
    with jax.default_matmul_precision("highest"):
        o, s = kda.kda_scan(*args, chunk=8)
        o_ref, s_ref = _sequential(*args)
    assert float(jnp.max(jnp.abs(o - o_ref))) < FORM_TOL
    assert float(jnp.max(jnp.abs(s - s_ref))) < FORM_TOL


def test_a_bfloat16_state_would_fail_the_forms_tolerance():
    args = _kda_inputs(T=19, seed=4)
    with jax.default_matmul_precision("highest"):
        o, _ = _sequential(*args, state_dtype=jnp.bfloat16)
        o_ref, _ = _sequential(*args)
    assert float(jnp.max(jnp.abs(o - o_ref))) > 10 * FORM_TOL


def test_one_step_form_is_the_sequential_recurrence():
    q, k, v, g, beta, s0 = _kda_inputs(T=1, seed=9)
    o, s = ku.kda_update_reference(s0, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    o_ref, s_ref = _sequential(q, k, v, g, beta, s0)
    assert float(jnp.max(jnp.abs(o - o_ref[:, 0]))) < 1e-5
    assert float(jnp.max(jnp.abs(s - s_ref))) < 1e-5


@pytest.mark.parametrize("live", [(1, 1, 1, 1), (0, 1, 0, 1), (1, 0, 0, 0), (0, 0, 0, 0)])
def test_the_kernel_is_its_jnp_twin_and_keeps_idle_rows(live):
    """The Pallas step in interpret mode on a stack of two layers, at 16
    heads of 128 (two head blocks a row): equal to the ``jax.numpy`` step,
    every idle row and the other layer bit for bit."""
    B, H, D = 4, 16, 128
    ks = jax.random.split(jax.random.PRNGKey(sum(live)), 7)
    state = jax.random.normal(ks[0], (2, B, H, D, D))
    q = kda.l2_normalize(jax.random.normal(ks[1], (B, H, D))) * D ** -0.5
    k = kda.l2_normalize(jax.random.normal(ks[2], (B, H, D)))
    v = jax.random.normal(ks[3], (B, H, D))
    g = -jnp.exp(jax.random.normal(ks[4], (B, H, D)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (B, H)))
    alive = jnp.asarray(live, jnp.bool_)
    o, new = ku.kda_update(state, q, k, v, g, beta, alive, 1, interpret=True)
    o_ref, new_ref = ku.kda_update_reference(state, q, k, v, g, beta, alive, 1)
    assert float(jnp.max(jnp.abs(o - o_ref))) < 1e-5
    assert float(jnp.max(jnp.abs(new - new_ref))) < 1e-5
    assert bool(jnp.all(new[0] == state[0]))
    idle = ~alive
    assert bool(jnp.all(new[1][idle] == state[1][idle])) and bool(jnp.all(o[idle] == 0))


def test_positions_past_valid_leave_the_state_alone():
    q, k, v, g, beta, s0 = _kda_inputs(T=8, seed=3)
    real = jnp.arange(8)[None, :, None] < jnp.asarray([5, 0])[:, None, None]
    _, s = kda.kda_scan(q, k, v, jnp.where(real[..., None], g, 0.0),
                        jnp.where(real, beta, 0.0), s0, chunk=8)
    _, s_ref = _sequential(q[:1, :5], k[:1, :5], v[:1, :5], g[:1, :5], beta[:1, :5], s0[:1])
    assert float(jnp.max(jnp.abs(s[0] - s_ref[0]))) < FORM_TOL
    assert float(jnp.max(jnp.abs(s[1] - s0[1]))) < 1e-6  # no real token


# ---- (c) through the caches -------------------------------------------------


@pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
def test_state_and_latent_rows_through_the_cache_are_the_full_forward(
        family, monkeypatch, paged):
    """Prefill 13 positions (chunked form from a zero state, two chunks of
    8), then 7
    single-token steps (one-step form) through the cache: the KDA state and
    the latent rows, absorbed, with no rotary, against the full forward."""
    cfg, model, _, params = family
    monkeypatch.setattr(kda, "CHUNK", 8)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 20), 0, 256)
    dm = decode_model(cfg, 32, kv_pages=(2 * 4 + 1, 8) if paged else None)
    cache = init_cache(dm, 2)
    if paged:
        table = 1 + jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
        cache = jax.tree_util.tree_map_with_path(
            lambda p, x: jnp.broadcast_to(table, x.shape) if "block_table" in str(p[-1]) else x,
            cache)
        # ONE tree: the stacked state beside the stacked latent pool
        assert cache["ssm_state"].shape == (6, 2, 2, 16, 16)
        assert cache["conv_state"].shape == (6, 2, 3 * 3 * 32)
        assert cache["cached_latent"].shape == (2, 9, 8, 128)
    outs = []
    with jax.default_matmul_precision("highest"):
        for window in [toks[:, :13]] + [toks[:, t:t + 1] for t in range(13, 20)]:
            logits, out = dm.apply({"params": params, "cache": cache}, window, mutable=["cache"])
            cache = out["cache"]
            outs.append(logits)
    got = jnp.concatenate(outs, axis=1)
    assert float(jnp.max(jnp.abs(got - _reference(params, toks, model)))) < TOL


# ---- (d) the expert share ---------------------------------------------------


def test_the_shares_of_every_chip_add_up_to_the_uncut_layer():
    """Four modules holding experts [0, 4), [4, 8), [8, 12), [12, 16), the
    first with the shared expert: their outputs add up to the layer that
    holds all 16, and each routes over all 16 with the bias."""
    from zero_transformer_tpu.models.moe import DroplessMoE
    from zero_transformer_tpu.parallel.sharding import unbox

    cfg = model_config("kimi_linear_test", param_dtype="float32", compute_dtype="float32",
                       moe_experts_held=None)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 9, 64))
    whole = unbox(DroplessMoE(cfg).init(jax.random.PRNGKey(6), x)["params"])
    whole = dict(whole, router_bias=0.05 * jax.random.normal(jax.random.PRNGKey(7), (16,)))
    with jax.default_matmul_precision("highest"):
        want, counts = DroplessMoE(cfg).apply({"params": whole}, x)
        parts = []
        for lo in range(0, 16, 4):
            mine = dict(whole, **{n: whole[n][lo:lo + 4] for n in ("wi", "gate", "wo")})
            if lo:
                mine.pop("shared")
            got, c = DroplessMoE(cfg, experts=(lo, lo + 4), shared=lo == 0).apply(
                {"params": mine}, x)
            assert bool(jnp.all(c == counts))  # the whole layer's routing, every share
            parts.append(got)
    assert float(jnp.max(jnp.abs(sum(parts) - want))) < 1e-5
    assert float(jnp.max(jnp.abs(parts[1]))) > 0  # a share without the shared expert works


# ---- (e) the engine ---------------------------------------------------------


def _engine(cfg, params, **over):
    from zero_transformer_tpu.serving import ServingEngine

    args = dict(n_slots=4, cache_len=64, eos_token_id=None,
                sampling=SamplingConfig(greedy=True, repetition_penalty=1.0),
                prefill_chunk=8, page_size=4, page_pool_tokens=256)
    return ServingEngine(cfg, params, **dict(args, **over))


def _serve(engine, prompts, n_new=6):
    """Serve ``prompts`` a tick at a time and return the handles with every
    logits row the engine held for a decoding slot after a tick: ``(request,
    positions consumed, row)``: the LOGITS are compared."""
    rows = []
    with jax.default_matmul_precision("highest"):
        handles = [engine.submit(prompts[0], max_new_tokens=n_new, seed=0)]
        for tick in range(200):
            if tick == 2:
                # the first request decodes while the others are still
                # mid-prefill, so their slots ride its decode ticks
                handles += [engine.submit(p, max_new_tokens=n_new, seed=i + 1)
                            for i, p in enumerate(prompts[1:])]
            if not engine.step() and tick > 2:
                break
            held = np.asarray(engine._last_logits)
            for slot, act in enumerate(engine._active):
                if act is not None:
                    i = handles.index(act.handle)
                    rows.append((i, len(prompts[i]) + len(act.handle.tokens), held[slot]))
    return handles, rows


def _worst_miss(params, model, prompts, handles, rows, n_new=6):
    """The widest |held logits - the reference's| over every row a decoding
    slot held: the reference's full forward over prompt + served tokens."""
    served = [h.result() for h in handles]
    assert all(len(s) == n_new for s in served)
    ref = [np.asarray(_reference(params, [p + s], model)[0]) for p, s in zip(prompts, served)]
    assert len(rows) >= len(prompts) * (n_new - 1)
    return max(float(np.max(np.abs(row - ref[i][consumed - 1]))) for i, consumed, row in rows)


PROMPT_LENS = (5, 19, 8)


def _prompts(seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, 256, size=n)] for n in PROMPT_LENS]


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "interpret_kernel"])
def test_engine_chunked_prefill_then_decode_with_state_and_latent_pages(
        family, monkeypatch, kernel):
    """Three requests in four slots (one parked), prompts of one, three and
    one chunks of 8 (ragged real-token counts: a padded last chunk, a padded
    ROW), the second request mid-prefill while the first decodes: every
    logits row a decoding slot held is the reference's to the float32
    tolerance."""
    from zero_transformer_tpu.ops.pallas import kernel_traces
    from zero_transformer_tpu.serving import engine as eng

    cfg, model, _, params = family
    if kernel:
        monkeypatch.setenv("ZT_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("ZT_PALLAS_INTERPRET", raising=False)
    # the gate is read where the programs are traced: trace them anew, or
    # the shared jits hand back another case's programs
    monkeypatch.setattr(eng, "_FUSED_SHARED", eng._jit_fused_step(fresh=True))
    monkeypatch.setattr(eng, "_PAGED_CHUNK_SHARED", eng._jit_paged_chunk(fresh=True))

    before = kernel_traces[ku.KERNEL_NAME]
    engine = _engine(cfg, params)
    prompts = _prompts()
    handles, rows = _serve(engine, prompts)
    assert _worst_miss(params, model, prompts, handles, rows) < TOL
    assert (kernel_traces[ku.KERNEL_NAME] > before) == kernel
    snap = engine.metrics_snapshot()
    assert snap["state_bytes_per_slot"] == cfg.state_bytes_per_slot == \
        6 * (2 * 16 * 16 * 4 + 3 * 3 * 32 * 4)
    assert snap["state_pool_bytes"] == 4 * cfg.state_bytes_per_slot
    assert snap["state_resets"] == 3 and snap["state_rows_in_use"] == 0
    assert snap["kv_bytes_per_token"] == 2 * 128 * 4  # two latent rows a position
    assert snap["kernel_latent_attention"] == int(kernel)
    assert snap["moe_tokens_routed"] > 0
    steps = [a for _, track, name, _, _, a in engine.tracer.spans()
             if track == "engine" and name == "decode_step"]
    assert steps and all(a["state_rows"] == a["active"] for a in steps)
    assert all(a["state_rows_skipped"] == 4 - a["state_rows"] for a in steps)
    # the touched experts are the HELD ones: at most 7 routed layers x 4
    assert all(0 < a["experts_touched"] <= 7 * 4 for a in steps)
    assert any(a["active"] == 1 for a in steps) and any(a["active"] == 3 for a in steps)


def test_a_slot_used_by_a_second_request_reads_a_zero_state(family):
    cfg, model, _, params = family
    engine = _engine(cfg, params, n_slots=1, page_pool_tokens=64)
    prompts = _prompts(seed=1)[:2]
    for prompt in prompts:
        assert engine.slots.free_count == 1
        handles, rows = _serve(engine, [prompt], n_new=4)
        assert _worst_miss(params, model, [prompt], handles, rows, 4) < TOL
        assert float(jnp.max(jnp.abs(engine.slots.cache["ssm_state"]))) > 0
    assert engine.metrics_snapshot()["state_resets"] == 2


@pytest.mark.parametrize("control", ["advance_on_padding", "ungated_decode", "bfloat16_state"])
def test_state_controls_are_far_outside(family, monkeypatch, control):
    """What the engine's gates are for, for this state too: a chunk program
    that runs the state through its padded tail and a decode step that
    advances every slot's state whoever decodes each hold logits far from
    the reference's; and the state held in bfloat16 between ticks is
    outside the float32 tolerance."""
    from zero_transformer_tpu.models import gpt
    from zero_transformer_tpu.serving import engine as eng

    cfg, model, _, params = family
    real_apply = Transformer.apply
    real_leaves = kda.kda_state_leaves

    def narrow(cfg, rows, dtype):
        leaves = real_leaves(cfg, rows, dtype)
        shape, _ = leaves[kda.SSM_LEAF]
        return dict(leaves, **{kda.SSM_LEAF: (shape, jnp.bfloat16)})

    if control == "bfloat16_state":
        monkeypatch.setattr(kda, "kda_state_leaves", narrow)
        monkeypatch.setattr(gpt, "kda_state_leaves", narrow)

    def blind(self, variables, tokens, *a, valid=None, **kw):
        T = tokens.shape[1]
        if valid is not None and (T > 1) == (control == "advance_on_padding"):
            valid = jnp.full_like(valid, T)
        return real_apply(self, variables, tokens, *a, valid=valid, **kw)

    monkeypatch.setattr(Transformer, "apply", blind)
    monkeypatch.setattr(eng, "_FUSED_SHARED", eng._jit_fused_step(fresh=True))
    monkeypatch.setattr(eng, "_PAGED_CHUNK_SHARED", eng._jit_paged_chunk(fresh=True))
    engine = _engine(cfg, params)
    prompts = _prompts()
    handles, rows = _serve(engine, prompts)
    far = 10 if control == "bfloat16_state" else 100
    assert _worst_miss(params, model, prompts, handles, rows) > far * TOL


def test_what_needs_the_state_elsewhere_is_refused_by_name(family):
    cfg, _, _, params = family
    with pytest.raises(ValueError, match="draft_k > 0 is refused.*recurrent state"):
        _engine(cfg, params, draft_k=2)
    with pytest.raises(ValueError, match="multiple of prefill_chunk.*recurrent state"):
        _engine(cfg, params, cache_len=60)
    with pytest.raises(ValueError, match="role must be 'mixed'.*recurrent state"):
        _engine(cfg, params, role="decode")
    engine = _engine(cfg, params, prefix_cache_chunks=16)
    assert engine.metrics_snapshot()["state_refusals_prefix_cache"] == 1
    with pytest.raises(ValueError, match="export_page_span is refused.*recurrent state"):
        engine.slots.export_page_span(0, 3)
    rejected = engine.import_stream({"kind": "decode"})
    assert rejected.status == "rejected" and "recurrent state" in rejected.error


# ---- (f) the configuration guard --------------------------------------------


def test_the_guard_accepts_this_stack_and_leaves_granite_scanned():
    cfg = model_config("kimi_linear_test")
    assert cfg.hybrid and not cfg.scan_layers and cfg.latent_attention
    assert cfg.q_lora_rank is None and cfg.position == "none"
    granite = model_config("granite_hybrid_test")
    assert granite.scan_layers and granite.recurrent_kind == "mamba"


@pytest.mark.parametrize("over,match", [
    (dict(layer_pattern=("kda", "mamba")), "one kind of recurrent mixer"),
    (dict(layer_pattern=("kda", "conv")), "one period"),
    (dict(kda_heads=0), "kda_heads"),
    (dict(kda_conv=1), "kda_conv >= 2"),
    (dict(scan_layers=True), "unrolled"),
    (dict(n_experts=0, moe_experts_held=None, moe_dispatch="capacity",
          moe_dense_layers=0, moe_shared_experts=0), "needs scan_layers=True"),
    (dict(moe_experts_held=17), "moe_experts_held"),
    (dict(position="alibi"), "RoPE"),
    (dict(n_loops=2), "one pass"),
    (dict(kv_cache_dtype="int8"), "recurrent state|no int8 pages"),
])
def test_configuration_refuses_what_the_family_has_not(over, match):
    with pytest.raises(ValueError, match=match):
        model_config("kimi_linear_test", **over)


# ---- (g) published-size counts ----------------------------------------------


def test_published_size_counts_state_and_latent_bytes():
    from zero_transformer_tpu.analysis.memory import kv_bytes_per_token, state_bytes_per_slot

    import json

    config = json.loads((ROOT / "benchmark/configs/kimi_linear_48b_a3b.json").read_text())
    cfg = harness.model_config(config)
    assert cfg.num_params == config["parameters"]
    assert REF.active_params(config["model"]) == config["active_parameters"]
    kda_mixer = 3 * 2304 * 4096 + 4096 * 2304 + 3 * 4096 * 4 + 2 * (2304 * 128 + 128 * 4096) \
        + 2304 * 32 + 32 + 4096 + 128
    assert kda_mixer == 39_514_272
    mla = 2304 * 6144 + 2304 * 576 + 512 + 512 * 8192 + 4096 * 2304
    assert mla == 29_114_880
    expert = 3 * 2304 * 1024
    routed = 32 * expert + expert + 2304 * 256 + 256 + 2 * 2304
    assert kda_mixer + routed == 273_679_264 and mla + routed == 263_279_872
    assert cfg.num_params == 103_219_872 + 5 * 273_679_264 + 2 * 263_279_872 \
        + 2 * 20480 * 2304 + 2304 == 2_092_550_080
    # 6 layers x (32 x 128 x 128 float32 + 3 taps x 3 x 4096 bfloat16)
    assert state_bytes_per_slot(cfg) == cfg.state_bytes_per_slot == 6 * (2_097_152 + 73_728)
    # two latent rows of 640 bfloat16 lanes a position
    assert kv_bytes_per_token(cfg) == 2 * 640 * 2

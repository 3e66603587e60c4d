"""The latent-attention + dropless-routed family (GLM-4.7-Flash's block) at a
small size, held to its plain reference ``benchmark/reference/
glm4_moe_lite.py`` on seeded weights from the family's own ``leaf_table``:
the full forward, the absorbed form through the cache, chunked prefill +
decode through latent pages in the serving engine (dead slots, a padded
last chunk), the router's contract and the shares of a split expert layer.

Tolerances. Everything here runs in float32 at ``highest`` against a float32
reference whose sums differ only in their order: logits of size 1 agree to
a few float32 ulps of the residual stream, 2e-6 is ten times what is seen
(1.5e-7). The same forward in bfloat16 misses by 2e-3 and more, a thousand
times the tolerance (``test_bfloat16_would_fail_the_tolerance``)."""
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
from zero_transformer_tpu.models import Transformer  # noqa: E402
from zero_transformer_tpu.models.moe import DroplessMoE, route_sigmoid  # noqa: E402

TOL = 2e-6
REF = harness.load_reference({"reference": "benchmark/reference/glm4_moe_lite.py"})


def _model_group(cfg) -> dict:
    """The reference's ``model`` group from a ``ModelConfig``."""
    keys = ("vocab_size", "d_model", "n_heads", "n_layers", "max_seq_len", "rope_theta",
            "head_dim", "d_ff", "n_experts", "moe_top_k", "moe_d_ff", "moe_shared_experts",
            "moe_routed_scale", "moe_dense_layers", "scan_layers", "kv_lora_rank",
            "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "norm_eps",
            "param_dtype")
    return {k: getattr(cfg, k) for k in keys}


@pytest.fixture(scope="module")
def family():
    cfg = model_config("glm_test", param_dtype="float32", compute_dtype="float32")
    model = _model_group(cfg)
    table = REF.leaf_table(model)
    params = weights.build(table, weights.seed_key(2**31 + 5, "weights"))
    return cfg, model, table, params


def _reference(params, tokens, model):
    with jax.default_matmul_precision("highest"):
        return REF.logits(params, jnp.asarray(tokens), model, "f32")


def test_leaf_table_is_the_programs_tree_and_counts_agree(family):
    cfg, model, table, _ = family
    from zero_transformer_tpu.parallel.sharding import unbox

    abstract = jax.eval_shape(
        lambda: Transformer(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    weights.check_tree(table, unbox(abstract))
    held = sum(int(np.prod(s)) for s, _ in table.values())
    assert held == cfg.num_params == 202_184
    # what a token is multiplied by: the program also counts norm scales
    # and the selection bias, which multiply nothing
    norms = 3 * (2 * 64 + 24 + 16) + 64 + 2 * 8
    assert REF.active_params(model) == cfg.params_per_token - norms == 111_488
    assert [cfg.layer_kind(i) for i in range(3)] == ["dense", "moe", "moe"]


def test_full_forward_matches_the_reference(family):
    cfg, model, _, params = family
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 256)
    with jax.default_matmul_precision("highest"):
        got = Transformer(cfg).apply({"params": params}, toks)
    assert float(jnp.max(jnp.abs(got - _reference(params, toks, model)))) < TOL


def test_bfloat16_would_fail_the_tolerance(family):
    cfg, model, _, params = family
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 256)
    import dataclasses

    low = Transformer(dataclasses.replace(cfg, compute_dtype="bfloat16"))
    got = low.apply({"params": params}, toks).astype(jnp.float32)
    assert float(jnp.max(jnp.abs(got - _reference(params, toks, model)))) > 100 * TOL
    with jax.default_matmul_precision("highest"):
        ctrl = REF.logits(params, toks, model, "bf16")
    assert float(jnp.max(jnp.abs(ctrl - _reference(params, toks, model)))) > 100 * TOL


@pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
def test_absorbed_form_through_the_cache_is_the_naive_forward(family, paged):
    """Prefill 16 positions, then 8 single-token steps through the latent
    cache (queries absorbed, the latent attended over, the output
    up-projected) against the naive full forward."""
    cfg, model, _, params = family
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 24), 0, 256)
    dm = decode_model(cfg, 64, kv_pages=(2 * 16 + 1, 4) if paged else None)
    cache = init_cache(dm, 2)
    if paged:
        table = 1 + jnp.arange(32, dtype=jnp.int32).reshape(2, 16)
        cache = jax.tree_util.tree_map_with_path(
            lambda p, x: jnp.broadcast_to(table, x.shape) if "block_table" in str(p[-1]) else x,
            cache)
    outs = []
    with jax.default_matmul_precision("highest"):
        for window in [toks[:, :16]] + [toks[:, t:t + 1] for t in range(16, 24)]:
            logits, out = dm.apply({"params": params, "cache": cache}, window, mutable=["cache"])
            cache = out["cache"]
            outs.append(logits)
    got = jnp.concatenate(outs, axis=1)
    assert float(jnp.max(jnp.abs(got - _reference(params, toks, model)))) < TOL
    leaves = {str(p[-1].key) for p, _ in jax.tree_util.tree_leaves_with_path(cache)}
    assert "cached_latent" in leaves and not leaves & {"cached_key", "cached_value"}


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "interpret_kernel"])
def test_engine_chunked_prefill_then_decode_through_latent_pages(family, monkeypatch, kernel):
    """Three requests in four slots (one slot dead), prompts of several
    8-token chunks with a padded last one, decoded through latent pages:
    every served (greedy) token is the reference's first at its position,
    to the float32 tolerance on the logit gap, and the engine's expert
    counters count the live rows alone."""
    from zero_transformer_tpu.serving import ServingEngine

    cfg, model, _, params = family
    if kernel:
        monkeypatch.setenv("ZT_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("ZT_PALLAS_INTERPRET", raising=False)
    engine = ServingEngine(
        cfg, params, n_slots=4, cache_len=64, eos_token_id=None,
        sampling=SamplingConfig(greedy=True, repetition_penalty=1.0),
        prefill_chunk=8, page_size=4, page_pool_tokens=128,
    )
    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(0, 256, size=n)] for n in (5, 19, 8)]
    with jax.default_matmul_precision("highest"):
        handles = [engine.submit(p, max_new_tokens=6, seed=i) for i, p in enumerate(prompts)]
        engine.run_until_idle()
    for prompt, handle in zip(prompts, handles):
        served = handle.result()
        assert len(served) == 6
        rows = _reference(params, [prompt + served], model)[0][len(prompt) - 1:-1]
        gap = jnp.max(rows, axis=-1) - rows[jnp.arange(6), jnp.asarray(served)]
        assert float(jnp.max(gap)) < TOL
    snap = engine.metrics_snapshot()
    assert snap["kernel_latent_attention"] == int(kernel)
    assert snap["kernel_paged_attention"] == 0
    assert snap["kv_bytes_per_token"] == 3 * 128 * 4  # one 128-lane row a layer, float32
    steps = [a for _, track, name, _, _, a in engine.tracer.spans()
             if track == "engine" and name == "decode_step"]
    # 2 routed layers x 2 choices a DECODING row; a dead or mid-prefill
    # slot rides along in the step and is not counted
    assert all(a["moe_routed"] == 4 * a["active"] for a in steps)
    assert all(1 <= a["experts_touched"] <= a["moe_routed"] for a in steps)
    assert snap["moe_tokens_routed"] == sum(a["moe_routed"] for a in steps)
    assert snap["moe_expert_load_mean"] == pytest.approx(snap["moe_tokens_routed"] / 8)
    # a chunk-prefill program's own count rides the next decode tick's fetch:
    # (layer, expert) pairs over ALL 4 x 8 rows it computes, 2 layers of 8
    chunks = [a["prefill_experts_touched"] for a in steps if "prefill_experts_touched" in a]
    assert chunks and all(2 * 2 <= n <= 2 * 8 for n in chunks)


# ---- the routed layer on its own -------------------------------------------


def _layer(cfg, seed=0, **over):
    """A ``DroplessMoE``, its params and 12 rows of input."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (3, 4, cfg.d_model), jnp.float32)
    moe = DroplessMoE(cfg)
    params = moe.init(jax.random.PRNGKey(seed + 1), x)["params"]
    params = jax.tree.map(lambda p: p.value if hasattr(p, "value") else p, params,
                          is_leaf=lambda p: hasattr(p, "value"))
    params = dict(params, router_bias=0.05 * jax.random.normal(jax.random.PRNGKey(9), (cfg.n_experts,)))
    params.update(over)
    return moe, params, x


def _by_hand(cfg, params, x):
    """The reference's routed FFN on rows: every expert on every row, the
    unchosen at weight zero, the shared expert beside them."""
    s = REF._statics(_model_group(cfg))
    rows = x.reshape(-1, cfg.d_model)
    w = REF.route(rows, params["router"], params["router_bias"], s)
    y = sum(w[:, e:e + 1] * REF.swiglu(rows, params["wi"][e], params["gate"][e],
                                       params["wo"][e], "f32")
            for e in range(cfg.n_experts))
    sh = params["shared"]
    y = y + REF.swiglu(rows, sh["wi"]["kernel"], sh["gate"]["kernel"], sh["wo"]["kernel"], "f32")
    return y.reshape(x.shape), w


def test_no_token_is_dropped_under_the_most_uneven_routing(family):
    """A bias that sends EVERY row to experts 0 and 1: a capacity dispatch
    would drop all but its buffers' worth; here every row gets both."""
    cfg = family[0]
    bias = jnp.zeros((8,)).at[:2].set(10.0)
    moe, params, x = _layer(cfg, router_bias=bias)
    with jax.default_matmul_precision("highest"):
        out, counts = moe.apply({"params": params}, x)
        want, w = _by_hand(cfg, params, x)
    assert counts.sum(axis=0).tolist() == [12, 12, 0, 0, 0, 0, 0, 0]
    assert bool(jnp.all((w > 0).sum(axis=1) == 2))
    assert float(jnp.max(jnp.abs(out - want))) < TOL


def test_a_rows_output_does_not_depend_on_the_other_rows(family):
    cfg = family[0]
    moe, params, x = _layer(cfg)
    with jax.default_matmul_precision("highest"):
        whole, counts = moe.apply({"params": params}, x)
        alone = jnp.stack([moe.apply({"params": params}, x[b:b + 1, t:t + 1])[0][0, 0]
                           for b in range(3) for t in range(4)]).reshape(whole.shape)
        # another batch (a dead slot's junk in the middle row) changes
        # nothing of the other rows, nor of their counts
        junk = x.at[1].set(100.0)
        other, other_counts = moe.apply({"params": params}, junk)
    assert float(jnp.max(jnp.abs(whole - alone))) < TOL
    assert float(jnp.max(jnp.abs((whole - other)[jnp.asarray([0, 2])]))) < TOL
    # counts are per batch row: 4 positions x 2 choices each
    assert counts.shape == (3, 8) and counts.sum(axis=1).tolist() == [8, 8, 8]
    assert other_counts[jnp.asarray([0, 2])].tolist() == counts[jnp.asarray([0, 2])].tolist()


def test_the_bias_moves_the_choice_and_not_the_weight(family):
    cfg = family[0]
    _, params, x = _layer(cfg)
    rows = x.reshape(-1, cfg.d_model)
    plain, w_plain = route_sigmoid(rows, params["router"], jnp.zeros((8,)), 2, 1.8)
    bias = jnp.zeros((8,)).at[7].set(10.0)
    chosen, w = route_sigmoid(rows, params["router"], bias, 2, 1.8)
    assert bool(jnp.all(jnp.any(chosen == 7, axis=1)))  # the choice moved
    assert not bool(jnp.all(jnp.any(plain == 7, axis=1)))
    # the weights are the SCORES of the chosen, normalised and scaled: the
    # bias of 10 is in none of them
    s = jax.nn.sigmoid(jnp.einsum("nd,de->ne", rows, params["router"],
                                  precision=jax.lax.Precision.HIGHEST))
    picked = jnp.take_along_axis(s, chosen, axis=1)
    want = picked / (picked.sum(axis=1, keepdims=True) + 1e-20) * 1.8
    assert float(jnp.max(jnp.abs(w - want))) < 1e-6
    assert float(jnp.max(jnp.abs(w.sum(axis=1) - 1.8))) < 1e-5
    # and the layer as a whole is the reference's, bias and all
    moe = DroplessMoE(cfg)
    with jax.default_matmul_precision("highest"):
        out, _ = moe.apply({"params": dict(params, router_bias=bias)}, x)
        want_out, _ = _by_hand(cfg, dict(params, router_bias=bias), x)
    assert float(jnp.max(jnp.abs(out - want_out))) < TOL


def test_the_shares_of_a_split_expert_layer_add_up(family):
    """The layer told it holds experts 0-3 plus the layer told 4-7, the
    shared expert counted once, are the whole layer and the reference."""
    cfg = family[0]
    moe, params, x = _layer(cfg)

    def share(lo, hi, shared):
        mine = {k: v for k, v in params.items() if shared or k != "shared"}
        mine.update({k: params[k][lo:hi] for k in ("wi", "gate", "wo")})
        return DroplessMoE(cfg, experts=(lo, hi), shared=shared).apply({"params": mine}, x)

    with jax.default_matmul_precision("highest"):
        whole, counts = moe.apply({"params": params}, x)
        (a, counts_a), (b, counts_b) = share(0, 4, True), share(4, 8, False)
        want, _ = _by_hand(cfg, params, x)
    assert float(jnp.max(jnp.abs(a + b - whole))) < TOL
    assert float(jnp.max(jnp.abs(a + b - want))) < TOL
    assert float(jnp.max(jnp.abs(a - whole))) > 100 * TOL  # a share is not the layer
    # every share routes over ALL the experts
    assert counts_a.tolist() == counts_b.tolist() == counts.tolist()


# ---- configuration ----------------------------------------------------------


def test_published_size_counts_and_latent_bytes():
    """Held against active parameters (5.5 times apart at the served cut)
    and the bytes a cached position really holds."""
    from zero_transformer_tpu.analysis.memory import kv_bytes_per_token

    cut, whole = model_config("glm_4_7_flash_7l"), model_config("glm_4_7_flash")
    assert (cut.n_layers, whole.n_layers) == (7, 47)
    assert cut.num_params == 4_530_936_960 and cut.params_per_token == 816_356_480
    assert [cut.layer_kind(i) for i in range(7)] == ["dense"] + ["moe"] * 6
    assert whole.num_params == 84_677_888 + 46 * 635_311_424 + 2 * 317_194_240 + 2048
    # one 640-lane bfloat16 row a layer (512 latent + 64 key + 64 of padding)
    assert cut.latent_row == 640 and kv_bytes_per_token(cut) == 7 * 1280
    assert kv_bytes_per_token(whole) == 47 * 1280
    # the training recipes' capacity MoE counts every expert, as it did
    moe = model_config("moe_test")
    assert moe.params_per_token == moe.num_params and moe.layer_kind(0) == "moe"


@pytest.mark.parametrize("over,match", [
    (dict(moe_dispatch="sorted"), "invalid moe_dispatch"),
    (dict(moe_dispatch="capacity", moe_top_k=2), "belong to"),
    (dict(activation="gelu"), "SwiGLU"),
    (dict(head_dim=32), "head_dim must be"),
    (dict(position="alibi"), "RoPE"),
    (dict(kv_cache_dtype="int8"), "no int8 pages"),
    (dict(qk_rope_head_dim=7, head_dim=19), "even"),
    (dict(moe_dense_layers=4), "moe_dense_layers"),
    (dict(n_experts=0), "needs n_experts"),
    (dict(scan_layers=True), "scan_layers=False"),
    (dict(q_lora_rank=0), "needs q_lora_rank"),
])
def test_configuration_refuses_what_the_family_has_not(over, match):
    import dataclasses

    with pytest.raises(ValueError, match=match):
        dataclasses.replace(model_config("glm_test"), **over)


def test_a_scanned_dense_latent_stack_through_the_stacked_pool():
    """Latent attention without the routed layers, SCANNED: the latent pool
    is then one stacked leaf on the layer loop's carry, indexed by layer in
    the same scatter and gather. Chunked windows then single steps through
    latent pages against the reference's naive forward."""
    import dataclasses

    cfg = dataclasses.replace(
        model_config("glm_test", param_dtype="float32", compute_dtype="float32"),
        n_experts=0, moe_dispatch="capacity", moe_shared_experts=0, moe_dense_layers=0,
        scan_layers=True,
    )
    model = dict(_model_group(cfg), moe_dense_layers=cfg.n_layers)
    params = weights.build(REF.leaf_table(model), weights.seed_key(11, "weights"))
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 24), 0, 256)
    dm = decode_model(cfg, 64, kv_pages=(2 * 16 + 1, 4))
    cache = init_cache(dm, 2)
    assert cache["cached_latent"].shape == (3, 33, 4, 128)
    table = 1 + jnp.arange(32, dtype=jnp.int32).reshape(2, 16)
    cache = jax.tree_util.tree_map_with_path(
        lambda p, x: jnp.broadcast_to(table, x.shape) if "block_table" in str(p[-1]) else x,
        cache)
    outs = []
    with jax.default_matmul_precision("highest"):
        for window in [toks[:, :8], toks[:, 8:16]] + [toks[:, t:t + 1] for t in range(16, 24)]:
            logits, out = dm.apply({"params": params, "cache": cache}, window, mutable=["cache"])
            cache = out["cache"]
            outs.append(logits)
    got = jnp.concatenate(outs, axis=1)
    assert float(jnp.max(jnp.abs(got - _reference(params, toks, model)))) < TOL


def test_serving_form_keeps_the_router_in_the_dtype_its_scores_are_computed_from():
    """From a float32 checkpoint a bfloat16 server holds every matrix it
    multiplies in bfloat16 (the experts, the latent projections, the tables)
    and the router, its selection bias and the norm scales in float32: the
    scores are computed in float32 from float32 weights."""
    from zero_transformer_tpu.inference.generate import serving_params
    from zero_transformer_tpu.parallel.sharding import unbox

    cfg = model_config("glm_test")  # float32 params, bfloat16 compute
    dm = decode_model(cfg, 32, kv_pages=(9, 4))
    params = unbox(Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    held = weights.flatten(serving_params(dm, params))
    kept = {p for p, x in held.items() if x.dtype == jnp.float32}
    assert {p.rsplit("/", 1)[-1] for p in kept} == {"scale", "router", "router_bias"}
    assert all(x.dtype == jnp.bfloat16 for p, x in held.items() if p not in kept)
    assert held["block_1/moe/wi"].dtype == jnp.bfloat16
    assert held["block_1/attn/kv_b/kernel"].dtype == jnp.bfloat16

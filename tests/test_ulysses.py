"""Ulysses (all-to-all) sequence parallelism vs the unsharded XLA path.

Second context-parallel engine next to ring attention (the reference has
neither — SURVEY §2 checklist: SP/CP = none). Exactness is the contract:
after the head/sequence all-to-all reshard, each device's local full-T flash
call must reproduce unsharded attention for every mesh layout — including
tensor-sharded heads (global ALiBi slope slices), GQA, and packed documents.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zero_transformer_tpu.config import MeshConfig, ModelConfig
from zero_transformer_tpu.models import Transformer
from zero_transformer_tpu.ops.attention import xla_attention
from zero_transformer_tpu.ops.ulysses import ulysses_attention
from zero_transformer_tpu.parallel.mesh import make_mesh



def _qkv(B, T, H, KVH, D, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        jax.random.normal(ks[0], (B, T, H, D)),
        jax.random.normal(ks[1], (B, T, KVH, D)),
        jax.random.normal(ks[2], (B, T, KVH, D)),
    )


@pytest.mark.parametrize(
    "mesh_cfg,H,KVH,alibi",
    [
        (MeshConfig(data=2, sequence=4), 4, 4, False),
        (MeshConfig(data=2, sequence=4), 4, 4, True),
        (MeshConfig(data=1, sequence=8), 8, 8, True),
        (MeshConfig(data=2, sequence=4), 8, 4, True),  # GQA
        (MeshConfig(data=2, tensor=2, sequence=2), 4, 4, True),  # TP-sharded heads
        (MeshConfig(data=2, tensor=2, sequence=2), 8, 4, False),  # TP + GQA
    ],
)
def test_ulysses_matches_full_attention(devices, mesh_cfg, H, KVH, alibi):
    mesh = make_mesh(mesh_cfg)
    B, T, D = 2, 32, 16
    q, k, v = _qkv(B, T, H, KVH, D)
    ref = xla_attention(q, k, v, causal=True, alibi=alibi)
    out = jax.jit(
        lambda q, k, v: ulysses_attention(q, k, v, mesh, causal=True, alibi=alibi)
    )(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize(
    "mesh_cfg,H,KVH",
    [
        (MeshConfig(data=2, sequence=4), 4, 4),
        (MeshConfig(data=2, tensor=2, sequence=2), 8, 4),  # TP + GQA slopes
    ],
)
def test_ulysses_gradients_match(devices, mesh_cfg, H, KVH):
    mesh = make_mesh(mesh_cfg)
    B, T, D = 1, 32, 16
    q, k, v = _qkv(B, T, H, KVH, D)
    g = jax.random.normal(jax.random.PRNGKey(7), (B, T, H, D))

    def loss_uly(q, k, v):
        return jnp.sum(ulysses_attention(q, k, v, mesh, causal=True, alibi=True) * g)

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=True, alibi=True) * g)

    gu = jax.jit(jax.grad(loss_uly, argnums=(0, 1, 2)))(q, k, v)
    gx = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for name, a, b in zip("qkv", gu, gx):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-4, err_msg=f"d{name}")


def test_ulysses_rejects_indivisible_heads(devices):
    mesh = make_mesh(MeshConfig(data=1, sequence=8))
    q, k, v = _qkv(1, 32, 4, 4, 16)  # 4 heads cannot split over 8 seq ranks
    with pytest.raises(ValueError, match="head"):
        ulysses_attention(q, k, v, mesh)


def test_ulysses_rejects_indivisible_seq(devices):
    mesh = make_mesh(MeshConfig(data=1, sequence=8))
    q, k, v = _qkv(1, 28, 8, 8, 16)
    with pytest.raises(ValueError, match="sequence"):
        ulysses_attention(q, k, v, mesh)


# -- flash inner engine (Pallas, interpret mode) ------------------------------


@pytest.mark.parametrize(
    "mesh_cfg,H,KVH,alibi",
    [
        (MeshConfig(data=2, sequence=4), 4, 4, True),
        (MeshConfig(data=2, sequence=4), 8, 4, False),  # GQA
        (MeshConfig(data=2, tensor=2, sequence=2), 4, 4, True),  # TP slopes
    ],
)
def test_flash_ulysses_matches_full_attention(devices, mesh_cfg, H, KVH, alibi):
    mesh = make_mesh(mesh_cfg)
    B, T, D = 1, 512, 64
    q, k, v = _qkv(B, T, H, KVH, D)
    ref = xla_attention(q, k, v, causal=True, alibi=alibi)
    out = jax.jit(
        lambda q, k, v: ulysses_attention(
            q, k, v, mesh, causal=True, alibi=alibi, impl="flash", interpret=True
        )
    )(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("H,KVH,alibi", [(4, 4, True), (8, 4, False)])
def test_flash_ulysses_gradients_match(devices, H, KVH, alibi):
    mesh = make_mesh(MeshConfig(data=2, sequence=4))
    B, T, D = 2, 512, 64
    q, k, v = _qkv(B, T, H, KVH, D)
    g = jax.random.normal(jax.random.PRNGKey(7), (B, T, H, D))

    def loss_uly(q, k, v):
        return jnp.sum(
            ulysses_attention(
                q, k, v, mesh, causal=True, alibi=alibi, impl="flash", interpret=True
            )
            * g
        )

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=True, alibi=alibi) * g)

    gu = jax.jit(jax.grad(loss_uly, argnums=(0, 1, 2)))(q, k, v)
    gx = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for name, a, b in zip("qkv", gu, gx):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-3, err_msg=f"d{name}")


@pytest.mark.parametrize("impl,kwargs", [
    ("xla", {}),
    ("flash", {"interpret": True}),
])
def test_ulysses_doc_mask_matches_full_attention(devices, impl, kwargs):
    """Packed documents under Ulysses: ids all-gather to the full sequence
    inside the body, so cross-document masking is exact even when boundaries
    straddle the original sequence shards."""
    mesh = make_mesh(MeshConfig(data=2, sequence=4))
    B, T, H, D = 2, 512, 4, 64
    q, k, v = _qkv(B, T, H, H, D)
    ids = jnp.asarray(
        np.concatenate([np.zeros(200), np.ones(190), np.full(122, 2)])[None]
        .repeat(B, 0),
        jnp.int32,
    )
    g = jax.random.normal(jax.random.PRNGKey(7), (B, T, H, D))

    ref = xla_attention(q, k, v, causal=True, alibi=True, doc_ids=ids)
    out = jax.jit(
        lambda q, k, v: ulysses_attention(
            q, k, v, mesh, causal=True, alibi=True, doc_ids=ids, impl=impl, **kwargs
        )
    )(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def loss_uly(q, k, v):
        return jnp.sum(
            ulysses_attention(
                q, k, v, mesh, causal=True, alibi=True, doc_ids=ids, impl=impl,
                **kwargs
            ) * g
        )

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=True, alibi=True, doc_ids=ids) * g)

    gu = jax.jit(jax.grad(loss_uly, argnums=(0, 1, 2)))(q, k, v)
    gx = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for name, a, b in zip("qkv", gu, gx):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-3, err_msg=f"d{name}")


# -- model / train-step integration ------------------------------------------


@pytest.mark.parametrize("position", ["alibi", "rope"])
def test_model_with_ulysses_matches_single(devices, position):
    """Full model forward with cp_impl=ulysses == unsharded model."""
    cfg = ModelConfig(
        name="t", vocab_size=64, d_model=32, n_heads=4, n_layers=2,
        max_seq_len=32, dropout=0.0, compute_dtype="float32", position=position,
        cp_impl="ulysses",
    )
    mesh = make_mesh(MeshConfig(data=2, sequence=4))
    plain = Transformer(cfg)
    sharded = Transformer(cfg, mesh=mesh)
    x = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, (2, 32)), jnp.int32
    )
    params = plain.init(jax.random.PRNGKey(0), x)["params"]
    ref = plain.apply({"params": params}, x, labels=x)[1]
    out = jax.jit(lambda p, x: sharded.apply({"params": p}, x, labels=x)[1])(params, x)
    np.testing.assert_allclose(float(out), float(ref), rtol=1e-5)


def test_ulysses_train_step_decreases_loss(devices):
    """cp_impl=ulysses inside the fused ZeRO train step (remat on, bf16
    compute): the all-to-alls must compose with jax.checkpoint and the
    donated jit step exactly like ring attention does."""
    from zero_transformer_tpu.config import OptimizerConfig
    from zero_transformer_tpu.parallel import (
        init_train_state, make_plan, make_train_step,
    )
    from zero_transformer_tpu.training.optimizer import make_optimizer, make_schedule

    cfg = ModelConfig(
        name="uly_t", vocab_size=128, d_model=64, n_heads=4, n_layers=2,
        max_seq_len=32, dropout=0.0, position="alibi", remat=True,
        compute_dtype="bfloat16", cp_impl="ulysses",
    )
    opt = OptimizerConfig(peak_learning_rate=3e-3, warmup_steps=2, total_steps=40)
    mesh = make_mesh(MeshConfig(data=2, sequence=4))
    model = Transformer(cfg, mesh=mesh)
    tx = make_optimizer(opt)
    plan = make_plan(model, tx, mesh, (4, 32), zero_stage=1)
    state = init_train_state(model, tx, jax.random.PRNGKey(0), mesh, (4, 32), plan)
    step = make_train_step(model, tx, mesh, plan, 1, make_schedule(opt))

    batch = jnp.asarray(
        np.random.default_rng(0).integers(0, 128, (1, 4, 32)), jnp.int32
    )
    losses = []
    rng = jax.random.PRNGKey(1)
    for _ in range(15):
        state, metrics = step(state, batch, rng)
        losses.append(float(metrics["loss"]))
        assert np.isfinite(losses[-1]) and np.isfinite(float(metrics["grad_norm"]))
    assert losses[-1] < losses[0] - 0.5, f"no learning under ulysses: {losses}"


def test_ulysses_with_remat_zero3_trains_llama_shapes(devices):
    """Ulysses composed with ZeRO-3 (FSDP) and per-block remat at
    llama-family shapes (GQA + RoPE + RMSNorm + SwiGLU, scaled down) on a
    data=4 x sequence=2 mesh — the all-to-alls must survive jax.checkpoint's
    rematerialized backward and the GSPMD ZeRO-3 param gathers."""
    from zero_transformer_tpu.config import OptimizerConfig
    from zero_transformer_tpu.parallel import (
        init_train_state, make_plan, make_train_step,
    )
    from zero_transformer_tpu.training.optimizer import make_optimizer, make_schedule

    cfg = ModelConfig(
        name="llama_uly_t", vocab_size=128, d_model=64, n_heads=4, n_kv_heads=2,
        n_layers=2, max_seq_len=32, dropout=0.0, position="rope", norm="rmsnorm",
        activation="swiglu", tie_embeddings=False, remat=True,
        compute_dtype="bfloat16", cp_impl="ulysses",
    )
    opt = OptimizerConfig(peak_learning_rate=3e-3, warmup_steps=2, total_steps=40)
    mesh = make_mesh(MeshConfig(data=4, sequence=2, zero_stage=3))
    model = Transformer(cfg, mesh=mesh)
    tx = make_optimizer(opt)
    plan = make_plan(model, tx, mesh, (4, 32), zero_stage=3)
    state = init_train_state(model, tx, jax.random.PRNGKey(0), mesh, (4, 32), plan)
    step = make_train_step(model, tx, mesh, plan, 3, make_schedule(opt))

    batch = jnp.asarray(
        np.random.default_rng(0).integers(0, 128, (1, 4, 32)), jnp.int32
    )
    losses = []
    rng = jax.random.PRNGKey(1)
    for _ in range(15):
        state, metrics = step(state, batch, rng)
        losses.append(float(metrics["loss"]))
        assert np.isfinite(losses[-1]) and np.isfinite(float(metrics["grad_norm"]))
    assert losses[-1] < losses[0] - 0.5, f"no learning under ulysses+zero3: {losses}"


def test_ulysses_step_compiles_to_all_to_all(devices):
    """The compiled HLO of a cp_impl=ulysses train step must contain
    all-to-all collectives (the engine's defining reshard) — and the ring
    engine's compiled step must contain collective-permute instead. Guards
    against either engine silently degrading to all-gather materialization."""
    from zero_transformer_tpu.config import OptimizerConfig
    from zero_transformer_tpu.parallel import (
        init_train_state, make_plan, make_train_step,
    )
    from zero_transformer_tpu.training.optimizer import make_optimizer

    mesh = make_mesh(MeshConfig(data=2, sequence=4))
    opt = OptimizerConfig(peak_learning_rate=1e-3, warmup_steps=2, total_steps=40)
    tx = make_optimizer(opt)
    batch = jnp.zeros((1, 4, 32), jnp.int32)
    rng = jax.random.PRNGKey(0)

    def hlo_for(cp_impl):
        cfg = ModelConfig(
            name=f"hlo_{cp_impl}", vocab_size=64, d_model=32, n_heads=4,
            n_layers=2, max_seq_len=32, dropout=0.0, cp_impl=cp_impl,
        )
        model = Transformer(cfg, mesh=mesh)
        plan = make_plan(model, tx, mesh, (4, 32), zero_stage=1)
        state = init_train_state(model, tx, jax.random.PRNGKey(0), mesh, (4, 32), plan)
        step = make_train_step(model, tx, mesh, plan, 1)
        return step.lower(state, batch, rng).compile().as_text()

    uly = hlo_for("ulysses")
    assert "all-to-all" in uly, "no all-to-all in compiled ulysses step"
    ring = hlo_for("ring")
    assert "collective-permute" in ring, "no ppermute in compiled ring step"
    assert "all-to-all" not in ring

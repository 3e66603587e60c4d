"""Pallas flash attention vs the XLA reference path (interpret mode on CPU).

The reference has no kernel tier at all — its attention materializes the full
[T, T] score matrix (reference ``src/models/layers.py:159-173``); these tests
pin the blockwise kernel to that math, forward and backward.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zero_transformer_tpu.ops.attention import xla_attention
from zero_transformer_tpu.ops.pallas.flash import flash_attention


def _make_qkv(B, T, H, KVH, D, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), dtype)
    k = jax.random.normal(ks[1], (B, T, KVH, D), dtype)
    v = jax.random.normal(ks[2], (B, T, KVH, D), dtype)
    return q, k, v


@pytest.mark.parametrize(
    "B,T,H,KVH,D,alibi",
    [
        (2, 256, 4, 4, 64, False),
        (2, 256, 4, 4, 64, True),
        (1, 128, 8, 2, 64, False),  # GQA
        (1, 128, 6, 6, 64, True),  # non-power-of-2 heads → interpolated slopes
    ],
)
def test_forward_matches_xla(B, T, H, KVH, D, alibi):
    q, k, v = _make_qkv(B, T, H, KVH, D)
    ref = xla_attention(q, k, v, causal=True, alibi=alibi)
    out = flash_attention(q, k, v, causal=True, alibi=alibi, block=64, interpret=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_forward_non_causal():
    q, k, v = _make_qkv(1, 128, 4, 4, 64)
    ref = xla_attention(q, k, v, causal=False)
    out = flash_attention(q, k, v, causal=False, block=64, interpret=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("alibi,KVH", [(False, 4), (True, 4), (False, 2)])
def test_gradients_match_xla(alibi, KVH):
    B, T, H, D = 1, 128, 4, 64
    q, k, v = _make_qkv(B, T, H, KVH, D)
    g = jax.random.normal(jax.random.PRNGKey(9), (B, T, H, D))

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=True, alibi=alibi) * g)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, alibi=alibi, block=64, interpret=True) * g
        )

    ref_grads = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    out_grads = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for name, r, o in zip("qkv", ref_grads, out_grads):
        np.testing.assert_allclose(o, r, atol=5e-5, rtol=5e-4, err_msg=f"d{name}")


def test_uneven_blocks_rejected():
    q, k, v = _make_qkv(1, 96, 4, 4, 64)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, block=64, interpret=True)


def test_bf16_forward_close():
    q, k, v = _make_qkv(1, 128, 4, 4, 64, dtype=jnp.bfloat16)
    ref = xla_attention(q, k, v, causal=True, alibi=True)
    out = flash_attention(q, k, v, causal=True, alibi=True, block=64, interpret=True)
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), atol=2e-2, rtol=2e-2
    )


# -------------------------------------------------- serving shapes (PR 11)


def _serving_case(B, C, L, H, KVH, D, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, C, H, D), dtype)
    k = jax.random.normal(ks[1], (B, L, KVH, D), dtype)
    v = jax.random.normal(ks[2], (B, L, KVH, D), dtype)
    offs = jax.random.randint(ks[3], (B,), 0, L - C + 1, jnp.int32)
    seg = (jnp.arange(L)[None, :] < (offs[:, None] + C)).astype(jnp.int32)
    return q, k, v, offs, seg


@pytest.mark.parametrize("alibi,B,C,L,H,KVH,D", [
    (True, 3, 8, 48, 4, 2, 64),    # GQA + ALiBi, chunked-prefill window
    (False, 2, 16, 64, 6, 6, 64),  # MHA, non-pow2 heads, causal only
])
def test_serving_per_row_offsets_and_validity(alibi, B, C, L, H, KVH, D):
    """The engine's cache shapes: every row's query window at its OWN
    offset (vector cache index) with a kv-validity mask — the calls the
    gate used to decline, now pinned few-ulp against the XLA path."""
    from zero_transformer_tpu.ops.pallas.flash import flash_serving

    q, k, v, offs, seg = _serving_case(B, C, L, H, KVH, D)
    ref = xla_attention(q, k, v, causal=True, alibi=alibi, q_offset=offs,
                        segment_ids=seg)
    out = flash_serving(q, k, v, causal=True, alibi=alibi, q_offset=offs,
                        segment_ids=seg, interpret=True)
    np.testing.assert_allclose(out, ref, atol=3e-6, rtol=3e-6)


def test_serving_scalar_traced_offset():
    from zero_transformer_tpu.ops.pallas.flash import flash_serving

    q, k, v, _, _ = _serving_case(2, 8, 48, 4, 4, 64, seed=3)
    off = jnp.int32(5)
    seg = jnp.broadcast_to(
        (jnp.arange(48)[None, :] < off + 8).astype(jnp.int32), (2, 48)
    )
    ref = xla_attention(q, k, v, causal=True, alibi=True, q_offset=off,
                        segment_ids=seg)
    out = flash_serving(q, k, v, causal=True, alibi=True, q_offset=off,
                        segment_ids=seg, interpret=True)
    np.testing.assert_allclose(out, ref, atol=3e-6, rtol=3e-6)


def test_serving_rope_rotated_inputs():
    """RoPE rides OUTSIDE the kernel (the model rotates q/k before the
    call); the kernel must stay exact on rotated inputs at per-row
    positions — the serving RoPE-decode shape."""
    from zero_transformer_tpu.ops.pallas.flash import flash_serving
    from zero_transformer_tpu.ops.positions import apply_rope

    B, C, L, H, D = 2, 8, 48, 4, 64
    q, k, v, offs, seg = _serving_case(B, C, L, H, H, D, seed=5)
    pos_q = offs[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    q = apply_rope(q, pos_q, 10000.0)
    k = apply_rope(k, jnp.arange(L, dtype=jnp.int32), 10000.0)
    ref = xla_attention(q, k, v, causal=True, alibi=False, q_offset=offs,
                        segment_ids=seg)
    out = flash_serving(q, k, v, causal=True, alibi=False, q_offset=offs,
                        segment_ids=seg, interpret=True)
    np.testing.assert_allclose(out, ref, atol=3e-6, rtol=3e-6)


# ------------------------------------------------------- gate honesty (PR 11)


def test_gate_and_wrapper_signatures_match():
    """The small-fix contract: every kwarg ``supported`` inspects, the
    wrapper accepts and THREADS — the gate may never advertise a
    distinction (alibi, q_offset, segment_ids, doc_ids) it then drops."""
    import inspect

    from zero_transformer_tpu.ops import flash_attention as fa

    gate = set(inspect.signature(fa.supported).parameters) - {"q", "k", "v"}
    wrapper = set(inspect.signature(fa.flash_attention).parameters) - {
        "q", "k", "v"
    }
    assert gate == wrapper, (gate, wrapper)


def test_gate_alibi_is_threaded(monkeypatch):
    """alibi=True through the DISPATCHING wrapper must change the output
    (the pre-fix gate accepted the kwarg and the wrapper dropped no
    distinction — pin that it stays that way through the serving path
    too)."""
    from zero_transformer_tpu.ops import flash_attention as fa

    monkeypatch.setenv("ZT_PALLAS_INTERPRET", "1")
    q, k, v = _make_qkv(1, 128, 4, 4, 64)
    assert fa.supported(q, k, v, causal=True, alibi=True)
    on = fa.flash_attention(q, k, v, causal=True, alibi=True)
    off = fa.flash_attention(q, k, v, causal=True, alibi=False)
    assert not np.allclose(np.asarray(on), np.asarray(off))
    # serving path threads it too
    q2, k2, v2, offs, seg = _serving_case(2, 8, 48, 4, 4, 64)
    on = fa.flash_attention(q2, k2, v2, causal=True, alibi=True,
                            q_offset=offs, segment_ids=seg)
    off = fa.flash_attention(q2, k2, v2, causal=True, alibi=False,
                             q_offset=offs, segment_ids=seg)
    assert not np.allclose(np.asarray(on), np.asarray(off))


def test_forced_flash_decodes_without_raising(monkeypatch):
    """attention_impl='flash' must not crash the decode loop: flash-or-raise
    guards the O(T^2) training shapes, but the cache branch's T=1 fallback
    is an O(S) read that is XLA/paged by design — the model downgrades
    'flash' to 'auto' there (regression: PR 11 review finding)."""
    from zero_transformer_tpu.config import model_config
    from zero_transformer_tpu.inference.generate import decode_model, generate
    from zero_transformer_tpu.inference.sampling import SamplingConfig

    monkeypatch.setenv("ZT_PALLAS_INTERPRET", "1")
    cfg = model_config(
        "test", dropout=0.0, compute_dtype="float32", attention_impl="flash"
    )
    model = decode_model(cfg, 32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    out = generate(
        model, params, jnp.asarray([[1, 5, 9, 2, 7, 3, 4, 8]], jnp.int32), 4,
        jax.random.PRNGKey(1), SamplingConfig(greedy=True),
    )
    assert out.shape == (1, 4)


def test_gate_serving_decisions(monkeypatch):
    from zero_transformer_tpu.ops import flash_attention as fa

    monkeypatch.setenv("ZT_PALLAS_INTERPRET", "1")
    q, k, v, offs, seg = _serving_case(2, 8, 48, 4, 2, 64)
    # serving shapes now accepted (traced vector offset + validity mask)
    assert fa.supported(q, k, v, causal=True, q_offset=offs, segment_ids=seg)
    # single-token decode stays declined: the paged kernel owns it
    q1 = q[:, :1]
    assert not fa.supported(q1, k, v, causal=False, q_offset=offs,
                            segment_ids=seg)
    # packed-doc masks never combine with cache shapes
    assert not fa.supported(
        q, k, v, causal=True, q_offset=offs, segment_ids=seg,
        doc_ids=jnp.zeros((2, 8), jnp.int32),
    )
    # off-TPU without interpret mode: decline everything
    monkeypatch.delenv("ZT_PALLAS_INTERPRET")
    if jax.default_backend() != "tpu":
        assert not fa.supported(q, k, v, causal=True, q_offset=offs,
                                segment_ids=seg)


# ------------------------------------------------------------------ on a mesh


def test_kernels_run_per_device_on_a_mesh(monkeypatch, devices):
    """GSPMD cannot partition a Mosaic call, so on a mesh the dispatch-site
    entries run the kernel per device (``shard_kernel``): batch over the
    data axes, heads — and their ALiBi slopes — over the tensor axis. Same
    numbers as the unsharded kernel, forward and gradients."""
    from zero_transformer_tpu.config import MeshConfig
    from zero_transformer_tpu.ops.attention import dot_product_attention
    from zero_transformer_tpu.parallel.mesh import make_mesh

    monkeypatch.setenv("ZT_PALLAS_INTERPRET", "1")
    B, T, H, D = 8, 64, 4, 16
    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(i), (B, T, H, D), jnp.float32)
        for i in range(3)
    )
    ids = jnp.repeat(jnp.arange(2), T // 2)[None].repeat(B, 0)

    def loss(q, k, v):
        out = dot_product_attention(
            q, k, v, causal=True, alibi=True, doc_ids=ids, impl="flash"
        )
        return jnp.sum(out * out), out

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))
    ref_g, ref_out = grad(q, k, v)
    mesh = make_mesh(MeshConfig(data=4, tensor=2))
    with jax.set_mesh(mesh):
        got_g, got_out = jax.jit(
            jax.grad(loss, argnums=(0, 1, 2), has_aux=True)
        )(q, k, v)
    np.testing.assert_allclose(got_out, ref_out, rtol=1e-6, atol=1e-6)
    for a, b in zip(got_g, ref_g):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_gate_declines_what_the_mesh_does_not_divide(monkeypatch, caplog, devices):
    """A batch the data axes do not divide: ``auto`` takes the XLA path and
    says so ONCE, ``flash`` raises — never a silent per-device recompute of
    the whole batch."""
    import logging

    from zero_transformer_tpu.config import MeshConfig
    from zero_transformer_tpu.ops.attention import dot_product_attention
    from zero_transformer_tpu.ops.pallas import kernel_traces
    from zero_transformer_tpu.parallel.mesh import make_mesh

    monkeypatch.setenv("ZT_PALLAS_INTERPRET", "1")
    mesh = make_mesh(MeshConfig(data=8))
    q = jax.random.normal(jax.random.PRNGKey(0), (12, 64, 4, 16), jnp.float32)

    def run(impl, q):
        with jax.set_mesh(mesh):
            return jax.jit(
                lambda q: dot_product_attention(q, q, q, causal=True, impl=impl)
            )(q)

    before = kernel_traces["flash_fwd"]
    with caplog.at_level(logging.WARNING, logger="zero_transformer_tpu"):
        run("auto", q)
        run("auto", q * 2)
    assert kernel_traces["flash_fwd"] == before  # XLA path both times
    assert sum("does not divide" in r.message for r in caplog.records) == 1
    with pytest.raises(NotImplementedError):
        run("flash", q)
    run("auto", q[:8])  # divisible: the kernel is in the program
    assert kernel_traces["flash_fwd"] > before

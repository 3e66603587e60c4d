"""Interpret-mode parity report for the Pallas kernel lane.

ONE implementation of the correctness half of the per-op kernel A/B,
shared by ``scripts/train_step_bench.py`` (the ``interpret_parity`` block
of BENCH_step.json) and ``bench.py``'s flash child (its off-TPU output) —
the two artifacts must never assert different parity contracts
(tolerances, shapes, the jit-boundary rule) for the same kernels.

Cases:
- ``flash_train_fwd_bwd`` — the differentiable training kernel, forward
  and gradients, few-ulp vs ``xla_attention``;
- ``flash_serving_offsets_mask`` — the serving entry (per-row offsets +
  kv-validity mask), few-ulp;
- ``paged_decode_vs_gather`` — the paged decode kernel, few-ulp vs the
  gather-to-slab path it replaces. Both sides run under jit with the
  gather INSIDE the reference program, as the engine's fused step computes
  take + attention in one compiled program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

FWD_TOL = 3e-5
BWD_TOL = 3e-4


def interpret_parity_report() -> dict:
    """Run all three parity cases in Pallas interpret mode on THIS backend
    and return the labeled report (no timing — timed kernel numbers are
    TPU-only by the repo's provenance discipline)."""
    from zero_transformer_tpu.ops.attention import xla_attention
    from zero_transformer_tpu.ops.pallas.flash import (
        flash_attention, flash_serving,
    )
    from zero_transformer_tpu.ops.pallas.paged_attention import paged_attention

    cases = []
    # training shape, fwd + grads, few-ulp bar
    B, T, H, D = 2, 128, 4, 64
    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(i), (B, T, H, D), jnp.float32)
        for i in range(3)
    )
    ref = xla_attention(q, k, v, causal=True, alibi=True)
    out = flash_attention(q, k, v, causal=True, alibi=True, block=64,
                          interpret=True)
    fwd_diff = float(jnp.max(jnp.abs(ref - out)))
    g = jax.random.normal(jax.random.PRNGKey(9), (B, T, H, D))
    ref_g = jax.grad(lambda q: jnp.sum(
        xla_attention(q, k, v, causal=True, alibi=True) * g))(q)
    out_g = jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, causal=True, alibi=True, block=64, interpret=True) * g))(q)
    bwd_diff = float(jnp.max(jnp.abs(ref_g - out_g)))
    cases.append({
        "case": "flash_train_fwd_bwd", "shape": [B, T, H, D],
        "max_abs_diff_fwd": fwd_diff, "max_abs_diff_bwd": bwd_diff,
        "ok": fwd_diff < FWD_TOL and bwd_diff < BWD_TOL,
    })

    # serving shape: per-row offsets + kv-validity mask
    L = 192
    offs = jnp.asarray([0, 40], jnp.int32)
    kl = jax.random.normal(jax.random.PRNGKey(3), (B, L, H, D), jnp.float32)
    vl = jax.random.normal(jax.random.PRNGKey(4), (B, L, H, D), jnp.float32)
    qc = q[:, :64]
    seg = (jnp.arange(L)[None, :] < (offs[:, None] + 64)).astype(jnp.int32)
    ref = xla_attention(qc, kl, vl, causal=True, alibi=True, q_offset=offs,
                        segment_ids=seg)
    out = flash_serving(qc, kl, vl, causal=True, alibi=True, q_offset=offs,
                        segment_ids=seg, interpret=True)
    sdiff = float(jnp.max(jnp.abs(ref - out)))
    cases.append({
        "case": "flash_serving_offsets_mask", "shape": [B, 64, H, D],
        "max_abs_diff_fwd": sdiff, "ok": sdiff < FWD_TOL,
    })

    # paged decode kernel vs the gather-to-slab path it replaces: f32
    # few-ulp (summation order only — the kernel module's exactness contract)
    page, n_blocks = 16, 4
    n_pages = 12
    S = page * n_blocks
    kp = jax.random.normal(jax.random.PRNGKey(5), (n_pages, page, H, D), jnp.float32)
    vp = jax.random.normal(jax.random.PRNGKey(6), (n_pages, page, H, D), jnp.float32)
    table = jax.random.randint(
        jax.random.PRNGKey(7), (B, n_blocks), 1, n_pages, jnp.int32
    )
    doff = jnp.asarray([17, 42], jnp.int32)

    def _gather_ref(q, kp, vp, tbl, o):
        gk = jnp.take(kp, tbl, axis=0).reshape(B, S, H, D)
        gv = jnp.take(vp, tbl, axis=0).reshape(B, S, H, D)
        s = (jnp.arange(S)[None, :] < (o[:, None] + 1)).astype(jnp.int32)
        return xla_attention(q, gk, gv, causal=False, alibi=True,
                             q_offset=o, segment_ids=s)

    ref = jax.jit(_gather_ref)(q[:, :1], kp, vp, table, doff)
    out = jax.jit(lambda q, kp, vp, t, o: paged_attention(
        q, kp, vp, t, o, causal=False, alibi=True, interpret=True,
    ))(q[:, :1], kp, vp, table, doff)
    pdiff = float(jnp.max(jnp.abs(ref - out)))
    cases.append({
        "case": "paged_decode_vs_gather", "shape": [B, 1, H, D],
        "page_size": page, "max_abs_diff_fwd": pdiff, "ok": pdiff < FWD_TOL,
    })

    return {
        "provenance": "interpret_mode_parity",
        "platform": jax.default_backend(),
        "cases": cases,
        "ok": all(c["ok"] for c in cases),
    }

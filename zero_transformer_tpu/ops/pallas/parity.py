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

``latent_vs_gather`` is ``paged_vs_gather``'s twin for the latent decode
kernel (``chip_smoke.py``'s ``kernels`` phase, ``tests``);
``ssm_update_vs_xla`` holds the decode state-update kernel to its plain
``jax.numpy`` twin.
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
    paged = paged_vs_gather(
        B=B, T=1, H=H, KVH=H, D=D, page=16, n_blocks=4, dtype=jnp.float32,
        int8=False, interpret=True,
    )
    cases.append({
        "case": "paged_decode_vs_gather", "shape": [B, 1, H, D],
        "page_size": 16, "max_abs_diff_fwd": paged["max_abs_diff"],
        "ok": paged["max_abs_diff"] < FWD_TOL,
    })

    return {
        "provenance": "interpret_mode_parity",
        "platform": jax.default_backend(),
        "cases": cases,
        "ok": all(c["ok"] for c in cases),
    }


def paged_vs_gather(
    *, B: int, T: int, H: int, KVH: int, D: int, page: int, n_blocks: int,
    dtype, int8: bool, alibi: bool = True, seed: int = 0,
    interpret: bool = False, ragged: bool = False,
) -> dict:
    """The paged decode kernel against the gather-to-slab path it replaces
    (``jnp.take(pool, table)`` + ``xla_attention``'s per-row branch), on
    random q / pools / scales from ``seed``, a block table that is a random
    permutation of distinct pages, and per-row offsets that include a page
    boundary, one before it, and a full cache. ``ragged`` is the batch a
    server at low load hands the kernel, whose walk is bounded by each
    row's own length: one row full, one at offset 0, the others a few pages
    long (one ending on a page's last position), and every table entry
    past a row's live pages the trash page 0. ``interpret=False`` on a TPU
    is the Mosaic-compiled kernel — how ``chip_smoke.py`` holds it to the
    on-chip bar of the kernel module's exactness contract.

    Differences are in units of one ``dtype`` ulp at the output's scale
    (``eps * max|ref|``). ``control_ulps`` is the same measure for a
    reference whose offsets are ONE position off — a bar means something
    only while that control is far above it."""
    from zero_transformer_tpu.ops.attention import xla_attention
    from zero_transformer_tpu.ops.pallas.paged_attention import paged_attention

    S = page * n_blocks
    n_pages = B * n_blocks + 1  # page 0: the serving layer's trash page
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    q = jax.random.normal(ks[0], (B, T, H, D), dtype)
    if int8:
        k_pool, v_pool = (
            jax.random.randint(k, (n_pages, page, KVH, D), -127, 128, jnp.int32)
            .astype(jnp.int8) for k in ks[1:3]
        )
        scales = tuple(
            jax.random.uniform(k, (n_pages, page, KVH, 1), jnp.float32, 1e-3, 2e-2)
            for k in ks[3:5]
        )
    else:
        k_pool, v_pool = (
            jax.random.normal(k, (n_pages, page, KVH, D), dtype) for k in ks[1:3]
        )
        scales = ()
    table = (1 + jax.random.permutation(ks[5], n_pages - 1)).reshape(B, n_blocks)
    table = table.astype(jnp.int32)
    if ragged:
        offsets = jax.random.randint(ks[6], (B,), 1, min(S, 6 * page) - T, jnp.int32)
        edges = jnp.asarray([S - T, 0, 2 * page - T])
    else:
        offsets = jax.random.randint(ks[6], (B,), 0, S - T + 1, jnp.int32)
        edges = jnp.asarray([S - T, (n_blocks // 2) * page, (n_blocks // 2) * page - 1])
    offsets = offsets.at[: min(B, 3)].set(edges[: min(B, 3)].astype(jnp.int32))
    if ragged:
        live_pages = (offsets + T + page - 1) // page
        table = jnp.where(jnp.arange(n_blocks)[None, :] < live_pages[:, None], table, 0)

    def gather(pool, scale, tbl):
        x = jnp.take(pool, tbl, axis=0)
        if scale is not None:  # the engine's dequant, verbatim
            x = (x.astype(jnp.float32) * jnp.take(scale, tbl, axis=0)).astype(dtype)
        return x.reshape(B, S, KVH, D)

    @jax.jit
    def reference(q, k_pool, v_pool, tbl, off, *scales):
        k_sc, v_sc = scales or (None, None)
        valid = (jnp.arange(S)[None, :] < (off[:, None] + T)).astype(jnp.int32)
        return xla_attention(
            q, gather(k_pool, k_sc, tbl), gather(v_pool, v_sc, tbl),
            causal=T > 1, alibi=alibi, q_offset=off, segment_ids=valid,
        )

    @jax.jit
    def kernel(q, k_pool, v_pool, tbl, off, *scales):
        k_sc, v_sc = scales or (None, None)
        return paged_attention(
            q, k_pool, v_pool, tbl, off, causal=T > 1, alibi=alibi,
            k_scale=k_sc, v_scale=v_sc, interpret=interpret,
        )

    def lanes(pool):
        # the engine's pool layout: heads merged into the lane axis
        return pool.reshape(n_pages, page, -1)

    args = (q, k_pool, v_pool, table)
    ref = reference(*args, offsets, *scales).astype(jnp.float32)
    out = kernel(
        q, lanes(k_pool), lanes(v_pool), table, offsets, *map(lanes, scales)
    ).astype(jnp.float32)
    off_by_one = jnp.where(offsets > 0, offsets - 1, offsets + 1)
    control = reference(*args, off_by_one, *scales).astype(jnp.float32)
    ulp = float(jnp.finfo(dtype).eps) * float(jnp.max(jnp.abs(ref)))
    diff = float(jnp.max(jnp.abs(out - ref)))
    return {
        "shape": {"B": B, "T": T, "H": H, "KVH": KVH, "D": D, "page": page,
                  "cache_len": S},
        "dtype": jnp.dtype(dtype).name, "int8_pages": int8, "ragged": ragged,
        "finite": bool(jnp.all(jnp.isfinite(out))),
        "max_abs_diff": diff, "ulps": diff / ulp,
        "control_ulps": float(jnp.max(jnp.abs(control - ref))) / ulp,
    }


def latent_vs_gather(
    *, B: int, T: int, H: int, R: int, value_width: int, page: int,
    n_blocks: int, dtype, seed: int = 0, interpret: bool = False,
    layers: int = 2,
) -> dict:
    """``paged_vs_gather`` for the latent decode kernel
    (``ops.pallas.latent_attention``) against its own gather path, on a
    stacked pool with a traced layer index, the ragged batch a server hands
    it: one row full, one at offset 0, one ending on a page's last position,
    the others a few pages long, every table entry past a row's live pages
    the trash page 0. Same units, same control."""
    from zero_transformer_tpu.ops.pallas import latent_attention as la

    S = page * n_blocks
    n_pages = B * n_blocks + 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, T, H, R), dtype)
    pool = jax.random.normal(ks[1], (layers, n_pages, page, R), dtype)
    table = (1 + jax.random.permutation(ks[2], n_pages - 1)).reshape(B, n_blocks)
    offsets = jax.random.randint(ks[3], (B,), 1, min(S, 6 * page) - T, jnp.int32)
    edges = jnp.asarray([S - T, 0, 2 * page - T], jnp.int32)
    offsets = offsets.at[: min(B, 3)].set(edges[: min(B, 3)])
    live_pages = (offsets + T + page - 1) // page
    table = jnp.where(
        jnp.arange(n_blocks)[None, :] < live_pages[:, None], table, 0
    ).astype(jnp.int32)
    kw = dict(value_width=value_width, causal=T > 1, softmax_scale=R ** -0.5)
    layer = jnp.int32(layers - 1)
    reference = jax.jit(lambda q, pool, tbl, off, l: la.gather_attention(
        q, pool, tbl, off, layer=l, **kw))
    kernel = jax.jit(lambda q, pool, tbl, off, l: la.latent_paged_attention(
        q, pool, tbl, off, layer=l, interpret=interpret, **kw))
    ref = reference(q, pool, table, offsets, layer).astype(jnp.float32)
    out = kernel(q, pool, table, offsets, layer).astype(jnp.float32)
    off_by_one = jnp.where(offsets > 0, offsets - 1, offsets + 1)
    control = reference(q, pool, table, off_by_one, layer).astype(jnp.float32)
    ulp = float(jnp.finfo(dtype).eps) * float(jnp.max(jnp.abs(ref)))
    diff = float(jnp.max(jnp.abs(out - ref)))
    return {
        "shape": {"B": B, "T": T, "H": H, "row": R, "value_width": value_width,
                  "page": page, "cache_len": S},
        "dtype": jnp.dtype(dtype).name,
        "finite": bool(jnp.all(jnp.isfinite(out))),
        "max_abs_diff": diff, "ulps": diff / ulp,
        "control_ulps": float(jnp.max(jnp.abs(control - ref))) / ulp,
    }


def ssm_update_vs_xla(
    *, rows: int, heads: int, head_dim: int, d_state: int, layers: int = 2,
    seed: int = 0, interpret: bool = False, time_calls: int = 0,
) -> dict:
    """The decode state-update kernel (``ops.pallas.ssm_update``) against
    its plain ``jax.numpy`` twin on a stacked state with a traced layer
    index, some rows not decoding, both under jit with the state donated:
    the new state and ``y`` in float32 ulps at their own scale. The control
    is a STALE state: the same step from the state one step earlier, which
    must land far outside. With ``time_calls`` also the mean seconds of a
    call of each, waited for (a number for a TPU only)."""
    import time

    from zero_transformer_tpu.ops.pallas import ssm_update as su

    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    f32 = jnp.float32
    state = jax.random.normal(ks[0], (layers, rows, heads, head_dim, d_state), f32)
    x = jax.random.normal(ks[1], (rows, heads, head_dim), f32)
    dt = jax.nn.softplus(jax.random.normal(ks[2], (rows, heads), f32))
    A = -jnp.exp(jax.random.normal(ks[3], (heads,), f32))
    Bm = jax.random.normal(ks[4], (rows, d_state), f32)
    Cm = jax.random.normal(ks[5], (rows, d_state), f32)
    D = jax.random.normal(ks[6], (heads,), f32)
    live = jax.random.bernoulli(ks[7], 0.75, (rows,)).at[0].set(True)
    layer = jnp.int32(layers - 1)
    small = (x, dt, A, Bm, Cm, D, live, layer)
    reference = jax.jit(
        lambda st, *a: su.ssm_update_reference(st, *a), donate_argnums=(0,))
    kernel = jax.jit(
        lambda st, *a: su.ssm_update(st, *a, interpret=interpret), donate_argnums=(0,))
    y_ref, new_ref = reference(state + 0.0, *small)
    y, new = kernel(state + 0.0, *small)
    # the stale control: the state as it was one step earlier (decayed back)
    y_stale, _ = reference(state * 0.5, *small)

    def ulps(a, b):
        scale = float(jnp.finfo(f32).eps) * float(jnp.max(jnp.abs(b)))
        return float(jnp.max(jnp.abs(a - b))) / scale

    out = {
        "shape": {"rows": rows, "heads": heads, "head_dim": head_dim,
                  "d_state": d_state, "layers": layers},
        "finite": bool(jnp.all(jnp.isfinite(y)) & jnp.all(jnp.isfinite(new))),
        "ulps": max(ulps(y, y_ref), ulps(new, new_ref)),
        "state_ulps": ulps(new, new_ref), "y_ulps": ulps(y, y_ref),
        "idle_rows_kept": bool(jnp.all(jnp.where(
            live[:, None, None, None], True, new[layers - 1] == state[layers - 1]))),
        "other_layers_kept": bool(jnp.all(new[: layers - 1] == state[: layers - 1])),
        "control_ulps": ulps(y_stale, y_ref),
    }
    for name, fn in (("kernel_s", kernel), ("xla_s", reference)) if time_calls else ():
        st = jax.block_until_ready(fn(state + 0.0, *small)[1])
        t0 = time.perf_counter()
        for _ in range(time_calls):
            _, st = fn(st, *small)
        jax.block_until_ready(st)
        out[name] = (time.perf_counter() - t0) / time_calls
    return out

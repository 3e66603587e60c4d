"""Ring attention: exact causal attention over a sequence-sharded mesh axis.

The reference has NO sequence/context parallelism — its long-context story is
ALiBi length extrapolation plus *reducing* context to dodge OOM (reference
``src/models/layers.py:80-101``, ``logs/1B.md:7``; SURVEY §2 checklist). This
module adds the TPU-native mechanism: activations stay sharded [B, T/n, H, D]
over the ``sequence`` mesh axis; K/V shards rotate around the ring with
``lax.ppermute`` (ICI neighbor exchange) while each device folds one KV shard
per step into an online-softmax merge. Attention is exact (same numerics as a
full all-gather) but peak memory per chip stays at one KV shard per in-flight
step and the transfers overlap with the block compute.

Two inner engines:

- **flash** (default on TPU): each ring step is one Pallas flash-attention
  call at the shard's global position offsets (``ops/pallas/flash.py
  flash_partial``), merged across steps by logsumexp weights; the backward is
  a ring of ``flash_grads`` calls against the GLOBAL lse (the flash identity
  p = exp(s - lse) makes per-shard backwards independent), with (dk, dv)
  accumulators riding the same ppermute ring home to their owners. HBM per
  step stays at flash-kernel level — no [t, t] score matrix ever exists.
- **xla** fallback (CPU tests, unsupported shapes): the same merge with plain
  einsums, rematerialized per step via ``jax.checkpoint``.

Global-view entry: ``ring_attention(q, k, v, mesh, ...)`` wraps the SPMD body
in ``shard_map`` with specs derived from the mesh (batch over data/fsdp axes,
sequence over ``sequence``, heads over ``tensor``), so it drops into a jitted
train step like any other op.
"""
from __future__ import annotations

import contextvars
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from zero_transformer_tpu.ops.positions import NEG_INF, alibi_slopes
from zero_transformer_tpu.parallel.mesh import (
    DATA_AXIS,
    FSDP_AXIS,
    SEQUENCE_AXIS,
    TENSOR_AXIS,
)

_INIT_M = -1e30


# -- shared helpers -----------------------------------------------------------


def _specs(mesh: Mesh, B: int, tp: int):
    batch_axes = tuple(a for a in (DATA_AXIS, FSDP_AXIS) if mesh.shape.get(a, 1) > 1)
    # keep only batch axes whose product divides B (small eval batches stay
    # replicated rather than erroring)
    while batch_axes and B % math.prod(mesh.shape[a] for a in batch_axes):
        batch_axes = batch_axes[:-1]
    head_axis = TENSOR_AXIS if tp > 1 else None
    qkv = P(batch_axes or None, SEQUENCE_AXIS, head_axis, None)
    lse = P(batch_axes or None, head_axis, SEQUENCE_AXIS, None)
    return qkv, lse


def _engine_ctx(mesh: Mesh, specs: tuple):
    """Resolve (mesh_arg, manual_axes, restricted_specs) for the engine's
    shard_maps so the context-parallel engines NEST inside the explicit
    ZeRO shard_map core (round-5: ZeRO-2/3 x sequence-parallel previously
    fell back to the GSPMD hint path, which compiled to ZERO
    reduce-scatters and weight-sized all-reduces — stage-1 traffic).

    Standalone (no ambient manual axes): unchanged full behavior — the
    engine manualizes every axis its specs mention (batch over data/fsdp,
    sequence, tensor), which the Pallas kernels require (GSPMD cannot
    auto-partition a pallas_call). Nested inside a partial-manual region:
    the axes already manual there (the ZeRO data/fsdp axes) are dropped
    from the specs — the batch dim arrives pre-sliced — and the engine
    manualizes only what remains; shard_map must then be handed the
    ambient ABSTRACT mesh, whose axis types record what is already manual
    (a concrete all-Auto mesh is rejected inside the region).
    """
    amesh = jax.sharding.get_abstract_mesh()
    ctx_manual: set = set()
    mesh_arg = mesh
    if amesh.axis_names and dict(amesh.shape) == dict(mesh.shape):
        ctx_manual = {
            name for name, t in zip(amesh.axis_names, amesh.axis_types)
            if t == jax.sharding.AxisType.Manual
        }
        if ctx_manual:
            mesh_arg = amesh
    mentioned: set = set()
    for s in specs:
        for e in s:
            if e is not None:
                mentioned |= set(e) if isinstance(e, tuple) else {e}
    axes = frozenset(mentioned - ctx_manual)

    def drop(spec: P) -> P:
        def keep(e):
            if e is None:
                return None
            kept = tuple(
                a for a in (e if isinstance(e, tuple) else (e,))
                if a not in ctx_manual
            )
            return kept if len(kept) > 1 else (kept[0] if kept else None)

        return P(*(keep(e) for e in spec))

    return mesh_arg, axes, tuple(drop(s) for s in specs)


# True while tracing an engine body that is NESTED inside another manual
# region (set by _engine_shard_map; read at trace time, so the chosen branch
# is baked per compiled program).
_NESTED_ENGINE = contextvars.ContextVar("zt_engine_nested", default=False)


def _axis_rank(name: str, size: int) -> jax.Array:
    """``jax.lax.axis_index``, except under NESTED partial-manual shard_map
    lowering: there, axis_index's Shardy lowering emits its own
    sdy.manual_computation binding EVERY manual axis, which is rejected
    ("operates on axis ... already bound by a parent" — upstream; plain
    collectives lower fine). The nested branch derives the rank from a tiny
    psum_scatter of an identical arange (device r's slice sums to size*r);
    the standalone hot path keeps the free axis_index."""
    if size == 1:
        return jnp.zeros((), jnp.int32)
    if not _NESTED_ENGINE.get():
        return jax.lax.axis_index(name)
    s = jax.lax.psum_scatter(
        jnp.arange(size, dtype=jnp.int32), name, scatter_dimension=0, tiled=True
    )
    return s[0] // size


def _engine_shard_map(fn, mesh, in_specs, out_specs, axes, operands):
    """ONE shard_map for an engine body, with the nested-context flag set
    while the body traces (see ``_axis_rank``). ``mesh`` carrying any
    Manual axis type marks the nested case."""
    nested = not isinstance(mesh, Mesh) and any(
        t == jax.sharding.AxisType.Manual for t in mesh.axis_types
    )
    token = _NESTED_ENGINE.set(nested)
    try:
        return shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            axis_names=axes, check_vma=False,
        )(*operands)
    finally:
        _NESTED_ENGINE.reset(token)


def _explicit_vjp_engine(body, mesh, qkv_spec, ids_spec, axes, q, k, v, ids):
    """Run ``body(q, k, v, ids)`` under one engine shard_map with an
    EXPLICIT recompute vjp: the backward differentiates the body INSIDE a
    fresh shard_map from the saved q/k/v/ids instead of letting jax
    transpose the forward shard_map — that transpose mis-lowers when the
    engine nests inside the explicit ZeRO core. Shared by the XLA-fallback
    ring and the Ulysses engine (the flash ring hand-rolls the same
    structure because its backward consumes the forward's lse)."""
    return _engine_vjp_call(q, k, v, ids, body, mesh, qkv_spec, ids_spec, axes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _engine_vjp_call(q, k, v, ids, body, mesh, qkv_spec, ids_spec, axes):
    return _engine_shard_map(
        body, mesh, (qkv_spec,) * 3 + (ids_spec,), qkv_spec, axes,
        (q, k, v, ids),
    )


def _engine_vjp_fwd(q, k, v, ids, body, mesh, qkv_spec, ids_spec, axes):
    out = _engine_vjp_call(q, k, v, ids, body, mesh, qkv_spec, ids_spec, axes)
    return out, (q, k, v, ids)


def _engine_vjp_bwd(body, mesh, qkv_spec, ids_spec, axes, res, do):
    q, k, v, ids = res

    def bwd_body(q, k, v, ids, do):
        _, vjp = jax.vjp(lambda q, k, v: body(q, k, v, ids), q, k, v)
        return vjp(do)

    dq, dk, dv = _engine_shard_map(
        bwd_body, mesh, (qkv_spec,) * 3 + (ids_spec, qkv_spec), (qkv_spec,) * 3,
        axes, (q, k, v, ids, do),
    )
    return dq, dk, dv, jnp.zeros_like(ids)


_engine_vjp_call.defvjp(_engine_vjp_fwd, _engine_vjp_bwd)


def _local_slopes(H_global: int, H_local: int, tp: int, alibi: bool):
    """[H_local, 1] ALiBi slope table for this tensor-parallel shard (zeros
    when ALiBi is off — the kernels ignore it then)."""
    if not alibi:
        return jnp.zeros((H_local, 1), jnp.float32)
    all_slopes = alibi_slopes(H_global)
    if tp > 1:
        h_off = _axis_rank(TENSOR_AXIS, tp) * H_local
        return jax.lax.dynamic_slice_in_dim(all_slopes, h_off, H_local).reshape(
            H_local, 1
        )
    return all_slopes.reshape(H_local, 1)


def _rotate(x, axis_name: str, n: int):
    return jax.lax.ppermute(x, axis_name, [(j, (j + 1) % n) for j in range(n)])


def _validate_cp_shapes(kind: str, T: int, S: int, n: int, tp: int, H: int, KVH: int):
    """Shared entry guards for the context-parallel engines (ring / ulysses)."""
    if T != S:
        raise ValueError(f"{kind} attention requires q and kv sequence lengths equal")
    if T % n:
        raise ValueError(f"sequence length {T} not divisible by sequence axis {n}")
    if tp > 1 and (H % tp or KVH % tp):
        raise ValueError(f"heads ({H}, {KVH}) not divisible by tensor axis {tp}")
    if H % KVH:
        raise ValueError(f"query heads {H} not divisible by kv heads {KVH}")


# -- flash-backed ring (custom VJP) ------------------------------------------


def _ring_flash_fwd_body(q, k, v, ids, *, n, tp, H, causal, alibi, docs, scale, interpret):
    from zero_transformer_tpu.ops.pallas.flash import flash_partial

    B, t_q, H_l, D = q.shape
    my = _axis_rank(SEQUENCE_AXIS, n)
    q_off = my * t_q
    t_kv = k.shape[1]
    slopes = _local_slopes(H, H_l, tp, alibi)

    def fold(m, norm, acc, k_cur, v_cur, kid_cur, src):
        o_i, lse_i = flash_partial(
            q, k_cur, v_cur,
            causal=causal, alibi=alibi, softmax_scale=scale,
            q_offset=q_off, kv_offset=src * t_kv, slopes=slopes,
            q_ids=ids if docs else None, k_ids=kid_cur,
            interpret=interpret,
        )
        lse_i = lse_i[..., 0]  # [B, H_l, t_q]
        m_new = jnp.maximum(m, lse_i)
        w_prev = jnp.exp(m - m_new)
        w_i = jnp.exp(lse_i - m_new)
        norm_new = norm * w_prev + w_i
        wp = jnp.transpose(w_prev, (0, 2, 1))[..., None]  # [B, t_q, H_l, 1]
        wi = jnp.transpose(w_i, (0, 2, 1))[..., None]
        return m_new, norm_new, acc * wp + o_i * wi

    def step(carry, _):
        # ids ride the scan carry (and the ppermute ring) ONLY when packing:
        # the non-packed hot path pays zero extra collectives
        if docs:
            m, norm, acc, k_cur, v_cur, kid_cur, src = carry
        else:
            m, norm, acc, k_cur, v_cur, src = carry
            kid_cur = None
        m, norm, acc = fold(m, norm, acc, k_cur, v_cur, kid_cur, src)
        out = (
            m, norm, acc,
            _rotate(k_cur, SEQUENCE_AXIS, n), _rotate(v_cur, SEQUENCE_AXIS, n),
        )
        if docs:
            out += (_rotate(kid_cur, SEQUENCE_AXIS, n),)
        return out + ((src - 1) % n,), None

    m0 = jnp.full((B, H_l, t_q), _INIT_M, jnp.float32)
    n0 = jnp.zeros((B, H_l, t_q), jnp.float32)
    a0 = jnp.zeros((B, t_q, H_l, D), jnp.float32)
    init = (m0, n0, a0, k, v) + ((ids,) if docs else ()) + (my,)
    # n-1 rotated steps + a final fold without the (discarded) last rotation
    carry, _ = jax.lax.scan(step, init, None, length=n - 1)
    if docs:
        m, norm, acc, k_last, v_last, kid_last, src = carry
    else:
        m, norm, acc, k_last, v_last, src = carry
        kid_last = None
    m, norm, acc = fold(m, norm, acc, k_last, v_last, kid_last, src)
    norm_safe = jnp.where(norm == 0.0, 1.0, norm)
    out = acc / jnp.transpose(norm_safe, (0, 2, 1))[..., None]
    lse = (m + jnp.log(norm_safe))[..., None]  # [B, H_l, t_q, 1]
    return out.astype(q.dtype), lse


def _ring_flash_bwd_body(
    q, k, v, ids, o, lse, do, *, n, tp, H, causal, alibi, docs, scale, interpret
):
    from zero_transformer_tpu.ops.pallas.flash import flash_grads

    B, t_q, H_l, D = q.shape
    my = _axis_rank(SEQUENCE_AXIS, n)
    q_off = my * t_q
    t_kv = k.shape[1]
    slopes = _local_slopes(H, H_l, tp, alibi)
    # rowsum(do * o) is identical for every ring step — compute it once,
    # in the kernels' [B, H, T, 1] layout
    delta = jnp.swapaxes(
        jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1), 1, 2
    )[..., None]

    def grads_at(dq, dk_rot, dv_rot, k_cur, v_cur, kid_cur, src):
        dq_i, dk_i, dv_i = flash_grads(
            q, k_cur, v_cur, o, lse, do,
            causal=causal, alibi=alibi, softmax_scale=scale,
            q_offset=q_off, kv_offset=src * t_kv, slopes=slopes, delta=delta,
            q_ids=ids if docs else None, k_ids=kid_cur,
            interpret=interpret,
        )
        return dq + dq_i, dk_rot + dk_i, dv_rot + dv_i

    def step(carry, _):
        if docs:
            dq, dk_rot, dv_rot, k_cur, v_cur, kid_cur, src = carry
        else:
            dq, dk_rot, dv_rot, k_cur, v_cur, src = carry
            kid_cur = None
        dq, dk_rot, dv_rot = grads_at(dq, dk_rot, dv_rot, k_cur, v_cur, kid_cur, src)
        # (dk, dv) accumulators ride the ring WITH their kv shard; after the
        # final rotation they land back on the shard's owner
        out = (
            dq,
            _rotate(dk_rot, SEQUENCE_AXIS, n), _rotate(dv_rot, SEQUENCE_AXIS, n),
            _rotate(k_cur, SEQUENCE_AXIS, n), _rotate(v_cur, SEQUENCE_AXIS, n),
        )
        if docs:
            out += (_rotate(kid_cur, SEQUENCE_AXIS, n),)
        return out + ((src - 1) % n,), None

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dkv0 = jnp.zeros(k.shape, jnp.float32)
    init = (dq0, dkv0, dkv0, k, v) + ((ids,) if docs else ()) + (my,)
    carry, _ = jax.lax.scan(step, init, None, length=n - 1)
    if docs:
        dq, dk, dv, k_last, v_last, kid_last, src = carry
    else:
        dq, dk, dv, k_last, v_last, src = carry
        kid_last = None
    # final step: fold the last shard, then rotate ONLY the grad accumulators
    # (the kv rotation would be discarded)
    dq, dk, dv = grads_at(dq, dk, dv, k_last, v_last, kid_last, src)
    dk = _rotate(dk, SEQUENCE_AXIS, n)
    dv = _rotate(dv, SEQUENCE_AXIS, n)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14))
def _ring_flash(
    q, k, v, ids, mesh, qkv_spec, lse_spec, ids_spec, n, tp, causal, alibi,
    scale, interpret, axes,
):
    out, _ = _ring_flash_fwd(
        q, k, v, ids, mesh, qkv_spec, lse_spec, ids_spec, n, tp, causal, alibi,
        scale, interpret, axes,
    )
    return out


def _ring_flash_fwd(
    q, k, v, ids, mesh, qkv_spec, lse_spec, ids_spec, n, tp, causal, alibi,
    scale, interpret, axes,
):
    H = q.shape[2]
    docs = ids is not None
    if not docs:  # dummy rides the ring; the static flag skips mask compute
        ids = jnp.zeros(q.shape[:2], jnp.float32)
    body = functools.partial(
        _ring_flash_fwd_body,
        n=n, tp=tp, H=H, causal=causal, alibi=alibi, docs=docs, scale=scale,
        interpret=interpret,
    )
    out, lse = _engine_shard_map(
        body, mesh, (qkv_spec, qkv_spec, qkv_spec, ids_spec),
        (qkv_spec, lse_spec), axes, (q, k, v, ids),
    )
    return out, (q, k, v, ids if docs else None, out, lse)


def _ring_flash_bwd(
    mesh, qkv_spec, lse_spec, ids_spec, n, tp, causal, alibi, scale, interpret,
    axes, res, do,
):
    q, k, v, ids, out, lse = res
    H = q.shape[2]
    docs = ids is not None
    d_ids = None if ids is None else jnp.zeros_like(ids)
    if not docs:
        ids = jnp.zeros(q.shape[:2], jnp.float32)
    body = functools.partial(
        _ring_flash_bwd_body,
        n=n, tp=tp, H=H, causal=causal, alibi=alibi, docs=docs, scale=scale,
        interpret=interpret,
    )
    dq, dk, dv = _engine_shard_map(
        body, mesh,
        (qkv_spec, qkv_spec, qkv_spec, ids_spec, qkv_spec, lse_spec, qkv_spec),
        (qkv_spec, qkv_spec, qkv_spec), axes, (q, k, v, ids, out, lse, do),
    )
    return dq, dk, dv, d_ids


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


# -- XLA fallback ring (autodiff through the scan) ---------------------------


def _block_bias(slopes, q_off, kv_off, t_q: int, t_kv: int, causal: bool):
    """[H|1, t_q, t_kv] f32 bias; offsets may be traced scalars."""
    q_pos = q_off + jnp.arange(t_q, dtype=jnp.int32)
    kv_pos = kv_off + jnp.arange(t_kv, dtype=jnp.int32)
    dist = q_pos[:, None] - kv_pos[None, :]
    bias = jnp.zeros((1, t_q, t_kv), jnp.float32)
    if slopes is not None:
        bias = bias - slopes[:, None, None] * jnp.maximum(dist, 0).astype(jnp.float32)
    if causal:
        bias = bias + jnp.where(dist >= 0, 0.0, NEG_INF).astype(jnp.float32)
    return bias


def _ring_xla_body(q, k, v, ids, *, n, tp, H, causal, alibi, docs, scale):
    """Einsum inner engine: same merge math, full [t_q, t_kv] block per step
    (rematerialized in the backward via jax.checkpoint)."""
    B, t_q, H_l, D = q.shape
    _, t_kv, KVH, _ = k.shape
    G = H_l // KVH
    qg = q.reshape(B, t_q, KVH, G, D)
    my = _axis_rank(SEQUENCE_AXIS, n)
    q_off = my * t_q
    slopes = _local_slopes(H, H_l, tp, alibi)[:, 0] if alibi else None

    @jax.checkpoint
    def fold(m, l, acc, k_cur, v_cur, kid_cur, src):
        bias = _block_bias(slopes, q_off, src * t_kv, t_q, t_kv, causal)
        s = jnp.einsum(
            "btkgd,bskd->bkgts", qg, k_cur, preferred_element_type=jnp.float32
        )
        s = s * jnp.float32(scale)
        if bias.shape[0] == 1:
            s = s + bias[None, :, None]
        else:
            s = s + bias.reshape(1, KVH, G, t_q, t_kv)
        if docs:
            same = ids[:, :, None] == kid_cur[:, None, :]  # [B, t_q, t_kv]
            s = s + jnp.where(same, 0.0, NEG_INF)[:, None, None]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        pv = jnp.einsum(
            "bkgts,bskd->btkgd", p, v_cur, preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc * alpha.transpose(0, 3, 1, 2)[..., None] + pv

    def step(carry, _):
        if docs:
            m, l, acc, k_cur, v_cur, kid_cur, src = carry
        else:
            m, l, acc, k_cur, v_cur, src = carry
            kid_cur = None
        m, l, acc = fold(m, l, acc, k_cur, v_cur, kid_cur, src)
        out = (
            m, l, acc,
            _rotate(k_cur, SEQUENCE_AXIS, n), _rotate(v_cur, SEQUENCE_AXIS, n),
        )
        if docs:
            out += (_rotate(kid_cur, SEQUENCE_AXIS, n),)
        return out + ((src - 1) % n,), None

    m0 = jnp.full((B, KVH, G, t_q), _INIT_M, jnp.float32)
    l0 = jnp.zeros((B, KVH, G, t_q), jnp.float32)
    a0 = jnp.zeros((B, t_q, KVH, G, D), jnp.float32)
    init = (m0, l0, a0, k, v) + ((ids,) if docs else ()) + (my,)
    # n-1 rotated steps + a final fold without the (discarded) last rotation
    carry, _ = jax.lax.scan(step, init, None, length=n - 1)
    if docs:
        m, l, acc, k_last, v_last, kid_last, src = carry
    else:
        m, l, acc, k_last, v_last, src = carry
        kid_last = None
    m, l, acc = fold(m, l, acc, k_last, v_last, kid_last, src)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe.transpose(0, 3, 1, 2)[..., None]
    return out.reshape(B, t_q, H_l, D).astype(q.dtype)


# -- public entry -------------------------------------------------------------


def _flash_local_ok(t_local: int, D: int, dtype, interpret: bool) -> bool:
    from zero_transformer_tpu.ops.pallas.flash import pick_block

    if pick_block(t_local, 512) is None:
        return False
    if D % 64 or D > 256:
        return False
    if dtype not in (jnp.bfloat16, jnp.float32):
        return False
    if not interpret and jax.default_backend() != "tpu":
        return False
    return True


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = True,
    alibi: bool = False,
    doc_ids: Optional[jax.Array] = None,
    softmax_scale: Optional[float] = None,
    impl: str = "auto",  # "auto" | "flash" | "xla"
    interpret: bool = False,  # run the Pallas engine interpreted (CPU tests)
) -> jax.Array:
    """Global-view ring attention. q [B,T,H,D]; k,v [B,T,KVH,D].

    T must divide by the ``sequence`` axis size; heads by the ``tensor`` axis
    size when that is >1. With sequence=1 this degrades to a single local
    fold (still correct, but use the flash/XLA paths instead).

    ``doc_ids`` [B, T] int: packed-sequence document mask — ids shard over
    the sequence axis with q, and each device's kv ids ride the ppermute
    ring with its kv shard, so cross-shard cross-document attention is
    masked exactly.
    """
    B, T, H, D = q.shape
    _, S, KVH, _ = k.shape
    n = mesh.shape[SEQUENCE_AXIS]
    tp = mesh.shape[TENSOR_AXIS]
    _validate_cp_shapes("ring", T, S, n, tp, H, KVH)
    scale = float(softmax_scale if softmax_scale is not None else 1.0 / (D**0.5))
    qkv_spec, lse_spec = _specs(mesh, B, tp)
    ids_spec = P(qkv_spec[0], SEQUENCE_AXIS)
    mesh_arg, axes, (qkv_spec, lse_spec, ids_spec) = _engine_ctx(
        mesh, (qkv_spec, lse_spec, ids_spec)
    )
    docs = doc_ids is not None
    ids = doc_ids.astype(jnp.float32) if docs else None

    use_flash = impl in ("auto", "flash") and _flash_local_ok(
        T // n, D, q.dtype, interpret
    )
    if impl == "flash" and not use_flash:
        raise NotImplementedError(
            f"flash ring attention unsupported for local shape "
            f"T/n={T // n}, D={D}, dtype={q.dtype}"
        )
    if use_flash:
        return _ring_flash(
            q, k, v, ids, mesh_arg, qkv_spec, lse_spec, ids_spec, n, tp, causal,
            alibi, scale, interpret, axes,
        )

    if not docs:
        ids = jnp.zeros((B, T), jnp.float32)
    body = functools.partial(
        _ring_xla_body, n=n, tp=tp, H=H, causal=causal, alibi=alibi, docs=docs,
        scale=scale,
    )
    return _explicit_vjp_engine(
        body, mesh_arg, qkv_spec, ids_spec, axes, q, k, v, ids
    )

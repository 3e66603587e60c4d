"""Flash attention as Pallas TPU kernels (forward + backward).

Blockwise online-softmax attention that never materializes the [T, T] score
matrix the reference allocates in full (reference ``src/models/layers.py:159-173``).
Supports causal masking, ALiBi bias (reference ``layers.py:17-44``),
grouped-query attention, and **global position offsets** so the same kernels
serve ring attention (``ops/ring_attention.py``), where each device's q / kv
shard starts at a different absolute position. Softmax statistics are carried
in float32 — the dtype discipline the reference adopted after its bf16-softmax
quality bug (reference ``logs/580.md:94-98``).

Kernels run on a [B, H, T, D] layout (Mosaic requires the blocked time axis in
the sublane position); the public wrappers transpose from the model's
[B, T, H, D] at the boundary — XLA fuses these transposes into neighboring
ops. The grid walks (batch, head, q-block, k-block) with the online-softmax
state (m, l, acc) carried in VMEM scratch across the innermost k-block
dimension; causally-skipped blocks are predicated off with ``pl.when``. The
backward pass is two more kernels over the same tiling: one carrying dq across
k-blocks, one carrying (dk, dv) across q-blocks, both recomputing
p = exp(s - lse) from the forward's saved logsumexp.

Three entry points:
- ``flash_attention``      — differentiable, self-contained (custom VJP);
- ``flash_partial``        — forward returning (out, lse); building block for
                             cross-device softmax merges (ring attention);
- ``flash_grads``          — backward given a (possibly *global*) lse/out.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from zero_transformer_tpu.ops.pallas import kernel_traces
from zero_transformer_tpu.ops.positions import NEG_INF, alibi_slopes

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
_INIT_M = -1e30  # below any finite score; never produced by real inputs


def pick_block(n: int, prefer: int, floor: int = 8) -> Optional[int]:
    """Largest sublane-aligned block <= prefer dividing n (>= ``floor``,
    multiple of 8 — the f32 sublane tile), or None if none exists.

    Shared by the wrappers and the dispatch gate (``ops.flash_attention``) so
    "supported" and "will actually run" can never disagree. The floor used
    to be 128 (MXU-efficiency conservatism); serving shapes — chunked
    prefill windows of 64, small test caches — are legal Mosaic blocks down
    to the 8-sublane tile, and the gate applies a dtype-aware floor (16 for
    bf16) on top."""
    b = min(prefer, n)
    while b >= floor:
        if n % b == 0 and b % 8 == 0:
            return b
        b //= 2
    return None


def _bias_block(slope, q_pos0, k_pos0, block_q: int, block_k: int, alibi, causal):
    """f32 additive bias for one score block whose first q/k global positions
    are ``q_pos0`` / ``k_pos0`` (traced scalars under ring attention).

    Matches ``ops.positions.alibi_bias`` / ``causal_mask_bias`` exactly
    (distance clamped at 0, mask additive NEG_INF) so the kernels are
    numerically interchangeable with the XLA path."""
    q_pos = q_pos0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = k_pos0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    dist = q_pos - k_pos
    bias = jnp.zeros((block_q, block_k), jnp.float32)
    if alibi:
        bias = bias - slope * jnp.maximum(dist, 0).astype(jnp.float32)
    if causal:
        bias = bias + jnp.where(dist >= 0, 0.0, NEG_INF).astype(jnp.float32)
    return bias


def _scores(
    slope, offs_ref, b, q_ref, k_ref, qid_ref, kid_ref, seg_ref, scale,
    alibi, causal, docs, segs, i, j
):
    """[block_q, block_k] f32 score block shared by all three kernels.

    ``docs`` (static) adds the packed-sequence document mask: positions with
    different ids (float32-encoded ints, exact ==) cannot attend. ``segs``
    (static) adds the serving path's kv validity mask: segment id 0 =
    padding / not-yet-written cache positions, masked out. Offsets are
    PER-ROW (``offs_ref`` is [2, B]; ``b`` the batch grid index) so the
    continuous-batching engine's vector cache index — every slot at its own
    position — rides the same kernels."""
    q = q_ref[0, 0, :, :]
    k = k_ref[0, 0, :, :]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    q_pos0 = offs_ref[0, b] + i * q.shape[0]
    k_pos0 = offs_ref[1, b] + j * k.shape[0]
    s = s * scale + _bias_block(
        slope, q_pos0, k_pos0, q.shape[0], k.shape[0], alibi, causal
    )
    if docs:
        same = qid_ref[0, 0, :][:, None] == kid_ref[0, 0, :][None, :]
        s = s + jnp.where(same, 0.0, NEG_INF).astype(jnp.float32)
    if segs:
        s = s + jnp.where(
            seg_ref[0, 0, :][None, :] != 0.0, 0.0, NEG_INF
        ).astype(jnp.float32)
    return s


def _run_predicate(offs_ref, b, i, j, block_q: int, block_k: int, causal: bool):
    """Does block (i, j) contain any causally-visible entry for row b?"""
    if not causal:
        return True
    first_k = offs_ref[1, b] + j * block_k
    last_q = offs_ref[0, b] + i * block_q + block_q - 1
    return first_k <= last_q


def _fwd_kernel(
    slope_ref, offs_ref, *args,
    scale: float, causal: bool, alibi: bool, docs: bool, segs: bool, n_k: int,
):
    # id/segment operands exist ONLY when their masking is on: per-grid-step
    # VMEM copies measurably slow the un-masked path (~2x at T=1024 on v5e)
    rest = list(args)
    qid_ref, kid_ref = (rest[0], rest[1]) if docs else (None, None)
    rest = rest[2:] if docs else rest
    seg_ref = rest[0] if segs else None
    rest = rest[1:] if segs else rest
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    slope = slope_ref[pl.program_id(1), 0]
    block_q, block_k = q_ref.shape[2], k_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _INIT_M)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(_run_predicate(offs_ref, b, i, j, block_q, block_k, causal))
    def _compute():
        s = _scores(
            slope, offs_ref, b, q_ref, k_ref, qid_ref, kid_ref, seg_ref,
            scale, alibi, causal, docs, segs, i, j,
        )
        v = v_ref[0, 0, :, :]
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:, :1] = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        m_scr[:, :1] = m_new
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    # the grid's k dimension is innermost-sequential: the final j visit for
    # this (b, h, i) is always j == n_k-1, even when it was causally skipped
    @pl.when(j == n_k - 1)
    def _write():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0, :, :] = (m_scr[:, :1] + jnp.log(l_safe)).astype(jnp.float32)


def _dq_kernel(
    slope_ref, offs_ref, *args,
    scale: float, causal: bool, alibi: bool, docs: bool, n_k: int,
):
    qid_ref, kid_ref = (args[0], args[1]) if docs else (None, None)
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
     dq_scr) = args[2 if docs else 0:]
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    slope = slope_ref[pl.program_id(1), 0]
    block_q, block_k = q_ref.shape[2], k_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(_run_predicate(offs_ref, b, i, j, block_q, block_k, causal))
    def _compute():
        s = _scores(
            slope, offs_ref, b, q_ref, k_ref, qid_ref, kid_ref, None,
            scale, alibi, causal, docs, False, i, j,
        )
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :].astype(jnp.float32)
        p = jnp.exp(s - lse_ref[0, 0, :, :])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0, 0, :, :])
        dq_scr[:] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == n_k - 1)
    def _write():
        dq_ref[0, 0, :, :] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(
    slope_ref, offs_ref, *args,
    scale: float, causal: bool, alibi: bool, docs: bool, n_q: int,
):
    qid_ref, kid_ref = (args[0], args[1]) if docs else (None, None)
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
     dk_scr, dv_scr) = args[2 if docs else 0:]
    # grid: (B, H, n_k, n_q) — j is the k-block, inner index i walks q-blocks
    b, j, i = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    slope = slope_ref[pl.program_id(1), 0]
    block_q, block_k = q_ref.shape[2], k_ref.shape[2]

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(_run_predicate(offs_ref, b, i, j, block_q, block_k, causal))
    def _compute():
        s = _scores(
            slope, offs_ref, b, q_ref, k_ref, qid_ref, kid_ref, None,
            scale, alibi, causal, docs, False, i, j,
        )
        q = q_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :].astype(jnp.float32)
        p = jnp.exp(s - lse_ref[0, 0, :, :])  # [bq, bk]
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0, 0, :, :])
        dk_scr[:] += scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(i == n_q - 1)
    def _write():
        dk_ref[0, 0, :, :] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scr[:].astype(dv_ref.dtype)


def _slopes_arg(n_heads: int, alibi: bool) -> jax.Array:
    if alibi:
        return alibi_slopes(n_heads).reshape(n_heads, 1)
    return jnp.zeros((n_heads, 1), jnp.float32)


def _offsets_arg(q_offset, kv_offset, B: int) -> jax.Array:
    """[2, B] int32 (q row 0, kv row 1): scalars broadcast, [B] vectors pass
    through — the per-row form the serving engine's vector cache index
    needs (every slot's query block at its own position)."""
    qo = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32).reshape(-1), (B,))
    ko = jnp.broadcast_to(jnp.asarray(kv_offset, jnp.int32).reshape(-1), (B,))
    return jnp.stack([qo, ko])


def _smem_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _ids_args(q_ids, k_ids, B, T, S):
    """[B, 1, T]/[B, 1, S] f32 id arrays — built only when document masking
    is on (the operands and their per-grid-step VMEM copies cost ~2x at
    T=1024 when present but unused).

    The singleton middle axis is load-bearing: Mosaic requires the last two
    block dims to be (div 8, div 128) or equal to the array dims. A [B, T]
    layout with (1, block) blocks violates the sublane rule on real TPUs
    (interpret mode does not enforce it); [B, 1, T] with (1, 1, block)
    blocks is legal (1 == array dim, block >= 128)."""
    qi = q_ids.astype(jnp.float32).reshape(B, 1, T)
    ki = k_ids.astype(jnp.float32).reshape(B, 1, S)
    return qi, ki


def _fwd(q, k, v, causal, alibi, scale, block_q, block_k, interpret,
         q_offset=0, kv_offset=0, slopes=None, out_dtype=None,
         q_ids=None, k_ids=None, segment_ids=None):
    # [B, T, H, D] → [B, H, T, D]: Mosaic needs the blocked time axis in the
    # sublane position
    docs = q_ids is not None
    segs = segment_ids is not None
    q, k, v = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    B, H, T, D = q.shape
    _, KVH, S, _ = k.shape
    G = H // KVH
    n_q, n_k = T // block_q, S // block_k
    id_args = _ids_args(q_ids, k_ids, B, T, S) if docs else ()
    seg_args = (
        (segment_ids.astype(jnp.float32).reshape(B, 1, S),) if segs else ()
    )

    if slopes is None:
        slopes = _slopes_arg(H, alibi)
    kernel_traces["flash_fwd"] += 1
    q_spec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h // G, j, 0))
    qid_spec = pl.BlockSpec((1, 1, block_q), lambda b, h, i, j: (b, 0, i))
    kid_spec = pl.BlockSpec((1, 1, block_k), lambda b, h, i, j: (b, 0, j))
    id_specs = [qid_spec, kid_spec] if docs else []
    seg_specs = [kid_spec] if segs else []
    o, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=causal, alibi=alibi, docs=docs,
            segs=segs, n_k=n_k,
        ),
        grid=(B, H, n_q, n_k),
        in_specs=[_smem_spec(), _smem_spec(), *id_specs, *seg_specs,
                  q_spec, kv_spec, kv_spec],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, out_dtype or q.dtype),
            jax.ShapeDtypeStruct((B, H, T, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # m (col 0 used)
            pltpu.VMEM((block_q, 128), jnp.float32),  # l
            pltpu.VMEM((block_q, D), jnp.float32),  # acc
        ],
        interpret=interpret,
        name="flash_fwd",
    )(slopes, _offsets_arg(q_offset, kv_offset, B), *id_args, *seg_args, q, k, v)
    return jnp.swapaxes(o, 1, 2), lse


def _bwd(q, k, v, o, lse, do, causal, alibi, scale, block_q, block_k, interpret,
         q_offset=0, kv_offset=0, slopes=None, grad_dtype=None, delta=None,
         q_ids=None, k_ids=None):
    docs = q_ids is not None
    q, k, v, o, do = (jnp.swapaxes(x, 1, 2) for x in (q, k, v, o, do))
    B, H, T, D = q.shape
    _, KVH, S, _ = k.shape
    G = H // KVH
    n_q, n_k = T // block_q, S // block_k
    id_args = _ids_args(q_ids, k_ids, B, T, S) if docs else ()

    if delta is None:  # rowsum(do * o) — loop-invariant for ring callers
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[..., None]

    if slopes is None:
        slopes = _slopes_arg(H, alibi)
    offs = _offsets_arg(q_offset, kv_offset, B)
    kernel_traces["flash_bwd"] += 1
    q_spec_iq = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0))
    kv_spec_iq = pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h // G, j, 0))
    row_spec_iq = pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0))

    qid_spec_iq = pl.BlockSpec((1, 1, block_q), lambda b, h, i, j: (b, 0, i))
    kid_spec_iq = pl.BlockSpec((1, 1, block_k), lambda b, h, i, j: (b, 0, j))
    id_specs_iq = [qid_spec_iq, kid_spec_iq] if docs else []
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, causal=causal, alibi=alibi, docs=docs,
            n_k=n_k,
        ),
        grid=(B, H, n_q, n_k),
        in_specs=[_smem_spec(), _smem_spec(), *id_specs_iq,
                  q_spec_iq, kv_spec_iq, kv_spec_iq,
                  q_spec_iq, row_spec_iq, row_spec_iq],
        out_specs=q_spec_iq,
        out_shape=jax.ShapeDtypeStruct(q.shape, grad_dtype or q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(slopes, offs, *id_args, q, k, v, do, lse, delta)

    # k-block-major grid; q walked innermost. dk/dv computed per *query* head
    # ([B, H, S, D]) then group-summed to KVH for GQA.
    q_spec_jq = pl.BlockSpec((1, 1, block_q, D), lambda b, h, j, i: (b, h, i, 0))
    kv_spec_jq = pl.BlockSpec((1, 1, block_k, D), lambda b, h, j, i: (b, h // G, j, 0))
    kv_out_jq = pl.BlockSpec((1, 1, block_k, D), lambda b, h, j, i: (b, h, j, 0))
    row_spec_jq = pl.BlockSpec((1, 1, block_q, 1), lambda b, h, j, i: (b, h, i, 0))
    qid_spec_jq = pl.BlockSpec((1, 1, block_q), lambda b, h, j, i: (b, 0, i))
    kid_spec_jq = pl.BlockSpec((1, 1, block_k), lambda b, h, j, i: (b, 0, j))
    id_specs_jq = [qid_spec_jq, kid_spec_jq] if docs else []
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal, alibi=alibi, docs=docs,
            n_q=n_q,
        ),
        grid=(B, H, n_k, n_q),
        in_specs=[_smem_spec(), _smem_spec(), *id_specs_jq,
                  q_spec_jq, kv_spec_jq, kv_spec_jq,
                  q_spec_jq, row_spec_jq, row_spec_jq],
        out_specs=[kv_out_jq, kv_out_jq],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), grad_dtype or k.dtype),
            jax.ShapeDtypeStruct((B, H, S, D), grad_dtype or v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(slopes, offs, *id_args, q, k, v, do, lse, delta)

    dq = jnp.swapaxes(dq, 1, 2)
    dk = jnp.swapaxes(dk, 1, 2)  # [B, S, H, D]
    dv = jnp.swapaxes(dv, 1, 2)
    if G > 1:
        dk = dk.reshape(B, S, KVH, G, D).sum(axis=3).astype(grad_dtype or k.dtype)
        dv = dv.reshape(B, S, KVH, G, D).sum(axis=3).astype(grad_dtype or v.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash(q, k, v, doc_ids, slopes, causal, alibi, scale, block_q, block_k, interpret):
    # doc_ids: [B, T] float32 (or None) — f32 so its zero cotangent below is
    # a plain zeros_like rather than float0 plumbing. slopes: [H, 1] f32 (or
    # None) overriding the ALiBi table for head-sharded callers (ulysses/TP).
    o, _ = _fwd(q, k, v, causal, alibi, scale, block_q, block_k, interpret,
                slopes=slopes, q_ids=doc_ids, k_ids=doc_ids)
    return o


def _flash_fwd(q, k, v, doc_ids, slopes, causal, alibi, scale, block_q, block_k, interpret):
    o, lse = _fwd(q, k, v, causal, alibi, scale, block_q, block_k, interpret,
                  slopes=slopes, q_ids=doc_ids, k_ids=doc_ids)
    return o, (q, k, v, doc_ids, slopes, o, lse)


def _flash_bwd(causal, alibi, scale, block_q, block_k, interpret, res, do):
    q, k, v, doc_ids, slopes, o, lse = res
    dq, dk, dv = _bwd(
        q, k, v, o, lse, do, causal, alibi, scale, block_q, block_k, interpret,
        slopes=slopes, q_ids=doc_ids, k_ids=doc_ids,
    )
    d_ids = None if doc_ids is None else jnp.zeros_like(doc_ids)
    d_slopes = None if slopes is None else jnp.zeros_like(slopes)
    return dq, dk, dv, d_ids, d_slopes


_flash.defvjp(_flash_fwd, _flash_bwd)


def _resolve_blocks(T, S, block, block_q, block_k):
    block_q = block_q or block or pick_block(T, DEFAULT_BLOCK_Q) or DEFAULT_BLOCK_Q
    block_k = block_k or block or pick_block(S, DEFAULT_BLOCK_K) or DEFAULT_BLOCK_K
    block_q, block_k = min(block_q, T), min(block_k, S)
    if T % block_q or S % block_k:
        raise ValueError(
            f"seq lengths ({T}, {S}) not divisible by blocks ({block_q}, {block_k})"
        )
    return block_q, block_k


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    alibi: bool = False,
    doc_ids: Optional[jax.Array] = None,
    softmax_scale: Optional[float] = None,
    slopes: Optional[jax.Array] = None,
    block: Optional[int] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Differentiable flash attention. q [B,T,H,D]; k,v [B,S,KVH,D].

    ``doc_ids`` [B, T] int: packed-sequence document mask (requires T == S;
    different ids cannot attend to each other). ``slopes`` [H, 1] f32
    overrides the ALiBi slope table — for head-sharded callers (ulysses / TP
    local attention) whose local head 0 is not global head 0. Slopes are
    treated as a CONSTANT of the kernel (stop_gradient applied): unlike the
    XLA path, the custom VJP does not propagate slope gradients — do not use
    this entry point with learnable slopes."""
    B, T, H, D = q.shape
    _, S, KVH, _ = k.shape
    if H % KVH:
        raise ValueError(f"query heads {H} not divisible by kv heads {KVH}")
    if doc_ids is not None and T != S:
        raise ValueError("doc_ids requires full-sequence shapes (T == S)")
    if slopes is not None:
        slopes = jax.lax.stop_gradient(slopes)
    block_q, block_k = _resolve_blocks(T, S, block, block_q, block_k)
    scale = softmax_scale if softmax_scale is not None else 1.0 / (D**0.5)
    ids = None if doc_ids is None else doc_ids.astype(jnp.float32)
    return _flash(
        q, k, v, ids, slopes, causal, alibi, float(scale), block_q, block_k,
        interpret,
    )


# graftlint: hot-path
def flash_serving(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    alibi: bool = False,
    q_offset=0,
    segment_ids: Optional[jax.Array] = None,
    softmax_scale: Optional[float] = None,
    slopes: Optional[jax.Array] = None,
    block: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Forward-only flash attention for the serving cache shapes the
    differentiable entry point cannot express:

    - ``q_offset`` scalar or PER-ROW ``[B]`` (traced): the query block of
      row r starts at global position ``q_offset[r]`` — the engine's
      chunked prefill window / spec-verify block over a vector cache index;
    - ``segment_ids`` ``[B, S]``: kv validity (0 = not-yet-written cache
      positions past each row's fill cursor, masked out exactly like the
      XLA path's pad mask).

    Decode never differentiates, so this skips the custom-VJP plumbing and
    the lse output. Numerics: same online-softmax kernel as training flash,
    pinned few-ulp against ``ops.attention.xla_attention`` (tests)."""
    B, T, H, D = q.shape
    _, S, KVH, _ = k.shape
    if H % KVH:
        raise ValueError(f"query heads {H} not divisible by kv heads {KVH}")
    if segment_ids is not None and tuple(segment_ids.shape) != (B, S):
        raise ValueError(
            f"segment_ids must be [B, S] = {(B, S)}, got {segment_ids.shape}"
        )
    if slopes is not None:
        slopes = jax.lax.stop_gradient(slopes).reshape(-1, 1).astype(jnp.float32)
    block_q, block_k = _resolve_blocks(T, S, block, None, None)
    scale = softmax_scale if softmax_scale is not None else 1.0 / (D**0.5)
    o, _ = _fwd(
        q, k, v, causal, alibi, float(scale), block_q, block_k, interpret,
        q_offset=q_offset, kv_offset=0, slopes=slopes,
        segment_ids=segment_ids,
    )
    return o


def flash_partial(
    q, k, v, *, causal, alibi, softmax_scale, q_offset, kv_offset,
    slopes=None, q_ids=None, k_ids=None, out_dtype=jnp.float32,
    block: Optional[int] = None, interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Forward-only: (out [B,T,H,D], lse [B,H,T,1]) at global offsets.

    ``out`` is normalized by the LOCAL softmax sum; merge across kv shards
    with the lse (ring attention does this — hence the f32 ``out_dtype``
    default: merged, and rounded once, by the caller). ``slopes`` overrides
    the ALiBi slope table for head-sharded calls; ``q_ids``/``k_ids`` are
    this shard's document ids (ring packing — the kv ids rotate with the kv
    shard). NOT differentiable — pair with ``flash_grads`` under a custom
    VJP (ring attention's, or the mesh dispatch's in
    ``ops.flash_attention``).
    """
    B, T, H, D = q.shape
    _, S, KVH, _ = k.shape
    block_q, block_k = _resolve_blocks(T, S, block, None, None)
    scale = softmax_scale if softmax_scale is not None else 1.0 / (D**0.5)
    return _fwd(
        q, k, v, causal, alibi, float(scale), block_q, block_k, interpret,
        q_offset=q_offset, kv_offset=kv_offset, slopes=slopes,
        out_dtype=out_dtype, q_ids=q_ids, k_ids=k_ids,
    )


def flash_grads(
    q, k, v, o, lse, do, *, causal, alibi, softmax_scale, q_offset, kv_offset,
    slopes=None, delta=None, q_ids=None, k_ids=None, grad_dtype=jnp.float32,
    block: Optional[int] = None, interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(dq, dk, dv) given the GLOBAL (out, lse) of the merged softmax —
    the flash backward identity p = exp(s - lse_global) makes per-shard
    backward passes independent (ring attention sums them across ring
    steps, hence the f32 ``grad_dtype`` default; None = the operands')."""
    B, T, H, D = q.shape
    _, S, KVH, _ = k.shape
    block_q, block_k = _resolve_blocks(T, S, block, None, None)
    scale = softmax_scale if softmax_scale is not None else 1.0 / (D**0.5)
    return _bwd(
        q, k, v, o, lse, do, causal, alibi, float(scale), block_q, block_k,
        interpret, q_offset=q_offset, kv_offset=kv_offset, slopes=slopes,
        grad_dtype=grad_dtype, delta=delta, q_ids=q_ids, k_ids=k_ids,
    )

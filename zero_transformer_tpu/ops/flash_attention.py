"""Flash attention dispatch gate for ``ops.attention.dot_product_attention``.

``supported`` decides whether the Pallas flash kernel
(``zero_transformer_tpu.ops.pallas.flash``) handles the call; anything it
declines falls back to the XLA path, keeping one call site for the hot op.

Since PR 11 the gate accepts the SERVING cache shapes it used to decline:
a traced scalar or per-row ``[B]`` ``q_offset`` (the engine's vector cache
index — chunked prefill windows, spec-verify blocks) and a ``[B, S]``
``segment_ids`` kv-validity mask both route to the forward-only
``flash_serving`` kernel entry. What still falls back to XLA, by design:

- single-token decode (T = 1 — no legal sublane block; the PAGED decode
  kernel owns that dispatch, ``ops.pallas.paged_attention``);
- non-TPU backends, unless ``ZT_PALLAS_INTERPRET=1`` opts into Pallas
  interpret mode (how this CPU image exercises the kernels' numerics);
- shapes without a sublane-aligned block decomposition, head widths the
  MXU lane layout cannot take, f16, packed doc masks on cache shapes;
- a mesh whose data axes do not divide the batch, or whose tensor axis
  does not divide the heads: an undivided dim would have every device
  compute all of it. Logged once.

On a mesh the kernels run PER DEVICE: GSPMD cannot partition a Mosaic call,
so this module — the dispatch site, not the kernel module, which knows
nothing of meshes — wraps each kernel call in a shard_map
(``parallel.sharding.shard_kernel``: batch over the data axes, heads and
their ALiBi slopes over the tensor axis, slopes and per-row offsets passed
as explicit operands so every shard gets its own). The differentiable entry
is a custom VJP built here from the kernel module's per-device forward
(``flash_partial``) and backward (``flash_grads``), each under its OWN
shard_map, so jax never has to transpose one. The context-parallel engines
(``ops.ring_attention``, ``ops.ulysses``) call the kernels from inside their
own shard_maps and do not come through here.

The gate and the wrapper share ONE keyword surface — every kwarg
``supported`` inspects, ``flash_attention`` threads to the kernel (pinned
by test: the gate may never advertise a distinction it then drops).
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp

from zero_transformer_tpu.ops.pallas.flash import (
    DEFAULT_BLOCK_K,
    DEFAULT_BLOCK_Q,
    flash_grads,
    flash_partial,
    flash_serving as _pallas_serving,
    pick_block,
)
from zero_transformer_tpu.ops.positions import alibi_slopes
from zero_transformer_tpu.parallel.sharding import kernel_shardable, shard_kernel

# logical activation names of the kernels' operands, for shard_kernel
_Q = ("batch", None, "heads", None)  # q / o / do / dq   [B, T, H, D]
_KV = ("batch", None, "kvheads", None)  # k / v / dk / dv  [B, S, KVH, D]
_LSE = ("batch", "heads", None, None)  # [B, H, T, 1]
_ROWS = ("batch", None)  # doc ids [B, T], kv validity [B, S]
_SLOPES = ("heads", None)  # [H, 1]


def interpret_enabled() -> bool:
    """``ZT_PALLAS_INTERPRET=1``: run the Pallas kernels in interpret mode
    off-TPU (CPU parity tests / bench lanes). Trace-time read — set it
    before building the model or engine. ONE implementation shared with
    the paged gate (``ops.pallas.paged_attention.interpret_requested``)
    so the two kernels can never disagree about interpret mode."""
    from zero_transformer_tpu.ops.pallas.paged_attention import (
        interpret_requested,
    )

    return interpret_requested()


@functools.cache
def _log_unshardable(B: int, H: int, KVH: int, mesh_shape: tuple) -> None:
    logging.getLogger(__name__).warning(
        "flash attention: mesh %s does not divide batch=%d / heads=%d / "
        "kv_heads=%d; attention_impl=auto takes the XLA path here",
        dict(mesh_shape), B, H, KVH,
    )


def _is_training_call(q_offset, segment_ids) -> bool:
    """Static-zero offset and no validity mask = the full-sequence
    self-attention shape the differentiable custom-VJP kernel serves."""
    return (
        isinstance(q_offset, int) and q_offset == 0 and segment_ids is None
    )


def supported(
    q, k, v, *, causal: bool, alibi: bool = False, q_offset=0,
    segment_ids=None, doc_ids=None,
) -> bool:
    B, T, H, D = q.shape
    _, S, KVH, _ = k.shape
    if H % KVH:
        return False
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not interpret_enabled():
        return False
    if q.dtype not in (jnp.bfloat16, jnp.float32) or k.dtype != q.dtype:
        return False
    if on_tpu and (D % 64 or D > 256):
        # Mosaic lane-dim constraint — interpret mode (the CPU parity
        # lane) has no tiling and accepts any structurally valid width
        return False
    if not _is_training_call(q_offset, segment_ids):
        # serving path: forward-only kernel with per-row offsets + validity
        if getattr(q_offset, "ndim", None) not in (0, 1) and not isinstance(
            q_offset, int
        ):
            return False
        if doc_ids is not None:
            return False  # cache shapes never carry packed-doc masks
        if segment_ids is not None and tuple(segment_ids.shape) != (B, S):
            return False
    if doc_ids is not None and T != S:
        return False  # document masking needs full self-attention shapes
    # alibi imposes no extra shape constraint (slopes interpolate for any
    # head count, and the per-row bias path covers vector offsets) — but it
    # IS threaded to the kernel below; the signature-parity test pins that
    bq = pick_block(T, DEFAULT_BLOCK_Q)
    bk = pick_block(S, DEFAULT_BLOCK_K)
    if bq is None or bk is None:
        return False
    if on_tpu:
        floor = 16 if q.dtype == jnp.bfloat16 else 8
        if bq % floor or bk % floor:
            return False
    if not kernel_shardable(batch=B, heads=H, kvheads=KVH):
        _log_unshardable(
            B, H, KVH, tuple(jax.sharding.get_abstract_mesh().shape.items())
        )
        return False
    return True


def _slopes(n_heads: int, alibi: bool) -> jax.Array:
    """The [H, 1] f32 slope table as an explicit operand: a head shard must
    get ITS heads' slopes, not the table of a model with fewer heads."""
    if alibi:
        return alibi_slopes(n_heads).reshape(n_heads, 1)
    return jnp.zeros((n_heads, 1), jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash(q, k, v, slopes, doc_ids, causal, alibi, interpret):
    return _flash_fwd(q, k, v, slopes, doc_ids, causal, alibi, interpret)[0]


def _flash_fwd(q, k, v, slopes, doc_ids, causal, alibi, interpret):
    # doc_ids: [B, T] float32 (or None) — f32 so its zero cotangent is a
    # plain zeros_like rather than float0 plumbing
    docs = () if doc_ids is None else (doc_ids,)

    def local(q, k, v, slopes, *docs):
        ids = docs[0] if docs else None
        return flash_partial(
            q, k, v, causal=causal, alibi=alibi, softmax_scale=None,
            q_offset=0, kv_offset=0, slopes=slopes, q_ids=ids, k_ids=ids,
            out_dtype=q.dtype, interpret=interpret,
        )

    o, lse = shard_kernel(
        local, (_Q, _KV, _KV, _SLOPES, *(_ROWS for _ in docs)), (_Q, _LSE)
    )(q, k, v, slopes, *docs)
    return o, (q, k, v, slopes, doc_ids, o, lse)


def _flash_bwd(causal, alibi, interpret, res, do):
    q, k, v, slopes, doc_ids, o, lse = res
    docs = () if doc_ids is None else (doc_ids,)

    def local(q, k, v, o, lse, do, slopes, *docs):
        ids = docs[0] if docs else None
        return flash_grads(
            q, k, v, o, lse, do, causal=causal, alibi=alibi,
            softmax_scale=None, q_offset=0, kv_offset=0, slopes=slopes,
            q_ids=ids, k_ids=ids, grad_dtype=None, interpret=interpret,
        )

    dq, dk, dv = shard_kernel(
        local,
        (_Q, _KV, _KV, _Q, _LSE, _Q, _SLOPES, *(_ROWS for _ in docs)),
        (_Q, _KV, _KV),
    )(q, k, v, o, lse, do, slopes, *docs)
    d_ids = None if doc_ids is None else jnp.zeros_like(doc_ids)
    return dq, dk, dv, jnp.zeros_like(slopes), d_ids


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q, k, v, *, causal: bool = True, alibi: bool = False, q_offset=0,
    segment_ids=None, doc_ids=None,
) -> jax.Array:
    """Kernel wrapper with EXACTLY the gate's keyword surface. Training
    shapes take the differentiable custom-VJP entry; serving shapes
    (traced/vector offsets, validity masks) take the forward-only entry.
    Either way the kernel runs per device under ``shard_kernel``."""
    interpret = jax.default_backend() != "tpu" and interpret_enabled()
    B, _, H, _ = q.shape
    slopes = _slopes(H, alibi)
    if _is_training_call(q_offset, segment_ids):
        ids = None if doc_ids is None else doc_ids.astype(jnp.float32)
        return _flash(q, k, v, slopes, ids, causal, alibi, interpret)
    # per-row offsets, so they split with the batch
    offs = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32).reshape(-1), (B,))
    segs = () if segment_ids is None else (segment_ids,)

    def local(q, k, v, slopes, offs, *segs):
        return (_pallas_serving(
            q, k, v, causal=causal, alibi=alibi, q_offset=offs,
            segment_ids=segs[0] if segs else None, slopes=slopes,
            interpret=interpret,
        ),)

    (out,) = shard_kernel(
        local, (_Q, _KV, _KV, _SLOPES, ("batch",), *(_ROWS for _ in segs)), (_Q,)
    )(q, k, v, slopes, offs, *segs)
    return out

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
  does not divide the heads: the kernel runs per device under a shard_map
  (``parallel.sharding.shard_kernel`` — GSPMD cannot partition a Mosaic
  call), and an undivided dim would have every device compute all of it.
  Logged once.

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
    flash_attention as _pallas_flash,
    flash_serving as _pallas_serving,
    pick_block,
)
from zero_transformer_tpu.parallel.sharding import kernel_shardable


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


def flash_attention(
    q, k, v, *, causal: bool = True, alibi: bool = False, q_offset=0,
    segment_ids=None, doc_ids=None,
) -> jax.Array:
    """Kernel wrapper with EXACTLY the gate's keyword surface. Training
    shapes take the differentiable custom-VJP entry; serving shapes
    (traced/vector offsets, validity masks) take the forward-only entry."""
    interpret = jax.default_backend() != "tpu" and interpret_enabled()
    if _is_training_call(q_offset, segment_ids):
        return _pallas_flash(
            q, k, v, causal=causal, alibi=alibi, doc_ids=doc_ids,
            interpret=interpret,
        )
    return _pallas_serving(
        q, k, v, causal=causal, alibi=alibi, q_offset=q_offset,
        segment_ids=segment_ids, interpret=interpret,
    )

"""Attention ops.

The XLA path below is the always-correct reference implementation: causal
multi-head/grouped-query attention with an additive bias (ALiBi) and a
float32 softmax — the dtype discipline the reference learned the hard way
(reference ``src/models/layers.py:167-173``; bug log ``logs/580.md:94-98``).

``dot_product_attention`` dispatches between this and the Pallas flash kernel
(``zero_transformer_tpu.ops.flash_attention``) which never materializes the
[T, T] score matrix the reference allocates in full (reference ``layers.py:159-173``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from zero_transformer_tpu.ops.positions import (
    NEG_INF,
    alibi_bias,
    alibi_slopes,
    causal_mask_bias,
)


def xla_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    alibi: bool = False,
    q_offset=0,
    segment_ids: Optional[jax.Array] = None,
    doc_ids: Optional[jax.Array] = None,
    softmax_scale: Optional[float] = None,
    slopes: Optional[jax.Array] = None,
) -> jax.Array:
    """Attention via explicit einsums, softmax in float32.

    Args:
      q: [B, Tq, H, D]
      k, v: [B, Tkv, KVH, D]; KVH must divide H (GQA).
      q_offset: position of q[0] within the full sequence (decode w/ KV cache).
        May be a traced scalar, or a traced [B] vector when every batch row
        sits at its own position (continuous-batching decode: one fused step
        over slots whose sequences have different lengths).
      slopes: optional [H] or [H, 1] f32 ALiBi slope override — for
        head-sharded callers (ulysses / TP local attention) whose local head
        0 is not global head 0.
      segment_ids: optional [B, Tkv] int mask; 0 = padding (masked out).
      doc_ids: optional [B, T] int document ids (Tq == Tkv); positions in
        DIFFERENT documents cannot attend to each other — the packed-sequence
        training mask.
    """
    B, Tq, H, D = q.shape
    _, Tkv, KVH, _ = k.shape
    G = H // KVH
    scale = softmax_scale if softmax_scale is not None else 1.0 / (D**0.5)

    qg = q.reshape(B, Tq, KVH, G, D)
    # scores in f32
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, k, preferred_element_type=jnp.float32)
    scores = scores * jnp.float32(scale)

    per_row = getattr(q_offset, "ndim", 0) == 1
    if per_row:
        # per-row q positions: biases get a leading batch dim
        q_pos = q_offset[:, None] + jnp.arange(Tq, dtype=jnp.int32)[None, :]  # [B, Tq]
        kv_pos = jnp.arange(Tkv, dtype=jnp.int32)
        if alibi:
            s = (alibi_slopes(H) if slopes is None else slopes).reshape(H)
            dist = jnp.maximum(
                q_pos[:, :, None] - kv_pos[None, None, :], 0
            ).astype(jnp.float32)  # [B, Tq, Tkv]
            bias = -s[None, :, None, None] * dist[:, None]  # [B, H, Tq, Tkv]
            if causal:
                visible = kv_pos[None, None, :] <= q_pos[:, :, None]
                bias = bias + jnp.where(visible, 0.0, NEG_INF)[:, None]
            scores = scores + bias.reshape(B, KVH, G, Tq, Tkv)
        elif causal:
            visible = kv_pos[None, None, :] <= q_pos[:, :, None]  # [B, Tq, Tkv]
            scores = scores + jnp.where(visible, 0.0, NEG_INF)[:, None, None]
    elif alibi:
        bias = alibi_bias(H, Tq, Tkv, offset=q_offset, slopes=slopes)  # [H, Tq, Tkv]
        if causal:
            bias = bias + causal_mask_bias(Tq, Tkv, offset=q_offset)[None]
        scores = scores + bias.reshape(1, KVH, G, Tq, Tkv)
    elif causal:
        scores = scores + causal_mask_bias(Tq, Tkv, offset=q_offset)[None, None, None]
    if segment_ids is not None:
        pad = jnp.where(segment_ids[:, None, None, None, :] != 0, 0.0, NEG_INF)
        scores = scores + pad
    if doc_ids is not None:
        if Tq != Tkv:
            raise ValueError("doc_ids requires full-sequence shapes (Tq == Tkv)")
        same = doc_ids[:, :, None] == doc_ids[:, None, :]  # [B, Tq, Tkv]
        scores = scores + jnp.where(same, 0.0, NEG_INF)[:, None, None]

    weights = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", weights, v)
    return out.reshape(B, Tq, H, D)


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    alibi: bool = False,
    q_offset=0,
    segment_ids: Optional[jax.Array] = None,
    doc_ids: Optional[jax.Array] = None,
    impl: str = "auto",
) -> jax.Array:
    """Dispatching attention entry point used by the models.

    impl="auto" picks the Pallas flash kernel on TPU (or under
    ``ZT_PALLAS_INTERPRET=1`` interpret mode) for full-sequence causal
    training shapes — including document-masked packing — AND for the
    serving cache shapes (chunked prefill / spec-verify windows with a
    traced or per-row q_offset and a kv-validity segment mask), falling
    back to the XLA path everywhere else (single-token decode, CPU, odd
    shapes).
    """
    if impl in ("auto", "flash"):
        from zero_transformer_tpu.ops import flash_attention as fa

        if fa.supported(
            q, k, v, causal=causal, alibi=alibi, q_offset=q_offset,
            segment_ids=segment_ids, doc_ids=doc_ids,
        ):
            return fa.flash_attention(
                q, k, v, causal=causal, alibi=alibi, q_offset=q_offset,
                segment_ids=segment_ids, doc_ids=doc_ids,
            )
        if impl == "flash":
            # flash-or-raise contract: never silently hand an explicit
            # flash request the O(T^2) fallback
            raise NotImplementedError(
                f"flash attention unsupported for shapes q={q.shape} k={k.shape}"
            )
    return xla_attention(
        q,
        k,
        v,
        causal=causal,
        alibi=alibi,
        q_offset=q_offset,
        segment_ids=segment_ids,
        doc_ids=doc_ids,
    )


def paged_kernel_supported(impl: str, *, H: int, KVH: int, **shape) -> bool:
    """Does the paged decode kernel take this dispatch? ONE gate consulted
    by both the model's paged read path (``models/gpt.py``) and the engine's
    dispatch-site bookkeeping, so "supported" and "will actually run" can
    never disagree: a tensor axis that divides the heads — the kernel runs
    per device on a mesh — and the kernel's own shape gate on the heads one
    device then holds."""
    from zero_transformer_tpu.ops.pallas import paged_attention as pa
    from zero_transformer_tpu.parallel.sharding import (
        kernel_local_size, kernel_shardable,
    )

    return kernel_shardable(heads=H, kvheads=KVH) and pa.supported(
        impl, H=kernel_local_size("heads", H),
        KVH=kernel_local_size("kvheads", KVH), **shape
    )


# graftlint: hot-path
def paged_decode_attention(
    q, k_pool, v_pool, block_table, q_offset, *, causal: bool,
    layer=None, alibi: bool = False, k_scale=None, v_scale=None,
) -> jax.Array:
    """The paged decode kernel (``ops.pallas.paged_attention``) at its
    dispatch site: on a mesh (``serve --tensor N``) each device walks ITS
    kv heads' pages under ``parallel.sharding.shard_kernel`` — GSPMD cannot
    partition a Mosaic call — with the heads' ALiBi slopes and the per-row
    offsets as explicit operands. The pools arrive in the kernel's own
    layout (``[..., n_pages, page, KVH * D]``, stacked over layers when
    ``layer`` is given); a device's shard of the merged lane axis is its
    kv heads' lanes, heads being contiguous in it."""
    from zero_transformer_tpu.ops.pallas import paged_attention as pa
    from zero_transformer_tpu.parallel.sharding import shard_kernel

    B, _, H, _ = q.shape
    offs = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32).reshape(-1), (B,))
    slopes = alibi_slopes(H) if alibi else jnp.zeros((H,), jnp.float32)
    pools = (k_pool, v_pool) + (() if k_scale is None else (k_scale, v_scale))
    if layer is None:  # an unstacked pool is a stack of one: moves no byte
        layer, pools = 0, tuple(p[None] for p in pools)
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)

    def local(q, block_table, offs, slopes, lyr, k_pool, v_pool, *scales):
        k_sc, v_sc = scales or (None, None)
        return (pa.paged_attention(
            q, k_pool, v_pool, block_table, offs, causal=causal, alibi=alibi,
            layer=lyr[0], k_scale=k_sc, v_scale=v_sc, slopes=slopes,
        ),)

    q_names = ("batch", None, "heads", None)
    pool = (None, None, None, "kvheads")
    (out,) = shard_kernel(
        local,
        (q_names, ("batch", None), ("batch",), ("heads",), (None,),
         *(pool for _ in pools)),
        (q_names,),
    )(q, block_table, offs, slopes.reshape(H), lyr, *pools)
    return out


def latent_kernel_supported(impl: str, *, H: int, **shape) -> bool:
    """``paged_kernel_supported`` for the latent decode kernel
    (``ops.pallas.latent_attention``): the heads a device holds, one cached
    row serving them all."""
    from zero_transformer_tpu.ops.pallas import latent_attention as la
    from zero_transformer_tpu.parallel.sharding import (
        kernel_local_size, kernel_shardable,
    )

    return kernel_shardable(heads=H) and la.supported(
        impl, H=kernel_local_size("heads", H), **shape
    )


# graftlint: hot-path
def latent_decode_attention(
    q, pool, block_table, q_offset, *, value_width: int, causal: bool,
    softmax_scale: float, layer=None,
) -> jax.Array:
    """The latent decode kernel at its dispatch site: on a mesh each device
    attends for ITS heads over the (replicated) latent pool, the absorbed
    form having made the heads independent of one another."""
    from zero_transformer_tpu.ops.pallas import latent_attention as la
    from zero_transformer_tpu.parallel.sharding import shard_kernel

    B = q.shape[0]
    offs = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32).reshape(-1), (B,))
    if layer is None:  # an unstacked pool is a stack of one: moves no byte
        layer, pool = 0, pool[None]
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)

    def local(q, block_table, offs, lyr, pool):
        return (la.latent_paged_attention(
            q, pool, block_table, offs, value_width=value_width, causal=causal,
            softmax_scale=softmax_scale, layer=lyr[0],
        ),)

    names = ("batch", None, "heads", None)
    (out,) = shard_kernel(
        local,
        (names, ("batch", None), ("batch",), (None,), (None, None, None, None)),
        (names,),
    )(q, block_table, offs, lyr, pool)
    return out

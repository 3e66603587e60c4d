"""Ulysses sequence parallelism: all-to-all context-parallel attention.

The reference has NO sequence/context parallelism (SURVEY §2 checklist; its
long-context story is ALiBi extrapolation plus *reducing* context to dodge
OOM, reference ``src/models/layers.py:80-101``, ``logs/1B.md:7``). This module
is the second of this framework's two context-parallel engines, alongside
``ops/ring_attention.py``:

- **ring**: K/V shards rotate with ``lax.ppermute`` (ICI neighbor exchange);
  per-chip memory stays at one KV shard; comm volume grows with the number of
  ring steps. Best at very long T where each fold is compute-heavy. Works at
  any head count.
- **ulysses** (this file): two ``lax.all_to_all`` reshards per attention call.
  Activations arrive sequence-sharded [B, T/n, H, D]; the first all-to-all
  re-shards them to head-sharded [B, T, H/n, D], each device runs ONE local
  flash-attention call over the FULL sequence for its head group, and the
  second all-to-all restores sequence sharding. Comm volume is O(T·d_model/n)
  per call regardless of T — cheaper than ring when the per-step folds are
  small — and the attention itself needs no cross-device softmax merging, so
  the flash kernel runs at exactly its single-chip efficiency.

The head dimension is the parallel resource: the ``sequence`` axis must divide
the (tensor-sharded) head counts, queries AND kv (GQA group boundaries always
align because H/KVH is preserved under the split). ALiBi slopes are sliced to
each device's global head range and handed to the shared attention wrappers
via their ``slopes`` override; packed-document ids are all-gathered (they are
[B, T] int — tiny) so the local mask is exact.

Composes with the same mesh axes as ring attention: batch over data/fsdp,
heads over ``tensor``, sequence over ``sequence``. Select per-model with
``ModelConfig.cp_impl = "ulysses"``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from zero_transformer_tpu.ops.attention import xla_attention
from zero_transformer_tpu.ops.positions import alibi_slopes
from zero_transformer_tpu.ops.ring_attention import (
    _axis_rank,
    _engine_ctx,
    _explicit_vjp_engine,
    _flash_local_ok,
    _specs,
    _validate_cp_shapes,
)
from zero_transformer_tpu.parallel.mesh import SEQUENCE_AXIS, TENSOR_AXIS


def _ulysses_body(
    q, k, v, ids, *, n, tp, H, causal, alibi, docs, scale, flash, interpret
):
    B, t, H_tp, D = q.shape
    if n > 1:
        # seq-sharded [B, T/n, h, D] → head-sharded [B, T, h/n, D]: local head
        # chunk j ships to sequence-rank j, time chunks concatenate in rank
        # order — device (tensor=r, sequence=s) ends up owning global heads
        # [r·H_tp + s·h_loc, r·H_tp + (s+1)·h_loc).
        q = jax.lax.all_to_all(q, SEQUENCE_AXIS, split_axis=2, concat_axis=1, tiled=True)
        k = jax.lax.all_to_all(k, SEQUENCE_AXIS, split_axis=2, concat_axis=1, tiled=True)
        v = jax.lax.all_to_all(v, SEQUENCE_AXIS, split_axis=2, concat_axis=1, tiled=True)
    ids_full = None
    if docs:
        ids_full = (
            jax.lax.all_gather(ids, SEQUENCE_AXIS, axis=1, tiled=True)
            if n > 1 else ids
        )

    H_loc = q.shape[2]
    slopes = None
    if alibi:
        h_off = _axis_rank(SEQUENCE_AXIS, n) * H_loc
        if tp > 1:
            h_off = h_off + _axis_rank(TENSOR_AXIS, tp) * H_tp
        slopes = jax.lax.dynamic_slice_in_dim(alibi_slopes(H), h_off, H_loc)
        slopes = slopes.reshape(H_loc, 1)

    if flash:
        from zero_transformer_tpu.ops.pallas.flash import flash_attention

        out = flash_attention(
            q, k, v, causal=causal, alibi=alibi, doc_ids=ids_full,
            softmax_scale=scale, slopes=slopes, interpret=interpret,
        )
    else:
        # NOT wrapped in jax.checkpoint: a checkpoint region inside this
        # shard_map body deadlocks the XLA:CPU collective rendezvous (the
        # rematerialized replay re-issues the surrounding collectives in a
        # divergent order across devices — observed hang at all-gather/
        # all-to-all, 8-device CPU mesh). Long-context memory is instead
        # governed by the model's per-block remat (cfg.remat), whose
        # checkpoint sits OUTSIDE the shard_map call and already discards
        # the [B, KVH, G, T, T] softmax residuals this fallback produces;
        # at long T use the flash engine anyway (this path is the
        # odd-shape/CPU fallback).
        out = xla_attention(
            q, k, v, causal=causal, alibi=alibi, softmax_scale=scale,
            doc_ids=ids_full, slopes=slopes,
        )

    if n > 1:
        # head-sharded back to seq-sharded: time chunk j returns to rank j,
        # head groups concatenate in rank order, restoring the original order.
        out = jax.lax.all_to_all(out, SEQUENCE_AXIS, split_axis=1, concat_axis=2, tiled=True)
    return out


def ulysses_attention(
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
    """Global-view Ulysses attention. q [B,T,H,D]; k,v [B,T,KVH,D].

    Requires the ``sequence`` axis size n to divide T and BOTH tensor-local
    head counts (H/tp and KVH/tp) — the head dimension is what Ulysses
    parallelizes over. Use ring attention when heads are too few.

    ``doc_ids`` [B, T] int: packed-sequence document mask, sharded over the
    sequence axis like q; all-gathered inside the body (ids are tiny).
    """
    B, T, H, D = q.shape
    _, S, KVH, _ = k.shape
    n = mesh.shape[SEQUENCE_AXIS]
    tp = mesh.shape[TENSOR_AXIS]
    _validate_cp_shapes("ulysses", T, S, n, tp, H, KVH)
    if (H // tp) % n or (KVH // tp) % n:
        raise ValueError(
            f"ulysses needs the sequence axis ({n}) to divide the tensor-local "
            f"head counts ({H // tp} query / {KVH // tp} kv); use cp_impl='ring' "
            f"for few-headed models"
        )
    scale = float(softmax_scale if softmax_scale is not None else 1.0 / (D**0.5))
    qkv_spec, _ = _specs(mesh, B, tp)
    ids_spec = P(qkv_spec[0], SEQUENCE_AXIS)
    # nested-context resolution (see ring_attention._engine_ctx): inside the
    # explicit ZeRO core the data/fsdp axes are already manual — drop them
    # from the specs and manualize only sequence(+tensor)
    mesh_arg, axes, (qkv_spec, ids_spec) = _engine_ctx(mesh, (qkv_spec, ids_spec))
    docs = doc_ids is not None

    # the local flash call sees the FULL sequence length T
    use_flash = impl in ("auto", "flash") and _flash_local_ok(T, D, q.dtype, interpret)
    if impl == "flash" and not use_flash:
        raise NotImplementedError(
            f"flash ulysses attention unsupported for T={T}, D={D}, dtype={q.dtype}"
        )

    ids = (
        doc_ids.astype(jnp.float32) if docs
        else jnp.zeros((B, T), jnp.float32)
    )
    # explicit recompute vjp shared with the XLA-fallback ring: jax's
    # transpose of a nested partial-manual shard_map mis-lowers, so the
    # backward re-differentiates the body inside a fresh shard_map
    body = functools.partial(
        _ulysses_body, n=n, tp=tp, H=H, causal=causal, alibi=alibi, docs=docs,
        scale=scale, flash=use_flash, interpret=interpret,
    )
    return _explicit_vjp_engine(
        body, mesh_arg, qkv_spec, ids_spec, axes, q, k, v, ids
    )

"""Paged-attention decode kernel: block tables read INSIDE the kernel grid.

The serving engine's paged KV cache (``serving/slots.py``, PR 6) stores K/V
in a global page pool addressed through per-row int32 block tables. Until
this kernel, every decode/spec-verify dispatch first materialized a
gather-to-slab view — ``jnp.take(pool, table)`` builds a fresh
``[B, cache_len, KVH, D]`` copy of every live row's K/V per token — and
then ran the slab attention over it. That gather is pure HBM traffic the
math never needed: attention only has to *read* each page once.

This kernel walks the block table inside the Pallas grid instead: grid
``(B, n_blocks)``, with the page axis resolved per grid step through a
scalar-prefetched table (``PrefetchScalarGridSpec``) so the BlockSpec index
map fetches ``pool[layer, table[b, j]]`` directly — the pipelined HBM→VMEM
copy IS the page walk, and no slab view ever exists. int8 KV pages
dequantize in-register (per-page scale blocks ride the same index map) on
their way into the VMEM K/V scratch.

Pool layout. The pool is ALLOCATED in the shape this kernel's ``BlockSpec``
reads — K/V ``[n_pages, page, KVH * D]``, int8 scales ``[n_pages, page,
KVH]``, under the scanned layer stack one more leading ``n_layers`` axis
with the layer index a third scalar-prefetch operand — and the wrapper
hands it to Mosaic as it is. It used to be declared ``[n_pages, page, KVH,
D]`` and merged here with a reshape that reads as free and on a TPU is
not: a ``(KVH, D)`` minor pair tiles with 12 heads padded to 16, so XLA
kept the pool in another physical layout than the row-major operand a
Mosaic call insists on, and converted between them with pool-sized copies,
per layer, per tick (54 of a 66 ms decode tick at 580M: ledger, PR 24).
Nothing between allocation and this ``pallas_call`` may reshape, transpose
or slice a pool-sized value (``tests/test_chip_compile.py`` counts them).

Exactness contract. The kernel computes, per row, the op sequence of the
gather path (``jnp.take`` + ``ops.attention.xla_attention`` per-row
branch): scores in f32, the scalar scale multiply, one bias add (ALiBi with
the causal mask folded in), a second validity add, ``jax.nn.softmax`` in
f32, weights rounded to the compute dtype, the output matmul accumulated in
f32 and rounded once. What the two paths may NOT share is the order in
which a backend sums a contraction or a softmax row: the kernel's layouts
are the ones Mosaic lowers (kv-head-batched 3-D matmuls over a head-major
scratch), not the gather path's 5-D einsums, which the chip's compiler
refuses. So the bar is:

- interpret mode (CPU): output within 1 ulp (bf16) / 4 ulp (f32; observed
  1-2) of the gather path at the output's scale — the bf16 GQA case is
  still bit-equal — and a served token stream byte-identical to the gather
  engine's (``tests/test_paged_kernel.py``);
- on the chip: output within 2 bf16 ulps of the gather path at the
  output's scale, bf16 and int8 pages, decode and spec-verify windows, at
  the server's shapes (``ops.pallas.parity.paged_vs_gather``, held by
  ``chip_smoke.py``'s ``kernels`` phase — the only place the
  Mosaic-compiled kernel's numbers exist), and greedy token streams
  identical to ``attention_impl: xla`` (its ``serve`` phase).

VMEM note: the whole row's K/V lands in a ``[KVH, cache_len, D]`` scratch
pair — 8 MiB at 16 heads x 1k positions x D=128 bf16 — beside f32
score-sized temporaries. The wrapper asks Mosaic for that much scoped VMEM
and the gate declines what would not fit the core's 128 MiB with room to
spare; past that, a production variant would switch to an online-softmax
page walk.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from zero_transformer_tpu.ops.pallas import kernel_traces
from zero_transformer_tpu.ops.positions import NEG_INF, alibi_slopes

# decode window ceiling: 1 (plain decode) .. 1 + draft_k (spec verify).
# Larger query windows belong to the flash kernel's chunked-prefill path.
MAX_DECODE_T = 8


def interpret_requested() -> bool:
    """True when ``ZT_PALLAS_INTERPRET=1``: run the Pallas kernels in
    interpret mode off-TPU so their numerics are exercised on this CPU
    image (tests, bench parity lanes). Read at TRACE time — flip it before
    building the engine/model, not mid-run."""
    return os.environ.get("ZT_PALLAS_INTERPRET", "") == "1"


# scoped-VMEM budget the gate admits (v5e has 128 MiB per core; leave
# headroom for the pipelined page blocks and Mosaic's own stack)
VMEM_CEILING = 96 << 20


def vmem_bytes(*, T: int, H: int, KVH: int, D: int, S: int, dtype) -> int:
    """Scoped VMEM one grid step needs: the K/V scratch pair plus the f32
    score-shaped temporaries (scores, bias, exp, weights — rows pad to the
    8-sublane tile) and a fixed allowance for the double-buffered blocks."""
    rows = -(-(T * (H // KVH)) // 8) * 8
    scratch = 2 * KVH * S * D * jnp.dtype(dtype).itemsize
    return scratch + 6 * KVH * rows * S * 4 + (8 << 20)


def supported(
    impl: str,
    *,
    T: int,
    H: int,
    KVH: int,
    D: int,
    S: int,
    page_size: int,
    dtype,
    interpret: bool = False,
) -> bool:
    """Shape gate: can the paged kernel take this decode dispatch on one
    device? (The dispatch site, ``ops.attention.paged_kernel_supported``,
    adds what the mesh has to say.) ``impl`` is
    ``cfg.attention_impl``; ``xla`` always declines (the gather path is the
    reference), ``auto``/``flash`` accept on TPU or under interpret mode.
    ``S`` is the cache length (``n_blocks * page_size``).
    """
    if impl not in ("auto", "flash"):
        return False
    on_tpu = jax.default_backend() == "tpu"
    if not (on_tpu or interpret or interpret_requested()):
        return False
    if T < 1 or T > MAX_DECODE_T:
        return False  # decode/spec-verify windows only
    if dtype not in (jnp.bfloat16, jnp.float32):
        return False
    if on_tpu:
        # Mosaic lowering constraints — interpret mode (the CPU parity
        # lane) has no tiling and accepts any structurally valid shape
        if D % 64 or D > 256:
            return False  # lane-dim alignment for the MXU
        if page_size % 8:
            return False  # sublane-aligned page copies into the K/V scratch
        if vmem_bytes(T=T, H=H, KVH=KVH, D=D, S=S, dtype=dtype) > VMEM_CEILING:
            return False
    return True


def _kernel(
    # scalar-prefetch refs
    table_ref, offs_ref, layer_ref,
    # operands
    tq_ref, slope_ref, q_ref, k_ref, v_ref, *args,
    T: int, KVH: int, D: int, page: int, n_blocks: int, scale: float,
    causal: bool, alibi: bool, int8: bool,
):
    """One row's attention over its paged K/V, ALL heads per grid step.

    Grid (B, n_blocks): step j copies page ``table[b, j]``'s block —
    already pipelined into VMEM by the index map — into the head-major
    K/V scratch at its logical position (dequantized when int8); the final
    step runs the full-softmax attention as ONE kv-head-batched matmul
    pair. Keeping the kv-head axis a BATCH dim of the contraction (not a
    grid dim or a Python loop of 2-D dots) is load-bearing twice: it is
    the batched-matmul form Mosaic lowers to the MXU, and on the CPU
    interpret lane XLA sends a per-head 2-D dot through a different gemm
    path than the reference's batched einsum (ulps apart at M=1).

    Layouts (all chosen so every slice the kernel takes is a static lane
    slice or a page-aligned sublane window): q/out ``[KVH, M, D]`` with
    row ``m = t * G + g``; a pool block ``[page, KVH * D]`` (the index map
    picked its layer and page); an int8 scale block ``[page, KVH]``; ``tq``
    ``[M, 1]`` the window position ``t`` of row m; ``slope`` ``[KVH, M, 1]``
    the ALiBi slope of (head, row)."""
    # arg order: remaining inputs (int8 scale blocks), the output ref,
    # then the scratch buffers
    if int8:
        ks_ref, vs_ref, o_ref, k_scr, v_scr = args
    else:
        o_ref, k_scr, v_scr = args
    b, j = pl.program_id(0), pl.program_id(1)
    S = n_blocks * page
    M = q_ref.shape[2]
    rows = pl.ds(pl.multiple_of(j * page, page), page)

    for h in range(KVH):
        lanes = slice(h * D, (h + 1) * D)
        kb = k_ref[0, 0, :, lanes]  # [page, D]
        vb = v_ref[0, 0, :, lanes]
        if int8:
            # exact mirror of the gather path's dequant:
            # (int8 -> f32) * f32 scale -> compute dtype, elementwise
            kb = kb.astype(jnp.float32) * ks_ref[0, 0, :, h:h + 1]
            vb = vb.astype(jnp.float32) * vs_ref[0, 0, :, h:h + 1]
        k_scr[h, rows, :] = kb.astype(k_scr.dtype)
        v_scr[h, rows, :] = vb.astype(v_scr.dtype)

    @pl.when(j == n_blocks - 1)
    def _compute():
        off = offs_ref[b]
        # scores in f32, THEN the scalar scale multiply — xla_attention's
        # exact order
        s = jnp.einsum(
            "hmd,hsd->hms", q_ref[0], k_scr[...],
            preferred_element_type=jnp.float32,
        )
        s = s * jnp.float32(scale)  # [KVH, M, S]
        q_pos = off + tq_ref[...]  # [M, 1]
        kv_pos = jax.lax.broadcasted_iota(jnp.int32, (M, S), 1)
        if alibi:
            # xla per-row branch: bias = -slope*dist (+ causal NEG_INF
            # folded into the SAME bias tensor), ONE add onto the scores
            dist = jnp.maximum(q_pos - kv_pos, 0).astype(jnp.float32)  # [M, S]
            bias = -slope_ref[...] * dist[None]  # [KVH, M, S]
            if causal:
                visible = kv_pos <= q_pos
                bias = bias + jnp.where(visible, 0.0, NEG_INF)[None]
            s = s + bias
        elif causal:
            visible = kv_pos <= q_pos
            s = s + jnp.where(visible, 0.0, NEG_INF)[None]
        # validity pad is its own SECOND add, exactly like the xla path's
        # segment_ids term (order matters for the bitwise contract)
        valid = kv_pos[:1, :] < off + T  # [1, S]
        s = s + jnp.where(valid, 0.0, NEG_INF)[None]
        w = jax.nn.softmax(s, axis=-1).astype(v_scr.dtype)
        # f32 accumulation (the MXU has no narrower accumulator), rounded
        # ONCE into the compute dtype
        out = jnp.einsum(
            "hms,hsd->hmd", w, v_scr[...],
            preferred_element_type=jnp.float32,
        )
        o_ref[0] = out.astype(o_ref.dtype)


# graftlint: hot-path
def paged_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_table: jax.Array,
    q_offset: jax.Array,
    *,
    causal: bool,
    layer: Optional[jax.Array] = None,
    alibi: bool = False,
    softmax_scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    slopes: Optional[jax.Array] = None,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention straight off the page pool. q ``[B, T, H, D]``
    (T = 1 decode, 1+K spec verify; RoPE already applied, overflow rows
    already NaN-poisoned by the caller); ``k_pool``/``v_pool``
    ``[n_pages, page, KVH * D]`` (int8 with ``k_scale``/``v_scale``
    ``[n_pages, page, KVH]`` f32, or the compute dtype) — or, with
    ``layer`` (an int32 scalar, traced under the layer scan), the stacked
    ``[n_layers, n_pages, page, ...]`` pools, of which only layer
    ``layer``'s pages are fetched; ``block_table``
    ``[B, n_blocks]`` int32 (zeros = the serving layer's trash page);
    ``q_offset`` ``[B]`` (or scalar) — row r's query block starts at
    position ``q_offset[r]``, and positions ``>= q_offset[r] + T`` are
    masked invalid, the gather path's ``kv_valid``. ``slopes`` ``[H]`` f32
    overrides the ALiBi slope table for a caller holding a shard of the
    heads.

    Forward-only (the decode path never differentiates). How close the
    output is to gather-to-slab + ``xla_attention`` is the module
    docstring's exactness contract.
    """
    B, T, H, D = q.shape
    if k_pool.ndim != (3 if layer is None else 4):
        raise ValueError(
            f"pool {k_pool.shape}: expected [n_pages, page, KVH * D], or "
            "[n_layers, n_pages, page, KVH * D] with a layer index"
        )
    page, lanes = k_pool.shape[-2:]
    KVH = lanes // D
    if lanes % D or H % KVH:
        raise ValueError(
            f"pool lanes {lanes} are not kv heads of width {D} dividing "
            f"the {H} query heads"
        )
    int8 = k_pool.dtype == jnp.int8
    if int8 and (k_scale is None or v_scale is None):
        raise ValueError("int8 pools need k_scale/v_scale pools")
    scale = float(softmax_scale if softmax_scale is not None else 1.0 / (D**0.5))
    offs = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32).reshape(-1), (B,))
    if slopes is None:
        slopes = alibi_slopes(H) if alibi else jnp.zeros((H,), jnp.float32)
    slopes = slopes.reshape(H).astype(jnp.float32)
    interpret = interpret or (
        jax.default_backend() != "tpu" and interpret_requested()
    )
    block_table = block_table.astype(jnp.int32)
    G = H // KVH
    _, n_blocks = block_table.shape
    S = n_blocks * page
    dtype = q.dtype
    kernel_traces["paged_attention"] += 1
    # kernel layouts (see _kernel): q rows m = t * G + g under each kv head.
    # The pools go in as allocated (module docstring): an unstacked pool
    # only gains a unit layer axis, which moves no byte.
    M = T * G
    qk = q.reshape(B, T, KVH, G, D).transpose(0, 2, 1, 3, 4).reshape(B, KVH, M, D)
    tq = jnp.repeat(jnp.arange(T, dtype=jnp.int32), G).reshape(M, 1)
    slope = jnp.broadcast_to(
        slopes.reshape(KVH, 1, G), (KVH, T, G)
    ).reshape(KVH, M, 1)
    pools = [k_pool, v_pool] + ([k_scale, v_scale] if int8 else [])
    if layer is None:
        layer = 0
        pools = [p[None] for p in pools]
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)

    # index maps receive the scalar-prefetch refs (table, offsets, layer)
    # last; the layer and page axes of every pool operand resolve through
    # them — the pipelined block fetch IS the page walk
    qo_spec = pl.BlockSpec((1, KVH, M, D), lambda b, j, tbl, off, lyr: (b, 0, 0, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, page, lanes), lambda b, j, tbl, off, lyr: (lyr[0], tbl[b, j], 0, 0)
    )
    sc_spec = pl.BlockSpec(
        (1, 1, page, KVH), lambda b, j, tbl, off, lyr: (lyr[0], tbl[b, j], 0, 0)
    )
    in_specs = [
        pl.BlockSpec((M, 1), lambda b, j, tbl, off, lyr: (0, 0)),
        pl.BlockSpec((KVH, M, 1), lambda b, j, tbl, off, lyr: (0, 0, 0)),
        qo_spec, kv_spec, kv_spec,
    ]
    if int8:
        in_specs += [sc_spec, sc_spec]
    operands = [tq, slope, qk, *pools]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_blocks),
        in_specs=in_specs,
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((KVH, S, D), dtype),
            pltpu.VMEM((KVH, S, D), dtype),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, T=T, KVH=KVH, D=D, page=page, n_blocks=n_blocks,
            scale=scale, causal=causal, alibi=alibi, int8=int8,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, M, D), dtype),
        name="paged_attention",
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_bytes(T=T, H=H, KVH=KVH, D=D, S=S, dtype=dtype)
        ),
        interpret=interpret,
    )(block_table, offs, lyr, *operands)
    return (
        out.reshape(B, KVH, T, G, D).transpose(0, 2, 1, 3, 4).reshape(B, T, H, D)
    )

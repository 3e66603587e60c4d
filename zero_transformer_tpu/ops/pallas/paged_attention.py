"""Paged-attention decode kernel: block tables read INSIDE the kernel grid.

The serving engine's paged KV cache (``serving/slots.py``, PR 6) stores K/V
in a global page pool addressed through per-row int32 block tables. Until
this kernel, every decode/spec-verify dispatch first materialized a
gather-to-slab view — ``jnp.take(pool, table)`` builds a fresh
``[B, cache_len, KVH, D]`` copy of every live row's K/V per token — and
then ran the slab attention over it. That gather is pure HBM traffic the
math never needed: attention only has to *read* each page once.

This kernel walks the block table itself, and only as far as each row is
live. Grid ``(B,)``, one step a row; the pools stay in HBM
(``memory_space=pl.ANY``) and the step DMAs ``pool[layer, table[b, j]]``
for the row's live pages ``j < ceil((q_offset[b] + T) / page)`` — the
table, the offsets and the layer index are scalar-prefetched — in groups,
double-buffered across rows, so the page walk IS the HBM read and no slab
view ever exists. What is bounded by a row's own length: the pages fetched,
the pages copied into the head-major K/V scratch, and the width of the
closing scores / softmax / output matmul (the live extent rounded up to a
static bucket: 128 positions doubling up to the cache length). An idle slot
(cursor 0) is a one-page row. What is NOT bounded by it: the grid's ``B``
steps, and the scratch, which is allocated for a full row (VMEM note
below). int8 KV pages dequantize in-register on their way into the
scratch; their scales reach the kernel as the rows' ``[B, S, KVH]`` gather
(Mosaic DMAs no array whose lanes are not whole tiles; 3% of the bytes).

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

VMEM note: a full row's K/V lands in a ``[KVH, cache_len, D]`` scratch
pair — 8 MiB at 16 heads x 1k positions x D=128 bf16 — beside the f32
score-sized temporaries of the widest bucket and the walk's two staging
slots (at most 4 MiB). The wrapper asks Mosaic for that much scoped VMEM
and the gate declines what would not fit the core's 128 MiB with room to
spare; past that, a production variant would switch to an online-softmax
page walk. The gate also declines, on a TPU, a device's pool rows that are
not whole lane tiles (``KVH * D % 128``: D 64 with an odd number of kv
heads on the device), which the kernel's own DMAs cannot address.
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
# headroom for Mosaic's own stack)
VMEM_CEILING = 96 << 20

# The page walk lands a GROUP of pages at a time in a two-slot staging
# buffer (one group in flight while the one before it is placed): this many
# positions a group, cut down where a pool row is so wide that the staging
# would pass its share of the fixed allowance in ``vmem_bytes``.
STAGE_POSITIONS = 256
STAGE_BYTES_MAX = 4 << 20

# The closing compute runs over the row's live extent rounded up to a
# static bucket: this many positions (one lane tile of scores), doubling
# up to the cache length.
BUCKET_POSITIONS = 128


def _stage_bytes(pages: int, page: int, KVH: int, D: int, pool_dtype) -> int:
    """The two slots of K and V staging."""
    return 2 * 2 * pages * page * KVH * D * jnp.dtype(pool_dtype).itemsize


def stage_pages(*, page: int, n_blocks: int, KVH: int, D: int, pool_dtype) -> int:
    """Pages in one staged group of the walk."""
    pages = max(1, min(STAGE_POSITIONS // page, n_blocks))
    while pages > 1 and _stage_bytes(pages, page, KVH, D, pool_dtype) > STAGE_BYTES_MAX:
        pages //= 2
    return pages


def bucket_widths(*, page: int, n_blocks: int) -> tuple:
    """The static widths (in positions, whole pages each) the closing
    compute is built for: ``BUCKET_POSITIONS`` doubling while it stays under
    the cache length, then the cache length itself."""
    S = n_blocks * page
    width = -(-BUCKET_POSITIONS // page) * page
    widths = []
    while width < S:
        widths.append(width)
        width *= 2
    return (*widths, S)


def vmem_bytes(
    *, T: int, H: int, KVH: int, D: int, S: int, dtype, page: int,
    pool_dtype=None,
) -> int:
    """Scoped VMEM the kernel needs: the head-major K/V scratch pair, the
    f32 score-shaped temporaries of the widest bucket (scores, bias, exp,
    weights — rows pad to the 8-sublane tile), the walk's staging slots and
    a fixed allowance for the q / output blocks and Mosaic's stack (staging
    plus allowance never pass the 8 MiB the gate has always set aside);
    for int8 pools also the double-buffered rows of f32 scales, whose
    ``KVH`` lanes pad to a lane tile."""
    rows = -(-(T * (H // KVH)) // 8) * 8
    scratch = 2 * KVH * S * D * jnp.dtype(dtype).itemsize
    pool_dtype = jnp.dtype(dtype if pool_dtype is None else pool_dtype)
    pages = stage_pages(
        page=page, n_blocks=max(1, S // page), KVH=KVH, D=D, pool_dtype=pool_dtype
    )
    staging = _stage_bytes(pages, page, KVH, D, pool_dtype)
    if pool_dtype == jnp.int8:
        staging += 2 * 2 * S * (-(-KVH // 128) * 128) * 4
    return scratch + 6 * KVH * rows * S * 4 + staging + (4 << 20)


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
        if (KVH * D) % 128:
            return False  # a page DMA moves whole lane tiles of a pool row
        if page_size % 8:
            return False  # sublane-aligned page copies into the K/V scratch
        if vmem_bytes(
            T=T, H=H, KVH=KVH, D=D, S=S, dtype=dtype, page=page_size
        ) > VMEM_CEILING:
            return False
    return True


def _kernel(
    # scalar-prefetch refs
    table_ref, offs_ref, layer_ref,
    # operands
    tq_ref, slope_ref, q_ref, k_hbm, v_hbm, *refs,
    T: int, KVH: int, D: int, page: int, n_blocks: int, group: int,
    buckets: tuple, scale: float, causal: bool, alibi: bool, int8: bool,
):
    """One row's attention over its LIVE pages, all heads per grid step.

    Grid ``(B,)``, and under each step a loop over the row's groups of
    ``group`` live pages. Row b is live up to ``offs[b] + T`` positions,
    ``ceil`` of that over ``page`` pages; no page past the last live one is
    fetched, placed or computed on.

    The walk. A group's pages are DMAed from the pools (left in HBM) into
    one of two staging slots, one DMA a page a pool, and the NEXT group in
    the sequence (this row's, or the next row's first: the DMAs and the
    group counter outlive a grid step) is started before this one is waited
    for, so the fetch runs under the placing and under the closing compute
    of the row before. Placing copies a staged page head by head into the
    head-major K/V scratch at its logical position (dequantized when int8);
    the page the live extent ends inside is placed with its positions past
    the extent set to zero, and the scratch from there to the bucket's edge
    is zero-filled, so nothing a dead table entry, a page's unwritten tail
    or an earlier row left behind can reach a product (``0 * NaN``).

    The closing compute is the full-softmax attention as ONE
    kv-head-batched matmul pair over the live extent rounded up to a static
    bucket (``buckets``; the branch is chosen by the live length). Keeping
    the kv-head axis a BATCH dim of the contraction (not a grid dim or a
    Python loop of 2-D dots) is load-bearing twice: it is the
    batched-matmul form Mosaic lowers to the MXU, and on the CPU interpret
    lane XLA sends a per-head 2-D dot through a different gemm path than
    the reference's batched einsum (ulps apart at M=1).

    Layouts (all chosen so every slice the kernel takes is a static lane
    slice or a page-aligned sublane window): q/out ``[KVH, M, D]`` with
    row ``m = t * G + g``; a pool ``[n_layers, n_pages, page, KVH * D]`` and
    its staging ``[2, group, page, KVH * D]``; the row's int8 scales
    ``[S, KVH]`` by logical position (the wrapper gathered them: Mosaic
    DMAs no array whose lanes are not whole tiles, and they are 3% of the
    bytes); ``tq`` ``[M, 1]`` the window position ``t`` of row m; ``slope``
    ``[KVH, M, 1]`` the ALiBi slope of (head, row)."""
    # arg order: remaining inputs (the row's int8 scales), the output ref,
    # then the scratch: the head-major K/V pair, the staging pair, the DMA
    # semaphores [slot, pool] and the count of groups walked so far
    if int8:
        ks_ref, vs_ref, *refs = refs
    o_ref, k_scr, v_scr, k_stage, v_stage, sem, walked = refs
    b, B = pl.program_id(0), pl.num_programs(0)
    M = q_ref.shape[2]
    S = n_blocks * page
    lyr = layer_ref[0]

    def live_extent(row):
        """A row's live positions, and the pages that hold them."""
        n = jnp.clip(offs_ref[row] + T, 1, S)
        return n, (n + page - 1) // page

    def transfer(row, g, slot, n_pages, wait: bool):
        """Start (or wait for) the page DMAs of group g of a row."""

        def one(i, _):
            pg = table_ref[row, g * group + i]
            for p, (hbm, stage) in enumerate(((k_hbm, k_stage), (v_hbm, v_stage))):
                dma = pltpu.make_async_copy(
                    hbm.at[lyr, pg], stage.at[slot, i], sem.at[slot, p]
                )
                dma.wait() if wait else dma.start()

        jax.lax.fori_loop(0, jnp.clip(n_pages - g * group, 0, group), one, None)

    def place(slot, i, j, n_live=None):
        """Staged page i of ``slot`` into the scratch as logical page j;
        with ``n_live``, positions from there on are set to zero."""
        rows = pl.ds(pl.multiple_of(j * page, page), page)
        if n_live is not None:
            pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (page, D), 0)
        scales = (ks_ref, vs_ref) if int8 else (None, None)
        for h in range(KVH):
            lanes = slice(h * D, (h + 1) * D)
            for stage, sc, scr in zip((k_stage, v_stage), scales, (k_scr, v_scr)):
                x = stage[slot, i, :, lanes]  # [page, D]
                if int8:
                    # exact mirror of the gather path's dequant:
                    # (int8 -> f32) * f32 scale -> compute dtype, elementwise
                    x = x.astype(jnp.float32) * sc[0, rows, h:h + 1]
                if n_live is not None:
                    x = jnp.where(pos < n_live, x.astype(jnp.float32), 0.0)
                scr[h, rows, :] = x.astype(scr.dtype)

    def compute(W: int):
        off = offs_ref[b]
        # scores in f32, THEN the scalar scale multiply — xla_attention's
        # exact order
        s = jnp.einsum(
            "hmd,hsd->hms", q_ref[0], k_scr[:, :W, :],
            preferred_element_type=jnp.float32,
        )
        s = s * jnp.float32(scale)  # [KVH, M, W]
        q_pos = off + tq_ref[...]  # [M, 1]
        kv_pos = jax.lax.broadcasted_iota(jnp.int32, (M, W), 1)
        if alibi:
            # xla per-row branch: bias = -slope*dist (+ causal NEG_INF
            # folded into the SAME bias tensor), ONE add onto the scores
            dist = jnp.maximum(q_pos - kv_pos, 0).astype(jnp.float32)  # [M, W]
            bias = -slope_ref[...] * dist[None]  # [KVH, M, W]
            if causal:
                visible = kv_pos <= q_pos
                bias = bias + jnp.where(visible, 0.0, NEG_INF)[None]
            s = s + bias
        elif causal:
            visible = kv_pos <= q_pos
            s = s + jnp.where(visible, 0.0, NEG_INF)[None]
        # validity pad is its own SECOND add, exactly like the xla path's
        # segment_ids term (order matters for the bitwise contract)
        valid = kv_pos[:1, :] < off + T  # [1, W]
        s = s + jnp.where(valid, 0.0, NEG_INF)[None]
        w = jax.nn.softmax(s, axis=-1).astype(v_scr.dtype)
        # f32 accumulation (the MXU has no narrower accumulator), rounded
        # ONCE into the compute dtype
        out = jnp.einsum(
            "hms,hsd->hmd", w, v_scr[:, :W, :],
            preferred_element_type=jnp.float32,
        )
        o_ref[0] = out.astype(o_ref.dtype)

    @pl.when(b == 0)
    def _prime():
        walked[0] = 0
        transfer(0, 0, 0, live_extent(0)[1], wait=False)

    n_live, n_pages = live_extent(b)
    n_groups = (n_pages + group - 1) // group
    first = walked[0]  # its parity is this row's first staging slot

    def walk(g, _):
        slot = (first + g) % 2
        last = g + 1 == n_groups
        nb, ng = jnp.where(last, b + 1, b), jnp.where(last, 0, g + 1)

        @pl.when(nb < B)
        def _prefetch():
            transfer(nb, ng, 1 - slot, live_extent(nb)[1], wait=False)

        transfer(b, g, slot, n_pages, wait=True)
        whole = jnp.clip(n_live // page - g * group, 0, group)
        jax.lax.fori_loop(
            0, whole, lambda i, _: place(slot, i, g * group + i), None
        )

        @pl.when(g * group + whole < n_pages)
        def _tail():  # the one page the live extent ends inside
            place(slot, whole, g * group + whole, n_live)

    jax.lax.fori_loop(0, n_groups, walk, None)
    walked[0] = first + n_groups

    def close(W: int):
        def zero(j, _):
            rows = pl.ds(pl.multiple_of(j * page, page), page)
            for scr in (k_scr, v_scr):
                scr[:, rows, :] = jnp.zeros((KVH, page, D), scr.dtype)

        jax.lax.fori_loop(n_pages, W // page, zero, None)
        compute(W)

    bucket = sum((n_live > W).astype(jnp.int32) for W in buckets[:-1])
    for k, W in enumerate(buckets):
        pl.when(bucket == k)(functools.partial(close, W))


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
    group = stage_pages(
        page=page, n_blocks=n_blocks, KVH=KVH, D=D, pool_dtype=k_pool.dtype
    )

    # index maps receive the scalar-prefetch refs (table, offsets, layer)
    # last. The pools stay where they lie: the kernel's own DMAs, addressed
    # through those three, ARE the page walk
    def row(shape):
        return pl.BlockSpec(
            (1, *shape), lambda b, tbl, off, lyr: (b,) + (0,) * len(shape)
        )

    in_specs = [
        pl.BlockSpec((M, 1), lambda b, tbl, off, lyr: (0, 0)),
        pl.BlockSpec((KVH, M, 1), lambda b, tbl, off, lyr: (0, 0, 0)),
        row((KVH, M, D)),
        pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [tq, slope, qk, *pools[:2]]
    if int8:
        # the rows' scales by logical position, [B, S, KVH]: one small
        # gather (dead entries read the trash page's; never used)
        in_specs += [row((S, KVH)), row((S, KVH))]
        operands += [p[lyr[0], block_table].reshape(B, S, KVH) for p in pools[2:]]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=in_specs,
        out_specs=row((KVH, M, D)),
        scratch_shapes=[
            pltpu.VMEM((KVH, S, D), dtype),
            pltpu.VMEM((KVH, S, D), dtype),
            pltpu.VMEM((2, group, page, lanes), k_pool.dtype),
            pltpu.VMEM((2, group, page, lanes), k_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, T=T, KVH=KVH, D=D, page=page, n_blocks=n_blocks,
            group=group, buckets=bucket_widths(page=page, n_blocks=n_blocks),
            scale=scale, causal=causal, alibi=alibi, int8=int8,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, M, D), dtype),
        name="paged_attention",
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_bytes(
                T=T, H=H, KVH=KVH, D=D, S=S, dtype=dtype, page=page,
                pool_dtype=k_pool.dtype,
            )
        ),
        interpret=interpret,
    )(block_table, offs, lyr, *operands)
    return (
        out.reshape(B, KVH, T, G, D).transpose(0, 2, 1, 3, 4).reshape(B, T, H, D)
    )

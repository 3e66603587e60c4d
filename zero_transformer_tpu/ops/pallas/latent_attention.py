"""Latent paged-attention decode kernel: one cached row serves every head.

Latent attention (MLA, ``models/mla.py``) caches ONE row a position: the
normed latent ``c_kv`` (``kv_lora_rank`` values), the rotated shared key
``k_pe`` (``qk_rope_head_dim``) and zero lanes up to whole 128-lane tiles
(``ModelConfig.latent_row``: 512 + 64 + 64 = 640 at the published widths).
With the up-projections absorbed into the query and the output, head ``h``'s
score against a position is ``[q_lat_h | q_pe_h | 0] . row`` and its output
``sum p row[:kv_lora_rank]``: the key is the whole row, the value its first
lanes, and the row is fetched once for all heads.

The walk is ``paged_attention``'s, simpler by what a latent row allows: grid
``(B,)``, the pool left in HBM, the row's LIVE pages
``j < ceil((q_offset[b] + T) / page)`` DMAed from ``pool[layer, table[b,
j]]`` straight to their logical place in a ``[S, row]`` scratch (there is no
head-major re-placing: the row IS the key of every head), the next row's
pages started into the other of two such scratches before this row's are
waited for. The page the live extent ends inside has its positions past the
extent set to zero and the scratch from there to the bucket's edge is
zero-filled (``0 * NaN``), and the closing scores / softmax / output matmul
run over the live extent rounded up to a static bucket
(``paged_attention.bucket_widths``).

The pool is allocated in the shape the DMAs read,
``[n_layers, n_pages, page, row]`` (``models.mla.latent_pool_leaves``), and
nothing between allocation and this call reshapes, transposes or slices a
pool-sized value (``tests/test_chip_compile.py`` counts them).

Exactness contract: ``paged_attention``'s, against this module's own
gather path (``gather_attention`` below: the op sequence the kernel
mirrors): scores in f32, the scalar scale multiply, the causal add, the
validity add, ``jax.nn.softmax`` in f32, weights rounded to the compute
dtype, the output matmul accumulated in f32 and rounded once. Interpret
mode: within 1 ulp (bf16) / 4 ulp (f32) at the output's scale
(``tests/test_latent_attention.py``); on the chip within 2 bf16 ulps
(``ops.pallas.parity.latent_vs_gather``, ``chip_smoke.py``'s ``kernels``
phase).

The kernel's ``name=`` is ``latent_paged_attention``: a capture's events of
it are what ``latent_attention_roofline`` reads, and what keeps them out of
``paged_attention_roofline``, which reckons full-head bytes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from zero_transformer_tpu.ops.pallas import kernel_traces
from zero_transformer_tpu.ops.pallas.paged_attention import (
    MAX_DECODE_T,
    VMEM_CEILING,
    bucket_widths,
    interpret_requested,
)
from zero_transformer_tpu.ops.positions import NEG_INF

KERNEL_NAME = "latent_paged_attention"


def _rows(T: int, H: int) -> int:
    """Query rows of one batch row, padded to the 8-sublane tile."""
    return -(-(T * H) // 8) * 8


def vmem_bytes(*, T: int, H: int, R: int, S: int, dtype) -> int:
    """Scoped VMEM the kernel needs: the two ``[S, R]`` row scratches, the
    f32 score-shaped temporaries of the widest bucket and a fixed allowance
    for the q / output blocks and Mosaic's stack."""
    scratch = 2 * S * R * jnp.dtype(dtype).itemsize
    return scratch + 6 * _rows(T, H) * S * 4 + (4 << 20)


def supported(
    impl: str, *, T: int, H: int, R: int, S: int, page_size: int, dtype,
    interpret: bool = False,
) -> bool:
    """Shape gate, as ``paged_attention.supported``: ``R`` is the cached
    row's lanes, ``S`` the cache length."""
    if impl not in ("auto", "flash"):
        return False
    on_tpu = jax.default_backend() == "tpu"
    if not (on_tpu or interpret or interpret_requested()):
        return False
    if T < 1 or T > MAX_DECODE_T:
        return False
    if dtype not in (jnp.bfloat16, jnp.float32):
        return False
    if on_tpu:
        if R % 128:
            return False  # a page DMA moves whole lane tiles of a pool row
        if page_size % 8:
            return False
        if vmem_bytes(T=T, H=H, R=R, S=S, dtype=dtype) > VMEM_CEILING:
            return False
    return True


def gather_attention(
    q: jax.Array, pool: jax.Array, block_table: jax.Array, q_offset: jax.Array,
    *, value_width: int, causal: bool, softmax_scale: float,
    layer: Optional[jax.Array] = None, by_row: bool = False,
) -> jax.Array:
    """The gather path: each row's pages gathered to a ``[S, row]`` view and
    attended over in XLA. q ``[B, T, H, R]`` (absorbed queries, zero in the
    row's padding lanes) -> ``[B, T, H, value_width]``. With ``by_row`` the
    batch rows are taken one at a time (``jax.lax.map``): a prefill chunk's
    ``T * H`` queries against a whole cache row would otherwise hold
    ``B * T * H * S`` float32 scores at once."""
    B, T, H, R = q.shape
    at = () if layer is None else (layer,)
    offs = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32).reshape(-1), (B,))
    tq = jnp.repeat(jnp.arange(T, dtype=jnp.int32), H)  # [M]

    def one(qm, table_row, off):
        rows = pool[at + (table_row,)]  # [n_blocks, page, R]
        rows = rows.reshape(-1, R)  # only the GATHERED view is reshaped
        S = rows.shape[0]
        s = jnp.einsum("mr,sr->ms", qm, rows, preferred_element_type=jnp.float32)
        s = s * jnp.float32(softmax_scale)
        q_pos = off + tq[:, None]
        kv_pos = jnp.arange(S, dtype=jnp.int32)[None, :]
        if causal:
            s = s + jnp.where(kv_pos <= q_pos, 0.0, NEG_INF)
        s = s + jnp.where(kv_pos < off + T, 0.0, NEG_INF)
        w = jax.nn.softmax(s, axis=-1).astype(qm.dtype)
        out = jnp.einsum(
            "ms,sv->mv", w, rows[:, :value_width],
            preferred_element_type=jnp.float32,
        )
        return out.astype(qm.dtype)

    qm = q.reshape(B, T * H, R)
    if by_row:
        out = jax.lax.map(lambda a: one(*a), (qm, block_table, offs))
    else:
        out = jax.vmap(one)(qm, block_table, offs)
    return out.reshape(B, T, H, value_width)


def _kernel(
    table_ref, offs_ref, layer_ref,  # scalar prefetch
    tq_ref, q_ref, pool_hbm, o_ref, lat, sem,
    *, T: int, page: int, n_blocks: int, buckets: tuple, scale: float,
    causal: bool, value_width: int,
):
    """One batch row's attention over its LIVE pages, all heads at once.
    q/out ``[M, R | value_width]`` with row ``m = t * H + h`` (padded to the
    sublane tile); ``tq`` ``[M, 1]`` the window position of row m; ``lat``
    the two ``[S, R]`` row scratches, row b's in slot ``b % 2``."""
    b, B = pl.program_id(0), pl.num_programs(0)
    M, R = q_ref.shape[1:]
    S = n_blocks * page
    lyr = layer_ref[0]
    slot = b % 2

    def live_extent(row):
        n = jnp.clip(offs_ref[row] + T, 1, S)
        return n, (n + page - 1) // page

    def transfer(row, to, wait: bool):
        """Start (or wait for) the DMAs of a row's live pages."""

        def one(j, _):
            dma = pltpu.make_async_copy(
                pool_hbm.at[lyr, table_ref[row, j]],
                lat.at[to, pl.ds(pl.multiple_of(j * page, page), page)],
                sem.at[to],
            )
            dma.wait() if wait else dma.start()

        jax.lax.fori_loop(0, live_extent(row)[1], one, None)

    @pl.when(b == 0)
    def _prime():
        transfer(0, 0, wait=False)

    @pl.when(b + 1 < B)
    def _prefetch():
        transfer(b + 1, 1 - slot, wait=False)

    transfer(b, slot, wait=True)
    n_live, n_pages = live_extent(b)

    # the page the live extent ends inside: positions past it to zero
    tail = pl.ds(pl.multiple_of((n_pages - 1) * page, page), page)
    pos = (n_pages - 1) * page + jax.lax.broadcasted_iota(jnp.int32, (page, R), 0)
    x = lat[slot, tail, :].astype(jnp.float32)
    lat[slot, tail, :] = jnp.where(pos < n_live, x, 0.0).astype(lat.dtype)

    def compute(W: int):
        off = offs_ref[b]
        rows = lat[slot, :W, :]
        s = jax.lax.dot_general(
            q_ref[0], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        s = s * jnp.float32(scale)  # [M, W]
        q_pos = off + tq_ref[...]  # [M, 1]
        kv_pos = jax.lax.broadcasted_iota(jnp.int32, (M, W), 1)
        if causal:
            s = s + jnp.where(kv_pos <= q_pos, 0.0, NEG_INF)
        s = s + jnp.where(kv_pos[:1, :] < off + T, 0.0, NEG_INF)
        w = jax.nn.softmax(s, axis=-1).astype(lat.dtype)
        out = jnp.dot(
            w, rows[:, :value_width], preferred_element_type=jnp.float32
        )
        o_ref[0] = out.astype(o_ref.dtype)

    def close(W: int):
        def zero(j, _):
            at = pl.ds(pl.multiple_of(j * page, page), page)
            lat[slot, at, :] = jnp.zeros((page, R), lat.dtype)

        jax.lax.fori_loop(n_pages, W // page, zero, None)
        compute(W)

    bucket = sum((n_live > W).astype(jnp.int32) for W in buckets[:-1])
    for k, W in enumerate(buckets):
        pl.when(bucket == k)(functools.partial(close, W))


# graftlint: hot-path
def latent_paged_attention(
    q: jax.Array, pool: jax.Array, block_table: jax.Array, q_offset: jax.Array,
    *, value_width: int, causal: bool, softmax_scale: float,
    layer: Optional[jax.Array] = None, interpret: bool = False,
) -> jax.Array:
    """Decode attention straight off the latent page pool. q ``[B, T, H,
    R]`` (T = 1 decode, 1 + K spec verify; absorbed, rotated, zero in the
    row's padding lanes; overflow rows NaN-poisoned by the caller); ``pool``
    ``[n_pages, page, R]`` or, with ``layer``, the stacked ``[n_layers,
    n_pages, page, R]``; ``block_table`` ``[B, n_blocks]``; ``q_offset``
    ``[B]``. Returns ``[B, T, H, value_width]``: the attended latent, which
    the caller up-projects. Forward-only."""
    B, T, H, R = q.shape
    if pool.ndim != (3 if layer is None else 4) or pool.shape[-1] != R:
        raise ValueError(
            f"pool {pool.shape}: expected [n_pages, page, {R}], or "
            f"[n_layers, n_pages, page, {R}] with a layer index"
        )
    page = pool.shape[-2]
    interpret = interpret or (
        jax.default_backend() != "tpu" and interpret_requested()
    )
    block_table = block_table.astype(jnp.int32)
    n_blocks = block_table.shape[1]
    S = n_blocks * page
    dtype = q.dtype
    offs = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32).reshape(-1), (B,))
    if layer is None:  # an unstacked pool is a stack of one: moves no byte
        layer, pool = 0, pool[None]
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)
    kernel_traces[KERNEL_NAME] += 1
    M = _rows(T, H)
    qm = jnp.pad(q.reshape(B, T * H, R), ((0, 0), (0, M - T * H), (0, 0)))
    tq = jnp.pad(jnp.repeat(jnp.arange(T, dtype=jnp.int32), H), (0, M - T * H))

    def row(*shape):
        return pl.BlockSpec(
            (1, *shape), lambda b, tbl, off, lyr: (b,) + (0,) * len(shape)
        )

    out = pl.pallas_call(
        functools.partial(
            _kernel, T=T, page=page, n_blocks=n_blocks,
            buckets=bucket_widths(page=page, n_blocks=n_blocks),
            scale=float(softmax_scale), causal=causal, value_width=value_width,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((M, 1), lambda b, tbl, off, lyr: (0, 0)),
                row(M, R),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=row(M, value_width),
            scratch_shapes=[
                pltpu.VMEM((2, S, R), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, M, value_width), dtype),
        name=KERNEL_NAME,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_bytes(T=T, H=H, R=R, S=S, dtype=dtype)
        ),
        interpret=interpret,
    )(block_table, offs, lyr, tq.reshape(M, 1), qm, pool)
    return out[:, : T * H].reshape(B, T, H, value_width)

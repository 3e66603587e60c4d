"""Decode-time state update of a Mamba-2 layer: one step of the recurrence
for every batch row, the state updated IN PLACE.

Per (row, head) the layer keeps ``H`` ``[head_dim, d_state]`` in float32
(``models/mamba.py``). A decode step is::

    H' = exp(dt * A) * H + dt * (x outer B);   y = H' C + D * x

which reads and writes the whole state once and multiplies almost nothing:
at granite-4.0-h-micro's widths a row's state is 2.1 MB a layer, a step's
operands 17 KB. The state is allocated stacked, ``[n_layers, rows, heads,
head_dim, d_state]``, and rides the layer loop's carry as the K/V pools do
(``models.gpt.kv_pool_leaves`` says why): this kernel takes the WHOLE stack
with the layer as a prefetched scalar and aliases it to its output, so
nothing slices a state-pool-sized value out of the carry or copies it back
(a custom call's operands are whole buffers: PERF.md section 6, PR 31).

Grid ``(rows, heads / block)``; each step moves one ``[block, head_dim,
d_state]`` tile in and out through the pipeline, and the grid visits the
rows that decode this tick ALONE. ``live`` (``[rows]``) is compacted by
``live_order`` into ``order`` (the live rows, then the last of them
repeated) and ``n_live``, both prefetched (``models.mamba.step_rows`` builds
them once a decode program, before the layer loop); step ``(s, h)`` maps
every operand to row ``order[s]``. A parked or mid-prefill row is never
DMA'd: its state stays in the aliased HBM buffer, bit for bit, and its ``y``
is zeroed outside the kernel. The steps past ``n_live`` map to the SAME
block as the last live step (its row, the last head block), so the pipeline
fetches and writes back nothing for them, and their body does nothing: the
block they hold is that step's computed output. The pipeline writes back every block
it visits, filled or not: with no row live every step maps to one block,
which the first step copies through. With every row live the grid is the
all-rows grid it replaced.

What it buys, measured in the serving cell (PERF.md section 6, PR 33): the
decode program of granite-4.0-h-micro at 32 slots takes 19.04 ms with this
kernel and 21.32 ms with the ``jax.numpy`` step below in its place (63 us a
layer; XLA updates the carry in place too, so neither copies the state).
Timed ALONE, one layer, the two are equal (339 against 333 us): the
difference is what the step's neighbours in a whole program cost XLA's
fusions, so measure it there. Visiting the decoding rows alone (PR 37, the
same cell, some 12 of 32 rows decoding): 77.96 us a call against 217.2, the
decode program 14.01 ms against 18.74.

``ssm_update_reference`` is the same step in plain ``jax.numpy``: what the
CPU runs, what a refused shape falls back to (``supported``), and what the
kernel is held to (interpret mode: ``tests/test_state_slots.py``; on the
chip: ``ops.pallas.parity.ssm_update_vs_xla``). Both compute in float32 in
the same order of operations; they differ by the lane reduction's order of
sums in ``y`` and by where a compiler contracts a multiply-add: the new
state within 2 ulps at its own scale, ``y`` within 4.

The kernel's ``name=`` is ``ssm_state_update``: a capture's events of it are
what ``ssm_state_update_roofline`` reads.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from zero_transformer_tpu.ops.pallas import kernel_traces
from zero_transformer_tpu.ops.pallas.paged_attention import (
    VMEM_CEILING,
    interpret_requested,
)

KERNEL_NAME = "ssm_state_update"
# bytes of state one grid step moves each way: large enough that a step's
# fixed cost (0.35 us) is small beside its DMA (2.6 us at 819 GB/s), small
# enough that in + out, double-buffered, sit far inside scoped VMEM
TILE_BYTES = 1 << 20


def head_block(heads: int, head_dim: int, d_state: int) -> int:
    """Heads a grid step takes: the most whose tile is within ``TILE_BYTES``,
    a divisor of ``heads`` and a whole number of sublane tiles (or all)."""
    per_head = head_dim * d_state * 4
    for hb in range(heads, 0, -1):
        if heads % hb == 0 and hb * per_head <= TILE_BYTES and (
            hb % 8 == 0 or hb == heads
        ):
            return hb
    return heads


def vmem_bytes(*, heads: int, head_dim: int, d_state: int) -> int:
    hb = head_block(heads, head_dim, d_state)
    return 4 * hb * head_dim * d_state * 4 + 3 * hb * head_dim * d_state * 4 + (4 << 20)


def supported(
    *, heads: int, head_dim: int, d_state: int, dtype=jnp.float32,
    interpret: bool = False,
) -> bool:
    """Shape gate: a TPU (or interpret mode), a float32 state whose minor
    pair is whole ``(8, 128)`` tiles."""
    on_tpu = jax.default_backend() == "tpu"
    if not (on_tpu or interpret or interpret_requested()):
        return False
    if jnp.dtype(dtype) != jnp.float32:
        return False
    if on_tpu:
        if d_state % 128 or head_dim % 8:
            return False
        if vmem_bytes(heads=heads, head_dim=head_dim, d_state=d_state) > VMEM_CEILING:
            return False
    return True


def ssm_update_reference(state, x, dt, A, Bm, Cm, D, live=None, layer=None):
    """The step in plain ``jax.numpy``. ``state`` ``[rows, heads, head_dim,
    d_state]`` float32 or, with ``layer``, the stack ``[n_layers, ...]``;
    ``x`` ``[rows, heads, head_dim]``, ``dt`` ``[rows, heads]`` (after
    softplus), ``A``, ``D`` ``[heads]``, ``Bm``, ``Cm`` ``[rows, d_state]``,
    all float32; ``live`` ``[rows]`` bool or None (every row). Returns ``(y
    [rows, heads, head_dim], state)`` with the rows that are not live left
    as they were."""
    h = state if layer is None else state[layer]
    decay = jnp.exp(dt * A)[..., None, None]
    new = decay * h + (dt[..., None] * x)[..., None] * Bm[:, None, None, :]
    y = jnp.sum(new * Cm[:, None, None, :], axis=-1) + D[:, None] * x
    if live is not None:
        new = jnp.where(live[:, None, None, None], new, h)
        y = jnp.where(live[:, None, None], y, 0.0)
    return y, (new if layer is None else state.at[layer].set(new))


def live_order(live: jax.Array):
    """``(order [rows], n_live [1])`` int32 from ``live`` ``[rows]``: the live
    rows in ascending order, then the last of them repeated (row 0 with
    none), and how many are live: grid step ``s`` visits row ``order[s]``.
    One-hot sums, not a sort: a few small fusions of ``rows x rows``."""
    live = live.astype(jnp.int32)
    n_live = jnp.sum(live)
    rows = jnp.arange(live.shape[0], dtype=jnp.int32)
    # the k-th live row is the one with k live rows before it; past the
    # last, the last
    k = jnp.minimum(rows, jnp.maximum(n_live - 1, 0))
    seen = jnp.cumsum(live) - live
    hit = (live[None, :] > 0) & (seen[None, :] == k[:, None])
    order = jnp.sum(jnp.where(hit, rows[None, :], 0), axis=1)
    return order.astype(jnp.int32), n_live.reshape(1)


def _kernel(layer_ref, order_ref, n_live_ref, h_ref, da_ref, dtx_ref, b_ref,
            c_ref, dx_ref, o_ref, y_ref):
    """One ``[hb, P, N]`` tile of one row. ``da`` ``[hb, 1]`` is ``exp(dt
    A)``, ``dtx`` ``[hb, P]`` is ``dt * x``, ``dx`` ``D * x``."""
    del layer_ref, order_ref
    step, n_live = pl.program_id(0), n_live_ref[0]

    @pl.when(step < n_live)
    def _step():
        new = da_ref[0][:, :, None] * h_ref[0, 0] \
            + dtx_ref[0][:, :, None] * b_ref[0][None, :, :]
        o_ref[0, 0] = new
        y_ref[0] = jnp.sum(new * c_ref[0][None, :, :], axis=-1) + dx_ref[0]

    # no row live: the one block every step holds goes back as it came
    @pl.when((n_live == 0) & (step == 0) & (pl.program_id(1) == 0))
    def _keep():
        o_ref[0, 0] = h_ref[0, 0]
        y_ref[0] = jnp.zeros_like(y_ref[0])


# graftlint: hot-path
def ssm_update(
    state: jax.Array, x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
    Cm: jax.Array, D: jax.Array, live: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None, *, rows: Optional[tuple] = None,
    interpret: bool = False,
):
    """``ssm_update_reference`` as a Pallas kernel, the state aliased to the
    output: with ``layer`` the caller's stack is updated in place at that
    layer (donate it, or carry it through a loop). ``rows`` is
    ``live_order(live)`` where the caller built it once for many calls."""
    stacked = layer is not None
    if not stacked:  # an unstacked state is a stack of one: moves no byte
        layer, state = 0, state[None]
    _, S, H, P, N = state.shape
    interpret = interpret or (
        jax.default_backend() != "tpu" and interpret_requested()
    )
    hb = head_block(H, P, N)
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)
    live = jnp.ones((S,), jnp.int32) if live is None else live.astype(jnp.int32)
    order, n_live = live_order(live) if rows is None else rows
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    da = jnp.exp(dt * A.astype(f32))[..., None]  # [S, H, 1]
    dtx = dt[..., None] * x  # [S, H, P]
    dx = D.astype(f32)[:, None] * x
    kernel_traces[KERNEL_NAME] += 1

    def heads(s, h, n_live):
        """Step ``(s, h)``'s head block: past the live rows, the last (the
        last live step's block, or row 0's last with none)."""
        return lax.select(s < n_live[0], h, H // hb - 1)

    def per_head(width):
        def index(s, h, lyr, order, n_live):
            return order[s], heads(s, h, n_live), 0
        return pl.BlockSpec((1, hb, width), index)

    def per_row():
        return pl.BlockSpec((1, 1, N), lambda s, h, lyr, order, n_live: (order[s], 0, 0))

    def tile_index(s, h, lyr, order, n_live):
        return lyr[0], order[s], heads(s, h, n_live), 0, 0

    tile = pl.BlockSpec((1, 1, hb, P, N), tile_index)
    new, y = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S, H // hb),
            in_specs=[tile, per_head(1), per_head(P), per_row(),
                      per_row(), per_head(P)],
            out_specs=[tile, per_head(P)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct((S, H, P), f32),
        ],
        # operand 3 (after the three prefetched scalars) is the state
        input_output_aliases={3: 0},
        name=KERNEL_NAME,
        compiler_params=pltpu.CompilerParams(
            # a step past the live rows holds the block its predecessor
            # computed: the steps run in order
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_bytes(heads=H, head_dim=P, d_state=N),
        ),
        interpret=interpret,
    )(lyr, order, n_live, state, da, dtx, Bm.astype(f32)[:, None, :],
      Cm.astype(f32)[:, None, :], dx)
    # the rows the grid did not visit hold no y: zero, as the reference's
    y = jnp.where(live[:, None, None] > 0, y, 0.0)
    return y, (new if stacked else new[0])

"""Decode-time state update of a KDA layer (Kimi Delta Attention,
``models/kda.py``): one step of the gated delta rule for every batch row,
the state updated IN PLACE.

Per (row, head) the layer keeps ``S`` ``[D, D]`` in float32. A decode step
is::

    S' = Diag(exp(g)) S;   S = S' + beta k (v - S'^T k)^T;   o = S^T q

which reads and writes the whole state once and multiplies almost nothing:
at Kimi-Linear-48B-A3B's widths a row's state is 2.1 MB a layer (32 heads of
128 x 128), a step's operands 64 KB. The state is allocated stacked,
``[n_layers, rows, heads, D, D]``, and rides the stack's carry as the K/V
pools do: this kernel takes the WHOLE stack with the layer as a prefetched
scalar and aliases it to its output, so nothing slices a state-pool-sized
value out of the carry or copies it back.

Grid ``(rows, heads / block)``, the schedule of ``ops.pallas.ssm_update``:
each step moves one ``[block, D, D]`` tile in and out, and the grid visits
the rows that decode this tick ALONE (``live_order``'s ``order`` and
``n_live``, prefetched; built once a decode program by
``models.mamba.step_rows``). A parked or mid-prefill row is never DMA'd: its
state stays in the aliased HBM buffer, bit for bit, and its ``o`` is zeroed
outside the kernel. The steps past ``n_live`` map to the SAME block as the
last live step, so the pipeline fetches and writes back nothing for them.

``kda_update_reference`` is the same step in plain ``jax.numpy``: what the
CPU runs, what a refused shape falls back to (``supported``), and what the
kernel is held to (interpret mode: ``tests/test_kimi_family.py``). Both
compute in float32 in the same order of operations; they differ by the
order of the sublane sums.

The kernel's ``name=`` is ``kda_state_update``: a capture's events of it are
what ``kda_state_update_roofline`` reads.
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
from zero_transformer_tpu.ops.pallas.ssm_update import TILE_BYTES, live_order

KERNEL_NAME = "kda_state_update"


def head_block(heads: int, head_dim: int) -> int:
    """Heads a grid step takes: the most whose tile is within ``TILE_BYTES``,
    a divisor of ``heads`` and a whole number of sublane tiles (or all)."""
    per_head = head_dim * head_dim * 4
    for hb in range(heads, 0, -1):
        if heads % hb == 0 and hb * per_head <= TILE_BYTES and (
            hb % 8 == 0 or hb == heads
        ):
            return hb
    return heads


def vmem_bytes(*, heads: int, head_dim: int) -> int:
    hb = head_block(heads, head_dim)
    return 7 * hb * head_dim * head_dim * 4 + (4 << 20)


def supported(*, heads: int, head_dim: int, dtype=jnp.float32,
              interpret: bool = False) -> bool:
    """Shape gate: a TPU (or interpret mode), a float32 state whose minor
    pair is whole ``(8, 128)`` tiles."""
    on_tpu = jax.default_backend() == "tpu"
    if not (on_tpu or interpret or interpret_requested()):
        return False
    if jnp.dtype(dtype) != jnp.float32:
        return False
    if on_tpu:
        if head_dim % 128:
            return False
        if vmem_bytes(heads=heads, head_dim=head_dim) > VMEM_CEILING:
            return False
    return True


def kda_update_reference(state, q, k, v, g, beta, live=None, layer=None):
    """The step in plain ``jax.numpy``. ``state`` ``[rows, heads, D, D]``
    float32 or, with ``layer``, the stack ``[n_layers, ...]``; ``q``, ``k``,
    ``v``, ``g`` ``[rows, heads, D]``, ``beta`` ``[rows, heads]``, all
    float32; ``live`` ``[rows]`` bool or None (every row). Returns ``(o
    [rows, heads, D], state)`` with the rows that are not live left as they
    were."""
    s = state if layer is None else state[layer]
    decayed = jnp.exp(g)[..., None] * s
    u = beta[..., None] * (v - jnp.sum(k[..., None] * decayed, axis=-2))
    new = decayed + k[..., None] * u[..., None, :]
    o = jnp.sum(q[..., None] * new, axis=-2)
    if live is not None:
        new = jnp.where(live[:, None, None, None], new, s)
        o = jnp.where(live[:, None, None], o, 0.0)
    return o, (new if layer is None else state.at[layer].set(new))


def _kernel(layer_ref, order_ref, n_live_ref, s_ref, a_ref, k_ref, v_ref,
            q_ref, b_ref, o_ref, out_ref):
    """One ``[hb, D, D]`` tile of one row. ``a`` ``[hb, D]`` is ``exp(g)``,
    ``b`` ``[hb, 1]`` is beta."""
    del layer_ref, order_ref
    step, n_live = pl.program_id(0), n_live_ref[0]

    @pl.when(step < n_live)
    def _step():
        k = k_ref[0][:, :, None]
        decayed = a_ref[0][:, :, None] * s_ref[0, 0]
        u = b_ref[0] * (v_ref[0] - jnp.sum(k * decayed, axis=1))
        new = decayed + k * u[:, None, :]
        o_ref[0, 0] = new
        out_ref[0] = jnp.sum(q_ref[0][:, :, None] * new, axis=1)

    # no row live: the one block every step holds goes back as it came
    @pl.when((n_live == 0) & (step == 0) & (pl.program_id(1) == 0))
    def _keep():
        o_ref[0, 0] = s_ref[0, 0]
        out_ref[0] = jnp.zeros_like(out_ref[0])


# graftlint: hot-path
def kda_update(
    state: jax.Array, q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
    beta: jax.Array, live: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None, *, rows: Optional[tuple] = None,
    interpret: bool = False,
):
    """``kda_update_reference`` as a Pallas kernel, the state aliased to the
    output: with ``layer`` the caller's stack is updated in place at that
    layer (donate it, or carry it through a loop). ``rows`` is
    ``live_order(live)`` where the caller built it once for many calls."""
    stacked = layer is not None
    if not stacked:  # an unstacked state is a stack of one: moves no byte
        layer, state = 0, state[None]
    _, S, H, D, _ = state.shape
    interpret = interpret or (
        jax.default_backend() != "tpu" and interpret_requested()
    )
    hb = head_block(H, D)
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)
    live = jnp.ones((S,), jnp.int32) if live is None else live.astype(jnp.int32)
    order, n_live = live_order(live) if rows is None else rows
    f32 = jnp.float32
    a = jnp.exp(g.astype(f32))
    kernel_traces[KERNEL_NAME] += 1

    def heads(s, h, n_live):
        """Step ``(s, h)``'s head block: past the live rows, the last."""
        return lax.select(s < n_live[0], h, H // hb - 1)

    def per_head(width):
        def index(s, h, lyr, order, n_live):
            return order[s], heads(s, h, n_live), 0
        return pl.BlockSpec((1, hb, width), index)

    def tile_index(s, h, lyr, order, n_live):
        return lyr[0], order[s], heads(s, h, n_live), 0, 0

    tile = pl.BlockSpec((1, 1, hb, D, D), tile_index)
    new, o = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S, H // hb),
            in_specs=[tile, per_head(D), per_head(D), per_head(D), per_head(D),
                      per_head(1)],
            out_specs=[tile, per_head(D)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct((S, H, D), f32),
        ],
        # operand 3 (after the three prefetched scalars) is the state
        input_output_aliases={3: 0},
        name=KERNEL_NAME,
        compiler_params=pltpu.CompilerParams(
            # a step past the live rows holds the block its predecessor
            # computed: the steps run in order
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_bytes(heads=H, head_dim=D),
        ),
        interpret=interpret,
    )(lyr, order, n_live, state, a, k.astype(f32), v.astype(f32), q.astype(f32),
      beta.astype(f32)[..., None])
    # the rows the grid did not visit hold no o: zero, as the reference's
    o = jnp.where(live[:, None, None] > 0, o, 0.0)
    return o, (new if stacked else new[0])

"""Position encodings: ALiBi slopes/bias and RoPE.

ALiBi math mirrors the reference's capability (reference ``src/models/layers.py:17-44``:
geometric slope schedule with the non-power-of-2 interpolation from the ALiBi
paper) but is re-derived here in closed form and built lazily under jit for the
trace-time sequence length — this is what gives train-short/test-long
extrapolation (reference ``logs/580.md:30``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

NEG_INF = -1e10  # additive mask value; large but finite so f32 softmax is exact


@functools.lru_cache(maxsize=None)
def alibi_slopes_list(n_heads: int) -> tuple:
    """ALiBi head slopes: geometric sequence starting at 2^(-8/n) for
    power-of-two n, with the published interpolation otherwise."""

    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start**i) for i in range(n)]

    if math.log2(n_heads).is_integer():
        return tuple(pow2_slopes(n_heads))
    closest = 2 ** math.floor(math.log2(n_heads))
    extra = pow2_slopes(2 * closest)[0::2][: n_heads - closest]
    return tuple(pow2_slopes(closest) + extra)


def alibi_slopes(n_heads: int, dtype=jnp.float32) -> jax.Array:
    return jnp.asarray(alibi_slopes_list(n_heads), dtype=dtype)


def alibi_bias(
    n_heads: int, q_len: int, kv_len: int, offset: int = 0, dtype=jnp.float32,
    slopes=None,
) -> jax.Array:
    """[n_heads, q_len, kv_len] additive attention bias: -slope * distance.

    ``offset`` positions the query block within the full sequence — used for
    single-query decode with a KV cache, where q position = offset (the
    capability the reference's Flax side lacks and its torch side rebuilds
    dynamically, reference ``torch_compatability/GPT2.py:191-235``).
    ``slopes`` ([n_heads] or [n_heads, 1]) overrides the slope table for
    head-sharded callers whose local head 0 is not global head 0.
    """
    q_pos = jnp.arange(q_len, dtype=jnp.int32) + offset
    kv_pos = jnp.arange(kv_len, dtype=jnp.int32)
    # distance to the key, clamped at 0 (future keys are masked separately)
    dist = jnp.maximum(q_pos[:, None] - kv_pos[None, :], 0).astype(dtype)
    if slopes is None:
        slopes = alibi_slopes(n_heads, dtype)
    slopes = slopes.reshape(n_heads).astype(dtype)
    return -slopes[:, None, None] * dist[None, :, :]


def causal_mask_bias(q_len: int, kv_len: int, offset: int = 0, dtype=jnp.float32) -> jax.Array:
    """[q_len, kv_len] additive causal mask (0 where visible, NEG_INF where not)."""
    q_pos = jnp.arange(q_len, dtype=jnp.int32) + offset
    kv_pos = jnp.arange(kv_len, dtype=jnp.int32)
    visible = kv_pos[None, :] <= q_pos[:, None]
    return jnp.where(visible, 0.0, NEG_INF).astype(dtype)


def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jax.Array:
    """Inverse frequencies for rotary embeddings, [head_dim // 2] float32."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponent)


def apply_rope(
    x: jax.Array, positions: jax.Array, theta: float = 10000.0,
    interleaved: bool = False,
) -> jax.Array:
    """Rotate [..., T, n_heads, head_dim] by position. ``positions`` is [T] or
    broadcastable to x's batch+time dims; rotation math runs in float32.
    A pair is lanes ``(i, i + head_dim/2)``, or with ``interleaved`` lanes
    ``(2i, 2i + 1)``."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)  # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., T, hd/2]
    # insert head axis
    angles = angles[..., None, :]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    xf = x.astype(jnp.float32)
    if interleaved:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
        rotated = jnp.stack(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        ).reshape(x.shape)
        return rotated.astype(x.dtype)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return rotated.astype(x.dtype)

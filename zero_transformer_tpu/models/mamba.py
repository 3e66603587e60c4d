"""Mamba-2 mixer (Dao & Gu 2405.21060; the ``granitemoehybrid`` /  Bamba
layer): a selective state-space layer in place of attention.

Per position ``t`` of a row, with ``n`` the normed input::

    [z | xBC | dt] = n W_in                     inner | inner + 2 N | heads
    xBC_t  = silu(b + sum_j w_j xBC_{t-K+1+j})  causal depthwise conv, K taps
    [x | B | C] = xBC                           x: heads x head_dim;  B, C: N
    dt_t   = softplus(dt_t + dt_bias);   A = -exp(A_log)          (per head)
    H_t    = exp(dt_t A) H_{t-1} + dt_t (x_t outer B_t)   [heads, head_dim, N]
    y_t    = H_t C_t + D x_t
    out    = (w * g / sqrt(mean(g^2) + eps)) W_out,   g = y * silu(z)

Softplus, the decay, the state and the gated norm are float32.

**What the layer keeps a row, whatever the row's length**: the state ``H``
(float32) and the conv's last ``K - 1`` inputs (the compute dtype), declared
by ``mamba_state_leaves`` as ``kv_pool_leaves`` declares an attention's K/V.
A serving slot's state lives beside the K/V pages: under the paged stack ONE
stacked leaf a kind, ``ssm_state [n_mamba_layers, rows, heads, head_dim, N]``
and ``conv_state [n_mamba_layers, rows, (K - 1) * channels]`` (the taps
merged into the lane axis: a ``[3, channels]`` minor pair would pad to 16
sublanes), riding the layer loop's carry and indexed by the layer's place
among the mamba layers. One layout from allocation to kernel.

Two orders of the same sums. A window of ``T > 1`` positions (a full
forward, a prefill chunk) is the CHUNKED form (``ssd_chunk``): with ``a_t =
dt_t A`` and ``L_t = sum_{s<=t} a_s``::

    y_t = C_t . (exp(L_t) H_0 + sum_{s<=t} exp(L_t - L_s) dt_s (x_s outer B_s)) + D x_t
    H_T = exp(L_T) H_0 + sum_s exp(L_T - L_s) dt_s (x_s outer B_s)

matmuls over the window, float32 at ``highest``. ``T == 1`` through the cache
is one step of the recurrence (``ops.pallas.ssm_update``: the state in
place). ``valid`` ``[rows]`` is how many of the window's positions are REAL:
the positions past it (a chunk's padded tail; a row that does not decode
this tick has 0 of 1) leave ``H`` and the conv inputs exactly as they were:
their ``dt`` is 0 and the kept inputs are gathered from the last real ones.
"""
from __future__ import annotations

import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.nn import initializers

from zero_transformer_tpu.config import ModelConfig, resolve_dtype

SSM_LEAF, CONV_LEAF = "ssm_state", "conv_state"
# cache leaves that hold a ROW's recurrent state: a rows axis (axis 1 under
# the stacked paged cache) and no position axis
STATE_LEAVES = (SSM_LEAF, CONV_LEAF)
HIGHEST = jax.lax.Precision.HIGHEST


def mamba_state_leaves(cfg: ModelConfig, rows: int, dtype) -> dict:
    """``{leaf name: (shape, dtype)}`` of ONE mamba layer's state for
    ``rows`` batch rows."""
    return {
        SSM_LEAF: (
            (rows, cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state),
            jnp.float32,
        ),
        CONV_LEAF: ((rows, (cfg.mamba_conv - 1) * cfg.mamba_conv_dim), dtype),
    }


def causal_conv(xbc, tail, w, b, valid=None):
    """Depthwise causal conv over ``xbc`` ``[B, T, C]`` continued from the
    ``K - 1`` inputs before it (``tail`` ``[B, K - 1, C]``), as ``K`` shifted
    sums in float32. Returns ``(out [B, T, C] float32, the last K - 1 REAL
    inputs)``: with ``valid`` ``[B]`` the inputs that end at position
    ``valid``, so a row with none keeps its tail."""
    K = w.shape[0]
    T = xbc.shape[1]
    full = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    out = b.astype(jnp.float32) + sum(
        w[j].astype(jnp.float32) * full[:, j : j + T].astype(jnp.float32)
        for j in range(K)
    )
    if valid is None:
        return out, full[:, T:]
    keep = jax.vmap(
        lambda f, v: jax.lax.dynamic_slice_in_dim(f, v, K - 1, axis=0)
    )(full, valid)
    return out, keep


def ssd_chunk(x, dt, A, Bm, Cm, D, h0):
    """The chunked form over one window. ``x`` ``[B, T, H, P]``, ``dt``
    ``[B, T, H]`` (after softplus; 0 at a position that must not move the
    state), ``A``, ``D`` ``[H]``, ``Bm``, ``Cm`` ``[B, T, N]``, ``h0`` ``[B,
    H, P, N]``, all float32. Returns ``(y [B, T, H, P], h_T)``."""
    T = x.shape[1]
    L = jnp.cumsum(dt * A, axis=1)  # [B, T, H], <= 0 and falling
    # exp(L_t - L_s) for s <= t; masked BEFORE the exp (s > t would overflow)
    diff = L[:, :, None, :] - L[:, None, :, :]  # [B, T, S, H]
    causal = jnp.tril(jnp.ones((T, T), jnp.bool_))[None, :, :, None]
    within = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    G = jnp.einsum("btn,bsn->bts", Cm, Bm, precision=HIGHEST)
    M = G[..., None] * within * dt[:, None, :, :]
    y = jnp.einsum("btsh,bshp->bthp", M, x, precision=HIGHEST)
    y = y + jnp.exp(L)[..., None] * jnp.einsum(
        "btn,bhpn->bthp", Cm, h0, precision=HIGHEST
    )
    y = y + D[None, None, :, None] * x
    to_end = jnp.exp(L[:, -1:, :] - L) * dt  # [B, T, H]
    h = jnp.exp(L[:, -1, :])[..., None, None] * h0 + jnp.einsum(
        "bsh,bshp,bsn->bhpn", to_end, x, Bm, precision=HIGHEST
    )
    return y, h


def ssd_scan(x, dt, A, Bm, Cm, D, h0, chunk: int):
    """``ssd_chunk`` over a window of any length, ``chunk`` positions a
    step (the tail padded with ``dt`` 0, which moves nothing)."""
    B, T = x.shape[:2]
    if T <= chunk:
        return ssd_chunk(x, dt, A, Bm, Cm, D, h0)
    n = -(-T // chunk)
    pad = n * chunk - T

    def split(a):
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape((B, n, chunk) + a.shape[2:]), 1, 0)

    def step(h, xs):
        y, h = ssd_chunk(xs[0], xs[1], A, xs[2], xs[3], D, h)
        return h, y

    h, ys = jax.lax.scan(step, h0, (split(x), split(dt), split(Bm), split(Cm)))
    y = jnp.moveaxis(ys, 0, 1).reshape((B, n * chunk) + x.shape[2:])
    return y[:, :T], h


def ssm_step(state, x, dt, A, Bm, Cm, D, live, layer):
    """One decode step for every row, the state (a stack when ``layer`` is
    given) updated in place: the Pallas kernel where its gate accepts the
    shape, the same step in ``jax.numpy`` elsewhere."""
    from zero_transformer_tpu.ops.pallas import ssm_update as su

    _, H, P, N = state.shape[-4:]
    if su.supported(heads=H, head_dim=P, d_state=N, dtype=state.dtype):
        from zero_transformer_tpu.parallel.sharding import shard_kernel

        def local(state, x, dt, A, Bm, Cm, D, live, lyr):
            y, new = su.ssm_update(
                state, x, dt, A, Bm, Cm, D, live,
                None if layer is None else lyr[0],
            )
            return y, new

        lyr = jnp.asarray(0 if layer is None else layer, jnp.int32).reshape(1)
        args = (state, x, dt, A, Bm, Cm, D, live.astype(jnp.int32), lyr)
        # on a mesh every device takes every operand whole (the state is
        # replicated: it has no head axis the tensor axis is known to divide)
        whole = [(None,) * a.ndim for a in args]
        return shard_kernel(local, whole, (whole[1], whole[0]))(*args)
    return su.ssm_update_reference(state, x, dt, A, Bm, Cm, D, live, layer)


def _dt_bias_init(key, shape, dtype):
    """The published init: ``dt`` log-uniform in [1e-3, 1e-1], stored as the
    softplus's inverse."""
    u = jax.random.uniform(key, shape, jnp.float32)
    dt = jnp.exp(u * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log_init(key, shape, dtype):
    """The published init: ``A`` uniform in [1, 16]."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)


class Mamba2Mixer(nn.Module):
    """The mixer of a "mamba" block. Called with ``pools`` (the paged
    stack's carry, which holds the stacked state leaves) and this layer's
    place among the mamba layers, it reads and writes the stack at
    ``layer`` and returns ``(out, pools)``; without, a decode model keeps
    its own per-layer leaves. ``valid``: see the module docstring."""

    cfg: ModelConfig
    decode: bool = False

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        valid: Optional[jax.Array] = None,
        pools: Optional[dict] = None,
        layer: Optional[jax.Array] = None,
    ):
        cfg = self.cfg
        dtype = x.dtype
        param_dtype = resolve_dtype(cfg.param_dtype)
        B, T, _ = x.shape
        H, P, N, K = cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state, cfg.mamba_conv
        inner, C = cfg.mamba_inner, cfg.mamba_conv_dim
        f32 = jnp.float32

        def dense(features, axes, std, name):
            return nn.Dense(
                features, use_bias=False, dtype=dtype, param_dtype=param_dtype,
                kernel_init=nn.with_partitioning(initializers.normal(stddev=std), axes),
                name=name,
            )

        def vector(name, init, n):
            return self.param(
                name, nn.with_partitioning(init, (None,)), (n,), param_dtype
            ).astype(f32)

        conv_w = self.param(
            "conv_kernel",
            nn.with_partitioning(initializers.normal(stddev=K ** -0.5), (None, "mlp")),
            (K, C), param_dtype,
        )
        conv_b = vector("conv_bias", initializers.zeros, C)
        dt_bias = vector("dt_bias", _dt_bias_init, H)
        A = -jnp.exp(vector("A_log", _a_log_init, H))
        D = vector("D", initializers.ones, H)
        norm_w = vector("norm_scale", initializers.ones, inner)

        use_cache = False
        own = None
        if self.decode:
            leaves = mamba_state_leaves(cfg, B, dtype)
            if pools is None:
                use_cache = not self.is_initializing() and self.has_variable(
                    "cache", SSM_LEAF
                )
                own = {
                    name: self.variable("cache", name, jnp.zeros, shape, dt)
                    for name, (shape, dt) in leaves.items()
                }
                state = {name: var.value for name, var in own.items()}
            else:
                # (init traces hand the stack back untouched)
                use_cache = not self.is_initializing()
                state = pools

        with jax.named_scope("ssm_project"):
            zxbcdt = dense(inner + C + H, ("embed", "mlp"), 0.02, "in_proj")(x)
            z, xbc, dt = jnp.split(zxbcdt, [inner, inner + C], axis=-1)

        with jax.named_scope("ssm_conv"):
            if use_cache:
                tail = state[CONV_LEAF] if layer is None else state[CONV_LEAF][layer]
                tail = tail.reshape(B, K - 1, C)
            else:
                tail = jnp.zeros((B, K - 1, C), dtype)
            xbc, tail = causal_conv(xbc, tail, conv_w, conv_b, valid)
            xbc = jax.nn.silu(xbc)
        xs = xbc[..., :inner].reshape(B, T, H, P)
        Bm, Cm = xbc[..., inner : inner + N], xbc[..., inner + N :]
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias)
        if valid is not None:
            real = jnp.arange(T, dtype=jnp.int32)[None, :] < valid[:, None]
            dt = jnp.where(real[..., None], dt, 0.0)

        with jax.named_scope("ssm_scan"):
            if use_cache:
                h0 = state[SSM_LEAF]
            else:
                h0 = jnp.zeros((B, H, P, N), f32)
            if use_cache and T == 1:
                live = jnp.ones((B,), jnp.bool_) if valid is None else valid > 0
                y, h = ssm_step(
                    h0, xs[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D, live, layer
                )
                y = y[:, None]
            else:
                start = h0 if (layer is None or not use_cache) else h0[layer]
                y, h = ssd_scan(xs, dt, A, Bm, Cm, D, start, cfg.mamba_chunk)
                if use_cache and layer is not None:
                    h = h0.at[layer].set(h)
        if use_cache:
            tail = tail.reshape(B, (K - 1) * C).astype(state[CONV_LEAF].dtype)
            if layer is not None:
                tail = state[CONV_LEAF].at[layer].set(tail)
            state = dict(state, **{SSM_LEAF: h, CONV_LEAF: tail})
            if own is not None:
                for name, var in own.items():
                    var.value = state[name]

        with jax.named_scope("ssm_gate_norm"):
            g = y.reshape(B, T, inner) * jax.nn.silu(z.astype(f32))
            g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + cfg.norm_eps)
            o = (norm_w * g).astype(dtype)
        resid_std = 0.02 / (2 * cfg.n_layers) ** 0.5
        out = dense(cfg.d_model, ("mlp", "embed"), resid_std, "out_proj")(o)
        if pools is None:
            return out
        return out, (state if use_cache else pools)

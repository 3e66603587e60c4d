"""Kimi Delta Attention (KDA; the Kimi Linear technical report, arXiv:2510.26692,
and the flash-linear-attention ``KimiDeltaAttention`` layer it ships with): a
gated delta-rule linear-attention mixer that keeps a MATRIX state a head.

Per position ``t`` of a row, with ``n`` the normed input, ``H`` heads of ``D``
(the key and the value width alike)::

    q, k, v   = silu(conv(n W_q)), silu(conv(n W_k)), silu(conv(n W_v))   causal depthwise, no bias
    q, k      = q / |q| * D^-1/2,  k / |k|                                 per head
    g_t       = -exp(A_log_h) * softplus(n W_f_down W_f_up + dt_bias)       per channel, <= 0
    beta_t    = sigmoid(n W_b)                                             per head
    S_t       = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T     [D, D] a head
    o_t       = S_t^T q_t
    out       = concat_h(RMSNorm_D(o_t) * sigmoid(n W_g_down W_g_up)) W_o

In update order: decay ``S' = Diag(exp(g)) S``, then ``S = S' + beta k (v -
S'^T k)^T``. The decay, beta, the state and the output norm are float32.

**What the layer keeps a row, whatever the row's length**: the state ``S``
(float32 ``[heads, D, D]``) and the conv's last ``K - 1`` inputs of q, k and
v (the compute dtype), declared by ``kda_state_leaves``. They are the cache's
recurrent-state class under the names a mamba layer's state has
(``models.mamba.STATE_LEAVES``: a stack has one kind of recurrent mixer), so
the serving engine stages, resets and refuses them as it does a mamba
layer's: ``ssm_state [n_kda_layers, rows, heads, D, D]`` and ``conv_state
[n_kda_layers, rows, (K - 1) * 3 * heads * D]`` on the stack's carry.

Two orders of the same sums. A window of ``T > 1`` positions (a full
forward, a prefill chunk) is the CHUNKED form (``kda_chunk``, the WY / UT
form of the report): with ``G_t = sum_{s<=t} g_s`` inside a chunk and
``u_t = beta_t (v_t - S'_t^T k_t)`` the correction a position adds,
``S_t = Diag(e^{G_t}) S_0 + sum_{s<=t} Diag(e^{G_t - G_s}) k_s u_s^T``, so::

    (I + A) U = Diag(beta) (V - (e^G * K) S_0),   A_ts = beta_t sum_c k_tc k_sc e^{G_tc - G_sc}   (s < t)
    o_t  = S_0^T (e^{G_t} * q_t) + sum_{s<=t} u_s sum_c q_tc k_sc e^{G_tc - G_sc}
    S_C  = Diag(e^{G_C}) S_0 + sum_s Diag(e^{G_C - G_s}) k_s u_s^T

matmuls over the chunk and ONE triangular solve, float32 at ``highest``; every
exponent is ``G_t - G_s`` with ``s <= t``, never positive (masked BEFORE the
exp). ``T == 1`` through the cache is one step of the recurrence
(``ops.pallas.kda_update``: the state in place, the decoding rows alone).
``valid`` ``[rows]`` is how many of the window's positions are REAL: the
positions past it leave ``S`` and the conv inputs as they were (their
``beta`` and ``g`` are 0: nothing added, nothing decayed).
"""
from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.nn import initializers

from zero_transformer_tpu.config import ModelConfig, resolve_dtype
from zero_transformer_tpu.models.mamba import CONV_LEAF, SSM_LEAF, causal_conv

HIGHEST = jax.lax.Precision.HIGHEST
# positions a step of the chunked form: the serving engine's prefill chunk,
# so a chunk program runs ONE step (a [C, C, heads, D] decay tensor a row)
CHUNK = 64


def kda_state_leaves(cfg: ModelConfig, rows: int, dtype) -> dict:
    """``{leaf name: (shape, dtype)}`` of ONE kda layer's state for ``rows``
    batch rows."""
    H, D = cfg.kda_heads, cfg.kda_head_dim
    return {
        SSM_LEAF: ((rows, H, D, D), jnp.float32),
        CONV_LEAF: ((rows, (cfg.kda_conv - 1) * 3 * cfg.kda_inner), dtype),
    }


def l2_normalize(x, eps: float = 1e-6):
    """``x / sqrt(sum x^2 + eps)`` over the last axis, float32."""
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def kda_chunk(q, k, v, g, beta, s0):
    """The chunked form over one window. ``q``, ``k``, ``g`` ``[B, T, H,
    D]``, ``v`` ``[B, T, H, D]``, ``beta`` ``[B, T, H]`` (0 at a position
    that must not move the state, with ``g`` 0 there), ``s0`` ``[B, H, D,
    D]``, all float32. Returns ``(o [B, T, H, D], s_T)``."""
    T = q.shape[1]
    G = jnp.cumsum(g, axis=1)  # [B, T, H, D], <= 0 and falling
    # e^{G_t - G_s} for s <= t; masked BEFORE the exp (s > t would overflow)
    diff = G[:, :, None] - G[:, None, :]  # [B, T, S, H, D]
    causal = jnp.tril(jnp.ones((T, T), jnp.bool_))[None, :, :, None, None]
    within = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    ks = k[:, None] * within  # k_s e^{G_t - G_s}: [B, T, S, H, D]
    kk = jnp.einsum("bthc,btshc->bhts", k, ks, precision=HIGHEST)
    qk = jnp.einsum("bthc,btshc->bhts", q, ks, precision=HIGHEST)
    strict = jnp.tril(jnp.ones((T, T), jnp.bool_), -1)
    bt = jnp.moveaxis(beta, 1, 2)  # [B, H, T]
    A = jnp.where(strict, bt[..., None] * kk, 0.0)
    eG = jnp.exp(G)
    rhs = bt[..., None] * jnp.moveaxis(
        v - jnp.einsum("bthc,bhcd->bthd", eG * k, s0, precision=HIGHEST), 1, 2
    )  # [B, H, T, D]
    eye = jnp.eye(T, dtype=A.dtype)
    u = jax.lax.linalg.triangular_solve(
        eye + A, rhs, left_side=True, lower=True, unit_diagonal=True
    )  # [B, H, T, D]
    o = jnp.einsum("bthc,bhcd->bthd", eG * q, s0, precision=HIGHEST)
    o = o + jnp.einsum("bhts,bhsd->bthd", qk, u, precision=HIGHEST)
    to_end = jnp.exp(G[:, -1:] - G) * k  # [B, T, H, D]
    s = eG[:, -1][..., None] * s0 + jnp.einsum(
        "bshc,bhsd->bhcd", to_end, u, precision=HIGHEST
    )
    return o, s


def kda_scan(q, k, v, g, beta, s0, chunk: int):
    """``kda_chunk`` over a window of any length, ``chunk`` positions a
    step (the tail padded with ``beta`` and ``g`` 0, which moves nothing)."""
    B, T = q.shape[:2]
    if T <= chunk:
        return kda_chunk(q, k, v, g, beta, s0)
    n = -(-T // chunk)
    pad = n * chunk - T

    def split(a):
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape((B, n, chunk) + a.shape[2:]), 1, 0)

    def step(s, xs):
        o, s = kda_chunk(*xs, s)
        return s, o

    s, os_ = jax.lax.scan(step, s0, tuple(split(a) for a in (q, k, v, g, beta)))
    o = jnp.moveaxis(os_, 0, 1).reshape((B, n * chunk) + q.shape[2:])
    return o[:, :T], s


def kda_step(state, q, k, v, g, beta, live, layer, rows=None):
    """One decode step for every row, the state (a stack when ``layer`` is
    given) updated in place: the Pallas kernel where its gate accepts the
    shape, the same step in ``jax.numpy`` elsewhere. ``rows``: ``live``
    compacted (``models.mamba.step_rows``), where the caller has it."""
    from zero_transformer_tpu.ops.pallas import kda_update as ku
    from zero_transformer_tpu.ops.pallas.ssm_update import live_order

    _, H, D, _ = state.shape[-4:]
    if ku.supported(heads=H, head_dim=D, dtype=state.dtype):
        from zero_transformer_tpu.parallel.sharding import shard_kernel

        def local(state, q, k, v, g, beta, live, lyr, order, n_live):
            return ku.kda_update(
                state, q, k, v, g, beta, live,
                None if layer is None else lyr[0], rows=(order, n_live),
            )

        lyr = jnp.asarray(0 if layer is None else layer, jnp.int32).reshape(1)
        order, n_live = live_order(live) if rows is None else rows
        args = (state, q, k, v, g, beta, live.astype(jnp.int32), lyr, order, n_live)
        # on a mesh every device takes every operand whole
        whole = [(None,) * a.ndim for a in args]
        return shard_kernel(local, whole, (whole[1], whole[0]))(*args)
    return ku.kda_update_reference(state, q, k, v, g, beta, live, layer)


def _dense(features, axes, std, dtype, param_dtype, name):
    return nn.Dense(
        features, use_bias=False, dtype=dtype, param_dtype=param_dtype,
        kernel_init=nn.with_partitioning(initializers.normal(stddev=std), axes),
        name=name,
    )


class KDAMixer(nn.Module):
    """The mixer of a "kda" block. Same call contract as
    ``models.mamba.Mamba2Mixer``: with ``pools`` (the paged stack's carry)
    and this layer's place among the kda layers it reads and writes the
    stacked state at ``layer`` and returns ``(out, pools)``; without, a
    decode model keeps its own per-layer leaves."""

    cfg: ModelConfig
    decode: bool = False

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        valid: Optional[jax.Array] = None,
        pools: Optional[dict] = None,
        layer=None,
        rows: Optional[tuple] = None,
    ):
        cfg = self.cfg
        dtype = x.dtype
        param_dtype = resolve_dtype(cfg.param_dtype)
        B, T, _ = x.shape
        H, D, K = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv
        inner = cfg.kda_inner
        f32 = jnp.float32

        def dense(features, axes, name, std=0.02):
            return _dense(features, axes, std, dtype, param_dtype, name)

        def vector(name, init, n):
            return self.param(
                name, nn.with_partitioning(init, (None,)), (n,), param_dtype
            ).astype(f32)

        taps = [
            self.param(
                f"{p}_conv",
                nn.with_partitioning(initializers.normal(stddev=K ** -0.5), (None, "mlp")),
                (K, inner), param_dtype,
            )
            for p in "qkv"
        ]
        A_log = vector("A_log", initializers.zeros, H)
        dt_bias = vector("dt_bias", initializers.zeros, inner)
        norm_w = vector("o_norm_scale", initializers.ones, D)

        use_cache = False
        own = None
        if self.decode:
            if pools is None:
                use_cache = not self.is_initializing() and self.has_variable(
                    "cache", SSM_LEAF
                )
                own = {
                    name: self.variable("cache", name, jnp.zeros, shape, dt)
                    for name, (shape, dt) in kda_state_leaves(cfg, B, dtype).items()
                }
                state = {name: var.value for name, var in own.items()}
            else:
                # (init traces hand the stack back untouched)
                use_cache = not self.is_initializing()
                state = pools

        with jax.named_scope("kda_project"):
            qkv = jnp.concatenate([
                dense(inner, ("embed", "mlp"), f"{p}_proj")(x) for p in "qkv"
            ], axis=-1)
            f = dense(inner, (None, "mlp"), "f_b")(dense(D, ("embed", None), "f_a")(x))
            gate = dense(inner, (None, "mlp"), "g_b")(dense(D, ("embed", None), "g_a")(x))
            beta = jax.nn.sigmoid(dense(H, ("embed", None), "b_proj")(x).astype(f32))

        with jax.named_scope("kda_conv"):
            if use_cache:
                tail = state[CONV_LEAF] if layer is None else state[CONV_LEAF][layer]
                tail = tail.reshape(B, K - 1, 3 * inner)
            else:
                tail = jnp.zeros((B, K - 1, 3 * inner), dtype)
            qkv, tail = causal_conv(
                qkv, tail, jnp.concatenate(taps, axis=1), jnp.zeros((), f32), valid
            )
            qkv = jax.nn.silu(qkv)
        q, k, v = (a.reshape(B, T, H, D) for a in jnp.split(qkv, 3, axis=-1))
        q = l2_normalize(q) * jnp.float32(D ** -0.5)
        k = l2_normalize(k)
        g = -jnp.exp(A_log)[:, None] * jax.nn.softplus(
            f.astype(f32).reshape(B, T, H, D) + dt_bias.reshape(H, D)
        )
        if valid is not None:
            real = jnp.arange(T, dtype=jnp.int32)[None, :] < valid[:, None]
            beta = jnp.where(real[..., None], beta, 0.0)
            g = jnp.where(real[..., None, None], g, 0.0)

        if use_cache:
            s0 = state[SSM_LEAF]
        else:
            s0 = jnp.zeros((B, H, D, D), f32)
        if use_cache and T == 1:
            live = jnp.ones((B,), jnp.bool_) if valid is None else valid > 0
            o, s = kda_step(
                s0, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], live, layer, rows
            )
            o = o[:, None]
        else:
            with jax.named_scope("kda_chunk"):
                start = s0 if (layer is None or not use_cache) else s0[layer]
                o, s = kda_scan(q, k, v, g, beta, start, CHUNK)
                if use_cache and layer is not None:
                    s = s0.at[layer].set(s)
        if use_cache:
            tail = tail.reshape(B, (K - 1) * 3 * inner).astype(state[CONV_LEAF].dtype)
            if layer is not None:
                tail = state[CONV_LEAF].at[layer].set(tail)
            state = dict(state, **{SSM_LEAF: s, CONV_LEAF: tail})
            if own is not None:
                for name, var in own.items():
                    var.value = state[name]

        with jax.named_scope("kda_gate_norm"):
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + cfg.norm_eps)
            o = norm_w * o * jax.nn.sigmoid(gate.astype(f32)).reshape(B, T, H, D)
            o = o.reshape(B, T, inner).astype(dtype)
        resid_std = 0.02 / (2 * cfg.n_layers) ** 0.5
        out = dense(cfg.d_model, ("mlp", "embed"), "o_proj", resid_std)(o)
        if pools is None:
            return out
        return out, (state if use_cache else pools)

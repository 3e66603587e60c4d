"""Mixture-of-Experts MLP with top-k routing and expert parallelism.

Beyond the reference (which is dense-only; SURVEY §2 checklist: EP/MoE =
none). TPU-first design choices:

- **einsum dispatch** (GShard/Switch formulation): routing builds one-hot
  dispatch/combine tensors ``[B, T, E, C]`` and moves tokens with two
  einsums. Static shapes, no gather/scatter, MXU-friendly — XLA lowers the
  expert-dim resharding to an all-to-all when the ``expert`` mesh axis is
  active (capacity C bounds the per-expert buffer, so the communication
  volume is fixed at trace time).
- **capacity-based top-k** (k ∈ {1, 2}): per-expert queue positions come
  from a cumulative sum over the token axis; overflowing tokens are dropped
  (their residual path passes through unchanged) — the standard
  fixed-capacity contract that keeps every shape static under jit.
- **router in float32** with a load-balance auxiliary loss (Switch: E ·
  Σ_e fraction_e · prob_e over first-choice assignments) and a router
  z-loss; both are returned to the caller and added to the training loss
  only (never to eval perplexity).
- expert weights are stacked ``[E, d, f]`` with the ``expert`` logical axis
  → sharded over the mesh's ``expert`` axis (EP) and composable with
  Megatron TP on the ``mlp`` axis within each expert.

``DroplessMoE`` (``cfg.moe_dispatch == "dropless"``) is the SERVED routed
layer, the DeepSeek-V3 one: sigmoid scores, the top k of score + a selection
bias, weights from the scores alone, a shared expert beside the routed ones,
rows sorted by expert into a grouped matmul (``jax.lax.ragged_dot``). No
token is dropped and a row's result depends on that row alone: what the
serving engine needs (a dropped token makes chunked prefill + decode
disagree with a full forward; dead slots and a chunk's padding rows share
every batch). ``MoEMLP``'s capacity path stays for the training recipes.
"""
from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.nn import initializers

from zero_transformer_tpu.config import ModelConfig, resolve_dtype


def _routing(
    logits: jax.Array, top_k: int, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k capacity-limited assignment.

    Args:
      logits: [B, T, E] float32 router scores.
      top_k: 1 (Switch: output scaled by raw router prob) or 2 (GShard:
        weights renormalized over the chosen pair).
      capacity: per-expert queue length C.

    Returns (dispatch [B,T,E,C] 0/1, combine [B,T,E,C], aux) where aux is
    the Switch load-balance loss (coefficient-free; caller scales).
    """
    B, T, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)

    p = probs
    masks, gates = [], []
    for _ in range(top_k):
        idx = jnp.argmax(p, axis=-1)
        m = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # [B, T, E]
        gates.append(jnp.sum(p * m, axis=-1))  # [B, T]
        masks.append(m)
        p = p * (1.0 - m)

    if top_k == 1:
        weights = gates  # Switch: scale by the raw router probability
    else:
        denom = sum(gates) + 1e-9
        weights = [g / denom for g in gates]

    dispatch = jnp.zeros((B, T, E, capacity), jnp.float32)
    combine = jnp.zeros((B, T, E, capacity), jnp.float32)
    queued = jnp.zeros((B, 1, E), jnp.float32)  # tokens enqueued per expert
    for m, w in zip(masks, weights):
        pos = jnp.cumsum(m, axis=1) - m + queued  # queue slot per token
        keep = m * (pos < capacity)
        queued = queued + jnp.cumsum(m, axis=1)[:, -1:, :]
        slot = jax.nn.one_hot(pos.astype(jnp.int32), capacity, dtype=jnp.float32)
        slot = slot * keep[..., None]  # [B, T, E, C]
        dispatch = dispatch + slot
        combine = combine + slot * w[:, :, None, None]

    # load balance over FIRST choices (Switch §2.2): E * Σ_e f_e * P_e
    f = jnp.mean(masks[0], axis=(0, 1))  # fraction routed to e
    pmean = jnp.mean(probs, axis=(0, 1))  # mean router prob for e
    aux = E * jnp.sum(f * pmean)
    return dispatch, combine, aux


class MoEMLP(nn.Module):
    """Drop-in MLP replacement: returns (output, aux_loss)."""

    cfg: ModelConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        dtype = x.dtype
        param_dtype = resolve_dtype(cfg.param_dtype)
        B, T, d = x.shape
        E, k, f = cfg.n_experts, cfg.moe_top_k, cfg.ff_dim
        C = max(1, int(cfg.capacity_factor * k * T / E))
        resid_std = 0.02 / (2 * cfg.n_layers) ** 0.5

        router = self.param(
            "router",
            nn.with_partitioning(initializers.normal(stddev=0.02), ("embed", None)),
            (d, E),
            param_dtype,
        )
        # router math in f32: routing decisions are precision-sensitive (the
        # same discipline as the f32 softmax, reference ``layers.py:167-173``)
        logits = jnp.einsum(
            "btd,de->bte", x, router, preferred_element_type=jnp.float32
        )
        dispatch, combine, balance = _routing(logits, k, C)
        zloss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
        aux = (
            jnp.float32(cfg.router_aux_coef) * balance
            + jnp.float32(cfg.router_z_coef) * zloss
        )

        # stacked expert weights; `expert` logical axis → EP mesh axis.
        # param_quant="int8" (inference only): int8 expert tensors +
        # per-(expert, out-channel) f32 scales, applied AFTER each einsum —
        # exact for this quantization granularity, same contract as
        # models/quant.py::QuantDense
        quant = cfg.param_quant == "int8"

        def expert_weight(name, shape, axes, std):
            if quant:
                from zero_transformer_tpu.models.quant import (
                    _int8_normal,
                    _q_scale,
                )

                q = self.param(
                    f"{name}_q",
                    nn.with_partitioning(_int8_normal(std), axes),
                    shape,
                    jnp.int8,
                )
                scale = self.param(
                    f"{name}_scale",
                    nn.with_partitioning(_q_scale(std), (axes[0], axes[-1])),
                    (shape[0], shape[-1]),
                    jnp.float32,
                )
                return q, scale
            w = self.param(
                name,
                nn.with_partitioning(initializers.normal(stddev=std), axes),
                shape,
                param_dtype,
            )
            return w, None

        def expert_einsum(lhs, w, scale, spec="ebcd,edf->ebcf"):
            y = jnp.einsum(spec, lhs, w.astype(dtype))
            if scale is not None:
                y = y * scale[:, None, None, :].astype(dtype)
            return y

        wi, wi_scale = expert_weight(
            "wi", (E, d, f), ("expert", "embed", "mlp"), 0.02
        )
        wo, wo_scale = expert_weight(
            "wo", (E, f, d), ("expert", "mlp", "embed"), resid_std
        )

        # dispatch: [B,T,d] tokens -> [E,B,C,d] expert buffers (all-to-all
        # over the expert axis when sharded)
        xin = jnp.einsum("btec,btd->ebcd", dispatch.astype(dtype), x)
        # named for remat_policy="qkv_mlp" (models/gpt.py
        # resolve_remat_policy): saving the expert pre-activations skips the
        # dispatch + wi einsum recompute — the dominant MoE re-forward cost —
        # exactly as saving mlp_wi does in the dense MLP
        h = checkpoint_name(expert_einsum(xin, wi, wi_scale), "mlp_wi")
        if cfg.activation == "swiglu":
            wg, wg_scale = expert_weight(
                "gate", (E, d, f), ("expert", "embed", "mlp"), 0.02
            )
            g = checkpoint_name(expert_einsum(xin, wg, wg_scale), "mlp_gate")
            h = nn.silu(g) * h
        else:
            h = nn.gelu(h)
        out_e = expert_einsum(h, wo, wo_scale, "ebcf,efd->ebcd")
        out = jnp.einsum("btec,ebcd->btd", combine.astype(dtype), out_e)
        out = nn.Dropout(cfg.dropout, deterministic=self.deterministic)(out)
        return out, aux


def route_sigmoid(
    x: jax.Array, router: jax.Array, bias: jax.Array, top_k: int, scale: float,
) -> Tuple[jax.Array, jax.Array]:
    """``[N, d]`` rows -> (chosen experts ``[N, k]`` int32, weights ``[N, k]``
    float32). Scores ``s = sigmoid(x W_r)`` in float32; the top ``k`` of
    ``s + bias`` are chosen; the weights are ``s[chosen]`` (the bias moves
    the CHOICE, never the weight), normalised over the chosen and times
    ``scale``. Every row on its own."""
    s = jax.nn.sigmoid(jnp.einsum(
        "nd,de->ne", x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ))
    _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), w * jnp.float32(scale)


class _SwiGLU(nn.Module):
    """``wo(silu(gate x) * wi x)`` at width ``f``: the shared expert."""

    cfg: ModelConfig
    f: int

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        param_dtype = resolve_dtype(cfg.param_dtype)

        def dense(features, axes, std, name):
            return nn.Dense(
                features, use_bias=False, dtype=x.dtype, param_dtype=param_dtype,
                kernel_init=nn.with_partitioning(initializers.normal(stddev=std), axes),
                name=name,
            )

        h = nn.silu(dense(self.f, ("embed", "mlp"), 0.02, "gate")(x)) * dense(
            self.f, ("embed", "mlp"), 0.02, "wi")(x)
        return dense(
            cfg.d_model, ("mlp", "embed"), 0.02 / (2 * cfg.n_layers) ** 0.5, "wo"
        )(h)


class DroplessMoE(nn.Module):
    """The routed layer of ``cfg.moe_dispatch == "dropless"``: returns
    ``(output, expert_counts)``.

    ``experts = (lo, hi)`` says which experts THIS module holds (None: the
    configuration's share, the first ``cfg.experts_held``). It routes every row over all of them, computes the
    part of its own — expert weights ``[hi - lo, ...]`` — and, with
    ``shared``, the shared expert's: the parts of modules that split the
    experts between them (one of them holding the shared expert) add up to
    the whole layer, which is the shape an expert-parallel cut has. No code
    here stands in for absent chips.

    ``expert_counts`` ``[B, n_experts]`` int32: how many of a batch row's
    positions were sent to each expert (the caller knows which batch rows
    are live)."""

    cfg: ModelConfig
    experts: Optional[Tuple[int, int]] = None
    shared: bool = True

    @nn.compact
    def __call__(self, x: jax.Array):
        cfg = self.cfg
        dtype = x.dtype
        param_dtype = resolve_dtype(cfg.param_dtype)
        B, T, d = x.shape
        E, k, f = cfg.n_experts, cfg.moe_top_k, cfg.moe_ff_dim
        lo, hi = self.experts or (0, cfg.experts_held)
        held = hi - lo
        N = B * T
        rows = x.reshape(N, d)

        router = self.param(
            "router",
            nn.with_partitioning(initializers.normal(stddev=0.02), ("embed", None)),
            (d, E), param_dtype,
        )
        bias = self.param(
            "router_bias", nn.with_partitioning(initializers.zeros, (None,)),
            (E,), param_dtype,
        )
        with jax.named_scope("moe_route"):
            chosen, weight = route_sigmoid(rows, router, bias, k, cfg.moe_routed_scale)
            counts = jnp.sum(
                jax.nn.one_hot(chosen, E, dtype=jnp.int32).reshape(B, T * k, E), axis=1
            )
            # every (row, choice) pair, sorted by the expert that takes it;
            # a pair whose expert another module holds sorts past the last
            # group, where the grouped matmul computes nothing
            pair_expert = chosen.reshape(N * k) - lo
            mine = (pair_expert >= 0) & (pair_expert < held)
            group = jnp.where(mine, pair_expert, held)
            order = jnp.argsort(group, stable=True)
            sizes = jnp.sum(
                jax.nn.one_hot(group, held, dtype=jnp.int32), axis=0
            )
            back = jnp.argsort(order)

        def experts_weight(name, shape, axes, std):
            return self.param(
                name, nn.with_partitioning(initializers.normal(stddev=std), axes),
                shape, param_dtype,
            ).astype(dtype)

        wi = experts_weight("wi", (held, d, f), ("expert", "embed", "mlp"), 0.02)
        wg = experts_weight("gate", (held, d, f), ("expert", "embed", "mlp"), 0.02)
        wo = experts_weight(
            "wo", (held, f, d), ("expert", "mlp", "embed"),
            0.02 / (2 * cfg.n_layers) ** 0.5,
        )
        with jax.named_scope("moe_experts"):
            xs = rows[order // k]  # [N * k, d], grouped by expert
            h = nn.silu(jax.lax.ragged_dot(xs, wg, sizes)) * jax.lax.ragged_dot(xs, wi, sizes)
            ys = jax.lax.ragged_dot(h, wo, sizes)
            # back to (row, choice) order; another module's pairs add nothing
            # (whatever the grouped matmul left in the rows past its groups)
            y = ys[back].reshape(N, k, d).astype(jnp.float32) * weight[..., None]
            out = jnp.sum(
                jnp.where(mine.reshape(N, k, 1), y, 0.0), axis=1
            ).astype(dtype)
        if self.shared and cfg.moe_shared_experts:
            with jax.named_scope("moe_shared"):
                out = out + _SwiGLU(
                    cfg, cfg.moe_shared_experts * f, name="shared"
                )(rows)
        return out.reshape(B, T, d), counts

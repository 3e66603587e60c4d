"""Plain reference for the looped language model family (ByteDance Ouro,
"LoopLM"): ``n_layers`` decoder blocks run ``n_loops`` times over the SAME
weights.

Straightforward ``jax.numpy`` in float32 with matmuls at ``highest``
precision: no cache, no kernels, no batching tricks; a Python loop over the
passes around a scan over the layers. It imports nothing of the program.

The equations (``model`` is the configuration's ``model`` group)::

    block l, input h:   a  = h + N2_l(Attn_l(N1_l(h)))
                        h' = a + N4_l(W_down_l(silu(W_gate_l x) * (W_up_l x))),  x = N3_l(a)
    model:              h = E[tokens]
                        for t in 1..n_loops:
                            for l in 1..n_layers: h = block_l(h)     # same weights at every t
                            h = N_f(h);  s_t = h;  g_t = w_g . h + b_g
                        lam_t = sigmoid(g_t);  p_t = lam_t * prod_{j<t}(1 - lam_j)  (the last pass: what is left)
                        exit = first t whose cumulative p reaches exit_threshold, else the last
                        logits = s_exit @ W_head

``N*`` are RMSNorms (eps 1e-6) with their own scales: one before AND one
after each sublayer. ``Attn`` is causal softmax(q k^T / sqrt(D)) v with
rotary positions on q and k (half-split rotation, base ``rope_theta``), no
biases. A token's position is the same at every pass. Every pass always
runs: the exit only selects which ``s_t`` the head reads.

``mode`` is the precision the matmul operands are rounded to on the way in:
``"f32"`` is the reference; ``"bf16"`` and ``"fp8"`` are the controls the
limits are read against. Apart from that, every leaf is rounded to the
configuration's ``param_dtype`` and back where it is used, inside the layer
loop: the driver hands this module float32 leaves from the same key the
served (``param_dtype``) leaves were made from, so program and reference
hold the same numbers and the comparison judges the arithmetic. No second
tree is ever made.

What the harness asks of a family's reference module: ``leaf_table``,
``active_params``, ``attention_flops_per_position``, ``decays``, ``logits``
(and ``loss`` for the CPU tests); ``decode_read_bytes`` is what the
``decode_bandwidth_share`` reader divides by.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

RMS_EPS = 1e-6
_ROUND = {"f32": None, "bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}
_ITEMSIZE = {"float32": 4, "bfloat16": 2}  # the param_dtypes ``_held`` knows


def _sizes(model: dict) -> tuple:
    kvh = model.get("n_kv_heads") or model["n_heads"]
    return (model["d_model"], model["n_layers"], model["vocab_size"],
            model["n_heads"] * model["head_dim"], kvh * model["head_dim"], model["d_ff"])


def leaf_table(model: dict) -> dict:
    """path -> (shape, init) of the program's own parameter tree
    (``models/gpt.py`` with ``scan_layers``: layer leaves stacked on a
    leading ``n_layers`` axis, shared by every pass). init is a normal's
    standard deviation (0.02; residual projections 0.02/sqrt(2L); 0.0 for the
    gate's bias), or "ones" for a norm scale."""
    d, L, V, qd, kvd, f = _sizes(model)
    s = 0.02
    r = s / (2 * L) ** 0.5
    return {
        "wte/embedding": ((V, d), s),
        "blocks/ln_attn/scale": ((L, d), "ones"),
        "blocks/attn/query/kernel": ((L, d, qd), s),
        "blocks/attn/key/kernel": ((L, d, kvd), s),
        "blocks/attn/value/kernel": ((L, d, kvd), s),
        "blocks/attn/out/kernel": ((L, qd, d), r),
        "blocks/ln_attn_post/scale": ((L, d), "ones"),
        "blocks/ln_mlp/scale": ((L, d), "ones"),
        "blocks/mlp/wi/kernel": ((L, d, f), s),
        "blocks/mlp/gate/kernel": ((L, d, f), s),
        "blocks/mlp/wo/kernel": ((L, f, d), r),
        "blocks/ln_mlp_post/scale": ((L, d), "ones"),
        "ln_f/scale": ((d,), "ones"),
        "exit_gate/kernel": ((d, 1), s),
        "exit_gate/bias": ((1,), 0.0),
        "lm_head/kernel": ((d, V), s),
    }


def layer_matrix_params(model: dict) -> int:
    """Parameters of ONE layer's seven matrices."""
    d, _, _, qd, kvd, f = _sizes(model)
    return 2 * d * qd + 2 * d * kvd + 3 * d * f


def active_params(model: dict) -> int:
    """Parameters a token is multiplied by: the layer matrices once a PASS
    and the head. The embedding is a lookup and is not counted; nor are the
    norm scales and the gate (d + 1)."""
    return (model["n_loops"] * model["n_layers"] * layer_matrix_params(model)
            + model["d_model"] * model["vocab_size"])


def attention_flops_per_position(model: dict) -> float:
    """Forward operations of one token attending over ONE cached position:
    q.k and p.v in every layer of every pass."""
    return 4.0 * model["n_loops"] * model["n_layers"] * model["n_heads"] * model["head_dim"]


def kv_bytes_per_position(model: dict, itemsize: int = 2) -> int:
    """K and V of one cached position in all ``n_loops * n_layers`` entries."""
    kvh = model.get("n_kv_heads") or model["n_heads"]
    return 2 * model["n_loops"] * model["n_layers"] * kvh * model["head_dim"] * itemsize


def decode_read_bytes(model: dict, live_positions: int, kv_itemsize: int = 2) -> float:
    """The bytes ONE decode tick must read, whatever implements it: the
    layer matrices once a PASS (pass t+1 needs pass t's last layer and the
    layers do not stay on the chip, so no implementation reads them less
    often) and the head once, in the served ``param_dtype``, plus K and V of
    the ``live_positions`` cached positions (summed over the tick's rows) in
    every entry. Norm scales, the gate, the embedding rows and the
    activations are left out: what is counted is a lower bound."""
    w = _ITEMSIZE[model.get("param_dtype", "float32")]
    return float(active_params(model) * w
                 + live_positions * kv_bytes_per_position(model, kv_itemsize))


def decays(path: str) -> bool:
    """The usual weight-decay mask: matrices and the embedding."""
    return path.rsplit("/", 1)[-1] in ("kernel", "embedding")


# ----------------------------------------------------------------- forward


def _q(x, mode):
    dt = _ROUND[mode]
    if dt is None:
        return x
    return x + jax.lax.stop_gradient(x.astype(dt).astype(jnp.float32) - x)


def _mm(eq, a, b, mode):
    return jnp.einsum(eq, _q(a, mode), _q(b, mode),
                      precision=jax.lax.Precision.HIGHEST)


def _held(x, param_dtype: str):
    """A float32 leaf as the program holds it: rounded to ``param_dtype``
    (float32 or bfloat16) and back. The rounding to bfloat16 (nearest, ties
    to even) is done on the bits by hand: a ``convert`` of a slice of the
    stacked layers is moved out of the layer loop by the chip's compiler,
    which then holds a second, bfloat16 copy of every layer matrix (4.9 GB
    beside 10.7 GB of float32 leaves: more than the chip has)."""
    if param_dtype == "float32":
        return x
    if param_dtype != "bfloat16":
        raise ValueError(f"param_dtype {param_dtype!r}: float32 or bfloat16")
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + RMS_EPS) * scale


def rope(x, theta: float):
    """[B, T, H, D] rotated by position 0..T-1, half-split: with x = (x1, x2)
    the halves of the head, (x1 cos - x2 sin, x2 cos + x1 sin), the angle of
    pair i at position p being p / theta^(2i / D)."""
    T, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[None, :, None, :], jnp.cos(ang)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layer(pl: dict, h, n_heads: int, n_kv_heads: int, theta: float, mode: str = "f32"):
    """One block. ``pl`` holds one layer's leaves; ``h`` is [B, T, d]."""
    B, T, _ = h.shape
    a = pl["attn"]
    x = rmsnorm(h, pl["ln_attn"]["scale"])
    q = _mm("btd,de->bte", x, a["query"]["kernel"], mode).reshape(B, T, n_heads, -1)
    k = _mm("btd,de->bte", x, a["key"]["kernel"], mode).reshape(B, T, n_kv_heads, -1)
    v = _mm("btd,de->bte", x, a["value"]["kernel"], mode).reshape(B, T, n_kv_heads, -1)
    q, k = rope(q, theta), rope(k, theta)
    if n_kv_heads != n_heads:
        k = jnp.repeat(k, n_heads // n_kv_heads, axis=2)
        v = jnp.repeat(v, n_heads // n_kv_heads, axis=2)
    s = _mm("bthd,bshd->bhts", q, k, mode) / math.sqrt(q.shape[-1])
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), axis=-1)
    o = _mm("bhts,bshd->bthd", p, v, mode).reshape(B, T, -1)
    h = h + rmsnorm(_mm("bte,ed->btd", o, a["out"]["kernel"], mode), pl["ln_attn_post"]["scale"])
    x = rmsnorm(h, pl["ln_mlp"]["scale"])
    m = jax.nn.silu(_mm("btd,df->btf", x, pl["mlp"]["gate"]["kernel"], mode)) \
        * _mm("btd,df->btf", x, pl["mlp"]["wi"]["kernel"], mode)
    return h + rmsnorm(_mm("btf,fd->btd", m, pl["mlp"]["wo"]["kernel"], mode),
                       pl["ln_mlp_post"]["scale"])


def exit_state(states: list, gates: list, threshold: float):
    """The state each position decodes from: ``states[t]`` [B, T, d],
    ``gates[t]`` [B, T]."""
    n = len(states)
    lam = [jax.nn.sigmoid(g) for g in gates]
    remaining = jnp.ones_like(gates[0])
    cum = jnp.zeros_like(gates[0])
    exit_at = jnp.full(gates[0].shape, n - 1, jnp.int32)
    for t in range(n - 1):
        cum = cum + lam[t] * remaining
        remaining = remaining * (1.0 - lam[t])
        # the FIRST pass that reaches the threshold wins
        exit_at = jnp.where((exit_at == n - 1) & (cum >= threshold), t, exit_at)
    out = jnp.zeros_like(states[0])
    for t in range(n):
        out = out + jnp.where((exit_at == t)[..., None], states[t], 0.0)
    return out


# XLA may keep MORE precision than a program asks for (its default) and then
# drops ``_q``'s roundings: on the chip the "bf16" logits equalled float32's
# (my chip runs, PR 30; PR 26 read its bfloat16 control as 0.0 for that
# reason). Compiled to round where it says.
STRICT = {"xla_allow_excess_precision": False}


def _forward(params, tokens, n_heads, n_kv_heads, n_loops, theta, threshold,
             param_dtype, mode):
    def held(tree):
        return jax.tree.map(lambda x: _held(x, param_dtype), tree)

    h = _held(jnp.take(params["wte"]["embedding"], tokens, axis=0), param_dtype)
    lnf = held(params["ln_f"]["scale"])
    gate = held(params["exit_gate"])
    states, gates = [], []
    for _ in range(n_loops):
        h, _ = jax.lax.scan(
            lambda h, pl: (layer(held(pl), h, n_heads, n_kv_heads, theta, mode), None),
            h, params["blocks"],
        )
        h = rmsnorm(h, lnf)
        states.append(h)
        # the gate is a d-wide dot product: kept in float32 in every mode
        gates.append(jnp.einsum("btd,d->bt", h, gate["kernel"][:, 0],
                                precision=jax.lax.Precision.HIGHEST) + gate["bias"][0])
    s = exit_state(states, gates, threshold)
    return _mm("btd,dv->btv", s, held(params["lm_head"]["kernel"]), mode)


_SIZES = ("n_heads", "n_kv_heads", "n_loops", "theta", "threshold", "param_dtype", "mode")
_logits = jax.jit(_forward, static_argnames=_SIZES, compiler_options=STRICT)
# ``compiler_options`` are a top-level jit's alone: what a caller
# differentiates or jits itself (``loss``) goes through this one
_logits_nested = jax.jit(_forward, static_argnames=_SIZES)


def _statics(model: dict, mode: str) -> dict:
    return dict(
        n_heads=model["n_heads"], n_kv_heads=model.get("n_kv_heads") or model["n_heads"],
        n_loops=model["n_loops"], theta=float(model["rope_theta"]),
        threshold=float(model["exit_threshold"]),
        param_dtype=model.get("param_dtype", "float32"), mode=mode,
    )


def logits(params: dict, tokens, model: dict, mode: str = "f32"):
    """[B, T] tokens -> [B, T, V] float32 logits: the whole forward pass."""
    return _logits(params, tokens, **_statics(model, mode))


def loss(params: dict, tokens, model: dict, mode: str = "f32"):
    """Next-token cross entropy of the exit state's logits, averaged over
    the ``T - 1`` predicted positions of every row."""
    lg = _logits_nested(params, tokens, **_statics(model, mode))[:, :-1]
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    lab = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - lab)

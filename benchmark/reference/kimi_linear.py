"""Plain reference for the ``kimi_linear`` family (Kimi-Linear-48B-A3B): a
HYBRID stack whose mixers are Kimi Delta Attention (KDA, a gated delta-rule
linear attention with a matrix state a head) and latent attention (MLA) with
a direct query projection and NO rotary, every layer followed by a SwiGLU MLP
(the leading ``moe_dense_layers``) or a sigmoid-routed expert layer with a
shared expert.

Straightforward ``jax.numpy`` in float32 with matmuls at ``highest``
precision. It imports nothing of the program: no cache, no chunked form, no
absorption, no kernels, no sorting, no grouped matmul. The recurrence is a
``lax.scan`` over positions, a sequence whole through each layer; the conv
four shifted sums; attention the naive form (every position's keys and
values up-projected); every HELD expert applied to every row, the unchosen
with weight zero.

The equations (``model`` is the configuration's ``model`` group), per layer,
pre-norm, ``N`` an RMSNorm (eps ``norm_eps``) with its own scale::

    u = x + Mixer(N(x));   y = u + FFN(N(u));   logits = N_f(y_L) W_head

    KDA (H heads of D):
        q, k, v = silu(conv(n W_q)), silu(conv(n W_k)), silu(conv(n W_v))   depthwise, K taps, no bias,
                                                                           zeros before the sequence
        q = q / |q| / sqrt(D);  k = k / |k|                  per head (|.| with eps 1e-6 under the root)
        g = -exp(A_log_h) softplus(n W_fa W_fb + dt_bias);   beta = sigmoid(n W_b)
        S_t = Diag(exp(g_t)) S_{t-1};  S_t += beta_t k_t (v_t - S_t^T k_t)^T;  o_t = S_t^T q_t;  S_{-1} = 0
        out = concat_h(N_D(o_t) * sigmoid(n W_ga W_gb)) W_o          N_D: over a head's D, one shared scale
    MLA: q_h = [q_nope_h | q_pe_h] = x W_q,h                 (no low rank)
         [c_kv | k_pe] = x W_kva;  c_kv = N(c_kv);  [k_nope_h | v_h] = c_kv W_kvb,h
         score_h = (q_nope_h . k_nope_h + q_pe_h . k_pe) / sqrt(nope + rope), causal;  NO rotary
         out = concat_h(softmax(score_h) v_h) W_o
    dense FFN: W_down(silu(W_gate x) * W_up x), width d_ff
    routed FFN: s = sigmoid(x W_r) in float32 over ALL n_experts; chosen = top k of (s + b)
                w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scale            (b is NOT in w)
                y = sum_{e chosen, e < held} w_e E_e(x) + E_shared(x),  each E a SwiGLU of moe_d_ff

The experts this chip HOLDS are ids ``[0, moe_experts_held)`` of the
``n_experts`` routed over: the routing is the whole layer's, the sum only
over the held experts (one chip's part of an expert-parallel layer; the
parts of every share, with the shared expert once, add up to the layer).

Departures from the published description, each a line here and under
``assumed`` in the configuration file: the conv has no bias; q is scaled by
1/sqrt(D); the output gate's up projection has no bias; the ``1e-20`` in the
routing's normalisation and the router in float32; the init is what
``benchmark/weights.py`` can draw (``leaf_table`` says what).

``mode`` is the precision the matmul operands are rounded to on the way in:
``"f32"`` is the reference; ``"bf16"`` and ``"fp8"`` the controls. A fourth,
``"state_bf16"``, leaves the matmuls in float32 and rounds the recurrent
state to bfloat16 after every position (the configuration states float32).
Apart from that every leaf is rounded to the configuration's ``param_dtype``
and back where it is used, so reference and program hold the same numbers.

What the harness asks of a family: ``leaf_table``, ``active_params``,
``attention_flops_per_position``, ``logits`` (the whole tree: CPU tests at a
small size) and ``logits_by_blocks``. For the per-layer metrics of this
family: ``state_update_ops_bytes`` and ``decode_read_bytes``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

_ROUND = {"f32": None, "bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn,
          "state_bf16": None}
_KEPT = {"state_bf16": jnp.bfloat16}  # what the kept state is rounded to
_HELD = {"float32": None, "bfloat16": jnp.bfloat16}
STRICT = {"xla_allow_excess_precision": False}
HIGHEST = jax.lax.Precision.HIGHEST
QUERY_ROWS = 512  # queries a step of the naive attention scores at once


# ------------------------------------------------------------- what there is


def _widths(model: dict) -> dict:
    H, D = model["kda_heads"], model["kda_head_dim"]
    E = model["n_experts"]
    return dict(
        d=model["d_model"], f=model["d_ff"], V=model["vocab_size"],
        KH=H, KD=D, inner=H * D, K=model.get("kda_conv", 4),
        AH=model["n_heads"], r=model["kv_lora_rank"], nope=model["qk_nope_head_dim"],
        rope=model["qk_rope_head_dim"], v=model["v_head_dim"],
        E=E, held=model.get("moe_experts_held") or E, k=model["moe_top_k"],
        fe=model["moe_d_ff"], fs=model["moe_shared_experts"] * model["moe_d_ff"],
    )


def layers(model: dict) -> list:
    """``[(leaf prefix, (mixer, mlp))]``, a layer each: the mixer as
    ``layer_pattern`` repeats, the MLP "dense" for the leading
    ``moe_dense_layers`` and "moe" after. The program's stack is unrolled
    (a routed stack always is): block ``i``'s leaves lie under
    ``block_<i>``."""
    pattern = list(model["layer_pattern"])
    dense = model.get("moe_dense_layers", 0)
    return [(f"block_{i}", (pattern[i % len(pattern)], "dense" if i < dense else "moe"))
            for i in range(model["n_layers"])]


def _layer_leaves(model: dict, kind: tuple) -> dict:
    """name -> (shape of ONE layer, init)."""
    w = _widths(model)
    mixer, mlp = kind
    d, s = w["d"], 0.02
    res = s / (2 * model.get("init_depth", model["n_layers"])) ** 0.5
    out = {"ln_attn/scale": ((d,), "ones")}
    if mixer == "kda":
        inner, D = w["inner"], w["KD"]
        out.update({
            "kda/q_proj/kernel": ((d, inner), s),
            "kda/k_proj/kernel": ((d, inner), s),
            "kda/v_proj/kernel": ((d, inner), s),
            "kda/q_conv": ((w["K"], inner), 0.5),
            "kda/k_conv": ((w["K"], inner), 0.5),
            "kda/v_conv": ((w["K"], inner), 0.5),
            "kda/f_a/kernel": ((d, D), s),
            "kda/f_b/kernel": ((D, inner), s),
            "kda/b_proj/kernel": ((d, w["KH"]), s),
            "kda/A_log": ((w["KH"],), 1.0),
            "kda/dt_bias": ((inner,), 1.0),
            "kda/g_a/kernel": ((d, D), s),
            "kda/g_b/kernel": ((D, inner), s),
            "kda/o_norm_scale": ((D,), "ones"),
            "kda/o_proj/kernel": ((inner, d), res),
        })
    else:
        H, qk = w["AH"], w["nope"] + w["rope"]
        out.update({
            "attn/q/kernel": ((d, H * qk), s),
            "attn/kv_a/kernel": ((d, w["r"] + w["rope"]), s),
            "attn/kv_norm/scale": ((w["r"],), "ones"),
            "attn/kv_b/kernel": ((w["r"], H * (w["nope"] + w["v"])), s),
            "attn/out/kernel": ((H * w["v"], d), res),
        })
    out["ln_mlp/scale"] = ((d,), "ones")
    if mlp == "dense":
        out.update({
            "mlp/wi/kernel": ((d, w["f"]), s), "mlp/gate/kernel": ((d, w["f"]), s),
            "mlp/wo/kernel": ((w["f"], d), res),
        })
    else:
        out.update({
            "moe/router": ((d, w["E"]), s), "moe/router_bias": ((w["E"],), 0.05),
            "moe/wi": ((w["held"], d, w["fe"]), s), "moe/gate": ((w["held"], d, w["fe"]), s),
            "moe/wo": ((w["held"], w["fe"], d), res),
            "moe/shared/wi/kernel": ((d, w["fs"]), s),
            "moe/shared/gate/kernel": ((d, w["fs"]), s),
            "moe/shared/wo/kernel": ((w["fs"], d), res),
        })
    return out


def leaf_table(model: dict) -> dict:
    """path -> (shape, init) of the program's own parameter tree. init is a
    normal's standard deviation or "ones": matrices 0.02, residual
    projections (KDA and MLA out, every down projection) 0.02 / sqrt(2 x
    ``init_depth``), the PUBLISHED depth; the selection bias 0.05, so that a
    program that drops it fails the comparison; conv taps 0.5; ``A_log``
    and ``dt_bias`` normal 1.0 (the published init, A uniform in [1, 16]
    and dt log-uniform, cannot be drawn: at these the channels' decay a
    token spans about exp(-16) to 0.97, so a dropped, doubled or stale
    state fails the comparison through the slow channels); norm scales 1."""
    w = _widths(model)
    table = {"wte/embedding": ((w["V"], w["d"]), 0.02)}
    for prefix, kind in layers(model):
        for name, (shape, init) in _layer_leaves(model, kind).items():
            table[f"{prefix}/{name}"] = (shape, init)
    table["ln_f/scale"] = ((w["d"],), "ones")
    table["lm_head/kernel"] = ((w["d"], w["V"]), 0.02)
    return table


def _mixer_matrices(w: dict, mixer: str) -> int:
    if mixer == "kda":
        inner, D, d = w["inner"], w["KD"], w["d"]
        return 4 * d * inner + 2 * (d * D + D * inner) + d * w["KH"]
    qk = w["nope"] + w["rope"]
    return (w["d"] * w["AH"] * qk + w["d"] * (w["r"] + w["rope"])
            + w["r"] * w["AH"] * (w["nope"] + w["v"]) + w["AH"] * w["v"] * w["d"])


def active_params(model: dict) -> int:
    """Matrix parameters ONE token is multiplied by ON THIS CHIP: every
    layer's mixer; a dense layer's MLP; a routed layer's router, shared
    expert and the ``moe_top_k`` chosen experts that fall among the held ones
    on average (``k x held / n_experts``: 1 at 8 x 32 / 256); the head. The
    lookup is no multiplication; norm scales, conv taps, the per-channel
    vectors and the selection bias multiply nothing."""
    w = _widths(model)
    expert = 3 * w["d"] * w["fe"]
    chosen = round(w["k"] * w["held"] / w["E"])
    total = w["d"] * w["V"]
    for _, (mixer, mlp) in layers(model):
        ffn = 3 * w["d"] * w["f"] if mlp == "dense" else (
            w["d"] * w["E"] + 3 * w["d"] * w["fs"] + chosen * expert)
        total += _mixer_matrices(w, mixer) + ffn
    return total


def attention_flops_per_position(model: dict) -> float:
    """Forward operations of one token attending over ONE cached position,
    as the naive form has them: q.k over ``nope + rope`` and p.v over ``v``
    lanes a head, in the MLA layers (a KDA layer's cost does not grow with
    the context)."""
    w = _widths(model)
    mla = sum(mixer == "attention" for _, (mixer, _) in layers(model))
    return 2.0 * mla * w["AH"] * (w["nope"] + w["rope"] + w["v"])


def state_bytes_per_slot(model: dict, itemsize: int = 2) -> int:
    """What ONE request keeps in the KDA layers, whatever its length: the
    float32 state and the conv's last ``K - 1`` inputs of q, k and v at
    ``itemsize``."""
    w = _widths(model)
    kda = sum(mixer == "kda" for _, (mixer, _) in layers(model))
    return kda * (w["inner"] * w["KD"] * 4 + (w["K"] - 1) * 3 * w["inner"] * itemsize)


def state_update_ops_bytes(model: dict, rows: int) -> tuple:
    """ONE KDA layer's decode-time state update for ``rows`` rows that
    decode: each row's float32 state ``[heads, D, D]`` read and written
    once, the step's small operands (q, k, v, the decay and o a head's D
    each, beta a head) beside it; seven operations a state value (decay,
    k times it and the sum, the outer product and its add, q times the new
    state and the sum). An implementation that also moves the rows that do
    not decode reads no less."""
    w = _widths(model)
    state = w["inner"] * w["KD"]
    small = 5 * w["inner"] + w["KH"]
    return 7.0 * rows * state, float(rows * (2 * state + small) * 4)


def decode_read_bytes(model: dict, rows: int, touched: int, live_positions: int,
                      itemsize: int = 2) -> float:
    """Bytes ONE decode tick must move: every layer's mixer matrices, a
    dense layer's MLP, a routed layer's router and shared expert, the
    ``touched`` (layer, held expert) pairs' experts and the head, once each
    (``itemsize``); the decoding rows' recurrent state in and out (it changes
    whole, every tick); the live cached latent rows of the MLA layers
    (unpadded). No implementation moves less."""
    w = _widths(model)
    total = w["d"] * w["V"] + touched * 3 * w["d"] * w["fe"]
    mla = 0
    for _, (mixer, mlp) in layers(model):
        ffn = 3 * w["d"] * w["f"] if mlp == "dense" else (
            w["d"] * w["E"] + 3 * w["d"] * w["fs"])
        total += _mixer_matrices(w, mixer) + ffn
        mla += mixer == "attention"
    total += mla * live_positions * (w["r"] + w["rope"])
    return float(total * itemsize + 2 * rows * state_bytes_per_slot(model, itemsize))


# ------------------------------------------------------------- the equations


def _q(x, mode):
    dt = _ROUND[mode]
    return x if dt is None else x.astype(dt).astype(jnp.float32)


def _mm(eq, a, b, mode):
    return jnp.einsum(eq, _q(a, mode), _q(b, mode), precision=HIGHEST)


def _kept(x, mode):
    dt = _KEPT.get(mode)
    return x if dt is None else x.astype(dt).astype(jnp.float32)


def _held(x, param_dtype: str):
    dt = _HELD[param_dtype]
    return x if dt is None else x.astype(dt).astype(jnp.float32)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def kda(m: dict, n, s: dict, mode: str):
    """One sequence ``[T, d]`` (normed) through a KDA mixer, from a zero
    state: the recurrence a position at a time."""
    T = n.shape[0]
    H, D = s["KH"], s["KD"]
    qkv = jnp.concatenate([_mm("td,de->te", n, m[f"{p}_proj"]["kernel"], mode)
                           for p in "qkv"], axis=-1)
    taps = jnp.concatenate([m["q_conv"], m["k_conv"], m["v_conv"]], axis=1)
    K = taps.shape[0]
    before = jnp.pad(qkv, ((K - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(taps[j] * before[j:j + T] for j in range(K)))
    q, k, v = (a.reshape(T, H, D) for a in jnp.split(qkv, 3, axis=-1))
    q, k = _unit(q) / jnp.sqrt(jnp.float32(D)), _unit(k)
    f = _mm("tr,re->te", _mm("td,dr->tr", n, m["f_a"]["kernel"], mode), m["f_b"]["kernel"], mode)
    g = -jnp.exp(m["A_log"])[:, None] * jax.nn.softplus(
        f.reshape(T, H, D) + m["dt_bias"].reshape(H, D))
    beta = jax.nn.sigmoid(_mm("td,dh->th", n, m["b_proj"]["kernel"], mode))

    def step(S, at):
        q_t, k_t, v_t, g_t, b_t = at
        S = jnp.exp(g_t)[:, :, None] * S
        u = b_t[:, None] * (v_t - jnp.einsum("hc,hcd->hd", k_t, S, precision=HIGHEST))
        S = _kept(S + k_t[:, :, None] * u[:, None, :], mode)
        return S, jnp.einsum("hc,hcd->hd", q_t, S, precision=HIGHEST)

    _, o = jax.lax.scan(step, jnp.zeros((H, D, D), jnp.float32), (q, k, v, g, beta))
    gate = jax.nn.sigmoid(_mm("tr,re->te", _mm("td,dr->tr", n, m["g_a"]["kernel"], mode),
                              m["g_b"]["kernel"], mode))
    o = rmsnorm(o, m["o_norm_scale"], s["eps"]) * gate.reshape(T, H, D)
    return _mm("te,ed->td", o.reshape(T, H * D), m["o_proj"]["kernel"], mode)


def mla(a: dict, n, s: dict, mode: str):
    """One sequence through latent attention in the naive form, no rotary:
    every key is visible to the queries at and after it."""
    T = n.shape[0]
    H, r, nope = s["AH"], s["r"], s["nope"]
    q = _mm("td,de->te", n, a["q"]["kernel"], mode).reshape(T, H, -1)
    kv = _mm("td,dr->tr", n, a["kv_a"]["kernel"], mode)
    c_kv = rmsnorm(kv[:, :r], a["kv_norm"]["scale"], s["eps"])
    k_pe = kv[:, r:]
    up = _mm("tr,re->te", c_kv, a["kv_b"]["kernel"], mode).reshape(T, H, -1)
    k = jnp.concatenate(
        [up[..., :nope], jnp.broadcast_to(k_pe[:, None, :], (T, H, k_pe.shape[-1]))], -1)
    v = up[..., nope:]
    outs = []
    for at in range(0, T, QUERY_ROWS):
        rows = jnp.arange(at, min(at + QUERY_ROWS, T))
        sc = _mm("nhd,shd->hns", q[rows], k, mode) / jnp.sqrt(jnp.float32(q.shape[-1]))
        visible = jnp.arange(T)[None, :] <= rows[:, None]
        p = jax.nn.softmax(jnp.where(visible[None], sc, -jnp.inf), axis=-1)
        outs.append(_mm("hns,shv->nhv", p, v, mode).reshape(rows.shape[0], -1))
    return _mm("te,ed->td", jnp.concatenate(outs), a["out"]["kernel"], mode)


def swiglu(x, wi, gate, wo, mode):
    h = jax.nn.silu(_mm("nd,df->nf", x, gate, mode)) * _mm("nd,df->nf", x, wi, mode)
    return _mm("nf,fd->nd", h, wo, mode)


def route(x, router, bias, s: dict):
    """``[n, d]`` -> the ``[n, E]`` weight of every expert routed over,
    zero for the unchosen. Float32 whatever the mode."""
    sc = jax.nn.sigmoid(jnp.einsum("nd,de->ne", x, router, precision=HIGHEST))
    _, chosen = jax.lax.top_k(sc + bias, s["k"])
    picked = jnp.sum(jax.nn.one_hot(chosen, sc.shape[-1], dtype=jnp.float32), axis=1)
    w = sc * picked
    return w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * s["scale"]


def ffn(pl: dict, x, mlp: str, s: dict, mode: str):
    if mlp == "dense":
        m = pl["mlp"]
        return swiglu(x, m["wi"]["kernel"], m["gate"]["kernel"], m["wo"]["kernel"], mode)
    m = pl["moe"]
    w = route(x, m["router"], m["router_bias"], s)
    held = m["wi"].shape[0]

    def one(y, e):  # every held expert on every row; the unchosen weigh zero
        wi, gate, wo, we = e
        return y + we[:, None] * swiglu(x, wi, gate, wo, mode), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (m["wi"], m["gate"], m["wo"], w[:, :held].T))
    sh = m["shared"]
    return y + swiglu(x, sh["wi"]["kernel"], sh["gate"]["kernel"], sh["wo"]["kernel"], mode)


def layer(pl: dict, h, kind: tuple, s: dict, mode: str):
    mixer, mlp = kind
    n = rmsnorm(h, pl["ln_attn"]["scale"], s["eps"])
    h = h + (kda(pl["kda"], n, s, mode) if mixer == "kda" else mla(pl["attn"], n, s, mode))
    return h + ffn(pl, rmsnorm(h, pl["ln_mlp"]["scale"], s["eps"]), mlp, s, mode)


def head(h, scale, kernel, s: dict, mode: str):
    return _mm("td,dv->tv", rmsnorm(h, scale, s["eps"]), kernel, mode)


def _statics(model: dict) -> dict:
    return dict(
        KH=model["kda_heads"], KD=model["kda_head_dim"], AH=model["n_heads"],
        r=model["kv_lora_rank"], nope=model["qk_nope_head_dim"],
        eps=float(model.get("norm_eps", 1e-6)), k=model["moe_top_k"],
        scale=float(model.get("moe_routed_scale", 1.0)),
        param_dtype=model.get("param_dtype", "float32"),
    )


def _hold(tree, s: dict):
    return jax.tree.map(lambda x: _held(x, s["param_dtype"]), tree)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, x in flat.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = x
    return out


# --------------------------------------------------------- the whole forward


def logits(params: dict, tokens, model: dict, mode: str = "f32"):
    """[B, T] tokens -> [B, T, V] float32 logits from the whole tree: a
    Python loop over the rows of the batch and the layers (small sizes)."""
    s = _statics(model)
    params = _hold(params, s)
    out = []
    for row in tokens:
        h = jnp.take(params["wte"]["embedding"], row, axis=0)
        for prefix, kind in layers(model):
            h = layer(params[prefix], h, kind, s, mode)
        out.append(head(h, params["ln_f"]["scale"], params["lm_head"]["kernel"], s, mode))
    return jnp.stack(out)


# ------------------------------------------------------- the same, in blocks


def _frozen(s: dict):
    return tuple(sorted(s.items()))


@partial(jax.jit, static_argnames=("kind", "s", "mode"), compiler_options=STRICT)
def _layer_block(pl, h, kind, s, mode):
    s = dict(s)
    return layer(_hold(pl, s), h, kind, s, mode)


@partial(jax.jit, static_argnames=("s", "mode"), compiler_options=STRICT)
def _head_block(h, scale, kernel, s, mode):
    s = dict(s)
    return head(h, _held(scale, s["param_dtype"]), _held(kernel, s["param_dtype"]), s, mode)


@partial(jax.jit, static_argnames=("param_dtype",))
def _embed(table, tokens, param_dtype):
    return _held(jnp.take(table, tokens, axis=0), param_dtype)


def _layer_weights(make, prefix: str, kind: tuple, model: dict) -> dict:
    """One layer's leaves from ``make``, the routed experts an expert at a
    time (``make(paths, e)``: bit for bit the stacked leaf's slice ``e``).
    One program making a routed layer's three ``[32, 2304, 1024]`` expert
    stacks whole takes 19 s to compile for the chip, and every layer's is
    another program (its paths differ); one expert's slice compiles in about
    a second, and the layer's one program serves all of its experts."""
    names = tuple(f"{prefix}/{name}" for name in _layer_leaves(model, kind))
    experts = tuple(f"{prefix}/moe/{w}" for w in ("wi", "gate", "wo")) if kind[1] == "moe" else ()
    flat = dict(make(tuple(n for n in names if n not in experts)))
    if experts:
        parts = [make(experts, e) for e in range(_widths(model)["held"])]
        flat.update({p: jnp.stack([part[p] for part in parts]) for p in experts})
    return _nest({p[len(prefix) + 1:]: x for p, x in flat.items()})


def logits_by_blocks(make, tokens, model: dict, mode: str = "f32"):
    """``logits`` without the tree: the weights are asked for a block at a
    time (the table, one layer, the final norm and the head; ``make(paths)``
    / ``make(paths, index)`` as ``weights.leaf_maker`` gives them) and each
    sequence goes through a layer whole: a recurrence has no rows to take
    apart. The same ``layer`` and ``head`` as ``logits``; only the loops are
    on the host. What the device holds is one layer in float32 (1.1 GB for a
    routed layer at the published widths and 32 held experts) and one
    sequence's activations."""
    s = _statics(model)
    fs = _frozen(s)
    table = make(("wte/embedding",))["wte/embedding"]
    hs = [_embed(table, row, s["param_dtype"]) for row in tokens]
    del table
    for prefix, kind in layers(model):
        pl = _layer_weights(make, prefix, kind, model)
        hs = [_layer_block(pl, h, kind, fs, mode) for h in hs]
        del pl
    last = make(("ln_f/scale", "lm_head/kernel"))
    return jnp.stack([_head_block(h, last["ln_f/scale"], last["lm_head/kernel"], fs, mode)
                      for h in hs])

"""Plain reference for the ``glm4_moe_lite`` family (GLM-4.7-Flash; the
published DeepSeek-V3 block at other numbers): latent attention (MLA) and a
dropless sigmoid-routed expert layer with a shared expert, after
``moe_dense_layers`` leading dense layers.

Straightforward ``jax.numpy`` in float32 with matmuls at ``highest``
precision. It imports nothing of the program: no cache, no absorption, no
kernels, no sorting, no grouped matmul. Every position's keys and values are
up-projected (the NAIVE form) and every expert is applied to every row, the
unchosen with weight zero.

The equations (``model`` is the configuration's ``model`` group), per layer,
pre-norm, ``N`` an RMSNorm (eps ``norm_eps``) with its own scale::

    h = x + MLA(N(x));   y = h + FFN(N(h));   logits = N_f(y_L) W_head

    MLA:  c_q = N(x W_qa);  q_h = c_q W_qb,h = [q_nope_h | q_pe_h]
          [c_kv | k_pe] = x W_kva;  c_kv = N(c_kv)
          q_pe_h, k_pe = RoPE(.)                  ONE k_pe for all heads
          [k_nope_h | v_h] = c_kv W_kvb,h
          score_h = (q_nope_h . k_nope_h + q_pe_h . k_pe) / sqrt(nope + rope), causal
          out = concat_h(softmax(score_h) v_h) W_o
    dense FFN (the first ``moe_dense_layers``):  W_down(silu(W_gate x) * W_up x), width d_ff
    routed FFN:  s = sigmoid(x W_r) in float32;  chosen = top k of (s + b)
                 w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scale     (b is NOT in w)
                 y = sum_e w_e E_e(x) + E_shared(x),  each E the SwiGLU above at width moe_d_ff

Departures from the published description, each a line here and under
``assumed`` in the configuration file: RoPE pairs lanes ``(2i, 2i + 1)``
(interleaved, as the DeepSeek-V3 block this model type derives from; with
random weights either pairing is a permutation of columns); the ``1e-20`` in
the normalisation; the router's scores in float32 whatever ``mode``; the
selection bias drawn from a normal (std 0.05), so that a dropped bias fails
the comparison; no multi-token-prediction layer.

``mode`` is the precision the matmul operands are rounded to on the way in:
``"f32"`` is the reference; ``"bf16"`` and ``"fp8"`` the controls. Apart from
that every leaf is rounded to the configuration's ``param_dtype`` and back
where it is used: the driver hands this module float32 leaves from the same
key the served leaves were made from, so both hold the same numbers and the
comparison judges the arithmetic.

What the harness asks of a family: ``leaf_table``, ``active_params``,
``attention_flops_per_position``, ``logits`` (the whole tree: CPU tests at a
small size) and its block-wise twin ``logits_by_blocks``. For the per-layer
metrics of this family's kernels: ``expert_ops_bytes``,
``latent_decode_ops_bytes`` and ``decode_read_bytes``.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

_ROUND = {"f32": None, "bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}
_HELD = {"float32": None, "bfloat16": jnp.bfloat16}
STRICT = {"xla_allow_excess_precision": False}
HIGHEST = jax.lax.Precision.HIGHEST
ROWS = 256  # the block of rows ``logits_by_blocks`` works in


# ------------------------------------------------------------- what there is


def layers(model: dict) -> list:
    """``[(leaf prefix, kind, index)]``, a layer each: the first
    ``moe_dense_layers`` "dense", the rest "moe". The program's stack is
    unrolled (``scan_layers`` false: a routed stack always is, its expert
    weights are then whole buffers, which a grouped matmul takes without a
    copy): block ``i``'s leaves lie under ``block_<i>``, index None. A
    scanned stack, of one kind, keeps them stacked under ``blocks`` on a
    leading layer axis: index ``i``."""
    L, k = model["n_layers"], model.get("moe_dense_layers", 0)
    kinds = ["dense"] * k + ["moe"] * (L - k)
    if not model.get("scan_layers", True):
        return [(f"block_{i}", kind, None) for i, kind in enumerate(kinds)]
    if len(set(kinds)) > 1:
        raise SystemExit("a scanned stack has one kind of layer")
    return [("blocks", kind, i) for i, kind in enumerate(kinds)]


def _widths(model: dict) -> dict:
    return dict(
        d=model["d_model"], H=model["n_heads"], r=model["kv_lora_rank"],
        rq=model["q_lora_rank"], nope=model["qk_nope_head_dim"],
        rope=model["qk_rope_head_dim"], v=model["v_head_dim"], f=model["d_ff"],
        E=model["n_experts"], k=model["moe_top_k"], fe=model["moe_d_ff"],
        fs=model["moe_shared_experts"] * model["moe_d_ff"], V=model["vocab_size"],
    )


def _layer_leaves(model: dict, kind: str) -> dict:
    """name -> (shape of ONE layer, init)."""
    w = _widths(model)
    d, H, qk = w["d"], w["H"], w["nope"] + w["rope"]
    s = 0.02
    res = s / (2 * model.get("init_depth", model["n_layers"])) ** 0.5
    out = {
        "ln_attn/scale": ((d,), "ones"),
        "attn/q_a/kernel": ((d, w["rq"]), s),
        "attn/q_a_norm/scale": ((w["rq"],), "ones"),
        "attn/q_b/kernel": ((w["rq"], H * qk), s),
        "attn/kv_a/kernel": ((d, w["r"] + w["rope"]), s),
        "attn/kv_norm/scale": ((w["r"],), "ones"),
        "attn/kv_b/kernel": ((w["r"], H * (w["nope"] + w["v"])), s),
        "attn/out/kernel": ((H * w["v"], d), res),
        "ln_mlp/scale": ((d,), "ones"),
    }
    if kind == "dense":
        out.update({
            "mlp/wi/kernel": ((d, w["f"]), s), "mlp/gate/kernel": ((d, w["f"]), s),
            "mlp/wo/kernel": ((w["f"], d), res),
        })
    else:
        out.update({
            "moe/router": ((d, w["E"]), s), "moe/router_bias": ((w["E"],), 0.05),
            "moe/wi": ((w["E"], d, w["fe"]), s), "moe/gate": ((w["E"], d, w["fe"]), s),
            "moe/wo": ((w["E"], w["fe"], d), res),
            "moe/shared/wi/kernel": ((d, w["fs"]), s),
            "moe/shared/gate/kernel": ((d, w["fs"]), s),
            "moe/shared/wo/kernel": ((w["fs"], d), res),
        })
    return out


def leaf_table(model: dict) -> dict:
    """path -> (shape, init) of the program's own parameter tree (a scanned
    stack's leaves stacked on a leading layer axis). init is a normal's standard deviation (0.02; residual projections
    0.02 / sqrt(2 x ``init_depth``), the PUBLISHED depth; the selection bias
    0.05) or "ones"."""
    w = _widths(model)
    table = {"wte/embedding": ((w["V"], w["d"]), 0.02)}
    stacked = (model["n_layers"],) if model.get("scan_layers", True) else ()
    for prefix, kind, _ in layers(model):
        for name, (shape, init) in _layer_leaves(model, kind).items():
            table[f"{prefix}/{name}"] = ((*stacked, *shape), init)
    table["ln_f/scale"] = ((w["d"],), "ones")
    table["lm_head/kernel"] = ((w["d"], w["V"]), 0.02)
    return table


def _attention_matrices(w: dict) -> int:
    qk = w["nope"] + w["rope"]
    return (w["d"] * w["rq"] + w["rq"] * w["H"] * qk + w["d"] * (w["r"] + w["rope"])
            + w["r"] * w["H"] * (w["nope"] + w["v"]) + w["H"] * w["v"] * w["d"])


def active_params(model: dict) -> int:
    """Matrix parameters ONE token is multiplied by: in a routed layer the
    attention, the router, the shared expert and ``moe_top_k`` experts; a
    dense layer whole; the head. The embedding is a lookup; norm scales and
    the selection bias multiply nothing."""
    w = _widths(model)
    expert = 3 * w["d"] * w["fe"]
    total = w["d"] * w["V"]
    for _, kind, _ in layers(model):
        ffn = 3 * w["d"] * w["f"] if kind == "dense" else (
            w["d"] * w["E"] + 3 * w["d"] * w["fs"] + w["k"] * expert)
        total += _attention_matrices(w) + ffn
    return total


def attention_flops_per_position(model: dict) -> float:
    """Forward operations of one token attending over ONE cached position,
    as the naive form has them: q.k over ``nope + rope`` and p.v over ``v``
    lanes a head a layer."""
    w = _widths(model)
    return 2.0 * model["n_layers"] * w["H"] * (w["nope"] + w["rope"] + w["v"])


def expert_ops_bytes(model: dict, rows: int, touched: int, itemsize: int = 2) -> tuple:
    """The routed experts' matmuls of ONE layer over ``rows`` batch rows of
    which ``touched`` distinct experts were chosen: three matmuls a (row,
    choice) pair; each touched expert's three matrices read once, the rows
    in and out once a pair."""
    w = _widths(model)
    pairs = rows * w["k"]
    ops = 2.0 * 3 * w["d"] * w["fe"] * pairs
    byts = (touched * 3 * w["d"] * w["fe"] + 2 * pairs * w["d"]) * itemsize
    return ops, float(byts)


def latent_decode_ops_bytes(model: dict, live_positions: int, rows: int,
                            itemsize: int = 2) -> tuple:
    """One layer's decode attention in the absorbed form over
    ``live_positions`` cached positions summed over its ``rows``: every head
    scores against the latent and the rotated key (``kv_lora_rank + rope``
    lanes) and sums the latent (``kv_lora_rank``); a cached row is read once
    for all heads, UNPADDED; the absorbed queries in and the attended
    latents out."""
    w = _widths(model)
    row = w["r"] + w["rope"]
    ops = 2.0 * w["H"] * (row + w["r"]) * live_positions
    byts = (live_positions * row + rows * w["H"] * (row + w["r"])) * itemsize
    return ops, float(byts)


def decode_read_bytes(model: dict, touched: int, live_positions: int,
                      itemsize: int = 2) -> float:
    """Bytes ONE decode tick must read: every layer's attention matrices, a
    dense layer's MLP, a routed layer's router and shared expert, the
    ``touched`` (layer, expert) pairs' experts, the head, and the live
    cached rows of every layer (unpadded). No implementation reads a touched
    expert less than once a layer a tick."""
    w = _widths(model)
    total = w["d"] * w["V"] + touched * 3 * w["d"] * w["fe"]
    for _, kind, _ in layers(model):
        ffn = 3 * w["d"] * w["f"] if kind == "dense" else (
            w["d"] * w["E"] + 3 * w["d"] * w["fs"])
        total += _attention_matrices(w) + ffn
    total += model["n_layers"] * live_positions * (w["r"] + w["rope"])
    return float(total * itemsize)


# ------------------------------------------------------------- the equations


def _q(x, mode):
    dt = _ROUND[mode]
    return x if dt is None else x.astype(dt).astype(jnp.float32)


def _mm(eq, a, b, mode):
    return jnp.einsum(eq, _q(a, mode), _q(b, mode), precision=HIGHEST)


def _held(x, param_dtype: str):
    dt = _HELD[param_dtype]
    return x if dt is None else x.astype(dt).astype(jnp.float32)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def rope(x, pos, theta: float):
    """Rotate ``[n, ..., D]`` by ``pos`` ``[n]``: pairs ``(2i, 2i + 1)``."""
    D = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) * freqs
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                     x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


def swiglu(x, wi, gate, wo, mode):
    h = jax.nn.silu(_mm("nd,df->nf", x, gate, mode)) * _mm("nd,df->nf", x, wi, mode)
    return _mm("nf,fd->nd", h, wo, mode)


def project(pl: dict, h, pos, s: dict, mode: str):
    """Rows ``h`` ``[n, d]`` at positions ``pos`` -> their queries
    ``[n, H, nope + rope]``, keys (the same width, the rotated key repeated
    for every head) and values ``[n, H, v]``: the naive form."""
    a = pl["attn"]
    n = h.shape[0]
    x = rmsnorm(h, pl["ln_attn"]["scale"], s["eps"])
    c_q = rmsnorm(_mm("nd,dr->nr", x, a["q_a"]["kernel"], mode), a["q_a_norm"]["scale"], s["eps"])
    q = _mm("nr,re->ne", c_q, a["q_b"]["kernel"], mode).reshape(n, s["H"], -1)
    kv = _mm("nd,dr->nr", x, a["kv_a"]["kernel"], mode)
    c_kv = rmsnorm(kv[:, :s["r"]], a["kv_norm"]["scale"], s["eps"])
    k_pe = rope(kv[:, s["r"]:], pos, s["theta"])
    up = _mm("nr,re->ne", c_kv, a["kv_b"]["kernel"], mode).reshape(n, s["H"], -1)
    q = jnp.concatenate([q[..., :s["nope"]], rope(q[..., s["nope"]:], pos, s["theta"])], -1)
    k = jnp.concatenate(
        [up[..., :s["nope"]], jnp.broadcast_to(k_pe[:, None, :], (n, s["H"], k_pe.shape[-1]))], -1)
    return q, k, up[..., s["nope"]:]


def attend(q, pos, k_all, v_all, mode: str):
    """Queries at ``pos`` over ALL keys (key j sits at position j; a key
    past a query's position is masked, so padding beyond the sequence is
    never seen) -> ``[n, H * v]``."""
    sc = _mm("nhd,shd->hns", q, k_all, mode) / math.sqrt(q.shape[-1])
    visible = jnp.arange(k_all.shape[0])[None, :] <= pos[:, None]
    p = jax.nn.softmax(jnp.where(visible[None], sc, -jnp.inf), axis=-1)
    return _mm("hns,shv->nhv", p, v_all, mode).reshape(q.shape[0], -1)


def route(x, router, bias, s: dict):
    """``[n, d]`` -> the ``[n, E]`` weight of every expert, zero for the
    unchosen. Float32 whatever the mode."""
    sc = jax.nn.sigmoid(jnp.einsum("nd,de->ne", x, router, precision=HIGHEST))
    _, chosen = jax.lax.top_k(sc + bias, s["k"])
    picked = jnp.sum(jax.nn.one_hot(chosen, sc.shape[-1], dtype=jnp.float32), axis=1)
    w = sc * picked
    return w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * s["scale"]


def finish(pl: dict, h, o, kind: str, s: dict, mode: str):
    """The attention's output projection and the layer's FFN, on rows."""
    h = h + _mm("ne,ed->nd", o, pl["attn"]["out"]["kernel"], mode)
    x = rmsnorm(h, pl["ln_mlp"]["scale"], s["eps"])
    if kind == "dense":
        m = pl["mlp"]
        return h + swiglu(x, m["wi"]["kernel"], m["gate"]["kernel"], m["wo"]["kernel"], mode)
    m = pl["moe"]
    w = route(x, m["router"], m["router_bias"], s)

    def one(y, e):  # every expert on every row; the unchosen weigh zero
        wi, gate, wo, we = e
        return y + we[:, None] * swiglu(x, wi, gate, wo, mode), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (m["wi"], m["gate"], m["wo"], w.T))
    sh = m["shared"]
    y = y + swiglu(x, sh["wi"]["kernel"], sh["gate"]["kernel"], sh["wo"]["kernel"], mode)
    return h + y


def _statics(model: dict) -> dict:
    return dict(
        H=model["n_heads"], r=model["kv_lora_rank"], nope=model["qk_nope_head_dim"],
        theta=float(model["rope_theta"]), eps=float(model.get("norm_eps", 1e-6)),
        k=model.get("moe_top_k", 0),
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
    pos = jnp.arange(tokens.shape[1])
    out = []
    for row in tokens:
        h = jnp.take(params["wte"]["embedding"], row, axis=0)
        for prefix, kind, l in layers(model):
            pl = params[prefix] if l is None else jax.tree.map(lambda x: x[l], params[prefix])
            q, k, v = project(pl, h, pos, s, mode)
            h = finish(pl, h, attend(q, pos, k, v, mode), kind, s, mode)
        h = rmsnorm(h, params["ln_f"]["scale"], s["eps"])
        out.append(_mm("nd,dv->nv", h, params["lm_head"]["kernel"], mode))
    return jnp.stack(out)


# ------------------------------------------------------- the same, in blocks


def _frozen(s: dict):
    return tuple(sorted(s.items()))


@partial(jax.jit, static_argnames=("s", "mode"), compiler_options=STRICT)
def _project_block(pl, h, pos, s, mode):
    s = dict(s)
    return project(_hold(pl, s), h, pos, s, mode)


@partial(jax.jit, static_argnames=("mode",), compiler_options=STRICT)
def _attend_block(q, pos, k_all, v_all, mode):
    return attend(q, pos, k_all, v_all, mode)


@partial(jax.jit, static_argnames=("kind", "s", "mode"), compiler_options=STRICT)
def _finish_block(pl, h, o, kind, s, mode):
    s = dict(s)
    return finish(_hold(pl, s), h, o, kind, s, mode)


@partial(jax.jit, static_argnames=("s", "mode"), compiler_options=STRICT)
def _head_block(h, scale, kernel, s, mode):
    s = dict(s)
    return _mm("nd,dv->nv", rmsnorm(h, scale, s["eps"]), _held(kernel, s["param_dtype"]), mode)


@partial(jax.jit, static_argnames=("param_dtype",))
def _embed(table, tokens, param_dtype):
    return _held(jnp.take(table, tokens, axis=0), param_dtype)


def logits_by_blocks(make, tokens, model: dict, mode: str = "f32"):
    """``logits`` without the tree, and without a score matrix of the whole
    sequence: the weights are asked for a block at a time (the table, one
    layer, the final norm and the head; ``make(paths)`` / ``make(paths,
    layer)`` as ``weights.leaf_maker`` gives them) and the rows go through
    every step ``ROWS`` at a time, the keys and values of the whole sequence
    (padded to ``max_seq_len``, so one program serves every length) kept
    between a layer's two halves. The same ``project``, ``attend`` and
    ``finish`` as ``logits``; only the loops are on the host. What the
    device holds is one layer in float32 and one sequence's keys and
    values."""
    s = _statics(model)
    fs = _frozen(s)
    B, T = tokens.shape
    if T % ROWS:
        raise SystemExit(f"the block-wise reference takes rows in {ROWS}s; got {T}")
    S = max(model["max_seq_len"], T)
    blocks = [slice(i, i + ROWS) for i in range(0, T, ROWS)]
    table = make(("wte/embedding",))["wte/embedding"]
    hs = [_embed(table, row, s["param_dtype"]) for row in tokens]
    del table
    for prefix, kind, l in layers(model):
        names = tuple(f"{prefix}/{name}" for name in _layer_leaves(model, kind))
        pl = _nest({p[len(prefix) + 1:]: x for p, x in make(names, l).items()})
        for b, h in enumerate(hs):
            parts = [_project_block(pl, h[at], jnp.arange(at.start, at.stop), fs, mode)
                     for at in blocks]
            pad = ((0, S - T), (0, 0), (0, 0))
            k_all = jnp.pad(jnp.concatenate([p[1] for p in parts]), pad)
            v_all = jnp.pad(jnp.concatenate([p[2] for p in parts]), pad)
            hs[b] = jnp.concatenate([
                _finish_block(
                    pl, h[at],
                    _attend_block(q, jnp.arange(at.start, at.stop), k_all, v_all, mode),
                    kind, fs, mode)
                for at, (q, _, _) in zip(blocks, parts)])
        del pl
    last = make(("ln_f/scale", "lm_head/kernel"))
    return jnp.stack([
        jnp.concatenate([_head_block(h[at], last["ln_f/scale"], last["lm_head/kernel"], fs, mode)
                         for at in blocks])
        for h in hs])

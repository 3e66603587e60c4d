"""Plain reference for the ``granitemoehybrid`` family with no experts
(granite-4.0-h-micro): a HYBRID stack of Mamba-2 state-space layers and a
few grouped-head attention layers with no position encoding, every layer
followed by a SwiGLU MLP.

Straightforward ``jax.numpy`` in float32 with matmuls at ``highest``
precision. It imports nothing of the program: no cache, no chunked form, no
kernels. The recurrence is a ``lax.scan`` over positions, the conv four
shifted sums, attention a full masked softmax with the K/V heads repeated.

The equations (``model`` is the configuration's ``model`` group), ``r`` the
residual multiplier, ``N`` an RMSNorm (eps ``norm_eps``) with its own scale::

    h0 = embedding_multiplier * Embed(tokens)
    u = x + r * Mixer(N(x));   y = u + r * MLP(N(u));   MLP(z) = W_o (silu(z W_g) * z W_i)
    logits = (N_f(y_L) Embed^T) / logits_scaling

    mamba:  [z | xBC | dt] = n W_in                      inner | inner + 2 S | heads
            xBC_t = silu(b + sum_{j<K} w_j xBC_{t-K+1+j})     zeros before the sequence
            [x | B | C] = xBC;  dt_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
            H_t = exp(dt_t A) H_{t-1} + dt_t (x_t outer B_t);  y_t = H_t C_t + D x_t;  H_{-1} = 0
            out = (w * g / sqrt(mean(g^2) + eps)) W_out,  g = y * silu(z)     one group of ``inner``
    attention:  q, k, v, o without bias; head h reads K/V head h // (H / KVH);
            scores = (q . k) * attention_scale, causal; NO rotary, bias or table

Departures from the published description, each a line here and under
``assumed`` in the configuration file: the MLP's fused input matrix
(``shared_mlp.input_linear``, 2048 x 16384) is held as its two halves
(``gate``, ``wi``): the same numbers; the init is what ``benchmark/
weights.py`` can draw, a normal or ones (``A_log`` and ``dt_bias`` normal
1.0, not the published uniform / log-uniform; conv taps 0.5, conv bias
0.02; the table 0.004 and residual projections 0.02: ``leaf_table`` says
why); ``time_step_limit`` is (0, inf), which clamps nothing.

``mode`` is the precision the matmul operands are rounded to on the way in:
``"f32"`` is the reference; ``"bf16"`` and ``"fp8"`` the controls. A fourth,
``"state_bf16"``, leaves the matmuls in float32 and rounds what a request
KEEPS to bfloat16 at every position: the recurrent state after each step and
the conv's inputs (the configuration states float32 for the first and
bfloat16 for the second: ``assumed.state_dtypes``). Apart from that every
leaf is rounded to the configuration's ``param_dtype`` and back where it is
used, so reference and program hold the same numbers.

What the harness asks of a family: ``leaf_table``, ``active_params``,
``attention_flops_per_position``, ``logits`` (the whole tree: CPU tests at a
small size) and ``logits_by_blocks``. No ``decays``: the family is served,
not trained. For the per-layer metrics of this family: ``state_update_
ops_bytes`` and ``decode_read_bytes``.
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


# ------------------------------------------------------------- what there is


def _widths(model: dict) -> dict:
    H, P = model["mamba_heads"], model["mamba_head_dim"]
    S = model["mamba_state"]
    return dict(
        d=model["d_model"], f=model["d_ff"], V=model["vocab_size"],
        QH=model["n_heads"], KVH=model["n_kv_heads"], D=model["head_dim"],
        H=H, P=P, S=S, K=model.get("mamba_conv", 4), inner=H * P, C=H * P + 2 * S,
    )


def layers(model: dict) -> list:
    """``[(leaf prefix, kind, period)]``, a layer each, kinds as
    ``layer_pattern`` repeats. The program scans over PERIODS of the pattern:
    block ``j`` of every period lies under ``periods/block_<j>``, stacked on
    a leading period axis."""
    pattern = list(model["layer_pattern"])
    return [(f"periods/block_{i % len(pattern)}", pattern[i % len(pattern)],
             i // len(pattern)) for i in range(model["n_layers"])]


def _layer_leaves(model: dict, kind: str) -> dict:
    """name -> (shape of ONE layer, init)."""
    w = _widths(model)
    d, s = w["d"], 0.02
    res = s  # NOT s / sqrt(2 L): see ``leaf_table``
    out = {"ln_attn/scale": ((d,), "ones")}
    if kind == "mamba":
        out.update({
            "mamba/in_proj/kernel": ((d, w["inner"] + w["C"] + w["H"]), s),
            "mamba/conv_kernel": ((w["K"], w["C"]), 0.5),
            "mamba/conv_bias": ((w["C"],), s),
            "mamba/dt_bias": ((w["H"],), 1.0),
            "mamba/A_log": ((w["H"],), 1.0),
            "mamba/D": ((w["H"],), "ones"),
            "mamba/norm_scale": ((w["inner"],), "ones"),
            "mamba/out_proj/kernel": ((w["inner"], d), res),
        })
    else:
        out.update({
            "attn/query/kernel": ((d, w["QH"] * w["D"]), s),
            "attn/key/kernel": ((d, w["KVH"] * w["D"]), s),
            "attn/value/kernel": ((d, w["KVH"] * w["D"]), s),
            "attn/out/kernel": ((w["QH"] * w["D"], d), res),
        })
    out.update({
        "ln_mlp/scale": ((d,), "ones"),
        "mlp/wi/kernel": ((d, w["f"]), s), "mlp/gate/kernel": ((d, w["f"]), s),
        "mlp/wo/kernel": ((w["f"], d), res),
    })
    return out


def leaf_table(model: dict) -> dict:
    """path -> (shape, init) of the program's own parameter tree (a scanned
    stack's leaves stacked on a leading period axis). init is a normal's
    standard deviation or "ones".

    The table is drawn at 0.004 and the residual projections at 0.02, not at
    this repo's usual 0.02 and 0.02 / sqrt(2 L). With ``embedding_multiplier``
    12 and a TIED head, a 0.02 table under small sublayers reads its own
    token back: the logit of the token just fed is 12 |e|^2 / (rms(h) x 8) =
    4.6 against 0.11 for the spread of all the others, so the greedy stream
    repeats the prompt's last token, every served token is the reference's
    first by a margin no rounding can reach, and every statistic of the
    gap read 0.0 for the program (and would for any control): measured, 747
    judged tokens, PERF.md section 6, PR 33. At 0.004 / 0.02 the 80 sublayers'
    sum (RMS about 1) outweighs the embedding (0.05) and the fed token's logit
    stands two of the others' deviations out: the logits are the LAYERS'."""
    w = _widths(model)
    table = {"wte/embedding": ((w["V"], w["d"]), 0.004)}
    periods = model["n_layers"] // len(model["layer_pattern"])
    for prefix, kind, period in layers(model):
        if period:
            continue  # one stacked leaf for all periods
        for name, (shape, init) in _layer_leaves(model, kind).items():
            table[f"{prefix}/{name}"] = ((periods, *shape), init)
    table["ln_f/scale"] = ((w["d"],), "ones")
    return table


def _matrices(w: dict, kind: str) -> int:
    mlp = 3 * w["d"] * w["f"]
    if kind == "mamba":
        return w["d"] * (w["inner"] + w["C"] + w["H"]) + w["inner"] * w["d"] + mlp
    return 2 * w["d"] * w["QH"] * w["D"] + 2 * w["d"] * w["KVH"] * w["D"] + mlp


def active_params(model: dict) -> int:
    """Matrix parameters ONE token is multiplied by: every layer's
    projections and MLP once, the tied table as the head. The lookup is no
    multiplication; norm scales, the conv's taps and the per-head vectors
    are elementwise."""
    w = _widths(model)
    return w["d"] * w["V"] + sum(_matrices(w, kind) for _, kind, _ in layers(model))


def attention_flops_per_position(model: dict) -> float:
    """Forward operations of one token attending over ONE cached position:
    q.k and p.v over ``head_dim`` lanes a query head, in the layers that
    attend (a mamba layer's cost does not grow with the context)."""
    w = _widths(model)
    attending = sum(kind == "attention" for _, kind, _ in layers(model))
    return 4.0 * attending * w["QH"] * w["D"]


def state_bytes_per_slot(model: dict, itemsize: int = 2) -> int:
    """What ONE request keeps in the mamba layers, whatever its length: the
    float32 state and the conv's last ``K - 1`` inputs at ``itemsize``."""
    w = _widths(model)
    mamba = sum(kind == "mamba" for _, kind, _ in layers(model))
    return mamba * (w["inner"] * w["S"] * 4 + (w["K"] - 1) * w["C"] * itemsize)


def state_update_ops_bytes(model: dict, rows: int) -> tuple:
    """ONE mamba layer's decode-time state update for ``rows`` rows that
    decode: each row's float32 state ``[heads, head_dim, d_state]`` read and
    written once, the step's small operands (x, D x and y ``heads x
    head_dim``, B and C ``d_state``, the decay a head) beside it; five
    operations a state value (decay, outer product, sum, times C, reduce).
    An implementation that also moves the rows that do not decode reads no
    less."""
    w = _widths(model)
    state = w["inner"] * w["S"]
    small = 3 * w["inner"] + 2 * w["S"] + w["H"]
    return 5.0 * rows * state, float(rows * (2 * state + small) * 4)


def decode_read_bytes(model: dict, rows: int, live_positions: int,
                      itemsize: int = 2) -> float:
    """Bytes ONE decode tick must move: every matrix once (``itemsize``),
    the decoding rows' recurrent state in and out, and the live cached K/V
    positions of the layers that attend. No implementation moves less: the
    state of a row that decodes changes whole, every tick."""
    w = _widths(model)
    attending = sum(kind == "attention" for _, kind, _ in layers(model))
    kv = attending * 2 * w["KVH"] * w["D"] * itemsize * live_positions
    return float(active_params(model) * itemsize
                 + 2 * rows * state_bytes_per_slot(model, itemsize) + kv)


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


def mamba(m: dict, n, s: dict, mode: str):
    """One sequence ``[T, d]`` (normed) through a mamba mixer, from a zero
    state: the recurrence a position at a time."""
    T = n.shape[0]
    H, P, S, inner = s["H"], s["P"], s["S"], s["H"] * s["P"]
    proj = _mm("td,de->te", n, m["in_proj"]["kernel"], mode)
    z, xbc, dt = proj[:, :inner], proj[:, inner:inner + inner + 2 * S], proj[:, -H:]
    K = m["conv_kernel"].shape[0]
    before = jnp.pad(_kept(xbc, mode), ((K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(m["conv_bias"] + sum(
        m["conv_kernel"][j] * before[j:j + T] for j in range(K)))
    x, B, C = xbc[:, :inner].reshape(T, H, P), xbc[:, inner:inner + S], xbc[:, inner + S:]
    dt = jax.nn.softplus(dt + m["dt_bias"])
    A = -jnp.exp(m["A_log"])

    def step(state, at):
        x_t, B_t, C_t, dt_t = at
        state = _kept(jnp.exp(dt_t * A)[:, None, None] * state
                      + (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :], mode)
        return state, jnp.sum(state * C_t[None, None, :], axis=-1) + m["D"][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, S), jnp.float32), (x, B, C, dt))
    g = y.reshape(T, inner) * jax.nn.silu(z)
    g = m["norm_scale"] * g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + s["eps"])
    return _mm("te,ed->td", g, m["out_proj"]["kernel"], mode)


def attention(a: dict, n, s: dict, mode: str):
    """One sequence through grouped-head causal attention, no position
    encoding: every key is visible to the queries at and after it."""
    T = n.shape[0]
    QH, KVH, D = s["QH"], s["KVH"], s["D"]
    q = _mm("td,de->te", n, a["query"]["kernel"], mode).reshape(T, QH, D)
    k = _mm("td,de->te", n, a["key"]["kernel"], mode).reshape(T, KVH, D)
    v = _mm("td,de->te", n, a["value"]["kernel"], mode).reshape(T, KVH, D)
    k, v = jnp.repeat(k, QH // KVH, axis=1), jnp.repeat(v, QH // KVH, axis=1)
    sc = _mm("thd,shd->hts", q, k, mode) * s["scale"]
    visible = jnp.tril(jnp.ones((T, T), jnp.bool_))
    p = jax.nn.softmax(jnp.where(visible[None], sc, -jnp.inf), axis=-1)
    o = _mm("hts,shd->thd", p, v, mode).reshape(T, QH * D)
    return _mm("te,ed->td", o, a["out"]["kernel"], mode)


def layer(pl: dict, h, kind: str, s: dict, mode: str):
    n = rmsnorm(h, pl["ln_attn"]["scale"], s["eps"])
    mixed = mamba(pl["mamba"], n, s, mode) if kind == "mamba" else attention(pl["attn"], n, s, mode)
    h = h + s["r"] * mixed
    n = rmsnorm(h, pl["ln_mlp"]["scale"], s["eps"])
    m = pl["mlp"]
    up = jax.nn.silu(_mm("td,df->tf", n, m["gate"]["kernel"], mode)) \
        * _mm("td,df->tf", n, m["wi"]["kernel"], mode)
    return h + s["r"] * _mm("tf,fd->td", up, m["wo"]["kernel"], mode)


def head(h, scale, table, s: dict, mode: str):
    return _mm("td,vd->tv", rmsnorm(h, scale, s["eps"]), table, mode) / s["logits_scaling"]


def _statics(model: dict) -> dict:
    w = _widths(model)
    scale = model.get("attention_scale")
    return dict(
        QH=w["QH"], KVH=w["KVH"], D=w["D"], H=w["H"], P=w["P"], S=w["S"],
        scale=float(scale if scale is not None else w["D"] ** -0.5),
        eps=float(model.get("norm_eps", 1e-6)),
        r=float(model.get("residual_multiplier", 1.0)),
        embedding_multiplier=float(model.get("embedding_multiplier", 1.0)),
        logits_scaling=float(model.get("logits_scaling", 1.0)),
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


def _at(tree: dict, prefix: str) -> dict:
    for part in prefix.split("/"):
        tree = tree[part]
    return tree


# --------------------------------------------------------- the whole forward


def logits(params: dict, tokens, model: dict, mode: str = "f32"):
    """[B, T] tokens -> [B, T, V] float32 logits from the whole tree: a
    Python loop over the rows of the batch and the layers (small sizes)."""
    s = _statics(model)
    params = _hold(params, s)
    table = params["wte"]["embedding"]
    out = []
    for row in tokens:
        h = s["embedding_multiplier"] * jnp.take(table, row, axis=0)
        for prefix, kind, i in layers(model):
            pl = jax.tree.map(lambda x: x[i], _at(params, prefix))
            h = layer(pl, h, kind, s, mode)
        out.append(head(h, params["ln_f"]["scale"], table, s, mode))
    return jnp.stack(out)


# ------------------------------------------------------- the same, in blocks


def _frozen(s: dict):
    return tuple(sorted(s.items()))


@partial(jax.jit, static_argnames=("kind", "s", "mode"), compiler_options=STRICT)
def _layer_block(pl, h, kind, s, mode):
    s = dict(s)
    return layer(_hold(pl, s), h, kind, s, mode)


@partial(jax.jit, static_argnames=("s", "mode"), compiler_options=STRICT)
def _head_block(h, scale, table, s, mode):
    s = dict(s)
    return head(h, _held(scale, s["param_dtype"]), _held(table, s["param_dtype"]), s, mode)


@partial(jax.jit, static_argnames=("s",))
def _embed(table, tokens, s):
    s = dict(s)
    return s["embedding_multiplier"] * _held(jnp.take(table, tokens, axis=0), s["param_dtype"])


def logits_by_blocks(make, tokens, model: dict, mode: str = "f32"):
    """``logits`` without the tree: the weights are asked for a block at a
    time (the table, which is also the head; one layer; the final norm;
    ``make(paths)`` / ``make(paths, index)`` as ``weights.leaf_maker`` gives
    them) and each sequence goes through a layer whole: a recurrence has no
    rows to take apart, and at the cells' lengths (at most 512) nothing it
    holds is large. The same ``layer`` and ``head`` as ``logits``; only the
    loops are on the host. What the device holds is the table (0.82 GB at
    the published size) and one layer in float32."""
    s = _statics(model)
    fs = _frozen(s)
    table = make(("wte/embedding",))["wte/embedding"]
    hs = [_embed(table, row, fs) for row in tokens]
    for prefix, kind, index in layers(model):
        names = tuple(f"{prefix}/{name}" for name in _layer_leaves(model, kind))
        pl = _nest({p[len(prefix) + 1:]: x for p, x in make(names, index).items()})
        hs = [_layer_block(pl, h, kind, fs, mode) for h in hs]
        del pl
    scale = make(("ln_f/scale",))["ln_f/scale"]
    return jnp.stack([_head_block(h, scale, table, fs, mode) for h in hs])

"""Plain reference for the GPT-2 + ALiBi family (fattorib/ZeRO-transformer).

Straightforward ``jax.numpy`` in float32 with matmuls at ``highest``
precision: no kernels, no cache, no batching tricks. It imports nothing of
the program. Pre-norm blocks, bias-free projections and LayerNorms
(eps 1e-6), tanh GELU, tied embeddings, causal softmax attention with the
ALiBi bias ``-slope_h * (i - j)``, next-token cross entropy averaged over
the ``T - 1`` predicted positions of every row.

``mode`` is the precision the matmul operands are rounded to on the way in
(straight-through, so the backward pass stays float32): ``"f32"`` is the
reference; ``"bf16"`` and ``"fp8"`` are the controls the limits are read
against.

What the harness asks of a family's reference module, which it finds by
the configuration file's ``reference`` key: ``leaf_table``,
``active_params``, ``attention_flops_per_position``, ``decays``, ``logits``
(and, where the tree should never be held whole, its block-wise twin
``logits_by_blocks``), ``step_loss_and_grads`` and ``step_loss``, each
taking the configuration's ``model`` group. The drivers name no family.

Training is followed micro-batch by micro-batch with a hand-written
layer-by-layer backward pass (``jax.vjp`` of one layer at a time, the
gradient added in place), because three float32 copies of 1.3e9 parameters
(weights, running sum, one micro-batch's gradient) do not fit a 16 GB chip.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

LN_EPS = 1e-6
_ROUND = {"f32": None, "bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}
# XLA may keep MORE precision than a program asks for (its default): on the
# chip it then drops ``_q``'s roundings, all of bfloat16's and, in a program
# that takes a layer's weights as arguments, most of fp8's (my chip run, PR
# 30: the "bf16" logits equalled float32's to 2.5e-6). The programs a control
# is read through are compiled to round where they say.
STRICT = {"xla_allow_excess_precision": False}


def alibi_slopes(n_heads: int) -> jnp.ndarray:
    def pow2(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start ** (i + 1) for i in range(n)]

    if math.log2(n_heads).is_integer():
        s = pow2(n_heads)
    else:
        c = 2 ** math.floor(math.log2(n_heads))
        s = pow2(c) + pow2(2 * c)[0::2][: n_heads - c]
    return jnp.asarray(s, jnp.float32)


def leaf_table(model: dict) -> dict:
    """path -> (shape, init) of the program's own parameter tree
    (``models/gpt.py`` with ``scan_layers``: layer leaves stacked on a
    leading ``n_layers`` axis; the drivers check it against the program's
    abstract tree). init is a normal's standard deviation, or "ones" for a
    norm scale: GPT-2's, as the source's (0.02; residual projections
    0.02/sqrt(2L))."""
    d, L, V = model["d_model"], model["n_layers"], model["vocab_size"]
    hd = model["n_heads"] * model["head_dim"]
    f = model["d_ff"]
    s = 0.02
    r = s / (2 * L) ** 0.5
    return {
        "wte/embedding": ((V, d), s),
        "blocks/ln_attn/scale": ((L, d), "ones"),
        "blocks/attn/query/kernel": ((L, d, hd), s),
        "blocks/attn/key/kernel": ((L, d, hd), s),
        "blocks/attn/value/kernel": ((L, d, hd), s),
        "blocks/attn/out/kernel": ((L, hd, d), r),
        "blocks/ln_mlp/scale": ((L, d), "ones"),
        "blocks/mlp/wi/kernel": ((L, d, f), s),
        "blocks/mlp/wo/kernel": ((L, f, d), r),
        "ln_f/scale": ((d,), "ones"),
    }


def active_params(model: dict) -> int:
    """Parameters a token passes through (dense: all of them, the tied
    embedding once)."""
    return sum(math.prod(shape) for shape, _ in leaf_table(model).values())


def attention_flops_per_position(model: dict) -> float:
    """Forward operations of one token attending over ONE cached position:
    q.k and p.v in every layer."""
    return 4.0 * model["n_layers"] * model["n_heads"] * model["head_dim"]


def decays(path: str) -> bool:
    """The recipe's weight-decay mask: matrices and the embedding."""
    return path.rsplit("/", 1)[-1] in ("kernel", "embedding")


def _q(x, mode):
    dt = _ROUND[mode]
    if dt is None:
        return x
    return x + jax.lax.stop_gradient(x.astype(dt).astype(jnp.float32) - x)


def _mm(eq, a, b, mode):
    return jnp.einsum(eq, _q(a, mode), _q(b, mode),
                      precision=jax.lax.Precision.HIGHEST)


def layernorm(x, scale):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * scale


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


def layer(pl: dict, h, n_heads: int, mode: str = "f32"):
    """One block. ``pl`` holds one layer's leaves; ``h`` is [B, T, d]."""
    B, T, d = h.shape
    x = layernorm(h, pl["ln_attn"]["scale"])
    a = pl["attn"]
    q = _mm("btd,de->bte", x, a["query"]["kernel"], mode).reshape(B, T, n_heads, -1)
    k = _mm("btd,de->bte", x, a["key"]["kernel"], mode).reshape(B, T, n_heads, -1)
    v = _mm("btd,de->bte", x, a["value"]["kernel"], mode).reshape(B, T, n_heads, -1)
    D = q.shape[-1]
    s = _mm("bthd,bshd->bhts", q, k, mode) / math.sqrt(D)
    i = jnp.arange(T)[:, None]
    j = jnp.arange(T)[None, :]
    dist = (i - j).astype(jnp.float32)
    bias = -alibi_slopes(n_heads)[:, None, None] * dist[None]
    s = jnp.where((j <= i)[None, None], s + bias[None], -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("bhts,bshd->bthd", p, v, mode).reshape(B, T, -1)
    h = h + _mm("bte,ed->btd", o, a["out"]["kernel"], mode)
    x = layernorm(h, pl["ln_mlp"]["scale"])
    m = gelu(_mm("btd,df->btf", x, pl["mlp"]["wi"]["kernel"], mode))
    return h + _mm("btf,fd->btd", m, pl["mlp"]["wo"]["kernel"], mode)


def _head_logits(hL, lnf, table, mode):
    return _mm("btd,vd->btv", layernorm(hL, lnf), table, mode)


def _head_loss(hL, lnf, table, tokens, mode):
    logits = _head_logits(hL, lnf, table, mode)[:, :-1]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    lab = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - lab)


def logits(params: dict, tokens, model: dict, mode: str = "f32"):
    """[B, T] tokens -> [B, T, V] float32 logits: the whole forward pass."""
    return _logits(params, tokens, n_heads=model["n_heads"], mode=mode)


@partial(jax.jit, static_argnames=("n_heads", "mode"), compiler_options=STRICT)
def _logits(params: dict, tokens, n_heads: int, mode: str = "f32"):
    h = jnp.take(params["wte"]["embedding"], tokens, axis=0)
    h, _ = jax.lax.scan(
        lambda h, pl: (layer(pl, h, n_heads, mode), None), h, params["blocks"]
    )
    return _head_logits(h, params["ln_f"]["scale"], params["wte"]["embedding"], mode)


def logits_by_blocks(make, tokens, model: dict, mode: str = "f32"):
    """``logits`` without the tree: the weights are asked for a block at a
    time. ``make(paths)`` gives {path: leaf} for unstacked leaves of
    ``leaf_table``, ``make(paths, layer)`` one layer's slice of stacked
    ones. A block is the table, one layer, or the final norm and the (tied)
    table again, so what the device holds is the largest of them and one
    request's activations, whatever the depth. The same ``layer`` and head
    as ``logits``; only the loop over the layers is on the host."""
    h = _embed(make(("wte/embedding",))["wte/embedding"], tokens)
    for l in range(model["n_layers"]):
        h = _layer(_nest(make(LAYER_LEAVES, l)), h, n_heads=model["n_heads"], mode=mode)
    last = make(("ln_f/scale", "wte/embedding"))
    return _head(h, last["ln_f/scale"], last["wte/embedding"], mode=mode)


LAYER_LEAVES = tuple(
    f"blocks/{name}" for name in (
        "ln_attn/scale", "attn/query/kernel", "attn/key/kernel", "attn/value/kernel",
        "attn/out/kernel", "ln_mlp/scale", "mlp/wi/kernel", "mlp/wo/kernel"))


def _nest(layer_leaves: dict) -> dict:
    """{"blocks/attn/query/kernel": x, ...} -> {"attn": {"query": {"kernel": x}}, ...}"""
    out: dict = {}
    for path, x in layer_leaves.items():
        node = out
        *parents, last = path.split("/")[1:]
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = x
    return out


_embed = jax.jit(lambda table, tokens: jnp.take(table, tokens, axis=0))
_layer = jax.jit(layer, static_argnames=("n_heads", "mode"), compiler_options=STRICT)
_head = jax.jit(_head_logits, static_argnames=("mode",), compiler_options=STRICT)


@partial(jax.jit, static_argnames=("n_heads", "mode"), donate_argnums=(2,))
def micro_loss_and_grads(params: dict, tokens, gsum: dict, weight, n_heads: int,
                         mode: str = "f32"):
    """Loss of one block of rows, and ``gsum += weight * d loss / d params``
    in place. The caller sums over the blocks of one step."""
    table = params["wte"]["embedding"]
    blocks = params["blocks"]
    L = jax.tree.leaves(blocks)[0].shape[0]
    h0 = jnp.take(table, tokens, axis=0)

    def fwd(h, pl):
        return layer(pl, h, n_heads, mode), h

    hL, hs = jax.lax.scan(fwd, h0, blocks)
    loss, head_vjp = jax.vjp(
        lambda h, s, t: _head_loss(h, s, t, tokens, mode),
        hL, params["ln_f"]["scale"], table,
    )
    dh, dlnf, dtable = head_vjp(jnp.asarray(weight, jnp.float32))

    def bwd(carry, l):
        dh, g = carry
        pl = jax.tree.map(lambda a: a[l], blocks)
        _, vjp = jax.vjp(lambda p, h: layer(p, h, n_heads, mode), pl, hs[l])
        dpl, dh = vjp(dh)
        g = jax.tree.map(lambda G, x: G.at[l].add(x), g, dpl)
        return (dh, g), None

    (dh0, gblocks), _ = jax.lax.scan(
        bwd, (dh, gsum["blocks"]), jnp.arange(L - 1, -1, -1)
    )
    gtable = (gsum["wte"]["embedding"] + dtable).at[tokens].add(dh0)
    return loss, {
        "wte": {"embedding": gtable},
        "blocks": gblocks,
        "ln_f": {"scale": gsum["ln_f"]["scale"] + dlnf},
    }


def step_loss_and_grads(params, batch, model, mode="f32", rows_per_block=4,
                        row_weights=None):
    """Mean loss and mean gradient over every row of ``batch`` ([rows, T]),
    taken ``rows_per_block`` rows at a time. ``row_weights`` (one number per
    block, summing to 1) is for the planted faults."""
    rows = batch.shape[0]
    n = rows // rows_per_block
    gsum = jax.tree.map(jnp.zeros_like, params)
    total = 0.0
    for b in range(n):
        w = 1.0 / n if row_weights is None else row_weights[b]
        if w == 0.0:
            continue
        loss, gsum = micro_loss_and_grads(
            params, batch[b * rows_per_block:(b + 1) * rows_per_block], gsum, w,
            n_heads=model["n_heads"], mode=mode,
        )
        total = total + w * loss
    return total, gsum


@partial(jax.jit, static_argnames=("n_heads", "mode"))
def block_loss(params: dict, tokens, n_heads: int, mode: str = "f32"):
    h = jnp.take(params["wte"]["embedding"], tokens, axis=0)
    h, _ = jax.lax.scan(
        lambda h, pl: (layer(pl, h, n_heads, mode), None), h, params["blocks"]
    )
    return _head_loss(h, params["ln_f"]["scale"], params["wte"]["embedding"],
                      tokens, mode)


def step_loss(params, batch, model, mode="f32", rows_per_block=4,
              row_weights=None) -> float:
    """Mean loss over every row of ``batch``, forward only."""
    n = batch.shape[0] // rows_per_block
    total = 0.0
    for b in range(n):
        w = 1.0 / n if row_weights is None else row_weights[b * len(row_weights) // n]
        if w == 0.0:
            continue
        total += w * float(block_loss(
            params, batch[b * rows_per_block:(b + 1) * rows_per_block],
            n_heads=model["n_heads"], mode=mode))
    return total

"""Plain adafactor, and how to read the first gradient back out of the
program's optimizer state. A traffic file's ``optimizer.optimizer`` names
its file here; every optimizer's file has ``init``, ``update`` and
``first_gradient_norms``. It imports nothing of the program."""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from benchmark import weights


def _factored_dims(shape, min_dim=128):
    if len(shape) < 2:
        return None
    order = sorted(range(len(shape)), key=lambda i: (shape[i], i))
    if shape[order[-2]] < min_dim:
        return None
    return order[-2], order[-1]


def init(params):
    def one(p):
        dims = _factored_dims(p.shape)
        if dims is None:
            return {"v": jnp.zeros_like(p)}
        d1, d0 = dims
        return {
            "v_row": jnp.zeros([s for i, s in enumerate(p.shape) if i != d0]),
            "v_col": jnp.zeros([s for i, s in enumerate(p.shape) if i != d1]),
        }

    return {"count": 0, "v": {k: one(p) for k, p in params.items()}}


@partial(jax.jit, static_argnames=("decays",), donate_argnums=(0, 2))
def _adafactor_leaf(p, g, v, t, lr, weight_decay, decays):
    """Adafactor (Shazeer & Stern 2018) as the recipe configures it: factored
    second moments over the two largest axes (decay 1 - t**-0.8, eps 1e-30),
    no update clipping, no momentum, the update scaled by max(rms(p), 1e-3)
    and the learning rate, then decoupled weight decay lr*wd*p."""
    decay = 1.0 - t ** (-0.8)
    gsq = jnp.square(g) + 1e-30
    if "v" in v:
        nv = {"v": decay * v["v"] + (1 - decay) * gsq}
        u = g * jax.lax.rsqrt(nv["v"])
    else:
        d1, d0 = _factored_dims(p.shape)
        row = decay * v["v_row"] + (1 - decay) * jnp.mean(gsq, axis=d0)
        col = decay * v["v_col"] + (1 - decay) * jnp.mean(gsq, axis=d1)
        nv = {"v_row": row, "v_col": col}
        rd1 = d1 - 1 if d1 > d0 else d1
        rfac = jax.lax.rsqrt(row / jnp.mean(row, axis=rd1, keepdims=True))
        u = g * jnp.expand_dims(rfac, d0) * jnp.expand_dims(jax.lax.rsqrt(col), d1)
    u = u * lr * jnp.maximum(jnp.sqrt(jnp.mean(jnp.square(p))), 1e-3)
    new_p = p - u
    if decays:
        new_p = new_p - lr * weight_decay * p
    return new_p, nv


def update(params, grads, state, lr, weight_decay, decays):
    """Flat dicts path -> leaf; ``decays(path)`` is the family's weight-decay
    mask. Consumes params, grads and state."""
    t = jnp.asarray(state["count"] + 1, jnp.float32)
    new_p, new_v = {}, {}
    for path in list(params):
        new_p[path], new_v[path] = _adafactor_leaf(
            params.pop(path), grads.pop(path), state["v"].pop(path), t,
            jnp.float32(lr), jnp.float32(weight_decay), decays=decays(path),
        )
    return new_p, {"count": state["count"] + 1, "v": new_v}


def _find_state(tree, field: str):
    """The optimizer's state object that has ``field`` (optax NamedTuples)."""
    if hasattr(tree, "_fields"):
        if field in tree._fields:
            return tree
        tree = tuple(tree)
    if isinstance(tree, (tuple, list)):
        for t in tree:
            found = _find_state(t, field)
            if found is not None:
                return found
    return None


def first_gradient_norms(opt_state, params_flat: dict) -> dict:
    """Per-leaf norm of the gradient the program's optimizer was given at
    step 1, worked out from its state after that step: at t = 1 adafactor's
    second moments are the means of g*g + 1e-30 (its decay is
    1 - t**-0.8 = 0)."""
    st = _find_state(opt_state, "v_row")
    rows, fulls = weights.flatten(st.v_row), weights.flatten(st.v)
    out = {}
    for path, p in params_flat.items():
        dims = _factored_dims(p.shape)
        n = math.prod(p.shape)
        if dims is None:
            sq = float(jnp.sum(fulls[path])) - 1e-30 * n
        else:
            sq = float(jnp.sum(rows[path])) * p.shape[dims[1]] - 1e-30 * n
        out[path] = math.sqrt(max(sq, 0.0))
    return out

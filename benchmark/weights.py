"""Weights made on the device from ``--seed``, by the benchmark alone.

One jitted call makes every leaf in the type it is served or trained in.
The program under test and the plain reference both get their weights from
here, each from its own call: the reference takes nothing the program made.

Which leaves there are is the family's to say: a ``table`` here is the
``leaf_table(model)`` of the configuration's reference module, path ->
(shape, init), where init is a normal's standard deviation or "ones". The
drivers check the table against the program's abstract tree before they
install what it gives.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: str = "") -> jax.Array:
    """A threefry key from any whole number (``--seed`` may pass 2**31) and a
    stream name, mixed on the host so no device integer ever overflows."""
    words = np.random.SeedSequence([int(seed), zlib.crc32(stream.encode())])
    return jax.random.wrap_key_data(
        jnp.asarray(words.generate_state(2), jnp.uint32), impl="threefry2x32"
    )


def nest(flat: dict) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, path))
        else:
            out[path] = v
    return out


def make_leaf(key: jax.Array, path: str, shape, init, dtype=jnp.float32):
    if init == "ones":
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    return (jax.random.normal(k, shape, jnp.float32) * init).astype(dtype)


def make_params(table: dict, key: jax.Array, dtype=jnp.float32) -> dict:
    """The whole tree; call under ``jax.jit`` (with ``out_shardings`` where
    the program shards its parameters)."""
    return nest({
        path: make_leaf(key, path, shape, init, dtype)
        for path, (shape, init) in table.items()
    })


def build(table: dict, key: jax.Array, dtype=jnp.float32, out_shardings=None) -> dict:
    """The whole tree in one jitted call. The key is an ARGUMENT of the
    compiled program, not a constant in it, so every seed runs the one
    program the compile cache already holds."""
    fn = jax.jit(lambda k: make_params(table, k, dtype), out_shardings=out_shardings)
    return fn(key)


def leaf_distance(leaf, key: jax.Array, path: str, shape, init):
    """|| leaf - the leaf the seed gives ||, the seed's leaf made on the fly."""
    fn = jax.jit(lambda p, k: jnp.sqrt(jnp.sum(jnp.square(
        p - make_leaf(k, path, shape, init)))))
    return fn(leaf, key)


def check_tree(ours: dict, theirs) -> None:
    """Raise unless the program's abstract parameter tree has exactly our
    paths and shapes."""
    mine = {p: tuple(s) for p, (s, _) in ours.items()}
    prog = {p: tuple(v.shape) for p, v in flatten(theirs).items()}
    if mine != prog:
        diff = sorted(set(mine.items()) ^ set(prog.items()))
        raise SystemExit(f"benchmark weights do not match the program's tree: {diff}")

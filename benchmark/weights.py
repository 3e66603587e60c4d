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

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry2x32_p


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


def make_leaf(key: jax.Array, path: str, shape, init, dtype=jnp.float32, layer=None):
    """One leaf of a table. With ``layer`` (an index on the leading, stacked
    axis; a host integer or a traced scalar) only that layer's slice, bit for
    bit the whole leaf's ``[layer]``, and nothing of the rest is made."""
    if init == "ones":
        return jnp.ones(shape if layer is None else shape[1:], dtype)
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    if layer is None:
        normal = jax.random.normal(k, shape, jnp.float32)
    else:
        normal = _normal_layer(k, shape, layer)
    return (normal * init).astype(dtype)


def _normal_layer(key: jax.Array, shape, layer):
    """``jax.random.normal(key, shape, float32)[layer]`` from the counters of
    that slice alone. XLA does not move a slice into the generator (a sliced
    [18, 1536, 6144] leaf compiles to 2.9 GB of temporaries on a v5e), so the
    block-wise reference could not otherwise be smaller than a stacked leaf.
    With ``jax_threefry_partitionable`` (the default) element ``i`` of the
    flattened array is threefry2x32 of the 64-bit counter ``i`` alone, its
    two words xor-ed; the bits become a float in [1, 2) by the mantissa, then
    a uniform in (-1, 1), then ``sqrt(2) * erf_inv``: ``jax.random``'s own
    steps, which ``tests/benchmark`` holds this to, bit for bit."""
    if not jax.config.jax_threefry_partitionable:
        raise SystemExit("a leaf's slice is made from jax's partitionable threefry counters")
    inner = math.prod(shape[1:])
    if inner >= 2 ** 32:
        raise SystemExit(f"one layer of {shape} is more than 2**32 values")
    u32 = jnp.uint32
    # the slice starts at the 64-bit counter layer * inner: 16-bit limbs, so
    # no product overflows 32 bits
    layer = jnp.asarray(layer, u32)
    l_lo, l_hi = layer & u32(0xFFFF), layer >> 16
    i_lo, i_hi = u32(inner & 0xFFFF), u32(inner >> 16)
    mid = l_lo * i_hi + ((l_lo * i_lo) >> 16)
    mid2 = l_hi * i_lo + (mid & u32(0xFFFF))
    base_lo = ((l_lo * i_lo) & u32(0xFFFF)) | (mid2 << 16)
    base_hi = l_hi * i_hi + (mid >> 16) + (mid2 >> 16)
    lo = base_lo + jax.lax.iota(u32, inner)
    hi = jnp.broadcast_to(base_hi, lo.shape) + (lo < base_lo).astype(u32)
    k1, k2 = jax.random.key_data(key)
    bits1, bits2 = threefry2x32_p.bind(k1, k2, hi, lo)
    one_to_two = jax.lax.bitcast_convert_type(
        ((bits1 ^ bits2) >> 9) | u32(np.array(1.0, np.float32).view(np.uint32)), jnp.float32)
    low = np.nextafter(np.float32(-1.0), np.float32(0.0))
    uniform = jnp.maximum(low, (one_to_two - np.float32(1.0)) * (np.float32(1.0) - low) + low)
    return (np.float32(np.sqrt(2)) * jax.lax.erf_inv(uniform)).reshape(shape[1:])


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


def leaf_maker(table: dict, key: jax.Array, dtype=jnp.float32):
    """``make(paths)`` gives {path: leaf} for whole leaves of the table,
    ``make(paths, layer)`` one layer's slice of each (stacked) leaf: bit for
    bit what ``build`` gives under the same key, a block at a time, so that
    a reference can run in blocks and never hold the tree. One program per
    group of paths, whatever the layer: a new process loads each from the
    compile cache, a third of a second apiece, so ask for a block's leaves
    together."""
    programs: dict = {}

    def make(paths, layer=None) -> dict:
        paths = tuple(paths)
        if (paths, layer is None) not in programs:
            programs[paths, layer is None] = jax.jit(lambda k, l: {
                p: make_leaf(k, p, *table[p], dtype, layer=l) for p in paths})
        return programs[paths, layer is None](key, layer)

    return make


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

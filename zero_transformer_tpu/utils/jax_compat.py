"""Two small helpers around jax's ambient-mesh and donation seams.

The codebase calls the installed jax (0.9) directly — ``jax.shard_map``,
``jax.set_mesh``, ``jax.sharding.get_abstract_mesh``; what lives here is
what is not a one-liner: ``clear_abstract_mesh`` and the donation-safety
copy ``ensure_donatable``.
"""
from __future__ import annotations

import jax


def clear_abstract_mesh():
    """Context clearing the ambient mesh (see ``inference.generate``:
    flax boxing must not read logical axis names as mesh axes)."""
    from jax.sharding import AbstractMesh

    return jax.sharding.use_abstract_mesh(AbstractMesh((), ()))


def ensure_donatable(tree):
    """Copy every leaf into an XLA-runtime-owned buffer (eager add-0).

    ``jax.device_put`` from host numpy and orbax restores can hand back
    arrays whose buffers the runtime does NOT own (zero-copy views of host
    memory). The train step donates its input state, and donating such a
    foreign buffer lets XLA recycle memory it never owned — observed on the
    CPU backend as state that silently turns to garbage within a step or two
    and a glibc heap-corruption abort. An eager add-0 per leaf runs a
    real XLA computation, so every output buffer is freshly allocated and
    runtime-owned (shardings are preserved: eager ops follow their committed
    operands). Call this on ANY state that flows into a donating jit from
    outside one: checkpoint restores, host-RAM rollback snapshots, warm-init
    imports.
    """
    import jax.numpy as jnp

    return jax.tree.map(lambda x: jnp.add(x, jnp.zeros((), x.dtype)), tree)

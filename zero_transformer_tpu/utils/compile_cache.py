"""Where jax's persistent compilation cache lives — decided from OUTSIDE.

One rule for every entry point that jits (``train.py``, ``serve``,
``bench.py``, ``chip_smoke.py``, the scripts, and — through
``tests/_compile_cache.py`` — the test suite): if
``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and the program
sets nothing; otherwise the cache is ONE fixed directory inside the
checkout. The path is part of the cache key's lookup, so it is never derived
from a temp name, pid or time — a directory that moves never hits — and
nothing is written outside the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure(subdir: str = "") -> str:
    """Apply the rule above; returns the directory in effect. Call before
    the first compile (importing jax first is fine). ``subdir`` keeps a
    caller's entries apart under the fixed directory (the tests: CPU
    programs keyed by the host's CPU features); the env var ignores it."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    directory = str(DEFAULT_DIR / subdir)
    jax.config.update("jax_compilation_cache_dir", directory)
    return directory

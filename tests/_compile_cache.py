"""Where the test suite's persistent XLA compile cache lives.

Used by ``tests/conftest.py`` AND the standalone multihost / fleet workers
so every process — pytest, xdist workers, spawned ``jax.distributed``
subprocesses — lands in the same directory. The same rule as the program's
own ``zero_transformer_tpu.utils.compile_cache``: if
``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and nothing here
sets a path; otherwise the cache is one FIXED directory (never a temp name,
pid or time — a directory that moves never hits).

The fixed path carries a host-CPU fingerprint subdirectory: cached CPU AOT
entries are only valid for the feature set they were compiled with, and a
stale entry from another host has shown up as SIGILL'd xdist workers and a
SIGABRT mid-compile (2026-07-31, twice). The fingerprint is a function of
the host's CPU flags, not of time or pid.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
FIXED_BASE = "/tmp/zero_transformer_tpu_jax_cache"


def cpu_fingerprint() -> str:
    try:
        import zlib  # crc32: no crypto, so FIPS-enabled hosts can't reject it

        with open("/proc/cpuinfo") as f:
            for line in f:
                # x86 spells it "flags", aarch64 "Features"
                if line.startswith(("flags", "Features")):
                    return f"{zlib.crc32(line.encode()):08x}"
    except OSError:
        pass
    return "nofp"


def resolve_cache_dir() -> str:
    """The directory in effect: the env var's, else the fixed one."""
    return os.environ.get(ENV_VAR) or os.path.join(FIXED_BASE, cpu_fingerprint())


def configure(jax_module) -> str:
    """Point jax's persistent compile cache at the fixed directory unless
    the env var already placed it; returns the directory in effect."""
    cache_dir = resolve_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax_module.config.update("jax_compilation_cache_dir", cache_dir)
        # say so to whatever this process starts or calls in-process: the
        # entry points' own ``compile_cache.configure()`` and the children
        # tests spawn then find the env var set and leave the cache here
        os.environ[ENV_VAR] = cache_dir
    # default min compile-time threshold (1s) would skip most test programs;
    # cache everything — CPU test compiles of 2+ seconds are the norm here
    jax_module.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax_module.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir

"""Where the test suite's persistent XLA compile cache lives.

Used by ``tests/conftest.py`` AND the standalone multihost / fleet workers
so every process — pytest, xdist workers, spawned ``jax.distributed``
subprocesses — lands in the same directory. The directory is placed by the
program's own helper (``zero_transformer_tpu.utils.compile_cache``): if
``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and nothing here
sets a path; otherwise it is ``<checkout>/.jax_cache/tests/<fingerprint>`` —
inside the checkout, so two checkouts on one machine never share entries.

The host-CPU fingerprint subdirectory: cached CPU AOT entries are only valid
for the feature set they were compiled with, and a stale entry from another
host has shown up as SIGILL'd xdist workers and a SIGABRT mid-compile
(2026-07-31, twice). The fingerprint is a function of the host's CPU flags,
not of time or pid.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from zero_transformer_tpu.utils import compile_cache  # noqa: E402

ENV_VAR = compile_cache.ENV_VAR


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


def configure(jax_module) -> str:
    """Place the cache (the program's rule, under ``tests/<fingerprint>``)
    and have jax cache every program; returns the directory in effect."""
    cache_dir = compile_cache.configure(os.path.join("tests", cpu_fingerprint()))
    # say so to whatever this process starts or calls in-process: the entry
    # points' own ``compile_cache.configure()`` and the children tests spawn
    # then find the env var set and leave the cache here
    os.environ[ENV_VAR] = cache_dir
    # default min compile-time threshold (1s) would skip most test programs;
    # cache everything — CPU test compiles of 2+ seconds are the norm here
    jax_module.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax_module.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir

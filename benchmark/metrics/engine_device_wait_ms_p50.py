"""Median ``device_wait`` span (the engine thread inside the tick's one
``jax.device_get``) of the ticks that ran a ``decode_step`` from the
window's open to the end of its drain: the same ticks as
``engine_host_ms_per_tick``, whose reader picks them."""
from benchmark import arith, harness


def read(ctx):
    ticks = harness.load_reader("engine_host_ms_per_tick").decode_ticks(ctx)
    if not ticks:
        return None
    return arith.percentile([wait * 1e3 for _, wait in ticks], 50)

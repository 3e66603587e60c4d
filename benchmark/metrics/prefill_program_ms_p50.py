"""Median duration on the device of the engine's chunk-prefill program
(paged or slab): its events on the capture's ``XLA Modules`` line."""
from benchmark import host_trace

PROGRAM = r"^jit__(paged_)?chunk_prefill_impl\b"


def read(ctx):
    return host_trace.program_ms_p50(ctx, PROGRAM)

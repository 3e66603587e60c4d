"""Median duration on the device of the engine's decode program (the fused
step, or its speculative or de-fused variants): its events on the capture's
``XLA Modules`` line, which names every launched program after its jitted
function."""
from benchmark import host_trace

PROGRAM = r"^jit__(fused_step|spec_step|forward_only)_impl\b"


def read(ctx):
    return host_trace.program_ms_p50(ctx, PROGRAM)

"""95th percentile, over ALL requests due in the window, of first token (as
the collecting thread received it) minus DUE time, on the benchmark's own
clock; a request that never produced a token counts to the end of the
drain. With the hundred or so requests a window holds it swings by a tenth
from run to run (where a request falls in the engine's tick decides it), so
it stands among the per-layer metrics, with no bound."""
from benchmark import arith


def read(ctx):
    return arith.percentile(ctx["ttft_ms"], 95) if ctx.get("ttft_ms") else None

"""95th percentile of the per-request ``queue`` spans (submit to admission
into a slot) of the requests submitted in the window."""
from benchmark import arith


def read(ctx):
    spans = ctx.get("spans")
    if not spans:
        return None
    t0 = ctx["t0"]
    waits = [(e - s) * 1e3 for _, track, name, s, e, _ in spans
             if name == "queue" and track != "engine" and s >= t0]
    return arith.percentile(waits, 95) if waits else None

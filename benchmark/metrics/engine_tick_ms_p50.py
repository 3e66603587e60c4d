"""Median duration of the engine's ``tick`` spans that ran a ``decode_step``,
from the window's open to the end of its drain."""
from benchmark import arith


def read(ctx):
    spans = ctx.get("spans")
    if not spans:
        return None
    t0, t1 = ctx["t0"], ctx["t_end"]
    decoded = {a["tick"] for _, track, name, s, e, a in spans
               if track == "engine" and name == "decode_step" and a and s >= t0 and e <= t1}
    ticks = [(e - s) * 1e3 for _, track, name, s, e, a in spans
             if track == "engine" and name == "tick" and a and a.get("tick") in decoded
             and s >= t0 and e <= t1]
    return arith.percentile(ticks, 50) if ticks else None

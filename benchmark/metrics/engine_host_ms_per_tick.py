"""Host time of a decode tick: median, over the ticks that ran a
``decode_step`` from the window's open to the end of its drain, of the
engine's ``tick`` span minus its ``device_wait`` span (the one stretch of a
tick in which the engine thread only waits for the device). What is left is
scheduling, the prefill and decode dispatches, emit, and the tick's self
time. Ticks with no ``device_wait`` span (a program older than PR 24) give
nothing to read."""
from benchmark import arith


def decode_ticks(ctx):
    """[(tick_seconds, device_wait_seconds)] of the window's decode ticks,
    or None where the program records no ``device_wait``."""
    spans = ctx.get("spans")
    if not spans:
        return None
    t0, t1 = ctx["t0"], ctx["t_end"]
    per: dict = {}
    for _, track, name, s, e, a in spans:
        if track == "engine" and a and "tick" in a and s >= t0 and e <= t1 \
                and name in ("tick", "decode_step", "device_wait"):
            per.setdefault(a["tick"], {})[name] = e - s
    out = [(p["tick"], p["device_wait"]) for p in per.values()
           if {"tick", "decode_step", "device_wait"} <= set(p)]
    return out or None


def read(ctx):
    ticks = decode_ticks(ctx)
    if not ticks:
        return None
    return arith.percentile([(tick - wait) * 1e3 for tick, wait in ticks], 50)

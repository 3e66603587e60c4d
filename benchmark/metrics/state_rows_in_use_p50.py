"""Median share of the engine's slots that hold a request's recurrent state
(decoding or mid-prefill: the ``state_rows_in_use`` attribute of the
engine's ``decode_step`` spans, the same count as the gauge of that name)
over the slots, from the window's open to the end of its drain. A request's
state does not grow with its length, so the slots, not the pages, are what
such a model runs out of. A program whose spans carry no such attribute
gives nothing to read."""
from benchmark import arith


def read(ctx):
    spans, engine = ctx.get("spans"), (ctx.get("mix") or {}).get("engine") or {}
    slots = engine.get("n_slots", 0)
    if not spans or slots <= 0 or "t0" not in ctx:
        return None
    t0, t1 = ctx["t0"], ctx["t_end"]
    rows = [a["state_rows_in_use"] for _, track, name, s, e, a in spans
            if track == "engine" and name == "decode_step" and a
            and "state_rows_in_use" in a and s >= t0 and e <= t1]
    return 100.0 * arith.percentile(rows, 50) / slots if rows else None

"""Whole model step's share of the chip's peak while the engine works: the
forward operations of every prompt and output token processed from the
window's open to the end of its drain (2N + 4*L*H*D*context each), over the
summed duration of the engine's ``tick`` spans that did work in that time
times the table's peak FLOP/s."""
from benchmark import arith


def read(ctx):
    spans, records = ctx.get("spans"), ctx.get("records")
    if not spans or not records:
        return None
    t0, t1 = ctx["t0"], ctx["t_end"]
    work_s = sum(e - s for _, track, name, s, e, _ in spans
                 if track == "engine" and name == "tick" and s >= t0 and e <= t1)
    if work_s <= 0:
        return None
    flops = 0.0
    for r in records:
        n = len(r["prompt"]) + max(len(r["tokens"]) - 1, 0) if r["token_times"] else 0
        # positions 0..n-1 each attend over their own prefix: n tokens over
        # (n + 1) / 2 cached positions on average
        flops += n * arith.forward_flops(
            ctx["active_params"], ctx["attention_flops_per_position"], (n + 1) / 2.0)
    return 100.0 * flops / (work_s * ctx["peak"]["flops_per_s"] * ctx["chips"])

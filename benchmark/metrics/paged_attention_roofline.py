"""The paged decode kernel's share of its roofline: the least time the chip
could take for the kernel's calls over the time its events (named
``paged_attention`` by the kernel's own ``name=``) took in the trace.

The least time is per call: q.k and p.v over the LIVE cached positions of
the rows decoding in that tick, K and V read once (``arith.
paged_decode_ops_bytes``). It is bytes-bound at these shapes. The live
context of each tick inside the capture comes from the requests' own
records (prompt length plus tokens delivered so far)."""
from benchmark import arith, trace

KERNEL = r"^%?paged_attention[.\d]* = "


def read(ctx):
    tr, spans, records = ctx.get("trace"), ctx.get("spans"), ctx.get("records")
    if not tr or not spans or not records or "traced" not in ctx:
        return None
    seconds, calls = trace.kernel_seconds(tr, KERNEL)
    if calls == 0 or seconds <= 0:
        return None
    ta, tb = ctx["traced"]
    m = ctx["model"]
    least = []
    for _, track, name, s, e, _ in spans:
        if track != "engine" or name != "decode_step" or s < ta or e > tb:
            continue
        rows, live = 0, 0
        for r in records:
            if r["prefill_done_at"] is None or not (r["prefill_done_at"] <= s < (r["finished_at"] or s + 1)):
                continue
            rows += 1
            live += len(r["prompt"]) + sum(1 for t in r["token_times"] if t < s)
        if rows:
            ops, byts = arith.paged_decode_ops_bytes(live, rows, m["n_heads"], m["head_dim"])
            least.append(arith.roofline_seconds(ops, byts, ctx["peak"])[0])
    if not least:
        return None
    return 100.0 * (sum(least) / len(least)) / (seconds / calls)

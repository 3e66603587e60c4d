"""The KDA decode state-update kernel's share of its roofline: the least
time the chip could take for the kernel's calls over the time its events
(named ``kda_state_update`` by the kernel's own ``name=``) took in the trace.

The least time is per call (one KDA layer of one decode tick), from the
family's own count (``kimi_linear.state_update_ops_bytes``): the float32
state of the rows that DECODE in that tick read and written once, the
step's small operands beside it. It is bytes-bound. The rows of each traced
tick come from the requests' own records. The kernel visits the decoding
rows alone, and an implementation that also moved the others would read no
less, so the share cannot pass 100%. A model with no KDA layers, or a
program whose spans carry no ``state_rows``, gives nothing to read."""
from benchmark import arith, kda_ticks, trace

KERNEL = r"^%?kda_state_update[.\d]* = "


def read(ctx):
    ticks = kda_ticks.decode_ticks(ctx, live=False)
    if not ctx.get("trace") or not ticks:
        return None
    seconds, calls = trace.kernel_seconds(ctx["trace"], KERNEL)
    if calls == 0 or seconds <= 0:
        return None
    fam = kda_ticks.family()
    least = [arith.roofline_seconds(
        *fam.state_update_ops_bytes(ctx["model"], t["rows"]), ctx["peak"])[0]
        for t in ticks if t["rows"]]
    if not least:
        return None
    return 100.0 * (sum(least) / len(least)) / (seconds / calls)

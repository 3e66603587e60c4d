"""The latent decode kernel's share of its roofline: the least time the chip
could take for the kernel's calls over the time its events (named
``latent_paged_attention`` by the kernel's own ``name=``) took in the trace.

The least time is per call (one layer of one decode tick), from the family's
own count (``latent_decode_ops_bytes``): every head's scores against, and sum
over, the LIVE cached positions of the rows decoding in that tick, each
cached row read once for all heads at its UNPADDED width. The live context
of each traced tick comes from the requests' own records, as
``paged_attention_roofline`` finds it."""
from benchmark import arith, moe_ticks, trace

KERNEL = r"^%?latent_paged_attention[.\d]* = "


def read(ctx):
    ticks = moe_ticks.decode_ticks(ctx)
    if not ctx.get("trace") or not ticks:
        return None
    seconds, calls = trace.kernel_seconds(ctx["trace"], KERNEL)
    if calls == 0 or seconds <= 0:
        return None
    fam = moe_ticks.family()
    least = [arith.roofline_seconds(
        *fam.latent_decode_ops_bytes(ctx["model"], t["live"], t["rows"]), ctx["peak"])[0]
        for t in ticks if t["rows"]]
    if not least:
        return None
    return 100.0 * (sum(least) / len(least)) / (seconds / calls)

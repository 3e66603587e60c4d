"""The decode program's share of the chip's memory bandwidth, for the routed
family: the least time the bytes a decode tick MUST read could take, over the
time the traced decode programs took on the device (``decode_bandwidth_
share``'s twin; that reader is tied to the looped family).

The bytes are the family's own count (``decode_read_bytes``): every layer's
attention, the dense layer's MLP, each routed layer's router and shared
expert, the experts the tick's counters say were TOUCHED (``experts_touched``
of the ``decode_step`` spans), the head, and the live latent rows."""
from benchmark import moe_ticks


def read(ctx):
    ticks = moe_ticks.decode_ticks(ctx)
    programs = moe_ticks.programs(ctx)
    if not ticks or not programs:
        return None
    fam = moe_ticks.family()
    least = [fam.decode_read_bytes(ctx["model"], t["touched"], t["live"])
             / ctx["peak"]["bytes_per_s"] for t in ticks]
    took = [e - s for s, e in programs]
    return 100.0 * (sum(least) / len(least)) / (sum(took) / len(took))

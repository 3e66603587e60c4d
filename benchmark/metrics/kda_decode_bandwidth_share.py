"""The decode program's share of the chip's memory bandwidth, for the
Kimi-Linear family: the least time the bytes a decode tick MUST move could
take, over the time the traced decode programs took on the device (the twin
of ``ssm_decode_bandwidth_share`` and ``moe_decode_bandwidth_share``, which
name their own families in their files).

The bytes are the family's own count (``kimi_linear.decode_read_bytes``):
every layer's mixer, the dense layer's MLP, each routed layer's router and
shared expert, the held experts the tick's counters say were TOUCHED
(``experts_touched`` of the ``decode_step`` spans), the head, the decoding
rows' KDA state in and out (it changes whole, every tick), and the live
latent rows of the MLA layers. Rows and context of each traced tick come
from the requests' own records."""
from benchmark import host_trace, kda_ticks


def read(ctx):
    ticks = kda_ticks.decode_ticks(ctx)
    loaded = host_trace.load() if ctx.get("trace") and ticks else None
    took = host_trace.program_durations(loaded, kda_ticks.DECODE_PROGRAM) if loaded else []
    if not ticks or not took:
        return None
    fam = kda_ticks.family()
    least = [fam.decode_read_bytes(ctx["model"], t["rows"], t["touched"], t["live"])
             / ctx["peak"]["bytes_per_s"] for t in ticks]
    return 100.0 * (sum(least) / len(least)) / (sum(took) / len(took))

"""The decode program's share of the chip's memory bandwidth, for the hybrid
state-space family: the least time the bytes a decode tick MUST move could
take, over the time the traced decode programs took on the device
(``decode_bandwidth_share``'s third twin; that reader names the looped
family in its own file, ``moe_decode_bandwidth_share`` the routed one).

The bytes are the family's own count (``decode_read_bytes``): every matrix
once, the recurrent state of the rows that DECODE in the tick in and out
(it changes whole, every tick), and the live cached K/V positions of the
layers that attend. Rows and context of each traced tick come from the
requests' own records."""
from benchmark import host_trace, ssm_ticks


def read(ctx):
    ticks = ssm_ticks.decode_ticks(ctx)
    loaded = host_trace.load() if ctx.get("trace") and ticks else None
    took = host_trace.program_durations(loaded, ssm_ticks.DECODE_PROGRAM) if loaded else []
    if not ticks or not took:
        return None
    fam = ssm_ticks.family()
    least = [fam.decode_read_bytes(ctx["model"], t["rows"], t["live"])
             / ctx["peak"]["bytes_per_s"] for t in ticks]
    return 100.0 * (sum(least) / len(least)) / (sum(took) / len(took))

"""The decode state-update kernel's share of its roofline: the least time
the chip could take for the kernel's calls over the time its events (named
``ssm_state_update`` by the kernel's own ``name=``) took in the trace.

The least time is per call (one mamba layer of one decode tick), from the
family's own count (``state_update_ops_bytes``): the float32 state of the
rows that DECODE in that tick read and written once, the step's small
operands beside it. It is bytes-bound. The rows of each traced tick come
from the requests' own records, as ``paged_attention_roofline`` finds them.
The kernel also carries the rows that do not decode through (unchanged), and
their bytes are not counted: an implementation that skips them reads no
less, so the share cannot pass 100%. A program whose spans carry no
``state_rows`` (one that keeps no recurrent state) gives nothing to read."""
from benchmark import arith, ssm_ticks, trace

KERNEL = r"^%?ssm_state_update[.\d]* = "


def read(ctx):
    ticks = ssm_ticks.decode_ticks(ctx, live=False)
    if not ctx.get("trace") or not ticks:
        return None
    seconds, calls = trace.kernel_seconds(ctx["trace"], KERNEL)
    if calls == 0 or seconds <= 0:
        return None
    fam = ssm_ticks.family()
    least = [arith.roofline_seconds(
        *fam.state_update_ops_bytes(ctx["model"], t["rows"]), ctx["peak"])[0]
        for t in ticks if t["rows"]]
    if not least:
        return None
    return 100.0 * (sum(least) / len(least)) / (seconds / calls)

"""The routed experts' matmuls' share of their roofline in the decode tick:
the least time the chip could take for them over the time their events took
inside the capture's decode programs.

The events are the grouped matmuls of the ``moe_experts`` scope
(``jax.lax.ragged_dot``: the trace names them ``ragged-dot``). The least
time of a tick is the family's own count (``expert_ops_bytes``) over the
routed layers: three matmuls a (row, choice) pair against the chip's peak, or
the weights of the experts the tick's COUNTERS say were touched (the
``decode_step`` span's ``experts_touched``) plus the rows in and out against
its bandwidth, whichever is longer. No implementation reads a touched expert
less than once a layer a tick, so the share cannot pass 100%."""
from benchmark import arith, moe_ticks

EXPERT_MATMUL = r"ragged-dot"


def read(ctx):
    ticks = moe_ticks.decode_ticks(ctx)
    programs = moe_ticks.programs(ctx)
    if not ticks or not programs:
        return None
    seconds, events = moe_ticks.op_seconds(ctx, EXPERT_MATMUL, programs)
    if events == 0 or seconds <= 0:
        return None
    fam, m = moe_ticks.family(), ctx["model"]
    layers = moe_ticks.routed_layers(m)
    least = []
    for t in ticks:
        # the counters are sums over the routed layers, and so is the count
        ops, byts = fam.expert_ops_bytes(m, t["rows"] * layers, t["touched"])
        least.append(arith.roofline_seconds(ops, byts, ctx["peak"])[0])
    return 100.0 * (sum(least) / len(least)) / (seconds / len(programs))

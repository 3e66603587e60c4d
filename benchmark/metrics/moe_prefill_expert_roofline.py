"""The routed experts' matmuls' share of their roofline in the chunk-prefill
program, which is most of what ``itl_p95_ms`` reads: the least time the chip
could take for them over the time their events (``ragged-dot``) took inside
the capture's chunk-prefill programs.

The program computes ``n_slots x prefill_chunk`` rows whoever prefills, so a
layer's grouped matmuls are handed that many rows x ``moe_top_k`` pairs. The
least time of a program is the family's own count (``expert_ops_bytes``)
over the routed layers: three matmuls a pair against the chip's peak, or the
weights of the experts the program's own COUNT says took any row (the
``decode_step`` span's ``prefill_experts_touched``, fetched with the tick's
tokens) plus the rows in and out against its bandwidth, whichever is longer.
No implementation reads a touched expert less than once a layer a program,
so the share cannot pass 100%."""
from benchmark import arith, moe_ticks

EXPERT_MATMUL = r"ragged-dot"


def read(ctx):
    touched = moe_ticks.prefill_touched(ctx)
    programs = moe_ticks.programs(ctx, moe_ticks.PREFILL_PROGRAM)
    if not touched or not programs:
        return None
    seconds, events = moe_ticks.op_seconds(ctx, EXPERT_MATMUL, programs)
    if events == 0 or seconds <= 0:
        return None
    fam, m, engine = moe_ticks.family(), ctx["model"], ctx["mix"]["engine"]
    rows = engine["n_slots"] * engine["prefill_chunk"] * moe_ticks.routed_layers(m)
    least = [arith.roofline_seconds(*fam.expert_ops_bytes(m, rows, t), ctx["peak"])[0]
             for t in touched]
    return 100.0 * (sum(least) / len(least)) / (seconds / len(programs))

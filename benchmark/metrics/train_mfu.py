"""Whole step's share of the chip's peak: the operations the forward and
backward passes require per token (6N + 12*L*d*T, recomputation not
counted) times tokens per second per chip, over the table's peak FLOP/s."""
from benchmark import arith


def read(ctx):
    if ctx.get("tokens_per_s_chip") is None:
        return None
    flops = arith.train_flops_per_token(ctx["active_params"], ctx["attention_flops_per_position"],
                                        ctx["seq_len"])
    return 100.0 * ctx["tokens_per_s_chip"] * flops / ctx["peak"]["flops_per_s"]

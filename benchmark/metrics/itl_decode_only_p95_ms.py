"""95th percentile of the inter-token gaps NO chunk tick made, of those that
ended inside the readers' window (``chunk_ticks.split_gaps``): what
``itl_p95_ms`` would read if no request were admitted while others decode,
the decode tick's own tail."""
from benchmark import arith, chunk_ticks


def read(ctx):
    split = chunk_ticks.split_gaps(ctx)
    if split is None or not split[1]:
        return None
    return arith.percentile(split[1], 95)

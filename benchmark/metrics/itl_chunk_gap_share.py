"""Share of the inter-token gaps (the gaps ``itl_p95_ms`` takes its
percentile of; those that ended inside the readers' window) that a chunk tick
made (``chunk_ticks.split_gaps``). Over 5% the 95th percentile IS a chunk
tick; near 5% the cell sits on the percentile's edge and reads one kind of
tick or the other seed by seed. The counts go to standard error."""
from benchmark import chunk_ticks


def read(ctx):
    split = chunk_ticks.split_gaps(ctx)
    if split is None:
        return None
    made, others = split
    chunk_ticks.report_gaps(ctx, made, others)
    return 100.0 * len(made) / (len(made) + len(others))

"""Median of the ``install`` spans of the readers' window
(``chunk_ticks.window``): ``_install_completed`` moving a request whose prompt
has prefilled into the decode set (or shipping it), after the chunk program's
dispatch and inside the tick's ``prefill`` span."""
from benchmark import arith, chunk_ticks


def read(ctx):
    took = chunk_ticks.installs(ctx)
    return arith.percentile([t * 1e3 for t in took], 50) if took else None

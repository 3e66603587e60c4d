"""Median duration of the CHUNK ticks: the engine's ``tick`` spans that note
``chunks`` >= 1 and ran a ``decode_step`` (``chunk_ticks.py``), the ticks
``itl_p95_ms`` reads wherever more than a twentieth of the gaps hold one.
From the ring, over the readers' window (``chunk_ticks.window``: up to the
capture's opening). The tick's account (host and waiting halves, ``prefill``
and ``install``) goes to standard error."""
from benchmark import arith, chunk_ticks


def read(ctx):
    ticks = chunk_ticks.ring_ticks(ctx)
    if not ticks:
        return None
    chunk_ticks.report_ring(ctx, ticks)
    return arith.percentile([(p["end"] - p["start"]) * 1e3 for p in ticks], 50)

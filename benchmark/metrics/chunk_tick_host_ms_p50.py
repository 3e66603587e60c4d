"""Host time of a chunk tick: median, over the chunk ticks of the readers'
window (``chunk_ticks.py``), of the ``tick`` span minus its ``device_wait`` and
every ``chunk_wait``, the two stretches in which the engine thread only
waits for the device. What is left is scheduling, the chunk program's
dispatch, the install, the decode dispatch, emit and the tick's self time."""
from benchmark import arith, chunk_ticks


def read(ctx):
    ticks = chunk_ticks.ring_ticks(ctx)
    if not ticks:
        return None
    return arith.percentile([(p["end"] - p["start"] - p["wait_s"]) * 1e3 for p in ticks], 50)

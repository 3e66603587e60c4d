"""Median, over the capture's chunk ticks (``chunk_ticks.py``), of the time
the device ran nothing inside the tick's ``engine/tick`` annotation: the
device's idle gaps, shifted by the capture's own host-device offset, clipped
to the tick. The mean a tick, split by the innermost engine span over each
instant (``host_trace.label_gaps``), goes to standard error."""
import sys

from benchmark import arith, chunk_ticks, host_trace


def read(ctx):
    found = chunk_ticks.capture(ctx)
    if found is None:
        return None
    loaded, offset_s, ticks = found
    per_tick = chunk_ticks.idle_in_ticks(loaded, offset_s, ticks, ctx.get("chips"))
    labelled = host_trace.label_gaps([g for gaps in per_tick for g in gaps],
                                     host_trace.annotations(loaded, "engine/"), offset_s)
    print(f"device idle in the capture's {len(ticks)} chunk ticks, mean ms a tick by span: "
          + ", ".join(f"{name} {sec * 1e3 / len(ticks):.3f}" for name, sec in
                      sorted(labelled["by_name"].items(), key=lambda kv: -kv[1])),
          file=sys.stderr)
    return arith.percentile([sum(b - a for a, b in gaps) * 1e3 for gaps in per_tick], 50)

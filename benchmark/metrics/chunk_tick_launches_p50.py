"""Median number of programs the device is handed in a chunk tick: the
capture's ``XLA Modules`` events (any name) that start, shifted by the
capture's own host-device offset, inside an ``engine/tick`` annotation that
holds an ``engine/prefill_chunk`` and an ``engine/decode_step``
(``chunk_ticks.py``). The programs of the median such tick go to standard
error by name."""
import sys

from benchmark import arith, chunk_ticks


def read(ctx):
    found = chunk_ticks.capture(ctx)
    if found is None:
        return None
    per_tick = chunk_ticks.launches(*found)
    counts = [sum(mine.values()) for mine in per_tick]
    median = sorted(per_tick, key=lambda mine: sum(mine.values()))[len(per_tick) // 2]
    print(f"launches in the capture's {len(per_tick)} chunk ticks: min {min(counts)}, max "
          f"{max(counts)}; the median tick's: " + ", ".join(
              f"{name} {n}" for name, n in sorted(median.items(), key=lambda kv: -kv[1])),
          file=sys.stderr)
    return arith.percentile(counts, 50)

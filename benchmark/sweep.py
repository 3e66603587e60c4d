"""The one rate sweep that finds a serving cell's knee, on the chip:

    python benchmark/sweep.py --workload <name> --rates 4,8,12,16 --seconds 20

Each rate is one run of the cell's own driver in this process, with only
``rate_per_s`` changed. The knee is the highest swept rate whose queue does
not grow through the window: few requests still waiting for a first token at
the close, and the second half's time to first token no worse than the
first's. The cell's traffic file then fixes 0.8 of it. The builder may
depart from 0.8, downwards only, where six seeds at 0.8 show an end-to-end
metric spreading by more than half its bound and a lower rate does not (a
window that closes on the trace's longest request, a percentile that sits
between two kinds of tick), and must then say so, with both readings, in the
traffic file's ``rate_note``, as both serving mixes do. One JSON line a rate.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=11)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    devices = harness.require_chips(cell["chips"])
    harness.configure_jax()
    driver = harness.load_driver(cell["traffic"]["kind"])
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell["traffic"]["rate_per_s"] = rate
        seed = args.seed + i  # the schedule is the mix's own; the seed gives ids and weights
        res = driver.run(cell, devices, seed=seed, seconds=args.seconds, trace=False)
        print(json.dumps({
            "rate_per_s": rate, "seed": seed, "compared": res["compared"], "attempted": res["attempted"], "failed": res["failed"],
            "backlog_at_close": res["backlog_at_close"], "drain_s": res["drain_s"],
            "ttft_p50_halves_ms": res["ttft_p50_halves_ms"],
            "lateness_ms_max": res["lateness_ms_max"], "correct": res["correct"],
            "ttft_p95_ms": res["ttft_p95_ms"],
            **{k: v["value"] for k, v in res["metrics"].items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

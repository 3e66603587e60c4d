"""Reads the numbers the limits of ``correct`` are set from, on the chip at
a cell's own size, several seeds in one process:

    python benchmark/calibrate.py --workload <name> --seeds 1,2,3 --controls 3

For each seed the program's readings against the reference (the lower
reading is their largest); for the first ``--controls`` seeds also the
control (the reference in the next precision down, put in the program's
place) and the planted faults: what the driver of the cell's traffic
``kind`` yields from its ``calibrate``. One JSON line per seed on standard
output. The benchmark's own runs never call this.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    devices = harness.require_chips(cell["chips"])
    harness.configure_jax()
    seeds = [int(s) for s in args.seeds.split(",")]
    driver = harness.load_driver(cell["traffic"]["kind"])
    for line in driver.calibrate(cell, devices, seeds, args.controls, args.seconds):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""One cell, once:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic and metrics are found by the names in
``BENCHMARK.json``; the traffic file's ``kind`` picks the driver. The last
line of standard output is the result. Any backend but a TPU is an error.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    driver = harness.load_driver(cell["traffic"]["kind"])
    devices = harness.require_chips(cell["chips"])
    harness.configure_jax()
    result = driver.run(cell, devices, seed=args.seed, seconds=args.seconds,
                        trace=bool(args.trace))
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""One training-fleet worker process (spawned by train_coordinator.py).

Joins the coordinator, bootstraps state (fresh init / peer state /
verified snapshot restore), then loops: compute owned shards, push grads,
apply the released fold. Prints ``LOSS step=N <loss>`` per applied step
and ``WORKER_OK`` on clean shutdown — the same contract as
tests/multihost_resume_worker.py, so test harnesses parse one format.

Chaos faults are injected per-process via ``--chaos kind@step[:duration]``
(e.g. ``--chaos sigkill@7``, ``--chaos slow_worker@3:0.4``): the process
being killed/frozen/partitioned is THIS one, which is the point.
"""
from __future__ import annotations

import argparse
import os
import sys

# CPU-only, and set before jax is imported: one CPU device per worker —
# each worker is one DP rank, the multi-"host" topology is the process fleet
# itself, and several jax processes on one host cannot share a chip
os.environ["JAX_PLATFORMS"] = "cpu"

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "tests"))

import jax  # noqa: E402

try:
    # shared persistent compile cache (tests/_compile_cache.py): N workers
    # compile the SAME tiny program — without this, N identical XLA compiles
    import _compile_cache  # noqa: E402

    _compile_cache.configure(jax)
except ImportError as e:
    print(f"fleet-worker: no compile cache ({e}); cold compiles", file=sys.stderr)

from zero_transformer_tpu.resilience.chaos import ChaosMonkey, Fault  # noqa: E402
from zero_transformer_tpu.training.fleet import FleetWorker  # noqa: E402


def parse_fault(spec: str) -> Fault:
    """``kind@step[:duration]`` -> Fault (duration in seconds for the
    time-windowed kinds, defaulting to 1)."""
    kind, sep, rest = spec.partition("@")
    if not sep:
        raise ValueError(f"bad --chaos spec {spec!r} (want kind@step[:dur])")
    step_s, _, dur = rest.partition(":")
    return Fault(
        kind=kind, step=int(step_s), duration=float(dur) if dur else 1
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--coordinator", required=True, help="coordinator base URL")
    ap.add_argument("--id", required=True, help="worker id (e.g. w0)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument(
        "--resume", action="store_true",
        help="restore the newest verified snapshot before joining",
    )
    ap.add_argument(
        "--chaos", action="append", default=[],
        metavar="KIND@STEP[:DUR]", help="inject a process-level fault",
    )
    ap.add_argument("--hb-interval", type=float, default=0.2)
    args = ap.parse_args(argv)

    chaos = (
        ChaosMonkey([parse_fault(s) for s in args.chaos])
        if args.chaos else None
    )
    worker = FleetWorker(
        args.coordinator,
        args.id,
        ckpt_dir=args.ckpt_dir,
        resume=args.resume,
        chaos=chaos,
        hb_interval_s=args.hb_interval,
    )
    applied = worker.run()
    print(f"WORKER_OK applied={applied}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

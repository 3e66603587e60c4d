"""Cluster health check: verify every device/host still participates in
collectives.

Modern-API re-think of the reference's manual smoke script (reference
``src/utils/pod_test.py:1-34``: global + local ``pmap(psum)``, with the
documented failure mode of hung processes needing ``pkill``). Here:

- the global check is a jitted ``psum`` under ``shard_map`` over a 1-D mesh of
  every device — the same ICI/DCN all-reduce a training step issues;
- the local check sums over this process's devices only;
- both verify the *value* (device count), so a silently dropped participant
  is caught, and a wall-clock timeout turns a hang into a diagnosis instead
  of a mystery (``pod_check(timeout)`` runs the collective in a worker thread).

Usage: ``python -m zero_transformer_tpu.utils.pod_check [--timeout 60]``.
"""
from __future__ import annotations

import argparse
import functools
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map


def _allreduce_count(devices) -> float:
    """psum of ones over a 1-D mesh of ``devices`` — returns the device count
    as seen by the collective (must equal ``len(devices)``)."""
    mesh = Mesh(np.asarray(devices), ("all",))

    @functools.partial(
        shard_map, mesh=mesh, in_specs=P("all"), out_specs=P(), check_vma=False
    )
    def count(x):
        return jax.lax.psum(jnp.sum(x), "all")

    ones = jax.device_put(
        jnp.ones((len(devices),), jnp.float32),
        jax.sharding.NamedSharding(mesh, P("all")),
    )
    return float(count(ones))


def allreduce_bandwidth(
    mib: float = 64.0,
    reps: int = 5,
    devices=None,
    verbose: bool = True,
    timeout: float = 300.0,
) -> dict:
    """Time a training-shaped psum (f32, ``mib`` MiB per device) over every
    device and report achieved algorithmic bandwidth.

    The number a slow pod run needs first: whether the gradient all-reduce
    is getting ICI-class or DCN-class throughput. Algorithmic bandwidth =
    buffer bytes / wall time per all-reduce (the ring-transfer bytes are
    2(n-1)/n of that, reported too). One device short-circuits in HBM, so
    the single-chip figure is a sanity ceiling, not an interconnect number.

    Runs under the same hang-to-diagnosis guard as ``pod_check``: a link
    that passes the few-bytes health psum but wedges on a real-sized
    transfer returns ``{"error": "timeout..."}`` instead of hanging.
    """
    import time

    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    mesh = Mesh(np.asarray(devices), ("all",))
    per_dev = int(mib * (1 << 20) // 4)
    result: dict = {}

    def run() -> None:
        @functools.partial(
            shard_map, mesh=mesh, in_specs=P("all"), out_specs=P("all"),
            check_vma=False,
        )
        def reduce(x):
            return jax.lax.psum(x, "all")

        sharding = jax.sharding.NamedSharding(mesh, P("all"))
        # build the buffer already sharded — an eager jnp.ones would
        # materialize the full n x per_dev global array on one device
        # first, which OOMs exactly the large pods this diagnoses
        x = jax.jit(
            lambda: jnp.ones((n * per_dev,), jnp.float32),
            out_shardings=sharding,
        )()
        reduced = jax.jit(reduce)
        ssum = jax.jit(jnp.sum)  # ONE warmed barrier fn, reused in the
        np.asarray(ssum(reduced(x)))  # timed window (cold jit in the window
        t0 = time.perf_counter()  # would deflate the reported bandwidth)
        for _ in range(reps):
            out = reduced(x)
        np.asarray(ssum(out))  # sync barrier (scalar fetch)
        dt = (time.perf_counter() - t0) / reps

        bytes_per_dev = per_dev * 4
        algo_gbs = bytes_per_dev / dt / 1e9
        ring_gbs = algo_gbs * (2 * (n - 1) / n) if n > 1 else algo_gbs
        result.update(
            devices=n,
            buffer_mib_per_device=round(bytes_per_dev / (1 << 20), 1),
            seconds_per_allreduce=round(dt, 6),
            algo_bandwidth_GBps=round(algo_gbs, 2),
            ring_transfer_GBps=round(ring_gbs, 2),
        )

    def guarded() -> None:
        try:
            run()
        except Exception as e:  # reported distinctly from a timeout below
            result["raised"] = e

    worker = threading.Thread(target=guarded, daemon=True)
    worker.start()
    worker.join(timeout)
    if "raised" in result:
        msg = f"bandwidth measurement raised: {result['raised']!r}"
        if verbose:
            print(f"UNHEALTHY: {msg}")
        return {"error": msg}
    if worker.is_alive() or "devices" not in result:
        msg = (
            f"timeout: {mib} MiB allreduce did not complete within "
            f"{timeout:.0f}s — the health psum passed but a real-sized "
            "transfer wedged (suspect one marginal link)"
        )
        if verbose:
            print(f"UNHEALTHY: {msg}")
        return {"error": msg}
    if verbose:
        print(
            f"allreduce {result['buffer_mib_per_device']} MiB/device over "
            f"{n} devices: {result['seconds_per_allreduce']*1e3:.2f} ms -> "
            f"{result['algo_bandwidth_GBps']:.1f} GB/s algorithmic"
            + (f" ({result['ring_transfer_GBps']:.1f} GB/s ring transfer)"
               if n > 1 else " (single device: HBM sanity ceiling)")
        )
    return result


def pod_check(timeout: float = 60.0, verbose: bool = True) -> bool:
    """Run global + local collective checks. Returns True when healthy."""

    result: dict = {}

    def run() -> None:
        try:
            result["global"] = _allreduce_count(jax.devices())
            result["local"] = _allreduce_count(jax.local_devices())
        except Exception as e:  # reported distinctly from a timeout below
            result["error"] = e

    # A hung collective cannot be cancelled from Python: the worker must be a
    # daemon thread so it never blocks process exit (a ThreadPoolExecutor's
    # __exit__ would join it forever — the exact hang this check diagnoses).
    worker = threading.Thread(target=run, daemon=True)
    worker.start()
    worker.join(timeout)
    if "error" in result:
        if verbose:
            print(f"UNHEALTHY: collective raised: {result['error']!r}")
        return False
    if worker.is_alive() or "local" not in result:
        if verbose:
            print(
                f"UNHEALTHY: collective did not complete within {timeout:.0f}s "
                "— a host or device is hung (the reference's documented "
                "remedy: kill stray processes on every host and restart, "
                "pod_test.py:1-6)"
            )
        return False
    global_count, local_count = result["global"], result["local"]

    ok = global_count == jax.device_count() and local_count == jax.local_device_count()
    if verbose:
        state = "healthy" if ok else "UNHEALTHY"
        print(
            f"{state}: global allreduce saw {global_count:.0f}/{jax.device_count()} "
            f"devices; local saw {local_count:.0f}/{jax.local_device_count()} "
            f"(process {jax.process_index()}/{jax.process_count()})"
        )
    return ok


def main(argv=None) -> None:
    p = argparse.ArgumentParser(prog="zero_transformer_tpu.utils.pod_check")
    p.add_argument("--timeout", type=float, default=60.0)
    p.add_argument("--bandwidth", type=float, default=0.0, metavar="MiB",
                   help="after the health check, time a MiB-per-device psum "
                        "and report achieved all-reduce bandwidth (the "
                        "ICI-vs-DCN diagnosis for a slow pod run); shares "
                        "--timeout with the health leg")
    args = p.parse_args(argv)
    healthy = pod_check(args.timeout)
    if healthy and args.bandwidth > 0:
        if "error" in allreduce_bandwidth(
            mib=args.bandwidth, timeout=args.timeout
        ):
            healthy = False  # wedged mid-transfer: exit through the same
            # hard-exit path (the daemon worker still holds the collective)
    if not healthy:
        # The daemon worker may still hold the hung collective; a normal exit
        # would wait on runtime teardown. Flush and hard-exit with the
        # diagnosis already printed.
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(1)
    raise SystemExit(0)


if __name__ == "__main__":
    main()

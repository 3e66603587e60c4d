"""The host side of a ``jax.profiler`` trace, joined to the device side.

``trace.py`` reads what the device did (``XLA Ops``). This module reads, from
the same ``.xplane.pb``:

- every event of every line of ``/host:CPU`` by name: the runtime's own
  (``DoEnqueueProgram`` ...) and the program's live spans, which are
  ``jax.profiler.TraceAnnotation``s named ``"<track>/<name>"`` with their
  attributes as stats (``zero_transformer_tpu/obs/spans.py``);
- the ``XLA Modules`` line of each device: one event per launched program,
  named after its jitted function, with a ``run_id``;
- the OFFSET between the two timelines. Inside one file the device's and the
  host's clocks are not aligned to better than a millisecond, so nothing here
  lays a host span over a device gap before it is found: no device program
  can start before the host enqueued its ``run_id``, and none can end after
  the host saw it complete, so

      offset in [ max(enqueue - device start), min(complete - device end) ]

  over all launches that can be paired, where host time = device time +
  offset. ``complete`` is the runtime's completion callback of the same
  ``run_id`` where the capture holds one (host tracer level 2), and the end
  of a host span that waits for the device (``waits``: the program's own
  ``device_wait`` / ``device_sync``) for every program enqueued before that
  span ENDED: the thread that waits is the one that launches, the runtime
  may enqueue a dispatched program only after the wait has begun (on the
  chip the enqueue runs on a thread of its own), the wait returns only when
  the last program launched has finished, and a device runs its programs
  in order. The midpoint is used and the half-width is the error;
- ``label_gaps``: each idle gap of the device, shifted by that offset, split
  among the host spans that cover it, innermost first.
"""
from __future__ import annotations

import bisect
import re
import sys
from pathlib import Path

from benchmark import arith, trace

HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"
COMPLETE = "CompleteCallbacks"
# the program's spans that hold the host until the device has finished
WAITS = ("engine/device_wait", "train/device_sync")
UNATTRIBUTED = "unattributed"
ANNOTATION = re.compile(r"^[\w.\-]+/[\w.\-]+$")  # "<track>/<name>"

_CACHE: dict = {}


def newest_xplane(root=None) -> Path | None:
    """The newest ``.xplane.pb`` under ``<checkout>/.bench_out/*/profile``
    (a run wipes its cell's directory when it starts), or None."""
    root = Path(root) if root is not None else Path(__file__).resolve().parent.parent / ".bench_out"
    found = list(root.glob("*/profile/**/*.xplane.pb"))
    return max(found, key=lambda p: p.stat().st_mtime) if found else None


def load(path=None) -> dict | None:
    """{"host": {name: [(start_s, end_s, stats)]},
        "modules": {ordinal: [(name, start_s, end_s, run_id)]},
        "ops": {ordinal: [(start_s, end_s)]}, "extent": (t0, t1)}
    of one capture (the newest of this checkout's runs when no path is
    given), read once per file; None where there is no capture."""
    path = Path(path) if path is not None else newest_xplane()
    if path is None or not path.exists():
        return None
    key = (str(path), path.stat().st_mtime_ns)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = _read(path)
    return _CACHE[key]


def _read(path: Path) -> dict:
    from jax.profiler import ProfileData

    host: dict = {}
    modules: dict = {}
    ops: dict = {}
    lo = hi = None
    for plane in ProfileData.from_file(str(path)).planes:
        device = trace.DEVICE_PLANE.match(plane.name)
        on_host = plane.name == HOST_PLANE
        for line in plane.lines:
            is_ops = device is not None and line.name == trace.OPS_LINE
            is_modules = device is not None and line.name == MODULES_LINE
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                lo = s if lo is None or s < lo else lo
                hi = e if hi is None or e > hi else hi
                if is_ops:
                    ops.setdefault(int(device.group(1)), []).append((s, e))
                elif is_modules:
                    modules.setdefault(int(device.group(1)), []).append(
                        (ev.name, s, e, dict(ev.stats).get("run_id")))
                elif on_host:
                    host.setdefault(ev.name, []).append((s, e, dict(ev.stats)))
    for evs in host.values():
        evs.sort(key=lambda x: x[0])
    return {"host": host, "modules": modules, "ops": ops, "extent": (lo, hi)}


def annotations(loaded: dict, prefix: str = "") -> dict:
    """The program's live spans in the capture: the host events named
    ``"<track>/<name>"`` (those whose name starts with ``prefix``)."""
    return {name: evs for name, evs in loaded["host"].items()
            if ANNOTATION.match(name) and name.startswith(prefix)}


def program_durations(loaded: dict, pattern: str) -> list:
    """Seconds of every ``XLA Modules`` event whose name matches."""
    rx = re.compile(pattern)
    return [e - s for evs in loaded["modules"].values()
            for name, s, e, _ in evs if rx.search(name)]


def programs(loaded: dict) -> dict:
    """{jitted function: [launches, seconds on the device]} of a capture's
    ``XLA Modules`` line, so that a traced run says how many ticks it holds."""
    out: dict = {}
    for evs in loaded["modules"].values():
        for name, s, e, _ in evs:
            entry = out.setdefault(name.split("(")[0], [0, 0.0])
            entry[0] += 1
            entry[1] += e - s
    return out


def program_ms_p50(ctx: dict, pattern: str) -> float | None:
    """Median milliseconds of the matching programs in a traced run's
    capture: what the ``*_program_ms_p50`` readers return. None in an
    untraced run, without a capture, or where no program matches."""
    loaded = load() if ctx.get("trace") else None
    durations = program_durations(loaded, pattern) if loaded else []
    return arith.percentile([d * 1e3 for d in durations], 50) if durations else None


def offset(loaded: dict, waits=WAITS) -> dict | None:
    """{"offset_s", "error_s", "low_s", "high_s", "pairs", "upper_from",
    "consistent"}: host time = device time + offset, ``pairs`` the launches
    found on both sides by ``run_id``; None where none could be bounded from
    both sides (the readers that need it then return None: they never
    attribute uncorrected)."""
    host = loaded["host"]
    enqueued = {}
    for s, _, stats in host.get(ENQUEUE, ()):
        if stats.get("run_id") is not None:
            enqueued.setdefault(stats["run_id"], s)
    completed = {}
    for s, _, stats in host.get(COMPLETE, ()):
        if stats.get("run_id") is not None:
            completed.setdefault(stats["run_id"], s)
    launches = sorted((enqueued[rid], rid, s, e) for evs in loaded["modules"].values()
                      for _, s, e, rid in evs if rid in enqueued)
    if not launches:
        return None
    low = max(h - s for h, _, s, _ in launches)
    uppers = [(completed[rid] - e, COMPLETE) for _, rid, _, e in launches if rid in completed]
    enqueue_times = [h for h, *_ in launches]
    for name in waits:
        for _, w1, _ in host.get(name, ()):
            # the last program enqueued before the wait returned ended before it did
            i = bisect.bisect_left(enqueue_times, w1) - 1
            if i >= 0:
                uppers.append((w1 - launches[i][3], name))
    if not uppers:
        return None
    high, upper_from = min(uppers)
    return {"offset_s": (low + high) / 2.0, "error_s": abs(high - low) / 2.0,
            "low_s": low, "high_s": high, "pairs": len(launches),
            "upper_from": upper_from, "consistent": high >= low}


def idle_gaps(loaded: dict, chips: int | None = None) -> list:
    """[(start_s, end_s)] on the device's clock: where no operation ran on a
    device inside the capture's extent, as ``trace.reduce`` counts idle time
    (every device used contributes its own gaps)."""
    t0, t1 = loaded["extent"]
    gaps = []
    used = sorted(d for d, evs in loaded["ops"].items() if evs)
    for d in used[:chips]:
        end = t0
        for s, e in sorted(loaded["ops"][d]):
            if s > end:
                gaps.append((end, s))
            end = max(end, e)
        if t1 > end:
            gaps.append((end, t1))
    return gaps


def label_gaps(gaps, spans: dict, offset_s: float) -> dict:
    """Split each device idle gap among the host spans that cover it.

    ``gaps`` are [(start_s, end_s)] on the device's clock, ``spans`` is
    {name: [(start_s, end_s, ...)]} on the host's, ``offset_s`` shifts the
    first onto the second. Every instant of a gap goes to the INNERMOST span
    that covers it (the one that started last; of two that started together,
    the shorter), the rest to ``unattributed``. Idle time under a span that
    waits for the device (``device_wait``) is launch and completion latency,
    not host work: it keeps that span's name and counts as attributed.

    Returns {"idle_s", "by_name": {name: seconds}, "gaps": [(start_s,
    seconds, {name: seconds})]} with ``gaps`` longest first, on the device's
    clock."""
    flat = sorted((s, e, name) for name, evs in spans.items() for s, e, *_ in evs if e > s)
    starts = [s for s, _, _ in flat]
    longest = max((e - s for s, e, _ in flat), default=0.0)
    by_name: dict = {}
    out = []
    idle = 0.0
    for g0, g1 in gaps:
        h0, h1 = g0 + offset_s, g1 + offset_s
        if h1 <= h0:
            continue
        idle += h1 - h0
        lo = bisect.bisect_left(starts, h0 - longest)
        hi = bisect.bisect_left(starts, h1)
        cover = [(s, e, name) for s, e, name in flat[lo:hi] if e > h0]
        cuts = sorted({h0, h1, *(t for s, e, _ in cover for t in (s, e) if h0 < t < h1)})
        mine: dict = {}
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2.0
            inner = [(s, -(e - s), name) for s, e, name in cover if s <= mid < e]
            name = max(inner)[2] if inner else UNATTRIBUTED
            mine[name] = mine.get(name, 0.0) + (b - a)
        for name, sec in mine.items():
            by_name[name] = by_name.get(name, 0.0) + sec
        out.append((g0, g1 - g0, mine))
    out.sort(key=lambda g: -g[1])
    return {"idle_s": idle, "by_name": by_name, "gaps": out}


def attributed_percent(labelled: dict) -> float | None:
    if labelled["idle_s"] <= 0:
        return None
    return 100.0 * (1.0 - labelled["by_name"].get(UNATTRIBUTED, 0.0) / labelled["idle_s"])


def report(found: dict | None, labelled: dict | None, out=None, top: int = 5) -> None:
    """The offset with its error and the split of idle time by span name, on
    standard error."""
    out = sys.stderr if out is None else out
    if found is None:
        print("host trace: the capture holds no span of the program, or no launch "
              "that both clocks saw: host-device offset unknown, idle gaps not attributed",
              file=out)
        return
    print(f"host-device offset {found['offset_s'] * 1e3:+.4f} ms +- {found['error_s'] * 1e3:.4f} ms "
          f"(interval [{found['low_s'] * 1e3:.4f}, {found['high_s'] * 1e3:.4f}] ms over "
          f"{found['pairs']} launches; upper bound from {found['upper_from']})"
          + ("" if found["consistent"] else " INCONSISTENT: the bounds cross"), file=out)
    if labelled is None:
        return
    idle = labelled["idle_s"]
    print(f"device idle {idle:.6f} s by host span:", file=out)
    for name, sec in sorted(labelled["by_name"].items(), key=lambda kv: -kv[1]):
        print(f"  {name:<28s} {sec:.6f} s  {100.0 * sec / idle if idle else 0.0:6.2f}%", file=out)
    for at, dur, mine in labelled["gaps"][:top]:
        split = ", ".join(f"{n} {s * 1e3:.3f}" for n, s in sorted(mine.items(), key=lambda kv: -kv[1]))
        print(f"  gap of {dur * 1e3:.3f} ms at {at:.6f} s (device clock): {split} ms", file=out)

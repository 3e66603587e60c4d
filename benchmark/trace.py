"""From a ``jax.profiler`` trace (``*.xplane.pb``) to numbers.

Device planes are those named ``/device:TPU:<n>``; on each, the line
``XLA Ops`` holds one event per executed operation (a ``while`` event spans
the operations of its body, on the same line). Busy time is the union of
that line's intervals; an operation's own time is its duration minus what
its nested operations cover. Everything is averaged over the device planes
used. The traced window is the extent of all events of all planes, host
threads included: what lay between ``start_trace`` and ``stop_trace``.
"""
from __future__ import annotations

import glob
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


def short_name(name: str) -> str:
    """The trace names an operation by its whole HLO line; keep the
    instruction's name and the shape it makes."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    shape = rest.split(" ", 1)[0] if not rest.startswith("(") else "(tuple)"
    return f"{head.lstrip('%')} {shape}"[:120]


class capture:
    """``with capture(dir):`` traces what runs inside, Python tracing off:
    the default options trace every Python call, which slows a host-bound
    loop to a fraction of its speed and fills the file with host events."""

    def __init__(self, directory):
        self.directory = str(directory)

    def __enter__(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()
        return False


def union_seconds(intervals) -> float:
    """Total length covered by [(start, end), ...] in the units given."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def self_times(events) -> list:
    """[(name, start, end)] -> [(name, self_seconds)]: each event's duration
    minus the time its directly nested events cover."""
    evs = sorted(events, key=lambda e: (e[1], -(e[2] - e[1])))
    out, stack = [], []  # stack of [name, start, end, child_cover]

    def close(until):
        while stack and stack[-1][2] <= until:
            name, s, e, cover = stack.pop()
            out.append((name, (e - s) - cover))
            if stack:
                stack[-1][3] += e - s

    for name, s, e in evs:
        close(s)
        stack.append([name, s, e, 0.0])
    close(float("inf"))
    return out


def read_planes(path) -> dict:
    """{"devices": {ordinal: [(name, start_s, end_s)]}, "extent": (t0, t1)}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, lo, hi = {}, None, None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            keep = m is not None and line.name == OPS_LINE
            evs = []
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                lo = s if lo is None or s < lo else lo
                hi = e if hi is None or e > hi else hi
                if keep:
                    evs.append((ev.name, s, e))
            if keep:
                devices.setdefault(int(m.group(1)), []).extend(evs)
    return {"devices": devices, "extent": (lo, hi)}


def reduce(planes: dict, chips: int) -> dict:
    devices = {k: v for k, v in planes["devices"].items() if v}
    if not devices:
        raise SystemExit("the trace holds no operation that ran on a device")
    used = sorted(devices)[:chips]
    t0, t1 = planes["extent"]
    busy = sum(union_seconds([(s, e) for _, s, e in devices[d]]) for d in used) / len(used)
    by_name: dict = {}
    for d in used:
        for name, sec in self_times(devices[d]):
            key = short_name(name)
            by_name[key] = by_name.get(key, 0.0) + sec / len(used)
    gaps = []
    for d in used:
        iv = sorted((s, e) for _, s, e in devices[d])
        end = t0
        for s, e in iv:
            if s > end:
                gaps.append((s - end, end - t0))
            end = max(end, e)
        if t1 > end:
            gaps.append((t1 - end, end - t0))
    gaps.sort(reverse=True)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy,
        "window_s": t1 - t0,
        "events": {d: devices[d] for d in used},
        "self_by_name": by_name,
        "breakdown": {
            "device_ops": [[n, s] for n, s in top],
            "idle_gaps": [[f"device idle at +{at:.4f}s of the trace", g]
                          for g, at in gaps[:10]],
        },
    }


def idle_percent(reduced) -> float | None:
    """100 * (1 - busy / traced window); None where there is no trace."""
    if not reduced or reduced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def find_xplane(directory) -> Path:
    found = sorted(glob.glob(str(Path(directory) / "**" / "*.xplane.pb"), recursive=True))
    if not found:
        raise SystemExit(f"no .xplane.pb under {directory}")
    return Path(found[-1])


def reduce_dir(directory, chips: int) -> dict:
    return reduce(read_planes(find_xplane(directory)), chips)


def kernel_seconds(reduced: dict, pattern: str) -> tuple:
    """(summed seconds per device, calls per device) of events whose name
    matches ``pattern``."""
    rx = re.compile(pattern)
    total, calls = 0.0, 0
    for evs in reduced["events"].values():
        for name, s, e in evs:
            if rx.search(name):
                total += e - s
                calls += 1
    n = len(reduced["events"])
    return total / n, calls / n


def summarize(path, top: int = 40) -> str:
    """What a trace holds, for reading one by hand."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    lines = []
    for plane in data.planes:
        lines.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            tot: dict = {}
            for ev in evs:
                t = tot.setdefault(ev.name, [0, 0.0])
                t[0] += 1
                t[1] += ev.duration_ns * 1e-9
            lines.append(f"  line {line.name!r}: {len(evs)} events")
            if DEVICE_PLANE.match(plane.name):
                for name, (c, sec) in sorted(tot.items(), key=lambda kv: -kv[1][1])[:top]:
                    lines.append(f"    {sec:10.6f}s x{c:<6d} {name}")
                if line.name == OPS_LINE and evs:
                    for ev in evs[:3]:
                        lines.append(f"    stats of {ev.name}: {dict(ev.stats)}")
    return "\n".join(lines)


if __name__ == "__main__":
    import sys

    print(summarize(find_xplane(sys.argv[1])))

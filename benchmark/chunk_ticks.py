"""What the chunk tick's per-layer readers share: the ticks that ran a chunk
program AND a decode step (the ticks ``itl_p95_ms`` reads), found in the ring
from the window's open to the capture's and in the capture over its few
seconds, and the requests' inter-token gaps split by whether such a tick made
them.

A CHUNK TICK is, in the ring, an engine ``tick`` span inside the readers'
window (``window``) that notes ``chunks`` >= 1 and whose tick ran a
``decode_step`` (a prefill-only tick makes no gap between tokens: nothing
decodes while one runs); in a capture, an ``engine/tick`` annotation that holds an
``engine/prefill_chunk`` and an ``engine/decode_step`` annotation (an
annotation takes its stats at entry, so ``chunks`` is on the ring's side
only). Every function gives nothing (an empty list, None) on a program whose
``tick`` spans carry no ``chunks`` (the parent of PR 35) or without a capture.
"""
from __future__ import annotations

import bisect
import collections
import sys

from benchmark import arith, host_trace

_TICK_SPANS = ("tick", "decode_step", "prefill", "device_wait", "chunk_wait")


def window(ctx: dict) -> tuple:
    """(t0, t1) the ring's readers look at: from the benchmark window's open
    to the instant the run's capture OPENED (to the end of the drain where
    there was none). Once a capture has closed, ``stop_trace`` works through
    it beside the engine for the rest of the window, and every host span of
    the tick thread runs 1.5 to 1.9 times as long (my chip runs, PR 35: all
    four serving cells; the chat cell's ``install`` 7.2 ms before, 12.0 after):
    a median over the whole window would mix the two and fall on either."""
    opened = (ctx.get("traced") or (None,))[0]
    return ctx["t0"], ctx["t_end"] if opened is None else opened


def ring_ticks(ctx: dict) -> list:
    """The window's chunk ticks, oldest first: ``{"tick", "start", "end",
    "chunks", "decoded": the end of its decode_step, "wait_s": device_wait
    plus every chunk_wait, "prefill_s"}``, seconds on the engine's clock."""
    spans = ctx.get("spans")
    if not spans:
        return []
    t0, t1 = window(ctx)
    per: dict = {}
    for _, track, name, s, e, a in spans:
        if track != "engine" or name not in _TICK_SPANS or not a or "tick" not in a \
                or s < t0 or e > t1:
            continue
        p = per.setdefault(a["tick"], {"wait_s": 0.0})
        if name == "tick":
            p.update(tick=a["tick"], start=s, end=e, chunks=a.get("chunks", 0))
        elif name == "decode_step":
            p["decoded"] = e
        elif name == "prefill":
            p["prefill_s"] = e - s
        else:
            p["wait_s"] += e - s
    return sorted((p for p in per.values() if p.get("chunks") and "decoded" in p),
                  key=lambda p: p["start"])


def installs(ctx: dict) -> list:
    """Seconds of every ``install`` span of the readers' window."""
    if not ctx.get("spans"):
        return []
    t0, t1 = window(ctx)
    return [e - s for _, track, name, s, e, _ in ctx["spans"]
            if track == "engine" and name == "install" and s >= t0 and e <= t1]


def report_ring(ctx: dict, ticks: list) -> None:
    """The account of ``ticks`` (``ring_ticks(ctx)``) on standard error: the
    tick, its host and waiting halves, its ``prefill`` span and the
    ``install`` spans, each a median over the readers' window."""
    def p50(values):
        return arith.percentile([v * 1e3 for v in values], 50) if values else float("nan")

    by_chunks = collections.Counter(p["chunks"] for p in ticks)
    took = installs(ctx)
    print(f"chunk ticks in the first {window(ctx)[1] - ctx['t0']:.2f} s of the window "
          f"{len(ticks)} (chunks a tick: "
          + ", ".join(f"{k} x {n}" for k, n in sorted(by_chunks.items()))
          + f"): tick p50 {p50([p['end'] - p['start'] for p in ticks]):.3f} ms = host p50 "
          f"{p50([p['end'] - p['start'] - p['wait_s'] for p in ticks]):.3f} + device_wait and "
          f"chunk_wait p50 {p50([p['wait_s'] for p in ticks]):.3f}; prefill span p50 "
          f"{p50([p['prefill_s'] for p in ticks if 'prefill_s' in p]):.3f}; "
          f"install p50 {p50(took):.3f} over {len(took)} spans", file=sys.stderr)


# ------------------------------------------------------------------ the gaps


def gap_instants(records) -> list:
    """[(a, b)] of every gap between consecutive output tokens of every
    request: ``arith.gaps_ms``'s gaps, in its order, as their two instants."""
    return [(a, b) for r in records for a, b in zip(r["token_times"], r["token_times"][1:])]


def split_gaps(ctx: dict) -> tuple | None:
    """(gaps a chunk tick made, the others), each in ms: the gaps
    ``itl_p95_ms`` takes its percentile of that ENDED inside the readers'
    window (``window``: in a traced run the later ones are the profiler's as
    much as the engine's). A chunk tick made the gap in which its
    ``decode_step`` ENDED: the next token of every decoding request is
    emitted right after that instant and stamped by the consumer no earlier,
    while the consumer's stamp of the token before trails its own emit by the
    consumer's polling interval at most (the tick's START often precedes that
    stamp: picked by it, the chat cell's "chunk" gaps are 4.4 ms at the median,
    a plain tick's, and the others' 99th percentile is 14.8; picked by the
    decode_step's end, which comes a device program later, 14.5, the chunk
    tick's own 14.4, and 5.6: my chip run, PR 35). One clock: the consumer's
    ``time.monotonic`` is the engine's. None without records, chunk ticks or a
    gap inside the window."""
    records = ctx.get("records")
    ticks = ring_ticks(ctx) if records else []
    if not ticks:
        return None
    closes = window(ctx)[1]
    decoded = sorted(p["decoded"] for p in ticks)
    made, others = [], []
    for a, b in gap_instants(records):
        if b > closes:
            continue
        holds = bisect.bisect_right(decoded, a) < bisect.bisect_right(decoded, b)
        (made if holds else others).append((b - a) * 1e3)
    return (made, others) if made or others else None


def report_gaps(ctx: dict, made: list, others: list) -> None:
    """The counts on standard error: every gap of the run against
    ``arith.gaps_ms``'s, those inside the readers' window, and the two kinds
    (``split_gaps(ctx)``)."""
    every = arith.gaps_ms([r["token_times"] for r in ctx["records"]])

    def tail(gaps):
        return (f" (p50 {arith.percentile(gaps, 50):.3f} ms, p95 "
                f"{arith.percentile(gaps, 95):.3f})") if gaps else ""

    print(f"inter-token gaps {len(gap_instants(ctx['records']))} (arith.gaps_ms: {len(every)}; "
          f"p95 {arith.percentile(every, 95):.3f} ms), {len(made) + len(others)} of them in the "
          f"first {window(ctx)[1] - ctx['t0']:.2f} s of the window: {len(made)} hold a chunk tick"
          f"{tail(made)}, {len(others)} hold none{tail(others)}", file=sys.stderr)


# --------------------------------------------------------------- the capture


def capture_ticks(loaded: dict) -> list:
    """[(start_s, end_s, stats)] on the host's clock of the capture's chunk
    ticks: the ``engine/tick`` annotations that hold an
    ``engine/prefill_chunk`` and an ``engine/decode_step``."""
    host = loaded["host"]

    def holds(name, s, e):
        evs = host.get(name, ())
        i = bisect.bisect_left(evs, s, key=lambda ev: ev[0])
        return i < len(evs) and evs[i][1] <= e

    return [(s, e, stats) for s, e, stats in host.get("engine/tick", ())
            if holds("engine/prefill_chunk", s, e) and holds("engine/decode_step", s, e)]


def capture(ctx: dict) -> tuple | None:
    """(loaded, offset_s, chunk ticks) of a traced run's capture; None in an
    untraced run, on a program whose ring holds no chunk tick, without a
    capture, without a launch both clocks saw (the readers never lay a device
    event over a host span uncorrected) or where the capture holds no chunk
    tick."""
    loaded = host_trace.load() if ctx.get("trace") and ring_ticks(ctx) else None
    if not loaded:
        return None
    found = host_trace.offset(loaded)
    ticks = capture_ticks(loaded) if found else []
    return (loaded, found["offset_s"], ticks) if ticks else None


def launches(loaded: dict, offset_s: float, ticks: list) -> list:
    """Per chunk tick, {program: launches}: the ``XLA Modules`` events (any
    name) that START inside the tick, device clock shifted onto the host's."""
    starts = sorted((s + offset_s, name.split("(")[0])
                    for evs in loaded["modules"].values() for name, s, _, _ in evs)
    out = []
    for t0, t1, _ in ticks:
        mine: dict = {}
        lo = bisect.bisect_left(starts, (t0, ""))
        for at, name in starts[lo:]:
            if at >= t1:
                break
            mine[name] = mine.get(name, 0) + 1
        out.append(mine)
    return out


def idle_in_ticks(loaded: dict, offset_s: float, ticks: list, chips=None) -> list:
    """Per chunk tick, [(start_s, end_s)] on the DEVICE's clock: the device's
    idle gaps clipped to the tick's ``engine/tick`` annotation."""
    gaps = host_trace.idle_gaps(loaded, chips)
    out = []
    for t0, t1, _ in ticks:
        d0, d1 = t0 - offset_s, t1 - offset_s
        out.append([(max(a, d0), min(b, d1)) for a, b in gaps if a < d1 and b > d0])
    return out

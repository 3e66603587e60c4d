"""Span tracing: a low-overhead, ring-buffered timeline of named intervals.

The serving request lifecycle (admit → queue → prefill chunks → decode
ticks → detok → finish/shed/expire) and the training step loop (data fetch,
dispatch, device sync, checkpoint save, replica audit) both record into one
``Tracer``. Design constraints, in order:

- **hot-path cost**: recording a span is ONE ``deque.append`` of a fixed
  7-tuple — no string formatting, no dict merging, no IO. The ring is
  bounded (``capacity``), so a long-lived server holds the most recent
  window and the overflow is *counted*, never silently unbounded.
- **clock**: timestamps are caller-supplied floats on ONE monotonic clock
  (the engine's ``now()`` / ``time.monotonic``). Spans recorded at finish
  time from timestamps captured earlier (``add``) are first-class — the
  request lifecycle is emitted as one batch when the request reaches a
  terminal state, so the hot emit path allocates nothing per token.
- **two clocks at once**: a LIVE host phase (``with tracer.span(...)``: the
  engine's tick tree, the trainer's loop) is also a
  ``jax.profiler.TraceAnnotation`` named ``"<track>/<name>"`` for its
  life, so an open profiler capture holds the same span on the profiler's
  clock, beside the device's programs. With no capture open that costs
  about half a microsecond a span.
- **export**: ``chrome_trace()`` renders Perfetto/Chrome ``traceEvents``
  JSON (complete "X" events, one ``tid`` per track); ``write_jsonl``
  appends newly finished spans to a ``spans.jsonl`` beside
  ``metrics.jsonl`` (incremental — safe to call at every log point).

Tracks are correlation keys: ``"engine"`` / ``"train"`` for the scheduler
timelines, the request id for per-request span trees. A request's span tree
is well-nested by construction: the root span is ``[submitted, finished]``
and every phase span is a sub-interval of it.
"""
from __future__ import annotations

import itertools
import json
import logging
import threading
import time
from collections import deque
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

log = logging.getLogger("zero_transformer_tpu")

# span record layout (fixed tuple, index-addressed):
# (seq, track, name, t0_s, t1_s, attrs_or_None)
SEQ, TRACK, NAME, T0, T1, ATTRS = range(6)


class LiveSpan:
    """One ``Tracer.span``. ``note`` adds attributes learned inside the body
    to the ring record (the annotation took its stats at entry); ``discard``
    leaves the ring unwritten — an annotation once entered cannot be
    withdrawn, so the profiler's side keeps it."""

    __slots__ = ("_tracer", "_name", "_track", "_attrs", "_t0", "_ann", "_keep")

    def __init__(self, tracer: "Tracer", name: str, track: str, attrs: dict):
        self._tracer, self._name, self._track = tracer, name, track
        self._attrs = attrs
        self._keep = True

    # graftlint: hot-path
    def __enter__(self) -> "LiveSpan":
        self._ann = TraceAnnotation(f"{self._track}/{self._name}", **self._attrs)
        self._ann.__enter__()
        self._t0 = self._tracer.clock()
        return self

    # graftlint: hot-path
    def __exit__(self, *exc) -> bool:
        t1 = self._tracer.clock()
        self._ann.__exit__(*exc)
        if self._keep:
            self._tracer.add(self._name, self._track, self._t0, t1,
                             self._attrs or None)
        return False

    def note(self, **attrs) -> None:
        self._attrs.update(attrs)

    def discard(self) -> None:
        self._keep = False


class _NullSpan:
    """What a disabled tracer hands out: no ring record, no annotation."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **attrs):
        pass

    def discard(self):
        pass


_NULL_SPAN = _NullSpan()


class Tracer:
    """Bounded span ring. Thread-safe: ``deque.append`` is atomic under the
    GIL and readers snapshot with ``list(ring)``; no lock on the hot path."""

    def __init__(
        self,
        enabled: bool = True,
        capacity: int = 8192,
        clock=time.monotonic,
    ):
        self.enabled = enabled
        self.clock = clock
        self._ring: deque = deque(maxlen=capacity)
        self._seq = itertools.count()
        self._added = 0
        self._capacity = capacity
        # warn ONCE at first overflow: the drop count is exported on
        # /metrics (obs_spans_dropped), but an operator reading logs must
        # also learn that trace truncation started — silently losing the
        # head of every trace is the failure mode this flag makes loud
        self._overflow_warned = False
        # JSONL cursor: seq of the last span already flushed to disk
        self._flushed_seq = -1

    def __len__(self) -> int:
        return len(self._ring)

    @property
    def dropped(self) -> int:
        """Spans pushed out of the ring by overflow (bounded-buffer honesty:
        a trace that silently lost its head must say so)."""
        return max(0, self._added - len(self._ring))

    # ------------------------------------------------------------- recording

    # graftlint: hot-path
    def add(
        self,
        name: str,
        track: str,
        t0: float,
        t1: float,
        attrs: Optional[Dict[str, Any]] = None,
    ) -> None:
        """Record a finished span [t0, t1] (seconds on this tracer's clock)."""
        if not self.enabled:
            return
        self._ring.append((next(self._seq), track, name, t0, t1, attrs))
        self._added += 1
        if self._added > self._capacity and not self._overflow_warned:
            self._overflow_warned = True
            log.warning(
                "tracer: span ring overflowed (capacity %d) — oldest spans "
                "are being dropped; obs_spans_dropped counts them on "
                "/metrics", self._capacity,
            )

    def instant(self, name: str, track: str, t: Optional[float] = None,
                attrs: Optional[Dict[str, Any]] = None) -> None:
        """Zero-duration marker event (renders as a thin slice)."""
        if not self.enabled:
            return
        ts = self.clock() if t is None else t
        self.add(name, track, ts, ts, attrs)

    def span(self, name: str, track: str = "main", **attrs) -> "LiveSpan":
        """A live host phase, on two clocks at once: ``with tracer.span(...)``
        appends the fixed tuple to the ring on this tracer's clock when the
        body ends, and for the body's life holds a
        ``jax.profiler.TraceAnnotation`` named ``"<track>/<name>"`` (``attrs``
        as its stats), so that an open ``jax.profiler`` capture shows the
        same span on the host plane, on the profiler's clock, on the thread
        that ran it. The span is recorded even when the body raises — a
        fault's timeline is the one that matters most. Disabled: neither."""
        if not self.enabled:
            return _NULL_SPAN
        return LiveSpan(self, name, track, attrs)

    # --------------------------------------------------------------- reading

    def spans(self) -> List[tuple]:
        """Snapshot of the current ring, oldest first."""
        return list(self._ring)

    def by_track(self, track: str) -> List[tuple]:
        return [s for s in self._ring if s[TRACK] == track]

    def track_dicts(self, track: Optional[str] = None,
                    tail: Optional[int] = None) -> List[Dict[str, Any]]:
        """Spans as JSON-ready dicts (the /admin/spans wire shape and the
        stitching input): one track's spans, or the whole ring tail."""
        spans = self.by_track(track) if track is not None else self.spans()
        if tail is not None:
            spans = spans[-tail:]
        return [span_dict(s) for s in spans]

    # --------------------------------------------------------------- export

    def chrome_trace(self, tail: Optional[int] = None) -> Dict[str, Any]:
        """Perfetto/Chrome ``traceEvents`` document (complete events).

        ``ts``/``dur`` are microseconds; each track gets its own ``tid``
        plus a ``thread_name`` metadata event so Perfetto labels the rows.
        """
        spans = self.spans()
        if tail is not None:
            spans = spans[-tail:]
        tids: Dict[str, int] = {}
        events: List[dict] = []
        for s in spans:
            tid = tids.get(s[TRACK])
            if tid is None:
                tid = tids[s[TRACK]] = len(tids) + 1
            ev = {
                "ph": "X",
                "name": s[NAME],
                "cat": s[TRACK],
                "ts": s[T0] * 1e6,
                "dur": max(0.0, (s[T1] - s[T0]) * 1e6),
                "pid": 0,
                "tid": tid,
            }
            if s[ATTRS]:
                ev["args"] = s[ATTRS]
            events.append(ev)
        meta = [
            {
                "ph": "M",
                "name": "thread_name",
                "pid": 0,
                "tid": tid,
                "args": {"name": track},
            }
            for track, tid in tids.items()
        ]
        return {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": {"dropped_spans": self.dropped},
        }

    def write_chrome_trace(self, path, tail: Optional[int] = None) -> str:
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(self.chrome_trace(tail=tail)) + "\n")
        return str(path)

    def write_jsonl(self, path) -> int:
        """Append spans not yet flushed (incremental: call at log points).
        Returns the number of spans written."""
        fresh = [s for s in self.spans() if s[SEQ] > self._flushed_seq]
        if not fresh:
            return 0
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        with open(path, "a") as f:
            for s in fresh:
                f.write(json.dumps({
                    "track": s[TRACK],
                    "name": s[NAME],
                    "t0": s[T0],
                    "t1": s[T1],
                    "dur_ms": round((s[T1] - s[T0]) * 1e3, 6),
                    "attrs": s[ATTRS],
                }) + "\n")
        self._flushed_seq = fresh[-1][SEQ]
        return len(fresh)


def span_dict(s: tuple) -> Dict[str, Any]:
    """One ring record as the cross-process wire/stitch shape."""
    return {
        "track": s[TRACK], "name": s[NAME], "t0": s[T0], "t1": s[T1],
        "attrs": s[ATTRS],
    }


def span_tree(spans: List[tuple], track: str) -> Dict[str, Any]:
    """Assemble one track's spans into {root, children} where root is the
    span named ``request`` (the full lifetime) — the shape the span-parity
    tests assert on. Returns {} when the track has no root."""
    mine = [s for s in spans if s[TRACK] == track]
    root = next((s for s in mine if s[NAME] == "request"), None)
    if root is None:
        return {}
    children = [s for s in mine if s is not root]
    return {"root": root, "children": children}


def coverage_fraction(tree: Dict[str, Any]) -> float:
    """Fraction of the root span's wall time covered by the union of its
    child spans (the >=95% acceptance bar). Children are clamped into the
    root interval and overlaps merged, so the result is in [0, 1]."""
    root = tree.get("root")
    if root is None:
        return 0.0
    r0, r1 = root[T0], root[T1]
    if r1 <= r0:
        return 1.0  # zero-length lifetime (e.g. rejected at submit)
    ivs = sorted(
        (max(r0, s[T0]), min(r1, s[T1])) for s in tree["children"]
    )
    covered = 0.0
    cur0 = cur1 = None
    for a, b in ivs:
        if b < a:
            continue
        if cur0 is None:
            cur0, cur1 = a, b
        elif a <= cur1:
            cur1 = max(cur1, b)
        else:
            covered += cur1 - cur0
            cur0, cur1 = a, b
    if cur0 is not None:
        covered += cur1 - cur0
    return covered / (r1 - r0)

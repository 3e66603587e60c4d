"""What the hybrid state-space family's per-layer readers share: the decode
ticks of a run as the program's own spans and the requests' records describe
them. Every function gives nothing (an empty list) where the program's spans
carry no state counters (a program that keeps no recurrent state: the
parent) or the run has no window to look in."""
from __future__ import annotations

from benchmark import harness

FAMILY = "benchmark/reference/granite_hybrid.py"
DECODE_PROGRAM = r"^jit__(fused_step|spec_step|forward_only)_impl\b"


def family():
    return harness.load_reference({"reference": FAMILY})


def decode_ticks(ctx: dict, within=None, live: bool = True) -> list:
    """Per ``decode_step`` span inside ``within`` (default: the capture)
    that carries the engine's ``state_rows``: ``{"rows": the requests that
    DECODE at that instant, from their own records (prefill done, not yet
    finished: the rows whose state the tick must advance), "live": cached
    positions summed over
    those requests (where ``live`` asks for it: it walks every request's
    tokens)}``."""
    m = ctx.get("model") or {}
    if not ctx.get("spans") or not ctx.get("records") or not m.get("mamba_heads"):
        return []
    ta, tb = within or ctx.get("traced") or (None, None)
    if ta is None:
        return []
    out = []
    for _, track, name, s, e, attrs in ctx["spans"]:
        if track != "engine" or name != "decode_step" or s < ta or e > tb:
            continue
        if not attrs or "state_rows" not in attrs:
            continue
        decoding = [r for r in ctx["records"]
                    if r["prefill_done_at"] is not None
                    and r["prefill_done_at"] <= s < (r["finished_at"] or s + 1)]
        tick = {"rows": len(decoding)}
        if live:
            tick["live"] = sum(
                len(r["prompt"]) + sum(1 for t in r["token_times"] if t < s)
                for r in decoding)
        out.append(tick)
    return out

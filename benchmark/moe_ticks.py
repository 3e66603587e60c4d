"""What the routed family's per-layer readers share: the decode ticks of a
traced run as the program's own spans and the requests' records describe
them, and the device time of named operations inside the capture's decode
programs. Every function gives nothing (an empty list, zero) where the
program's spans carry no expert counters or the run has no capture."""
from __future__ import annotations

import re

from benchmark import harness, host_trace

FAMILY = "benchmark/reference/glm4_moe_lite.py"
DECODE_PROGRAM = r"^jit__(fused_step|spec_step|forward_only)_impl\b"
PREFILL_PROGRAM = r"^jit__(paged_)?chunk_prefill_impl\b"


def family():
    return harness.load_reference({"reference": FAMILY})


def routed_layers(model: dict) -> int:
    return model["n_layers"] - model.get("moe_dense_layers", 0)


def decode_ticks(ctx: dict, within=None, live: bool = True) -> list:
    """Per ``decode_step`` span inside ``within`` (default: the capture)
    that carries the engine's expert counters: ``{"rows": batch rows the
    counters counted, "touched": (layer, expert) pairs with at least one
    row, "routed": (row, expert) pairs over the routed layers, "load_max":
    the busiest expert's rows summed over the layers, "live": cached
    positions summed over the requests decoding at that instant (where
    ``live`` asks for it: it walks every request's tokens)}``."""
    m = ctx.get("model") or {}
    if not ctx.get("spans") or not ctx.get("records") or not m.get("moe_top_k"):
        return []
    ta, tb = within or ctx.get("traced") or (None, None)
    if ta is None:
        return []
    per_row = m["moe_top_k"] * routed_layers(m)
    out = []
    for _, track, name, s, e, attrs in ctx["spans"]:
        if track != "engine" or name != "decode_step" or s < ta or e > tb:
            continue
        if not attrs or "experts_touched" not in attrs:
            continue
        tick = {"rows": attrs["moe_routed"] / per_row, "touched": attrs["experts_touched"],
                "routed": attrs["moe_routed"], "load_max": attrs["moe_load_max"]}
        if live:
            tick["live"] = sum(
                len(r["prompt"]) + sum(1 for t in r["token_times"] if t < s)
                for r in ctx["records"]
                if r["prefill_done_at"] is not None
                and r["prefill_done_at"] <= s < (r["finished_at"] or s + 1))
        out.append(tick)
    return out


def prefill_touched(ctx: dict) -> list:
    """``prefill_experts_touched`` of the capture's ``decode_step`` spans
    that carry it: the (layer, expert) pairs the tick's chunk-prefill program
    handed rows to, over ALL the rows it computes whoever prefills."""
    ta, tb = ctx.get("traced") or (None, None)
    if ta is None:
        return []
    return [attrs["prefill_experts_touched"]
            for _, track, name, s, e, attrs in ctx.get("spans") or []
            if track == "engine" and name == "decode_step" and ta <= s and e <= tb
            and attrs and "prefill_experts_touched" in attrs]


def programs(ctx: dict, pattern: str = DECODE_PROGRAM) -> list:
    """[(start, end)] of the capture's programs named so (default: the
    decode programs), on the device's clock."""
    loaded = host_trace.load() if ctx.get("trace") else None
    if not loaded:
        return []
    rx = re.compile(pattern)
    return sorted((s, e) for evs in loaded["modules"].values()
                  for name, s, e, _ in evs if rx.search(name))


def op_seconds(ctx: dict, pattern: str, programs: list) -> tuple:
    """(seconds, events) of the ``XLA Ops`` events whose name matches
    ``pattern`` and which lie inside one of ``programs``."""
    rx = re.compile(pattern)
    total, count = 0.0, 0
    for evs in (ctx.get("trace") or {}).get("events", {}).values():
        for name, s, e in evs:
            if rx.search(name) and any(a <= s and e <= b for a, b in programs):
                total += e - s
                count += 1
    return total, count

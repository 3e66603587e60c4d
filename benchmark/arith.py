"""The benchmark's own arithmetic: tails, rates, windows, model operations
and bytes, rooflines. Nothing here touches JAX or the program."""
from __future__ import annotations

import json
import math
import statistics
from pathlib import Path


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation between order
    statistics; ``inf`` entries (failed requests) sort last."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if lo == hi or math.isinf(xs[hi]):
        return xs[hi] if pos > lo else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def spread(values) -> float:
    """Distance between the first and third quartile over the median, as the
    contract measures run-to-run spread."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def ttft_ms(due: list, first_token: list) -> list:
    """First token minus due time per request, in ms; a request that never
    produced a token counts as the worst (``inf``)."""
    return [
        math.inf if f is None else (f - d) * 1e3 for d, f in zip(due, first_token)
    ]


def gaps_ms(token_times: list) -> list:
    """Every gap between consecutive output tokens of every request, in ms."""
    out = []
    for ts in token_times:
        out.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:]))
    return out


def tokens_in_window(token_times: list, t0: float, t1: float) -> int:
    return sum(1 for ts in token_times for t in ts if t0 <= t <= t1)


def whole_step_window(steps: list) -> tuple:
    """``steps`` is [(fetch_start, sync_end), ...] of consecutive whole
    steps: the window runs from the first fetch to the last sync, and
    everything between them (a stall too) is in it."""
    if not steps:
        raise ValueError("no whole step in the window")
    return steps[0][0], steps[-1][1]


def rate(count: float, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError("a rate needs a positive time")
    return count / seconds


# ------------------------------------------------------------------- model


def train_flops_per_token(active_params: int, attention_flops_per_position: float,
                          seq_len: int) -> float:
    """6N for the matmuls forward and backward, plus three times the forward
    attention over the whole sequence (for GPT blocks 12*L*d*T, PaLM
    appendix B). Recomputation is not counted. N and the attention's
    operations per position come from the family's reference module."""
    return 6.0 * active_params + 3.0 * attention_flops_per_position * seq_len


def forward_flops(active_params: int, attention_flops_per_position: float,
                  context: float) -> float:
    """Forward operations of ONE token that attends over ``context`` cached
    positions: 2N plus the attention's."""
    return 2.0 * active_params + attention_flops_per_position * context


def paged_decode_ops_bytes(context_tokens: int, rows: int, heads: int,
                           head_dim: int, itemsize: int = 2) -> tuple:
    """One decode call over ``context_tokens`` live cached positions summed
    over its rows: q.k and p.v per position, K and V pages read once."""
    ops = 4.0 * context_tokens * heads * head_dim
    byts = 2.0 * context_tokens * heads * head_dim * itemsize \
        + 2.0 * rows * heads * head_dim * itemsize
    return ops, byts


def roofline_seconds(ops: float, byts: float, peak: dict) -> tuple:
    """The least time the chip could take, and which peak bounds it."""
    t_ops = ops / peak["flops_per_s"]
    t_bytes = byts / peak["bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "bandwidth")


def load_peak(device_kind: str, table: Path) -> dict:
    peaks = json.loads(Path(table).read_text())
    if device_kind not in peaks:
        raise SystemExit(
            f"no peaks recorded for device_kind {device_kind!r} in {table}; "
            f"known: {sorted(peaks)}. Add it with its source."
        )
    return peaks[device_kind]

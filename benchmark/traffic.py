"""The one general traffic generator. A traffic file is data; this reads it.

A serving schedule (arrival times, prompt and output lengths) is ONE trace
per mix and window length, the same for every ``--seed``; the seed gives the
token ids (and, in the drivers, the weights). A tail over the hundred or so
requests a window holds moves by tens of percent when the order of arrivals
changes, so the seed may not change it: a cell's tail is the tail of that one
trace, and its ``why`` says so.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float  # seconds after the window opens
    prompt: tuple
    max_new_tokens: int


def _lengths(rng, n: int, spec: dict) -> np.ndarray:
    """``n`` whole lengths with the file's ``mean``: ``min`` plus an
    exponential, the one-parameter shape for a source that publishes only a
    mean; clipped to ``max``."""
    x = spec["min"] + rng.exponential(spec["mean"] - spec["min"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def open_loop_requests(spec: dict, seed: int, seconds: float, vocab: int) -> list:
    """``rate_per_s * seconds`` requests on a Poisson schedule."""
    n = max(1, int(round(spec["rate_per_s"] * seconds)))
    # the mix's one trace: a stream each for prompts, outputs and gaps, so a
    # higher rate or a longer window sees the same requests, and more of them
    streams = [np.random.default_rng([0, k]) for k in range(3)]
    prompts = _lengths(streams[0], n, spec["prompt_len"])
    outputs = _lengths(streams[1], n, spec["output_len"])
    gaps = streams[2].exponential(1.0, size=n + 1)
    gaps *= seconds / gaps.sum()  # n arrivals, the last one before the close
    due = np.cumsum(gaps)[:n]
    rng = np.random.default_rng([int(seed), 1])
    return [
        Request(
            due_s=float(due[k]),
            prompt=tuple(int(t) for t in rng.integers(0, vocab, size=int(prompts[k]))),
            max_new_tokens=int(outputs[k]),
        )
        for k in range(n)
    ]


def warmup_requests(spec: dict, seed: int, vocab: int) -> list:
    """Requests that touch every shape the mix uses, for set-up: the file's
    ``warmup`` list of [prompt_len, output_len] pairs."""
    rng = np.random.default_rng([int(seed), 2])
    return [
        Request(0.0, tuple(int(t) for t in rng.integers(0, vocab, size=int(p))), int(o))
        for p, o in spec["warmup"]
    ]


def train_batch(seed: int, step: int, accum: int, rows: int, ctx: int,
                vocab: int) -> np.ndarray:
    """Step ``step``'s [accum, rows, ctx] int32 token ids: uniform ids, so
    every row differs."""
    rng = np.random.default_rng([int(seed), 3, int(step)])
    return rng.integers(0, vocab, size=(accum, rows, ctx), dtype=np.int32)

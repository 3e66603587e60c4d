"""Automap-style system autotuner core: a declared knob space, analytic
pre-pruning with auditable reasons, and successive-halving measured search.

Automap (arXiv 2112.02958) showed that search over partitioning/placement
decisions with a cheap cost model recovers expert-tuned performance
automatically; PartIR (arXiv 2401.11202) showed the value of keeping the
strategy space declarative and checkable. This repo already has every
ingredient they had to build — deterministic bench harnesses as the cost
model (``scripts/train_step_bench.py``, ``scripts/serve_loadgen.py``),
config validation + ``analysis.spec_check`` as the validity oracle, and
bitwise parity suites as the correctness gate. This module is the pure
search logic; ``scripts/autotune.py`` wires the measured trials and emits
the committed ``TUNE_<target>.json`` artifacts that ``train.py --tuned``
and ``serve.py --tuned`` load as defaults.

Design rules:

- **knobs are registered, not hardwired**: a new knob joins the search by
  declaring its name, domain, the dotted ``Config`` field it drives, and
  which bench grades it — nothing else;
- **every pruned point records its reason**: the search trace is auditable
  end to end (``enumerated == len(pruned) + len(survivors)``);
- **the validity oracle is the real one**: candidate points are
  constructed through ``config.apply_dotted_overrides``, so the exact
  ``ValueError`` a real run would raise is what prunes an invalid point —
  no measured trial ever runs an invalid config (``spec_check`` fires
  inside ``make_plan`` before any train trial compiles);
- **deterministic mechanics**: enumeration order, prune order, and the
  successive-halving promote rule (stable sort, index tie-break) are pure
  functions of (space, seed, workload) — re-running reproduces the same
  trace structure, and the driver re-runs the whole search to certify the
  same winner.

No device work and no timing in this module.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

TUNE_SCHEMA_VERSION = 1

# the committed-artifact contract, mirrored by tests/test_autotune.py the
# way tests/test_serve_bench.py pins BENCH_serve.json
TUNE_REQUIRED_KEYS = {
    "metric", "target", "value", "unit", "model", "platform",
    "workload", "workload_hash", "seed", "provenance",
    "space", "pruning", "search", "winner", "baseline", "improvement",
    "determinism", "measured_at_utc", "schema_version",
}


@dataclasses.dataclass(frozen=True)
class Knob:
    """One searchable knob: its domain, the dotted ``Config`` field it
    drives, and which bench grades it."""

    name: str
    values: Tuple[Any, ...]
    field: str  # dotted Config field, e.g. "mesh.overlap_comm"
    subsystem: str  # "train" | "serve"
    bench: str  # "BENCH_step" | "BENCH_serve"
    doc: str = ""

    def __post_init__(self):
        if not self.values:
            raise ValueError(f"knob {self.name!r} has an empty domain")
        if len(set(map(repr, self.values))) != len(self.values):
            raise ValueError(f"knob {self.name!r} has duplicate domain values")
        if "." not in self.field:
            raise ValueError(
                f"knob {self.name!r}: field {self.field!r} must be a dotted "
                "Config path (section.field)"
            )


class KnobSpace:
    """Ordered knob registry; enumeration is the cartesian product in
    registration order (deterministic, so the trace is reproducible)."""

    def __init__(self, target: str):
        if target not in ("train", "serve"):
            raise ValueError(f"invalid target {target!r}")
        self.target = target
        self._knobs: Dict[str, Knob] = {}

    def register(self, knob: Knob) -> "KnobSpace":
        if knob.name in self._knobs:
            raise ValueError(f"knob {knob.name!r} already registered")
        self._knobs[knob.name] = knob
        return self

    @property
    def knobs(self) -> List[Knob]:
        return list(self._knobs.values())

    def __getitem__(self, name: str) -> Knob:
        return self._knobs[name]

    @property
    def size(self) -> int:
        return math.prod(len(k.values) for k in self._knobs.values())

    def points(self) -> List[Dict[str, Any]]:
        """Every point of the space, deterministic order (last-registered
        knob varies fastest)."""
        out: List[Dict[str, Any]] = [{}]
        for knob in self._knobs.values():
            out = [{**p, knob.name: v} for p in out for v in knob.values]
        return out

    def overrides(self, point: Dict[str, Any]) -> Dict[str, Any]:
        """Dotted-field overrides for one point (the form
        ``config.apply_dotted_overrides`` and ``train.py --set`` take)."""
        return {self._knobs[name].field: value for name, value in point.items()}

    def describe(self) -> Dict[str, Any]:
        """Artifact-embeddable description of the registered space."""
        return {
            k.name: {
                "values": list(k.values),
                "field": k.field,
                "bench": k.bench,
                "doc": k.doc,
            }
            for k in self._knobs.values()
        }


def train_space() -> KnobSpace:
    """The training knob space (graded by BENCH_step): comm overlap, ZeRO
    stage, pipeline schedule family, microbatch count, remat."""
    s = KnobSpace("train")
    s.register(Knob("overlap_comm", (False, True), "mesh.overlap_comm",
                    "train", "BENCH_step",
                    "layer-bucketed in-scan ZeRO collectives vs serial"))
    s.register(Knob("zero_stage", (0, 1, 2, 3), "mesh.zero_stage",
                    "train", "BENCH_step",
                    "0=DP, 1=opt shard, 2=+grad scatter, 3=+param shard"))
    s.register(Knob("pipe", (1, 2), "mesh.pipe", "train", "BENCH_step",
                    "pipeline stages"))
    s.register(Knob("pp_schedule", ("gpipe", "1f1b", "interleaved"),
                    "mesh.pp_schedule", "train", "BENCH_step",
                    "pipeline wavefront schedule"))
    s.register(Knob("pp_interleave", (1, 2), "mesh.pp_interleave",
                    "train", "BENCH_step",
                    "virtual stages per rank (interleaved only)"))
    s.register(Knob("accum", (1, 2, 4),
                    "training.gradient_accumulation_steps",
                    "train", "BENCH_step",
                    "microbatch count splitting the workload's FIXED "
                    "global batch (same tokens per optimizer step in "
                    "every arm — a pure perf knob)"))
    s.register(Knob("remat", (False, True), "model.remat",
                    "train", "BENCH_step", "checkpoint each block"))
    s.register(Knob("remat_policy", ("none", "dots"), "model.remat_policy",
                    "train", "BENCH_step", "what the block checkpoint saves"))
    return s


def serve_space() -> KnobSpace:
    """The serving knob space (graded by BENCH_serve): paging, chunked
    prefill, speculation."""
    s = KnobSpace("serve")
    s.register(Knob("prefill_chunk", (8, 16), "serving.prefill_chunk",
                    "serve", "BENCH_serve",
                    "prompt tokens prefilled per tick"))
    s.register(Knob("page_size", (4, 8, 16), "serving.page_size",
                    "serve", "BENCH_serve", "tokens per KV page"))
    s.register(Knob("page_pool_tokens", (0, 192),
                    "serving.page_pool_tokens", "serve", "BENCH_serve",
                    "page-pool capacity (0 = slots x cache_len)"))
    s.register(Knob("draft_k", (0, 4), "serving.draft_k",
                    "serve", "BENCH_serve",
                    "speculative draft length per tick (0 = off)"))
    return s


@dataclasses.dataclass(frozen=True)
class PrunedPoint:
    index: int
    knobs: Dict[str, Any]
    rule: str
    reason: str


Validator = Tuple[str, Callable[[Dict[str, Any]], Optional[str]]]


def config_validator(space: KnobSpace, base_cfg) -> Validator:
    """The validity oracle: construct the candidate ``Config`` through the
    SAME dotted-override path ``train.py --set`` uses; the dataclass
    ``__post_init__`` refusal text becomes the prune reason verbatim."""
    from zero_transformer_tpu.config import apply_dotted_overrides

    def check(point: Dict[str, Any]) -> Optional[str]:
        try:
            apply_dotted_overrides(base_cfg, space.overrides(point))
        except ValueError as e:
            return str(e)
        return None

    return ("config_validation", check)


def train_redundancy_validator() -> Validator:
    """Dedup rules: points whose differing knob is inert compile the exact
    same program as a canonical sibling — measuring both would double-count
    the same arm (recorded, never silent)."""

    def check(point: Dict[str, Any]) -> Optional[str]:
        if not point.get("remat") and point.get("remat_policy", "none") != "none":
            return (
                "redundant: remat_policy is inert with remat=False "
                "(identical program to remat_policy='none')"
            )
        if point.get("pipe", 1) == 1 and point.get("pp_interleave", 1) != 1:
            # config validation already rejects schedule mismatches; this
            # catches the inert-interleave-on-gpipe duplicates
            return "redundant: pp_interleave is inert without a pipe axis"
        return None

    return ("redundancy", check)


def train_memory_validator(
    space: KnobSpace, base_cfg, budget_bytes: int, n_devices: int
) -> Validator:
    """Analytic HBM pre-prune: the ``analysis.memory`` stash/bubble/gather
    formulas against a per-device budget — the cheap cost model that keeps
    config points the AOT compiler would reject out of the measured set."""
    from zero_transformer_tpu.analysis.memory import analytic_memory
    from zero_transformer_tpu.config import apply_dotted_overrides

    def check(point: Dict[str, Any]) -> Optional[str]:
        try:
            cfg = apply_dotted_overrides(base_cfg, space.overrides(point))
        except ValueError:
            return None  # config_validation owns invalid points
        est = analytic_memory(cfg, n_devices=n_devices)
        if est["peak_bytes_est"] > budget_bytes:
            return (
                f"analytic peak {est['peak_bytes_est']} B exceeds the "
                f"{budget_bytes} B budget (state "
                f"{est['per_device_state_bytes_est']} B + stash/buffers)"
            )
        return None

    return ("memory_budget", check)


def serve_feasibility_validator(cache_len: int) -> Validator:
    """Workload-level analytic rules config validation cannot see (it has
    no cache_len): page divisibility of the cache and minimum pool size to
    hold one worst-case stream (admission would wedge, not error)."""

    def check(point: Dict[str, Any]) -> Optional[str]:
        ps = point.get("page_size", 4)
        if cache_len % ps:
            return (
                f"page_size={ps} does not divide cache_len={cache_len} "
                "(ragged final page; engine refuses)"
            )
        pool = point.get("page_pool_tokens", 0)
        if pool and pool < cache_len + ps:
            return (
                f"page_pool_tokens={pool} cannot hold one worst-case "
                f"stream (cache_len={cache_len}); admission would wait "
                "forever"
            )
        return None

    return ("workload_feasibility", check)


def prune_points(
    points: Sequence[Dict[str, Any]], validators: Sequence[Validator]
) -> Tuple[List[Tuple[int, Dict[str, Any]]], List[PrunedPoint]]:
    """Run every point through the validators in order; the first refusal
    prunes it with (rule, reason) recorded. Returns (survivors, pruned)
    with ``len(survivors) + len(pruned) == len(points)``."""
    survivors: List[Tuple[int, Dict[str, Any]]] = []
    pruned: List[PrunedPoint] = []
    for i, point in enumerate(points):
        for rule, check in validators:
            reason = check(point)
            if reason is not None:
                pruned.append(PrunedPoint(i, dict(point), rule, reason))
                break
        else:
            survivors.append((i, dict(point)))
    return survivors, pruned


def successive_halving(
    arms: Sequence[int],
    measure: Callable[[int, Any, int], Dict[str, Any]],
    budgets: Sequence[Any],
    keep_frac: float = 0.5,
    tie_frac: float = 0.0,
    log: Optional[Callable[[str], None]] = None,
) -> Tuple[int, List[Dict[str, Any]]]:
    """Successive halving over arm ids: cheap short trials gate expensive
    long ones. ``measure(arm_id, budget, rung)`` returns ``{"ok": bool,
    "score": float (lower is better), "metrics": {...}, "error": str?}``.
    Failed arms score ``inf`` and are never promoted. Promotion is a
    stable sort with arm-id tie-break, so identical scores reproduce the
    same trace.

    ``tie_frac``: relative noise floor for the FINAL winner — every arm
    whose last-rung score lands within ``tie_frac`` of the best magnitude
    is a statistical tie with the best, and the winner is the lowest arm
    index among them. Two arms that are really equivalent (e.g. two remat
    policies compiling to near-identical programs, or adjacent ZeRO
    stages on a comm-free box) swap raw order between reruns on noise;
    under this rule both reruns see the same tie set and pick the same
    arm. Promotion rungs rank raw (near-tied arms are simply both
    promoted). 0 = raw winner. Returns (winner_arm_id, rung_trace)."""
    if not arms:
        raise ValueError("successive_halving: no arms survived pruning")
    alive = list(arms)
    rungs: List[Dict[str, Any]] = []
    for rung_i, budget in enumerate(budgets):
        trials = []
        for arm in alive:
            r = measure(arm, budget, rung_i)
            score = r.get("score", float("inf")) if r.get("ok") else float("inf")
            trial = {
                "arm": arm,
                "ok": bool(r.get("ok")),
                "score": None if score == float("inf") else score,
                "metrics": r.get("metrics", {}),
            }
            if r.get("error"):
                trial["error"] = str(r["error"])[:300]
            trials.append(trial)
            if log:
                log(
                    f"rung {rung_i} budget={budget} arm={arm} "
                    f"score={trials[-1]['score']} ok={trials[-1]['ok']}"
                )
        ranked = sorted(
            trials,
            key=lambda t: (
                t["score"] if t["score"] is not None else float("inf"),
                t["arm"],
            ),
        )
        ok_trials = [t for t in ranked if t["ok"]]
        if not ok_trials:
            raise RuntimeError(
                f"successive_halving: every arm failed at rung {rung_i} "
                f"(budget {budget})"
            )
        ok_arms = [t["arm"] for t in ok_trials]
        last = rung_i == len(budgets) - 1
        if last:
            best = ok_trials[0]["score"]
            threshold = best + tie_frac * abs(best)
            tied = [t["arm"] for t in ok_trials if t["score"] <= threshold]
            promoted = [min(tied)]
        else:
            # tie-aware promotion (Hoeffding-race style): an arm within
            # tie_frac of the cut boundary promotes too — membership of
            # the next rung must never be decided by a noise-width margin,
            # or two certification passes diverge on WHICH arms the final
            # tie set even contains
            keep = max(1, math.ceil(len(ok_arms) * keep_frac))
            cutoff = ok_trials[keep - 1]["score"]
            boundary = cutoff + tie_frac * abs(ok_trials[0]["score"])
            promoted = [t["arm"] for t in ok_trials if t["score"] <= boundary]
        rungs.append({
            "rung": rung_i,
            "budget": budget,
            "trials": trials,
            "promoted": promoted,
        })
        alive = promoted
    return alive[0], rungs


def winner_overrides(artifact: Dict[str, Any]) -> Dict[str, Any]:
    """Dotted ``Config`` overrides of a TUNE artifact's winner — what
    ``train.py --tuned`` / ``serve.py --tuned`` apply as defaults. Reads
    the winner's pre-mapped overrides when present, else derives them from
    the embedded space description (knob -> field)."""
    winner = artifact.get("winner") or {}
    if winner.get("overrides"):
        return dict(winner["overrides"])
    space = artifact.get("space") or {}
    out = {}
    for name, value in (winner.get("knobs") or {}).items():
        desc = space.get(name)
        if not desc or "field" not in desc:
            raise ValueError(
                f"TUNE artifact winner knob {name!r} has no field mapping "
                "in the embedded space description"
            )
        out[desc["field"]] = value
    return out


def canonical_json(obj: Any) -> str:
    return json.dumps(obj, sort_keys=True, separators=(",", ":"))


def workload_hash(spec: Dict[str, Any]) -> str:
    """Stable short hash of a workload spec: byte-identical replay across
    arms and runs is part of the artifact's claim, so the hash rides in
    every BENCH/TUNE artifact the spec produced."""
    return hashlib.sha256(canonical_json(spec).encode()).hexdigest()[:16]


def trace_fingerprint(
    target: str,
    model: str,
    wl_hash: str,
    seed: int,
    space_desc: Dict[str, Any],
    pruned: Sequence[PrunedPoint],
    survivors: Sequence[Tuple[int, Dict[str, Any]]],
    budgets: Sequence[Any],
) -> str:
    """Hash of the DETERMINISTIC search structure (enumeration, pruning
    reasons, survivor set, rung budgets) — measured timings excluded. Two
    runs with the same (seed, space, workload) must produce the same
    fingerprint; the driver separately certifies the same winner."""
    payload = {
        "target": target,
        "model": model,
        "workload_hash": wl_hash,
        "seed": seed,
        "space": space_desc,
        "pruned": [
            {"index": p.index, "rule": p.rule, "reason": p.reason}
            for p in pruned
        ],
        "survivors": [i for i, _ in survivors],
        "budgets": list(budgets),
    }
    return hashlib.sha256(canonical_json(payload).encode()).hexdigest()[:16]

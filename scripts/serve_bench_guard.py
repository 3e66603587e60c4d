#!/usr/bin/env python
"""Serving-bench regression guard: fresh BENCH_serve.json vs the committed
baseline.

``make serve-bench`` snapshots the committed artifact before the load run,
then calls this with (baseline, fresh). The guard FAILS LOUDLY (exit 1)
when, on matching hardware, either headline metric regresses past the
tolerance:

- ``decode_tok_s`` (aggregate decode throughput) drops > 15%
- ``itl_ms.p99`` (tail inter-token latency) grows > 15%
- ``itl_ms_decode_only.p99`` (pure-decode tail — the paged kernel's home
  metric) grows > 15%
- the fresh artifact's measured span-tracing overhead (``obs_overhead``,
  from the loadgen's --obs-ab tracing-on/off A/B on this same run's
  hardware) exceeds 2% of decode tok/s — observability must stay
  effectively free on the hot path

"Matching hardware" is judged from the artifact's ``platform`` block (jax
backend + device kind): a TPU box must not be graded against a CPU
baseline, and a baseline from before the platform field existed can only be
skipped. Skips exit 0 with a reason — the guard's job is catching real
regressions on comparable runs, not adding noise on incomparable ones.

Usage: serve_bench_guard.py <baseline.json> <fresh.json> [--tolerance 0.15]
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_common  # noqa: E402  (shared skip-or-grade logic, ISSUE 14)

TOLERANCE = 0.15
# span tracing must cost <= this fraction of decode tok/s (ISSUE 7): the
# A/B inside one artifact ran both arms on the same box minutes apart, so
# unlike the baseline comparison there is no hardware-mismatch skip
OBS_OVERHEAD_MAX = 0.02
# the fleet router's near-linear-scaling bar (ISSUE 9): aggregate relayed
# tok/s at the largest fleet must be >= this multiple of the 1-replica run
ROUTER_SCALING_MIN = 3.0


def compare_router(
    baseline: dict, fresh: dict, tolerance: float = TOLERANCE,
    grade_scaling: bool = True,
):
    """BENCH_router.json pair. Correctness fields (zero dropped streams, a
    token-exact resumed failover, a clean rolling reload) grade on ANY
    hardware — a dropped stream is a dropped stream wherever it ran; they
    were already hard-enforced by the loadgen at artifact-write time and
    are re-checked so a hand-edited or stale artifact cannot sneak past.
    The scaling ratio (the absolute near-linear bar + the baseline
    tolerance) only grades on matching hardware, like every other perf
    number in this guard."""
    msgs = []
    ok = True
    if fresh.get("dropped_streams", -1) != 0:
        ok = False
        msgs.append(
            f"FAIL: router artifact has dropped_streams="
            f"{fresh.get('dropped_streams')} (must be 0)"
        )
    failover = fresh.get("failover") or {}
    if not failover.get("token_exact"):
        ok = False
        msgs.append("FAIL: router failover segment was not token-exact")
    reload_block = fresh.get("rolling_reload") or {}
    if not reload_block.get("ok") or reload_block.get("dropped_streams"):
        ok = False
        msgs.append(f"FAIL: rolling reload {reload_block}")
    # stitched-trace verification (ISSUE 15) is correctness: a merged trace
    # with orphan spans or <95% coverage is a broken observability plane on
    # any hardware (absent block = pre-PR15 artifact, skipped not failed)
    trace_block = fresh.get("fleet_trace")
    if trace_block is not None:
        if trace_block.get("coverage_min", 0) < 0.95:
            ok = False
            msgs.append(
                f"FAIL: stitched-trace coverage "
                f"{trace_block.get('coverage_min')} < 0.95"
            )
        if trace_block.get("orphans") or not trace_block.get("hops_ordered"):
            ok = False
            msgs.append(f"FAIL: stitched trace {trace_block}")
    if not grade_scaling:
        msgs.append(
            "SKIP: hardware mismatch vs baseline; router scaling ratio "
            "not graded (correctness fields were)"
        )
        return ok, msgs
    # the SLO verdict (ISSUE 15) grades with the perf numbers: on foreign
    # hardware a "violated" verdict may be the box, not the router — but on
    # matching hardware the declared objectives are part of the bar
    slo = fresh.get("slo") or {}
    if slo.get("verdict") == "violated":
        ok = False
        msgs.append(
            f"REGRESSION: SLO verdict violated — "
            f"{ {name: o.get('state') for name, o in (slo.get('objectives') or {}).items() if o.get('state') != 'ok'} }"
        )
    elif slo:
        msgs.append(f"ok: SLO verdict {slo.get('verdict')}")
    ratio = fresh.get("value", 0)
    if ratio < ROUTER_SCALING_MIN:
        ok = False
        msgs.append(
            f"REGRESSION: router scaling ratio {ratio:.2f} < the "
            f"near-linear bar {ROUTER_SCALING_MIN:.1f}"
        )
    else:
        msgs.append(
            f"ok: router scaling ratio {ratio:.2f} "
            f"(bar {ROUTER_SCALING_MIN:.1f})"
        )
    base_ratio = baseline.get("value", 0)
    if base_ratio and ratio < base_ratio * (1 - tolerance):
        ok = False
        msgs.append(
            f"REGRESSION: router scaling ratio {ratio:.2f} < "
            f"{(1 - tolerance) * 100:.0f}% of baseline {base_ratio:.2f}"
        )
    return ok, msgs


def compare_disagg(
    baseline: dict, fresh: dict, tolerance: float = TOLERANCE,
    grade_perf: bool = True,
):
    """BENCH_disagg.json pair (ISSUE 12). Correctness grades on ANY
    hardware: every stream token-exact and finished, zero dropped streams,
    the disaggregated arm actually split requests with ZERO replayed
    tokens, and the sawtooth segment scaled up AND back down without
    drops. The within-artifact A/B (the disaggregated arm must isolate
    background decode from the flood at least as well as the mixed-fleet
    control) also grades everywhere — both arms ran minutes apart on the
    same box, like the obs-overhead A/B. Only the cross-run degradation
    ratio vs the committed baseline is hardware-gated."""
    msgs = []
    ok = True
    flood = fresh.get("flood") or {}
    if flood:
        if not flood.get("token_exact"):
            ok = False
            msgs.append("FAIL: flood arm streams were not token-exact")
        if flood.get("dropped_streams", -1) != 0:
            ok = False
            msgs.append(
                f"FAIL: flood dropped_streams="
                f"{flood.get('dropped_streams')} (must be 0)"
            )
        disagg = flood.get("disagg") or {}
        mixed = flood.get("mixed") or {}
        if not disagg.get("disagg_dispatches"):
            ok = False
            msgs.append("FAIL: disagg arm never split a request by phase")
        if disagg.get("resume_replayed_tokens", -1) != 0:
            ok = False
            msgs.append(
                "FAIL: disagg arm replayed "
                f"{disagg.get('resume_replayed_tokens')} tokens (must be 0)"
            )
        d_deg = disagg.get("itl_bg_p50_degradation", 0)
        m_deg = mixed.get("itl_bg_p50_degradation", 0)
        on_cpu = (fresh.get("platform") or {}).get("backend") == "cpu"
        if d_deg and m_deg and on_cpu:
            # CPU-honesty (the BENCH_ckpt_integrity / train_bench
            # discipline): on a shared-core CPU box both "replicas"
            # compete for the same cores, so the flood steals cycles from
            # the decode replica whatever process it lives in — phase
            # isolation is a DEVICE-parallelism claim and measuring it
            # here is scheduler noise (observed flipping run to run).
            # Correctness still graded above; ratios recorded, not graded.
            msgs.append(
                f"SKIP: cpu backend — isolation ratio recorded "
                f"(disagg {d_deg:.2f}x vs mixed {m_deg:.2f}x) but not "
                "graded; replicas share the same cores here"
            )
        elif d_deg and m_deg:
            budget = max(m_deg * (1 + tolerance), 1.5)
            if d_deg > budget:
                ok = False
                msgs.append(
                    f"REGRESSION: disagg ITL degradation {d_deg:.2f}x under "
                    f"flood exceeds the mixed control's {m_deg:.2f}x "
                    f"(budget {budget:.2f}x) — disaggregation stopped "
                    "isolating decode"
                )
            else:
                msgs.append(
                    f"ok: flood stretches background decode ITL p50 "
                    f"{d_deg:.2f}x disaggregated vs {m_deg:.2f}x mixed"
                )
    saw = fresh.get("sawtooth") or {}
    if saw:
        if saw.get("dropped_streams", -1) != 0 or saw.get("hung"):
            ok = False
            msgs.append(f"FAIL: sawtooth dropped/hung streams: {saw}")
        if saw.get("streams_done") != saw.get("streams"):
            ok = False
            msgs.append(
                f"FAIL: sawtooth finished {saw.get('streams_done')} of "
                f"{saw.get('streams')} streams"
            )
        if not saw.get("autoscale_ups") or not saw.get("autoscale_downs"):
            ok = False
            msgs.append(
                "FAIL: autoscaler never tracked the sawtooth "
                f"(ups={saw.get('autoscale_ups')}, "
                f"downs={saw.get('autoscale_downs')})"
            )
        else:
            msgs.append(
                f"ok: sawtooth tracked (ups={saw['autoscale_ups']}, "
                f"downs={saw['autoscale_downs']}, dropped 0)"
            )
    if not grade_perf:
        msgs.append(
            "SKIP: hardware mismatch vs baseline; cross-run degradation "
            "not graded (correctness + within-artifact A/B were)"
        )
        return ok, msgs
    base_deg = (
        (baseline.get("flood") or {}).get("disagg") or {}
    ).get("itl_bg_p50_degradation", 0)
    fresh_deg = (
        (fresh.get("flood") or {}).get("disagg") or {}
    ).get("itl_bg_p50_degradation", 0)
    if (fresh.get("platform") or {}).get("backend") == "cpu":
        base_deg = 0  # same shared-core honesty as the within-artifact A/B
    if base_deg and fresh_deg and fresh_deg > base_deg * (1 + tolerance):
        ok = False
        msgs.append(
            f"REGRESSION: disagg ITL degradation {fresh_deg:.2f}x > "
            f"{(1 + tolerance) * 100:.0f}% of baseline {base_deg:.2f}x"
        )
    elif base_deg and fresh_deg:
        msgs.append(
            f"ok: disagg ITL degradation {fresh_deg:.2f}x "
            f"(baseline {base_deg:.2f}x)"
        )
    return ok, msgs


def compare_tenant(
    baseline: dict, fresh: dict, tolerance: float = TOLERANCE,
    grade_perf: bool = True,
):
    """BENCH_tenant.json pair (ISSUE 18). Correctness grades on ANY
    hardware: every gold stream done and token-exact, zero dropped
    streams, the flood actually throttled, every rejection retryable with
    a Retry-After, and the isolation machinery engaged. The gold p99
    ratio is a device-parallelism claim: on a shared-core CPU box the
    flood steals cycles from the gold replica whatever the admission
    plane does, so the ratio is recorded, not graded (same CPU-honesty
    discipline as the disagg isolation A/B); on an accelerator it grades
    against the artifact's own pinned factor and the committed baseline."""
    msgs = []
    ok = True
    for arm_name in ("baseline", "flood"):
        arm = fresh.get(arm_name) or {}
        if arm.get("gold_done") != arm.get("gold_offered"):
            ok = False
            msgs.append(
                f"FAIL: {arm_name} arm finished {arm.get('gold_done')} of "
                f"{arm.get('gold_offered')} gold streams"
            )
    if not fresh.get("token_exact"):
        ok = False
        msgs.append("FAIL: gold streams were not token-exact")
    if fresh.get("dropped_streams", -1) != 0:
        ok = False
        msgs.append(
            f"FAIL: dropped_streams={fresh.get('dropped_streams')} "
            "(must be 0)"
        )
    flood = fresh.get("flood") or {}
    if not flood.get("flood_rejected"):
        ok = False
        msgs.append("FAIL: the flood was never throttled — not a flood")
    if flood.get("flood_bad_rejections"):
        ok = False
        msgs.append(
            f"FAIL: {flood.get('flood_bad_rejections')} flood rejections "
            "without retryable semantics (non-429/503 or missing "
            "Retry-After)"
        )
    if sum((flood.get("isolation_counters") or {}).values()) == 0:
        ok = False
        msgs.append("FAIL: isolation machinery never engaged under flood")
    ratio = fresh.get("value", 0)
    limit = fresh.get("isolation_factor_limit", 0)
    on_cpu = (fresh.get("platform") or {}).get("backend") == "cpu"
    if on_cpu:
        msgs.append(
            f"SKIP: cpu backend — gold p99 ratio recorded ({ratio:.2f}x) "
            "but not graded; the flood shares the gold replica's cores here"
        )
        return ok, msgs
    if not grade_perf:
        msgs.append(
            "SKIP: hardware mismatch vs baseline; gold p99 ratio not "
            "graded (correctness fields were)"
        )
        return ok, msgs
    if limit and ratio > limit:
        ok = False
        msgs.append(
            f"REGRESSION: gold p99 ratio {ratio:.2f}x exceeds the pinned "
            f"isolation factor {limit:.2f}x"
        )
    base_ratio = baseline.get("value", 0)
    if base_ratio and ratio > base_ratio * (1 + tolerance):
        ok = False
        msgs.append(
            f"REGRESSION: gold p99 ratio {ratio:.2f}x > "
            f"{(1 + tolerance) * 100:.0f}% of baseline {base_ratio:.2f}x"
        )
    elif ok:
        msgs.append(
            f"ok: gold p99 ratio {ratio:.2f}x "
            f"(limit {limit:.2f}x, baseline {base_ratio:.2f}x)"
        )
    return ok, msgs


def compare(baseline: dict, fresh: dict, tolerance: float = TOLERANCE):
    """Returns (ok, messages). ok=True covers both pass and skip."""
    msgs = []
    # the tenant-isolation artifact dispatches before the generic platform
    # gate: its correctness fields grade everywhere, its latency ratio is
    # accelerator-only (CPU-honesty) and hardware-gated vs the baseline
    if str(fresh.get("metric", "")) == "tenant_isolation":
        grade = bench_common.correctness_gate(baseline, fresh)
        return compare_tenant(
            baseline if grade else {}, fresh, tolerance, grade_perf=grade
        )
    # the disagg artifact dispatches before the generic platform gate too:
    # its correctness fields + within-artifact A/B grade everywhere; the
    # perf grade decision is the ONE shared rule (bench_common, ISSUE 14 —
    # the router/disagg copies of this predicate had drifted)
    if str(fresh.get("metric", "")) == "disagg_flood_and_autoscale":
        grade = bench_common.correctness_gate(baseline, fresh)
        return compare_disagg(
            baseline if grade else {}, fresh, tolerance, grade_perf=grade
        )
    # the router artifact dispatches before the generic platform gate: its
    # correctness fields must grade everywhere, only its scaling perf is
    # hardware-gated
    if str(fresh.get("metric", "")) == "router_scaling_tok_s":
        grade = bench_common.correctness_gate(baseline, fresh)
        return compare_router(
            baseline if grade else {}, fresh, tolerance, grade_scaling=grade
        )
    hw_ok, hw_reason = bench_common.hardware_gate(baseline, fresh)
    if not hw_ok:
        return True, [hw_reason]
    if baseline.get("metric") != fresh.get("metric"):
        return True, ["SKIP: different metrics; not comparable"]
    if baseline.get("workload", "mixed") != fresh.get("workload", "mixed"):
        return True, ["SKIP: different workloads; not comparable"]

    ok = True
    base_tps = baseline.get("decode_tok_s", baseline.get("value", 0))
    fresh_tps = fresh.get("decode_tok_s", fresh.get("value", 0))
    if base_tps and fresh_tps < base_tps * (1 - tolerance):
        ok = False
        msgs.append(
            f"REGRESSION: decode_tok_s {fresh_tps:.1f} < "
            f"{(1 - tolerance) * 100:.0f}% of baseline {base_tps:.1f}"
        )
    else:
        msgs.append(f"ok: decode_tok_s {fresh_tps:.1f} (baseline {base_tps:.1f})")

    base_p99 = baseline.get("itl_ms", {}).get("p99", 0)
    fresh_p99 = fresh.get("itl_ms", {}).get("p99", 0)
    if base_p99 and fresh_p99 > base_p99 * (1 + tolerance):
        ok = False
        msgs.append(
            f"REGRESSION: itl_ms.p99 {fresh_p99:.3f} ms > "
            f"{(1 + tolerance) * 100:.0f}% of baseline {base_p99:.3f} ms"
        )
    else:
        msgs.append(f"ok: itl_ms.p99 {fresh_p99:.3f} ms (baseline {base_p99:.3f} ms)")

    # decode-only ITL tail (PR 11): the fused sampling tail's home metric —
    # ticks with no prefill work are pure decode, so a regression here is a
    # kernel/tail regression, not admission-mix noise
    base_d99 = (baseline.get("itl_ms_decode_only") or {}).get("p99", 0)
    fresh_d99 = (fresh.get("itl_ms_decode_only") or {}).get("p99", 0)
    if base_d99 and fresh_d99 > base_d99 * (1 + tolerance):
        ok = False
        msgs.append(
            f"REGRESSION: itl_ms_decode_only.p99 {fresh_d99:.3f} ms > "
            f"{(1 + tolerance) * 100:.0f}% of baseline {base_d99:.3f} ms"
        )
    elif base_d99:
        msgs.append(
            f"ok: itl_ms_decode_only.p99 {fresh_d99:.3f} ms "
            f"(baseline {base_d99:.3f} ms)"
        )

    obs = fresh.get("obs_overhead")
    if obs and obs.get("overhead_frac", 0) > OBS_OVERHEAD_MAX:
        ok = False
        msgs.append(
            f"REGRESSION: span-tracing overhead "
            f"{obs['overhead_frac'] * 100:.1f}% of decode tok/s > "
            f"{OBS_OVERHEAD_MAX * 100:.0f}% budget "
            f"(on {obs.get('decode_tok_s_trace_off', 0):.1f} tok/s traced off)"
        )
    elif obs:
        msgs.append(
            f"ok: span-tracing overhead {obs['overhead_frac'] * 100:.1f}% "
            f"(budget {OBS_OVERHEAD_MAX * 100:.0f}%)"
        )
    return ok, msgs


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("baseline", help="committed BENCH_serve.json snapshot")
    p.add_argument("fresh", help="artifact from the run under test")
    p.add_argument("--tolerance", type=float, default=TOLERANCE)
    args = p.parse_args(argv)
    baseline = json.loads(Path(args.baseline).read_text())
    fresh = json.loads(Path(args.fresh).read_text())
    ok, msgs = compare(baseline, fresh, args.tolerance)
    for m in msgs:
        print(f"serve-bench-guard: {m}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

"""scripts/serve_loadgen.py: the BENCH_serve.json artifact contract.

Same philosophy as test_bench_artifact.py for the training bench: the
artifact is the driver-facing evidence of a load run, so its schema and its
invariants (no drops, no garbling, occupancy actually reached the slot
count) are pinned here — a real (small) load run on CPU with the ``test``
zoo model, not a mocked one.
"""
import importlib.util
import json
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

REQUIRED_KEYS = {
    "metric", "value", "unit", "model", "mode", "slots", "requests",
    "max_new_tokens", "wall_s", "ttft_ms", "itl_ms", "peak_occupancy",
    "peak_queue_depth", "completed", "rejected", "dropped", "verified",
    "mismatches", "measured_at_utc",
    # resilience evidence (ISSUE 3): fault/shed/drain behavior is part of
    # the load-run contract, chaos or not
    "chaos", "errors", "error_rate", "shed", "shed_rate",
    "drain_latency_s", "tick_faults", "poisoned_slots", "breaker_trips",
    "final_state",
    # frozen-workload evidence (ISSUE 14): which spec this run replayed and
    # its hash — TUNE artifacts carry the same hash, so "tuned under this
    # workload" is checkable against the bench artifact
    "workload_spec", "workload_hash",
    # serving hot path evidence (ISSUE 4): chunked prefill, prefix caching,
    # per-phase latency attribution, and the regression guard's keys
    "workload", "decode_tok_s", "prefill_chunk", "prefix_cache",
    "itl_ms_decode_only", "prefill_ms_hit_p50", "prefill_ms_miss_p50",
    "no_prefix_cache", "platform",
    # paged KV + speculation evidence (ISSUE 6): pool pressure and
    # draft-and-verify acceptance economics with the spec-off control
    "page_size", "page_faults", "pages_reclaimed",
    "preemptions", "page_pool_util", "cow_copies",
    "draft_k", "acceptance_rate", "spec_ticks", "no_speculation",
    # kernel-lane evidence (ISSUE 11): whether the paged-attention kernel
    # traced into the decode program on this run's backend
    "kernel_paged_attention",
    # observability evidence (ISSUE 7): tracing-cost A/B (populated by
    # --obs-ab, None otherwise) and the Perfetto span artifact every run
    # writes beside the JSON
    "obs_overhead", "trace_file", "obs_spans",
}

ROUTER_REQUIRED_KEYS = {
    # fleet-router evidence (ISSUE 9): the replica-scaling sweep, routing
    # hit-rate, the token-exact mid-stream failover segment, and the
    # rolling-reload zero-drop proof
    "metric", "value", "unit", "replica_model", "replica_itl_ms",
    "replica_slots", "clients", "requests_per_client", "max_new_tokens",
    "scaling", "aggregate_tok_s", "routing", "failover", "rolling_reload",
    "dropped_streams", "platform", "measured_at_utc",
    # fleet observability plane (ISSUE 15): the merged-trace verification,
    # the SLO verdict over the run, and the aggregate cost ledger
    "fleet_trace", "slo", "ledger",
}

DISAGG_REQUIRED_KEYS = {"bench", "metric", "platform", "config", "flood",
                        "sawtooth"}
DISAGG_FLOOD_ARM_KEYS = {
    "roles", "itl_ms_decode_bg_no_flood", "itl_ms_decode_bg_flood",
    "ttft_ms_flood", "itl_bg_p50_degradation", "streams_done", "hung",
    "dropped_streams", "disagg_dispatches", "resume_replayed_tokens",
}
DISAGG_SAWTOOTH_KEYS = {
    "streams", "streams_done", "hung", "dropped_streams", "autoscale_ups",
    "autoscale_downs", "autoscale_aborts", "max_replicas_seen",
    "min_replicas_seen", "replica_trace",
}

TENANT_REQUIRED_KEYS = {
    # tenant-isolation evidence (ISSUE 18): the gold-trickle A/B under a
    # hostile batch flood, the retryable-rejection proof, and the
    # isolation counters that show WHICH mechanism absorbed the flood
    "bench", "metric", "value", "unit", "isolation_factor_limit", "config",
    "baseline", "flood", "token_exact", "dropped_streams", "platform",
    "measured_at_utc",
}
TENANT_ARM_KEYS = {
    "label", "gold_e2e_ms", "gold_ttft_ms", "gold_done", "gold_offered",
    "flood_attempts", "flood_ok", "flood_rejected", "flood_bad_rejections",
    "dropped_streams", "isolation_counters",
}


def _load():
    spec = importlib.util.spec_from_file_location(
        "serve_loadgen", REPO / "scripts" / "serve_loadgen.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_loadgen_artifact_schema_and_invariants(tmp_path):
    loadgen = _load()
    out = tmp_path / "BENCH_serve.json"
    artifact = loadgen.main([
        "--requests", "6", "--slots", "2", "--concurrency", "6",
        "--max-new-tokens", "8", "--out", str(out),
    ])

    on_disk = json.loads(out.read_text())
    assert on_disk == artifact  # stdout line and file artifact must agree

    missing = REQUIRED_KEYS - set(artifact)
    assert not missing, f"artifact missing keys: {sorted(missing)}"
    assert artifact["metric"] == "serve_tokens_per_sec_test"
    assert artifact["unit"] == "tokens/s"
    assert artifact["value"] > 0

    for block in ("ttft_ms", "itl_ms", "itl_ms_decode_only"):
        assert set(artifact[block]) == {"p50", "p90", "p99"}
        assert artifact[block]["p50"] <= artifact[block]["p99"]
    assert set(artifact["prefix_cache"]) == {"hits", "misses", "hit_rate"}
    assert set(artifact["platform"]) == {"backend", "device"}
    assert artifact["decode_tok_s"] == artifact["value"]
    assert artifact["workload"] == "mixed"
    assert artifact["prefill_chunk"] > 0  # chunked prefill is the default

    # the load-run correctness invariants the acceptance bar names
    assert artifact["completed"] == 6
    assert artifact["dropped"] == 0
    assert artifact["verified"] is True and artifact["mismatches"] == 0
    # 6 concurrent clients against 2 slots must saturate the engine
    assert artifact["peak_occupancy"] == 2
    assert artifact["peak_queue_depth"] >= 1
    # an undisturbed run ends with a clean graceful drain and zero faults
    assert artifact["chaos"] is False and artifact["errors"] == 0
    assert artifact["final_state"] == "stopped"
    assert artifact["drain_latency_s"] >= 0
    # speculation off in this run
    assert artifact["page_size"] > 0 and artifact["page_pool_util"] > 0
    assert artifact["preemptions"] == 0
    assert artifact["draft_k"] == 0 and artifact["no_speculation"] is None
    assert artifact["kernel_paged_attention"] in (True, False)
    # every run writes a Perfetto-loadable span trace next to the artifact
    assert artifact["obs_overhead"] is None  # --obs-ab not requested here
    assert artifact["obs_spans"] > 0
    trace = json.loads((out.parent / artifact["trace_file"]).read_text())
    assert trace["traceEvents"], "span trace artifact is empty"
    names = {e.get("name") for e in trace["traceEvents"]}
    assert {"request", "queue", "prefill", "decode"} <= names, names


def test_loadgen_speculative_run_verified_with_acceptance(tmp_path):
    """--spec-k + --greedy: every trajectory STILL byte-identical to
    (greedy) generate() — the verify step's exactness contract under real
    contention — with a nonzero acceptance rate and the spec-OFF control
    embedded for the A/B."""
    loadgen = _load()
    out = tmp_path / "BENCH_serve_spec.json"
    artifact = loadgen.main([
        "--requests", "6", "--slots", "2", "--concurrency", "6",
        "--max-new-tokens", "24", "--cache-len", "64",
        "--spec-k", "4", "--greedy", "--out", str(out),
    ])
    assert artifact["draft_k"] == 4
    assert artifact["verified"] is True and artifact["mismatches"] == 0
    assert artifact["completed"] == 6 and artifact["dropped"] == 0
    assert artifact["spec_ticks"] > 0
    assert artifact["acceptance_rate"] > 0
    assert artifact["no_speculation"] is not None
    assert artifact["no_speculation"]["decode_tok_s"] > 0


@pytest.mark.slow
def test_loadgen_obs_ab_measures_tracing_overhead(tmp_path):
    """--obs-ab: the tracing-on/off A/B runs both arms and embeds a sane
    obs_overhead block (fractions in [0, 1], both arms nonzero). Slow lane:
    the A/B is two extra full load runs; tier-1 covers the obs_overhead
    schema key (None without --obs-ab) and the guard logic, and
    make serve-bench runs the real best-of-5 A/B into the committed
    BENCH_serve.json where the guard enforces the <=2% budget."""
    loadgen = _load()
    out = tmp_path / "BENCH_serve_obs.json"
    artifact = loadgen.main([
        "--requests", "4", "--slots", "2", "--concurrency", "4",
        "--max-new-tokens", "8", "--obs-ab", "--obs-ab-repeats", "1",
        "--out", str(out),
    ])
    ab = artifact["obs_overhead"]
    assert ab is not None
    assert ab["decode_tok_s_trace_off"] > 0
    assert ab["decode_tok_s_trace_on"] > 0
    assert 0.0 <= ab["overhead_frac"] <= 1.0
    assert ab["repeats"] == 1


def test_loadgen_chaos_run_fails_retryably_and_drains(tmp_path):
    """--chaos: the injected decode fault + NaN-logit window fail SOME
    requests (retryably), hang none, garble none of the survivors (every
    completed request stays byte-identical to generate()), and the engine
    still drains to STOPPED — the quick-lane slice of the serving chaos
    acceptance bar."""
    loadgen = _load()
    out = tmp_path / "BENCH_serve_chaos.json"
    artifact = loadgen.main([
        "--requests", "6", "--slots", "2", "--concurrency", "6",
        "--max-new-tokens", "8", "--chaos", "--out", str(out),
    ])
    assert artifact["chaos"] is True
    assert artifact["errors"] > 0  # the faults really fired
    assert artifact["tick_faults"] >= 1 and artifact["poisoned_slots"] >= 1
    assert artifact["dropped"] == 0  # no request hung: all reached terminal
    assert artifact["mismatches"] == 0  # survivors byte-identical
    assert artifact["completed"] + artifact["errors"] == 6
    assert artifact["final_state"] == "stopped"


def test_loadgen_shared_prefix_hits_and_parity(tmp_path):
    """--shared-prefix: the common system prompt really hits the prefix
    cache (hit_rate > 0), every trajectory STILL matches single-request
    generate() byte-for-byte (reused K/V spans are bit-identical by
    construction), and admissions that hit reach their first token FASTER
    than the cache-off control — the TTFT win, measured on the component
    the engine controls (admission -> first token; full TTFT under a
    closed loop is dominated by queue wait)."""
    loadgen = _load()
    out = tmp_path / "BENCH_serve_prefix.json"
    artifact = loadgen.main([
        "--requests", "6", "--slots", "2", "--concurrency", "6",
        "--max-new-tokens", "8", "--cache-len", "48", "--shared-prefix",
        "--out", str(out),
    ])
    assert artifact["workload"] == "shared_prefix"
    assert artifact["prefix_cache"]["hits"] > 0
    assert artifact["prefix_cache"]["hit_rate"] > 0
    assert artifact["verified"] is True and artifact["mismatches"] == 0
    assert artifact["completed"] == 6 and artifact["dropped"] == 0
    # both phases have samples: someone paid the cold prefix prefill
    # (2+ chunk ticks) and someone skipped straight to the novel chunk
    assert artifact["prefill_ms_miss_p50"] > 0
    assert artifact["prefill_ms_hit_p50"] > 0
    # the headline: a prefix hit prefills strictly less than the cache-off
    # control's cold prefill (same workload, same seeds, same box)
    assert artifact["no_prefix_cache"] is not None
    assert artifact["prefill_ms_hit_p50"] < artifact["no_prefix_cache"]["prefill_ms_p50"]


def test_loadgen_router_artifact(tmp_path):
    """--router: the fleet-scaling scenario over paced stub replicas. Small
    here (2-replica sweep, short streams) — tier-1 pins the artifact schema
    and the correctness invariants (every stream token-exact, the failover
    segment resumed exactly, rolling reload with zero drops); make
    serve-bench runs the full 1 -> 4 sweep into the committed
    BENCH_router.json where the guard holds the >= 3x near-linear bar."""
    loadgen = _load()
    out = tmp_path / "BENCH_router.json"
    artifact = loadgen.main([
        "--router", "--router-replicas", "2", "--router-requests", "2",
        "--router-max-new", "12", "--router-itl-ms", "2",
        "--router-repeats", "1", "--out", str(out),
    ])
    on_disk = json.loads(out.read_text())
    assert on_disk == artifact
    missing = ROUTER_REQUIRED_KEYS - set(artifact)
    assert not missing, f"router artifact missing keys: {sorted(missing)}"
    assert artifact["metric"] == "router_scaling_tok_s"
    assert artifact["value"] > 1.0  # 2 replicas must beat 1
    # sweep shape: 1 and 2 replicas, aggregate == sum of per-replica rates
    assert [p["replicas"] for p in artifact["scaling"]] == [1, 2]
    for point in artifact["scaling"]:
        assert point["streams"] == artifact["clients"] * 2
        assert len(point["per_replica_tok_s"]) == point["replicas"]
        assert point["aggregate_tok_s"] > 0
    # each client's 2nd request rides prefix affinity back to its replica
    assert artifact["routing"]["hit_rate"] == 0.5
    assert artifact["routing"]["affinity_hits"] > 0
    # the failover segment resumed mid-stream, token-exact, on the survivor
    assert artifact["failover"]["token_exact"] is True
    assert artifact["failover"]["resumed_streams"] == 1
    assert artifact["failover"]["failovers"] >= 1
    # rolling reload under live streams: one step per replica, zero drops
    assert artifact["rolling_reload"]["ok"] is True
    assert artifact["rolling_reload"]["steps"] == 3
    assert artifact["rolling_reload"]["dropped_streams"] == 0
    assert artifact["dropped_streams"] == 0
    assert set(artifact["platform"]) == {"backend", "device"}
    # fleet observability plane (ISSUE 15): the merged trace stitched and
    # verified, the SLO verdict ok on a healthy run, and the aggregate
    # ledger schema-complete (FLEET_OBS_REQUIRED_KEYS is the contract)
    from zero_transformer_tpu.obs.fleet import FLEET_OBS_REQUIRED_KEYS

    ft = artifact["fleet_trace"]
    assert ft["coverage_min"] >= 0.95 and ft["orphans"] == 0
    assert ft["hops_ordered"] is True and ft["requests"] >= 1
    trace_doc = json.loads((out.parent / ft["file"]).read_text())
    assert trace_doc["traceEvents"], "merged fleet trace is empty"
    assert FLEET_OBS_REQUIRED_KEYS["slo"] <= set(artifact["slo"])
    assert artifact["slo"]["verdict"] == "ok"
    missing = FLEET_OBS_REQUIRED_KEYS["ledger"] - set(artifact["ledger"])
    assert not missing, f"aggregate ledger missing {sorted(missing)}"
    assert artifact["ledger"]["tokens_relayed"] > 0


def test_committed_disagg_artifact_schema():
    """BENCH_disagg.json (ISSUE 12): schema + the correctness invariants
    the acceptance bar names — token-exact phase split with zero replayed
    tokens, zero dropped streams, and a sawtooth the autoscaler tracked."""
    path = REPO / "BENCH_disagg.json"
    assert path.exists(), "commit BENCH_disagg.json (make disagg-bench)"
    artifact = json.loads(path.read_text())
    missing = DISAGG_REQUIRED_KEYS - set(artifact)
    assert not missing, f"disagg artifact missing keys: {sorted(missing)}"
    assert artifact["metric"] == "disagg_flood_and_autoscale"
    flood = artifact["flood"]
    for arm in ("mixed", "disagg"):
        missing = DISAGG_FLOOD_ARM_KEYS - set(flood[arm])
        assert not missing, f"{arm} arm missing: {sorted(missing)}"
    assert flood["token_exact"] is True
    assert flood["dropped_streams"] == 0
    assert flood["disagg"]["disagg_dispatches"] > 0
    assert flood["disagg"]["resume_replayed_tokens"] == 0
    assert flood["mixed"]["disagg_dispatches"] == 0  # the control is pure
    saw = artifact["sawtooth"]
    missing = DISAGG_SAWTOOTH_KEYS - set(saw)
    assert not missing, f"sawtooth missing: {sorted(missing)}"
    assert saw["dropped_streams"] == 0 and saw["hung"] == 0
    assert saw["streams_done"] == saw["streams"]
    assert saw["autoscale_ups"] >= 1 and saw["autoscale_downs"] >= 1
    assert saw["max_replicas_seen"] > saw["min_replicas_seen"]
    assert set(artifact["platform"]) == {"backend", "device"}


def test_committed_tenant_artifact_schema():
    """BENCH_tenant.json (ISSUE 18): schema + the correctness invariants
    the acceptance bar names — every gold stream done and token-exact,
    zero dropped streams, a flood that was actually throttled with every
    rejection retryable, and an engaged isolation plane."""
    path = REPO / "BENCH_tenant.json"
    assert path.exists(), "commit BENCH_tenant.json (make tenant-bench)"
    artifact = json.loads(path.read_text())
    missing = TENANT_REQUIRED_KEYS - set(artifact)
    assert not missing, f"tenant artifact missing keys: {sorted(missing)}"
    assert artifact["metric"] == "tenant_isolation"
    for arm_name in ("baseline", "flood"):
        arm = artifact[arm_name]
        missing = TENANT_ARM_KEYS - set(arm)
        assert not missing, f"{arm_name} arm missing: {sorted(missing)}"
        assert arm["gold_done"] == arm["gold_offered"] > 0
        assert arm["dropped_streams"] == 0
        for pcts in (arm["gold_e2e_ms"], arm["gold_ttft_ms"]):
            assert set(pcts) == {"p50", "p99"}
    assert artifact["token_exact"] is True
    assert artifact["dropped_streams"] == 0
    # the control arm had no flood; the flood arm was really throttled
    assert artifact["baseline"]["flood_attempts"] == 0
    flood = artifact["flood"]
    assert flood["flood_rejected"] > 0
    assert flood["flood_bad_rejections"] == 0
    assert sum(flood["isolation_counters"].values()) > 0
    assert artifact["value"] > 0
    assert artifact["value"] <= artifact["isolation_factor_limit"]
    assert set(artifact["platform"]) == {"backend", "device"}


def test_loadgen_sawtooth_segment_live(tmp_path):
    """The autoscale segment end to end on stub replicas: the control loop
    must spawn under the burst, retire in the trough, and drop nothing.
    (The flood A/B runs real engines and lives in make disagg-bench; its
    committed artifact is schema-checked above.)"""
    loadgen = _load()
    out = tmp_path / "BENCH_disagg.json"
    artifact = loadgen.main(["--sawtooth", "--out", str(out)])
    on_disk = json.loads(out.read_text())
    assert on_disk == artifact
    saw = artifact["sawtooth"]
    assert saw["dropped_streams"] == 0
    assert saw["streams_done"] == saw["streams"]
    assert saw["autoscale_ups"] >= 1 and saw["autoscale_downs"] >= 1


def test_serve_bench_guard_disagg_logic():
    """Disagg-artifact guard branch: correctness + the within-artifact A/B
    grade on ANY hardware; only the cross-run ratio is platform-gated."""
    spec = importlib.util.spec_from_file_location(
        "serve_bench_guard", REPO / "scripts" / "serve_bench_guard.py"
    )
    guard = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(guard)

    def arm(deg, dispatches=0, replayed=0):
        return {
            "itl_bg_p50_degradation": deg,
            "disagg_dispatches": dispatches,
            "resume_replayed_tokens": replayed,
            "streams_done": True, "hung": 0, "dropped_streams": 0,
        }

    good = {
        "metric": "disagg_flood_and_autoscale",
        "platform": {"backend": "cpu", "device": "x"},
        "flood": {
            "token_exact": True, "dropped_streams": 0,
            "mixed": arm(1.8), "disagg": arm(1.1, dispatches=5),
        },
        "sawtooth": {
            "streams": 12, "streams_done": 12, "hung": 0,
            "dropped_streams": 0, "autoscale_ups": 2, "autoscale_downs": 1,
        },
    }
    ok, _ = guard.compare(good, json.loads(json.dumps(good)))
    assert ok
    # dropped streams fail on any hardware
    bad = json.loads(json.dumps(good))
    bad["flood"]["dropped_streams"] = 1
    ok, msgs = guard.compare(good, bad)
    assert not ok and any("dropped" in m for m in msgs)
    # replayed tokens on the disagg arm fail (the zero-recompute claim)
    bad = json.loads(json.dumps(good))
    bad["flood"]["disagg"]["resume_replayed_tokens"] = 40
    ok, msgs = guard.compare(good, bad)
    assert not ok and any("replayed" in m for m in msgs)
    # on a CPU box the isolation ratio is recorded but NOT graded (both
    # replicas share the same cores — scheduler noise, not isolation)
    noisy = json.loads(json.dumps(good))
    noisy["flood"]["disagg"]["itl_bg_p50_degradation"] = 9.0
    ok, msgs = guard.compare(good, noisy)
    assert ok and any("share the same cores" in m for m in msgs)
    # on an accelerator the within-artifact A/B grades — even when the
    # baseline came from foreign hardware
    tpu = json.loads(json.dumps(good))
    tpu["platform"] = {"backend": "tpu", "device": "v4"}
    bad = json.loads(json.dumps(tpu))
    bad["flood"]["disagg"]["itl_bg_p50_degradation"] = 9.0
    ok, msgs = guard.compare(good, bad)
    assert not ok and any("isolating" in m for m in msgs)
    # an idle autoscaler fails: the sawtooth exists to prove tracking
    bad = json.loads(json.dumps(good))
    bad["sawtooth"]["autoscale_downs"] = 0
    ok, msgs = guard.compare(good, bad)
    assert not ok and any("autoscaler" in m for m in msgs)
    # cross-run regression: graded on matching ACCELERATOR hardware...
    worse = json.loads(json.dumps(tpu))
    worse["flood"]["disagg"]["itl_bg_p50_degradation"] = 1.4
    ok, msgs = guard.compare(tpu, worse)
    assert not ok and any("baseline" in m for m in msgs)
    # ...and skipped across a hardware mismatch
    worse["platform"] = {"backend": "tpu", "device": "v5e"}
    ok, msgs = guard.compare(tpu, worse)
    assert ok and any("SKIP" in m for m in msgs)


def test_serve_bench_guard_tenant_logic():
    """Tenant-artifact guard branch: correctness fields fail on ANY
    hardware; the gold p99 ratio is CPU-honesty gated (recorded, not
    graded, on a shared-core box) and baseline-gated on accelerators."""
    spec = importlib.util.spec_from_file_location(
        "serve_bench_guard", REPO / "scripts" / "serve_bench_guard.py"
    )
    guard = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(guard)

    def arm(label, attempts=0, rejected=0, counters=0):
        return {
            "label": label, "gold_e2e_ms": {"p50": 30.0, "p99": 50.0},
            "gold_ttft_ms": {"p50": 10.0, "p99": 20.0},
            "gold_done": 8, "gold_offered": 8,
            "flood_attempts": attempts, "flood_ok": 0,
            "flood_rejected": rejected, "flood_bad_rejections": 0,
            "dropped_streams": 0,
            "isolation_counters": {"router_rejected_quota": counters},
        }

    good = {
        "metric": "tenant_isolation", "value": 2.0,
        "isolation_factor_limit": 5.0,
        "platform": {"backend": "cpu", "device": "x"},
        "baseline": arm("baseline"),
        "flood": arm("flood", attempts=100, rejected=90, counters=90),
        "token_exact": True, "dropped_streams": 0,
    }
    ok, msgs = guard.compare(good, json.loads(json.dumps(good)))
    assert ok and any("not graded" in m for m in msgs)
    # correctness fails on any hardware
    bad = json.loads(json.dumps(good))
    bad["flood"]["gold_done"] = 7
    ok, msgs = guard.compare(good, bad)
    assert not ok and any("gold streams" in m for m in msgs)
    bad = json.loads(json.dumps(good))
    bad["flood"]["flood_rejected"] = 0
    bad["flood"]["isolation_counters"] = {"router_rejected_quota": 0}
    ok, msgs = guard.compare(good, bad)
    assert not ok and any("never throttled" in m for m in msgs)
    bad = json.loads(json.dumps(good))
    bad["flood"]["flood_bad_rejections"] = 3
    ok, msgs = guard.compare(good, bad)
    assert not ok and any("retryable" in m for m in msgs)
    # on CPU an awful ratio is recorded, not graded (shared cores)
    noisy = json.loads(json.dumps(good))
    noisy["value"] = 40.0
    ok, msgs = guard.compare(good, noisy)
    assert ok and any("cpu backend" in m for m in msgs)
    # on an accelerator the pinned factor grades...
    tpu = json.loads(json.dumps(good))
    tpu["platform"] = {"backend": "tpu", "device": "v4"}
    bad = json.loads(json.dumps(tpu))
    bad["value"] = 9.0
    ok, msgs = guard.compare(tpu, bad)
    assert not ok and any("pinned isolation factor" in m for m in msgs)
    # ...so does the baseline tolerance on matching hardware...
    worse = json.loads(json.dumps(tpu))
    worse["value"] = 3.0
    ok, msgs = guard.compare(tpu, worse)
    assert not ok and any("baseline" in m for m in msgs)
    # ...and a hardware mismatch skips the ratio but kept correctness
    worse["platform"] = {"backend": "tpu", "device": "v5e"}
    ok, msgs = guard.compare(tpu, worse)
    assert ok and any("SKIP" in m for m in msgs)


def test_serve_bench_guard_router_logic():
    """Router-artifact guard branch: correctness fields fail on ANY
    hardware, the scaling bar only grades against a matching-platform
    baseline."""
    spec = importlib.util.spec_from_file_location(
        "serve_bench_guard", REPO / "scripts" / "serve_bench_guard.py"
    )
    guard = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(guard)

    good = {
        "metric": "router_scaling_tok_s", "value": 3.4,
        "dropped_streams": 0,
        "failover": {"token_exact": True, "resumed_streams": 1},
        "rolling_reload": {"ok": True, "steps": 3, "dropped_streams": 0},
        "platform": {"backend": "cpu", "device": "x"},
        "fleet_trace": {"coverage_min": 0.99, "orphans": 0,
                        "hops_ordered": True, "requests": 4},
        "slo": {"verdict": "ok", "objectives": {}},
    }
    ok, _ = guard.compare(good, dict(good))
    assert ok
    # an SLO verdict of violated fails on matching hardware (ISSUE 15)...
    bad_slo = {**good, "slo": {"verdict": "violated", "objectives": {
        "availability": {"state": "fast_burn"}}}}
    ok, msgs = guard.compare(good, bad_slo)
    assert not ok and any("SLO" in m for m in msgs)
    # ...but skips with the other perf grades across a hardware mismatch
    ok, msgs = guard.compare(
        good, {**bad_slo, "platform": {"backend": "tpu", "device": "v4"}}
    )
    assert ok and any("SKIP" in m for m in msgs)
    # a broken stitched trace is correctness — fails anywhere
    ok, msgs = guard.compare(good, {
        **good, "platform": {"backend": "tpu", "device": "v4"},
        "fleet_trace": {"coverage_min": 0.5, "orphans": 0,
                        "hops_ordered": True},
    })
    assert not ok and any("coverage" in m for m in msgs)
    ok, msgs = guard.compare(good, {
        **good,
        "fleet_trace": {"coverage_min": 0.99, "orphans": 2,
                        "hops_ordered": True},
    })
    assert not ok and any("stitched" in m for m in msgs)
    # pre-PR15 artifacts (no fleet_trace/slo blocks) still grade cleanly
    legacy = {k: v for k, v in good.items()
              if k not in ("fleet_trace", "slo")}
    ok, _ = guard.compare(legacy, dict(legacy))
    assert ok
    # below the absolute near-linear bar fails on matching hardware
    ok, msgs = guard.compare(good, {**good, "value": 2.4})
    assert not ok and any("near-linear" in m for m in msgs)
    # >15% below the committed baseline fails even above the bar
    ok, msgs = guard.compare({**good, "value": 3.9}, {**good, "value": 3.2})
    assert not ok and any("baseline" in m for m in msgs)
    # hardware mismatch: scaling SKIPS instead of failing...
    other_hw = {**good, "value": 2.4,
                "platform": {"backend": "tpu", "device": "v4"}}
    ok, msgs = guard.compare(good, other_hw)
    assert ok and any("SKIP" in m for m in msgs)
    # ...but dropped streams / a non-exact failover / a failed reload are
    # correctness, and fail everywhere
    ok, msgs = guard.compare(good, {**other_hw, "dropped_streams": 1})
    assert not ok and any("dropped_streams" in m for m in msgs)
    ok, msgs = guard.compare(
        good, {**good, "failover": {"token_exact": False}}
    )
    assert not ok and any("token-exact" in m for m in msgs)
    ok, msgs = guard.compare(
        good,
        {**good, "rolling_reload": {"ok": True, "steps": 3,
                                    "dropped_streams": 2}},
    )
    assert not ok and any("rolling reload" in m for m in msgs)
    # a throughput artifact as "baseline" (metric mismatch) has no
    # comparable scaling number: the grade skips, correctness still checked
    ok, msgs = guard.compare({"metric": "serve_tokens_per_sec_test",
                              "platform": good["platform"]}, good)
    assert ok
    ok, msgs = guard.compare(
        {"metric": "serve_tokens_per_sec_test"},
        {**good, "dropped_streams": 3},
    )
    assert not ok


def test_serve_bench_guard_logic():
    """The regression guard fails loudly on >15% regressions when the
    hardware matches and skips (never fails) when it does not."""
    spec = importlib.util.spec_from_file_location(
        "serve_bench_guard", REPO / "scripts" / "serve_bench_guard.py"
    )
    guard = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(guard)

    base = {
        "decode_tok_s": 600.0, "itl_ms": {"p99": 2.0},
        "platform": {"backend": "cpu", "device": "x"}, "workload": "mixed",
    }
    same = dict(base)
    ok, _ = guard.compare(base, same)
    assert ok
    slow = {**base, "decode_tok_s": 400.0}
    ok, msgs = guard.compare(base, slow)
    assert not ok and any("decode_tok_s" in m for m in msgs)
    tail = {**base, "itl_ms": {"p99": 5.0}}
    ok, msgs = guard.compare(base, tail)
    assert not ok and any("p99" in m for m in msgs)
    # within tolerance passes
    ok, _ = guard.compare(base, {**base, "decode_tok_s": 540.0,
                                 "itl_ms": {"p99": 2.2}})
    assert ok
    # decode-only ITL tail (the paged kernel's home metric) is graded
    # too, and absent blocks (older baselines) are skipped, not failed
    both = {**base, "itl_ms_decode_only": {"p99": 1.0}}
    ok, msgs = guard.compare(both, {**both, "itl_ms_decode_only": {"p99": 1.5}})
    assert not ok and any("decode_only" in m for m in msgs)
    ok, _ = guard.compare(both, {**both, "itl_ms_decode_only": {"p99": 1.1}})
    assert ok
    ok, _ = guard.compare(base, both)
    assert ok
    # different hardware: a regression-shaped delta SKIPS instead of failing
    other_hw = {**slow, "platform": {"backend": "tpu", "device": "v4"}}
    ok, msgs = guard.compare(base, other_hw)
    assert ok and any("SKIP" in m for m in msgs)
    # pre-platform-field baselines can only skip
    ok, msgs = guard.compare({"decode_tok_s": 600.0, "itl_ms": {"p99": 2.0}}, slow)
    assert ok and any("SKIP" in m for m in msgs)
    # mismatched metrics (another kind of artifact) skip, not fail
    other = {"metric": "some_other_metric", "value": 8.0,
             "platform": {"backend": "cpu", "device": "x"}}
    ok, msgs = guard.compare(other, base)
    assert ok and any("SKIP" in m for m in msgs)
    # span-tracing overhead budget: >2% in the fresh artifact's own A/B
    # fails on matching hardware; <=2% passes; absent (no --obs-ab) passes
    heavy = {**base, "obs_overhead": {
        "overhead_frac": 0.05, "decode_tok_s_trace_off": 600.0,
        "decode_tok_s_trace_on": 570.0, "repeats": 3}}
    ok, msgs = guard.compare(base, heavy)
    assert not ok and any("tracing overhead" in m for m in msgs)
    light = {**base, "obs_overhead": {
        "overhead_frac": 0.01, "decode_tok_s_trace_off": 600.0,
        "decode_tok_s_trace_on": 594.0, "repeats": 3}}
    ok, _ = guard.compare(base, light)
    assert ok
    # hardware mismatch still skips BEFORE the overhead check fires
    ok, msgs = guard.compare(base, {**heavy, "platform": {"backend": "tpu",
                                                          "device": "v4"}})
    assert ok and any("SKIP" in m for m in msgs)


def test_loadgen_request_mix_is_deterministic():
    """Two processes building the mix must agree (the parity check decodes
    the reference from the same (prompt, seed) pairs)."""
    loadgen = _load()
    args = loadgen.parse_args(["--requests", "5"])
    a = loadgen.make_requests(args, 256, 32)
    b = loadgen.make_requests(args, 256, 32)
    assert a == b
    assert len(a) == 5
    assert all(2 <= len(p) <= 8 for p, _ in a)
    seeds = [s for _, s in a]
    assert seeds == list(range(5))  # seed = base + index

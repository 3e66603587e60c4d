# Developer entry points, mirroring CI (.github/workflows/ci.yml).
# Capability match: reference Makefile:1-6 (format + test targets).

PY ?= python

.PHONY: test test-full chaos elastic-chaos serve-chaos router-chaos disagg-chaos tenant-chaos chaos-fleet obs bench serve-bench tenant-bench train-bench kernel-bench tune tune-smoke fmt fmt-check dryrun lint

# Invariant lint lane (ISSUE 10): graftlint's repo-specific AST rules +
# the suppression audit over the whole tree. Pure stdlib — no jax import,
# no backend init — so it costs seconds. Exit 1 on any unsuppressed
# finding; every suppression's reason is printed for review. The same
# gate runs in the quick lane as test_graftlint.py::test_tree_is_clean.
lint:
	$(PY) scripts/graftlint.py --audit

# Quick lane: everything but tests marked slow (multi-process jax.distributed,
# long training loops, heavy cross-stage numerics). This is what CI runs on
# every push; CI adds PYTEST_ARGS="-n auto" (pytest-xdist) for multi-core.
# tests/conftest.py keeps a persistent XLA compilation cache (override dir
# via JAX_COMPILATION_CACHE_DIR); warm-cache timing 2026-07-30: full suite
# 273 passed in 9m20 at -n 4 on a heavily loaded box (cold cache ran >2x
# that). CI persists the cache across runs via actions/cache.
test:
	$(PY) -m pytest tests/ -x -q -m "not slow" $(PYTEST_ARGS)

# Full lane: the whole suite, nightly in CI.
test-full:
	$(PY) -m pytest tests/ -x -q $(PYTEST_ARGS)

# Fault-injection lane: every chaos-marked scenario (supervised recovery
# from injected loader/checkpoint/hang/preemption faults). The deterministic
# fast resilience cases are UN-marked and already run in the quick lane.
chaos:
	$(PY) -m pytest tests/test_resilience.py -q -m chaos $(PYTEST_ARGS)

# Trustworthy-restore lane: the elastic + integrity chaos suite — corrupt
# (truncated / bit-flipped) checkpoints quarantined with fallback, replica
# desync caught by the cross-replica audit, plus the full checkpoint
# integrity and elastic-resume test files. The multi-process elastic test
# (save on 8 simulated devices, resume on 4, and 4 -> 8) is slow-marked and
# runs in the full lane: tests/test_multihost.py::test_elastic_resume_across_world_sizes.
elastic-chaos:
	$(PY) -m pytest tests/test_resilience.py -q -m chaos \
		-k "ckpt_corruption or replica" $(PYTEST_ARGS)
	$(PY) -m pytest tests/test_checkpoint.py tests/test_elastic.py -q $(PYTEST_ARGS)

# Serving fault-injection lane: the full chaos scenario over the HTTP
# server (decode faults + NaN-logit windows + mid-load SIGTERM -> graceful
# drain, untouched requests byte-identical). The fast deterministic serving
# resilience cases are un-marked and run in the quick lane.
serve-chaos:
	$(PY) -m pytest tests/test_serving_resilience.py -q -m chaos $(PYTEST_ARGS)

# Fleet-router fault-injection lane (ISSUE 9): 3 real subprocess replicas
# under live streaming load through the router — one SIGKILLed mid-stream
# (every in-flight stream must resume token-exact on a survivor or end with
# a retryable terminal event; the victim is ejected with a flight-recorder
# dump) — plus a rolling fleet reload under load with dropped_streams == 0.
# The fast deterministic router cases (registry state machine, routing
# policy, stub-fleet failover/reload over HTTP) are un-marked and run in
# the quick lane.
router-chaos:
	$(PY) -m pytest tests/test_router.py -q -m chaos $(PYTEST_ARGS)

# Training-fleet fault-injection lane (ISSUE 17): N real worker processes
# training under a supervising coordinator — one SIGKILLed mid-run (bounded
# replay <= snapshot interval, loss trajectory rejoins the unfaulted run
# bitwise), a heartbeat blackhole (declared dead, then rejoins), a SIGSTOP
# hang (survivors finish bitwise without it), a slow worker (detected as a
# straggler and shed), and a full-fleet kill (snapshot rewind, bounded
# replay). The fast deterministic fleet cases (shard assignment, fold
# algebra, registry edge cases, HTTP surface) are un-marked and run in the
# quick lane.
chaos-fleet:
	$(PY) -m pytest tests/test_fleet_train.py -q -m chaos $(PYTEST_ARGS)

# Disaggregated-fleet fault-injection lane (ISSUE 12): SIGKILL a
# prefill-role replica mid-long-prompt-flood (every stream finishes
# token-exact or ends retryably through the recompute fallback, zero
# drops, the fleet keeps serving without its prefill tier), and kill a
# migration's TARGET mid-transfer (the ship fails, the source degrades the
# stream retryably, the router's recompute fallback resumes it token-exact
# on a survivor). The fast deterministic disagg cases (page-span roundtrip,
# migration parity, autoscaler logic) are un-marked and run in the quick lane.
disagg-chaos:
	$(PY) -m pytest tests/test_serving_disagg.py -q -m chaos $(PYTEST_ARGS)

# Tenant-isolation fault-injection lane (ISSUE 18): the multi-tenant flood
# proof (one tenant floods a real 2-replica QoS fleet with batch work while
# a gold tenant's trickle must ALL complete with zero dropped streams and
# every flood rejection retryable with a Retry-After) plus the slow_client
# chaos case (a stalled SSE consumer hits its bounded emit buffer and ends
# retryably; the concurrent healthy stream stays byte-identical). The fast
# deterministic QoS cases (token buckets, DWRR fairness, brownout ladder,
# floors, preemption) are un-marked and run in the quick lane.
tenant-chaos:
	$(PY) -m pytest tests/test_qos.py -q -m chaos $(PYTEST_ARGS)

# Tenant-isolation bench (ISSUE 18): the gold-trickle A/B under a hostile
# batch flood on a real 2-replica QoS fleet -> BENCH_tenant.json (gold p99
# ratio graded on accelerators only — on a shared-core CPU box the flood
# steals the gold replica's cycles whatever the admission plane does;
# correctness graded everywhere). Schema pinned by tests/test_serve_bench.py.
tenant-bench:
	@cp BENCH_tenant.json /tmp/_serve_tenant_baseline.json 2>/dev/null || true
	JAX_PLATFORMS=cpu $(PY) scripts/serve_loadgen.py --tenant-flood
	@if [ -f /tmp/_serve_tenant_baseline.json ]; then \
		$(PY) scripts/serve_bench_guard.py /tmp/_serve_tenant_baseline.json BENCH_tenant.json; \
	else \
		echo "serve-bench-guard: no committed tenant baseline; skipping"; \
	fi

# Observability lane (ISSUE 7 + ISSUE 15): the obs test files (span-tree
# parity over every request outcome, Prometheus exposition conformance
# under live traffic, X-Request-Id round trip, flight-recorder dump on
# breaker-open, /admin/profile lifecycle, fleet stitching/aggregation/SLO/
# ledger) plus two smokes: a loadgen trace smoke (one small run must
# produce a Perfetto-loadable span trace with nonzero events) and the
# stub-fleet stitched-trace smoke (router + 2 stub replicas -> ONE merged
# fleet trace, programmatically verified: >=95% coverage, zero orphans,
# rollup sums pinned, /slo verdict ok).
obs:
	$(PY) -m pytest tests/test_obs.py tests/test_fleet_obs.py -q $(PYTEST_ARGS)
	JAX_PLATFORMS=cpu $(PY) scripts/serve_loadgen.py --requests 4 --slots 2 \
		--max-new-tokens 8 --cache-len 64 --out /tmp/_obs_smoke.json
	$(PY) -c "import json; t=json.load(open('/tmp/_obs_smoke.trace.json')); \
		n=len(t['traceEvents']); assert n, 'empty trace'; \
		print(f'obs trace smoke ok: {n} events')"
	$(PY) scripts/fleet_obs_smoke.py

# One-line JSON benchmark artifact (driver contract).
bench:
	$(PY) bench.py

# Continuous-batching serving bench: 8 concurrent clients against a 2-slot
# engine on the CPU test model (paged KV cache + chunked prefill by
# default), every response verified byte-identical to single-request
# generate(). Scenarios:
#  - headline mixed-length run, SPECULATION ON (greedy so the byte-parity
#    check stays exact) with an embedded spec-OFF control (no_speculation)
#    -> BENCH_serve.json — the spec-on/spec-off pair;
#  - shared-prefix run (N personas x one system prompt; with paging a hit
#    is a page-refcount bump) -> BENCH_serve_prefix.json;
#  - fleet-router scaling: paced stub replicas behind the real router,
#    aggregate relayed tok/s at 1/2/4 replicas + token-exact mid-stream
#    failover + rolling reload with zero drops -> BENCH_router.json (the
#    guard holds the >= 3x near-linear bar on matching hardware and the
#    correctness fields everywhere);
#  - disaggregation A/B + autoscale sawtooth (ISSUE 12): a long-prompt
#    flood against a mixed fleet vs a prefill/decode split fleet (real
#    engines, token-exact, zero replayed tokens), plus the autoscaler
#    tracking a sawtooth on stub replicas with zero drops
#    -> BENCH_disagg.json (isolation ratios graded on accelerators only —
#    on a shared-core CPU box both replicas compete for the same cores).
# A regression guard compares the fresh runs against the previously
# committed artifacts (>15% on decode_tok_s / itl p99 / router scaling
# fails loudly on matching hardware, skips otherwise).
# Schema pinned by tests/test_serve_bench.py.
serve-bench:
	@cp BENCH_serve.json /tmp/_serve_baseline.json 2>/dev/null || true
	@cp BENCH_router.json /tmp/_serve_router_baseline.json 2>/dev/null || true
	@cp BENCH_disagg.json /tmp/_serve_disagg_baseline.json 2>/dev/null || true
	JAX_PLATFORMS=cpu $(PY) scripts/serve_loadgen.py --requests 8 --slots 2 \
		--spec-k 4 --greedy --max-new-tokens 32 --cache-len 64 --obs-ab
	JAX_PLATFORMS=cpu $(PY) scripts/serve_loadgen.py --requests 8 --slots 2 \
		--shared-prefix --cache-len 64 --out BENCH_serve_prefix.json
	JAX_PLATFORMS=cpu $(PY) scripts/serve_loadgen.py --router
	JAX_PLATFORMS=cpu $(PY) scripts/serve_loadgen.py --long-prompt-flood \
		--sawtooth --cache-len 64 --max-new-tokens 12 --slots 2
	@if [ -f /tmp/_serve_baseline.json ]; then \
		$(PY) scripts/serve_bench_guard.py /tmp/_serve_baseline.json BENCH_serve.json; \
	else \
		echo "serve-bench-guard: no committed baseline; skipping"; \
	fi
	@if [ -f /tmp/_serve_router_baseline.json ]; then \
		$(PY) scripts/serve_bench_guard.py /tmp/_serve_router_baseline.json BENCH_router.json; \
	else \
		echo "serve-bench-guard: no committed router baseline; skipping"; \
	fi
	@if [ -f /tmp/_serve_disagg_baseline.json ]; then \
		$(PY) scripts/serve_bench_guard.py /tmp/_serve_disagg_baseline.json BENCH_disagg.json; \
	else \
		echo "serve-bench-guard: no committed disagg baseline; skipping"; \
	fi

# Training step-time decomposition lane (ISSUE 8): overlap-on/off A/B with
# in-process BITWISE gradient parity, compute/exposed-comm split vs a
# single-device baseline, the analytic bubble table (gpipe/1f1b/interleaved),
# a measured tiny pipe run where the backend can execute it, the per-op
# flash-vs-XLA attention microbench, and the assumption-labeled v5e
# projection -> BENCH_step.json. The guard compares against the committed
# artifact (parity must stay bitwise everywhere; timing/reduction graded on
# matching hardware only). Schema pinned by tests/test_train_bench.py.
train-bench:
	@cp BENCH_step.json /tmp/_step_baseline.json 2>/dev/null || true
	JAX_PLATFORMS=cpu $(PY) scripts/train_step_bench.py
	@if [ -f /tmp/_step_baseline.json ]; then \
		$(PY) scripts/train_bench_guard.py /tmp/_step_baseline.json BENCH_step.json; \
	else \
		echo "train-bench-guard: no committed baseline; skipping"; \
	fi

# Kernel lane (ISSUE 11): interpret-mode parity for the Pallas kernels on
# THIS box (flash train fwd+bwd and serving offset/mask shapes pinned
# few-ulp vs the XLA reference; the paged-attention decode kernel pinned
# few-ulp vs the gather path it replaces, int8 scales included)
# plus the shared interpret-mode parity report. Timed kernel numbers are
# TPU-only (bench.py's flash child refuses to run off the chip).
# docs/KERNELS.md documents the dispatch-gate decision table.
kernel-bench:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_paged_kernel.py \
		tests/test_flash_attention.py -q $(PYTEST_ARGS)
	JAX_PLATFORMS=cpu $(PY) -c "import json; \
		from zero_transformer_tpu.ops.pallas.parity import interpret_parity_report; \
		out = interpret_parity_report(); \
		print(json.dumps(out)); assert out['ok'], 'kernel parity failed'"

# Autotuner lanes (ISSUE 14, docs/TUNING.md). `tune` runs the real
# per-(model, hardware, workload) searches and rewrites the committed
# TUNE_train.json / TUNE_serve.json (re-run on new hardware — the
# artifacts only ever apply under a matching platform block). `tune-smoke`
# is the CI lane: a tiny space, 2 measured trials, two full passes, and
# asserts the artifact schema plus determinism (same winner + same trace
# fingerprint across the passes — the --reruns 2 gate inside the script),
# mirroring the BENCH schema tests; the committed-artifact schema itself
# is pinned by tests/test_autotune.py (TUNE_REQUIRED_KEYS).
tune:
	JAX_PLATFORMS=cpu $(PY) scripts/autotune.py --target serve --reruns 2
	JAX_PLATFORMS=cpu $(PY) scripts/autotune.py --target train --reruns 2

tune-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/autotune.py --target serve --smoke \
		--reruns 2 --out /tmp/_tune_smoke.json
	$(PY) -c "import json; \
		from zero_transformer_tpu.analysis.autotune import TUNE_REQUIRED_KEYS; \
		art = json.load(open('/tmp/_tune_smoke.json')); \
		missing = TUNE_REQUIRED_KEYS - art.keys(); \
		assert not missing, f'smoke artifact missing {sorted(missing)}'; \
		det = art['determinism']; \
		assert det['winner_stable'] and det['fingerprints_equal'], det; \
		print(f\"tune-smoke ok: winner {art['winner']['knobs']} \" \
		      f\"({art['value']}x), fingerprint {det['fingerprint']}\")"

# Multi-chip sharding dry-run on an 8-device virtual CPU mesh.
dryrun:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('dryrun ok')"

fmt:
	@$(PY) -c "import black" 2>/dev/null && $(PY) -m black zero_transformer_tpu tests train.py bench.py || echo "black not installed; skipping"
	@$(PY) -c "import isort" 2>/dev/null && $(PY) -m isort zero_transformer_tpu tests train.py bench.py || echo "isort not installed; skipping"

# Fails on misformatted code (or on a missing formatter) — safe to gate CI on.
fmt-check:
	$(PY) -m black --check zero_transformer_tpu tests train.py bench.py

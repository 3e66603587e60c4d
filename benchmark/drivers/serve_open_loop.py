"""Driver for traffic files of ``kind: serve_open_loop``: a model served by
``ServingEngine`` under an open loop.

The window drives ``ServingEngine.submit`` -> ``RequestHandle`` events with
``ServingEngine.run`` on its own thread, the loop ``run_server`` runs. One
thread submits at the due times the mix fixes, one collects every token of
every request as it arrives. Each request is timed from when it was DUE.

No family is named here: the configuration's ``reference`` key names the
plain reference that says which leaves there are and judges the served
tokens.
"""
from __future__ import annotations

import gc
import shutil
import sys
import threading
import time

import numpy as np

from benchmark import arith, harness, host_trace, traffic, weights
from benchmark import trace as trace_mod


class Client:
    """Submitter + collector. ``records[i]`` belongs to ``requests[i]``."""

    def __init__(self, engine, requests, t0: float, drain_s: float, window_s: float):
        self.engine, self.requests, self.t0 = engine, requests, t0
        self.deadline = t0 + window_s + drain_s
        self.records = [
            {"due": t0 + r.due_s, "submitted": None, "token_times": [],
             "tokens": [], "status": None, "handle": None}
            for r in requests
        ]
        self._open: list = []
        self._submitted_all = threading.Event()
        self.threads = [threading.Thread(target=self._submit, name="bench-submit"),
                        threading.Thread(target=self._collect, name="bench-collect")]

    def _submit(self):
        for i, r in enumerate(self.requests):
            due = self.t0 + r.due_s
            while True:
                wait = due - time.monotonic()
                if wait <= 0:
                    break
                time.sleep(min(wait, 0.05))
            handle = self.engine.submit(list(r.prompt), max_new_tokens=r.max_new_tokens,
                                        seed=i)
            rec = self.records[i]
            rec["submitted"] = time.monotonic()
            rec["handle"] = handle
            self._open.append(i)
        self._submitted_all.set()

    def _collect(self):
        open_now: list = []
        taken = 0
        while True:
            while taken < len(self._open):
                open_now.append(self._open[taken])
                taken += 1
            still = []
            for i in open_now:
                rec = self.records[i]
                done = False
                while True:
                    ev = rec["handle"].next_event(timeout=0)
                    if ev is None:
                        break
                    kind, value = ev
                    if kind == "token":
                        rec["token_times"].append(time.monotonic())
                        rec["tokens"].append(int(value))
                    else:
                        rec["status"] = value
                        done = True
                        break
                if not done:
                    still.append(i)
            open_now = still
            if self._submitted_all.is_set() and not open_now and taken == len(self._open):
                return
            if time.monotonic() > self.deadline:
                return
            time.sleep(0.0005)

    def run(self):
        for t in self.threads:
            t.start()
        for t in self.threads:
            t.join()


def build_engine(cell, params, out_dir):
    from zero_transformer_tpu.inference import SamplingConfig
    from zero_transformer_tpu.serving import ServingEngine

    mix = cell["traffic"]
    cfg = harness.model_config(cell["config"], mix.get("model_overrides"))
    return ServingEngine(
        cfg, params, sampling=SamplingConfig(**mix["sampling"]), eos_token_id=None,
        obs_dir=str(out_dir), **mix["engine"],
    )


def serve_and_collect(engine, requests, window_s, drain_s, during=None):
    """Open the window now; returns (t0, records)."""
    t0 = time.monotonic()
    client = Client(engine, requests, t0, drain_s, window_s)
    side = None
    if during is not None:
        side = threading.Thread(target=during, args=(t0,), name="bench-side")
        side.start()
    client.run()
    if side is not None:
        side.join()
    return t0, client.records


GAP_STATISTICS = ("max", "p90")  # what a mix's ``limits`` may name, as served_logit_gap_<s>


def reference_gaps(cell, seed, records, sample_ids, tokens_from=None, whole_tree=False):
    """The gap of EVERY judged token of the sampled requests: by how much a
    served token's logit lies below the reference's best at its position.
    With ``tokens_from`` (a mode), the tokens judged are the ones that
    precision puts first at each position of the same prompts and served
    tokens: the control.

    The reference runs in blocks wherever the family offers
    ``logits_by_blocks``: it is handed a way to make any leaf of its table
    from the seed, bit for bit what ``weights.build`` gives, and never holds
    the tree. A family without one (or ``whole_tree``, for the comparison of
    the two paths) gets the whole float32 tree."""
    import jax
    import jax.numpy as jnp

    model, mix = cell["config"]["model"], cell["traffic"]
    ref = harness.load_reference(cell["config"])
    table, key = ref.leaf_table(model), weights.seed_key(seed, "weights")
    if hasattr(ref, "logits_by_blocks") and not whole_tree:
        make = weights.leaf_maker(table, key)

        def forward(toks, mode):
            return ref.logits_by_blocks(make, toks, model, mode)
    else:
        params = weights.build(table, key)

        def forward(toks, mode):
            return ref.logits(params, toks, model, mode)

    pad_to = mix["reference"]["pad_to"]
    gaps: list = []
    for rid in sample_ids:
        prompt, served = records[rid]["prompt"], records[rid]["tokens"]
        if not served:
            continue
        seq = list(prompt) + list(served)
        T = len(seq)
        padded = seq + [0] * ((-T) % pad_to)
        toks = jnp.asarray([padded], jnp.int32)
        with jax.default_matmul_precision("highest"):
            rows = forward(toks, "f32")[0][len(prompt) - 1: T - 1]
            if tokens_from is None:
                judged = jnp.asarray(served, jnp.int32)
            else:
                low = forward(toks, tokens_from)[0]
                judged = jnp.argmax(low[len(prompt) - 1: T - 1], axis=-1)
        gap = jnp.max(rows, axis=-1) - jnp.take_along_axis(rows, judged[:, None], axis=-1)[:, 0]
        gaps.extend(np.asarray(gap).tolist())
    return gaps


def gap_statistics(gaps: list) -> dict:
    """Over all judged tokens: the widest gap, which reads the rarest flip,
    and beside it the 90th percentile, the mean and the share of tokens that
    are not the reference's first at all, which read the program."""
    if not gaps:
        return {"tokens": 0, "max": None, "p90": None, "mean": None, "not_first": None}
    return {"tokens": len(gaps), "max": max(gaps), "p90": arith.percentile(gaps, 90),
            "mean": sum(gaps) / len(gaps), "not_first": sum(g > 0 for g in gaps) / len(gaps)}


def pick_sample(records, seed, k):
    """The longest finished request and k-1 more drawn from the seed."""
    done = [i for i, r in enumerate(records) if r["status"] == "done" and r["tokens"]]
    if not done:
        return []
    longest = max(done, key=lambda i: len(records[i]["prompt"]) + len(records[i]["tokens"]))
    rng = np.random.default_rng([int(seed), 4])
    rest = [i for i in done if i != longest]
    extra = list(rng.choice(rest, size=min(k - 1, len(rest)), replace=False)) if rest else []
    return [longest] + [int(i) for i in extra]


def run(cell: dict, devices, seed: int, seconds: float, trace: bool,
        control_modes=()) -> dict:
    import jax

    mix, model = cell["traffic"], cell["config"]["model"]
    out_dir = harness.ROOT / ".bench_out" / cell["name"]
    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True)
    compiles = harness.CompileCounter()
    since = time.monotonic()
    phases = [("imports", since - harness.T_START)]

    def phase(name):
        nonlocal since
        now = time.monotonic()
        phases.append((name, now - since))
        since = now

    ref = harness.load_reference(cell["config"])
    key = weights.seed_key(seed, "weights")
    dtype = jax.numpy.dtype(mix["params_dtype"])
    params = jax.block_until_ready(weights.build(ref.leaf_table(model), key, dtype))
    phase("weights")
    engine = build_engine(cell, params, out_dir)
    phase("engine")
    stop = threading.Event()
    tick = threading.Thread(target=engine.run, args=(stop,), name="engine-run")
    tick.start()
    try:
        warm = traffic.warmup_requests(mix, seed, model["vocab_size"])
        _, warm_records = serve_and_collect(engine, warm, 0.0, 300.0)
        if any(r["status"] != "done" for r in warm_records):
            raise SystemExit(f"warm-up failed: {[r['status'] for r in warm_records]}")
        phase(f"{len(warm)} warm-up requests, {compiles.count} programs")
        print("set-up phases (s): " + ", ".join(f"{k} {v:.1f}" for k, v in phases),
              file=sys.stderr)
        requests = traffic.open_loop_requests(mix, seed, seconds, model["vocab_size"])
        profile = {}

        def take_trace(t0):
            # the benchmark's own capture, Python tracing off, while the
            # window's load is on the engine
            time.sleep(seconds * mix["trace_at_fraction"])
            with trace_mod.capture(out_dir / "profile"):
                profile["t0"] = time.monotonic()
                time.sleep(min(mix["trace_seconds"], seconds * 0.5))
                profile["t1"] = time.monotonic()

        compiled_before = compiles.count
        t0, records = serve_and_collect(
            engine, requests, seconds, mix["drain_seconds"],
            during=take_trace if trace else None)
        t_end = time.monotonic()
        compiled_in_window = compiles.count - compiled_before
        spans = engine.tracer.spans()
        counters = engine.metrics_snapshot()
        device = harness.device_block(devices)
    finally:
        stop.set()
        tick.join(timeout=120)
    setup_s = t0 - harness.T_START
    for rec, req in zip(records, requests):
        rec["prompt"] = req.prompt
        rec["max_new_tokens"] = req.max_new_tokens
        handle = rec.pop("handle", None)
        rec["prefill_done_at"] = getattr(handle, "prefill_done_at", None)
        rec["finished_at"] = getattr(handle, "finished_at", None)

    lateness = [(r["submitted"] - r["due"]) * 1e3 for r in records if r["submitted"]]
    print(f"generator lateness ms: p50 {arith.percentile(lateness, 50):.3f} "
          f"p99 {arith.percentile(lateness, 99):.3f} max {max(lateness):.3f}; "
          f"requests {len(records)}; compiles in window {compiled_in_window}",
          file=sys.stderr)

    close = t0 + seconds
    backlog = sum(1 for r in records
                  if not r["token_times"] or r["token_times"][0] > close)
    half = [(r["token_times"][0] - r["due"]) * 1e3 for r in records if r["token_times"]]
    mid = len(half) // 2
    served = [t for r in records for t in r["tokens"]]
    ttft_halves = [arith.percentile(half[:mid] or [0], 50), arith.percentile(half[mid:] or [0], 50)]
    print(f"backlog at close {backlog}; ttft p50 first half "
          f"{ttft_halves[0]:.1f} ms, second half {ttft_halves[1]:.1f} ms; drained "
          f"{t_end - close:.2f} s after the close; distinct served tokens "
          f"{len(set(served))} of {len(served)}", file=sys.stderr)
    ticks = [(e - s, s - t0) for _, track, name, s, e, _ in spans
             if track == "engine" and name == "tick" and t0 <= s <= close]
    if ticks:
        longest, at = max(ticks)
        print(f"longest engine tick in the window {longest * 1e3:.1f} ms at +{at:.2f} s "
              f"of {len(ticks)}", file=sys.stderr)
    failed = [i for i, r in enumerate(records)
              if r["status"] != "done" or len(r["tokens"]) != r["max_new_tokens"]]
    # a request that never produced a token has waited to the end of the drain
    first = [r["token_times"][0] if r["token_times"] else t_end for r in records]
    ttft = arith.ttft_ms([r["due"] for r in records], first)
    gaps = arith.gaps_ms([r["token_times"] for r in records])
    delivered = arith.tokens_in_window([r["token_times"] for r in records], t0, t0 + seconds)

    # free the engine before the reference takes the chip
    del engine, params
    gc.collect()

    sample = pick_sample(records, seed, mix["reference"]["sample"])
    t_ref, compiled_before = time.monotonic(), compiles.count
    judged = gap_statistics(reference_gaps(cell, seed, records, sample))
    reference_s = time.monotonic() - t_ref
    print(f"reference: {len(sample)} requests, {judged['tokens']} tokens judged in "
          f"{reference_s:.1f} s, {compiles.count - compiled_before} programs compiled",
          file=sys.stderr)
    # a control is a mode's tokens on the same prompts and served tokens;
    # "whole_tree" is the served tokens again, through the whole-tree path
    controls = {
        f"control_{m}": gap_statistics(reference_gaps(
            cell, seed, records, sample,
            **({"whole_tree": True} if m == "whole_tree" else {"tokens_from": m})))
        for m in control_modes}
    wrong_count = sum(1 for r in records
                      if r["status"] == "done" and len(r["tokens"]) != r["max_new_tokens"])
    # the mix's ``limits`` say which statistics of the gap decide ``correct``
    named = [s for s in GAP_STATISTICS if f"served_logit_gap_{s}" in mix["limits"]]
    if not named:
        raise SystemExit(f"the mix's limits name no served_logit_gap_<{'|'.join(GAP_STATISTICS)}>")
    compared = {
        **{f"served_logit_gap_{s}": {"value": judged[s],
                                     "limit": mix["limits"][f"served_logit_gap_{s}"],
                                     "tokens": judged["tokens"]} for s in named},
        "served_count_mismatch": {"value": float(wrong_count), "limit": 0.0},
        "compiles_in_window": {"value": float(compiled_in_window), "limit": 0.0},
    }
    result = {
        "correct": harness.decide(compared),
        "attempted": len(records),
        "failed": len(failed),
        "metrics": {},
        "device": device,
        "reference_s": reference_s,
        "served_logit_gap": judged,  # every statistic of the judged tokens, compared or not
        "lateness_ms_max": max(lateness),
        "ttft_p95_ms": arith.percentile(ttft, 95),  # a per-layer metric; here for the sweep
        "backlog_at_close": backlog,
        "ttft_p50_halves_ms": ttft_halves,  # a queue that grows shows in the second
        "drain_s": t_end - close,
        **controls,
        "compared": compared,
    }
    if not trace:
        result["metrics"] = {
            "itl_p95_ms": {"value": arith.percentile(gaps, 95), "unit": "ms"},
            "serve_tokens_per_s": {"value": arith.rate(delivered, seconds), "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        return result

    reduced = trace_mod.reduce_dir(out_dir / "profile", len(devices))
    ctx = {
        "kind": "serve_open_loop", "model": model, "mix": mix, "chips": len(devices),
        "active_params": ref.active_params(model),
        "attention_flops_per_position": ref.attention_flops_per_position(model),
        "spans": spans, "records": records, "ttft_ms": ttft, "t0": t0, "t_end": t_end,
        "window_s": seconds, "counters": counters, "trace": reduced,
        "traced": (profile["t0"], profile["t1"]),
        "peak": arith.load_peak(devices[0].device_kind, harness.HERE / "peaks.json"),
    }
    result["metrics"] = harness.read_per_layer(cell, ctx)
    result["device"]["busy_s"] = reduced["busy_s"]
    result["device"]["window_s"] = reduced["window_s"]
    result["breakdown"] = reduced["breakdown"]
    loaded = host_trace.load()
    result["capture_programs"] = host_trace.programs(loaded) if loaded else {}
    print(f"programs in the {reduced['window_s']:.2f} s capture: " + ", ".join(
        f"{name} {n} ({sec:.3f} s)" for name, (n, sec) in result["capture_programs"].items()),
        file=sys.stderr)
    return result


def calibrate(cell, devices, seeds, controls, seconds):
    """Yields, per seed, the numbers the limit is set from: a short window at
    the cell's own load, every statistic of the program's gap and, for the
    first ``controls`` seeds, of the gap of the tokens the reference puts
    first in fp8 and in bfloat16; where the family's reference runs in
    blocks, also the program's gap by the whole-tree path on the same served
    tokens."""
    modes = ("fp8", "bf16")
    if hasattr(harness.load_reference(cell["config"]), "logits_by_blocks"):
        modes += ("whole_tree",)
    for i, seed in enumerate(seeds):
        res = run(cell, devices, seed=seed, seconds=seconds, trace=False,
                  control_modes=modes if i < controls else ())
        yield {
            "seed": seed,
            "program": {k: v["value"] for k, v in res["compared"].items()},
            "served_logit_gap": res["served_logit_gap"],
            **{k: v for k, v in res.items() if k.startswith("control_")},
            "failed": res["failed"], "attempted": res["attempted"],
            "reference_s": res["reference_s"], "device": res["device"],
            "metrics": res["metrics"],
        }

"""Benchmark: training throughput (tokens/sec/chip) + MFU on the reference's
580M config, at an honest step size (>=64k tokens/step via grad accumulation).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.

Baseline: the reference trained its 580M model at ~4.3k tokens/sec/chip on
TPU v3-32 (derived in BASELINE.md from ``logs/580.md:34,49`` — 97k steps /
48B tokens / ~4 days / 32 chips). ``vs_baseline`` is the speedup over that
per-chip figure.

One process per chip: the parent process imports NO jax — each measurement
runs in a child subprocess, one after another, with a wall-clock timeout, so
a child that hangs is killed and recorded instead of taking the whole capture
down. Every child REQUIRES a TPU and fails without one: a number from any
other backend is never written under these metric names. Scenario ladder:

  1. TPU, 580M, remat on    (the memory-safe configuration — runs FIRST so a
     good number always lands before risky upside experiments; round-2 ran
     the OOM-prone remat-off config first and lost the artifact)
  2. TPU, 1.3B, remat on, adafactor — THE north-star scenario
     (BASELINE.json metric is "GPT-1.3B tokens/sec/chip"); if it lands it
     becomes the headline metric/value even though the smaller 580M posts
     higher raw tok/s, with vs_baseline computed against the per-model
     baseline table below.
  3. TPU, 580M, remat with the "dots" policy (saves matmul outputs,
     recomputes only elementwise — faster bwd if it fits)
  4. TPU, 580M, remat off   (upside experiment; smaller per-step batch so it
     has a chance of fitting 16 GB v5e HBM, same 64k tokens/step via accum)
  5. TPU flash-attention microbenchmark sweep T in {1k,4k,8k,16k}
     (extra; only after a TPU success)
  6. TPU KV-cache decode throughput (extra; only after a TPU success)

Exit code: 0 only when every scenario that ran succeeded on a TPU. With no
TPU result there is NO headline line and the exit code is 1; with a headline
but a failed scenario the line is printed (errors ride in ``extra.errors``)
and the exit code is still 1. Every string embedded in the output is
truncated to <=2 KB (a multi-hundred-KB XLA OOM dump stringified into the
line once made it unparseable), and the final line is verified with
``json.loads`` and size-capped before printing.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

BASELINE_TOK_S_CHIP = 4300.0  # reference 580M on TPU v3 (BASELINE.md, derived)

# Per-model reference baselines (tokens/sec/chip, TPU v3-32, derived in
# BASELINE.md from the reference's training logs). The reference published no
# 1.3B throughput; its 760M-derived 4.1k/chip is an UPPER bound on what its
# stack could do at 1.3B (a ~2x larger model is strictly slower per chip at
# equal efficiency), so vs_baseline for 1_3b is a LOWER bound on the true
# speedup — conservative, never flattering.
BASELINES = {"580m": 4300.0, "760m": 4100.0, "1_3b": 4100.0}

MAX_ERR_CHARS = 2048  # hard cap on any string embedded in the output JSON
MAX_LINE_CHARS = 24_000  # hard cap on the final JSON line itself


def _truncate(s: str, limit: int = MAX_ERR_CHARS) -> str:
    """Keep the head and tail of an oversized string (XLA dumps bury the
    actual error at both ends: the message up top, the allocation table at
    the bottom)."""
    if len(s) <= limit:
        return s
    head, tail = limit * 2 // 3, limit // 3
    return s[:head] + f" ...[{len(s) - head - tail} chars truncated]... " + s[-tail:]


def _sanitize(obj):
    """Recursively truncate every string in a JSON-able structure."""
    if isinstance(obj, str):
        return _truncate(obj)
    if isinstance(obj, dict):
        return {k: _sanitize(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_sanitize(v) for v in obj]
    return obj


# ----------------------------------------------------------------- children


def _require_tpu() -> str:
    """The measured paths run on a TPU or not at all; also places the
    compile cache (``utils.compile_cache``: the env var wins, else the fixed
    in-checkout directory)."""
    import jax

    from zero_transformer_tpu.utils import compile_cache

    platform = jax.default_backend()
    if platform != "tpu":
        raise RuntimeError(f"no TPU: jax default backend is {platform!r}")
    compile_cache.configure()
    return platform


def child_train() -> dict:
    """Timed fused train steps; returns the result dict (runs inside a child)."""
    import time

    import jax
    import jax.numpy as jnp

    from zero_transformer_tpu.config import MeshConfig, OptimizerConfig, model_config
    from zero_transformer_tpu.models.gpt import Transformer
    from zero_transformer_tpu.parallel.mesh import make_mesh
    from zero_transformer_tpu.parallel.zero import (
        init_train_state,
        make_plan,
        make_train_step,
    )
    from zero_transformer_tpu.training.optimizer import make_optimizer
    from zero_transformer_tpu.utils import monitoring

    model_name = os.environ.get("BENCH_MODEL", "580m")
    batch_size = int(os.environ.get("BENCH_BATCH", "8"))
    seq = int(os.environ.get("BENCH_SEQ", "1024"))
    accum = int(os.environ.get("BENCH_ACCUM", "8"))
    remat = os.environ.get("BENCH_REMAT", "0") == "1"
    remat_policy = os.environ.get("BENCH_REMAT_POLICY", "none")
    max_steps = int(os.environ.get("BENCH_STEPS", "10"))
    min_seconds = float(os.environ.get("BENCH_MIN_SECONDS", "45"))
    # "adamw" needs 12 bytes/param of optimizer+master state — too much for
    # 1.3B on one 16 GB v5e chip. "adafactor" (factored second moment) is how
    # the 1.3B north-star scenario fits; see training/optimizer.py.
    optimizer = os.environ.get("BENCH_OPT", "adamw")

    platform = _require_tpu()
    print(f"devices_ok platform={platform} n={jax.device_count()}", file=sys.stderr)

    loss_chunk = int(os.environ.get("BENCH_LOSS_CHUNK", "0")) or None
    # attention_impl A/B (ISSUE 8 satellite): "auto" (default) dispatches to
    # the Pallas flash kernel on TPU; BENCH_ATTN_IMPL=xla pins the O(T^2)
    # path so the pair of end-to-end runs prices the kernel in context
    attn_impl = os.environ.get("BENCH_ATTN_IMPL", "auto")
    cfg = model_config(
        model_name, dropout=0.0, remat=remat, remat_policy=remat_policy,
        loss_chunk=loss_chunk, attention_impl=attn_impl,
    )
    n_chips = jax.device_count()
    zero_stage = int(os.environ.get("BENCH_ZERO_STAGE", "1"))
    # BENCH_OVERLAP=1: bucketed ZeRO comm overlap (parallel/overlap.py) —
    # per-layer gathers/scatters inside the layer scan instead of the
    # serial bracket; gradients bitwise-identical, only placement moves
    overlap = os.environ.get("BENCH_OVERLAP", "0") == "1"
    mesh = make_mesh(MeshConfig(zero_stage=zero_stage))
    model = Transformer(cfg)
    tx = make_optimizer(
        OptimizerConfig(warmup_steps=10, total_steps=1000, optimizer=optimizer)
    )

    sample_shape = (batch_size, seq)
    plan = make_plan(model, tx, mesh, sample_shape, zero_stage=zero_stage)
    state = init_train_state(model, tx, jax.random.PRNGKey(0), mesh, sample_shape, plan)
    accum_dtype = os.environ.get("BENCH_ACCUM_DTYPE", "float32")
    step = make_train_step(
        model, tx, mesh, plan, zero_stage=zero_stage,
        grad_accum_dtype=accum_dtype, overlap_comm=overlap,
    )

    batch = jax.random.randint(
        jax.random.PRNGKey(1), (accum, batch_size, seq), 0, cfg.vocab_size, jnp.int32
    )
    rng = jax.random.PRNGKey(2)

    # warmup / compile (all steps chain through the donated state, so
    # blocking on one step's metrics waits for everything before it)
    t_compile = time.perf_counter()
    state, metrics = step(state, batch, rng)
    loss0 = float(jax.block_until_ready(metrics["loss"]))
    t_compile = time.perf_counter() - t_compile
    print(f"compiled+step0 in {t_compile:.1f}s loss={loss0:.3f}", file=sys.stderr)

    # timed: run until min_seconds elapsed or max_steps, whichever first
    n_steps = 0
    t0 = time.perf_counter()
    while n_steps < max_steps:
        state, metrics = step(state, batch, rng)
        n_steps += 1
        if n_steps >= 2 and time.perf_counter() - t0 > min_seconds:
            break
    loss = float(jax.block_until_ready(metrics["loss"]))
    dt = time.perf_counter() - t0

    tokens_per_step = batch_size * seq * accum
    tok_s_chip = tokens_per_step * n_steps / dt / n_chips
    fpt = monitoring.model_flops_per_token(
        cfg.num_params, cfg.n_layers, cfg.d_model, seq
    )
    mfu_val = monitoring.mfu(tok_s_chip, fpt)
    return {
        "ok": True,
        "platform": platform,
        "model": model_name,
        "tok_s_chip": round(tok_s_chip, 1),
        "mfu": round(mfu_val, 4) if mfu_val is not None else None,
        "tokens_per_step": tokens_per_step,
        "steps_timed": n_steps,
        "step_seconds": round(dt / n_steps, 3),
        "compile_seconds": round(t_compile, 1),
        "remat": remat,
        "remat_policy": remat_policy,
        "loss_chunk": loss_chunk,
        "grad_accum_dtype": accum_dtype,
        "optimizer": optimizer,
        "attention_impl": attn_impl,
        "zero_stage": zero_stage,
        "overlap_comm": overlap,
        "n_chips": n_chips,
        "loss_finite": bool(loss == loss),
        "device_kind": jax.devices()[0].device_kind,
    }


def child_decode() -> dict:
    """KV-cache decode throughput on the flagship config: one compiled
    prefill + one compiled while_loop decode (the in-tree replacement for the
    reference's CUDA inference side-car, ``torch_compatability/GPT2.py`` /
    ``app.py``). bf16 params — decode is HBM-bandwidth-bound, so weight bytes
    are the denominator that matters."""
    import time

    import jax
    import jax.numpy as jnp

    from zero_transformer_tpu.config import model_config
    from zero_transformer_tpu.inference.generate import decode_model, generate
    from zero_transformer_tpu.inference.sampling import SamplingConfig

    model_name = os.environ.get("BENCH_MODEL", "580m")
    B = int(os.environ.get("BENCH_DECODE_BATCH", "8"))
    prompt_len = int(os.environ.get("BENCH_DECODE_PROMPT", "128"))
    new = int(os.environ.get("BENCH_DECODE_NEW", "256"))
    kv_dtype = os.environ.get("BENCH_DECODE_KV", "auto")

    platform = _require_tpu()
    print(f"devices_ok platform={platform}", file=sys.stderr)
    # BENCH_DECODE_QUANT=int8: weight-only int8 serving path (random int8
    # init — decode throughput is weight-bandwidth-bound, values don't
    # matter). Paired with the bf16 row it measures what halving the weight
    # reads buys.
    quant = os.environ.get("BENCH_DECODE_QUANT", "none")
    cfg = model_config(
        model_name, dropout=0.0, param_dtype="bfloat16",
        compute_dtype="bfloat16", kv_cache_dtype=kv_dtype, param_quant=quant,
    )
    model = decode_model(cfg, prompt_len + new)
    prompt = jax.random.randint(
        jax.random.PRNGKey(0), (B, prompt_len), 0, cfg.vocab_size, jnp.int32
    )
    params = model.init(jax.random.PRNGKey(1), prompt[:, :8])["params"]
    # BENCH_DECODE_SAMPLING=greedy isolates the sampler's cost from the
    # forward's: top-k over the [B, 50304] f32 logits runs a TPU sort each
    # step, and the A/B against argmax says whether the decode gap to the
    # HBM-bandwidth ceiling lives in the model or in the sampler.
    # =topk_approx runs the same top-k through lax.approx_max_k (the TPU
    # partial-reduce) — the third arm that says how much of the sort cost
    # the approximate cutoff recovers.
    arm = os.environ.get("BENCH_DECODE_SAMPLING", "topk")
    if arm == "greedy":
        sampling = SamplingConfig(greedy=True)
    elif arm == "topk_approx":
        sampling = SamplingConfig(top_k=40, temperature=0.9, top_k_impl="approx")
    elif arm == "topk":
        sampling = SamplingConfig(top_k=40, temperature=0.9)
    else:  # a typo'd arm must not silently benchmark the wrong thing
        raise ValueError(f"BENCH_DECODE_SAMPLING={arm!r} (topk|topk_approx|greedy)")

    t_compile = time.perf_counter()
    out = generate(model, params, prompt, new, jax.random.PRNGKey(2), sampling)
    out.block_until_ready()
    t_compile = time.perf_counter() - t_compile
    print(f"compiled+decode0 in {t_compile:.1f}s", file=sys.stderr)

    reps = int(os.environ.get("BENCH_DECODE_REPS", "3"))
    t0 = time.perf_counter()
    for i in range(reps):
        out = generate(model, params, prompt, new, jax.random.PRNGKey(3 + i), sampling)
    out.block_until_ready()
    dt = (time.perf_counter() - t0) / reps

    # optional on-chip trace of one rep (view with xprof/tensorboard):
    # BENCH_DECODE_PROFILE=/path/dir — for chasing the gap between measured
    # ms/step and the weight-streaming lower bound
    prof_dir = os.environ.get("BENCH_DECODE_PROFILE")
    if prof_dir:
        with jax.profiler.trace(prof_dir):
            out = generate(model, params, prompt, new, jax.random.PRNGKey(99), sampling)
            out.block_until_ready()

    result = {
        "ok": True,
        "platform": platform,
        "model": model_name,
        "decode_tok_s": round(B * new / dt, 1),
        "ms_per_token": round(dt / new * 1e3, 3),
        "batch": B,
        "prompt_len": prompt_len,
        "new_tokens": new,
        "kv_cache_dtype": kv_dtype,
        "param_quant": quant,
        "sampling": ("greedy" if sampling.greedy
                     else f"top_k={sampling.top_k}:{sampling.top_k_impl}"),
        "compile_seconds": round(t_compile, 1),
        "note": "wall time includes one prefill per rep",
    }

    # batch-1 latency path: prompt-lookup speculative vs plain greedy on a
    # self-similar prompt (the regime speculation exists for)
    spec_k = int(os.environ.get("BENCH_DECODE_SPEC", "8"))
    if spec_k > 0:
        from zero_transformer_tpu.inference.generate import (
            decode_model as build_decode_model,
            generate as gen,
        )
        from zero_transformer_tpu.inference.speculative import generate_speculative

        piece = jax.random.randint(jax.random.PRNGKey(7), (32,), 0, cfg.vocab_size, jnp.int32)
        rep_prompt = jnp.tile(piece, 4)[None, :]  # [1, 128] periodic
        # the speculative scratch needs prompt + new + K cache slots — the
        # batch model above was sized without the K slack
        model = build_decode_model(cfg, rep_prompt.shape[1] + new + spec_k)
        greedy = SamplingConfig(greedy=True)

        def timed(fn, reps=3):
            jax.block_until_ready(fn())
            t0 = time.perf_counter()
            for _ in range(reps):
                out = fn()
            jax.block_until_ready(out)
            return (time.perf_counter() - t0) / reps

        t_plain = timed(lambda: gen(model, params, rep_prompt, new,
                                    jax.random.PRNGKey(0), greedy))
        spec_out, stats = generate_speculative(
            model, params, rep_prompt, new, draft_len=spec_k, return_stats=True
        )
        t_spec = timed(lambda: generate_speculative(
            model, params, rep_prompt, new, draft_len=spec_k))
        result["speculative"] = {
            "draft_len": spec_k,
            "plain_tok_s": round(new / t_plain, 1),
            "spec_tok_s": round(new / t_spec, 1),
            "speedup": round(t_plain / t_spec, 2),
            "tokens_per_forward": round(stats["tokens_per_forward"], 2),
        }
    return result


def child_loader() -> dict:
    """Tar-gzip loader throughput + prefetch-overlap microbench (CPU-only;
    no jax). See ``zero_transformer_tpu.data.loader_bench``."""
    from zero_transformer_tpu.data.loader_bench import run

    out = run()
    out["ok"] = True
    return out


def child_flash() -> dict:
    """Flash-vs-XLA attention microbenchmark, fwd+bwd, swept over sequence
    lengths (the kernel exists to make 8k-32k context viable — one 1k
    datapoint says nothing about that regime). Batch shrinks as T grows to
    hold tokens (B*T) constant, the way a real long-context run would.

    TPU only: off the chip Pallas runs in interpret mode, whose timings
    mean nothing (the kernels' interpret-mode PARITY lives in the tests and
    in ``scripts/train_step_bench.py``)."""
    import time

    import jax
    import jax.numpy as jnp

    from zero_transformer_tpu.ops.attention import xla_attention
    from zero_transformer_tpu.ops.pallas.flash import flash_attention

    print(f"devices_ok platform={_require_tpu()}", file=sys.stderr)
    seqs = [int(s) for s in os.environ.get("BENCH_FLASH_SEQS", "1024,4096,8192,16384").split(",")]
    H, D = 12, 128
    tokens = 8 * 1024  # B*T held constant across the sweep

    def bench(fn, q, k, v, reps=10):
        lossf = lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32))
        step = jax.jit(jax.grad(lossf, argnums=(0, 1, 2)))
        jax.block_until_ready(step(q, k, v))  # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            out = step(q, k, v)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / reps * 1e3  # ms

    points = []
    for T in seqs:
        B = max(1, tokens // T)
        try:
            q, k, v = (
                jax.random.normal(jax.random.PRNGKey(i), (B, T, H, D), jnp.bfloat16)
                for i in range(3)
            )
            flash_ms = bench(
                lambda q, k, v: flash_attention(q, k, v, causal=True, alibi=True), q, k, v
            )
            # XLA full-matrix attention at 16k materializes B*H*T*T scores;
            # guard it separately so a flash datapoint still lands if XLA OOMs.
            try:
                xla_ms = bench(
                    lambda q, k, v: xla_attention(q, k, v, causal=True, alibi=True), q, k, v
                )
            except Exception as e:
                xla_ms = None
            # fwd+bwd attention FLOPs: ~4*B*T^2*H*D fwd, x2.5 with bwd, causal halves
            flops = 4 * B * T * T * H * D * 2.5 / 2
            points.append(
                {
                    "shape": [B, T, H, D],
                    "xla_ms": round(xla_ms, 3) if xla_ms else None,
                    "flash_ms": round(flash_ms, 3),
                    "speedup": round(xla_ms / flash_ms, 2) if xla_ms else None,
                    "flash_tflops": round(flops / (flash_ms * 1e-3) / 1e12, 1),
                }
            )
        except Exception as e:
            points.append({"shape": [B, T, H, D], "error": _truncate(f"{type(e).__name__}: {e}", 512)})
    return {"ok": any("flash_ms" in p for p in points), "points": points}


# ------------------------------------------------------------------- parent


def _run_child(scenario: str, env_extra: dict, timeout: float) -> dict:
    """Run one scenario in a subprocess; parse its final JSON stdout line."""
    env = dict(os.environ)
    env["BENCH_CHILD"] = scenario
    env.update(env_extra)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env,
            capture_output=True,
            text=True,
            timeout=timeout,
        )
    except subprocess.TimeoutExpired as e:
        stderr = (e.stderr or b"")
        stderr = stderr.decode(errors="replace") if isinstance(stderr, bytes) else stderr
        backend_up = "devices_ok" in stderr
        return {
            "ok": False,
            "error": f"timeout after {timeout:.0f}s "
            + ("(backend was up; run too slow)" if backend_up else "(backend init hung)"),
            "backend_init_hung": not backend_up,
        }
    except Exception as e:  # spawn failure — still record, never raise
        return {"ok": False, "error": f"spawn failed: {e!r}"}
    for line in reversed(proc.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                break
    tail = (proc.stderr or "").strip().splitlines()[-8:]
    return {
        "ok": False,
        "error": _truncate(f"rc={proc.returncode}: " + " | ".join(tail)),
    }


def main() -> int:
    scenario = os.environ.get("BENCH_CHILD")
    if scenario:  # ---- child mode: run one measurement, print its JSON
        try:
            result = {
                "flash": child_flash,
                "loader": child_loader,
                "decode": child_decode,
            }.get(scenario, child_train)()
        except Exception as e:
            # XLA OOMs stringify to hundreds of KB — truncate HERE, at the
            # source, so no oversized string ever enters the artifact path.
            # The failure is not swallowed: the parent records it and the
            # run exits non-zero.
            result = {"ok": False, "error": _truncate(f"{type(e).__name__}: {e}"),
                      "no_tpu": str(e).startswith("no TPU")}
        print(json.dumps(_sanitize(result)), flush=True)
        return 0 if result.get("ok") else 1

    # ---- parent mode: scenario ladder, one final JSON line; the exit code
    # says whether everything that ran succeeded on a TPU
    errors: list = []
    results: dict = {}
    tpu_timeout = float(os.environ.get("BENCH_TPU_TIMEOUT", "900"))

    # remat_on runs FIRST: it is the memory-safe configuration, so a good
    # number always lands before upside experiments (round-2 lesson). The
    # remat_off upside run uses half the per-step batch (same 64k tokens/step
    # via doubled accum) so its activation temporaries have a chance of
    # fitting 16 GB v5e HBM. Upside scenarios get a SHORTER timeout (except
    # long_ctx_8k, whose compile alone is known to outlast it — see the
    # scenario comment): the
    # known-good config compiles in ~2 min, so a config that can't compile
    # in `upside_timeout` isn't going to win and must not eat the driver's
    # budget (observed: the dots-policy compile can take >30 min).
    upside_timeout = float(os.environ.get("BENCH_UPSIDE_TIMEOUT", "420"))

    # the two scenarios the whole capture exists for: the memory-safe 580M
    # number and the BASELINE.json 1.3B north star.
    HEADLINE = (
        ("remat_on", {"BENCH_REMAT": "1"}, tpu_timeout),
        # THE north-star scenario (BASELINE.json metric: "GPT-1.3B
        # tokens/sec/chip"): 1.3B on one 16 GB v5e chip needs remat +
        # adafactor (adamw's 12 bytes/param of state would never fit) AND a
        # bfloat16 grad-accumulation buffer: a 2026-07-31 run showed (AOT-
        # compile HBM rejection of the north_star_f32acc scenario) that
        # three param-sized f32 trees — master params, accumulator,
        # micro-grads — are 15.6 GB before activations. bf16 accumulator +
        # chunked CE + batch 4 brings the static picture to ~13 GB.
        # 64k tokens/step via accumulation, same as the 580m scenario.
        ("north_star_1_3b",
         {"BENCH_REMAT": "1", "BENCH_MODEL": "1_3b", "BENCH_OPT": "adafactor",
          "BENCH_BATCH": "4", "BENCH_ACCUM": "16", "BENCH_LOSS_CHUNK": "256",
          "BENCH_ACCUM_DTYPE": "bfloat16"}, tpu_timeout),
    )
    # upside experiments, in decreasing fit-probability order. These run
    # AFTER the flash/decode microbenches: a backend lost mid-ladder must
    # not cost the high-value micro datapoints.
    UPSIDE = (
        # north_star_f32acc: the same config with the default f32 accumulator
        # — marginal on paper (~15.9 GB static); if the AOT compiler accepts
        # it, full-precision accumulation becomes the headline instead.
        ("north_star_f32acc",
         {"BENCH_REMAT": "1", "BENCH_MODEL": "1_3b", "BENCH_OPT": "adafactor",
          "BENCH_BATCH": "4", "BENCH_ACCUM": "16", "BENCH_LOSS_CHUNK": "256"},
         upside_timeout),
        # north_star_b2: half the microbatch again — fallback insurance so a
        # 1.3B datapoint lands even if the batch-4 activation/temp picture
        # is tighter than the static estimate (an OOM rejection costs only
        # the AOT compile, ~3-5 min)
        ("north_star_b2",
         {"BENCH_REMAT": "1", "BENCH_MODEL": "1_3b", "BENCH_OPT": "adafactor",
          "BENCH_BATCH": "2", "BENCH_ACCUM": "32", "BENCH_LOSS_CHUNK": "256",
          "BENCH_ACCUM_DTYPE": "bfloat16"}, upside_timeout),
        # remat_qkv_mlp: the named-checkpoint middle ground — saves only
        # q/k/v + MLP pre-activations (~1.6 GB at batch 4 for 580M), which
        # skips ~85% of the re-forward matmul FLOPs the full-remat headline
        # pays. The dots policy was AOT-rejected at batch 8 AND its batch-4
        # retry is unproven, so this smaller-footprint policy is the most
        # likely to actually move the 59.7% MFU headline.
        ("remat_qkv_mlp",
         {"BENCH_REMAT": "1", "BENCH_REMAT_POLICY": "qkv_mlp",
          "BENCH_BATCH": "4", "BENCH_ACCUM": "16"}, upside_timeout),
        # the same lever pointed at the north star: 1.3B at batch 2 keeps
        # the saved-tensor set to ~1.4 GB (d2048, 24 layers) next to the
        # ~13 GB static picture — if the AOT compiler takes it, the
        # BASELINE.json metric itself moves up
        ("north_star_qkv_mlp_b2",
         {"BENCH_REMAT": "1", "BENCH_REMAT_POLICY": "qkv_mlp",
          "BENCH_MODEL": "1_3b", "BENCH_OPT": "adafactor",
          "BENCH_BATCH": "2", "BENCH_ACCUM": "32", "BENCH_LOSS_CHUNK": "256",
          "BENCH_ACCUM_DTYPE": "bfloat16"}, upside_timeout),
        # remat_dots at HALF the per-step batch (same 64k tokens/step): the
        # dots policy saves every matmul output, trading ~33% backward FLOPs
        # (the full-remat re-forward) for ~250 MB/layer of saved activations
        # at batch 8 — the batch-8 attempt was rejected by the AOT compiler
        # on 2026-07-31; batch 4 halves the saved set to ~2.3 GB, which fits
        # next to the 580M adamw state. If it lands, the MFU ceiling moves
        # from ~60% (full remat, 8 FLOPs/param/token) toward ~75%.
        ("remat_dots",
         {"BENCH_REMAT": "1", "BENCH_REMAT_POLICY": "dots",
          "BENCH_BATCH": "4", "BENCH_ACCUM": "16"}, upside_timeout),
        # overlapped ZeRO comm (ISSUE 8): the same 580M headline config with
        # zero_stage=2 serial vs overlapped collective placement — the pair
        # prices the exposed-comm reduction end-to-end on real ICI (grads
        # bitwise-identical between the arms, only placement moves). Run as
        # a pair so neither number is orphaned by a backend lost mid-ladder.
        ("zero2_serial",
         {"BENCH_REMAT": "1", "BENCH_ZERO_STAGE": "2"}, upside_timeout),
        ("zero2_overlap",
         {"BENCH_REMAT": "1", "BENCH_ZERO_STAGE": "2", "BENCH_OVERLAP": "1"},
         upside_timeout),
        # attention_impl A/B: same headline config pinned to the XLA O(T^2)
        # attention — the flash kernel's end-to-end value at training shapes
        # (the per-op sweep in child_flash prices it in isolation)
        ("attn_xla",
         {"BENCH_REMAT": "1", "BENCH_ATTN_IMPL": "xla"}, upside_timeout),
        ("remat_off", {"BENCH_REMAT": "0", "BENCH_BATCH": "4", "BENCH_ACCUM": "16"}, upside_timeout),
        # long-context training point: 580M at 8k tokens/row (the regime the
        # Pallas flash kernel + chunked CE exist for; same 64k tokens/step).
        # Full tpu_timeout, not the upside one: the 8k flash fwd+bwd compile
        # alone has outlasted 420s — this datapoint is the long-context
        # headline, so it gets the same budget as the headline scenarios
        # rather than being dropped as a non-fit.
        ("long_ctx_8k",
         {"BENCH_REMAT": "1", "BENCH_SEQ": "8192", "BENCH_BATCH": "1",
          "BENCH_ACCUM": "8", "BENCH_LOSS_CHUNK": "1024"}, tpu_timeout),
    )

    micros = None

    def run_micros() -> dict:
        """Flash/decode microbenches — once, at the earliest point a live
        TPU is proven."""
        flash = _run_child("flash", {}, 600.0)
        if not flash.get("ok"):
            errors.append(_truncate(f"flash: {flash.get('error')}"))
        decode = _run_child("decode", {}, 600.0)
        if not decode.get("ok"):
            errors.append(_truncate(f"decode: {decode.get('error')}"))
        # int8-KV guard (ADVICE r3): the int8 cache's HBM win rests on XLA
        # fusing the dequant into the attention reads; if that fusion ever
        # regresses, int8 decode tok/s falls BELOW the auto (bf16) number
        # measured above — so the pair of datapoints is the regression alarm.
        decode_int8 = _run_child(
            "decode", {"BENCH_DECODE_KV": "int8", "BENCH_DECODE_SPEC": "0"}, 600.0
        )
        if not decode_int8.get("ok"):
            errors.append(_truncate(f"decode_int8: {decode_int8.get('error')}"))
        # the fully bandwidth-optimized decode: int8 weights AND int8 KV —
        # what `serve --quantize int8 --kv-cache-dtype int8` runs
        decode_w8 = _run_child(
            "decode",
            {"BENCH_DECODE_QUANT": "int8", "BENCH_DECODE_KV": "int8",
             "BENCH_DECODE_SPEC": "0"}, 600.0,
        )
        if not decode_w8.get("ok"):
            errors.append(_truncate(f"decode_w8: {decode_w8.get('error')}"))
        return {
            "flash": flash, "decode": decode, "decode_int8": decode_int8,
            "decode_w8": decode_w8,
        }

    def run_block(scenarios, micros_at_first_tpu_ok=False) -> bool:
        """Run train scenarios in order; False = stop the ladder (the
        backend hung at init, or a child found no TPU). With
        ``micros_at_first_tpu_ok`` the microbenches fire the moment a
        scenario proves the TPU live (the upside block's edge case: both
        headline configs failed, so the micros haven't run, and waiting for
        the block's end risks losing them)."""
        nonlocal micros
        for name, env_extra, timeout in scenarios:
            if name == "north_star_b2" and any(
                results.get(n, {}).get("ok")
                for n in ("north_star_1_3b", "north_star_f32acc")
            ):
                continue  # fallback not needed: a batch-4 1.3B datapoint landed
            res = _run_child("train", env_extra, timeout)
            results[name] = res
            if not res.get("ok"):
                errors.append(_truncate(f"{name}: {res.get('error')}"))
                if res.get("backend_init_hung") or res.get("no_tpu"):
                    errors.append(
                        "skipping further TPU scenarios: no usable TPU backend"
                    )
                    return False
            elif micros_at_first_tpu_ok and micros is None:
                micros = run_micros()
        return True

    def any_tpu_ok() -> bool:
        return any(
            r.get("ok") and r.get("platform") == "tpu"
            for r in results.values()
        )

    alive = run_block(HEADLINE)
    if any_tpu_ok():
        micros = run_micros()
    if alive:
        # if the first TPU success arrives only inside this block (both
        # headline configs failed without hanging), the micros fire right
        # there — never after a block that ended in a backend hang
        run_block(UPSIDE, micros_at_first_tpu_ok=True)

    tpu_good = [
        r for r in results.values() if r.get("ok") and r.get("platform") == "tpu"
    ]
    if not tpu_good:
        # no TPU result: no headline, no stand-in number — just the reasons
        for e in errors:
            print(f"bench: {e}", file=sys.stderr)
        print("bench: no scenario produced a TPU result", file=sys.stderr)
        return 1

    # headline preference: the best 1.3B north-star variant if any landed
    # (it is the BASELINE.json metric, even though the smaller 580m
    # config posts higher raw tok/s); otherwise the best throughput.
    ns_good = [
        r for name, r in results.items()
        if name.startswith("north_star") and r.get("ok")
        and r.get("platform") == "tpu"
    ]
    best = (max(ns_good, key=lambda r: r["tok_s_chip"]) if ns_good
            else max(tpu_good, key=lambda r: r["tok_s_chip"]))
    flash, decode, decode_int8 = (
        micros["flash"], micros["decode"], micros["decode_int8"]
    )
    decode_w8 = micros.get("decode_w8", {"ok": False, "error": "not run"})
    loader = _run_child("loader", {}, 300.0)
    if not loader.get("ok"):
        errors.append(_truncate(f"loader: {loader.get('error')}"))
    baseline = BASELINES.get(best["model"], BASELINE_TOK_S_CHIP)
    out = {
        "metric": f"train_tokens_per_sec_per_chip_{best['model']}",
        "value": best["tok_s_chip"],
        "unit": "tokens/s/chip",
        "vs_baseline": round(best["tok_s_chip"] / baseline, 3),
        "mfu": best.get("mfu"),
        "extra": {
            "scenarios": results,
            "flash_microbench": flash,
            "decode_microbench": decode,
            "decode_int8_microbench": decode_int8,
            "decode_w8_microbench": decode_w8,
            "loader_microbench": loader,
            "errors": errors,
        },
    }
    # Artifact contract: exactly one JSON line, parseable, bounded size.
    line = json.dumps(_sanitize(out))
    if len(line) > MAX_LINE_CHARS:  # drop detail until it fits
        out["extra"] = {"errors": [_truncate(e, 512) for e in errors[:8]],
                        "detail_dropped": "output exceeded size cap"}
        line = json.dumps(_sanitize(out))
    json.loads(line)  # hard assert: never print an unparseable artifact
    print(line, flush=True)
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())

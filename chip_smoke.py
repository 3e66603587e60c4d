#!/usr/bin/env python3
"""Chip smoke: the trainer and the server, through their normal entry
points, on one TPU v5e chip — the quickest proof the system still starts
there.

    python chip_smoke.py             # one chip: train -> extract -> kernels -> serve x3
    python chip_smoke.py --chips 4   # four chips: ZeRO-1/2 on data=4 vs one chip

One process per chip: this parent never imports jax. It runs each phase as
a child (``--phase``, internal) one after another; every child checks for a
TPU before anything else, prints its device, and the parent copies that
into the last line. Each phase prints one JSON object; the LAST stdout line
is ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
and nothing else. Any failed phase — no TPU, a raise, a non-finite loss, an
absent kernel, a mismatch — exits non-zero naming the phase, and no last
line is printed.

Phases (one chip), all on the ``1_3b`` model at full width and depth:

- train: ``train.py`` on ``configs/train_1_3b_1chip.yaml`` (synthetic
  data, a few steps, anomaly guard on), checkpoint at the last step; then
  the checkpoint is restored once with digest verification and the compiled
  step is checked for the flash kernel (lowered text + trace counter).
- extract: ``python -m zero_transformer_tpu.export extract`` on it.
- kernels: the Mosaic-compiled paged decode kernel against the gather path
  it replaces, at the server's shapes (``1_3b`` heads, page 16, cache 1024,
  4 slots), decode and spec-verify windows, bf16 and int8 pages, random K/V
  from the seed — the kernel module's on-chip bar (``PAGED_ULPS``).
- serve (three servers, one after another): ``serve --server`` in bf16 with
  the user defaults (attention-impl auto) plus ``--tokenizer bytes
  --greedy``: (a) ``--draft-k 4``,
  (b) ``--draft-k 0``, (c) ``--attention-impl xla --draft-k 4``. Each takes
  the same concurrent shared-prefix requests; /healthz must reach 200,
  /metrics must show the kernels and counters, SIGTERM must drain to exit 0,
  and every request's tokens must be identical across the three.

Four chips (``--chips 4``), the recipe family with depth cut: the recipe's
own adafactor at its 64k tokens a step on one chip, then ZeRO-1 and ZeRO-2 on
``data=4`` (per-step losses compared, loss decreasing; adafactor's factored
statistics are replicated by design, so they are held to "tiny" instead of
"a quarter each"), then an adamw leg whose param-shaped state must sit a
quarter on each chip.

Everything is generated from a seed; output goes under ``chip_smoke_out/``
(git-ignored, too big for ``chiprun_out/``); nothing untracked is read.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chip_smoke_out"
SEED = 0

# ---- the one-chip run: the README's one-chip recipe, widths and depth whole
TRAIN_CFG = "configs/train_1_3b_1chip.yaml"
TRAIN_STEPS = 4
TRAIN_SETS = [
    "data.source=synthetic",
    f"data.shuffle_seed={SEED}",
    "training.log_frequency=1",
    # the recipe warms up over 2000 steps; a 4-step run has to learn in 3
    "optimizer.warmup_steps=1",
]
SERVE_MODEL = "1_3b"
DRAFT_K = 4
SERVE_ARGS = [
    "--tokenizer", "bytes", "--dtype", "bfloat16", "--greedy",
    "--repetition-penalty", "1.0", "--cache-len", "1024", "--slots", "4",
]
SERVE_VARIANTS = {
    "spec": ["--draft-k", str(DRAFT_K)],
    "plain": ["--draft-k", "0"],
    "xla": ["--attention-impl", "xla", "--draft-k", str(DRAFT_K)],
}
N_REQUESTS = 7
MAX_NEW_TOKENS = 24
# the paged decode kernel's on-chip bar (ops/pallas/paged_attention.py): its
# output within this many bf16 ulps, at the output's scale, of the gather
# path's (the order of a sum alone accounts for up to 1)
PAGED_ULPS = 2.0

# ---- the four-chip run: the recipe family (its own adafactor, its 64k
# tokens a step as batch 8 x accum 8), depth cut to 6 layers
ZERO_LAYERS = 6
ZERO_STEPS = 6
ZERO_SETS = TRAIN_SETS + [
    f"model.n_layers={ZERO_LAYERS}",
    "training.batch_size=8",
    "training.gradient_accumulation_steps=8",
]
# per-step |loss(4 chips) - loss(1 chip)|: same batch, seed and bf16 math,
# a different reduction order across the data axis
ZERO_LOSS_TOL = 0.02
# adafactor keeps factored row/column statistics, O(rows + cols) a matrix,
# replicated on every device BY DESIGN (training/optimizer.py: what ZeRO
# shards there is the work, not the storage) — held to this share of the
# params' bytes
FACTORED_STATE_MAX = 0.02
# what ZeRO does scatter is state shaped like the params: an adamw leg (mu
# and nu, 8 bytes a param — fits four chips, not one) must sit 1/4 on each
ZERO_ADAMW_SETS = ZERO_SETS[:-1] + [
    "optimizer.optimizer=adamw",
    "training.gradient_accumulation_steps=2",
]
ZERO_ADAMW_STEPS = 2


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


# --------------------------------------------------------------- children


def device_or_exit(require_tpu: bool = True) -> dict:
    """This process's device as jax reports it; exits non-zero without a TPU."""
    import jax

    d = jax.devices()[0]
    if require_tpu and d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (jax found {d.platform})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def versions() -> dict:
    import importlib.metadata as md

    return {p: md.version(p) for p in ("jax", "jaxlib", "libtpu", "flax", "optax")}


def cache_state() -> dict:
    """Place the compile cache (env var wins, else the fixed in-checkout
    directory) and say whether it came warm."""
    from zero_transformer_tpu.utils import compile_cache

    directory = compile_cache.configure()
    entries = len(os.listdir(directory)) if os.path.isdir(directory) else 0
    return {"cache_dir": directory, "cache_entries_at_start": entries,
            "cache_warm": entries > 0}


def watch_compiles() -> dict:
    """Live counters from jax's own monitoring events: seconds spent in
    backend compile-or-load, and persistent-cache hits and misses. A warm
    cache shows as ``compile_s`` far below a cold run's, hits up, misses 0."""
    import jax.monitoring

    seen = {"compile_s": 0.0, "cache_hits": 0, "cache_misses": 0}

    def on_duration(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen["compile_s"] = round(seen["compile_s"] + seconds, 2)

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            seen["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            seen["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return seen


def peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()
    return stats and stats.get("peak_bytes_in_use")


def run_train_main(cfg_path: str, sets: list, steps: int, ckpt: Path) -> None:
    """``python train.py --cfg ... --max-steps N --set ...`` in this process."""
    sys.path.insert(0, str(ROOT))
    import train

    argv = sys.argv
    sys.argv = ["train.py", "--cfg", str(ROOT / cfg_path), "--max-steps", str(steps),
                "--set", *sets, f"checkpoint.directory={ckpt}"]
    try:
        train.main()
    finally:
        sys.argv = argv


def train_rows(ckpt: Path) -> list:
    """The per-step rows of the metrics.jsonl a Trainer wrote."""
    with open(ckpt / "metrics.jsonl") as f:
        rows = [json.loads(line) for line in f]
    return [r for r in rows if "train/loss" in r]


def check_losses(rows: list, steps: int, decreasing: bool = True) -> list:
    """Finite, one per step and (``decreasing``) last below first — or raise."""
    import math

    losses = [r["train/loss"] for r in rows]
    if len(losses) != steps:
        raise RuntimeError(f"expected {steps} logged steps, got {len(losses)}")
    if not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"non-finite loss: {losses}")
    if decreasing and not losses[-1] < losses[0]:
        raise RuntimeError(f"loss did not decrease: {losses}")
    return losses


def train_phase(out: Path, cfg_path: str = TRAIN_CFG, sets=TRAIN_SETS,
                steps: int = TRAIN_STEPS, require_tpu: bool = True) -> dict:
    device = device_or_exit(require_tpu)
    cache = cache_state()
    compiles = watch_compiles()
    import jax
    import jax.numpy as jnp

    from zero_transformer_tpu.ops.pallas import kernel_traces

    ckpt = out / "train"
    t0 = time.perf_counter()
    run_train_main(cfg_path, list(sets), steps, ckpt)
    wall = time.perf_counter() - t0
    rows = train_rows(ckpt)
    losses = check_losses(rows, steps)
    step_s = sorted(r["train/step_time_s"] for r in rows if "train/step_time_s" in r)
    traced = dict(kernel_traces)
    if not (traced.get("flash_fwd") and traced.get("flash_bwd")):
        raise RuntimeError(f"flash kernel was not traced into the step: {traced}")

    # the checkpoint train.py left behind: restore it ONCE with digest
    # verification, through the Trainer the CLI builds for --resume
    import train
    from zero_transformer_tpu.config import load_config
    from zero_transformer_tpu.training.trainer import Trainer

    cfg = train.apply_overrides(
        load_config(ROOT / cfg_path),
        train.parse_overrides(
            [*sets, f"checkpoint.directory={ckpt}", "checkpoint.resume=True"]
        ),
    )
    trainer = Trainer(cfg)
    try:
        t0 = time.perf_counter()
        state = trainer.init_state()
        restore_s = time.perf_counter() - t0
        report = trainer._restore_report
        if report is None or report.step != steps or report.quarantined:
            raise RuntimeError(f"verified restore failed: {report}")
        if int(state.step) != steps:
            raise RuntimeError(f"restored step {int(state.step)} != {steps}")
        # the step program this config compiles to: is the kernel IN it?
        accum = max(cfg.training.gradient_accumulation_steps, 1)
        batch = jax.ShapeDtypeStruct(
            (accum, cfg.training.batch_size, cfg.training.train_context),
            jnp.int32,
        )
        lowered = trainer.train_step.lower(state, batch, trainer.rng).as_text()
        mosaic_calls = lowered.count("tpu_custom_call")
        if device["platform"] == "tpu" and not mosaic_calls:
            raise RuntimeError("no tpu_custom_call in the lowered train step")
    finally:
        trainer.close()
    return {
        "phase": "train", "ok": True, "device": device, "versions": versions(),
        "config": cfg_path, "steps": steps, "loss": losses,
        "loss_first": losses[0], "loss_last": losses[-1],
        # observations, not metrics: the first step's wall time is mostly
        # compilation; warm = median of the later steps
        "train_wall_s": round(wall, 1),
        "warm_step_s": step_s[len(step_s) // 2] if step_s else None,
        "peak_bytes_in_use": peak_bytes(),
        "kernel_traces": traced, "mosaic_calls_in_lowered_step": mosaic_calls,
        "checkpoint_step": report.step, "restore_verify_ms": report.verify_ms,
        "restore_s": round(restore_s, 1), **compiles, **cache,
    }


def extract_phase(out: Path, require_tpu: bool = True) -> dict:
    device = device_or_exit(require_tpu)
    from zero_transformer_tpu import export

    params = out / "params.msgpack"
    t0 = time.perf_counter()
    export.main(["extract", "--checkpoint-dir", str(out / "train"),
                 "--out", str(params)])
    return {"phase": "extract", "ok": True, "device": device,
            "params_bytes": params.stat().st_size,
            "seconds": round(time.perf_counter() - t0, 1)}


# (slots, heads, cache_len) of the benchmark's serving cells: 580M and the
# looped 2.6B, heads of 128 as SERVE_MODEL's
CELL_SHAPES = ((16, 12, 2048), (16, 16, 512))
# (slots, heads, cached row's lanes, value lanes, cache_len) of the latent
# serving cell: GLM-4.7-Flash's 20 heads over rows of 512 + 64 (+ 64) lanes
LATENT_SHAPES = ((16, 20, 640, 512, 5120),)
# (rows, heads, head_dim, d_state) of the state-space serving cell's decode
# state update: granite-4.0-h-micro's 32 slots of 64 heads of 64 x 128
SSM_SHAPES = ((32, 64, 64, 128),)
SSM_ULPS = 4  # float32 ulps at the state's / y's own scale


def kernels_phase(model: str = SERVE_MODEL, slots: int = 4,
                  cache_len: int = 1024, ragged_shapes: tuple = CELL_SHAPES,
                  latent_shapes: tuple = LATENT_SHAPES,
                  ssm_shapes: tuple = SSM_SHAPES,
                  require_tpu: bool = True) -> dict:
    """The paged decode kernel as the chip's compiler built it, against the
    gather path it replaces, at the shapes the servers below decode with —
    greedy tokens alone cannot tell a wrong mask from a right one on a
    random-weight model that emits one token — and, the kernel's walk being
    bounded by each row's own length, at the benchmark cells' shapes with
    most rows a few pages long; and the latent decode kernel against ITS
    gather path at the latent cell's shapes, to the same bar; and the decode
    state-update kernel against its plain ``jax.numpy`` twin at the
    state-space cell's shapes, a stale-state control far outside."""
    device = device_or_exit(require_tpu)
    import jax.numpy as jnp

    from zero_transformer_tpu.config import ServingConfig, model_config
    from zero_transformer_tpu.ops.pallas.parity import (
        SSM_LIVE, latent_vs_gather, paged_vs_gather, ssm_update_vs_xla,
    )

    cfg = model_config(model)
    page = ServingConfig().page_size
    shapes = [(slots, cfg.n_heads, cfg.kv_heads, cache_len, False)] + [
        (B, H, H, S, True) for B, H, S in ragged_shapes
    ]
    cases = [
        paged_vs_gather(
            B=B, T=T, H=H, KVH=KVH, D=cfg.head_width, page=page,
            n_blocks=S // page, dtype=jnp.bfloat16, int8=int8,
            alibi=cfg.position == "alibi", seed=SEED,
            interpret=device["platform"] != "tpu", ragged=ragged,
        )
        for B, H, KVH, S, ragged in shapes
        for T in (1, 1 + DRAFT_K) for int8 in (False, True)
    ]
    latent = [
        latent_vs_gather(
            B=B, T=T, H=H, R=R, value_width=V, page=page, n_blocks=S // page,
            dtype=jnp.bfloat16, seed=SEED, interpret=device["platform"] != "tpu",
        )
        for B, H, R, V, S in latent_shapes for T in (1, 1 + DRAFT_K)
    ]
    for case in cases + latent:
        if not (case["finite"] and case["ulps"] <= PAGED_ULPS < case["control_ulps"]):
            raise RuntimeError(
                f"paged kernel outside {PAGED_ULPS} bf16 ulps of the gather "
                f"path (or the control inside them): {case}"
            )
    on_tpu = device["platform"] == "tpu"
    ssm = [
        ssm_update_vs_xla(rows=S, heads=H, head_dim=P, d_state=N, live=live,
                          seed=SEED, interpret=not on_tpu,
                          time_calls=20 if on_tpu else 0)
        for S, H, P, N in ssm_shapes for live in SSM_LIVE
    ]
    for case in ssm:
        if not (case["finite"] and case["idle_rows_kept"] and case["other_layers_kept"]
                and case["ulps"] <= SSM_ULPS < case["control_ulps"]):
            raise RuntimeError(
                f"state-update kernel outside {SSM_ULPS} float32 ulps of its "
                f"jax.numpy twin (or the stale-state control inside them): {case}"
            )
    return {"phase": "kernels", "ok": True, "device": device, "model": model,
            "paged_ulps_bar": PAGED_ULPS, "paged_vs_gather": cases,
            "latent_vs_gather": latent, "ssm_update_vs_xla": ssm}


def serve_child(model: str, params: Path, port: int, extra: list,
                require_tpu: bool = True) -> None:
    """The real server, in this process, until SIGTERM drains it."""
    device = device_or_exit(require_tpu)
    emit({"phase": "serve_child", "device": device, **cache_state()})
    compiles = watch_compiles()
    from zero_transformer_tpu import serve
    from zero_transformer_tpu.ops.pallas import kernel_traces

    serve.main(["--model", model, "--params", str(params), "--server",
                "--port", str(port), *extra])
    emit({"phase": "serve_child_done", "kernel_traces": dict(kernel_traces),
          "peak_bytes_in_use": peak_bytes(), **compiles})


def zero_phase(out: Path, cfg_path: str = TRAIN_CFG, sets=ZERO_SETS,
               steps: int = ZERO_STEPS, adamw_sets=ZERO_ADAMW_SETS,
               adamw_steps: int = ZERO_ADAMW_STEPS, n_chips: int = 4,
               require_tpu: bool = True) -> dict:
    """ZeRO-1 and ZeRO-2 on a data=n mesh against the one-chip run of the
    same global batch and seed, then the adamw leg, all in this one process."""
    device = device_or_exit(require_tpu)
    cache = cache_state()
    compiles = watch_compiles()
    import jax

    if jax.device_count() != n_chips:
        raise RuntimeError(f"need {n_chips} devices, jax has {jax.device_count()}")
    sys.path.insert(0, str(ROOT))
    import train
    from zero_transformer_tpu.config import MeshConfig, load_config
    from zero_transformer_tpu.ops.pallas import kernel_traces
    from zero_transformer_tpu.parallel.mesh import make_mesh
    from zero_transformer_tpu.training.trainer import Trainer

    def run(name: str, sets: list, steps: int, decreasing: bool, mesh=None) -> dict:
        ckpt = out / name
        cfg = train.apply_overrides(
            load_config(ROOT / cfg_path),
            train.parse_overrides([*sets, f"checkpoint.directory={ckpt}"]),
        )
        before = dict(kernel_traces)
        trainer = Trainer(cfg, mesh=mesh)
        try:
            t0 = time.perf_counter()
            state = trainer.train(max_steps=steps)
            wall = time.perf_counter() - t0
            opt = jax.tree.leaves(state.opt_state)
            per_device = {}
            for leaf in opt:
                for shard in leaf.addressable_shards:
                    per_device[shard.device.id] = (
                        per_device.get(shard.device.id, 0) + shard.data.nbytes
                    )
            got = {
                "opt_state_bytes": sum(x.nbytes for x in opt),
                "param_bytes": sum(x.nbytes for x in jax.tree.leaves(state.params)),
                "opt_state_bytes_per_device": per_device,
            }
        finally:
            trainer.close()
        losses = check_losses(train_rows(ckpt), steps, decreasing)
        traced = {k: v - before.get(k, 0) for k, v in kernel_traces.items()}
        if not (traced.get("flash_fwd") and traced.get("flash_bwd")):
            raise RuntimeError(f"{name}: flash kernel not traced: {traced}")
        return {"loss": losses, "wall_s": round(wall, 1), **got,
                "kernel_traces": traced}

    def on_mesh(name: str, stage: int, sets: list, steps: int, decreasing: bool) -> dict:
        got = run(name, [*sets, f"mesh.data={n_chips}", f"mesh.zero_stage={stage}"],
                  steps, decreasing)
        per = got["opt_state_bytes_per_device"]
        if len(per) != n_chips:
            raise RuntimeError(f"{name}: state on devices {sorted(per)} only")
        got["max_opt_state_share"] = max(per.values()) / got["opt_state_bytes"]
        return got

    one = run("one_chip", [*sets, "mesh.data=1"], steps, True, mesh=make_mesh(
        MeshConfig(data=1), devices=jax.devices()[:1]
    ))
    result = {"phase": "zero4", "ok": True, "device": device,
              "versions": versions(), "config": cfg_path,
              "n_layers": ZERO_LAYERS, "steps": steps,
              "loss_tol": ZERO_LOSS_TOL, "one_chip": one, **cache}
    for stage in (1, 2):
        got = on_mesh(f"zero{stage}", stage, sets, steps, True)
        diffs = [abs(a - b) for a, b in zip(got["loss"], one["loss"])]
        if max(diffs) > ZERO_LOSS_TOL:
            raise RuntimeError(
                f"ZeRO-{stage} losses {got['loss']} differ from one chip's "
                f"{one['loss']} by more than {ZERO_LOSS_TOL}"
            )
        got["max_loss_diff"] = max(diffs)
        got["opt_state_of_params"] = got["opt_state_bytes"] / got["param_bytes"]
        if got["opt_state_of_params"] > FACTORED_STATE_MAX:
            raise RuntimeError(
                f"ZeRO-{stage}: replicated factored state is "
                f"{got['opt_state_of_params']:.3f} of the params' bytes"
            )
        got["opt_state"] = "factored statistics, replicated on every device by design"
        result[f"zero{stage}"] = got
    for stage in (1, 2):
        got = on_mesh(f"adamw_zero{stage}", stage, adamw_sets, adamw_steps, False)
        if got["max_opt_state_share"] > 1.25 / n_chips:
            raise RuntimeError(
                f"adamw ZeRO-{stage}: a device holds "
                f"{got['max_opt_state_share']:.2f} of the optimizer state, "
                f"expected about 1/{n_chips}"
            )
        result[f"adamw_zero{stage}"] = got
    diffs = [abs(a - b) for a, b in zip(result["adamw_zero1"]["loss"],
                                        result["adamw_zero2"]["loss"])]
    if max(diffs) > ZERO_LOSS_TOL:
        raise RuntimeError(f"adamw ZeRO-1 and ZeRO-2 losses differ: {result}")
    return {**result, **compiles}


# ----------------------------------------------------------------- parent


def child_cmd(*args: str) -> list:
    return [sys.executable, str(ROOT / "chip_smoke.py"), *args]


def phase_lines(stdout: str) -> list:
    """The JSON objects a child printed that carry a ``phase`` key (the
    entry points print lines of their own on the same stream)."""
    found = []
    for line in stdout.splitlines():
        if line.startswith("{"):
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if isinstance(obj, dict) and "phase" in obj:
                found.append(obj)
    return found


def run_child(name: str, cmd: list) -> dict:
    proc = subprocess.run(cmd, stdout=subprocess.PIPE, text=True, cwd=ROOT)
    lines = phase_lines(proc.stdout)
    if proc.returncode != 0 or not lines or not lines[-1].get("ok"):
        sys.stderr.write(proc.stdout[-4000:])
        sys.exit(f"chip_smoke: phase {name} failed (exit {proc.returncode})")
    emit(lines[-1])
    return lines[-1]


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(port: int, path: str, body=None, timeout: float = 600.0):
    """(status, parsed JSON body) — an HTTP error status is returned, not
    raised; a refused connection is ``(None, None)``."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data,
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, None
    except (urllib.error.URLError, ConnectionError, socket.timeout):
        return None, None


def prompts(n: int = N_REQUESTS, seed: int = SEED) -> list:
    """n prompts sharing a 160-byte prefix (two and a half prefill chunks —
    whole chunks of it are prefix-cache hits), each ~250 bytes so a prompt
    crosses several 64-token chunks. Pure function of the seed."""
    import random

    rng = random.Random(seed)
    words = ["zero", "shard", "page", "chunk", "ring", "mesh", "flash",
             "token", "step", "slot", "draft", "cache"]

    def text(n_bytes: int) -> str:
        s = ""
        while len(s) < n_bytes:
            s += rng.choice(words) + " "
        return s[:n_bytes]

    prefix = text(160)
    return [prefix + f"[{i}] " + text(85) for i in range(n)]


def drive_server(name: str, cmd: list, port: int, ready_timeout: float = 900.0,
                 max_new_tokens: int = MAX_NEW_TOKENS) -> dict:
    """Start one server child, wait for /healthz 200, send the requests
    concurrently, read /metrics, SIGTERM it, and require exit 0."""
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True, cwd=ROOT)
    try:
        t0 = time.perf_counter()
        seen = []
        while True:
            code, _ = http(port, "/healthz", timeout=5.0)
            if code is not None and (not seen or seen[-1] != code):
                seen.append(code)
            if code == 200:
                break
            if proc.poll() is not None:
                raise RuntimeError(f"phase serve: server {name} exited {proc.returncode} before READY")
            if time.perf_counter() - t0 > ready_timeout:
                raise RuntimeError(f"phase serve: server {name} not READY in {ready_timeout}s")
            time.sleep(0.2)
        ready_s = time.perf_counter() - t0
        # the CLI binds its port after the weights load and goes READY as
        # the scheduler thread starts, so a poll sees refused connections,
        # then at most a 503 or two, then 200 — never anything else
        if any(code != 503 for code in seen[:-1]):
            raise RuntimeError(f"phase serve: /healthz answered {seen} on the way to 200")

        results = [None] * N_REQUESTS

        def one(i: int, prompt: str) -> None:
            results[i] = http(port, "/generate", {
                "prompt": prompt, "max_new_tokens": max_new_tokens,
                "stream": False, "seed": SEED,
            })

        # the first request alone (its prefix chunks get stored), the rest
        # concurrently (they hit them)
        batch = prompts()
        one(0, batch[0])
        threads = [threading.Thread(target=one, args=(i, p))
                   for i, p in enumerate(batch) if i]
        t1 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        requests_s = time.perf_counter() - t1
        for i, (code, body) in enumerate(results):
            if code != 200 or len(body["tokens"]) != max_new_tokens:
                raise RuntimeError(f"phase serve: server {name} request {i}: {code} {body}")
        code, metrics = http(port, "/metrics")
        if code != 200:
            raise RuntimeError(f"phase serve: server {name} /metrics: {code}")
        proc.send_signal(signal.SIGTERM)
        stdout, _ = proc.communicate(timeout=120)
        if proc.returncode != 0:
            raise RuntimeError(f"phase serve: server {name} exited {proc.returncode} on SIGTERM")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    lines = {o["phase"]: o for o in phase_lines(stdout)}
    return {
        "device": lines["serve_child"]["device"],
        "cache_warm": lines["serve_child"]["cache_warm"],
        "healthz": seen, "ready_s": round(ready_s, 1),
        "requests": N_REQUESTS, "concurrent_requests_s": round(requests_s, 2),
        "tokens": [body["tokens"] for _, body in results],
        "text": [body["text"] for _, body in results],
        "metrics": metrics,
        **{k: v for k, v in lines["serve_child_done"].items() if k != "phase"},
    }


METRIC_KEYS = (
    "kernel_paged_attention", "prefill_chunk",
    "draft_k", "spec_ticks", "acceptance_rate", "prefix_hits",
    "prefill_chunks", "completed", "tokens_out", "page_pool_peak",
    "preemptions",
)


def check_server(name: str, got: dict, kernels: bool, spec: bool) -> None:
    m, traced = got["metrics"], got["kernel_traces"]
    bad = {k: v for k, v in m.items()
           if k.startswith("dispatch_") and k.endswith("_violations") and v}
    checks = {
        "page pool used": m["page_pool_peak"] > 0,
        "chunked prefill": m["prefill_chunk"] > 0 and m["prefill_chunks"] > 0,
        "all completed": m["completed"] >= N_REQUESTS,
        "prefix hits": m["prefix_hits"] > 0,
        "no dispatch violations": not bad,
        "paged kernel gauge": m["kernel_paged_attention"] == int(kernels),
        "paged kernel traced": bool(traced.get("paged_attention")) == kernels,
        "flash kernel traced": bool(traced.get("flash_fwd")) == kernels,
        "speculation": (m["spec_ticks"] > 0) == spec,
    }
    failed = [k for k, ok in checks.items() if not ok]
    if failed:
        raise RuntimeError(f"phase serve: server {name}: failed {failed}; metrics {m}; traced {traced}")


def serve_phase(out: Path, model: str, variants: dict, child) -> dict:
    """``child(variant, its_args, port) -> command line``. Runs the variants
    one after another (one process per chip) and compares their tokens."""
    runs = {}
    for name, extra in variants.items():
        port = free_port()
        got = drive_server(name, child(name, extra, port), port)
        check_server(name, got, kernels="xla" not in extra,
                     spec=extra[extra.index("--draft-k") + 1] != "0")
        runs[name] = got
    first = next(iter(runs))
    for name, got in runs.items():
        if got["tokens"] != runs[first]["tokens"] or got["text"] != runs[first]["text"]:
            raise RuntimeError(
                f"greedy output differs between servers {first} and {name}: "
                f"{runs[first]['tokens']} vs {got['tokens']}"
            )
    return {
        "phase": "serve", "ok": True, "device": runs[first]["device"],
        "model": model, "requests_per_server": N_REQUESTS,
        "greedy_identical_across": list(runs),
        "sample_tokens": runs[first]["tokens"][0][:8],
        "servers": {
            name: {
                "healthz": got["healthz"], "ready_s": got["ready_s"],
                "concurrent_requests_s": got["concurrent_requests_s"],
                "cache_warm": got["cache_warm"],
                **{k: got[k] for k in ("compile_s", "cache_hits", "cache_misses",
                                       "kernel_traces", "peak_bytes_in_use")},
                **{k: got["metrics"].get(k) for k in METRIC_KEYS},
                **{k: v for k, v in got["metrics"].items()
                   if k.startswith("dispatch_")},
            }
            for name, got in runs.items()
        },
    }


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4: only ZeRO-1/2 on a data=4 mesh and the one-chip "
                        "run they are compared with")
    # internal: what the parent starts its children with
    p.add_argument("--phase",
                   choices=("train", "extract", "kernels", "serve", "zero4"),
                   help=argparse.SUPPRESS)
    p.add_argument("--variant", choices=tuple(SERVE_VARIANTS), help=argparse.SUPPRESS)
    p.add_argument("--port", type=int, help=argparse.SUPPRESS)
    args = p.parse_args()

    if args.phase == "train":
        emit(train_phase(OUT))
    elif args.phase == "extract":
        emit(extract_phase(OUT))
    elif args.phase == "kernels":
        emit(kernels_phase())
    elif args.phase == "serve":
        serve_child(SERVE_MODEL, OUT / "params.msgpack", args.port,
                    SERVE_ARGS + SERVE_VARIANTS[args.variant])
    elif args.phase == "zero4":
        emit(zero_phase(OUT))
    else:
        shutil.rmtree(OUT, ignore_errors=True)
        OUT.mkdir(parents=True)
        if args.chips == 4:
            last = run_child("zero4", child_cmd("--phase", "zero4"))
        else:
            run_child("train", child_cmd("--phase", "train"))
            run_child("extract", child_cmd("--phase", "extract"))
            run_child("kernels", child_cmd("--phase", "kernels"))
            last = serve_phase(
                OUT, SERVE_MODEL, SERVE_VARIANTS,
                lambda variant, extra, port: child_cmd(
                    "--phase", "serve", "--variant", variant, "--port", str(port)
                ),
            )
            emit(last)
        device = last["device"]
        if device["platform"] != "tpu" or device["count"] != args.chips:
            sys.exit(f"chip_smoke: ran on {device}, wanted {args.chips} TPU chip(s)")
        shutil.rmtree(OUT, ignore_errors=True)  # ~10 GB of checkpoint + params
        emit({"ok": True, "device": device})


if __name__ == "__main__":
    main()

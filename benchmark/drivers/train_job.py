"""Driver for traffic files of ``kind: train_job``: one training job driven
through ``Trainer.train``.

Set-up builds ONE trainer with its compiled step and its state (weights made
on the device from ``--seed`` by ``benchmark/weights.py``), drives it through
its first steps with the window's own call and feed, reads what the
comparison needs after each, and hands the same trainer to the window. The
window is whole steps, timed on the benchmark's own clock through its feed.

No family and no optimizer is named here: the configuration's ``reference``
key names the family's plain reference (leaves, forward, backward), and the
job's ``optimizer.optimizer`` names the plain optimizer under
``benchmark/optimizers/``.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import shutil
import statistics
import sys
import time
from pathlib import Path

from benchmark import arith, harness, traffic, weights
from benchmark import trace as trace_mod


class SeededLoader:
    """The trainer's feed: step k's batch is a function of (seed, k). It
    is also the benchmark's own clock on the loop: ``fetched[k]`` is when the
    trainer asked for step k's batch, which opens that step."""

    def __init__(self, seed, accum, rows, ctx, vocab):
        self.args = (accum, rows, ctx, vocab)
        self.seed = seed
        self.step = 0
        self.fetched: list = []

    def __iter__(self):
        return self

    def __next__(self):
        self.fetched.append(time.monotonic())
        batch = traffic.train_batch(self.seed, self.step, *self.args)
        self.step += 1
        return batch

    def state(self):
        return {"steps_consumed": self.step}

    def fault_counters(self):
        return {}


class NoCheckpoint:
    """Stands in for the trainer's checkpoint manager and writes nothing
    (see the traffic file's ``departures``)."""

    last_digest_ms = 0.0

    def __init__(self):
        self._latest = None

    def save(self, step, state, meta=None, force=False):
        if force:
            self._latest = step
        return False

    def latest_step(self):
        return self._latest

    def incomplete_steps(self):
        return []

    def wait(self):
        pass

    def close(self):
        pass


def build_config(cell: dict, out_dir: Path):
    from zero_transformer_tpu.config import Config, apply_dotted_overrides

    cfg = dataclasses.replace(Config(), model=harness.model_config(cell["config"]))
    over = dict(cell["traffic"]["overrides"])
    over["mesh.data"] = cell["chips"]
    over["data.source"] = "synthetic"
    over["checkpoint.directory"] = str(out_dir)
    over["checkpoint.async_save"] = False
    return apply_dotted_overrides(cfg, over)


def change_norms(params, table: dict, key) -> dict:
    """Per-leaf norm of (params - the weights the seed gives)."""
    import jax
    import jax.numpy as jnp

    def f(p, k):
        p0 = weights.make_params(table, k)
        return jax.tree.map(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))), p, p0)

    return {k: float(v) for k, v in weights.flatten(jax.jit(f)(params, key)).items()}


def step_spans(tracer, lo: int, hi: int) -> list:
    """[(fetch_start, sync_end, fetch_s, dispatch_s)] of steps lo+1 .. hi."""
    per = {}
    for _, track, name, t0, t1, attrs in tracer.spans():
        if track != "train" or not attrs or "step" not in attrs:
            continue
        if name in ("data_fetch", "dispatch", "device_sync"):
            per.setdefault(attrs["step"], {})[name] = (t0, t1)
    out = []
    for step in range(lo + 1, hi + 1):
        s = per.get(step, {})
        if not {"data_fetch", "dispatch", "device_sync"} <= set(s):
            raise SystemExit(f"the trainer left no whole-step spans for step {step}")
        out.append((s["data_fetch"][0], s["device_sync"][1],
                    s["data_fetch"][1] - s["data_fetch"][0],
                    s["dispatch"][1] - s["dispatch"][0]))
    return out


def logged(trainer, key: str) -> dict:
    return {t["step"]: t[key] for _, t in trainer.flight.ticks() if key in t}


def gap_by_worst_leaf(prog: dict, reference: dict, skip=()) -> tuple:
    """max over leaves of |prog - ref| / max(ref, median ref): the gap
    between the two norms, not the norm of a difference."""
    med = statistics.median(reference.values())
    worst, where = 0.0, None
    for path, r in reference.items():
        if path in skip:
            continue
        g = abs(prog[path] - r) / max(r, med)
        if g > worst or where is None:
            worst, where = g, path
    return worst, where


def leaf_norms(tree):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), t))(tree)


def clip_by_global_norm(grads, max_norm):
    """The recipe's clip, in place."""
    import jax
    import jax.numpy as jnp

    def clip(g, m):
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        scale = jnp.where(norm < m, 1.0, m / norm)
        return jax.tree.map(lambda x: x * scale, g)

    return jax.jit(clip, donate_argnums=(0,))(grads, max_norm)


def run_reference(cell: dict, seed: int, mode: str = "f32", row_weights=None) -> dict:
    """The plain reference's readings over the first steps of the job:
    ``full_steps`` steps followed whole (loss, gradient, update), then the
    loss alone of the steps up to ``loss_steps``."""
    import jax

    job, model = cell["traffic"], cell["config"]["model"]
    over = job["overrides"]
    ref = harness.load_reference(cell["config"])
    opt = harness.load_optimizer(over["optimizer.optimizer"])
    steps = job["reference"]["full_steps"]
    accum = over["training.gradient_accumulation_steps"]
    rows, ctx = over["training.batch_size"], over["training.train_context"]
    lr, wd = over["optimizer.peak_learning_rate"], over["optimizer.weight_decay"]
    key = weights.seed_key(seed, "weights")
    table = ref.leaf_table(model)
    clock = [time.monotonic()]

    def rows_of(k):
        batch = traffic.train_batch(seed, k, accum, rows, ctx, model["vocab_size"])
        return jax.numpy.asarray(batch.reshape(accum * rows, ctx))

    def lap(what):
        now = time.monotonic()
        print(f"reference[{mode}] {what}: {now - clock[0]:.1f} s", file=sys.stderr)
        clock[0] = now

    params = weights.flatten(weights.build(table, key))
    state = opt.init(params)
    out = {"loss": {}, "grad": None, "change": None}
    for k in range(steps):
        with jax.default_matmul_precision("highest"):
            loss, grads = ref.step_loss_and_grads(
                weights.nest(params), rows_of(k), model, mode=mode,
                rows_per_block=job["reference"]["rows_per_block"],
                row_weights=row_weights,
            )
        out["loss"][k + 1] = float(loss)
        lap(f"step {k + 1} loss and gradient")
        grads = clip_by_global_norm(grads, over["optimizer.grad_clip"])
        if k == 0:
            out["grad"] = {p: float(v) for p, v in
                           weights.flatten(leaf_norms(grads)).items()}
        grads = weights.flatten(grads)
        params, state = opt.update(params, grads, state, lr, wd, ref.decays)
    for k in range(steps, job["reference"]["loss_steps"]):
        with jax.default_matmul_precision("highest"):
            out["loss"][k + 1] = ref.step_loss(
                weights.nest(params), rows_of(k), model, mode=mode,
                rows_per_block=job["reference"]["loss_rows_per_block"],
                row_weights=row_weights)
        lap(f"step {k + 1} loss")
    out["change"] = {
        path: float(weights.leaf_distance(params[path], key, path, *table[path]))
        for path in params
    }
    lap("change")
    return out


def compare(prog: dict, reference: dict, limits: dict) -> dict:
    """Every number compared, beside its limit."""
    compared = {}
    for step, r in reference["loss"].items():
        compared[f"loss_rel_gap_step{step}"] = {
            "value": abs(prog["loss"][step] - r) / abs(r),
            "limit": limits["loss_rel_gap"],
        }
    # leaves whose gradient is nought to rounding in the reference move under
    # Adam by round-off alone: out of the change by a rule, not by name
    med = statistics.median(reference["grad"].values())
    skip = {p for p, g in reference["grad"].items() if g < 1e-3 * med}
    g, g_at = gap_by_worst_leaf(prog["grad"], reference["grad"])
    c, c_at = gap_by_worst_leaf(prog["change"], reference["change"], skip)
    compared["grad_leaf_gap"] = {"value": g, "limit": limits["grad_leaf_gap"], "leaf": g_at}
    compared["change_leaf_gap"] = {"value": c, "limit": limits["change_leaf_gap"], "leaf": c_at}
    return compared


def program_readings(trainer, cell, seed, steps: int) -> dict:
    """Drive the trainer through its first ``steps`` steps, one call of
    ``Trainer.train`` each, and read what the comparison needs."""
    job, model = cell["traffic"], cell["config"]["model"]
    ref_steps = job["reference"]["full_steps"]
    key = weights.seed_key(seed, "weights")
    table = harness.load_reference(cell["config"]).leaf_table(model)
    opt = harness.load_optimizer(job["overrides"]["optimizer.optimizer"])
    prog = {"grad": None, "change": None}
    for k in range(1, steps + 1):
        trainer.train(max_steps=1)
        prog["returned"] = time.monotonic()
        if k == 1:
            prog["grad"] = opt.first_gradient_norms(
                trainer.state.opt_state, weights.flatten(trainer.state.params))
        if k == ref_steps:
            prog["change"] = change_norms(trainer.state.params, table, key)
    prog["loss"] = logged(trainer, "loss")
    return prog


def build_trainer(cell, seed, out_dir, devices=None):
    import jax

    from zero_transformer_tpu.parallel.mesh import make_mesh
    from zero_transformer_tpu.training.trainer import Trainer

    model = cell["config"]["model"]
    over = cell["traffic"]["overrides"]
    cfg = build_config(cell, out_dir)
    loader = SeededLoader(
        seed, over["training.gradient_accumulation_steps"],
        over["training.batch_size"], over["training.train_context"],
        model["vocab_size"])
    devices = devices if devices is not None else jax.devices()[: cell["chips"]]
    trainer = Trainer(cfg, mesh=make_mesh(cfg.mesh, devices), train_loader=loader)
    trainer.ckpt.close()
    trainer.ckpt = NoCheckpoint()
    table = harness.load_reference(cell["config"]).leaf_table(model)
    weights.check_tree(table, trainer.abstract_state().params)
    trainer.state = make_state(trainer, table, weights.seed_key(seed, "weights"))
    return trainer


def make_state(trainer, table, key):
    """Weights and a fresh optimizer state on the device, in the trainer's
    own shardings, from the seed's key (an argument, so one program serves
    every seed)."""
    import jax
    import jax.numpy as jnp

    from zero_transformer_tpu.parallel.zero import TrainState

    def init(k):
        params = weights.make_params(table, k)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=trainer.tx.init(params))

    return jax.jit(init, out_shardings=trainer.plan.state)(key)


def run(cell: dict, devices, seed: int, seconds: float, trace: bool) -> dict:
    import jax

    job, model = cell["traffic"], cell["config"]["model"]
    over = job["overrides"]
    out_dir = harness.ROOT / ".bench_out" / cell["name"]
    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True)
    compiles = harness.CompileCounter()
    setup_steps = job["setup_steps"]
    since = time.monotonic()
    phases = [("imports", since - harness.T_START)]

    def phase(name):
        nonlocal since
        now = time.monotonic()
        phases.append((name, now - since))
        since = now

    trainer = build_trainer(cell, seed, out_dir, devices)
    jax.block_until_ready(trainer.state.params)
    phase("trainer+state")
    try:
        prog = program_readings(trainer, cell, seed, setup_steps)
        phase(f"{setup_steps} steps+readings")
        # the warm step, and then the window, on the benchmark's own clock:
        # from the feed's hand-over of a step's batch to the return of the
        # call that ran it (the trainer syncs with the device at every step)
        feed = trainer.train_loader
        step_s = prog.pop("returned") - feed.fetched[-1]
        n = max(job["min_window_steps"], int(seconds // step_s))
        compiled_before = compiles.count
        trainer.train(max_steps=n)
        t_return = time.monotonic()
        compiled_in_window = compiles.count - compiled_before
        opened = feed.fetched[-n:]
        steps = step_spans(trainer.tracer, setup_steps, setup_steps + n)
        device = harness.device_block(devices)
        if trace:
            # one more step through the same call, traced by the benchmark
            # itself (Python tracing off) once the window has closed
            with trace_mod.capture(out_dir / "profile"):
                trainer.train(max_steps=1)
        losses = logged(trainer, "loss")
    finally:
        trainer.close()
    print("set-up phases (s): " + ", ".join(f"{k} {v:.1f}" for k, v in phases),
          file=sys.stderr)
    t0, t1 = arith.whole_step_window(list(zip(opened, opened[1:] + [t_return])))
    tokens_per_step = (over["training.batch_size"] * over["training.train_context"]
                       * over["training.gradient_accumulation_steps"])
    tok_s_chip = arith.rate(n * tokens_per_step, t1 - t0) / len(devices)
    setup_s = t0 - harness.T_START
    print(f"window: {n} whole steps in {t1 - t0:.4f} s ({steps[-1][1] - steps[0][0]:.4f} s "
          f"by the trainer's own spans); warm step {step_s:.4f} s; "
          f"compiles in window {compiled_in_window}", file=sys.stderr)

    # free the program's state before the reference takes the chip
    trainer.state = None
    trainer._live = None
    del trainer
    gc.collect()

    t_ref = time.monotonic()
    reference = run_reference(cell, seed, job["reference"]["precision"])
    reference_s = time.monotonic() - t_ref
    compared = compare(prog, reference, job["limits"])
    finite = all(math.isfinite(v) for v in losses.values())
    compared["window_losses_finite"] = {"value": 0.0 if finite else 1.0, "limit": 0.0}
    compared["compiles_in_window"] = {"value": float(compiled_in_window), "limit": 0.0}

    result = {
        "correct": harness.decide(compared),
        "attempted": n,
        "failed": 0,
        "metrics": {},
        "device": device,
        "reference_s": reference_s,
        "window_s": t1 - t0,
        "compared": compared,
    }
    if not trace:
        result["metrics"] = {
            "train_tokens_per_s_chip": {"value": tok_s_chip, "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        return result

    reduced = trace_mod.reduce_dir(out_dir / "profile", len(devices))
    ref = harness.load_reference(cell["config"])
    ctx = {
        "kind": "train_job", "model": model, "job": job, "chips": len(devices),
        "active_params": ref.active_params(model),
        "attention_flops_per_position": ref.attention_flops_per_position(model),
        "steps": steps, "tokens_per_step": tokens_per_step,
        "tokens_per_s_chip": tok_s_chip, "seq_len": over["training.train_context"],
        "rows_per_micro": over["training.batch_size"] // len(devices),
        "micro_batches": over["training.gradient_accumulation_steps"],
        "trace": reduced, "traced_steps": 1,
        "peak": arith.load_peak(devices[0].device_kind, harness.HERE / "peaks.json"),
    }
    result["metrics"] = harness.read_per_layer(cell, ctx)
    result["device"]["busy_s"] = reduced["busy_s"]
    result["device"]["window_s"] = reduced["window_s"]
    result["breakdown"] = reduced["breakdown"]
    return result


def calibrate(cell, devices, seeds, controls, seconds):
    """Yields, per seed, the numbers the limits are set from: the program's
    readings against the reference and, for the first ``controls`` seeds, the
    control's (the reference in fp8 in the program's place) and the planted
    half-batch fault's. One trainer (one compile) serves every seed: its
    state and feed are set anew from each, and freed before the reference
    takes the chip."""
    job, model = cell["traffic"], cell["config"]["model"]
    out_dir = harness.ROOT / ".bench_out" / (cell["name"] + "_calibrate")
    out_dir.mkdir(parents=True, exist_ok=True)
    table = harness.load_reference(cell["config"]).leaf_table(model)
    steps = job["reference"]["loss_steps"]
    n_blocks = (job["overrides"]["training.batch_size"]
                * job["overrides"]["training.gradient_accumulation_steps"]
                // job["reference"]["rows_per_block"])
    half = [2.0 / n_blocks if b < n_blocks // 2 else 0.0 for b in range(n_blocks)]

    def values(compared):
        return {k: (v["value"], v["leaf"]) if "leaf" in v else v["value"]
                for k, v in compared.items()}

    trainer = build_trainer(cell, seeds[0], out_dir, devices)
    for i, seed in enumerate(seeds):
        trainer.state = make_state(trainer, table, weights.seed_key(seed, "weights"))
        trainer.train_loader.seed, trainer.train_loader.step = seed, 0
        prog = program_readings(trainer, cell, seed, steps)
        trainer.state = None
        trainer._live = None
        gc.collect()
        reference = run_reference(cell, seed, "f32")
        line = {"seed": seed, "program": values(compare(prog, reference, job["limits"]))}
        if i < controls:
            for name, kw in (("control_fp8", {"mode": "fp8"}),
                             ("fault_half_batch", {"mode": "f32", "row_weights": half})):
                other = run_reference(cell, seed, **kw)
                line[name] = values(compare(other, reference, job["limits"]))
        yield line
    trainer.close()

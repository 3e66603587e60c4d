"""Trainer: the orchestrator the reference keeps inline in ``main_zero.py``.

One object wires config → mesh → model → optimizer → sharding plan → fused
train step → data → checkpoints → metrics, with the reference's semantics
(eval every N steps, checkpoint keep=K, resume = restore + rng fold + loader
fast-forward, warm-init from another run's params) but none of its per-step
resharding churn: state lives permanently in its ZeRO sharding and the hot
loop is ONE jitted call per step (vs the reference's four dispatches,
``main_zero.py:495-500``).
"""
from __future__ import annotations

import dataclasses
import logging
import signal
import threading
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from zero_transformer_tpu import checkpoint as ckpt_lib
from zero_transformer_tpu.config import Config
from zero_transformer_tpu.data import DataLoader, device_put_batch, make_loader
from zero_transformer_tpu.models.gpt import Transformer
from zero_transformer_tpu.parallel.mesh import make_mesh
from zero_transformer_tpu.parallel.zero import (
    TrainState,
    init_train_state,
    make_eval_step,
    make_plan,
    make_train_step,
)
from zero_transformer_tpu.training.optimizer import make_optimizer, make_schedule
from zero_transformer_tpu.obs import FlightRecorder, Tracer
from zero_transformer_tpu.obs.profiling import start_trace
from zero_transformer_tpu.utils import monitoring
from zero_transformer_tpu.utils.jax_compat import ensure_donatable

log = logging.getLogger("zero_transformer_tpu")


def _exposed_comm_from_artifact(
    path: str, overlap_comm: bool
) -> Optional[float]:
    """Read the measured exposed-comm fraction for the ACTIVE overlap arm
    from a BENCH_step.json (scripts/train_step_bench.py). Returns None —
    the gauge stays unregistered — on a missing/unreadable artifact or one
    from a different backend than this process (a CPU-box measurement must
    not masquerade as this TPU's decomposition)."""
    import json

    import jax as _jax

    try:
        with open(path) as f:
            art = json.load(f)
    except (OSError, ValueError):
        log.warning("step_bench_artifact %s unreadable; exposed_comm_frac "
                    "gauge disabled", path)
        return None
    # (platform, device_kind) is the comparability key — the same rule the
    # bench guard applies; a v4 measurement must not export as a v5e run's
    # decomposition any more than a CPU one may
    hw = (_jax.default_backend(), _jax.devices()[0].device_kind)
    art_hw = (art.get("platform"), art.get("device_kind"))
    if art_hw != hw:
        log.warning(
            "step_bench_artifact %s measured on %r but this run is on %r; "
            "exposed_comm_frac gauge disabled (re-run "
            "scripts/train_step_bench.py here)",
            path, art_hw, hw,
        )
        return None
    arm = art.get("overlap_on" if overlap_comm else "overlap_off") or {}
    frac = arm.get("exposed_comm_frac")
    return float(frac) if frac is not None else None


def remap_loader_state(
    meta: Optional[dict],
    batch_size: int,
    train_context: int,
    accum_steps: int = 1,
) -> Optional[dict]:
    """Map a saved loader position onto the CURRENT run's batch geometry.

    The loader position is stored in GLOBAL optimizer steps
    (``steps_consumed``; each consumes ``batch_size * accum_steps``
    sequences of ``train_context`` tokens), so a topology change alone
    (different device/host count) needs NO remap: every process assembles
    the same global batch and the global-token trajectory continues exactly
    where it left off. When the geometry changed — ``batch_size``,
    ``train_context``, or ``gradient_accumulation_steps`` (the canonical
    elastic move is halving the devices and doubling accum to preserve the
    global batch) — the position is remapped by TOKEN count, rounding DOWN
    to the previous whole-step boundary: up to one optimizer step's tokens
    are replayed, never skipped (the batch-boundary semantics documented in
    docs/RESILIENCE.md and pinned in tests/test_elastic.py)."""
    loader_state = (meta or {}).get("loader")
    if not loader_state:
        return None
    sched = (meta or {}).get("schedule") or {}
    old_bs = int(sched.get("batch_size", batch_size))
    old_ctx = int(sched.get("train_context", train_context))
    old_accum = int(sched.get("accum_steps", accum_steps))
    if (old_bs, old_ctx, old_accum) == (batch_size, train_context, accum_steps):
        return loader_state
    steps = int(loader_state.get("steps_consumed", 0))
    tokens = steps * old_bs * old_accum * old_ctx
    new_steps, replayed = divmod(
        tokens, batch_size * accum_steps * train_context
    )
    if replayed:
        log.warning(
            "loader remap: batch geometry changed (%d seq x %d accum x %d "
            "tok -> %d x %d x %d); resuming at optimizer step %d replays "
            "%d tokens (position rounds DOWN to a step boundary — replay, "
            "never skip)",
            old_bs, old_accum, old_ctx, batch_size, accum_steps,
            train_context, new_steps, replayed,
        )
    return {"steps_consumed": int(new_steps)}


@dataclasses.dataclass(frozen=True)
class TrainingBuild:
    """Mesh → model → optimizer → plan → compiled-step builders for a config.

    The data-free, side-effect-free half of Trainer construction, factored
    out so the ``--memory-analysis`` surface (and tests) can build the real
    train step without touching loaders or checkpoint directories."""

    mesh: Any
    model: Transformer
    schedule: Any
    tx: Any
    plan: Any
    train_step: Any
    eval_step: Any
    sample_shape: tuple


def build_training(cfg: Config, mesh=None) -> TrainingBuild:
    if cfg.model.param_quant != "none":
        raise ValueError(
            "param_quant is an inference-only configuration (serve "
            "--quantize); training runs on full-precision params"
        )
    mesh = mesh if mesh is not None else make_mesh(cfg.mesh)
    opt = dataclasses.replace(cfg.optimizer, total_steps=cfg.training.total_steps)
    # an active sequence axis routes attention through the ring-attention
    # context-parallel path (ops/ring_attention.py)
    from zero_transformer_tpu.parallel.mesh import SEQUENCE_AXIS

    seq_parallel = mesh.shape[SEQUENCE_AXIS] > 1
    model = Transformer(cfg.model, mesh=mesh if seq_parallel else None)
    schedule = make_schedule(opt)
    tx = make_optimizer(opt, schedule)

    sample_shape = (cfg.training.batch_size, cfg.training.train_context)
    plan = make_plan(
        model, tx, mesh, sample_shape, cfg.mesh.zero_stage,
        pp_schedule=cfg.mesh.pp_schedule,
    )
    train_step = make_train_step(
        model,
        tx,
        mesh,
        plan,
        cfg.mesh.zero_stage,
        schedule,
        # lets the explicit ZeRO-2/3 core rebuild the optimizer with a
        # shard-aware grad-clip norm (same opt-state structure)
        tx_factory=lambda norm_fn, zc=None: make_optimizer(
            opt, schedule, norm_fn, zero_collectives=zc
        ),
        pp_schedule=cfg.mesh.pp_schedule,
        grad_accum_dtype=cfg.training.grad_accum_dtype,
        pp_interleave=cfg.mesh.pp_interleave,
        overlap_comm=cfg.mesh.overlap_comm,
    )
    eval_step = make_eval_step(model, mesh, plan)
    return TrainingBuild(
        mesh=mesh, model=model, schedule=schedule, tx=tx, plan=plan,
        train_step=train_step, eval_step=eval_step, sample_shape=sample_shape,
    )


def _schedule_memory(
    cfg: Config, b: "TrainingBuild", abstract, accum: int
) -> Dict[str, Any]:
    """Analytic, schedule-aware memory itemization for ``memory_analysis``.

    Estimates (clearly labeled — the compiled ``temp_bytes`` is the ground
    truth when the backend reports it): per-microbatch activation bytes are
    one residual-stream tensor [batch, T, d_model] at compute dtype; the
    pipeline stash formulas count what each engine's wavefront keeps live
    (GPipe/interleaved: the differentiated tick scan saves its carry once
    per tick; 1F1B: the hand-managed 2P-slot input ring)."""
    from zero_transformer_tpu.analysis.memory import pp_stash_ticks
    from zero_transformer_tpu.config import resolve_dtype
    from zero_transformer_tpu.parallel.pipeline import bubble_fraction

    mc = cfg.mesh
    P_ = mc.pipe
    V = mc.pp_interleave
    out: Dict[str, Any] = {
        "pp_schedule": mc.pp_schedule,
        "pp_interleave": V,
        "overlap_comm": mc.overlap_comm,
        "bubble_frac": round(bubble_fraction(mc.pp_schedule, P_, accum, V), 5),
    }
    act = (
        cfg.training.batch_size
        * cfg.training.train_context
        * cfg.model.d_model
        * jnp.dtype(resolve_dtype(cfg.model.compute_dtype)).itemsize
    )
    out["microbatch_activation_bytes"] = act
    if P_ > 1:
        # ONE formula table with the analytic pruner (analysis/memory.py)
        stash_ticks = pp_stash_ticks(mc.pp_schedule, accum, P_, V)
        out["pp_activation_stash_bytes_est"] = stash_ticks * act
        if mc.pp_schedule == "interleaved":
            # interleaved stores the block stack pipe-replicated (see
            # sharding.plan_rules): P-1 extra copies vs the contiguous shard
            blocks_bytes = sum(
                leaf.size * jnp.dtype(leaf.dtype).itemsize
                for leaf in jax.tree.leaves(abstract.params["blocks"])
            )
            out["pp_block_replication_extra_bytes"] = (P_ - 1) * (
                blocks_bytes // P_
            )
    if mc.overlap_comm:
        from zero_transformer_tpu.parallel.overlap import bucket_summary

        out["overlap_buckets"] = bucket_summary(b.plan, b.mesh, abstract.params)
    return out


def memory_analysis(cfg: Config, accum: Optional[int] = None) -> Dict[str, Any]:
    """AOT-compile the train step for ``cfg`` and report the compiled memory
    picture — no state is materialized and nothing executes. The tool behind
    sizing runs for a 16 GB chip (see docs/DESIGN.md "The 16 GB budget"):
    the same HBM accounting the AOT compiler enforces when it rejects a
    config, exposed BEFORE a multi-minute failed launch.

    Compiled sizes (argument/output/temp/alias/peak) are PER DEVICE —
    exactly what must fit one chip's HBM; the ``*_global`` keys are the
    logical whole-tree sizes. Backends without ``memory_analysis`` support
    fall back to the shape-derived global totals with ``"exact": False``.

    The ``schedule`` block keeps the estimate honest per training schedule:
    the pipeline engines stash activations across the wavefront (O(M) ticks
    for GPipe, the 2P-slot ring for 1F1B, O(V*M) ticks for interleaved —
    which ALSO stores the block stack pipe-replicated), and ``overlap_comm``
    keeps up to two gathered layer buckets live while the scan runs; all of
    that is inside the compiled ``temp_bytes`` when exact, and itemized
    analytically here so a CPU sizing pass still sees it."""
    b = build_training(cfg)
    abstract = ckpt_lib.abstract_state(b.model, b.tx, b.plan, b.sample_shape)
    accum = accum or cfg.training.gradient_accumulation_steps
    batch = jax.ShapeDtypeStruct((accum, *b.sample_shape), jnp.int32)
    rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    compiled = b.train_step.lower(abstract, batch, rng).compile()

    def _tree_bytes(tree) -> int:
        return sum(
            leaf.size * jnp.dtype(leaf.dtype).itemsize
            for leaf in jax.tree.leaves(tree)
        )

    # GLOBAL logical sizes; the compiled numbers below are PER DEVICE (a
    # ZeRO-sharded opt state divides across the mesh, so on n devices
    # alias/argument bytes are roughly params + sharded-state/n each)
    out = {
        "state_bytes_global": _tree_bytes(abstract),
        "batch_bytes_global": _tree_bytes(batch),
        "n_devices": len(b.mesh.devices.ravel()),
        "tokens_per_step": accum * b.sample_shape[0] * b.sample_shape[1],
        "schedule": _schedule_memory(cfg, b, abstract, max(accum, 1)),
    }
    # the compile-free analytic itemization (analysis/memory.py) rides
    # along so one report carries both the compiled ground truth and the
    # numbers the autotuner's pruner would see for this point
    from zero_transformer_tpu.analysis.memory import analytic_memory

    out["analytic"] = analytic_memory(cfg, accum=accum)
    try:
        ma = compiled.memory_analysis()
        out.update(
            exact=True,
            argument_bytes=ma.argument_size_in_bytes,
            output_bytes=ma.output_size_in_bytes,
            temp_bytes=ma.temp_size_in_bytes,
            alias_bytes=ma.alias_size_in_bytes,
            generated_code_bytes=ma.generated_code_size_in_bytes,
            # donated state aliases in place, so the live peak is roughly
            # arguments (incl. state) + temps − aliased output
            peak_estimate_bytes=(
                ma.argument_size_in_bytes
                + ma.temp_size_in_bytes
                + ma.output_size_in_bytes
                - ma.alias_size_in_bytes
            ),
        )
    except Exception as e:  # backend without memory_analysis (CPU)
        out.update(exact=False, unavailable_reason=f"{type(e).__name__}: {e}")
    return out


class Trainer:
    def __init__(
        self,
        cfg: Config,
        mesh=None,
        train_loader: Optional[DataLoader] = None,
        val_loader: Optional[DataLoader] = None,
        use_wandb: bool = False,
        chaos=None,
    ):
        self.cfg = cfg
        build = build_training(cfg, mesh=mesh)
        self.mesh = build.mesh
        self.zero_stage = cfg.mesh.zero_stage
        self.model = build.model
        self.schedule = build.schedule
        self.tx = build.tx
        self.sample_shape = build.sample_shape
        self.plan = build.plan
        self.train_step = build.train_step
        self.eval_step = build.eval_step
        self.batch_sharding = NamedSharding(
            self.mesh, P(None, *self.plan.batch.spec)
        )

        self.train_loader = train_loader or make_loader(cfg)
        # lazy: a run with evaluation disabled must not require validation data
        self._val_loader = val_loader

        self.ckpt = ckpt_lib.CheckpointManager(
            cfg.checkpoint.directory,
            keep=cfg.checkpoint.keep,
            save_frequency=cfg.checkpoint.save_frequency,
            async_save=cfg.checkpoint.async_save,
            integrity=cfg.checkpoint.integrity,
        )
        # fail fast on a bad checkpoint destination (wrong bucket, perms)
        # before any compute is spent — the manager is otherwise lazy
        self.ckpt.ensure_ready()
        # chaos injection (tests/test_resilience.py): wrap the fault seams —
        # step function, loader, checkpoint manager — before anything
        # compiles against them. None in production runs.
        self._chaos = chaos
        if chaos is not None:
            self.train_step = chaos.wrap_train_step(self.train_step)
            self.train_loader = chaos.wrap_loader(self.train_loader)
            self.ckpt = chaos.wrap_checkpoint(self.ckpt)
        # anomaly-guard wrap cache, keyed on the identity of the step
        # function it wrapped (tests monkeypatch self.train_step; the guard
        # must wrap whatever is current at train() time, once)
        self._guard_cache: Optional[tuple] = None
        # supervisor-facing run status
        self.preempted = False
        self.last_step: Optional[int] = None
        self.resilience_report: Dict[str, Any] = {}
        # filled by a verified resume (quarantine/fallback counters)
        self._restore_report: Optional[ckpt_lib.RestoreReport] = None
        from zero_transformer_tpu.config import flatten_config

        self.metrics = monitoring.MetricsLogger(
            directory=cfg.checkpoint.directory,
            use_wandb=use_wandb,
            # full flattened run config at init (reference main_zero.py:354-366)
            config=flatten_config(cfg),
        )
        # observability (obs/): the step loop records per-phase spans (data
        # fetch, dispatch, device sync, checkpoint save, replica audit) into
        # a bounded tracer, and a flight recorder keeps the last N step
        # summaries + events for the post-mortem dump fired on anomaly
        # halt, watchdog abort, and checkpoint quarantine. Both export to
        # the run directory beside metrics.jsonl (local dirs only — object
        # stores have no append/dump semantics here).
        from zero_transformer_tpu.utils.paths import is_remote_path

        obs_dir = (
            cfg.checkpoint.directory
            if cfg.checkpoint.directory
            and not is_remote_path(cfg.checkpoint.directory)
            and jax.process_index() == 0
            else None
        )
        self.tracer = Tracer(capacity=16384)
        self.flight = FlightRecorder(directory=obs_dir, tracer=self.tracer)
        # step-time decomposition gauges (PR 8): bubble_frac is ANALYTIC —
        # exact for the configured schedule (pipeline.bubble_fraction, the
        # same formula the bench and memory_analysis use); exposed_comm_frac
        # is a MEASUREMENT and only reported when the operator points
        # training.step_bench_artifact at a BENCH_step.json measured for
        # this platform (scripts/train_step_bench.py). Scrape them from
        # /metrics via train.py --metrics-port (obs.MetricsExporter).
        from zero_transformer_tpu.obs import Registry
        from zero_transformer_tpu.parallel.pipeline import bubble_fraction

        self.registry = Registry()
        self._bubble_frac = bubble_fraction(
            cfg.mesh.pp_schedule,
            cfg.mesh.pipe,
            max(cfg.training.gradient_accumulation_steps, 1),
            cfg.mesh.pp_interleave,
        )
        self._exposed_comm_frac: Optional[float] = None
        if cfg.training.step_bench_artifact:
            self._exposed_comm_frac = _exposed_comm_from_artifact(
                cfg.training.step_bench_artifact, cfg.mesh.overlap_comm
            )
        self.registry.gauge_func(
            "train_bubble_frac",
            "analytic pipeline-bubble fraction of the configured schedule",
            lambda: self._bubble_frac,
        )
        if self._exposed_comm_frac is not None:
            self.registry.gauge_func(
                "train_exposed_comm_frac",
                "measured exposed-communication fraction of step time "
                "(from training.step_bench_artifact)",
                lambda: self._exposed_comm_frac,
            )
        self.rng = jax.random.PRNGKey(cfg.training.seed)
        # validation window pin: source state captured at first evaluate(),
        # restored before every later one, so eval always scores the SAME
        # data window and loss curves are comparable step-to-step
        self._val_window: Optional[dict] = None
        self.flops_per_token = monitoring.model_flops_per_token(
            cfg.model.params_per_token,
            cfg.model.kv_entries,  # attention runs once per (pass, layer)
            cfg.model.d_model,
            cfg.training.train_context,
        )
        self.state: Optional[TrainState] = None
        # compile-family sanitizer (analysis/runtime.py): the train step is
        # ONE program for the whole run — batch geometry, rng layout and
        # carry structure are fixed at build time. A second distinct
        # signature here means a shape leaked into the step loop (the
        # "training got slow" recompile class; strict mode raises in tests)
        from zero_transformer_tpu.analysis.runtime import bounded_dispatch

        self.dispatch_site = bounded_dispatch("trainer.step", 1)

    @property
    def val_loader(self) -> DataLoader:
        if self._val_loader is None:
            self._val_loader = make_loader(self.cfg, validation=True)
        return self._val_loader

    # -- state lifecycle ----------------------------------------------------

    def abstract_state(self) -> TrainState:
        return ckpt_lib.abstract_state(
            self.model, self.tx, self.plan, self.sample_shape
        )

    def _save_meta(self) -> dict:
        """Per-save JSON metadata: loader position + the topology and batch
        geometry the checkpoint was written under (what elastic resume
        validates and remaps against)."""
        from zero_transformer_tpu.parallel import sharding as shd

        return {
            "loader": self.train_loader.state(),
            "topology": shd.topology_summary(
                self.mesh, self.zero_stage, self.cfg.mesh.pp_schedule
            ),
            "schedule": {
                "batch_size": self.cfg.training.batch_size,
                "train_context": self.cfg.training.train_context,
                "accum_steps": max(
                    self.cfg.training.gradient_accumulation_steps, 1
                ),
            },
        }

    def _check_restore_meta(self, meta: dict) -> None:
        """Pre-restore elastic-topology validation (raises ValueError — fatal
        to the supervisor — on genuinely incompatible topologies, BEFORE any
        array IO or pjit compilation touches the checkpoint)."""
        from zero_transformer_tpu.parallel import sharding as shd

        notes = shd.check_elastic_compat(
            (meta or {}).get("topology"),
            self.mesh,
            self.zero_stage,
            self.cfg.training.batch_size,
            pp_schedule=self.cfg.mesh.pp_schedule,
        )
        for note in notes:
            log.warning("elastic resume: %s", note)

    def init_state(self) -> TrainState:
        """Fresh init, or resume / warm-init per the checkpoint config."""
        ck = self.cfg.checkpoint
        if ck.resume and self.ckpt.latest_step() is not None:
            # verified restore: digest-checks every leaf against the step's
            # integrity manifest, quarantines corrupt step dirs, falls back
            # to the newest verified older step, and validates/reshards
            # across topology changes (elastic ZeRO resume)
            state, meta, report = self.ckpt.restore_verified(
                self.abstract_state(),
                check_meta=self._check_restore_meta,
                on_event=self._restore_event,
            )
            self._restore_report = report
            # donation seam: restore_verified seals its output through
            # ensure_donatable at the source (checkpoint.py), so the state
            # is already runtime-owned when the donating train step sees it
            step = int(state.step)
            loader_state = remap_loader_state(
                meta,
                self.cfg.training.batch_size,
                self.cfg.training.train_context,
                max(self.cfg.training.gradient_accumulation_steps, 1),
            )
            if loader_state:
                self.train_loader.restore(loader_state)
            else:
                self.train_loader.skip(step)
            log.info(
                "resumed from step %d (verified in %.1f ms; %d quarantined, "
                "fell back %d step(s))",
                step, report.verify_ms, len(report.quarantined),
                report.fallback_steps,
            )
        else:
            if ck.resume:
                incomplete = self.ckpt.incomplete_steps()
                if incomplete:
                    # --resume with step dirs on disk but none COMPLETE:
                    # almost always a crash mid-first-save (fresh init is
                    # correct and save() will quarantine the leftovers), but
                    # if these were real checkpoints whose commit markers a
                    # backup tool dropped, the operator must know progress
                    # is being discarded — say so loudly, in metrics too
                    log.error(
                        "--resume: step dir(s) %s exist under %s but none "
                        "pass the completeness check (no commit markers) — "
                        "starting FRESH from step 0. If these are real "
                        "checkpoints, restore their _CHECKPOINT_METADATA/"
                        "state/_METADATA files and rerun",
                        incomplete, self.cfg.checkpoint.directory,
                    )
                    self.metrics.event(
                        "resume_found_only_incomplete_steps", 0,
                        steps=str(incomplete),
                    )
            state = init_train_state(
                self.model, self.tx, self.rng, self.mesh, self.sample_shape, self.plan
            )
            if ck.warm_init and ck.warm_init_msgpack:
                # donation seam sealed inside _warm_params_from_msgpack
                params = self._warm_params_from_msgpack(ck.warm_init_msgpack)
                state = TrainState(
                    step=state.step, params=params, opt_state=state.opt_state
                )
                log.info("warm-initialized params from %s", ck.warm_init_msgpack)
            elif ck.warm_init and ck.warm_init_dir:
                donor = ckpt_lib.CheckpointManager(ck.warm_init_dir, keep=1)
                abstract = self.abstract_state()
                # donation seam sealed inside restore_params (checkpoint.py)
                params = donor.restore_params(abstract.params)
                state = TrainState(
                    step=state.step, params=params, opt_state=state.opt_state
                )
                log.info("warm-initialized params from %s", ck.warm_init_dir)
        self.state = state
        return state

    def _restore_event(self, name: str, step: int, **fields) -> None:
        """Restore-path events -> metrics timeline AND flight recorder; a
        quarantined checkpoint additionally dumps the recorder window (the
        post-mortem for WHY a step dir failed its digest belongs next to
        the quarantined artifact — docs/RESILIENCE.md)."""
        self.metrics.event(name, step, **fields)
        self.flight.event(name, step=step, **fields)
        if name == "ckpt_quarantined":
            self.flight.dump("quarantine", extra={"step": step, **fields})

    def _warm_params_from_msgpack(self, path: str):
        """Load donor params, auto-extend depth / convert layer layout to this
        model, and place into the plan's shardings (the reference's scale-up
        warm start, reference ``main_zero.py:268-289`` + ``extend_params.py``)."""
        from zero_transformer_tpu.utils import surgery

        donor = ckpt_lib.import_params_msgpack(path)
        if surgery.num_layers(donor) != self.cfg.model.n_layers:
            donor = surgery.extend_depth(donor, self.cfg.model.n_layers)
        if self.cfg.model.n_experts > 0:
            # dense donor → MoE model: sparse upcycling. Runs BEFORE the
            # layout conversion (upcycle_moe needs the stacked layout, and
            # a scan_layers=False model would otherwise unstack first and
            # skip this branch entirely).
            stacked = surgery.stack_blocks(donor)
            if "mlp" in stacked.get("blocks", {}):
                donor = surgery.upcycle_moe(stacked, self.cfg.model.n_experts)
                log.info(
                    "upcycled dense donor to %d experts", self.cfg.model.n_experts
                )
        if surgery.is_stacked(donor) != self.cfg.model.scan_layers:
            donor = (
                surgery.stack_blocks(donor)
                if self.cfg.model.scan_layers
                else surgery.unstack_blocks(donor)
            )
        abstract = self.abstract_state().params
        donor_struct = jax.tree.structure(donor)
        if donor_struct != jax.tree.structure(abstract):
            raise ValueError(
                f"warm-init donor structure does not match model "
                f"{self.cfg.model.name!r} after surgery: {path}"
            )
        for (kp, d), (_, t) in zip(
            jax.tree_util.tree_flatten_with_path(donor)[0],
            jax.tree_util.tree_flatten_with_path(abstract)[0],
        ):
            if tuple(d.shape) != tuple(t.shape):
                name = "/".join(str(getattr(k, "key", k)) for k in kp)
                raise ValueError(
                    f"warm-init donor {path} has {name} shaped {tuple(d.shape)} "
                    f"but model {self.cfg.model.name!r} expects {tuple(t.shape)}"
                )
        # runtime-owned buffers: device_put of host msgpack leaves can be
        # zero-copy, and this tree flows into the donating train step
        return ensure_donatable(
            jax.tree.map(
                lambda leaf, tgt: jax.device_put(
                    jnp.asarray(leaf, tgt.dtype), tgt.sharding
                ),
                donor,
                abstract,
            )
        )

    # -- loops --------------------------------------------------------------

    def evaluate(self, state: Optional[TrainState] = None) -> Dict[str, float]:
        state = state if state is not None else self.state
        max_steps = self.cfg.training.maximum_evaluation_steps
        # Pin the validation window: without this every evaluate() consumes
        # the NEXT max_steps batches of a continuing stream, so each eval
        # scores different data and the loss curve is incomparable across
        # steps (round-2 verdict, "validation drift").
        if self._val_window is None:
            self._val_window = self.val_loader.source.state()
        else:
            self.val_loader.source.restore(self._val_window)
        total, n = 0.0, 0
        it = iter(self.val_loader)
        for _ in range(max_steps):
            local = next(it)[0]  # [local_batch, seq]
            batch = device_put_batch(local, self.plan.batch)
            total += float(self.eval_step(state.params, batch))
            n += 1
        loss = total / max(n, 1)
        return {"loss": loss, "perplexity": float(jnp.exp(jnp.minimum(loss, 20.0)))}

    def _install_preemption_handler(self):
        """SIGTERM → finish the current step, force-save, exit the train loop
        cleanly (preemption handling the reference lacks — its only recovery
        was rerunning with --resume, reference ``main_zero.py:48-52``).
        Returns (flag, restore_fn); no-op off the main thread."""
        flag = threading.Event()
        if threading.current_thread() is not threading.main_thread():
            return flag, lambda: None
        previous = signal.getsignal(signal.SIGTERM)

        def handler(signum, frame):
            log.warning("SIGTERM: will checkpoint and stop after this step")
            flag.set()

        signal.signal(signal.SIGTERM, handler)
        return flag, lambda: signal.signal(signal.SIGTERM, previous)

    # -- resilience plumbing ------------------------------------------------

    def _guarded_step(self):
        """(guard, wrapped_step) for the CURRENT ``self.train_step`` — cached
        so repeated ``train()`` calls reuse the compiled wrapper, but rebuilt
        if the step function was swapped (tests monkeypatch it)."""
        from zero_transformer_tpu.resilience.anomaly import AnomalyGuard

        cache = self._guard_cache
        if cache is None or cache[0] is not self.train_step:
            guard = AnomalyGuard(
                self.cfg.resilience, self.mesh, self.plan, self.batch_sharding
            )
            self._guard_cache = (
                self.train_step, guard, guard.wrap(self.train_step)
            )
        return self._guard_cache[1], self._guard_cache[2]

    def _hang_force_save(self):
        """Watchdog ``on_hang`` hook: best-effort checkpoint of the last
        COMPLETED step's state, from the watchdog thread, so the supervisor's
        restart resumes at the hang point instead of the last periodic save.
        (With a host-side hang the device state is intact; with a wedged
        device this save itself may hang — it runs after the stack dump, and
        the abort does not depend on it.)"""
        live = getattr(self, "_live", None)
        if live is None:
            return
        step, state = live
        try:
            self.ckpt.save(step, state, meta=self._save_meta(), force=True)
            self.ckpt.wait()
            log.warning("watchdog: force-saved checkpoint at step %d", step)
        except Exception:
            log.exception("watchdog: force-save failed (restart will use the "
                          "last periodic checkpoint)")

    def _data_fault_payload(self) -> Dict[str, float]:
        """Loader fault counters (skipped shards/members, retries) for the
        metrics stream — a pod run must SHOW the data it silently skipped."""
        counters = getattr(self.train_loader, "fault_counters", None)
        if counters is None:
            return {}
        return {f"data_{k}": float(v) for k, v in counters().items() if v}

    # graftlint: hot-path
    def train(self, max_steps: Optional[int] = None) -> TrainState:
        cfg = self.cfg.training
        res = self.cfg.resilience
        state = self.state if self.state is not None else self.init_state()
        # graftlint: allow[host-sync-in-hot-path] reason=once at run start before the loop, not per step — the resume step must be known to size the loop
        start = int(state.step)
        end = min(cfg.total_steps, start + max_steps) if max_steps else cfg.total_steps
        timer = monitoring.StepTimer()
        it = iter(self.train_loader)
        n_chips = max(jax.device_count(), 1)
        tokens_per_step = cfg.batch_size * cfg.train_context * max(
            cfg.gradient_accumulation_steps, 1
        )
        preempted, restore_handler = self._install_preemption_handler()
        profile_dir = cfg.profile_dir or f"{self.cfg.checkpoint.directory}/profile"
        # trace window: start_trace fires at loop top when the COMPLETED
        # step counter equals profile_trigger, so the traced steps are
        # [trigger+1, trigger+profile_steps]. The legacy default
        # (profile_start=0) keeps its historical trigger of start+1
        # (skip the compile step); --profile-window START:LEN pins the
        # absolute window [START, START+LEN) -> trigger START-1
        # (obs/profiling.py parses the flag)
        profile_trigger = (
            cfg.profile_start - 1 if cfg.profile_start else start + 1
        )
        profile_stop = (
            profile_trigger + cfg.profile_steps if cfg.profile_steps else None
        )
        if profile_stop and profile_trigger < start:
            log.warning(
                "profiler: window [%d, %d) is already behind resume step %d; "
                "no capture this run", cfg.profile_start,
                cfg.profile_start + cfg.profile_steps, start,
            )
        profiling = False
        tr = self.tracer

        # anomaly guard: in-graph detect-and-drop with a device-resident
        # carry; the host reads it only at log points (no per-step sync)
        guard = carry = None
        step_fn = self.train_step
        if res.anomaly_detection:
            guard, step_fn = self._guarded_step()
            carry = guard.init_carry()
        anom_seen = 0
        audit_seen = 0
        rollbacks = 0
        snapshot = None
        last_snap_step = start
        if guard is not None and res.anomaly_response == "rollback":
            from zero_transformer_tpu.resilience.anomaly import HostSnapshot

            snapshot = HostSnapshot()
            snapshot.capture(state)  # rollback target exists from step one
        watchdog = None
        if res.watchdog_timeout_s > 0:
            from zero_transformer_tpu.resilience.watchdog import Watchdog

            # armed AFTER the first step completes: step one legitimately
            # blocks for the whole XLA compile, which would need its own
            # (huge) deadline — the heartbeat contract is for steady state
            watchdog = Watchdog(
                res.watchdog_timeout_s, on_hang=self._hang_force_save
            )
        self.preempted = False
        self.last_step = start
        self.resilience_report = {"anomalies": 0, "rollbacks": 0,
                                  "watchdog_fired": False,
                                  "replica_audit_failures": 0}
        if self._restore_report is not None:
            # a verified resume's quarantine/fallback work is part of this
            # run's resilience story — surface it alongside the counters
            self.resilience_report["ckpt_quarantined"] = len(
                self._restore_report.quarantined
            )
            self.resilience_report["restore_fallback_steps"] = (
                self._restore_report.fallback_steps
            )

        step = start
        tick_step = start  # step at which the timing window last restarted
        try:
            while step < end:
                if profile_stop and not profiling and step == profile_trigger:
                    start_trace(profile_dir)
                    profiling = True
                    log.info("profiler: tracing %d steps to %s", cfg.profile_steps, profile_dir)
                # live spans (obs/spans.py): the ring on the tracer's clock
                # and, while a capture is open, "train/<name>" annotations
                # beside the device's programs on the profiler's
                with tr.span("data_fetch", "train", step=step + 1):
                    local = next(it)
                    batch = device_put_batch(local, self.batch_sharding)
                # dispatch, not compute: jax returns futures — the device
                # milliseconds show up in device_sync at the next log point
                # (and in a --profile-window capture)
                with tr.span("dispatch", "train", step=step + 1):
                    # observe only the axes that can vary mid-run (batch
                    # geometry, rng layout, guard carry) — state shapes are
                    # fixed at build time and threaded through step_fn, and
                    # describing the whole param tree would cost O(params)
                    # per step for no added detection
                    if guard is not None:
                        self.dispatch_site.observe(batch, self.rng, carry)
                        state, metrics, carry = step_fn(state, batch, self.rng, carry)
                    else:
                        self.dispatch_site.observe(batch, self.rng)
                        state, metrics = step_fn(state, batch, self.rng)
                step += 1
                self.last_step = step
                self._live = (step, state)
                if watchdog is not None:
                    if step == start + 1:
                        watchdog.start()
                    watchdog.beat()
                if profiling and step >= profile_stop:
                    # graftlint: allow[host-sync-in-hot-path] reason=profile-window close only — the trace must not stop before the steps it captured finish on device; never reached in steady state
                    jax.block_until_ready(metrics["loss"])
                    jax.profiler.stop_trace()
                    profiling = False

                paused = False
                if step % cfg.log_frequency == 0 or step == end:
                    # host-blocked time waiting on the device: the gap
                    # between dispatch rate and compute rate
                    with tr.span("device_sync", "train", step=step):
                        # graftlint: allow[host-sync-in-hot-path] reason=THE designed log-point sync (every log_frequency steps, not per step) — the device_sync span around it measures exactly this wait
                        loss = float(metrics["loss"])  # device sync point
                    if (
                        cfg.halt_on_nan
                        and not jnp.isfinite(loss)
                        and (guard is None or res.anomaly_response == "halt")
                    ):
                        # Without the guard this state is post-divergence (the
                        # NaN update already landed) — deliberately NOT saved,
                        # or it would bury the last GOOD checkpoint. With the
                        # guard the update was dropped in-graph, but 'halt'
                        # still means halt: surface it, don't train through.
                        good = self.ckpt.latest_step()
                        poisoned = (
                            "update was dropped in-graph (params still clean)"
                            if guard is not None
                            else "NOT checkpointed (state is already poisoned)"
                        )
                        self.flight.dump(
                            "anomaly_halt",
                            extra={"step": step, "loss": repr(loss),
                                   "cause": "halt_on_nan"},
                        )
                        raise RuntimeError(
                            f"non-finite loss {loss} at step {step}; {poisoned} "
                            f"— resume from step {good} and rerun with "
                            f"--debug-nans to find the source op"
                        )
                    dt = timer.tick()
                    payload = {
                        "loss": loss,
                        "perplexity": float(jnp.exp(jnp.minimum(jnp.float32(loss), 20.0))),
                        # graftlint: allow[host-sync-in-hot-path] reason=rides the log-point sync paid by loss above — the step's metrics materialized together; no extra device wait
                        "grad_norm": float(metrics["grad_norm"]),
                        "learning_rate": float(metrics.get("learning_rate", 0.0)),
                        "tokens_seen": float(step) * tokens_per_step,
                        "seq_len": cfg.train_context,
                    }
                    if dt and step > tick_step:
                        per_step = dt / (step - tick_step)
                        tok_s = tokens_per_step / per_step
                        payload["tokens_per_sec"] = tok_s
                        payload["step_time_s"] = per_step
                        util = monitoring.mfu(tok_s / n_chips, self.flops_per_token)
                        if util is not None:
                            payload["mfu"] = util
                        # step-time decomposition (PR 8): analytic bubble +
                        # bench-measured exposed comm, as metric keys — the
                        # same fractions the train_bubble_frac /
                        # train_exposed_comm_frac gauges export on /metrics.
                        # They are an operator's reading, not a measurement:
                        # the span timeline holds measured spans only
                        if self._bubble_frac > 0:
                            payload["bubble_frac"] = self._bubble_frac
                        if self._exposed_comm_frac is not None:
                            payload["exposed_comm_frac"] = (
                                self._exposed_comm_frac
                            )
                    hbm = monitoring.hbm_device_stats()
                    if hbm is not None:
                        # max across local devices (the OOM-relevant number;
                        # the old device-0-only read hid a skewed shard),
                        # mean alongside once there is more than one device
                        payload["hbm_gb"] = hbm["max_gb"]
                        if len(hbm["per_device_gb"]) > 1:
                            payload["hbm_gb_mean"] = hbm["mean_gb"]
                    payload.update(self._data_fault_payload())
                    if self.ckpt.last_digest_ms:
                        # digest time of the most recent manifest-carrying
                        # save tick (the <5% overhead budget, observable)
                        payload["ckpt_verify_ms"] = self.ckpt.last_digest_ms
                    if self._restore_report is not None and (
                        self._restore_report.quarantined
                    ):
                        payload["ckpt_quarantined"] = len(
                            self._restore_report.quarantined
                        )
                        payload["restore_fallback_steps"] = (
                            self._restore_report.fallback_steps
                        )
                    if guard is not None:
                        stats = guard.read(carry)  # host sync — log points only
                        new_anoms = stats.count - anom_seen
                        if new_anoms > 0:
                            # run-level total survives carry resets (rollback)
                            self.resilience_report["anomalies"] += new_anoms
                        if self.resilience_report["anomalies"]:
                            payload["anomalies_total"] = (
                                self.resilience_report["anomalies"]
                            )
                            payload["anomaly_streak"] = stats.streak
                        new_audit = stats.audit_failures - audit_seen
                        if new_audit > 0:
                            self.resilience_report["replica_audit_failures"] += (
                                new_audit
                            )
                        if self.resilience_report["replica_audit_failures"]:
                            payload["replica_audit_failures"] = (
                                self.resilience_report["replica_audit_failures"]
                            )
                    self.metrics.log(payload, step, prefix="train")
                    # flight ring + incremental span log, at log points only
                    # (the hot loop appends fixed records; IO lands here)
                    self.flight.tick({
                        "step": step, "loss": loss,
                        "grad_norm": payload["grad_norm"],
                        "anomalies": self.resilience_report["anomalies"],
                        "rollbacks": rollbacks,
                        "audit_failures": self.resilience_report[
                            "replica_audit_failures"
                        ],
                    })
                    if self.flight.directory:
                        tr.write_jsonl(
                            f"{self.flight.directory}/spans.jsonl"
                        )
                    tick_step = step
                    if guard is not None:
                        # guard-carry read + divergence/anomaly escalation
                        # + snapshot refresh, as one phase
                        with tr.span("replica_audit", "train", step=step) as audit_span:
                            state, carry, rolled = self._handle_replica_divergence(
                                new_audit, state, carry, guard, snapshot,
                                rollbacks, step,
                            )
                            if rolled:
                                # audit rollback reset the carry; both counters
                                # restart from zero at the next read
                                anom_seen = 0
                                audit_seen = 0
                            else:
                                audit_seen = stats.audit_failures
                                state, carry, rolled = self._handle_anomalies(
                                    stats, new_anoms, state, carry, guard, snapshot,
                                    rollbacks, step,
                                )
                                anom_seen = 0 if rolled else stats.count
                                if rolled:
                                    audit_seen = 0
                            if rolled:
                                rollbacks += 1
                                self.resilience_report["rollbacks"] = rollbacks
                                paused = True  # exclude rollback time from timing
                            # mirror a known-good state to host RAM on schedule.
                            # With the replica audit active, "known-good" also
                            # requires a CLEAN audit to have run since the last
                            # capture: otherwise a desync that happened between
                            # audits could be captured and later re-replicated
                            # by the "heal" rollback, baking the corruption into
                            # every replica. (Residual window: corruption in the
                            # <= audit_frequency steps since the last clean
                            # audit can still slip in — the audit bounds it.)
                            audit_vouched = (
                                getattr(guard, "_audit", None) is None
                                or (
                                    new_audit == 0
                                    and step // res.audit_frequency
                                    > last_snap_step // res.audit_frequency
                                )
                            )
                            if (
                                snapshot is not None
                                and stats.streak == 0
                                and not rolled
                                and audit_vouched
                                and step - last_snap_step >= res.snapshot_frequency
                            ):
                                snapshot.capture(state)
                                last_snap_step = step
                            audit_span.note(rolled=rolled)

                if cfg.evaluation_frequency and step % cfg.evaluation_frequency == 0:
                    with tr.span("evaluate", "train", step=step):
                        self.metrics.log(
                            self.evaluate(state), step, prefix="validation"
                        )
                    paused = True

                with tr.span("checkpoint_save", "train", step=step) as save_span:
                    saved = self.ckpt.save(step, state, meta=self._save_meta())
                    if not saved:
                        save_span.discard()  # not a save tick: no span
                if saved:
                    paused = True
                if paused:
                    # exclude eval/checkpoint wall time from the throughput window
                    timer.tick()
                    tick_step = step

                if self._chaos is not None:
                    self._chaos.on_step(step)
                    # replica_perturb chaos: desync one DP replica's copy of
                    # the (logically replicated) state — the SDC the audit
                    # exists to catch. No-op without such a fault.
                    state = self._chaos.perturb_state(step, state)
                if preempted.is_set():
                    log.warning("preemption: saving at step %d and stopping", step)
                    self.metrics.event("preemption", step)
                    self.preempted = True
                    break
        except KeyboardInterrupt:
            if watchdog is not None and watchdog.fired:
                from zero_transformer_tpu.resilience import HangError

                self.resilience_report["watchdog_fired"] = True
                self.metrics.event(
                    "watchdog_abort", step, timeout_s=res.watchdog_timeout_s
                )
                self.flight.dump(
                    "watchdog_abort",
                    extra={"step": step,
                           "timeout_s": res.watchdog_timeout_s},
                )
                raise HangError(
                    f"train loop produced no step for more than "
                    f"{res.watchdog_timeout_s}s (hung around step {step}); "
                    f"stacks dumped, checkpoint force-saved — restartable"
                ) from None
            raise
        finally:
            if profiling:
                jax.profiler.stop_trace()
            if watchdog is not None:
                watchdog.stop()
            restore_handler()
        # drain any in-flight async save BEFORE the latest_step comparison:
        # latest_step() now checks ON-DISK commit markers, and a step whose
        # background commit hasn't landed yet would read as absent — the
        # redundant force-save would then raise StepAlreadyExistsError
        self.ckpt.wait()
        if self.ckpt.latest_step() != step:
            self.ckpt.save(step, state, meta=self._save_meta(), force=True)
        self.ckpt.wait()
        self.state = state
        return state

    def _rollback_to_snapshot(self, state, guard, snapshot):
        """Restore params/opt-state from the host-RAM snapshot, KEEPING the
        current step counter (the loader and LR schedule move forward — the
        offending window is never replayed), with a fresh guard carry. The
        snapshot's ``restore()`` routes through ``ensure_donatable`` (the
        re-placed buffers enter the donating train step) and its
        ``device_put`` re-replicates ONE host copy onto every device —
        which is also what makes rollback heal a replica desync."""
        restored = snapshot.restore()
        state = TrainState(
            step=state.step,
            params=restored.params,
            opt_state=restored.opt_state,
        )
        return state, guard.init_carry()

    def _handle_replica_divergence(
        self, new, state, carry, guard, snapshot, rollbacks, step
    ):
        """Escalation when the cross-replica audit tripped since the last
        log point. A desynced replica cannot be skipped past (every
        subsequent step forks further) — the options are HEAL by re-placing
        identical copies from the host snapshot (``anomaly_response:
        rollback``; a ``device_put`` from one host buffer re-replicates
        bit-identical state on every device) or HALT so the operator swaps
        the suspect host. Returns (state, carry, did_rollback)."""
        if new <= 0:
            return state, carry, False
        res = self.cfg.resilience
        good = self.ckpt.latest_step()
        log.error(
            "replica audit: %d failed agreement check(s) by step %d — one "
            "DP replica's state no longer matches its peers (silent data "
            "corruption)", new, step,
        )
        self.metrics.event(
            "replica_divergence", step, new_failures=new,
            total=self.resilience_report["replica_audit_failures"],
        )
        from zero_transformer_tpu.resilience import AnomalyHalt

        if (
            res.anomaly_response == "rollback"
            and snapshot is not None
            and snapshot.captured
            and rollbacks < res.max_rollbacks
        ):
            state, carry = self._rollback_to_snapshot(state, guard, snapshot)
            log.warning(
                "replica divergence HEALED by rollback %d/%d: host snapshot "
                "of step %d re-replicated identical copies at step %d",
                rollbacks + 1, res.max_rollbacks, snapshot.step, step,
            )
            self.metrics.event(
                "replica_heal_rollback", step,
                to_step=snapshot.step, rollback=rollbacks + 1,
            )
            return state, carry, True
        self.flight.dump(
            "anomaly_halt",
            extra={"step": step, "cause": "replica_divergence",
                   "new_failures": new},
        )
        raise AnomalyHalt(
            f"cross-replica divergence at step {step} (audited every "
            f"{res.audit_frequency} steps): a DP replica's replicated state "
            f"differs bit-for-bit from its peers — silent data corruption "
            f"on one host/device. Resume from step {good} (restore "
            f"re-replicates identical copies); if it recurs, rotate out the "
            f"suspect host"
        )

    def _handle_anomalies(
        self, stats, new, state, carry, guard, snapshot, rollbacks, step
    ):
        """Host-side escalation from the guard carry, at a log point.

        The in-graph guard already DROPPED every flagged update (skip_batch
        is the floor, not a choice); what remains is whether to keep going,
        roll back, or stop. Returns (state, carry, did_rollback)."""
        res = self.cfg.resilience
        if new <= 0:
            return state, carry, False
        good = self.ckpt.latest_step()
        log.warning(
            "anomaly guard: %d flagged step(s) since last check "
            "(streak %d, total %d) — updates dropped in-graph",
            new, stats.streak, stats.count,
        )
        from zero_transformer_tpu.resilience import AnomalyHalt

        if res.anomaly_response == "halt":
            self.flight.dump(
                "anomaly_halt",
                extra={"step": step, "cause": "policy_halt", "new": new,
                       "streak": stats.streak},
            )
            raise AnomalyHalt(
                f"anomaly policy 'halt': {new} flagged step(s) by step {step} "
                f"(non-finite loss/grad or spike; streak {stats.streak}). "
                f"Updates were dropped in-graph; resume from step {good} "
                f"after inspecting the data window / lowering the LR"
            )
        if (
            res.anomaly_response == "rollback"
            and stats.streak >= res.rollback_after
            and snapshot is not None
            and snapshot.captured
        ):
            if rollbacks >= res.max_rollbacks:
                self.flight.dump(
                    "anomaly_halt",
                    extra={"step": step, "cause": "rollback_budget",
                           "streak": stats.streak},
                )
                raise AnomalyHalt(
                    f"rollback budget exhausted ({res.max_rollbacks}) with the "
                    f"anomaly streak still at {stats.streak} at step {step} — "
                    f"this divergence is persistent; resume from step {good} "
                    f"with a changed config"
                )
            state, carry = self._rollback_to_snapshot(state, guard, snapshot)
            log.warning(
                "anomaly rollback %d/%d: restored host snapshot of step %d "
                "at step %d (loader continues forward)",
                rollbacks + 1, res.max_rollbacks, snapshot.step, step,
            )
            self.metrics.event(
                "anomaly_rollback", step,
                to_step=snapshot.step, streak=stats.streak,
                rollback=rollbacks + 1,
            )
            return state, carry, True
        if stats.streak >= res.max_consecutive_anomalies:
            self.flight.dump(
                "anomaly_halt",
                extra={"step": step, "cause": "consecutive_anomalies",
                       "streak": stats.streak},
            )
            raise AnomalyHalt(
                f"{stats.streak} consecutive anomalous steps at step {step}: "
                f"every update is being dropped — no training progress is "
                f"possible; resume from step {good} with a changed config"
            )
        return state, carry, False

    def close(self) -> None:
        if self.flight.directory:
            # Perfetto trace + remaining spans beside metrics.jsonl — the
            # per-phase step timeline survives the process
            try:
                self.tracer.write_chrome_trace(
                    f"{self.flight.directory}/trace_train.json"
                )
                self.tracer.write_jsonl(f"{self.flight.directory}/spans.jsonl")
            except Exception:
                log.exception("obs: trace export failed (run results intact)")
        self.ckpt.close()
        self.metrics.close()

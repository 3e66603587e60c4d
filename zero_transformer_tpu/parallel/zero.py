"""ZeRO data-parallel training: one fused jit step on jax.Array shardings.

Replaces the reference's three-computation hot loop — xmap'd grad step, two
identity-pjit reshards, pjit'd optimizer update (reference ``main_zero.py:495-500``,
``src/partitioning/xmap_train_functions.py``) — with a SINGLE compiled step:

- batch sharded over the ``data`` axis → GSPMD lowers the gradient reduction
  to an ICI all-reduce (stage ≤1) or, with the in-scan sharding constraint,
  a reduce-scatter (stage 2), exactly the collective the reference got from
  ``lax.pmean`` inside xmap (``xmap_train_functions.py:83-84``);
- optimizer state lives permanently in its ZeRO NamedSharding (stage ≥1) —
  no replicated→sharded→replicated round trip per step;
- gradient accumulation is a ``lax.scan`` over a leading accum axis
  (reference used ``lax.fori_loop`` + dynamic_index, ``xmap_train_functions.py:62-81``),
  with the accumulator itself ZeRO-sharded at stage ≥2;
- buffers are donated: params/opt-state update in place in HBM.

Stages (cf. SURVEY §2 parallelism checklist):
  0: plain DP (everything replicated)
  1: optimizer state sharded          [reference's ceiling]
  2: + gradients reduce-scattered     [build target]
  3: + parameters stored sharded (FSDP); jit all-gathers weights per step
"""
from __future__ import annotations

import functools
import math
import os
from typing import Any, Callable, Optional

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp
import optax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from zero_transformer_tpu.parallel import sharding as shd
from zero_transformer_tpu.parallel.mesh import (
    SEQUENCE_AXIS,
    TENSOR_AXIS,
    zero_axes,
)


@flax.struct.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any


@flax.struct.dataclass
class ShardingPlan:
    """All NamedShardings for one training setup."""

    state: Any = flax.struct.field(pytree_node=False)
    batch: Any = flax.struct.field(pytree_node=False)
    zero: Any = flax.struct.field(pytree_node=False)  # fully-sharded per-param specs
    logical: Any = flax.struct.field(pytree_node=False)  # PartitionSpec of logical names


def make_plan(
    model: nn.Module,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    sample_input_shape: tuple,
    zero_stage: int = 1,
    pp_schedule: str = "gpipe",
) -> ShardingPlan:
    """Derive every sharding from abstract shapes — no real allocation.

    ``pp_schedule`` selects the layer-stack storage rule: gpipe/1f1b shard
    the stacked layer dim contiguously over ``pipe``; interleaved stores it
    pipe-replicated (see ``sharding.plan_rules``). Meshes without a pipe
    axis are unaffected by either."""

    def _init(rng):
        return model.init(rng, jnp.zeros(sample_input_shape, jnp.int32))

    rules = shd.plan_rules(pp_schedule)
    boxed = jax.eval_shape(_init, jax.random.PRNGKey(0))["params"]
    logical = shd.logical_specs(boxed)
    abstract_params = shd.unbox(boxed)
    param_specs = shd.param_sharding(
        mesh, abstract_params, logical, zero_stage, rules=rules
    )
    zero_specs = shd.zero_sharding(mesh, abstract_params, logical, rules=rules)
    abstract_opt = jax.eval_shape(tx.init, abstract_params)
    opt_specs = shd.opt_state_sharding(
        mesh, abstract_opt, abstract_params, zero_specs if zero_stage >= 1 else param_specs
    )
    state_shardings = TrainState(
        step=NamedSharding(mesh, P()), params=param_specs, opt_state=opt_specs
    )
    plan = ShardingPlan(
        state=state_shardings,
        batch=shd.batch_sharding(mesh),
        zero=zero_specs,
        logical=logical,
    )
    # machine-check the plan against the mesh BEFORE anything compiles
    # (ROADMAP item 1: specs are checked, never hand-trusted) — a bad rule
    # table or hand-edited spec fails here with a precise message instead
    # of deep inside pjit at first dispatch. Divisibility is strict ONLY on
    # the ZeRO axes: _add_zero_axis skips indivisible dims by construction,
    # so raggedness there means a hand-seeded/corrupted plan. Every other
    # axis may shard unevenly from honest inputs (an imported 50257 vocab
    # over tensor=2, a 3-layer stack over pipe=2) — GSPMD pads those, and
    # components that cannot pad own their refusal (pipeline's "divisible"
    # error in make_train_step).
    from zero_transformer_tpu.analysis import spec_check

    abstract_state = TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        params=abstract_params,
        opt_state=abstract_opt,
    )
    strict = set(zero_axes(mesh))
    spec_check.check_plan(
        plan,
        mesh,
        abstract_state=abstract_state,
        allow_uneven=tuple(a for a in mesh.axis_names if a not in strict),
    )
    return plan


def init_train_state(
    model: nn.Module,
    tx: optax.GradientTransformation,
    rng: jax.Array,
    mesh: Mesh,
    sample_input_shape: tuple,
    plan: ShardingPlan,
) -> TrainState:
    """Initialize params/opt-state directly into their target shardings (each
    device materializes only its shard — a 1.3B f32 init never exists fully
    replicated on any host)."""

    def _init(rng):
        variables = model.init(rng, jnp.zeros(sample_input_shape, jnp.int32))
        params = shd.unbox(variables["params"])
        return TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=tx.init(params))

    return jax.jit(_init, out_shardings=plan.state)(rng)


def _accum_dtype(name: str):
    dt = jnp.dtype(name)
    if dt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        raise ValueError(
            f"grad_accum_dtype must be float32 or bfloat16, got {name!r}"
        )
    return dt


def _accum_add(a, g):
    """Accumulate micro-step gradient ``g`` into buffer ``a``: add in the
    promoted dtype, round once into the accumulator dtype — a bfloat16
    accumulator rounds once per micro-step instead of once per operand, and
    an f32 accumulator is never downcast even when grads are low-precision
    (``model.param_dtype=bfloat16`` makes grads bf16; ``jnp.add`` promoted
    them before this helper existed and so does this)."""
    ct = jnp.promote_types(a.dtype, g.dtype)
    return (a.astype(ct) + g.astype(ct)).astype(a.dtype)


def _with_ambient_mesh(jitted, mesh: Mesh):
    """Run calls AND lowering of a jitted step under ``jax.set_mesh(mesh)``.

    The model's ``constrain_activation`` calls resolve logical PartitionSpecs
    against the ambient abstract mesh at TRACE time — which happens inside
    the first call (or an explicit ``.lower``), not at ``jax.jit`` wrap time.
    ``.lower`` is preserved because the HLO regression tests use it.

    ``jax.set_mesh`` refuses to be entered under a trace, and a step IS
    called under one when another mesh-wrapped jit composes it (the anomaly
    guard jits ``guarded``, which calls the train step). The outer wrapper
    has already entered the mesh by then, so a call that finds its mesh
    ambient runs the jitted step as is."""

    def under_mesh(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            if jax.sharding.get_abstract_mesh() == mesh.abstract_mesh:
                return fn(*args, **kwargs)
            with jax.set_mesh(mesh):
                return fn(*args, **kwargs)

        return run

    call = under_mesh(jitted)
    call.lower = under_mesh(jitted.lower)
    return call


def make_train_step(
    model: nn.Module,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    plan: ShardingPlan,
    zero_stage: int = 1,
    schedule: Optional[Callable] = None,
    tx_factory: Optional[Callable] = None,
    pp_schedule: str = "gpipe",
    grad_accum_dtype: str = "float32",
    pp_interleave: int = 1,
    overlap_comm: bool = False,
) -> Callable:
    """Build the fused jitted train step.

    Step signature: ``(state, batch, rng) -> (state, metrics)`` where
    ``batch`` is int32 [accum_steps, global_batch, seq_len] (accum may be 1).

    At stage >= 2 (any tensor-parallel degree, sequence = 1) the step is built
    around an EXPLICIT shard_map collective core — ``psum_scatter`` gradient
    reduce-scatter, sharded optimizer math, ``all_gather`` of updated params —
    so ZeRO-2/3 semantics are guaranteed by construction (and testable in the
    compiled HLO) rather than hoped for from GSPMD's all-reduce→reduce-scatter
    rewrite. The core is PARTIAL-MANUAL: only the ZeRO axes (data/fsdp) are
    manual shard_map axes; the tensor axis stays auto, so GSPMD still
    partitions the model math (Megatron TP) inside the body while the ZeRO
    collective schedule is hand-placed. (Verified need: at tensor=2 the
    constraint-hint path compiles to 0 reduce-scatters and 76 all-reduces —
    GSPMD legally satisfies the hints with all-reduce + slice.)
    ``tx_factory(global_norm_fn)`` rebuilds the optimizer with a shard-aware
    grad-clip norm for that core (see ``make_optimizer``); without it the
    core pre-clips using the provided ``tx`` (see
    ``_make_explicit_zero_step``). The sequence (context-parallel) axis
    composes: the ring/Ulysses engines nest their shard_maps inside the
    partial-manual core (``ops.ring_attention._engine_ctx`` — before round
    5 these meshes fell back to the GSPMD hint path, which compiled ZeRO-2
    to stage-1 traffic: zero reduce-scatters, weight-sized all-reduces).
    An active ``pipe`` axis routes to the GPipe wavefront step
    (``parallel.pipeline``).
    """
    from zero_transformer_tpu.parallel.mesh import PIPE_AXIS

    acc_dt = _accum_dtype(grad_accum_dtype)
    if mesh.shape[PIPE_AXIS] > 1:
        from zero_transformer_tpu.parallel.pipeline import make_pp_train_step

        if overlap_comm:
            raise ValueError(
                "overlap_comm does not apply to pipe meshes: the pipeline "
                "engine owns its own collective schedule (pp_schedule)"
            )
        # 1F1B accepts bfloat16 (its accumulator is a hand-placed scan
        # carry); GPipe rejects it there (accumulation lives in scan-VJP)
        return make_pp_train_step(
            model, tx, mesh, plan, zero_stage, schedule, tx_factory,
            pp_schedule=pp_schedule, grad_accum_dtype=grad_accum_dtype,
            pp_interleave=pp_interleave,
        )
    # sequence x tensor x explicit-core: XLA's SPMD partitioner CHECK-fails
    # (spmd_partitioner_util.cc:495 — the same upstream crash class as
    # pipe x tensor) partitioning the auto tensor axis around the nested CP
    # engine; those meshes keep the GSPMD constraint-hint path below.
    # ZTPU_SEQ_TENSOR_EXPLICIT_PROBE=1 re-probes on future jax upgrades
    # (subprocess only: the failure is a CHECK abort, not an exception).
    seq_tensor = (
        mesh.shape[SEQUENCE_AXIS] > 1 and mesh.shape[TENSOR_AXIS] > 1
        and os.environ.get("ZTPU_SEQ_TENSOR_EXPLICIT_PROBE") != "1"
    )
    if overlap_comm:
        from zero_transformer_tpu.parallel.overlap import make_overlap_zero_step

        if zero_stage < 1:
            raise ValueError(
                "overlap_comm requires zero_stage >= 1 (stage 0 has no ZeRO "
                "collective schedule to overlap)"
            )
        if seq_tensor:
            raise NotImplementedError(
                "overlap_comm on sequence x tensor meshes: those meshes "
                "cannot run an explicit shard_map core on this XLA (see the "
                "seq_tensor probe above) — drop overlap_comm or one axis"
            )
        return make_overlap_zero_step(
            model, tx, mesh, plan, zero_stage, schedule, tx_factory,
            grad_accum_dtype=grad_accum_dtype,
        )
    if zero_stage >= 2 and not seq_tensor:
        return _make_explicit_zero_step(
            model, tx, mesh, plan, zero_stage, schedule, tx_factory,
            grad_accum_dtype=grad_accum_dtype,
        )

    def loss_fn(params, micro, rng):
        _, loss = model.apply(
            {"params": params}, micro, labels=micro, train=True, rngs={"dropout": rng}
        )
        return loss

    grad_fn = jax.value_and_grad(loss_fn)

    def constrain_zero(tree):
        return jax.lax.with_sharding_constraint(tree, plan.zero)

    def train_step(state: TrainState, batch: jax.Array, rng: jax.Array):
        accum = batch.shape[0]
        step_rng = jax.random.fold_in(rng, state.step)

        def micro_grads(i):
            mrng = jax.random.fold_in(step_rng, i)
            loss, grads = grad_fn(state.params, batch[i], mrng)
            if zero_stage >= 2:
                # reduce-scatter instead of all-reduce; sharded accumulator
                grads = constrain_zero(grads)
            return loss, grads

        if accum == 1:
            loss, grads = micro_grads(0)
        else:

            def body(carry, i):
                loss_sum, grads_sum = carry
                loss, grads = micro_grads(i)
                grads_sum = jax.tree.map(_accum_add, grads_sum, grads)
                return (loss_sum + loss, grads_sum), None

            zero_grads = jax.tree.map(
                lambda p: jnp.zeros(p.shape, acc_dt), state.params
            )
            if zero_stage >= 2:
                zero_grads = constrain_zero(zero_grads)
            (loss, grads), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zero_grads), jnp.arange(accum)
            )
            loss = loss / accum
            grads = jax.tree.map(
                lambda g: g.astype(jnp.float32) / accum, grads
            )

        grad_norm = optax.global_norm(grads)
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        if zero_stage >= 1:
            # ZeRO: optimizer math runs sharded; the all-gather happens once,
            # on the updates, at apply time (stage<3) or never (stage 3).
            updates = constrain_zero(updates)
        new_params = optax.apply_updates(state.params, updates)
        new_params = jax.lax.with_sharding_constraint(new_params, plan.state.params)
        metrics = {
            "loss": loss,
            "grad_norm": grad_norm,
            "tokens": jnp.asarray(batch.size, jnp.float32),
        }
        if schedule is not None:
            metrics["learning_rate"] = schedule(state.step)
        new_state = TrainState(step=state.step + 1, params=new_params, opt_state=new_opt)
        return new_state, metrics

    batch_shard = NamedSharding(mesh, P(None, *plan.batch.spec))
    return _with_ambient_mesh(
        jax.jit(
            train_step,
            in_shardings=(plan.state, batch_shard, NamedSharding(mesh, P())),
            out_shardings=(plan.state, NamedSharding(mesh, P())),
            donate_argnums=(0,),
        ),
        mesh,
    )


def _zero_scatter_dim(spec: P, zaxes: tuple) -> int:
    """Index of the dim a ZeRO spec shards over the zero axes (-1: none).
    Mirrors ``sharding._add_zero_axis``'s entry encoding (axis name, or the
    axis tuple when the shard spans data+fsdp)."""
    entry = zaxes if len(zaxes) > 1 else zaxes[0]
    for i, e in enumerate(spec):
        if e == entry:
            return i
    return -1


def apply_tx_factory(tx_factory, norm_fn, zc):
    """Call ``tx_factory(norm_fn[, zc])``. The optional second argument hands
    the manual core's ``ZeroCollectives`` to optimizers that need shard-aware
    transforms beyond the clip norm (sharded adafactor); single-argument
    factories (the original contract) keep working unchanged."""
    import inspect

    try:
        n_pos = sum(
            1
            for p in inspect.signature(tx_factory).parameters.values()
            if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
        )
    except (TypeError, ValueError):
        n_pos = 1
    return tx_factory(norm_fn, zc) if n_pos >= 2 else tx_factory(norm_fn)


class ZeroCollectives:
    """The hand-placed ZeRO collective schedule, reusable by any partial-
    manual core whose manual axes include the ZeRO (data/fsdp) axes — the
    explicit stage-2/3 step below AND the pipeline engine's stage-2 path
    (``parallel.pipeline``). All methods are trace-time helpers meant to be
    called INSIDE a shard_map body."""

    def __init__(self, mesh: Mesh, plan: ShardingPlan):
        self.zaxes = zero_axes(mesh)
        self.axis = self.zaxes if len(self.zaxes) > 1 else self.zaxes[0]
        self.zsize = math.prod(mesh.shape[a] for a in self.zaxes)
        self.mesh = mesh
        # -1 sentinel (None would vanish as an empty pytree)
        self.sdims = jax.tree.map(
            lambda ns: _zero_scatter_dim(ns.spec, self.zaxes), plan.zero
        )

    def dev_index(self):
        idx = jax.lax.axis_index(self.zaxes[0])
        for a in self.zaxes[1:]:
            idx = idx * self.mesh.shape[a] + jax.lax.axis_index(a)
        return idx

    def shard_norm(self, tree):
        """True global grad norm from shard-local pieces."""
        sq_scattered = jnp.zeros((), jnp.float32)
        sq_replicated = jnp.zeros((), jnp.float32)
        for g, d in zip(jax.tree.leaves(tree), jax.tree.leaves(self.sdims)):
            s = jnp.sum(jnp.square(g.astype(jnp.float32)))
            if d < 0:
                sq_replicated = sq_replicated + s
            else:
                sq_scattered = sq_scattered + s
        return jnp.sqrt(jax.lax.psum(sq_scattered, self.axis) + sq_replicated)

    def reduce_grads(self, grads):
        """Full local grads → ZeRO-sharded mean grads (literal
        reduce-scatter on the ICI ring; psum for indivisible leaves)."""

        def one(g, d):
            if d < 0:
                return jax.lax.psum(g, self.axis)
            return jax.lax.psum_scatter(
                g, self.axis, scatter_dimension=d, tiled=True
            )

        return jax.tree.map(
            lambda g: g / self.zsize, jax.tree.map(one, grads, self.sdims)
        )

    def gather_full(self, shards):
        def one(p, d):
            if d < 0:
                return p
            return jax.lax.all_gather(p, self.axis, axis=d, tiled=True)

        return jax.tree.map(one, shards, self.sdims)

    def slice_local(self, full):
        def one(p, d):
            if d < 0:
                return p
            size = p.shape[d] // self.zsize
            return jax.lax.dynamic_slice_in_dim(
                p, self.dev_index() * size, size, axis=d
            )

        return jax.tree.map(one, full, self.sdims)


def _make_explicit_zero_step(
    model: nn.Module,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    plan: ShardingPlan,
    zero_stage: int,
    schedule: Optional[Callable],
    tx_factory: Optional[Callable],
    grad_accum_dtype: str = "float32",
) -> Callable:
    """ZeRO-2/3 train step with hand-placed collectives under shard_map.

    Per microbatch: local grads → ``psum_scatter`` (a literal reduce-scatter
    on the ICI ring) → sharded accumulator. The optimizer update then runs on
    1/N-size shards, and the updated params are ``all_gather``ed back whole
    (stage 2) or stay sharded (stage 3, where the forward all-gathers them
    per step instead — FSDP). This is the collective schedule ZeRO-2 *means*;
    the GSPMD path merely hints it with sharding constraints, which XLA may
    legally satisfy with all-reduce + slice (VERDICT r1 weak #4). The
    reference never got past stage 1 (its grads leave the step fully
    replicated, ``xmap_train_functions.py:83-84``).

    Grad-clip: the true global norm needs a psum across the ZeRO axis
    (optax's clip would see one device's shards). ``tx_factory`` rebuilds the
    optimizer with that norm; without it the provided ``tx`` is used as-is
    and its clip under-measures large-grad steps (documented fallback for
    direct ``make_train_step`` callers that don't clip or don't care).
    """
    zc = ZeroCollectives(mesh, plan)
    zaxes, axis = zc.zaxes, zc.axis
    acc_dt = _accum_dtype(grad_accum_dtype)

    tx_inner = (
        apply_tx_factory(tx_factory, zc.shard_norm, zc)
        if tx_factory is not None
        else tx
    )

    def loss_fn(params, micro, rng):
        _, loss = model.apply(
            {"params": params}, micro, labels=micro, train=True, rngs={"dropout": rng}
        )
        return loss

    grad_fn = jax.value_and_grad(loss_fn)

    def core(state: TrainState, batch: jax.Array, rng: jax.Array):
        accum = batch.shape[0]
        step_rng = jax.random.fold_in(rng, state.step)
        # distinct dropout masks per DP shard (pmap-era fold-in semantics)
        step_rng = jax.random.fold_in(step_rng, zc.dev_index())

        if zero_stage >= 3:
            param_shards = state.params
            full_params = zc.gather_full(param_shards)  # FSDP per-step all-gather
        else:
            full_params = state.params
            param_shards = zc.slice_local(full_params)

        def micro(i):
            mrng = jax.random.fold_in(step_rng, i)
            loss, grads = grad_fn(full_params, batch[i], mrng)
            return jax.lax.pmean(loss, axis), zc.reduce_grads(grads)

        if accum == 1:
            loss, grads = micro(0)
        else:

            def body(carry, i):
                loss_sum, grads_sum = carry
                loss, grads = micro(i)
                return (loss_sum + loss, jax.tree.map(_accum_add, grads_sum, grads)), None

            zero_grads = jax.tree.map(
                lambda p: jnp.zeros(p.shape, acc_dt), param_shards
            )
            (loss, grads), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zero_grads), jnp.arange(accum)
            )
            loss = loss / accum
            grads = jax.tree.map(
                lambda g: g.astype(jnp.float32) / accum, grads
            )

        grad_norm = zc.shard_norm(grads)
        updates, new_opt = tx_inner.update(grads, state.opt_state, param_shards)
        new_shards = optax.apply_updates(param_shards, updates)
        new_params = new_shards if zero_stage >= 3 else zc.gather_full(new_shards)
        metrics = {
            "loss": loss,
            "grad_norm": grad_norm,
            "tokens": jnp.asarray(batch.size * zc.zsize, jnp.float32),
        }
        if schedule is not None:
            metrics["learning_rate"] = schedule(state.step)
        new_state = TrainState(
            step=state.step + 1, params=new_params, opt_state=new_opt
        )
        return new_state, metrics

    zset = set(zaxes)

    def manual_part(spec: P) -> P:
        # tensor/expert axes stay auto (GSPMD) under the partial-manual
        # shard_map; specs handed to it may only mention the ZeRO axes
        return shd.restrict_spec(spec, zset)

    state_specs = TrainState(
        step=P(),
        params=jax.tree.map(lambda ns: manual_part(ns.spec), plan.state.params),
        opt_state=jax.tree.map(
            lambda ns: manual_part(ns.spec), plan.state.opt_state
        ),
    )
    batch_spec = manual_part(P(None, *plan.batch.spec))
    metric_specs = {"loss": P(), "grad_norm": P(), "tokens": P()}
    if schedule is not None:
        metric_specs["learning_rate"] = P()

    mapped = shard_map(
        core,
        mesh=mesh,
        in_specs=(state_specs, batch_spec, P()),
        out_specs=(state_specs, metric_specs),
        axis_names=frozenset(zaxes),
        check_vma=False,
    )
    return _with_ambient_mesh(
        jax.jit(
            mapped,
            in_shardings=(plan.state, NamedSharding(mesh, batch_spec), NamedSharding(mesh, P())),
            out_shardings=(plan.state, NamedSharding(mesh, P())),
            donate_argnums=(0,),
        ),
        mesh,
    )


def make_replica_audit(mesh: Mesh, plan: ShardingPlan) -> Optional[Callable]:
    """Trace-time cross-replica agreement check over the ZeRO (data/fsdp)
    axes: ``audit(state) -> bool`` True when any DP replica's copy of the
    REPLICATED state leaves disagrees bit-for-bit with the others.

    Silent data corruption that desyncs one replica is invisible to GSPMD —
    XLA *assumes* replicated operands are identical, so a flipped bit on one
    device quietly forks that replica's trajectory until the loss curves
    split (arXiv:2004.13336's cross-replica sharding makes the redundant
    copies explicit; this is the cheap agreement check that redundancy
    affords). Mechanics: a ``shard_map`` over the zero axes lets each device
    checksum ITS OWN physical copy (``detect.leaf_checksum`` — exact uint32
    bit-sums, so healthy replicas agree exactly); a scalar ``all_gather``
    compares them. Only leaves replicated over the zero axes participate —
    ZeRO-sharded leaves have no redundant copy to compare (at stage >= 1
    that is the optimizer state, at stage 3 also the params; the audit then
    covers whatever replication remains, params at stage <= 2 being the
    expensive tree that matters). Cost: one bandwidth-bound read of the
    replicated leaves + one scalar all-gather — run every
    ``audit_frequency`` steps under ``lax.cond``, riding the anomaly-guard
    carry with NO extra host sync.

    Returns None when the mesh has no ZeRO-axis redundancy to audit
    (zero world of 1)."""
    zaxes = zero_axes(mesh)
    zsize = math.prod(mesh.shape[a] for a in zaxes)
    if zsize <= 1:
        return None
    zset = set(zaxes)
    specs = TrainState(
        step=P(),
        params=jax.tree.map(
            lambda ns: shd.restrict_spec(ns.spec, zset), plan.state.params
        ),
        opt_state=jax.tree.map(
            lambda ns: shd.restrict_spec(ns.spec, zset), plan.state.opt_state
        ),
    )

    def core(state: TrainState):
        from zero_transformer_tpu.resilience.detect import leaf_checksum

        total = jnp.zeros((), jnp.uint32)
        for leaf, spec in zip(jax.tree.leaves(state), jax.tree.leaves(specs)):
            if any(e is not None for e in spec):
                continue  # ZeRO-sharded: no redundant copy to compare
            total = total + leaf_checksum(leaf)
        gathered = jax.lax.all_gather(total, zaxes if len(zaxes) > 1 else zaxes[0])
        return (gathered.reshape(-1) != gathered.reshape(-1)[0]).any()

    return shard_map(
        core,
        mesh=mesh,
        in_specs=(specs,),
        out_specs=P(),
        axis_names=frozenset(zaxes),
        check_vma=False,
    )


def make_eval_step(model: nn.Module, mesh: Mesh, plan: ShardingPlan) -> Callable:
    """Jitted eval: mean next-token loss over a [batch, seq] batch
    (reference ``xmap_train_functions.py:94-107``)."""

    def eval_step(params, batch):
        _, loss = model.apply({"params": params}, batch, labels=batch)
        return loss

    return _with_ambient_mesh(
        jax.jit(
            eval_step,
            in_shardings=(plan.state.params, plan.batch),
            out_shardings=NamedSharding(mesh, P()),
        ),
        mesh,
    )

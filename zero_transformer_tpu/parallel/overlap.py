"""Overlapped ZeRO communication: layer-granular collectives inside the scan.

The serial explicit core (``zero._make_explicit_zero_step``) brackets the
whole step with communication: one monolithic ``all_gather`` of every
parameter before the first forward flop (stage 3), backward to completion,
then one ``psum_scatter`` sweep over all gradients. Nothing overlaps —
the collectives sit squarely on the critical path (arXiv:2004.13336's
weight-update sharding and 2412.14374's async pipelines both exist to
remove exactly this exposed time).

This module rebuilds the step around **communication buckets derived from
the ShardingPlan** (``derive_buckets`` — never hand-listed):

- every parameter whose logical spec leads with ``"layers"`` (the stacked
  ``nn.scan`` block weights) forms one bucket PER LAYER, sliced along the
  stacked dim;
- everything else (wte, ln_f, lm_head, wpe) is the small ``dense`` bucket.

The forward is the same math as ``model.apply`` — the same ``Block`` /
``nn.Embed`` / norm modules applied piecewise, pinned bitwise in
``tests/test_overlap.py`` — but the layer loop is an explicit ``lax.scan``
whose body gathers ITS OWN layer's shard:

- forward: iteration ``l`` issues ``all_gather(bucket_l)`` with no data
  dependency on iteration ``l-1``'s compute, so XLA's latency-hiding
  scheduler / collective pipeliner can prefetch layer ``l+1``'s gather
  behind layer ``l``'s matmuls (the telescoping prefetch through the
  blocks' scan structure);
- backward: the gather's transpose IS ``psum_scatter``, so autodiff places
  one per-layer gradient reduce-scatter in the reverse scan exactly as
  each layer's backward retires — gradients arrive already ZeRO-sharded,
  no post-backward sweep;
- under ``cfg.remat`` the gather sits INSIDE the rematerialized body, so
  the backward re-gathers instead of saving gathered layers (the standard
  FSDP recompute economics; without remat XLA keeps the gathered values as
  residuals, same as the serial step keeps its monolithic gather).

``overlap=False`` builds the identical compute with the old serial
placement (bucket gathers hoisted before the scan, so the program orders
all communication ahead of all compute) — the bit-for-bit A/B arm.
Verified on this backend: overlap-on ≡ overlap-off ≡ the serial explicit
core, bitwise, including the optimizer trajectory.

Stage semantics: state LAYOUT follows the plan exactly as before (stage 1
params replicated / opt sharded, stage 2 + scattered grads, stage 3 params
stored sharded). At stage 1 the overlapped core's gradient traffic is the
reduce-scatter + all-gather pair (numerically the same mean as stage 1's
all-reduce, and no more bytes) — the bucketed-DDP overlap story.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from zero_transformer_tpu.config import resolve_dtype
from zero_transformer_tpu.ops.losses import chunked_next_token_loss, next_token_loss
from zero_transformer_tpu.parallel import sharding as shd
from jax import shard_map


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """Communication buckets derived from a ShardingPlan (not hand-listed).

    ``block_sdims`` / ``dense_sdims`` carry each leaf's ZeRO scatter dim in
    its STORED shape (-1 = replicated over the zero axes, no collective).
    Stacked leaves with scatter dim 0 would be sharded over the layer dim
    itself — a layer's weights then live on one replica, so there is no
    per-layer bucket to overlap; they are gathered up front (``stack_sdims``)
    and ride the scan pre-gathered. ``*_bytes`` are full (gathered) sizes
    for the memory report and the step bench."""

    block_sdims: Any  # per-blocks-leaf scatter dim, -1 replicated/up-front
    stack_sdims: Any  # per-blocks-leaf dim-0 scatter (layer-dim sharded), -1 none
    dense_sdims: Any  # per-dense-leaf scatter dim
    n_layers: int
    n_buckets: int  # layer buckets + 1 dense bucket
    layer_bucket_bytes: int  # one layer's full params
    dense_bucket_bytes: int


def derive_buckets(plan, mesh: Mesh, abstract_params: Any) -> BucketPlan:
    """Split the param tree into layer-granular comm buckets, driven by the
    plan's logical specs (``"layers"``-stacked leaves) and ZeRO scatter
    dims — a model family change reshapes the buckets with no code here."""
    from zero_transformer_tpu.parallel.mesh import zero_axes
    from zero_transformer_tpu.parallel.zero import _zero_scatter_dim

    zaxes = zero_axes(mesh)
    stacked = jax.tree.map(
        lambda spec: len(spec) > 0 and spec[0] == "layers", plan.logical
    )
    sdims = jax.tree.map(
        lambda ns: _zero_scatter_dim(ns.spec, zaxes), plan.zero
    )

    blocks_stacked = stacked.get("blocks")
    if blocks_stacked is None or not all(jax.tree.leaves(blocks_stacked)):
        raise ValueError(
            "overlap_comm requires scan_layers=True (layer buckets are the "
            "stacked nn.scan block params; an unstacked model has none)"
        )
    for key, sub in stacked.items():
        if key != "blocks" and any(jax.tree.leaves(sub)):
            raise ValueError(
                f"layers-stacked params outside the blocks subtree ({key}); "
                f"the bucket derivation does not understand this model"
            )

    block_sdims = jax.tree.map(
        lambda d: d if d > 0 else -1, sdims["blocks"]
    )
    stack_sdims = jax.tree.map(
        lambda d: 0 if d == 0 else -1, sdims["blocks"]
    )
    dense_sdims = {k: v for k, v in sdims.items() if k != "blocks"}

    def _bytes(tree) -> int:
        return sum(
            leaf.size * jnp.dtype(leaf.dtype).itemsize
            for leaf in jax.tree.leaves(tree)
        )

    n_layers = jax.tree.leaves(abstract_params["blocks"])[0].shape[0]
    return BucketPlan(
        block_sdims=block_sdims,
        stack_sdims=stack_sdims,
        dense_sdims=dense_sdims,
        n_layers=int(n_layers),
        n_buckets=int(n_layers) + 1,
        layer_bucket_bytes=_bytes(abstract_params["blocks"]) // int(n_layers),
        dense_bucket_bytes=_bytes(
            {k: v for k, v in abstract_params.items() if k != "blocks"}
        ),
    )


def bucket_summary(plan, mesh: Mesh, abstract_params: Any) -> dict:
    """JSON-able bucket picture for ``trainer.memory_analysis`` and the
    step bench: how many buckets, how big, what a prefetch buffer costs."""
    b = derive_buckets(plan, mesh, abstract_params)
    return {
        "n_layer_buckets": b.n_layers,
        "layer_bucket_bytes": b.layer_bucket_bytes,
        "dense_bucket_bytes": b.dense_bucket_bytes,
        # during overlap, the gather of layer l+1 is in flight while layer
        # l computes: two gathered layer buckets live at once
        "overlap_gather_buffer_bytes": 2 * b.layer_bucket_bytes,
    }


def make_overlap_zero_step(
    model: nn.Module,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    plan,
    zero_stage: int,
    schedule: Optional[Callable] = None,
    tx_factory: Optional[Callable] = None,
    grad_accum_dtype: str = "float32",
    overlap: bool = True,
) -> Callable:
    """Build the bucketed/overlapped ZeRO train step.

    Same contract as ``zero.make_train_step``: ``(state, batch, rng) ->
    (state, metrics)``, ``batch`` int32 [accum, global_batch, seq].
    ``overlap=False`` keeps the identical compute but hoists every bucket
    gather ahead of the layer scan — the serial-placement A/B arm, bitwise
    against both ``overlap=True`` and the legacy serial core.
    """
    from zero_transformer_tpu.models.gpt import (
        Block,
        _norm,
        doc_ids_from_tokens,
        mask_boundary_labels,
        resolve_remat_policy,
    )
    from zero_transformer_tpu.parallel.sharding import (
        constrain_activation,
        replicate_activation,
    )
    from zero_transformer_tpu.parallel.zero import (
        TrainState,
        ZeroCollectives,
        _accum_add,
        _accum_dtype,
        _with_ambient_mesh,
        apply_tx_factory,
    )

    cfg = model.cfg
    if not cfg.scan_layers:
        raise ValueError("overlap_comm requires scan_layers=True")
    if cfg.hybrid:
        raise ValueError(
            "overlap_comm builds one kind of block a layer; a hybrid stack "
            "(layer_pattern) is scanned by period"
        )
    if zero_stage < 1:
        raise ValueError("overlap_comm requires zero_stage >= 1")
    acc_dt = _accum_dtype(grad_accum_dtype)
    zc = ZeroCollectives(mesh, plan)
    zaxes, axis = zc.zaxes, zc.axis

    def _init(rng):
        return model.init(rng, jnp.zeros((1, 8), jnp.int32))

    abstract_params = shd.unbox(
        jax.eval_shape(_init, jax.random.PRNGKey(0))["params"]
    )
    buckets = derive_buckets(plan, mesh, abstract_params)

    tx_inner = (
        apply_tx_factory(tx_factory, zc.shard_norm, zc)
        if tx_factory is not None
        else tx
    )

    dtype = resolve_dtype(cfg.compute_dtype)
    param_dtype = resolve_dtype(cfg.param_dtype)
    packed = cfg.doc_sep_token is not None
    L = cfg.n_layers

    embed_mod = nn.Embed(
        num_embeddings=cfg.vocab_size,
        features=cfg.d_model,
        dtype=dtype,
        param_dtype=param_dtype,
    )
    norm_mod = _norm(cfg, dtype, "ln_f")
    wpe_mod = (
        nn.Embed(
            num_embeddings=cfg.max_seq_len,
            features=cfg.d_model,
            dtype=dtype,
            param_dtype=param_dtype,
        )
        if cfg.position == "learned"
        else None
    )
    block = Block(cfg, False, False, None, model.mesh)

    def _gather(x, d):
        if d < 0:
            return x
        return jax.lax.all_gather(x, axis, axis=d, tiled=True)

    def gather_layer(p_layer):
        """One layer bucket: shard slices → full layer params. Scatter dims
        were derived on the STACKED shapes; the scan slice dropped dim 0."""
        return jax.tree.map(
            lambda x, d: _gather(x, d - 1 if d > 0 else -1),
            p_layer, buckets.block_sdims,
        )

    def block_apply(p_layer, carry, mrng):
        return block.apply({"params": p_layer}, carry, rngs={"dropout": mrng})

    if cfg.remat:
        # the gather lives INSIDE the checkpointed region: backward
        # re-gathers the layer instead of saving a full gathered copy —
        # the FSDP recompute trade, same policy knob as the fused model
        block_remat = jax.checkpoint(
            lambda p_layer, carry, mrng: block_apply(
                gather_layer(p_layer), carry, mrng
            ),
            prevent_cse=False,
            policy=resolve_remat_policy(cfg),
        )

    def forward(params, blocks, tokens, mrng):
        """The fused model's forward, with the layer loop as an explicit
        scan over (possibly still-sharded) stacked block params. ``params``
        holds the dense bucket (full); ``blocks`` the stacked block leaves —
        sharded when ``overlap`` (gathered in-body), full otherwise. Bitwise
        against ``Transformer.__call__`` (pinned in tests/test_overlap.py);
        dropout draws differ from the fused path's flax scan rng split
        (same distribution — parity suites run dropout 0)."""
        table = replicate_activation(
            jnp.asarray(params["wte"]["embedding"], dtype)
        )
        h = jnp.take(table, tokens, axis=0)
        h = constrain_activation(h, "batch", "seq", "embed")
        if wpe_mod is not None:
            T = tokens.shape[1]
            if T > cfg.max_seq_len:
                raise ValueError(
                    f"sequence length {T} > max_seq_len {cfg.max_seq_len}: "
                    "learned positions cannot extrapolate"
                )
            h = h + wpe_mod.apply(
                {"params": params["wpe"]}, jnp.arange(T, dtype=jnp.int32)
            )
        if cfg.dropout > 0.0:
            h = nn.Dropout(cfg.dropout, deterministic=False).apply(
                {}, h, rngs={"dropout": jax.random.fold_in(mrng, L)}
            )

        aux = jnp.zeros((), jnp.float32)
        doc_ids = (
            doc_ids_from_tokens(tokens, cfg.doc_sep_token) if packed else None
        )
        carry = (h.astype(dtype), aux, doc_ids) if packed else (h.astype(dtype), aux)

        def body(carry, xs):
            p_layer, idx = xs
            lrng = jax.random.fold_in(mrng, idx)
            if cfg.remat:
                if not overlap:
                    # serial arm: gathers hoisted before the scan; remat
                    # only the block compute (matches the fused model)
                    carry, _ = jax.checkpoint(
                        block_apply, prevent_cse=False,
                        policy=resolve_remat_policy(cfg),
                    )(p_layer, carry, lrng)
                else:
                    carry, _ = block_remat(p_layer, carry, lrng)
            else:
                if overlap:
                    p_layer = gather_layer(p_layer)
                carry, _ = block_apply(p_layer, carry, lrng)
            return carry, None

        carry, _ = jax.lax.scan(
            body, carry, (blocks, jnp.arange(L, dtype=jnp.int32))
        )
        h, aux = carry[0], carry[1]
        h = norm_mod.apply({"params": params["ln_f"]}, h)

        labels = tokens
        ignore = None
        if packed:
            labels = mask_boundary_labels(labels, doc_ids)
            ignore = -1
        if cfg.loss_chunk:
            w_dv = (
                jnp.asarray(params["wte"]["embedding"], dtype).T
                if cfg.tie_embeddings
                else jnp.asarray(params["lm_head"]["kernel"], dtype)
            )
            loss = chunked_next_token_loss(
                h, w_dv, labels, cfg.loss_chunk, ignore_index=ignore
            )
        else:
            if cfg.tie_embeddings:
                logits = embed_mod.apply(
                    {"params": params["wte"]}, h, method="attend"
                )
            else:
                logits = (
                    h.astype(dtype)
                    @ jnp.asarray(params["lm_head"]["kernel"], dtype)
                )
            loss = next_token_loss(logits, labels, ignore_index=ignore)
        if cfg.n_experts > 0:
            loss = loss + aux
        return loss

    # leaves whose grads autodiff cannot reduce (no gather anywhere: not
    # per-layer bucketed, not layer-dim sharded / not ZeRO-scattered dense)
    needs_psum = {
        k: jax.tree.map(lambda d: d < 0, v)
        for k, v in buckets.dense_sdims.items()
    }
    needs_psum["blocks"] = jax.tree.map(
        lambda b, s: b < 0 and s < 0, buckets.block_sdims, buckets.stack_sdims
    )

    def core(state: TrainState, batch: jax.Array, rng: jax.Array):
        accum = batch.shape[0]
        step_rng = jax.random.fold_in(rng, state.step)
        step_rng = jax.random.fold_in(step_rng, zc.dev_index())

        # the step works on the SHARDED view regardless of storage: stage 3
        # stores shards; stage 1/2 store full and slice locally (free)
        param_shards = (
            state.params if zero_stage >= 3 else zc.slice_local(state.params)
        )

        def loss_fn(shards, tokens, mrng):
            dense = {k: v for k, v in shards.items() if k != "blocks"}
            dense_full = jax.tree.map(_gather, dense, buckets.dense_sdims)
            # leaves sharded over the LAYER dim itself have no per-layer
            # bucket — gathered up front either way
            blocks = jax.tree.map(
                _gather, shards["blocks"], buckets.stack_sdims
            )
            if not overlap:
                # serial placement: every bucket gather ahead of the scan
                blocks = jax.tree.map(_gather, blocks, buckets.block_sdims)
            return forward(dense_full, blocks, tokens, mrng)

        def micro(i):
            mrng = jax.random.fold_in(step_rng, i)
            loss, grads = jax.value_and_grad(loss_fn)(
                param_shards, batch[i], mrng
            )
            # the gather transpose psum_scatters SUMS over the zero axis for
            # every bucketed leaf — but leaves with NO scatter dim (nothing
            # divisible by the zero world; stored replicated, _gather a
            # no-op) get no collective from autodiff and must be psum'd
            # explicitly, exactly as the serial core's reduce_grads does for
            # its indivisible leaves. /zsize then makes both the mean.
            grads = jax.tree.map(
                lambda g, r: jax.lax.psum(g, axis) if r else g,
                grads, needs_psum,
            )
            grads = jax.tree.map(lambda g: g / zc.zsize, grads)
            return jax.lax.pmean(loss, axis), grads

        if accum == 1:
            loss, grads = micro(0)
        else:

            def body(carry, i):
                loss_sum, grads_sum = carry
                loss, grads = micro(i)
                return (
                    loss_sum + loss,
                    jax.tree.map(_accum_add, grads_sum, grads),
                ), None

            zero_grads = jax.tree.map(
                lambda p: jnp.zeros(p.shape, acc_dt), param_shards
            )
            (loss, grads), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zero_grads),
                jnp.arange(accum),
            )
            loss = loss / accum
            grads = jax.tree.map(lambda g: g.astype(jnp.float32) / accum, grads)

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
        return (
            TrainState(step=state.step + 1, params=new_params, opt_state=new_opt),
            metrics,
        )

    zset = set(zaxes)

    def manual_part(spec: P) -> P:
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
            in_shardings=(
                plan.state,
                NamedSharding(mesh, batch_spec),
                NamedSharding(mesh, P()),
            ),
            out_shardings=(plan.state, NamedSharding(mesh, P())),
            donate_argnums=(0,),
        ),
        mesh,
    )

"""Analytic memory model: machine-readable stash/bubble/gather-buffer
numbers per config point, with NO compilation and NO device work.

``trainer.memory_analysis`` AOT-compiles the real train step — the ground
truth, but minutes per point and impossible for a backend that cannot
execute the config (this image's jax cannot run the pipe engine).
``analytic_memory`` is the cheap twin the autotuner's pruner calls per
candidate point (``analysis/autotune.py``): pure arithmetic over the
config — parameter/optimizer/gradient tree bytes under the ZeRO stage,
the pipeline activation-stash formulas, the interleaved block-replication
tax, the overlapped-ZeRO gather-buffer residency, and the analytic bubble
fraction. Every number is an ESTIMATE (``"exact": False``) sharing one
formula table with the trainer's ``memory_analysis`` schedule block
(``pp_stash_ticks`` below), so the two surfaces cannot drift.

CLI (the machine-readable surface — a dict, not a pretty-printer):

    python -m zero_transformer_tpu.analysis.memory --cfg configs/train_test.yaml \
        [--set mesh.zero_stage=3 ...] [--accum N] [--devices N] [--json]
"""
from __future__ import annotations

from typing import Any, Dict, Optional

# Optimizer-state tree size as a multiple of the f32 master-param tree.
# adamw: mu + nu; lion: momentum only; adafactor: factored second moments —
# O(rows + cols) per matrix, a few percent of the param bytes at real
# d_model (labeled estimate; the compiled memory_analysis is ground truth).
OPT_TREE_FACTOR = {"adamw": 2.0, "lion": 1.0, "adafactor": 0.05}


def pp_stash_ticks(schedule: str, accum: int, pipe: int, interleave: int) -> int:
    """Activation-stash depth (in microbatch ticks) of each pipeline
    engine's wavefront — the ONE formula table shared by
    ``trainer.memory_analysis`` and the autotuner's pruner. GPipe /
    interleaved: the differentiated tick scan saves its carry once per
    tick; 1F1B: the hand-managed 2P-slot input ring."""
    return {
        "gpipe": accum + pipe - 1,
        "1f1b": 2 * pipe,
        "interleaved": interleave * accum + pipe - 1,
    }[schedule]


def _dtype_bytes(name: str) -> int:
    import jax.numpy as jnp

    from zero_transformer_tpu.config import resolve_dtype

    return jnp.dtype(resolve_dtype(name)).itemsize


def kv_bytes_per_token(m) -> int:
    """Bytes ONE cached position holds in the decode K/V cache of model
    config ``m``: K and V in every entry — one per (pass, layer), so a
    looped stack pays ``n_loops`` times a plain one — at the compute dtype,
    or int8 values plus one float32 scale per (position, head). What a
    serving pool is sized from: ``page_pool_tokens`` x this, beside the
    weights. Latent attention keeps ONE row an entry, the latent and the
    rotated key padded to whole lane tiles (``latent_row``): the bytes the
    pool really holds. In a hybrid stack only the layers that attend keep
    K/V (``kv_entries`` counts those); what its mamba layers keep does not
    grow with the position: ``state_bytes_per_slot``."""
    if m.latent_attention:
        return m.kv_entries * m.latent_row * _dtype_bytes(m.compute_dtype)
    per_head = (
        m.head_width + 4 if m.kv_cache_dtype == "int8"
        else m.head_width * _dtype_bytes(m.compute_dtype)
    )
    return 2 * m.kv_entries * m.kv_heads * per_head


def state_bytes_per_slot(m) -> int:
    """Bytes of recurrent state ONE serving slot keeps in model config
    ``m``, whatever the request's length: a hybrid stack's mamba layers'
    float32 SSM state and conv inputs (``ModelConfig.state_bytes_per_slot``);
    0 for a stack that only attends. What a serving deployment pays a SLOT
    beside ``kv_bytes_per_token`` a position: ``n_slots`` x this is the
    state pool (held once: both serving programs update it in place)."""
    return m.state_bytes_per_slot


def analytic_memory(
    cfg, accum: Optional[int] = None, n_devices: Optional[int] = None
) -> Dict[str, Any]:
    """Analytic per-device memory itemization for one config point.

    ``n_devices``: size of the ZeRO/data axis the state shards over
    (default: ``mesh.data`` when pinned, else the runtime device count
    divided by the model axes). Returns plain ints/floats — the pruner
    compares ``peak_bytes_est`` against an HBM budget and records the
    losing terms in the prune reason."""
    from zero_transformer_tpu.parallel.pipeline import bubble_fraction

    m, mc, t = cfg.model, cfg.mesh, cfg.training
    accum = accum or t.gradient_accumulation_steps
    accum = max(accum, 1)
    model_axes = mc.fsdp * mc.expert * mc.tensor * mc.pipe * mc.sequence
    if n_devices is None:
        if mc.data > 0:
            n_devices = mc.data
        else:
            import jax

            n_devices = max(1, jax.device_count() // max(1, model_axes))
    zero_div = max(1, n_devices)

    param_b = _dtype_bytes(m.param_dtype)
    compute_b = _dtype_bytes(m.compute_dtype)
    accum_b = _dtype_bytes(t.grad_accum_dtype)
    n_params = m.num_params
    params_bytes = n_params * param_b
    embed_params = m.vocab_size * m.d_model * (1 if m.tie_embeddings else 2)
    layer_params = max(1, (n_params - embed_params) // max(1, m.n_layers))

    stage = mc.zero_stage
    per_dev_params = params_bytes // (zero_div if stage >= 3 else 1)
    per_dev_opt = int(
        params_bytes
        * OPT_TREE_FACTOR[cfg.optimizer.optimizer]
        // (zero_div if stage >= 1 else 1)
    )
    per_dev_grads = params_bytes // (zero_div if stage >= 2 else 1)
    # the running accumulation buffer only exists when accumulating
    per_dev_accum = n_params * accum_b if accum > 1 else 0

    act = t.batch_size * t.train_context * m.d_model * compute_b
    batch_bytes = accum * t.batch_size * t.train_context * 4  # int32 tokens

    out: Dict[str, Any] = {
        "exact": False,
        "provenance": "analytic",
        "zero_stage": stage,
        "n_devices": zero_div,
        "accum": accum,
        "optimizer": cfg.optimizer.optimizer,
        "params_bytes_global": params_bytes,
        "per_device_params_bytes": per_dev_params,
        "per_device_opt_state_bytes": per_dev_opt,
        "per_device_grad_bytes": per_dev_grads,
        "grad_accum_buffer_bytes": per_dev_accum,
        "microbatch_activation_bytes": act,
        "batch_bytes": batch_bytes,
        "pp_schedule": mc.pp_schedule,
        "pp_interleave": mc.pp_interleave,
        "overlap_comm": mc.overlap_comm,
        "remat": m.remat,
        "remat_policy": m.remat_policy,
        "bubble_frac": round(
            bubble_fraction(mc.pp_schedule, mc.pipe, accum, mc.pp_interleave), 5
        ),
    }

    stash = act  # the live residual of the current microbatch
    if mc.pipe > 1:
        ticks = pp_stash_ticks(mc.pp_schedule, accum, mc.pipe, mc.pp_interleave)
        out["pp_activation_stash_ticks"] = ticks
        out["pp_activation_stash_bytes_est"] = ticks * act
        stash = ticks * act
        if mc.pp_schedule == "interleaved":
            # interleaved stores the block stack pipe-replicated
            # (sharding.plan_rules): P-1 extra copies vs the contiguous shard
            blocks_bytes = layer_params * m.n_layers * param_b
            out["pp_block_replication_extra_bytes"] = (mc.pipe - 1) * (
                blocks_bytes // mc.pipe
            )
            stash += out["pp_block_replication_extra_bytes"]
    gather_buf = 0
    if mc.overlap_comm and stage >= 1:
        # the bucketed in-scan placement keeps up to two gathered layer
        # buckets live while the layer scan runs (parallel/overlap.py)
        gather_buf = 2 * layer_params * param_b
        out["overlap_gather_buffer_bytes_est"] = gather_buf

    out["per_device_state_bytes_est"] = (
        per_dev_params + per_dev_opt + per_dev_grads + per_dev_accum
    )
    out["peak_bytes_est"] = (
        out["per_device_state_bytes_est"] + stash + gather_buf + batch_bytes
    )
    return out


def main(argv=None) -> None:
    import argparse
    import json

    from zero_transformer_tpu.config import (
        apply_dotted_overrides,
        load_config,
    )

    p = argparse.ArgumentParser(
        description="analytic per-config-point memory itemization (no "
        "compile, no device work; trainer.memory_analysis is the compiled "
        "ground truth)"
    )
    p.add_argument("--cfg", default="configs/train_test.yaml")
    p.add_argument("--set", nargs="*", action="extend", default=None,
                   metavar="KEY=VALUE")
    p.add_argument("--accum", type=int, default=None)
    p.add_argument("--devices", type=int, default=None,
                   help="ZeRO/data axis size (default: mesh.data, else the "
                        "runtime device count over the model axes)")
    p.add_argument("--json", action="store_true",
                   help="one-line JSON to stdout (the machine-readable "
                        "surface; default is one key per line)")
    args = p.parse_args(argv)

    import ast

    overrides = {}
    for pair in args.set or []:
        key, _, raw = pair.partition("=")
        try:
            overrides[key] = ast.literal_eval(raw)
        except (ValueError, SyntaxError):
            overrides[key] = raw
    cfg = apply_dotted_overrides(load_config(args.cfg), overrides)
    report = analytic_memory(cfg, accum=args.accum, n_devices=args.devices)
    if args.json:
        print(json.dumps(report))
    else:
        for k in report:
            print(f"{k} = {report[k]}")


if __name__ == "__main__":
    main()

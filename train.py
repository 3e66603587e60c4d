"""Training entry point (reference: ``python main_zero.py``, ``main_zero.py:41-55``).

Usage:
    python train.py --cfg configs/train_125m.yaml [--resume] [--set key=value ...]

``--set`` overrides any dotted config field, e.g.
``--set training.total_steps=100 model.n_layers=4``.
"""
from __future__ import annotations

import argparse
import ast
import dataclasses
import logging

import jax


def parse_overrides(pairs):
    out = {}
    for pair in pairs or []:
        key, _, raw = pair.partition("=")
        try:
            out[key] = ast.literal_eval(raw)
        except (ValueError, SyntaxError):
            out[key] = raw
    return out


def apply_overrides(cfg, overrides: dict):
    # one implementation with the autotuner's candidate-point construction:
    # config.apply_dotted_overrides (model.size-first ordering included)
    from zero_transformer_tpu.config import apply_dotted_overrides

    return apply_dotted_overrides(cfg, overrides)


def _bench_common():
    """scripts/bench_common.py via the shared by-path loader (the platform
    gate the bench guards and both --tuned surfaces use)."""
    from zero_transformer_tpu.utils.modload import load_script

    return load_script("bench_common.py")


# tuned-override couples (see scripts/autotune.py tuned_overrides): these
# fields are only meaningful TOGETHER — accum microbatches the tuned
# workload's fixed global batch, so batch_size rides with it. A user
# override of either member drops the whole group, never leaving half a
# pair applied (a stranded tuned batch_size would silently change the
# global batch — exactly what the pairing exists to prevent).
_COUPLED_TUNED_FIELDS = (
    ("training.gradient_accumulation_steps", "training.batch_size"),
)


def apply_tuned(cfg, path, user_overrides, logger=None):
    """Load a TUNE_train.json autotuner artifact (scripts/autotune.py) as
    config defaults. The artifact only applies where it was measured: a
    platform/model/target mismatch is REFUSED with a loud warning and the
    hand defaults stand (the BENCH_ckpt_integrity/BENCH_step honesty
    discipline — never silently apply foreign tuning). Explicit --set
    overrides always win over tuned values."""
    import logging

    from zero_transformer_tpu.analysis.autotune import winner_overrides

    logger = logger or logging.getLogger("zero_transformer_tpu")
    bc = _bench_common()
    artifact, reasons = bc.load_tuned(
        path, platform=bc.platform_block(), model=cfg.model.name,
        target="train",
    )
    if artifact is None:
        logger.warning(
            "--tuned %s REFUSED (%s); falling back to hand defaults",
            path, "; ".join(reasons),
        )
        return cfg
    overrides = {
        k: v for k, v in winner_overrides(artifact).items()
        if k not in user_overrides
    }
    for group in _COUPLED_TUNED_FIELDS:
        if any(k in user_overrides for k in group):
            dropped = [k for k in group if overrides.pop(k, None) is not None]
            if dropped:
                logger.warning(
                    "--tuned %s: dropping coupled tuned fields %s — the "
                    "user overrode %s and these only hold as a pair "
                    "(fixed global batch)",
                    path, dropped,
                    [k for k in group if k in user_overrides],
                )
    logger.info(
        "--tuned %s: applying autotuned defaults %s (tuned on %s, "
        "workload %s, improvement %sx)",
        path, overrides, artifact.get("platform"),
        artifact.get("workload_hash"), artifact.get("value"),
    )
    return apply_overrides(cfg, overrides)


def main():
    parser = argparse.ArgumentParser(description="TPU-native ZeRO transformer trainer")
    parser.add_argument("--cfg", default="configs/train_test.yaml")
    parser.add_argument(
        "--resume",
        action="store_true",
        default=False,
        help="resume from the newest VERIFIED checkpoint (corrupt step dirs "
        "are quarantined with fallback to an older verified step). Elastic: "
        "resuming onto a DIFFERENT device/host count than the checkpoint "
        "was saved under reshards the ZeRO state natively and preserves the "
        "global-token trajectory; genuinely incompatible topologies fail "
        "with a precise error before compilation",
    )
    parser.add_argument(
        "--audit-frequency",
        type=int,
        default=None,
        metavar="N",
        help="cross-replica divergence audit every N steps (overrides "
        "resilience.audit_frequency): bit-exact agreement check of the "
        "DP-replicated state inside the compiled step — catches silent "
        "data corruption that desyncs one replica",
    )
    parser.add_argument(
        "--supervise",
        action="store_true",
        default=False,
        help="run under the in-process supervisor: bounded restarts with "
        "exponential backoff on retryable failures (loader/storage IO, "
        "hangs, preemption), resuming from the last good checkpoint each "
        "time; fatal config/shape errors still exit immediately. Budget and "
        "backoff come from the `resilience` config block",
    )
    parser.add_argument("--wandb", action="store_true", default=False)
    parser.add_argument("--max-steps", type=int, default=None)
    parser.add_argument(
        "--metrics-port",
        type=int,
        default=0,
        metavar="PORT",
        help="serve the training Prometheus registry at "
        "http://0.0.0.0:PORT/metrics (train_bubble_frac, "
        "train_exposed_comm_frac, ...); 0 disables. Pair with "
        "training.step_bench_artifact pointing at a BENCH_step.json "
        "measured on this platform to populate the exposed-comm gauge",
    )
    parser.add_argument(
        "--profile",
        type=int,
        default=None,
        metavar="N",
        help="capture a jax.profiler trace of N steps (after the compile step)",
    )
    parser.add_argument(
        "--profile-window",
        default=None,
        metavar="START:LEN",
        help="capture a jax.profiler trace of the step window "
        "[START, START+LEN) — an absolute-step twin of --profile for "
        "profiling steady state or a suspect step range mid-run (e.g. "
        "1000:20). Lands in training.profile_dir next to the "
        "flight-recorder dumps",
    )
    parser.add_argument(
        "--memory-analysis",
        action="store_true",
        default=False,
        help="AOT-compile the train step and print the compiled HBM "
        "breakdown (state/temps/peak), then exit — nothing is allocated "
        "or executed. The pre-flight for sizing a config to a 16 GB chip.",
    )
    parser.add_argument(
        "--debug-nans",
        action="store_true",
        default=False,
        help="jax_debug_nans: fail fast at the op that produced a NaN "
        "(numeric sanitizer; ~2x slower — debugging only)",
    )
    parser.add_argument(
        "--tuned",
        nargs="?",
        const="TUNE_train.json",
        default=None,
        metavar="TUNE_JSON",
        help="load autotuned defaults from a scripts/autotune.py artifact "
        "(default: TUNE_train.json). Applied only when the artifact's "
        "platform/model match this run — a mismatch is refused with a loud "
        "warning and the hand defaults stand. --set overrides always win",
    )
    # action="extend": repeated --set flags accumulate instead of the last
    # occurrence silently replacing earlier ones
    parser.add_argument(
        "--set", nargs="*", action="extend", default=None, metavar="KEY=VALUE"
    )
    args = parser.parse_args()
    if args.debug_nans:
        jax.config.update("jax_debug_nans", True)
    from zero_transformer_tpu.utils import compile_cache

    compile_cache.configure()

    logging.basicConfig(level=logging.INFO)
    from zero_transformer_tpu.config import load_config
    from zero_transformer_tpu.parallel.bootstrap import maybe_initialize
    from zero_transformer_tpu.training.trainer import Trainer

    # multi-host: wire the DCN coordination service when coordinator env vars
    # are present (reference ran pods on the implicit runtime, main_zero.py:181-184)
    maybe_initialize()

    cfg = load_config(args.cfg)
    user_overrides = parse_overrides(args.set)
    if args.tuned:
        # a --set model.size zoo lookup applies BEFORE the tuned gate, so
        # the artifact's model is checked against the model actually being
        # trained — and the later full-override pass can no longer clobber
        # tuned model.* values with a whole-section replacement
        if "model.size" in user_overrides:
            cfg = apply_overrides(
                cfg, {"model.size": user_overrides.pop("model.size")}
            )
        cfg = apply_tuned(cfg, args.tuned, user_overrides)
    cfg = apply_overrides(cfg, user_overrides)
    if args.resume:
        cfg = dataclasses.replace(
            cfg, checkpoint=dataclasses.replace(cfg.checkpoint, resume=True)
        )
    if args.profile:
        cfg = dataclasses.replace(
            cfg, training=dataclasses.replace(cfg.training, profile_steps=args.profile)
        )
    if args.profile_window:
        from zero_transformer_tpu.obs import parse_profile_window

        p_start, p_len = parse_profile_window(args.profile_window)
        cfg = dataclasses.replace(
            cfg,
            training=dataclasses.replace(
                cfg.training, profile_start=p_start, profile_steps=p_len
            ),
        )
    if args.audit_frequency is not None:
        cfg = dataclasses.replace(
            cfg,
            resilience=dataclasses.replace(
                cfg.resilience, audit_frequency=args.audit_frequency
            ),
        )

    logging.info(
        "devices=%d processes=%d backend=%s",
        jax.device_count(),
        jax.process_count(),
        jax.default_backend(),
    )
    if args.memory_analysis:
        import json

        from zero_transformer_tpu.training.trainer import memory_analysis

        report = memory_analysis(cfg)
        gb = 1 << 30
        for k in sorted(report):
            v = report[k]
            logging.info(
                "memory-analysis %s = %s", k,
                f"{v / gb:.2f} GiB" if "_bytes" in k and isinstance(v, int) else v,
            )
        print(json.dumps(report), flush=True)
        return
    if args.supervise:
        from zero_transformer_tpu.resilience import Supervisor

        if args.metrics_port:
            # loud, not silent: the supervisor rebuilds the Trainer (and its
            # registry) on every restart, so a single exporter bound here
            # would scrape a dead registry after the first recovery
            logging.getLogger("zero_transformer_tpu").warning(
                "--metrics-port is not supported with --supervise "
                "(the trainer registry is rebuilt across restarts); "
                "no /metrics endpoint will be served"
            )
        Supervisor(cfg, use_wandb=args.wandb).run(max_steps=args.max_steps)
        return
    trainer = Trainer(cfg, use_wandb=args.wandb)
    exporter = None
    try:
        if args.metrics_port:
            # inside the try: a bind failure (port in use) must still close
            # the trainer's async checkpoint machinery on the way out
            from zero_transformer_tpu.obs import MetricsExporter

            exporter = MetricsExporter(trainer.registry, port=args.metrics_port)
        trainer.train(max_steps=args.max_steps)
    finally:
        if exporter is not None:
            exporter.close()
        trainer.close()


if __name__ == "__main__":
    main()

"""Metrics logging, step timing, MFU, HBM stats (formerly utils/monitoring).

Moved here when ``obs/`` unified observability (PR 7); ``utils.monitoring``
remains as a compatibility facade re-exporting everything below, so existing
imports keep working. The reference's observability was wandb-only
(reference ``main_zero.py:354-366,504-529,559-562``) with no profiling and
no MFU anywhere (SURVEY §5). Here:

- ``MetricsLogger`` fans out to console, a JSONL file, and wandb when the
  package is importable (this image has no wandb — it is import-gated);
- ``model_flops_per_token`` / ``mfu`` give the 6N + attention FLOPs estimate
  against per-chip peak;
- ``hbm_device_stats`` reports EVERY local device's HBM in use with max and
  mean rollups (the old ``hbm_used_gb`` read only device 0 — a skewed
  TP/PP shard or a leaking replica on device 3 was invisible);
- ``StepTimer`` measures wall-per-step with a sync-on-read design (value
  fetch, not ``block_until_ready`` — see bench.py note);
- ``profile`` context manager wraps ``jax.profiler`` trace capture.
"""
from __future__ import annotations

import contextlib
import json
import time
from pathlib import Path
from typing import Any, Dict, Optional

import jax

from zero_transformer_tpu.obs.profiling import start_trace

# bf16 peak FLOP/s of ONE jax device, keyed by its exact ``device_kind``
# (Google Cloud TPU documentation, per-generation system architecture
# pages; a v3 jax device is one of a chip's two cores)
TPU_PEAK_FLOPS = {
    "TPU v3": 123e12 / 2,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5": 459e12,  # v5p
    "TPU v6 lite": 918e12,  # v6e
}


def model_flops_per_token(
    n_params: int, n_layers: int, d_model: int, seq_len: int, backward: bool = True
) -> float:
    """FLOPs per trained token: 6N (fwd+bwd matmuls) + 12·L·d·T attention term
    (PaLM appendix-B style accounting)."""
    mult = 3.0 if backward else 1.0
    dense = 2.0 * n_params
    attn = 4.0 * n_layers * d_model * seq_len  # qk^T + av, causal halves the 2x
    return mult * (dense + attn)


def device_peak_flops() -> Optional[float]:
    """Peak bf16 FLOP/s of device 0: ``None`` off the TPU (no utilisation is
    ever reported for a CPU run); a TPU kind missing from the table is an
    error, not a default — add it with its source."""
    device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    if device.device_kind not in TPU_PEAK_FLOPS:
        raise KeyError(
            f"no peak FLOP/s recorded for device_kind {device.device_kind!r}; "
            f"known: {sorted(TPU_PEAK_FLOPS)}"
        )
    return TPU_PEAK_FLOPS[device.device_kind]


def mfu(
    tokens_per_sec_per_chip: float,
    flops_per_token: float,
    peak_flops: Optional[float] = None,
) -> Optional[float]:
    peak = peak_flops if peak_flops is not None else device_peak_flops()
    if not peak:
        return None
    return tokens_per_sec_per_chip * flops_per_token / peak


def hbm_device_stats() -> Optional[Dict[str, Any]]:
    """Per-device HBM in use (GB) with max/mean rollups, or None where the
    backend exposes no memory stats (CPU). The per-device view is the one
    that catches a SKEWED fleet — one TP shard 2 GB heavier than its peers,
    or a leak on a single replica — which a device-0-only read hides."""
    per: list = []
    try:
        for d in jax.local_devices():
            stats = d.memory_stats()
            if not stats or "bytes_in_use" not in stats:
                return None
            per.append(stats["bytes_in_use"] / 1e9)
    except Exception:
        return None
    if not per:
        return None
    return {
        "per_device_gb": per,
        "max_gb": max(per),
        "mean_gb": sum(per) / len(per),
    }


def hbm_used_gb() -> Optional[float]:
    """Device-0 HBM in use, GB — the legacy single-device read, kept for
    compatibility; prefer ``hbm_device_stats`` (max/mean over ALL local
    devices). The observability hook the reference never had: its OOMs were
    discovered by crashing (reference ``logs/1B.md:7``)."""
    stats = hbm_device_stats()
    return stats["per_device_gb"][0] if stats else None


class MetricsLogger:
    """Console + JSONL + optional-wandb metrics sink."""

    def __init__(
        self,
        directory: Optional[str | Path] = None,
        use_wandb: bool = False,
        wandb_project: str = "zero-transformer-tpu",
        config: Optional[dict] = None,
        enabled: bool = True,
    ):
        self.enabled = enabled and jax.process_index() == 0
        self._file = None
        self._wandb = None
        if not self.enabled:
            return
        if directory is not None:
            from zero_transformer_tpu.utils.paths import is_remote_path

            if is_remote_path(directory):
                # remote run directory (gs:// etc.): object stores don't
                # support the append-mode JSONL sink; wandb carries remote
                # metrics, and the console line always prints.
                print(f"metrics: remote directory {directory}; JSONL sink disabled "
                      "(use wandb for remote metric history)", flush=True)
            else:
                path = Path(directory)
                path.mkdir(parents=True, exist_ok=True)
                self._file = open(path / "metrics.jsonl", "a", buffering=1)
        if use_wandb:
            try:
                import wandb

                self._wandb = wandb
                wandb.init(project=wandb_project, config=config or {})
            except ImportError:
                pass

    def log(self, metrics: Dict[str, Any], step: int, prefix: str = "") -> None:
        if not self.enabled:
            return
        clean = {
            (f"{prefix}/{k}" if prefix else k): (
                float(v) if hasattr(v, "item") or isinstance(v, (int, float)) else v
            )
            for k, v in metrics.items()
        }
        if self._file:
            self._file.write(json.dumps({"step": step, **clean}) + "\n")
        if self._wandb:
            self._wandb.log(clean, step=step)
        parts = " ".join(
            f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
            for k, v in clean.items()
        )
        print(f"[step {step}] {parts}", flush=True)

    def event(self, name: str, step: int, **fields: Any) -> None:
        """One-off run event (anomaly rollback, supervisor restart, watchdog
        abort, skipped data shard) — lands in the same JSONL/wandb stream as
        the scalar metrics so a post-mortem reads ONE timeline, but tagged
        with ``event`` so dashboards can render it as an annotation instead
        of a curve."""
        if not self.enabled:
            return
        clean = {
            k: (float(v) if hasattr(v, "item") else v) for k, v in fields.items()
        }
        if self._file:
            self._file.write(
                json.dumps({"step": step, "event": name, **clean}) + "\n"
            )
        if self._wandb:
            self._wandb.log(
                {f"event/{name}/{k}": v for k, v in clean.items()}, step=step
            )
        parts = " ".join(f"{k}={v}" for k, v in clean.items())
        print(f"[step {step}] EVENT {name} {parts}", flush=True)

    def close(self) -> None:
        if self._file:
            self._file.close()
        if self._wandb:
            self._wandb.finish()


class StepTimer:
    """Rolling wall-clock per-step timer. Call ``tick()`` once per step after
    fetching a step output (the fetch is the device sync)."""

    def __init__(self, window: int = 50):
        self.window = window
        self._times: list[float] = []
        self._last: Optional[float] = None

    def tick(self) -> Optional[float]:
        now = time.perf_counter()
        dt = None
        if self._last is not None:
            dt = now - self._last
            self._times.append(dt)
            if len(self._times) > self.window:
                self._times.pop(0)
        self._last = now
        return dt

    def mean(self) -> Optional[float]:
        return sum(self._times) / len(self._times) if self._times else None


@contextlib.contextmanager
def profile(log_dir: str | Path, enabled: bool = True):
    """Capture a jax.profiler trace viewable in TensorBoard/XProf (Python
    tracing off, live spans on the host plane: ``obs/profiling.start_trace``)."""
    if not enabled:
        yield
        return
    start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()

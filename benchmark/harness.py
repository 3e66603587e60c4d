"""What every run shares: finding a cell's files by the names in
``BENCHMARK.json``, the device check, memory and compile accounting, the
per-layer metric readers, the decision on ``correct`` and the result line.
No cell, configuration, traffic mix or metric is named in this file."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def _process_start() -> float:
    """When this process started, on ``time.monotonic``'s clock."""
    now = time.monotonic()
    try:
        start_ticks = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()[19])
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        if 0.0 <= age < 3600.0:
            return now - age
    except (OSError, ValueError, IndexError):
        pass
    return now


T_START = _process_start()
# the seconds ``jax.devices()`` took to reach the chip: the machine's, not the
# program's, and inside ``setup_s``; every result line carries it beside it
CHIP_REACH_S = None


def load_cell(workload: str) -> dict:
    """The cell's entry, configuration and traffic, each from its own file."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / config_entry["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "name": workload,
        "chips": cell["chips"],
        "config": config,
        "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def load_driver(kind: str):
    """A traffic file's ``kind`` names its driver: ``drivers/<kind>.py``."""
    return _load(HERE / "drivers" / f"{kind}.py", f"benchmark_driver_{kind}")


def load_reader(metric: str):
    return _load(HERE / "metrics" / f"{metric}.py",
                 "benchmark_metric_" + metric.replace(".", "_").replace("-", "_"))


def load_reference(config: dict):
    """A configuration file's ``reference`` names its family's plain
    reference, which also says which leaves the family has and what a token
    costs: a path under the checkout."""
    path = (ROOT / config["reference"]).resolve()
    return _load(path, "benchmark_reference_" + path.stem)


def model_config(config: dict, overrides=None):
    """The program's ``ModelConfig`` from the keys of the configuration's
    ``model`` group (and a mix's ``model_overrides``) that are its fields."""
    from zero_transformer_tpu.config import ModelConfig

    m = dict(config["model"], **(overrides or {}))
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    return ModelConfig(name=config["name"], **{k: v for k, v in m.items() if k in fields})


def check_configuration(config: dict) -> None:
    """What a run relies on, without a byte of memory: the configuration
    builds the program's ``ModelConfig``, and the abstract parameter tree of
    that model has exactly the paths and shapes of the family's
    ``leaf_table``. Raises ``SystemExit`` where they differ."""
    import jax
    import jax.numpy as jnp

    from benchmark import weights
    from zero_transformer_tpu.models import Transformer
    from zero_transformer_tpu.parallel.sharding import unbox

    cfg = model_config(config)
    abstract = jax.eval_shape(
        lambda: Transformer(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    weights.check_tree(load_reference(config).leaf_table(config["model"]), unbox(abstract))


def load_optimizer(name: str):
    """A training job's ``optimizer.optimizer`` names the plain optimizer the
    reference follows: ``optimizers/<name>.py``."""
    return _load(HERE / "optimizers" / f"{name}.py", f"benchmark_optimizer_{name}")


_LOADED: dict = {}


def _load(path: Path, name: str):
    """One module object per file, however often it is asked for."""
    if path not in _LOADED:
        if not path.exists():
            raise SystemExit(f"no such file: {path}")
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _LOADED[path] = module
    return _LOADED[path]


def require_chips(chips: int):
    """The devices, or exit non-zero with no result: never a fallback."""
    import jax

    global CHIP_REACH_S
    asked = time.monotonic()
    devices = jax.devices()
    CHIP_REACH_S = time.monotonic() - asked
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"the benchmark measures a TPU; JAX found {devices[0].platform!r}"
        )
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX found {len(devices)}")
    return devices[:chips]


def configure_jax() -> str:
    """The compile cache where the program's own helper puts it (the env var,
    else ``<checkout>/.jax_cache``), and every program kept, however quick."""
    import jax

    from zero_transformer_tpu.utils import compile_cache

    directory = compile_cache.configure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return directory


class CompileCounter:
    """Counts backend compilations, so a window can show it compiled nothing."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def device_block(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }


def read_per_layer(cell: dict, ctx: dict) -> dict:
    """Each per-layer metric of the cell through its own reader. A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for metric in cell["per_layer"]:
        value = load_reader(metric["name"]).read(ctx)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def decide(compared: dict) -> bool:
    """``compared`` is name -> {"value", "limit"}: correct when every value
    is a number no greater than its limit."""
    ok = bool(compared)
    for entry in compared.values():
        v = entry["value"]
        ok = ok and v is not None and v == v and v <= entry["limit"]
    return ok


def emit(result: dict) -> None:
    """The compared numbers beside their limits as the last lines of
    standard error, then the one result line as the last of standard output."""
    sys.stdout.flush()
    for name, entry in result.get("compared", {}).items():
        print(f"compared {name} = {entry['value']!r} limit {entry['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    ordered = {k: result[k] for k in
               ("correct", "attempted", "failed", "metrics", "device") if k in result}
    for k in result:
        if k not in ordered and k != "compared":
            ordered[k] = result[k]
    if CHIP_REACH_S is not None:
        ordered["chip_reach_s"] = CHIP_REACH_S
    ordered["compared"] = result.get("compared", {})
    print(json.dumps(ordered), flush=True)

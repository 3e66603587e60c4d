"""``chip_smoke.py`` rehearsed without the chip.

The real script needs a TPU and says so with its exit code; these tests
import its phase functions — the only way to run them off the chip, there is
no option that lets the script itself pass without one — and drive them at
the ``test`` model on the CPU with the Pallas kernels in interpret mode:
wrong paths, arguments and control flow are found here, at no chip time.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import _compile_cache

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

from zero_transformer_tpu.utils import compile_cache  # noqa: E402


@pytest.fixture
def rehearsal(tmp_path, monkeypatch):
    """Interpret-mode kernels and a learnable corpus: the phases assert a
    DECREASING loss, which uniform random tokens cannot give a 2-layer model
    in four steps. (The compile cache stays the suite's own: conftest
    exported its directory, so the entry points' ``compile_cache.configure()``
    leaves this process's jax config alone.)"""
    from zero_transformer_tpu.data import write_memmap

    monkeypatch.setenv("ZT_PALLAS_INTERPRET", "1")
    corpus = tmp_path / "train.bin"
    write_memmap(np.tile(np.arange(64, dtype=np.int32), 2048), str(corpus))
    sets = [
        "data.source=memmap", f"data.train_path={corpus}",
        f"data.validation_path={corpus}", "training.log_frequency=1",
        "optimizer.warmup_steps=1", "optimizer.peak_learning_rate=0.01",
    ]
    return tmp_path, sets


def test_train_extract_serve_phases_at_test_size(rehearsal):
    """Rehearsal 1: train -> verified restore -> extract -> three servers
    (spec / plain / xla) with identical greedy tokens, kernels asserted
    traced where expected and absent under ``--attention-impl xla``."""
    out, sets = rehearsal
    train = chip_smoke.train_phase(
        out, "configs/train_test.yaml", sets, steps=4, require_tpu=False
    )
    assert train["ok"] and train["phase"] == "train"
    assert train["loss_last"] < train["loss_first"]
    assert train["checkpoint_step"] == 4
    assert train["kernel_traces"]["flash_fwd"] and train["kernel_traces"]["flash_bwd"]
    assert train["device"]["platform"] == "cpu"

    extract = chip_smoke.extract_phase(out, require_tpu=False)
    assert extract["params_bytes"] > 0

    args = ["--tokenizer", "bytes", "--dtype", "float32", "--greedy",
            "--repetition-penalty", "1.0", "--cache-len", "512", "--slots", "4",
            "--prefill-chunk", "16", "--page-size", "8"]

    def child(variant, extra, port):
        code = (
            f"import sys; sys.path.insert(0, {str(REPO)!r}); "
            "import chip_smoke; from pathlib import Path; "
            f"chip_smoke.serve_child('test', Path({str(out / 'params.msgpack')!r}), "
            f"{port}, {args + list(extra)!r}, require_tpu=False)"
        )
        return [sys.executable, "-c", code]

    serve = chip_smoke.serve_phase(out, "test", chip_smoke.SERVE_VARIANTS, child)
    assert serve["ok"] and serve["greedy_identical_across"] == ["spec", "plain", "xla"]
    spec, plain, xla = (serve["servers"][k] for k in ("spec", "plain", "xla"))
    assert spec["kernel_paged_attention"] == 1 and spec["spec_ticks"] > 0
    assert spec["prefix_hits"] > 0 and plain["spec_ticks"] == 0
    assert xla["kernel_paged_attention"] == 0 and not xla["kernel_traces"]
    assert all(s["completed"] == chip_smoke.N_REQUESTS for s in (spec, plain, xla))


def test_kernels_phase_holds_the_paged_kernel_to_its_bar(monkeypatch):
    """The on-chip numerics check, rehearsed in interpret mode at the
    ``test`` model's head shapes: every case inside the bar, its off-by-one
    control far outside — and a bar the control would pass is refused."""
    # ragged at a shape with three buckets and two staged groups (page 16)
    # and the latent kernel at a small row (96 latent + 16 key lanes of 128)
    kw = dict(slots=2, cache_len=64, ragged_shapes=((4, 4, 384),),
              latent_shapes=((4, 5, 128, 96, 384),), ssm_shapes=((5, 8, 16, 128),),
              require_tpu=False)
    got = chip_smoke.kernels_phase("test", **kw)
    assert got["ok"] and len(got["paged_vs_gather"]) == 8
    # every pattern of decoding rows, none and all among them
    ssm = got["ssm_update_vs_xla"]
    assert [c["live_rows"] for c in ssm] == [0, 1, 1, 2, 2, 5]
    for case in ssm:
        assert case["ulps"] <= chip_smoke.SSM_ULPS < 1000 < case["control_ulps"]
        assert case["idle_rows_kept"] and case["other_layers_kept"] and "kernel_s" not in case
    assert [c["shape"]["T"] for c in got["latent_vs_gather"]] == [1, 5]
    for case in got["latent_vs_gather"]:
        assert case["ulps"] <= chip_smoke.PAGED_ULPS < case["control_ulps"]
    assert {(c["shape"]["T"], c["int8_pages"], c["ragged"])
            for c in got["paged_vs_gather"]} == {
        (T, int8, ragged)
        for T in (1, 5) for int8 in (False, True) for ragged in (False, True)
    }
    for case in got["paged_vs_gather"]:
        assert case["ulps"] <= chip_smoke.PAGED_ULPS < case["control_ulps"]
    monkeypatch.setattr(chip_smoke, "PAGED_ULPS", 1e9)
    with pytest.raises(RuntimeError, match="paged kernel outside"):
        chip_smoke.kernels_phase("test", **kw)
    monkeypatch.setattr(chip_smoke, "PAGED_ULPS", 2)
    monkeypatch.setattr(chip_smoke, "SSM_ULPS", 1e12)  # the stale control would pass
    with pytest.raises(RuntimeError, match="state-update kernel outside"):
        chip_smoke.kernels_phase("test", **kw)


def test_zero_phase_on_the_virtual_mesh(rehearsal, devices):
    """Rehearsal 2: the recipe's adafactor under ZeRO-1 and ZeRO-2 on a
    ``data=8`` virtual mesh against the one-device run of the same global
    batch and seed — the flash kernel traced under ``shard_kernel`` (plain
    jit and the explicit ZeRO-2 core), losses decreasing and within the
    stated tolerance, factored state tiny and on every device — then the
    adamw leg, its param-shaped state spread 1/8 each. d_model 128, the
    narrowest width adafactor factors."""
    out, sets = rehearsal
    sets = sets + ["model.d_model=128", "training.batch_size=8",
                   "training.gradient_accumulation_steps=2"]
    got = chip_smoke.zero_phase(
        out, "configs/train_test.yaml",
        sets + ["optimizer.optimizer=adafactor"], steps=4,
        adamw_sets=sets + ["optimizer.optimizer=adamw"], adamw_steps=2,
        n_chips=len(devices), require_tpu=False,
    )
    assert got["ok"] and got["device"]["count"] == 8
    assert got["one_chip"]["loss"][-1] < got["one_chip"]["loss"][0]
    for stage in ("zero1", "zero2"):
        assert got[stage]["max_loss_diff"] <= chip_smoke.ZERO_LOSS_TOL
        assert got[stage]["opt_state_of_params"] <= chip_smoke.FACTORED_STATE_MAX
        assert got[stage]["max_opt_state_share"] == 1.0  # replicated by design
    for stage in ("adamw_zero1", "adamw_zero2"):
        assert got[stage]["max_opt_state_share"] < 1.25 / 8
    for leg in ("zero1", "zero2", "adamw_zero1", "adamw_zero2"):
        assert len(got[leg]["opt_state_bytes_per_device"]) == 8
        assert got[leg]["kernel_traces"]["flash_fwd"]


def _run_main(monkeypatch, capsys, tmp_path, argv, device):
    """``chip_smoke.main()`` with its children stubbed out."""
    phases = []

    def run_child(name, cmd):
        phases.append(name)
        return {"phase": name, "ok": True, "device": device}

    def serve_phase(out, model, variants, child):
        phases.append("serve")
        return {"phase": "serve", "ok": True, "device": device}

    monkeypatch.setattr(chip_smoke, "OUT", tmp_path / "out")
    monkeypatch.setattr(chip_smoke, "run_child", run_child)
    monkeypatch.setattr(chip_smoke, "serve_phase", serve_phase)
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py", *argv])
    chip_smoke.main()
    return phases, capsys.readouterr().out.strip().splitlines()


@pytest.mark.parametrize("argv,count,expected", [
    ([], 1, ["train", "extract", "kernels", "serve"]),
    (["--chips", "4"], 4, ["zero4"]),
])
def test_last_line_is_exactly_the_contract(
    monkeypatch, capsys, tmp_path, argv, count, expected
):
    """The driver reads ``ok`` and ``device.{platform,kind,count}`` off the
    LAST stdout line; nothing else goes in it. ``--chips 4`` runs the ZeRO
    phase and no other."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": count}
    phases, lines = _run_main(monkeypatch, capsys, tmp_path, argv, device)
    assert phases == expected
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": device}
    assert list(last) == ["ok", "device"]
    assert list(last["device"]) == ["platform", "kind", "count"]


@pytest.mark.parametrize("device", [
    {"platform": "cpu", "kind": "cpu", "count": 1},
    {"platform": "tpu", "kind": "TPU v5 lite", "count": 4},
], ids=["cpu", "wrong-count"])
def test_no_last_line_for_the_wrong_device(monkeypatch, capsys, tmp_path, device):
    """Even with every phase green, a device that is not the asked-for
    number of TPU chips exits non-zero and prints no result line."""
    with pytest.raises(SystemExit) as exit_info:
        _run_main(monkeypatch, capsys, tmp_path, [], device)
    assert exit_info.value.code not in (0, None)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert all("phase" in line for line in lines)  # phase notes, no result


def test_script_exits_nonzero_without_a_tpu(tmp_path):
    """The real script, as the driver runs it, in a sandbox with no
    accelerator: non-zero, the failing phase named, no result printed."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], env=env, cwd=tmp_path,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "phase train failed" in proc.stderr and "no TPU" in proc.stderr


def test_cache_helper_honours_the_env_var(monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set: the helper returns it and calls
    no ``jax.config.update``; unset: the fixed in-checkout directory."""
    import jax

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.configure() == str(tmp_path)
    assert calls == []
    monkeypatch.delenv(compile_cache.ENV_VAR)
    assert compile_cache.configure() == str(REPO / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", str(REPO / ".jax_cache"))]
    # the tests' own cache is placed by the same helper, inside the checkout
    tests_dir = str(REPO / ".jax_cache" / "tests" / _compile_cache.cpu_fingerprint())
    assert _compile_cache.configure(jax) == tests_dir
    assert ("jax_compilation_cache_dir", tests_dir) in calls
    n_calls = len(calls)
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert _compile_cache.configure(jax) == str(tmp_path)
    assert all(c[0] != "jax_compilation_cache_dir" for c in calls[n_calls:])


@pytest.mark.parametrize("kind,expected", [
    ("TPU v5 lite", 197e12), ("TPU v4", 275e12), ("TPU v6 lite", 918e12),
])
def test_peak_flops_is_keyed_by_exact_device_kind(monkeypatch, kind, expected):
    import types

    import jax

    from zero_transformer_tpu.obs.logging import device_peak_flops

    assert device_peak_flops() is None  # this process runs on the CPU
    fake = types.SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    assert device_peak_flops() == expected
    fake.device_kind = kind + " (unknown stepping)"
    with pytest.raises(KeyError, match="no peak FLOP/s recorded"):
        device_peak_flops()

"""The autotuner (ISSUE 14): knob-space mechanics, the validity oracle,
analytic pruning, successive-halving determinism, the committed
TUNE_<target>.json artifact contract, and the --tuned gating.

Philosophy matches test_serve_bench.py / test_train_bench.py: the
committed artifact is driver-facing evidence, so its schema and
invariants are pinned here; the search MECHANICS (enumerate -> prune ->
halve -> artifact) are unit-tested deterministically without timing.
"""
import importlib.util
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from zero_transformer_tpu.analysis import autotune as at
from zero_transformer_tpu.config import Config, apply_dotted_overrides

REPO = Path(__file__).resolve().parent.parent


def _file_module(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench_common():
    return _file_module("bench_common", REPO / "scripts" / "bench_common.py")


# ------------------------------------------------------------ space basics


def test_space_enumeration_is_deterministic_and_complete():
    s = at.train_space()
    points = s.points()
    assert len(points) == s.size
    assert points == at.train_space().points()  # rebuild -> same order
    # every point binds every knob to a domain value
    for p in points[:: max(1, len(points) // 17)]:
        for knob in s.knobs:
            assert p[knob.name] in knob.values
    # registering a knob is all it takes to join the search
    s2 = at.KnobSpace("train")
    s2.register(at.Knob("overlap_comm", (False, True), "mesh.overlap_comm",
                        "train", "BENCH_step"))
    assert s2.size == 2 and len(s2.points()) == 2
    with pytest.raises(ValueError, match="already registered"):
        s2.register(at.Knob("overlap_comm", (True,), "mesh.overlap_comm",
                            "train", "BENCH_step"))


def test_knob_rejects_empty_or_malformed_domains():
    with pytest.raises(ValueError, match="empty domain"):
        at.Knob("x", (), "mesh.pipe", "train", "BENCH_step")
    with pytest.raises(ValueError, match="dotted"):
        at.Knob("x", (1,), "pipe", "train", "BENCH_step")


# -------------------------------------------------- the validity oracle


@pytest.mark.parametrize("target", ["train", "serve"])
def test_validity_sweep_every_invalid_point_names_a_knob(target):
    """The acceptance-bar sweep: every invalid knob combination in the
    registered space must raise ValueError NAMING an offending knob —
    config validation is what keeps invalid points out of measured trials,
    so an anonymous refusal would make the prune trace unauditable."""
    space = at.train_space() if target == "train" else at.serve_space()
    base = Config()
    knob_tokens = [k.field.rsplit(".", 1)[1] for k in space.knobs] + [
        k.name for k in space.knobs
    ]
    invalid = 0
    for point in space.points():
        try:
            apply_dotted_overrides(base, space.overrides(point))
        except ValueError as e:
            invalid += 1
            msg = str(e)
            assert any(tok in msg for tok in knob_tokens), (
                f"refusal for {point} names no searched knob: {msg}"
            )
    assert invalid > 0, "the space contains no invalid combinations?"


@pytest.mark.parametrize("target", ["train", "serve"])
def test_pruning_majority_reasons_and_valid_survivors(target):
    """Analytic pre-pruning must eliminate the points it can name a reason
    for — >= 50% of the training space, whose inert and unbuildable
    combinations are most of it; in the serving space, which no longer has
    an inert dimension, the page sizes that do not divide the chunk — with
    every pruned point's (rule, reason) recorded, and every survivor must
    construct a valid Config: no measured trial ever runs an invalid
    point."""
    base = Config()
    if target == "train":
        space, least = at.train_space(), 0.5
        validators = [
            at.config_validator(space, base),
            at.train_redundancy_validator(),
        ]
    else:
        space, least = at.serve_space(), 1 / 6  # page 16 against chunk 8
        validators = [
            at.config_validator(space, base),
            at.serve_feasibility_validator(64),
        ]
    points = space.points()
    survivors, pruned = at.prune_points(points, validators)
    assert len(survivors) + len(pruned) == len(points)
    assert len(pruned) / len(points) >= least, (
        f"only {len(pruned)}/{len(points)} pruned analytically"
    )
    for p in pruned:
        assert p.rule and p.reason, p
        assert points[p.index] == p.knobs
    for _, knobs in survivors:
        apply_dotted_overrides(base, space.overrides(knobs))  # must not raise


def test_serve_feasibility_rules():
    check = dict([at.serve_feasibility_validator(64)])
    fn = at.serve_feasibility_validator(64)[1]
    assert "divide" in fn({"page_size": 7, "page_pool_tokens": 0})
    assert "worst-case" in fn({"page_size": 4, "page_pool_tokens": 32})
    assert fn({"page_size": 4, "page_pool_tokens": 0}) is None
    assert check  # the validator is (rule, fn) shaped


# ------------------------------------------------- successive halving


def _fake_measure(scores):
    calls = []

    def measure(arm, budget, rung):
        calls.append((arm, budget, rung))
        if scores[arm] is None:
            return {"ok": False, "error": "boom"}
        # deterministic fake cost model: score independent of budget
        return {"ok": True, "score": scores[arm],
                "metrics": {"score": scores[arm], "budget": budget}}

    return measure, calls


def test_successive_halving_deterministic_and_failure_safe():
    scores = {0: 5.0, 1: 1.0, 2: 3.0, 3: None, 4: 2.0}
    runs = []
    for _ in range(2):
        measure, calls = _fake_measure(scores)
        winner, rungs = at.successive_halving(
            sorted(scores), measure, budgets=[2, 8], keep_frac=0.5
        )
        runs.append((winner, rungs, calls))
    assert runs[0][0] == runs[1][0] == 1  # lowest score wins, both passes
    assert runs[0][1] == runs[1][1]  # identical rung traces
    r0 = runs[0][1][0]
    # the failed arm is recorded with its error and never promoted
    failed = next(t for t in r0["trials"] if t["arm"] == 3)
    assert failed["ok"] is False and "boom" in failed["error"]
    assert 3 not in r0["promoted"]
    # rung 0 keeps ceil(4 ok arms * 0.5) = 2; the final rung keeps 1
    assert r0["promoted"] == [1, 4]
    assert runs[0][1][1]["promoted"] == [1]
    # cheap budget gates the expensive one: rung 1 only measured survivors
    rung1_arms = {a for a, b, r in runs[0][2] if r == 1}
    assert rung1_arms == {1, 4}


def test_successive_halving_all_failed_raises():
    measure, _ = _fake_measure({0: None, 1: None})
    with pytest.raises(RuntimeError, match="every arm failed"):
        at.successive_halving([0, 1], measure, budgets=[1])


def test_successive_halving_tie_break_is_by_arm_index():
    measure, _ = _fake_measure({7: 1.0, 3: 1.0})
    winner, rungs = at.successive_halving([3, 7], measure, budgets=[1])
    assert winner == 3  # equal scores: lowest arm id, deterministically


def test_successive_halving_tie_frac_absorbs_noise():
    """Arms within the declared noise floor are a statistical tie and
    resolve by arm index — a rerun whose noise flips their raw order must
    still reproduce the same winner (the determinism the artifact gate
    certifies)."""
    # run A: arm 7 measures 1% "faster"; run B: arm 3 does
    for scores in ({3: -100.0, 7: -101.0}, {3: -101.0, 7: -100.0}):
        measure, _ = _fake_measure(scores)
        winner, _ = at.successive_halving(
            [3, 7], measure, budgets=[1], tie_frac=0.05
        )
        assert winner == 3
    # a gap far beyond the floor is a real ranking, not a tie
    measure, _ = _fake_measure({3: -100.0, 7: -150.0})
    winner, _ = at.successive_halving(
        [3, 7], measure, budgets=[1], tie_frac=0.05
    )
    assert winner == 7


# ------------------------------------------ committed artifact contract


@pytest.fixture(scope="module", params=["TUNE_train.json", "TUNE_serve.json"])
def tune_artifact(request):
    path = REPO / request.param
    assert path.exists(), (
        f"commit {request.param} (JAX_PLATFORMS=cpu python "
        f"scripts/autotune.py --target "
        f"{request.param.split('_')[1].split('.')[0]} --reruns 2)"
    )
    return json.loads(path.read_text())


def test_tune_artifact_schema(tune_artifact):
    missing = at.TUNE_REQUIRED_KEYS - tune_artifact.keys()
    assert not missing, f"TUNE artifact missing keys: {sorted(missing)}"
    assert tune_artifact["schema_version"] == at.TUNE_SCHEMA_VERSION
    assert set(tune_artifact["platform"]) == {
        "backend", "device", "device_count",
    }
    assert tune_artifact["provenance"] == "measured"
    assert tune_artifact["target"] in ("train", "serve")


def test_tune_artifact_pruning_trace_is_auditable(tune_artifact):
    """The ISSUE 14 bar: >= 50% of the enumerated space pruned BEFORE any
    measured trial, every pruned point carrying its (rule, reason), and
    the partition exact."""
    pr = tune_artifact["pruning"]
    assert pr["enumerated"] == pr["pruned"] + pr["survivors"]
    assert pr["pruned_frac"] >= 0.5, pr["pruned_frac"]
    assert len(pr["points"]) == pr["pruned"]
    for p in pr["points"]:
        assert p["rule"] and p["reason"], p
    assert sum(pr["rules"].values()) == pr["pruned"]
    # measured arms are exactly the survivors
    assert len(tune_artifact["search"]["arms"]) == pr["survivors"]


def test_tune_artifact_winner_beats_hand_defaults(tune_artifact):
    """The committed artifact's claim: the autotuned config beats the hand
    defaults on its bench metric, measured as a within-run A/B on the
    platform named in the artifact (honest provenance — the tuned numbers
    only ever apply under a matching platform block, enforced by
    check_tuned)."""
    imp = tune_artifact["improvement"]
    assert imp["higher_is_better"] is True
    assert imp["winner"] > imp["baseline"], imp
    assert tune_artifact["value"] == imp["ratio"] > 1.0
    # winner knobs live inside the declared space, with a field mapping
    space = tune_artifact["space"]
    for name, value in tune_artifact["winner"]["knobs"].items():
        assert value in space[name]["values"], (name, value)
        assert "." in space[name]["field"]


def test_train_tune_pins_global_batch(tune_artifact):
    """The train accum knob microbatches a FIXED global batch: the winner's
    loadable overrides must pin batch_size x accum == the workload's global
    batch, so --tuned reproduces the measured geometry (same tokens per
    optimizer step — a perf knob, never a silent trajectory change)."""
    if tune_artifact["target"] != "train":
        pytest.skip("serve artifact")
    for block in ("winner", "baseline"):
        ov = tune_artifact[block]["overrides"]
        accum = ov["training.gradient_accumulation_steps"]
        assert (
            ov["training.batch_size"] * accum
            == tune_artifact["workload"]["spec"]["batch"]
        ), (block, ov)


def test_tune_artifact_determinism_block(tune_artifact):
    det = tune_artifact["determinism"]
    assert det["reruns"] >= 2
    assert det["winner_stable"] is True
    assert det["fingerprints_equal"] is True
    assert len(det["fingerprint"]) == 16


def test_tune_artifact_workload_hash_rederivable(tune_artifact):
    """The embedded workload spec must hash to the embedded hash — the
    byte-identical-replay claim is checkable from the artifact alone."""
    spec = tune_artifact["workload"]["spec"]
    assert at.workload_hash(spec) == tune_artifact["workload_hash"]


def test_tune_artifact_winner_overrides_apply_cleanly(tune_artifact):
    """The winner must load back through the SAME validated path --tuned
    uses (a committed artifact that train.py would refuse at apply time
    is worse than none). ``serve --tuned`` takes the winner's knobs by name
    (``_TUNED_KNOBS``), so the committed serve artifact, measured while the
    space still held the slab layout and the defused tail, is held to the
    knobs the server still has."""
    overrides = at.winner_overrides(tune_artifact)
    assert overrides  # non-empty
    if tune_artifact["target"] == "serve":
        from zero_transformer_tpu.serve import _TUNED_KNOBS

        overrides = {
            f"serving.{k}": v
            for k, v in tune_artifact["winner"]["knobs"].items()
            if k in _TUNED_KNOBS
        }
        assert overrides
    apply_dotted_overrides(Config(), overrides)  # must not raise


def test_winner_overrides_fall_back_to_space_mapping():
    art = {
        "winner": {"knobs": {"overlap_comm": True}},
        "space": {"overlap_comm": {"field": "mesh.overlap_comm"}},
    }
    assert at.winner_overrides(art) == {"mesh.overlap_comm": True}
    with pytest.raises(ValueError, match="no field mapping"):
        at.winner_overrides({"winner": {"knobs": {"x": 1}}, "space": {}})


# ------------------------------------------------------ --tuned gating


def _tuned_artifact(platform=None, model="test", target="train"):
    # the matching platform is THIS process' block (device_count included:
    # 8 virtual devices under the test env — a 1-device artifact must not
    # match it, and vice versa)
    return {
        "target": target, "model": model,
        "platform": platform or _bench_common().platform_block(),
        "workload_hash": "abc123",
        "value": 1.2,
        "winner": {
            "knobs": {"overlap_comm": True},
            "overrides": {"mesh.overlap_comm": True},
        },
    }


def test_check_tuned_matching_passes_and_mismatches_name_offender():
    bc = _bench_common()
    here = bc.platform_block()
    ok, reasons = bc.check_tuned(
        _tuned_artifact(), platform=here, model="test", target="train"
    )
    assert ok and not reasons
    ok, reasons = bc.check_tuned(
        _tuned_artifact({"backend": "tpu", "device": "v5e"}),
        platform=here, model="test", target="train",
    )
    assert not ok and any("platform" in r for r in reasons)
    ok, reasons = bc.check_tuned(
        _tuned_artifact(), platform=here, model="1_3b", target="train"
    )
    assert not ok and any("model" in r for r in reasons)
    ok, reasons = bc.check_tuned(
        _tuned_artifact(), platform=here, model="test", target="serve"
    )
    assert not ok and any("target" in r for r in reasons)
    ok, reasons = bc.check_tuned(
        _tuned_artifact(), platform=here, model="test",
        workload_hash="other", target="train",
    )
    assert not ok and any("workload" in r for r in reasons)
    # not a TUNE artifact at all
    ok, reasons = bc.check_tuned({"metric": "x"}, platform=here)
    assert not ok and any("winner" in r for r in reasons)


def test_train_apply_tuned_applies_refuses_and_respects_user(tmp_path):
    import train as train_mod

    art = _tuned_artifact()
    path = tmp_path / "TUNE_train.json"
    path.write_text(json.dumps(art))
    cfg = Config()
    # matching artifact (this box IS cpu/cpu under the test env): applied
    tuned_cfg = train_mod.apply_tuned(cfg, path, {})
    assert tuned_cfg.mesh.overlap_comm is True
    # an explicit --set of the same field wins over the tuned value
    kept = train_mod.apply_tuned(cfg, path, {"mesh.overlap_comm": False})
    assert kept.mesh.overlap_comm is False
    # coupled fields apply or drop TOGETHER: overriding accum must also
    # drop the tuned batch_size (half the pair would silently change the
    # global batch the pairing exists to freeze)
    art_pair = _tuned_artifact()
    art_pair["winner"]["overrides"] = {
        "training.gradient_accumulation_steps": 4,
        "training.batch_size": 2,
        "mesh.zero_stage": 2,
    }
    path.write_text(json.dumps(art_pair))
    half = train_mod.apply_tuned(
        cfg, path, {"training.gradient_accumulation_steps": 1}
    )
    assert half.training.batch_size == cfg.training.batch_size  # untouched
    assert half.mesh.zero_stage == 2  # uncoupled tuned fields still apply
    # restore the simple artifact for the remaining cases
    path.write_text(json.dumps(art))
    # foreign platform: REFUSED, hand defaults stand
    art["platform"] = {"backend": "tpu", "device": "v5e"}
    path.write_text(json.dumps(art))
    assert train_mod.apply_tuned(cfg, path, {}) == cfg
    # model mismatch: refused
    art["platform"] = {"backend": "cpu", "device": "cpu"}
    art["model"] = "1_3b"
    path.write_text(json.dumps(art))
    assert train_mod.apply_tuned(cfg, path, {}) == cfg
    # unreadable artifact: refused, not crashed
    assert train_mod.apply_tuned(cfg, tmp_path / "missing.json", {}) == cfg


def test_serve_resolve_tuned_args(tmp_path):
    from zero_transformer_tpu.serve import _TUNED_KNOBS, _resolve_tuned_args
    from zero_transformer_tpu.config import ServingConfig

    defaults = ServingConfig()

    def args(tuned=None, **explicit):
        ns = SimpleNamespace(
            model="test", tuned=tuned, repetition_penalty=1.0,
            **{k: None for k in _TUNED_KNOBS},
        )
        for k, v in explicit.items():
            setattr(ns, k, v)
        return ns

    # no artifact: ServingConfig hand defaults fill the sentinels
    a = _resolve_tuned_args(args())
    assert a.page_size == defaults.page_size
    assert a.draft_k == defaults.draft_k
    # matching artifact: winner knobs become the defaults...
    art = _tuned_artifact(target="serve")
    # (knobs of an artifact from before the slab layout and the defused
    # tail were removed name nothing any more and are passed over)
    art["winner"] = {"knobs": {"draft_k": 4, "page_size": 8,
                               "kv_layout": "slab", "fused_tail": True}}
    path = tmp_path / "TUNE_serve.json"
    path.write_text(json.dumps(art))
    a = _resolve_tuned_args(args(tuned=str(path)))
    assert a.draft_k == 4 and a.page_size == 8
    # ...but an explicit flag still wins
    a = _resolve_tuned_args(args(tuned=str(path), draft_k=0))
    assert a.draft_k == 0 and a.page_size == 8
    # a tuned draft_k that the engine would silently drop (repetition
    # penalty != 1.0) is refused AT RESOLUTION with the remedy — the
    # headline tuned knob must never vanish downstream of the banner
    a = _resolve_tuned_args(args(tuned=str(path), repetition_penalty=1.1))
    assert a.draft_k == defaults.draft_k  # tuned draft_k dropped loudly
    assert a.page_size == 8  # the compatible tuned knobs still apply
    # platform mismatch: refused loudly, hand defaults stand
    art["platform"] = {"backend": "tpu", "device": "v5e"}
    path.write_text(json.dumps(art))
    a = _resolve_tuned_args(args(tuned=str(path)))
    assert a.draft_k == defaults.draft_k
    assert a.page_size == defaults.page_size


# --------------------------------------------------- bench_common gates


def test_hardware_gate_semantics():
    bc = _bench_common()
    a = {"platform": {"backend": "cpu", "device": "x"}}
    b = {"platform": {"backend": "tpu", "device": "v4"}}
    ok, reason = bc.hardware_gate(a, dict(a))
    assert ok and reason is None
    ok, reason = bc.hardware_gate(a, b)
    assert not ok and "SKIP" in reason and "mismatch" in reason
    ok, reason = bc.hardware_gate({}, a)
    assert not ok and "SKIP" in reason and "lacks" in reason
    # an EMPTY platform block is as unknown as a missing one: two equal
    # empty blocks must skip, never grade perf on unidentified hardware
    ok, reason = bc.hardware_gate({"platform": {}}, {"platform": {}})
    assert not ok and "SKIP" in reason
    # the train guard's two-field form
    t = {"platform": "cpu", "device_kind": "cpu"}
    ok, _ = bc.hardware_gate(t, dict(t), fields=("platform", "device_kind"))
    assert ok
    ok, reason = bc.hardware_gate(
        t, {"platform": "tpu", "device_kind": "v5e"},
        fields=("platform", "device_kind"), what="timing not comparable",
    )
    assert not ok and "timing not comparable" in reason


def test_correctness_gate_requires_metric_and_platform():
    bc = _bench_common()
    base = {"metric": "m", "platform": {"backend": "cpu"}}
    assert bc.correctness_gate(base, dict(base))
    assert not bc.correctness_gate({"metric": "other",
                                    "platform": base["platform"]}, base)
    assert not bc.correctness_gate({"metric": "m"}, base)
    assert not bc.correctness_gate(
        base, {"metric": "m", "platform": {"backend": "tpu"}}
    )


def test_provenance_gate():
    bc = _bench_common()
    ok, reason = bc.provenance_gate({"provenance": "measured"},
                                    {"provenance": "measured"})
    assert ok and reason is None
    ok, reason = bc.provenance_gate({"provenance": "measured"},
                                    {"provenance": "projected_v5e"})
    assert not ok and "provenance" in reason


# --------------------------------------------- workload spec resolution


def test_workload_spec_resolution_and_hash(tmp_path):
    loadgen = _file_module("serve_loadgen", REPO / "scripts" / "serve_loadgen.py")
    spec_path = REPO / "configs" / "workloads" / "tune_serve.json"
    args1 = loadgen.parse_args(["--workload", str(spec_path)])
    name1, spec1, hash1 = loadgen.resolve_workload(args1)
    args2 = loadgen.parse_args(["--workload", str(spec_path),
                                "--requests", "99"])
    name2, spec2, hash2 = loadgen.resolve_workload(args2)
    # the spec file is the frozen source of truth: the CLI's --requests is
    # overwritten by the file, so the resolved workloads are identical
    assert name1 == name2 == "tune_serve_v1"
    assert spec1 == spec2 and hash1 == hash2
    assert args2.requests == spec1["requests"]
    # the resolved request mix replays byte-identically
    reqs1 = loadgen.make_requests(args1, 256, spec1["cache_len"])
    reqs2 = loadgen.make_requests(args2, 256, spec2["cache_len"])
    assert reqs1 == reqs2 and len(reqs1) == spec1["requests"]
    # a different workload hashes differently
    other = dict(spec1, max_new_tokens=spec1["max_new_tokens"] + 1)
    assert at.workload_hash(other) != hash1
    # shared-prefix traffic derives its prefix from the prefill chunk, so
    # there the chunk is part of the workload identity: different chunks
    # must never carry the same hash
    sp8 = loadgen.parse_args(["--shared-prefix", "--prefill-chunk", "8"])
    sp16 = loadgen.parse_args(["--shared-prefix", "--prefill-chunk", "16"])
    assert loadgen.resolve_workload(sp8)[2] != loadgen.resolve_workload(sp16)[2]
    # unknown keys are an error, not silently different traffic
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"name": "x", "reqests": 4}))
    args3 = loadgen.parse_args(["--workload", str(bad)])
    with pytest.raises(SystemExit, match="unknown keys"):
        loadgen.resolve_workload(args3)
    # the committed TUNE_serve.json was tuned under the committed spec
    tune_path = REPO / "TUNE_serve.json"
    if tune_path.exists():
        art = json.loads(tune_path.read_text())
        assert art["workload_hash"] == hash1


# ---------------------------------------------------- analytic memory


def test_analytic_memory_is_machine_readable_and_schedule_aware():
    from zero_transformer_tpu.analysis.memory import (
        analytic_memory,
        pp_stash_ticks,
    )

    cfg = Config()
    base = analytic_memory(cfg, n_devices=8)
    assert base["exact"] is False and base["provenance"] == "analytic"
    assert base["peak_bytes_est"] > base["per_device_state_bytes_est"] > 0
    # ZeRO-3 shards params 8x vs stage 0
    z0 = analytic_memory(
        apply_dotted_overrides(cfg, {"mesh.zero_stage": 0}), n_devices=8
    )
    z3 = analytic_memory(
        apply_dotted_overrides(cfg, {"mesh.zero_stage": 3}), n_devices=8
    )
    assert z3["per_device_params_bytes"] * 8 == z0["per_device_params_bytes"]
    assert z3["per_device_opt_state_bytes"] < z0["per_device_opt_state_bytes"]
    # the overlap gather buffer only appears with overlap_comm
    ov = analytic_memory(
        apply_dotted_overrides(cfg, {"mesh.overlap_comm": True}), n_devices=8
    )
    assert ov["overlap_gather_buffer_bytes_est"] > 0
    assert "overlap_gather_buffer_bytes_est" not in base
    # the stash formula table is the trainer's (one source of truth)
    assert pp_stash_ticks("gpipe", 8, 4, 1) == 11
    assert pp_stash_ticks("1f1b", 8, 4, 1) == 8
    assert pp_stash_ticks("interleaved", 8, 4, 2) == 19


def test_analytic_memory_cli_json(capsys):
    from zero_transformer_tpu.analysis.memory import main

    main(["--cfg", str(REPO / "configs" / "train_test.yaml"),
          "--set", "mesh.zero_stage=2", "--devices", "8", "--json"])
    out = json.loads(capsys.readouterr().out.strip())
    assert out["zero_stage"] == 2 and out["n_devices"] == 8
    assert out["peak_bytes_est"] > 0


# ------------------------------------------------- end-to-end smoke lane


@pytest.mark.slow
def test_tune_smoke_end_to_end(tmp_path):
    """make tune-smoke in-process: tiny space, 2 measured trials, schema +
    determinism (same winner and trace fingerprint across two passes).
    Slow lane: it runs real engine trials; tier-1 pins the mechanics and
    the committed-artifact schema above."""
    tuner = _file_module("autotune_script", REPO / "scripts" / "autotune.py")
    out = tmp_path / "TUNE_smoke.json"
    artifact = tuner.main([
        "--target", "serve", "--smoke", "--reruns", "2",
        "--out", str(out),
    ])
    on_disk = json.loads(out.read_text())
    assert on_disk == artifact
    missing = at.TUNE_REQUIRED_KEYS - artifact.keys()
    assert not missing, sorted(missing)
    assert artifact["determinism"]["winner_stable"] is True
    assert artifact["determinism"]["fingerprints_equal"] is True
    assert artifact["pruning"]["enumerated"] == 4
    assert artifact["pruning"]["pruned_frac"] >= 0.5
    # the winner's final-rung trial was byte-verified against generate()
    assert artifact["winner"]["metrics"]["mismatches"] == 0

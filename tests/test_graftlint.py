"""graftlint: the repo's invariant analyzer + runtime sanitizers.

Four layers under test:

- **static rules** against the fixture corpus (``tests/fixtures/graftlint/``):
  every rule has a minimal true-positive snippet and a clean twin;
- **suppression audit**: a reasoned ``allow`` suppresses and is listed, a
  reasonless one is itself a finding, a stale one is a finding;
- **tree cleanliness** (tier-1): the analyzer over ``zero_transformer_tpu/``
  and ``scripts/`` must report zero unsuppressed findings — regressions of
  any hard-won invariant fail the suite here;
- **spec checker + compile-family sanitizer**: hand-seeded bad
  ``ShardingPlan`` rejected with precise messages; labeled dispatch sites
  trip on signature-family overflow and stay within bounds over a real
  serving run.

The static-rule tests load ``analysis/static_rules.py`` directly by file
path — the lint lane must work (and stay fast) with no jax import.
"""
import ast
import importlib.util
import sys
import warnings
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).parent / "fixtures" / "graftlint"


def _load_static_rules():
    path = REPO / "zero_transformer_tpu" / "analysis" / "static_rules.py"
    spec = importlib.util.spec_from_file_location("graftlint_static_t", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


SR = _load_static_rules()

# (rule, fixture stem): each *_bad.py must trigger exactly this rule and
# nothing else; each *_ok.py must be perfectly clean
RULE_FIXTURES = [
    ("donation-safety", "donation_safety"),
    ("host-sync-in-hot-path", "host_sync"),
    ("wall-clock-in-span-path", "wall_clock"),
    ("broad-except-in-supervised-seam", "broad_except"),
    ("lock-held-device-sync", "lock_sync"),
    ("sharding-spec", "sharding_spec"),
]


# ------------------------------------------------------------ rule fixtures


@pytest.mark.parametrize("rule,stem", RULE_FIXTURES)
def test_rule_true_positive(rule, stem):
    findings = SR.analyze_file(FIXTURES / f"{stem}_bad.py")
    assert findings, f"{stem}_bad.py must trigger {rule}"
    assert {f.rule for f in findings} == {rule}
    assert all(not f.suppressed for f in findings)


@pytest.mark.parametrize("rule,stem", RULE_FIXTURES)
def test_rule_true_negative(rule, stem):
    findings = SR.analyze_file(FIXTURES / f"{stem}_ok.py")
    assert findings == [], [f.format() for f in findings]


def test_duplicate_axis_in_partition_spec_flagged():
    src = (
        "from jax.sharding import PartitionSpec as P\n"
        'SPEC = P("data", "data")\n'
    )
    msgs = [f.message for f in SR.analyze_source(src)]
    assert any("twice" in m for m in msgs), msgs


def test_local_probe_mesh_axes_are_legal():
    """A module constructing its own Mesh may use those axis names (the
    pod_check 1-D probe-mesh pattern) without tripping sharding-spec."""
    src = (
        "from jax.sharding import Mesh, PartitionSpec as P\n"
        "def probe(devices):\n"
        '    mesh = Mesh(devices, ("all",))\n'
        '    return mesh, P("all")\n'
    )
    assert SR.analyze_source(src) == []


def test_donation_safety_flags_unsealed_return():
    """A function handing restored/device_put buffers to its CALLERS is
    flagged too — the donation may happen a module away."""
    src = (
        "import jax\n"
        "def load(params, shardings):\n"
        "    return jax.device_put(params, shardings)\n"
    )
    findings = SR.analyze_source(src)
    assert [f.rule for f in findings] == ["donation-safety"]


def test_donation_safety_reassignment_clears_taint():
    """Statement order matters: sealing the SAME name must clear it."""
    src = (
        "import jax\n"
        "from zero_transformer_tpu.utils.jax_compat import ensure_donatable\n"
        "def load(params, shardings):\n"
        "    params = jax.device_put(params, shardings)\n"
        "    params = ensure_donatable(params)\n"
        "    return params\n"
    )
    assert SR.analyze_source(src) == []


# ------------------------------------- control-plane except rule (path-scoped)
# No flat fixture pair for this rule: it fires only when the module PATH is
# in a control-plane location, so the fixtures are inline sources analyzed
# under explicit in-scope / out-of-scope paths.

IN_SCOPE = "zero_transformer_tpu/training/fleet.py"


def test_control_plane_bare_except_flagged():
    src = (
        "def sweep(self):\n"
        "    try:\n"
        "        self._relayout()\n"
        "    except:\n"
        "        pass\n"
    )
    findings = SR.analyze_source(src, path=IN_SCOPE)
    assert [f.rule for f in findings] == ["swallowed-except-in-control-plane"]
    assert "bare 'except:'" in findings[0].message


@pytest.mark.parametrize("exc", ["Exception", "BaseException"])
@pytest.mark.parametrize("body", ["pass", "continue", "..."])
def test_control_plane_swallow_only_broad_except_flagged(exc, body):
    src = (
        "def hb_loop(self):\n"
        "    while True:\n"
        "        try:\n"
        "            self.post()\n"
        f"        except {exc}:\n"
        f"            {body}\n"
    )
    findings = SR.analyze_source(src, path=IN_SCOPE)
    assert [f.rule for f in findings] == ["swallowed-except-in-control-plane"]
    assert "swallows the failure" in findings[0].message


def test_control_plane_observing_broad_except_clean():
    """Control loops legitimately outlive individual failures — a broad
    except that LOGS (or otherwise acts) is the sanctioned shape."""
    src = (
        "def hb_loop(self):\n"
        "    try:\n"
        "        self.post()\n"
        "    except Exception:\n"
        "        log.exception('heartbeat post failed; retrying')\n"
    )
    assert SR.analyze_source(src, path=IN_SCOPE) == []


def test_control_plane_narrow_except_pass_clean():
    """Swallowing a NAMED exception is a deliberate, reviewable choice —
    only the catch-everything shapes are flagged."""
    src = (
        "def poll(self):\n"
        "    try:\n"
        "        self.q.get_nowait()\n"
        "    except KeyError:\n"
        "        pass\n"
    )
    assert SR.analyze_source(src, path=IN_SCOPE) == []


@pytest.mark.parametrize(
    "path",
    [
        "zero_transformer_tpu/resilience/supervisor.py",
        "zero_transformer_tpu/training/fleet.py",
        "zero_transformer_tpu/serving/router.py",
        "scripts/train_coordinator.py",
        "scripts/train_fleet_worker.py",
        "scripts/serve_router.py",
    ],
)
def test_control_plane_scope_covers_all_declared_paths(path):
    src = "try:\n    go()\nexcept:\n    pass\n"
    findings = SR.analyze_source(src, path=path)
    assert [f.rule for f in findings] == ["swallowed-except-in-control-plane"]


def test_control_plane_rule_ignores_out_of_scope_paths():
    """Data-plane / model code is governed by the opt-in supervised-seam
    rule, not this one — the same source outside the scope list is clean."""
    src = "try:\n    go()\nexcept Exception:\n    pass\n"
    for path in (
        "zero_transformer_tpu/model/attention.py",
        "zero_transformer_tpu/training/loop.py",
        "tests/test_fleet_train.py",
    ):
        assert SR.analyze_source(src, path=path) == [], path


def test_control_plane_suppressible_with_reason():
    src = (
        "def drain(self):\n"
        "    try:\n"
        "        self.sock.close()\n"
        "    # graftlint: allow[swallowed-except-in-control-plane] reason=best-effort close on teardown\n"
        "    except Exception:\n"
        "        pass\n"
    )
    (f,) = SR.analyze_source(src, path=IN_SCOPE)
    assert f.suppressed and f.reason == "best-effort close on teardown"


# ------------------------------------------------------- suppression audit


def test_suppression_with_reason_silences_and_is_audited():
    src = (
        "import time\n"
        "def stamp():\n"
        "    # graftlint: allow[wall-clock-in-span-path] reason=unix stamp for humans\n"
        "    return time.time()\n"
    )
    (f,) = SR.analyze_source(src)
    assert f.suppressed and f.reason == "unix stamp for humans"


def test_suppression_without_reason_is_itself_a_finding():
    src = (
        "import time\n"
        "def stamp():\n"
        "    # graftlint: allow[wall-clock-in-span-path]\n"
        "    return time.time()\n"
    )
    rules = sorted(f.rule for f in SR.analyze_source(src))
    # the original finding stays ACTIVE and the naked allow is flagged
    assert rules == ["suppression-missing-reason", "wall-clock-in-span-path"]
    assert all(not f.suppressed for f in SR.analyze_source(src))


def test_stale_suppression_is_a_finding():
    src = (
        "def f():\n"
        "    # graftlint: allow[wall-clock-in-span-path] reason=nothing here anymore\n"
        "    return 1\n"
    )
    (f,) = SR.analyze_source(src)
    assert f.rule == "unused-suppression"
    assert "matched no finding" in f.message


def test_unknown_rule_in_allow_is_a_finding():
    src = (
        "def f():\n"
        "    # graftlint: allow[no-such-rule] reason=typo\n"
        "    return 1\n"
    )
    (f,) = SR.analyze_source(src)
    assert f.rule == "unused-suppression"
    assert "unknown rule" in f.message


def test_single_rule_run_does_not_stale_other_allows():
    """--rule invocations must not call another rule's allow stale."""
    src = (
        "import time\n"
        "def stamp():\n"
        "    # graftlint: allow[wall-clock-in-span-path] reason=unix stamp\n"
        "    return time.time()\n"
    )
    findings = SR.analyze_source(src, rules=["donation-safety"])
    assert findings == []


# ------------------------------------------------------- whole-tree lane


def test_tree_is_clean():
    """Tier-1 gate: zero unsuppressed findings over the whole tree. A
    failure here means a PR reintroduced one of the invariants each rule
    encodes — fix it or suppress WITH a reason that survives review."""
    paths = [
        REPO / "zero_transformer_tpu",
        REPO / "scripts",
        REPO / "train.py",
        REPO / "bench.py",
    ]
    axes = SR.refresh_mesh_axes(REPO)
    findings = SR.analyze_paths(
        [p for p in paths if p.exists()], mesh_axes=axes
    )
    active = [f for f in findings if not f.suppressed]
    assert not active, "\n".join(f.format() for f in active)


def test_mesh_axes_derive_from_mesh_py():
    """The CLI re-derives the axis universe from parallel/mesh.py's
    ``*_AXIS`` constants; the built-in fallback must agree so a renamed
    axis cannot silently stale the linter."""
    assert SR.refresh_mesh_axes(REPO) == SR.MESH_AXES


def test_checkpoint_restores_are_sealed():
    """Pin for ``static_rules._TAINT_LAST`` treating CheckpointManager
    restores as CLEAN sources: every restore entry point must seal its
    product through ``ensure_donatable`` before returning. If this fails,
    either re-seal checkpoint.py or move the method names back into the
    taint set."""
    tree = ast.parse(
        (REPO / "zero_transformer_tpu" / "checkpoint.py").read_text()
    )
    cm = next(
        n
        for n in tree.body
        if isinstance(n, ast.ClassDef) and n.name == "CheckpointManager"
    )
    for name in ("restore", "restore_verified", "restore_params"):
        fn = next(
            n
            for n in ast.walk(cm)
            if isinstance(n, ast.FunctionDef) and n.name == name
        )
        sealed = any(
            isinstance(call, ast.Call)
            and (
                getattr(call.func, "id", None) == "ensure_donatable"
                or getattr(call.func, "attr", None) == "ensure_donatable"
            )
            for ret in ast.walk(fn)
            if isinstance(ret, ast.Return) and ret.value is not None
            for call in ast.walk(ret.value)
        )
        assert sealed, (
            f"CheckpointManager.{name} no longer seals its product through "
            "ensure_donatable — donation-safety's taint exclusions are stale"
        )


# ------------------------------------------------------------ spec checker


def _mesh_2dev():
    import jax
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:2]), ("data",))


def test_spec_checker_rejects_hand_seeded_bad_plan():
    """Acceptance case: unknown axis + indivisible ZeRO dim, one SpecError,
    both inconsistencies named precisely."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from zero_transformer_tpu.analysis import spec_check
    from zero_transformer_tpu.parallel.zero import ShardingPlan, TrainState

    mesh = _mesh_2dev()
    repl = NamedSharding(mesh, P())
    state = TrainState(
        step=repl,
        params={
            # raw PartitionSpec leaf: NamedSharding's own constructor
            # rejects unknown axes, but a spec table/config file can
            # carry one all the way to plan time — exactly what the
            # checker must catch before compile
            "w": P("bogus"),
            "v": NamedSharding(mesh, P("data")),
        },
        opt_state={},
    )
    abstract = TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        params={
            "w": jax.ShapeDtypeStruct((4, 4), jnp.float32),
            # 3 is not divisible by data=2: the hand-seeded ragged shard
            "v": jax.ShapeDtypeStruct((3,), jnp.float32),
        },
        opt_state={},
    )
    plan = ShardingPlan(state=state, batch=repl, zero={}, logical=None)
    with pytest.raises(spec_check.SpecError) as ei:
        spec_check.check_plan(plan, mesh, abstract_state=abstract)
    msg = str(ei.value)
    assert "'bogus'" in msg and "not a mesh axis" in msg
    assert "not divisible" in msg and "size 3" in msg
    assert len(ei.value.errors) == 2


def test_spec_checker_flags_duplicate_axis():
    from jax.sharding import PartitionSpec as P

    from zero_transformer_tpu.analysis import spec_check

    errors = spec_check.check_entry_spec(
        P("data", "data"), _mesh_2dev(), "w"
    )
    assert len(errors) == 1 and "at most one dim" in errors[0]


def test_spec_checker_passes_good_plan():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from zero_transformer_tpu.analysis import spec_check
    from zero_transformer_tpu.parallel.zero import ShardingPlan, TrainState

    mesh = _mesh_2dev()
    repl = NamedSharding(mesh, P())
    state = TrainState(
        step=repl,
        params={"w": NamedSharding(mesh, P("data"))},
        opt_state={},
    )
    abstract = TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        params={"w": jax.ShapeDtypeStruct((4, 4), jnp.float32)},
        opt_state={},
    )
    plan = ShardingPlan(state=state, batch=repl, zero={}, logical=None)
    spec_check.check_plan(plan, mesh, abstract_state=abstract)  # no raise


def test_spec_checker_allow_uneven_scopes_divisibility():
    """The pipe axis may shard the stacked layer dim unevenly (GSPMD pads;
    the pipeline engine owns the "divisible" refusal) — ``allow_uneven``
    exempts exactly that axis while unknown/duplicate axes stay hard
    errors. Pins the make_plan contract test_pp_rejects_zero3_and_
    indivisible relies on: plan builds, make_train_step refuses."""
    from jax.sharding import PartitionSpec as P

    from zero_transformer_tpu.analysis import spec_check

    mesh = _mesh_2dev()
    ragged = spec_check.check_entry_spec(
        P("data"), mesh, "blocks", shape=(3, 8)
    )
    assert len(ragged) == 1 and "not divisible" in ragged[0]
    assert (
        spec_check.check_entry_spec(
            P("data"), mesh, "blocks", shape=(3, 8), allow_uneven=("data",)
        )
        == []
    )
    # the exemption is about raggedness ONLY: a bogus axis still fails
    assert spec_check.check_entry_spec(
        P("bogus"), mesh, "blocks", shape=(3, 8), allow_uneven=("bogus",)
    )


def test_spec_checker_mixed_axis_dim_stays_strict():
    """A dim sharded by an allowed-uneven axis AND a strict (ZeRO) axis is
    still checked at the full world: _add_zero_axis only adds the ZeRO
    axis when the whole product divides, so raggedness on a mixed dim
    means a hand-seeded or corrupted spec."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from zero_transformer_tpu.analysis import spec_check

    mesh = Mesh(
        np.array(jax.devices()[:4]).reshape(2, 2), ("tensor", "fsdp")
    )
    ragged = spec_check.check_entry_spec(
        P(("tensor", "fsdp")), mesh, "w", shape=(6,),
        allow_uneven=("tensor",),
    )
    assert len(ragged) == 1 and "not divisible" in ragged[0]
    # all axes allowed-uneven: exempt
    assert (
        spec_check.check_entry_spec(
            P(("tensor", "fsdp")), mesh, "w", shape=(6,),
            allow_uneven=("tensor", "fsdp"),
        )
        == []
    )


def test_make_plan_is_spec_checked(tmp_path):
    """make_plan routes every derived plan through check_plan — a poisoned
    rule table must fail at plan time with the precise message, not at
    first pjit dispatch."""
    import jax

    from zero_transformer_tpu.parallel import sharding as shd

    with pytest.raises(ValueError, match="unknown mesh axes"):
        shd.validate_rules({**shd.LOGICAL_RULES, "mlp": "tensorr"})


# ----------------------------------------------- compile-family sanitizer


class _Arr:
    """Duck-typed array stand-in: the sanitizer reads only shape/dtype."""

    def __init__(self, shape, dtype="float32", fill=0):
        self.shape = tuple(shape)
        self.dtype = dtype
        self.fill = fill  # value must NOT enter the signature


@pytest.fixture
def strict_sites():
    from zero_transformer_tpu.analysis import runtime as rt

    rt.set_strict(True)
    yield rt
    rt.set_strict(None)


def test_dispatch_site_trips_listing_offending_signatures(strict_sites):
    rt = strict_sites
    site = rt.bounded_dispatch("test.vary_shape", 1)
    site.observe(_Arr((2, 3)))
    site.observe(_Arr((2, 3), fill=7))  # same signature: values never count
    assert site.distinct == 1
    with pytest.raises(rt.CompileFamilyExceeded) as ei:
        site.observe(_Arr((2, 4)))  # the deliberately varied shape
    msg = str(ei.value)
    assert "test.vary_shape" in msg
    assert "(2, 3)" in msg and "(2, 4)" in msg  # every signature listed
    assert "NEW" in msg  # the fresh offender is marked


def test_dispatch_site_sees_through_dataclass_containers(strict_sites):
    """flax.struct-style dataclasses (TrainState) must be walked by field
    — collapsing them to their type would blind trainer.step to the very
    shapes that select the executable."""
    import dataclasses as dc

    rt = strict_sites

    @dc.dataclass
    class State:
        step: "_Arr"
        params: dict

    site = rt.bounded_dispatch("test.dataclass", 1)
    site.observe(State(_Arr(()), {"w": _Arr((4, 4))}))
    with pytest.raises(rt.CompileFamilyExceeded):
        site.observe(State(_Arr(()), {"w": _Arr((4, 8))}))


def test_dispatch_site_kwarg_values_enter_signature(strict_sites):
    """sorted(kwargs) would record key NAMES only — a per-call shape
    variation through a keyword argument must still trip the bound."""
    rt = strict_sites
    site = rt.bounded_dispatch("test.kwargs", 1)
    site.observe(x=_Arr((128,)))
    site.observe(x=_Arr((128,), fill=3))  # same signature
    with pytest.raises(rt.CompileFamilyExceeded):
        site.observe(x=_Arr((256,)))


def test_cli_rejects_unknown_rule_names():
    """A typo'd --rule must not run zero rules and exit 0 'clean'."""
    cli_path = REPO / "scripts" / "graftlint.py"
    spec = importlib.util.spec_from_file_location("graftlint_cli_t", cli_path)
    cli = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = cli
    spec.loader.exec_module(cli)
    assert cli.main(["--rule", "donation_safety"]) == 2  # underscore typo
    assert (
        cli.main(["--rule", "wall-clock-in-span-path", "zero_transformer_tpu/obs"])
        == 0
    )


def test_dispatch_site_statics_select_executables(strict_sites):
    rt = strict_sites
    site = rt.bounded_dispatch("test.vary_static", 1)
    site.observe(_Arr((2, 3)), 16)
    with pytest.raises(rt.CompileFamilyExceeded):
        site.observe(_Arr((2, 3)), 32)  # static arg value varies the family


def test_dispatch_site_warns_once_outside_strict():
    from zero_transformer_tpu.analysis import runtime as rt

    rt.set_strict(False)
    try:
        site = rt.bounded_dispatch("test.warn", 1)
        site.observe(_Arr((1,)))
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            site.observe(_Arr((2,)))
            site.observe(_Arr((3,)))
        assert len(w) == 1  # warned once, not per overflow
        assert site.violations == 2  # every overflow still counted
    finally:
        rt.set_strict(None)


def test_dispatch_site_wrap_instruments_callable(strict_sites):
    rt = strict_sites
    site = rt.bounded_dispatch("test.wrap", 1)
    fn = site.wrap(lambda x: x.shape)
    assert fn(_Arr((4, 4))) == (4, 4)
    with pytest.raises(rt.CompileFamilyExceeded):
        fn(_Arr((4, 5)))


def test_engine_dispatch_sites_stay_within_bounds(strict_sites):
    """Serving parity run under strict sanitizers: chunked prefill +
    decode over interleaved admissions must keep every instrumented site
    at ONE signature — the fixed-shape discipline, machine-checked."""
    import jax
    import jax.numpy as jnp

    from zero_transformer_tpu.config import model_config
    from zero_transformer_tpu.inference.sampling import SamplingConfig
    from zero_transformer_tpu.models import Transformer
    from zero_transformer_tpu.serving import ServingEngine

    cfg = model_config("test", dropout=0.0, compute_dtype="float32")
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]
    engine = ServingEngine(
        cfg,
        params,
        n_slots=2,
        cache_len=32,
        prefill_chunk=8,
        page_size=8,
        sampling=SamplingConfig(temperature=0.9, top_k=20),
    )
    first = [
        engine.submit([3, 7, 11], max_new_tokens=6, seed=0),
        engine.submit([5, 9], max_new_tokens=6, seed=1),
    ]
    for _ in range(3):
        engine.step()
    late = [engine.submit([2, 4, 6, 8], max_new_tokens=6, seed=2)]
    engine.run_until_idle()
    for h in first + late:
        assert h.status == "done"
    sites = {
        s.name: s.snapshot()
        for s in (engine._ds_decode, engine._ds_prefill, engine._ds_spec)
    }
    # a strict-mode trip would have raised mid-run; assert the positive too
    for name, snap in sites.items():
        assert snap["violations"] == 0, (name, snap)
        assert snap["distinct"] <= snap["max_entries"], (name, snap)
    assert sites["engine.decode_step"]["calls"] > 0
    assert sites["engine.decode_step"]["distinct"] == 1
    assert sites["engine.prefill_chunk"]["distinct"] == 1
    # a strict trip must ESCAPE the engine's supervised tick handler (not
    # be classified as a tick fault and fed to the breaker): reset the
    # decode site and poison it with a foreign signature so the next real
    # tick's (now-fresh) signature overflows the bound
    engine._ds_decode.reset()
    engine._ds_decode.signatures[("poison",)] = 1
    engine.submit([1, 2], max_new_tokens=2, seed=3)
    with pytest.raises(strict_sites.CompileFamilyExceeded):
        engine.run_until_idle()
    assert not engine._breaker.open

"""Chaos-injection tests for the fault-tolerance subsystem.

Each test injects one of the failure modes a preemptible pod run actually
hits — a divergent (NaN) step, a loader IO error, SIGTERM preemption, a
failing checkpoint write, a hung step — and asserts the run recovers
WITHOUT a human: the supervised/guarded run reaches the same step count as
an undisturbed run, with finite loss. The deterministic fast cases are
unmarked (tier-1 exercises supervisor/anomaly/watchdog logic on CPU); the
heavier end-to-end scenarios carry the ``chaos`` marker (``make chaos``).
"""
import dataclasses

import numpy as np
import pytest

import jax

from zero_transformer_tpu.config import (
    CheckpointConfig,
    Config,
    DataConfig,
    MeshConfig,
    ModelConfig,
    OptimizerConfig,
    ResilienceConfig,
    TrainingConfig,
)
from zero_transformer_tpu.resilience import (
    AnomalyHalt,
    ChaosMonkey,
    Fault,
    HangError,
    RetryableError,
    Supervisor,
    Watchdog,
    backoff_delay,
    classify,
)
from zero_transformer_tpu.resilience.watchdog import dump_stacks
from zero_transformer_tpu.training.trainer import Trainer


def tiny_config(tmp_path, total_steps=12, resilience=None, log_frequency=2,
                save_frequency=4, **ckpt_kwargs) -> Config:
    return Config(
        model=ModelConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                          max_seq_len=16, dropout=0.0),
        mesh=MeshConfig(),
        optimizer=OptimizerConfig(peak_learning_rate=1e-2, warmup_steps=2,
                                  total_steps=total_steps),
        training=TrainingConfig(batch_size=8, train_context=16,
                                total_steps=total_steps,
                                evaluation_frequency=0,
                                log_frequency=log_frequency, seed=0),
        data=DataConfig(source="synthetic", max_context=16),
        checkpoint=CheckpointConfig(directory=str(tmp_path / "run"),
                                    save_frequency=save_frequency,
                                    async_save=False, **ckpt_kwargs),
        resilience=resilience or ResilienceConfig(),
    )


def params_equal(a, b, rtol=1e-5, atol=1e-7):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=rtol,
                                   atol=atol)


def all_finite(tree) -> bool:
    return all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(tree))


def run_undisturbed(tmp_path, total_steps=12):
    cfg = tiny_config(tmp_path / "clean", total_steps=total_steps)
    t = Trainer(cfg)
    state = t.train()
    t.close()
    return state


def supervise(tmp_path, chaos, total_steps=12, resilience=None, **cfg_kwargs):
    """Supervised run with one ChaosMonkey shared across restarts."""
    cfg = tiny_config(tmp_path / "chaotic", total_steps=total_steps,
                      resilience=resilience, **cfg_kwargs)
    sleeps = []
    sup = Supervisor(
        cfg,
        trainer_factory=lambda c: Trainer(c, chaos=chaos),
        sleep_fn=sleeps.append,
    )
    state = sup.run()
    return state, sup, sleeps


# -- exception classification (pure logic) ----------------------------------


def test_classify_failure_classes():
    assert classify(RetryableError("x")) == "retryable"
    assert classify(HangError("x")) == "retryable"
    assert classify(OSError("disk detached")) == "retryable"
    assert classify(ConnectionResetError("peer")) == "retryable"
    assert classify(TimeoutError()) == "retryable"
    # XLA/storage fingerprints in foreign exception text
    assert classify(RuntimeError("RESOURCE_EXHAUSTED: hbm oom")) == "retryable"
    assert classify(RuntimeError("UNAVAILABLE: socket closed")) == "retryable"
    # config/shape/user errors restart cannot fix
    assert classify(ValueError("d_model must divide")) == "fatal"
    assert classify(TypeError("bad arg")) == "fatal"
    assert classify(FileNotFoundError("no such config")) == "fatal"
    assert classify(AnomalyHalt("diverged")) == "fatal"
    assert classify(KeyboardInterrupt()) == "fatal"
    # unknown bugs default fatal: a blind restart loop is not recovery
    assert classify(RuntimeError("some novel crash")) == "fatal"


def test_classify_walks_cause_chain_explicit_raise_from():
    """A RetryableError re-raised under a fatal-looking wrapper type must
    classify by the innermost cause: the loader raising ``ValueError(...)
    from RetryableError`` is still a transient IO failure underneath."""
    try:
        try:
            raise RetryableError("shard read reset")
        except RetryableError as inner:
            raise ValueError("while decoding shard 7") from inner
    except ValueError as exc:
        wrapped = exc
    assert classify(wrapped) == "retryable"


def test_classify_walks_cause_chain_implicit_context():
    """Same honor for the implicit ``__context__`` chain — an exception
    raised INSIDE an ``except RetryableError:`` block carries the original
    as context, not cause."""
    try:
        try:
            raise RetryableError("watchdog abort")
        except RetryableError:
            raise KeyError("cleanup lookup failed")
    except KeyError as exc:
        wrapped = exc
    assert wrapped.__cause__ is None and wrapped.__context__ is not None
    assert classify(wrapped) == "retryable"


def test_classify_retryable_wrapping_fatal_stays_retryable():
    # reversed nesting order: the outermost exception IS a RetryableError,
    # whatever it wrapped
    try:
        try:
            raise ValueError("bad shape deep down")
        except ValueError as inner:
            raise RetryableError("transient wrapper") from inner
    except RetryableError as exc:
        wrapped = exc
    assert classify(wrapped) == "retryable"


def test_classify_user_interrupt_beats_cause_chain():
    """Ctrl-C wins even when a RetryableError sits underneath: the user
    asked the run to die, the supervisor must not resurrect it."""
    ki = KeyboardInterrupt()
    ki.__cause__ = RetryableError("mid-retry when interrupted")
    assert classify(ki) == "fatal"


def test_classify_cause_cycle_terminates():
    a = RuntimeError("a")
    b = RuntimeError("b")
    a.__cause__, b.__cause__ = b, a
    assert classify(a) == "fatal"  # and, crucially, it returns at all


# -- backoff jitter (satellite of the fleet supervisor) ----------------------


def test_backoff_delay_pinned_to_jitter_window():
    """The jittered delay is PINNED inside [base*2^(k-1)*(1-j), ...*(1+j)]:
    rng extremes map exactly onto the window edges, the midpoint is the
    undithered exponential value, and the cap applies before jitter."""
    for attempt, nominal in [(1, 1.0), (2, 2.0), (3, 4.0), (10, 60.0)]:
        lo = backoff_delay(1.0, 60.0, attempt, jitter=0.25, rng=lambda: 0.0)
        mid = backoff_delay(1.0, 60.0, attempt, jitter=0.25, rng=lambda: 0.5)
        hi = backoff_delay(1.0, 60.0, attempt, jitter=0.25, rng=lambda: 1.0)
        assert mid == pytest.approx(nominal)
        assert lo == pytest.approx(nominal * 0.75)
        assert hi == pytest.approx(nominal * 1.25)
    # jitter=0 degenerates to the old deterministic schedule
    assert backoff_delay(0.01, 1.0, 2, jitter=0.0) == pytest.approx(0.02)
    # sampled delays stay inside the window and actually spread
    import random as _random

    rng = _random.Random(7).random
    samples = [
        backoff_delay(1.0, 60.0, 1, jitter=0.1, rng=rng) for _ in range(64)
    ]
    assert all(0.9 <= s <= 1.1 for s in samples)
    assert len({round(s, 6) for s in samples}) > 10  # not secretly constant


def test_config_backoff_jitter_validation():
    with pytest.raises(ValueError, match="backoff_jitter"):
        ResilienceConfig(backoff_jitter=1.0)
    with pytest.raises(ValueError, match="backoff_jitter"):
        ResilienceConfig(backoff_jitter=-0.1)
    ResilienceConfig(backoff_jitter=0.0)  # edges that must remain legal
    ResilienceConfig(backoff_jitter=0.999)


def test_config_resilience_block_validation():
    with pytest.raises(ValueError, match="anomaly_response"):
        ResilienceConfig(anomaly_response="retry")
    with pytest.raises(ValueError, match="ema_decay"):
        ResilienceConfig(ema_decay=1.5)
    ResilienceConfig(anomaly_response="rollback", loss_spike_factor=3.0)


# -- anomaly guard ----------------------------------------------------------


def test_nan_step_skipped_run_matches_undisturbed_step_count(tmp_path, devices):
    """A NaN step under 'skip_batch' is dropped in-graph; the run completes
    to the SAME step count as an undisturbed run with finite loss/params —
    the end-state parity contract for fault injection."""
    clean = run_undisturbed(tmp_path, total_steps=12)
    chaos = ChaosMonkey([Fault(kind="nan_step", step=4, duration=2)])
    cfg = tiny_config(
        tmp_path / "chaotic", total_steps=12,
        resilience=ResilienceConfig(anomaly_response="skip_batch"),
    )
    t = Trainer(cfg, chaos=chaos)
    state = t.train()
    assert int(state.step) == int(clean.step) == 12
    assert t.resilience_report["anomalies"] == 2
    assert all_finite(state.params), "guard let a NaN update land"
    assert np.isfinite(t.evaluate(state)["loss"])
    t.close()


def test_nan_at_non_log_step_detected_without_poisoning(tmp_path, devices):
    """The halt_on_nan blind spot, closed: divergence at a NON-log step is
    caught at the next log point, and because the update was dropped
    in-graph, NO further updates were poisoned in the meantime (the
    historical path poisoned up to log_frequency - 1 of them)."""
    chaos = ChaosMonkey([Fault(kind="nan_step", step=2, duration=1)])
    cfg = tiny_config(tmp_path, total_steps=12, log_frequency=5,
                      save_frequency=100)
    t = Trainer(cfg, chaos=chaos)  # default response: halt
    # the NaN hits while computing step 3; the loss fetched at the step-5
    # log point is finite again, so ONLY the in-graph carry can report it —
    # and it does, at the first log point after the fault
    with pytest.raises(AnomalyHalt, match="1 flagged step\\(s\\) by step 5"):
        t.train()
    # nothing was checkpointed: the last good checkpoint (none yet) stands
    assert t.ckpt.latest_step() is None
    t.close()


def test_rollback_restores_snapshot_and_completes(tmp_path, devices):
    """A sustained anomaly streak escalates to rollback: params/opt restore
    from the host-RAM snapshot, the loader continues FORWARD past the bad
    window, and the run still completes to the target step."""
    chaos = ChaosMonkey([Fault(kind="nan_step", step=4, duration=4)])
    res = ResilienceConfig(
        anomaly_response="rollback", rollback_after=2, max_rollbacks=5,
        snapshot_frequency=2,
    )
    cfg = tiny_config(tmp_path, total_steps=14, resilience=res,
                      log_frequency=2)
    t = Trainer(cfg, chaos=chaos)
    state = t.train()
    assert int(state.step) == 14
    assert t.resilience_report["rollbacks"] >= 1
    assert t.resilience_report["anomalies"] >= 2
    assert all_finite(state.params)
    assert np.isfinite(t.evaluate(state)["loss"])
    t.close()
    # the rollback landed in the metrics timeline as a tagged event
    import json

    lines = [json.loads(l) for l in
             (tmp_path / "run" / "metrics.jsonl").read_text().splitlines()]
    events = [l for l in lines if l.get("event") == "anomaly_rollback"]
    assert events and events[0]["to_step"] <= events[0]["step"]


def test_rollback_budget_exhaustion_halts(tmp_path, devices):
    """A divergence that persists through every rollback must eventually
    halt (needs a human), not burn the pod in a rollback loop."""
    chaos = ChaosMonkey([Fault(kind="nan_step", step=2, duration=1000)])
    res = ResilienceConfig(
        anomaly_response="rollback", rollback_after=1, max_rollbacks=2,
        snapshot_frequency=1,
    )
    cfg = tiny_config(tmp_path, total_steps=50, resilience=res,
                      log_frequency=1, save_frequency=1000)
    t = Trainer(cfg, chaos=chaos)
    with pytest.raises(AnomalyHalt, match="rollback budget exhausted"):
        t.train()
    t.close()


def test_skip_batch_streak_limit_halts(tmp_path, devices):
    """skip_batch cannot spin forever on an all-anomalous stream."""
    chaos = ChaosMonkey([Fault(kind="nan_step", step=0, duration=1000)])
    res = ResilienceConfig(anomaly_response="skip_batch",
                           max_consecutive_anomalies=4)
    cfg = tiny_config(tmp_path, total_steps=50, resilience=res,
                      log_frequency=2, save_frequency=1000)
    t = Trainer(cfg, chaos=chaos)
    with pytest.raises(AnomalyHalt, match="consecutive"):
        t.train()
    t.close()


def test_guard_adds_no_per_step_host_sync(tmp_path, devices):
    """The acceptance bound: on the non-logging path the guarded step makes
    ZERO device→host transfers. Asserted directly — several guarded steps
    run under jax's transfer guard with device→host set to disallow; any
    implicit fetch (what a host-side NaN check would need) raises."""
    cfg = tiny_config(tmp_path, total_steps=8)
    t = Trainer(cfg)
    state = t.init_state()
    guard, step_fn = t._guarded_step()
    carry = guard.init_carry()
    batch_np = np.zeros((1, 8, 16), np.int32)
    with jax.transfer_guard_device_to_host("disallow"):
        for _ in range(3):
            batch = jax.device_put(batch_np, t.batch_sharding)
            state, metrics, carry = step_fn(state, batch, t.rng, carry)
    # ... and the carry DOES carry the information once the host asks
    stats = guard.read(carry)
    assert stats.count == 0
    t.close()


def test_guard_trajectory_matches_unguarded(tmp_path, devices):
    """With no anomalies the guard is a semantic no-op: the select picks
    every new state, so params after N steps match a detection-off run
    (up to compile-level reassociation — the guard inlines the step into a
    larger XLA program, which reorders fusions by a few ulps)."""
    cfg_on = tiny_config(tmp_path / "on", total_steps=6)
    cfg_off = dataclasses.replace(
        tiny_config(tmp_path / "off", total_steps=6),
        resilience=ResilienceConfig(anomaly_detection=False),
    )
    t_on, t_off = Trainer(cfg_on), Trainer(cfg_off)
    s_on, s_off = t_on.train(), t_off.train()
    params_equal(s_on.params, s_off.params, rtol=1e-3, atol=1e-5)
    t_on.close()
    t_off.close()


# -- supervisor + chaos end-to-end ------------------------------------------


@pytest.mark.chaos
def test_loader_error_supervised_recovers(tmp_path, devices):
    """A hard loader IO error is retryable: the supervisor restarts from the
    last checkpoint and the run completes to the undisturbed step count."""
    chaos = ChaosMonkey([Fault(kind="loader_error", step=6, exc=OSError)])
    state, sup, sleeps = supervise(tmp_path, chaos, total_steps=12,
                                   save_frequency=4)
    assert int(state.step) == 12
    assert len(sup.history) == 1 and "OSError" in sup.history[0].reason
    # one backoff sleep, inside the jitter window around the base delay
    assert len(sleeps) == 1
    b, j = sup.res.backoff_base_s, sup.res.backoff_jitter
    assert b * (1 - j) <= sleeps[0] <= b * (1 + j)
    assert "loader_error@6" in chaos.fired_log


@pytest.mark.chaos
def test_sigterm_preemption_supervised_parity(tmp_path, devices):
    """Simulated preemption: SIGTERM mid-train → force-save → supervised
    resume reproduces the SAME final params as an uninterrupted run (the
    loader position and per-step rng are both checkpoint-derived, so the
    trajectory is identical — not just the step count)."""
    clean = run_undisturbed(tmp_path, total_steps=12)
    chaos = ChaosMonkey([Fault(kind="sigterm", step=5)])
    state, sup, _ = supervise(tmp_path, chaos, total_steps=12)
    assert int(state.step) == int(clean.step) == 12
    assert len(sup.history) == 1 and "preempted" in sup.history[0].reason
    params_equal(clean.params, state.params)


@pytest.mark.chaos
def test_checkpoint_write_failure_supervised_recovers(tmp_path, devices):
    """A failed checkpoint write surfaces at the save tick (not hours later)
    and is retryable; the rerun completes."""
    chaos = ChaosMonkey([Fault(kind="ckpt_fail", step=4, exc=OSError)])
    state, sup, _ = supervise(tmp_path, chaos, total_steps=12,
                              save_frequency=4)
    assert int(state.step) == 12
    assert len(sup.history) == 1 and "OSError" in sup.history[0].reason


@pytest.mark.chaos
def test_slow_checkpoint_write_still_completes(tmp_path, devices):
    """A slow (but succeeding) save is not a failure: no restart, run done."""
    chaos = ChaosMonkey([Fault(kind="ckpt_slow", step=4, duration=1.0)])
    state, sup, sleeps = supervise(tmp_path, chaos, total_steps=8,
                                   save_frequency=4)
    assert int(state.step) == 8
    assert sup.history == [] and sleeps == []


@pytest.mark.chaos
def test_hung_step_watchdog_aborts_and_supervisor_recovers(tmp_path, devices):
    """A hung step trips the watchdog (stack dump + force-save + retryable
    abort); the supervisor restarts from the force-saved checkpoint and the
    run completes to the target step."""
    chaos = ChaosMonkey([Fault(kind="hang", step=3, duration=120.0)])
    res = ResilienceConfig(watchdog_timeout_s=3.0)
    state, sup, sleeps = supervise(tmp_path, chaos, total_steps=8,
                                   resilience=res, save_frequency=100)
    assert int(state.step) == 8
    assert len(sup.history) == 1 and "HangError" in sup.history[0].reason
    # the watchdog force-saved at the hang point, so the restart resumed
    # from step 3, not from scratch
    assert sup.history[0].step == 3


@pytest.mark.chaos
def test_supervisor_max_steps_is_a_run_budget_not_per_attempt(tmp_path, devices):
    """--supervise --max-steps N must stop at N total even across restarts:
    a retry gets only the REMAINING budget, not a fresh one."""
    chaos = ChaosMonkey([Fault(kind="sigterm", step=5)])
    cfg = tiny_config(tmp_path / "budget", total_steps=100)
    sup = Supervisor(
        cfg,
        trainer_factory=lambda c: Trainer(c, chaos=chaos),
        sleep_fn=lambda s: None,
    )
    state = sup.run(max_steps=12)
    assert int(state.step) == 12  # not 5 + 12


def test_supervisor_fatal_error_propagates(tmp_path, devices):
    """Config/shape errors must NOT be retried."""
    cfg = tiny_config(tmp_path, total_steps=4)
    calls = []

    def factory(c):
        calls.append(c)
        raise ValueError("shape mismatch: d_model")

    sup = Supervisor(cfg, trainer_factory=factory, sleep_fn=lambda s: None)
    with pytest.raises(ValueError, match="shape mismatch"):
        sup.run()
    assert len(calls) == 1  # no second attempt


def test_supervisor_budget_exhaustion(tmp_path, devices):
    cfg = tiny_config(tmp_path, total_steps=4)
    cfg = dataclasses.replace(
        cfg, resilience=ResilienceConfig(max_restarts=2, backoff_base_s=0.01)
    )

    class Always:
        def __init__(self, c):
            pass

        def train(self, max_steps=None):
            raise OSError("bucket gone")

        def close(self):
            pass

    sleeps = []
    sup = Supervisor(cfg, trainer_factory=Always, sleep_fn=sleeps.append)
    with pytest.raises(RetryableError, match="restart budget exhausted"):
        sup.run()
    # exponential backoff (base, 2*base), each dithered by the jitter window
    j = sup.res.backoff_jitter
    assert len(sleeps) == 2
    assert 0.01 * (1 - j) <= sleeps[0] <= 0.01 * (1 + j)
    assert 0.02 * (1 - j) <= sleeps[1] <= 0.02 * (1 + j)


def test_supervisor_backoff_deterministic_with_seeded_rng(tmp_path, devices):
    """An injected rng makes the jittered schedule reproducible — the seam
    the fleet tests (and anyone replaying an incident) rely on."""
    cfg = tiny_config(tmp_path, total_steps=4)
    cfg = dataclasses.replace(
        cfg,
        resilience=ResilienceConfig(
            max_restarts=2, backoff_base_s=0.01, backoff_jitter=0.5
        ),
    )

    class Always:
        def __init__(self, c):
            pass

        def train(self, max_steps=None):
            raise OSError("bucket gone")

        def close(self):
            pass

    sleeps = []
    sup = Supervisor(
        cfg, trainer_factory=Always, sleep_fn=sleeps.append, rng=lambda: 1.0
    )
    with pytest.raises(RetryableError):
        sup.run()
    assert sleeps == pytest.approx([0.015, 0.03])  # top edge of each window


# -- trustworthy restore: integrity + replica-audit chaos --------------------


def _events(tmp_path, name):
    import json

    path = tmp_path / "chaotic" / "run" / "metrics.jsonl"
    if not path.exists():
        return []
    return [
        json.loads(l)
        for l in path.read_text().splitlines()
        if json.loads(l).get("event") == name
    ]


@pytest.mark.chaos
@pytest.mark.slow  # two supervised restart runs; `make chaos`/`elastic-chaos`
@pytest.mark.parametrize("kind", ["ckpt_truncate", "ckpt_bitflip"])
def test_ckpt_corruption_supervised_falls_back_and_completes(
    tmp_path, devices, kind
):
    """The acceptance scenario: the newest checkpoint is corrupted on disk
    (torn write / bit rot) AFTER a successful save; a later retryable fault
    forces a supervised restart. The restore must QUARANTINE the corrupt
    step, fall back to the previous VERIFIED step, and still reach the
    undisturbed step count with finite loss — instead of crash-looping on
    (or silently training from) the bad artifact."""
    chaos = ChaosMonkey([
        Fault(kind=kind, step=8),         # corrupts the step-8 save
        Fault(kind="loader_error", step=9, exc=OSError),  # forces a restart
    ])
    state, sup, _ = supervise(tmp_path, chaos, total_steps=12,
                              save_frequency=4)
    assert int(state.step) == 12
    assert all_finite(state.params)
    assert f"{kind}@8" in chaos.fired_log
    # the corrupt step-8 dir was quarantined; the restart resumed from 4
    run_dir = tmp_path / "chaotic" / "run"
    assert list(run_dir.glob("8.quarantined*")), list(run_dir.iterdir())
    quarantines = _events(tmp_path, "ckpt_quarantined")
    fallbacks = _events(tmp_path, "restore_fallback")
    assert quarantines and quarantines[0]["step"] == 8
    assert fallbacks and fallbacks[0]["from_step"] == 8
    assert fallbacks[0]["fallback_steps"] == 4  # 8 -> 4


@pytest.mark.chaos
@pytest.mark.slow  # full chaotic run; `make chaos`/`elastic-chaos` + nightly
def test_replica_perturb_audit_trips_within_frequency(tmp_path, devices):
    """SDC desyncs one DP replica mid-run: the in-graph audit must trip
    within audit_frequency steps and escalate per the anomaly response
    (halt), naming the failure class — not wait for the loss curves to
    fork."""
    chaos = ChaosMonkey([Fault(kind="replica_perturb", step=5)])
    res = ResilienceConfig(audit_frequency=2, anomaly_response="halt")
    cfg = tiny_config(tmp_path / "chaotic", total_steps=20, resilience=res,
                      log_frequency=2)
    t = Trainer(cfg, chaos=chaos)
    with pytest.raises(AnomalyHalt, match="cross-replica divergence") as ei:
        t.train()
    t.close()
    # perturb lands after step 5; audits run on even steps — the step-6
    # audit is the FIRST chance, and the halt surfaces at that log point
    assert "step 6" in str(ei.value)
    events = _events(tmp_path, "replica_divergence")
    assert events and events[0]["step"] == 6


@pytest.mark.chaos
@pytest.mark.slow  # full heal-and-complete run; `make chaos`/`elastic-chaos`
def test_replica_perturb_rollback_heals_and_completes(tmp_path, devices):
    """With anomaly_response=rollback the divergence is HEALED: the host
    snapshot re-replicates identical copies on every device and the run
    completes to the undisturbed step count with finite loss."""
    chaos = ChaosMonkey([Fault(kind="replica_perturb", step=5)])
    res = ResilienceConfig(audit_frequency=2, anomaly_response="rollback",
                           snapshot_frequency=2, max_rollbacks=3)
    cfg = tiny_config(tmp_path / "chaotic", total_steps=12, resilience=res,
                      log_frequency=2)
    t = Trainer(cfg, chaos=chaos)
    state = t.train()
    assert int(state.step) == 12
    assert t.resilience_report["replica_audit_failures"] == 1
    assert t.resilience_report["rollbacks"] == 1
    assert all_finite(state.params)
    assert np.isfinite(t.evaluate(state)["loss"])
    t.close()
    assert _events(tmp_path, "replica_heal_rollback")


def test_replica_audit_detects_single_device_desync(tmp_path, devices):
    """Unit: the in-graph audit distinguishes a healthy replicated state
    from one where a single device's copy differs by one bit-level change
    (the desync is invisible to everything else — XLA assumes replicated
    copies identical)."""
    from zero_transformer_tpu.parallel.zero import make_replica_audit
    from zero_transformer_tpu.resilience.chaos import perturb_one_replica

    res = ResilienceConfig(audit_frequency=2)
    cfg = tiny_config(tmp_path, total_steps=4, resilience=res)
    t = Trainer(cfg)
    state = t.init_state()
    audit = make_replica_audit(t.mesh, t.plan)
    assert audit is not None
    assert not bool(jax.jit(audit)(state))
    desynced = perturb_one_replica(state)
    assert bool(jax.jit(audit)(desynced))
    # ... and ONLY the audit notices: the perturbed leaf still claims full
    # replication, so a plain device_get reads one copy and sees nothing
    t.close()


def test_audit_requires_anomaly_detection():
    with pytest.raises(ValueError, match="audit_frequency requires"):
        ResilienceConfig(audit_frequency=5, anomaly_detection=False)


# -- watchdog unit ----------------------------------------------------------


def test_dump_stacks_lists_threads():
    text = dump_stacks("unit test")
    assert "thread stacks" in text and "MainThread" in text
    assert "live device arrays" in text


def test_watchdog_fires_only_past_deadline():
    import time

    beats: list = []
    wd = Watchdog(timeout_s=0.4, on_hang=lambda: beats.append("hang"),
                  poll_s=0.05)
    wd.start()
    try:
        for _ in range(4):  # healthy heartbeat: never fires
            time.sleep(0.1)
            wd.beat()
        assert not wd.fired and beats == []
        with pytest.raises(KeyboardInterrupt):
            while True:  # stalled: fires once, interrupts the main thread
                time.sleep(0.05)
    finally:
        wd.stop()
    assert wd.fired and beats == ["hang"]


# -- checkpoint async-error surfacing ---------------------------------------


def test_async_save_errors_surface_at_next_save_tick(tmp_path, devices):
    """A dead async commit kills the run at the NEXT save() call, not at
    wait()/close() hours later."""
    from zero_transformer_tpu import checkpoint as ckpt_lib

    mgr = ckpt_lib.CheckpointManager(tmp_path / "ck", save_frequency=1,
                                     async_save=True)
    mgr.ensure_ready()

    def boom():
        raise RuntimeError("async commit died: bucket detached")

    assert hasattr(mgr._mgr, "check_for_errors"), "orbax too old for test"
    mgr._mgr_inst.check_for_errors = boom
    with pytest.raises(RuntimeError, match="async commit died"):
        mgr.save(1, {"x": np.zeros(2)})


# -- loader hardening --------------------------------------------------------


def test_tarshard_retry_backoff_and_fault_counters(tmp_path, devices):
    """An unreadable shard is retried with backoff then skipped, and the
    skip is COUNTED — surfaced via DataLoader.fault_counters() into the
    metrics stream rather than vanishing into a log."""
    import io
    import tarfile

    from zero_transformer_tpu.data.loader import DataLoader
    from zero_transformer_tpu.data.tarshards import TarShardSource

    def write_shard(path, rows):
        with tarfile.open(path, "w") as tar:
            for i, row in enumerate(rows):
                buf = io.BytesIO()
                np.save(buf, np.asarray(row))
                data = buf.getvalue()
                info = tarfile.TarInfo(f"{i:05d}.npy")
                info.size = len(data)
                tar.addfile(info, io.BytesIO(data))
        return str(path)

    good = write_shard(tmp_path / "a.tar", [np.arange(8)] * 4)
    bad = tmp_path / "b.tar"
    bad.write_bytes(b"this is not a tar archive")
    src = TarShardSource([good, str(bad)], max_context=8, shuffle_shards=False,
                         retry_backoff_s=0.0)
    loader = DataLoader(src, batch_size=2, train_context=8,
                        process_index=0, process_count=1)
    it = iter(loader)
    # 3 batches = 6 rows: exhausts the 4 good rows, runs into the corrupt
    # shard (retry x2, then skip), and wraps into epoch 2
    for _ in range(3):
        next(it)
    counters = loader.fault_counters()
    assert counters["skipped_shards"] == 1
    assert counters["shard_retries"] == 2  # two retries before the skip
    assert counters["skipped_members"] == 0


def test_trainer_reports_data_fault_counters(tmp_path, devices):
    """The metrics stream carries the loader's fault counters at log points."""
    cfg = tiny_config(tmp_path, total_steps=4)
    t = Trainer(cfg)
    t.train_loader.source.fault_counters = {"skipped_shards": 3}
    payload = t._data_fault_payload()
    assert payload == {"data_skipped_shards": 3.0}
    t.close()

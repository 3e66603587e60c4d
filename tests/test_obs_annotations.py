"""Live spans on two clocks (ISSUE 24): ``Tracer.span`` writes the ring on
the tracer's clock and holds a ``jax.profiler.TraceAnnotation`` named
``"<track>/<name>"`` for the span's life, so an open capture (Python tracing
off, ``obs.profiling.start_trace``) shows the engine's tick tree and the
trainer's loop on the host plane, beside the device's programs.
"""
import glob
import json
import time

import jax
import jax.numpy as jnp
import pytest

from zero_transformer_tpu import obs
from zero_transformer_tpu.config import model_config
from zero_transformer_tpu.inference.sampling import SamplingConfig
from zero_transformer_tpu.models import Transformer
from zero_transformer_tpu.obs import profiling
from zero_transformer_tpu.obs.spans import ATTRS, NAME, T0, T1, TRACK
from zero_transformer_tpu.serving import ServingEngine

TICK_CHILDREN = {"schedule", "prefill", "grow_pages", "decode_step", "emit"}


@pytest.fixture(scope="module")
def cfg():
    return model_config("test", dropout=0.0, compute_dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    model = Transformer(cfg)
    return model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]


def make_engine(cfg, params, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("cache_len", 32)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("page_size", 8)
    kw.setdefault("sampling", SamplingConfig(temperature=0.9, top_k=20))
    return ServingEngine(cfg, params, **kw)


def host_events(directory):
    """[(name, start_ns, end_ns, stats)] of every line of ``/host:CPU`` in
    the newest capture under ``directory``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))
    assert paths, f"no capture under {directory}"
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                            dict(ev.stats)))
    return out


def drive(engine, n=3, max_new=5):
    handles = [engine.submit([1 + i, 2, 3], max_new_tokens=max_new, seed=i)
               for i in range(n)]
    engine.run_until_idle()
    assert all(h.status == "done" for h in handles)


# ------------------------------------------------------------- the tracer


def test_span_writes_ring_and_annotation_attrs_note_discard():
    clock = iter(range(100))
    tr = obs.Tracer(clock=lambda: float(next(clock)))
    with tr.span("phase", "engine", tick=7) as sp:
        sp.note(finished=2)
    with tr.span("spin", "engine", tick=8) as sp:
        sp.discard()
    with pytest.raises(RuntimeError):
        with tr.span("faulted", "engine"):
            raise RuntimeError("boom")
    spans = tr.spans()
    assert [(s[NAME], s[TRACK], s[T0], s[T1], s[ATTRS]) for s in spans] == [
        ("phase", "engine", 0.0, 1.0, {"tick": 7, "finished": 2}),
        ("faulted", "engine", 4.0, 5.0, None),  # recorded though the body raised
    ]


def test_disabled_tracer_records_and_annotates_nothing(monkeypatch):
    """``enabled=False`` skips both clocks: no ring record, and no
    ``TraceAnnotation`` is even constructed."""
    from zero_transformer_tpu.obs import spans as spans_mod

    made = []

    class Recorder:
        def __init__(self, name, **kw):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(spans_mod, "TraceAnnotation", Recorder)
    off = obs.Tracer(enabled=False)
    with off.span("tick", "engine", tick=1) as sp:
        sp.note(x=1)
        sp.discard()
    assert len(off) == 0 and made == []
    on = obs.Tracer()
    with on.span("tick", "engine", tick=1):
        pass
    assert len(on) == 1 and made == ["engine/tick"]


def test_disabled_engine_tracer_annotates_nothing(cfg, params, tmp_path):
    engine = make_engine(cfg, params, n_slots=4, trace=False)
    profiling.start_trace(tmp_path)
    try:
        drive(engine)  # a burst: an enabled tracer would write install and chunk_wait too
    finally:
        jax.profiler.stop_trace()
    assert len(engine.tracer) == 0
    assert not [e for e in host_events(tmp_path) if e[0].startswith("engine/")]


# ------------------------------------------------- the engine's tick tree


def test_capture_holds_the_tick_tree_on_both_clocks(cfg, params, tmp_path):
    """A real capture, Python tracing off, around a tiny engine run: the
    host plane holds engine/tick > engine/schedule, engine/prefill >
    engine/chunk_wait + engine/prefill_chunk + engine/install,
    engine/decode_step > engine/dispatch + engine/device_wait, engine/emit
    with the ring's own
    ``tick`` values, and every tick's children cover >= 95% of it (what is
    left is span overhead and the release of the step's arrays at return:
    some 60-90 us). The ticks here are made some 15 ms long (16 slots over
    4,096 positions): at 8 x 1,024 they were 3.5 ms, and one stall of the
    worker process of 0.2 ms outside the children (seen in a quiet run;
    six workers share the machine under xdist) left a tick at 94.7%."""
    engine = make_engine(cfg, params, n_slots=16, cache_len=4096)
    drive(engine, n=1)  # compile outside the capture
    engine.tracer = obs.Tracer(capacity=65536, clock=engine.now)
    t_open = time.monotonic()
    profiling.start_trace(tmp_path)
    try:
        drive(engine)
    finally:
        jax.profiler.stop_trace()
    assert time.monotonic() - t_open < 30.0  # seconds, not the Python tracer's minutes

    events = host_events(tmp_path)
    assert not [n for n, *_ in events if n.startswith("$")], "Python tracing was on"
    by_name = {}
    for name, s, e, stats in events:
        if name.startswith("engine/"):
            by_name.setdefault(name[len("engine/"):], {}).setdefault(
                stats.get("tick"), []).append((s, e))

    ring = {}
    for s in engine.tracer.by_track("engine"):
        ring.setdefault(s[NAME], {})[s[ATTRS]["tick"]] = s
    # three requests, two rows a dispatch: the first tick dispatches twice,
    # installs after each and waits for the first program before the second
    assert set(ring) == {"tick", "schedule", "prefill", "chunk_wait", "prefill_chunk",
                         "install", "grow_pages", "decode_step", "dispatch", "device_wait",
                         "emit"}
    decode_ticks = set(ring["decode_step"])
    assert decode_ticks and decode_ticks <= set(ring["tick"])
    # same names, same tick values on the profiler's side
    for name, per_tick in ring.items():
        assert set(per_tick) <= set(by_name[name]), name

    def inside(child, parent, tick):
        # one parent a tick; a burst's tick holds several prefill_chunk
        (ps, pe), = by_name[parent][tick]
        return all(ps <= cs and ce <= pe for cs, ce in by_name[child][tick])

    for tick in decode_ticks:
        for child in ("schedule", "grow_pages", "decode_step", "emit"):
            assert inside(child, "tick", tick), (child, tick)
        for child in ("dispatch", "device_wait"):
            assert inside(child, "decode_step", tick), (child, tick)
        d, w = by_name["dispatch"][tick][0], by_name["device_wait"][tick][0]
        assert d[1] <= w[0]  # the wait follows the dispatch
    for tick in ring["prefill_chunk"]:
        assert inside("prefill_chunk", "prefill", tick) and inside("prefill", "tick", tick)
    # the admission's two spans are on the profiler's side too, inside prefill;
    # an install follows a dispatch and lies over none
    assert ring["install"] and ring["chunk_wait"]
    for name in ("install", "chunk_wait"):
        for tick in ring[name]:
            assert inside(name, "prefill", tick), (name, tick)
    for tick in ring["install"]:
        chunks = by_name["prefill_chunk"][tick]
        for s, e in by_name["install"][tick]:
            assert any(ce <= s for _, ce in chunks)
            assert not any(cs < e and ce > s for cs, ce in chunks)

    # ring side: children cover each tick; a tick is its children plus self time
    for tick, root in ring["tick"].items():
        children = [per[tick] for name, per in ring.items()
                    if name in TICK_CHILDREN and tick in per]
        frac = obs.coverage_fraction({"root": root, "children": children})
        assert frac >= 0.95, (tick, frac, (root[T1] - root[T0]) * 1e3)
    # decode_step is dispatch + device_wait + the token list it builds
    for tick in decode_ticks:
        step = ring["decode_step"][tick]
        parts = [ring["dispatch"][tick], ring["device_wait"][tick]]
        assert obs.coverage_fraction({"root": step, "children": parts}) >= 0.9


def test_empty_spins_and_idle_stay_out_of_the_ring(cfg, params, tmp_path):
    """A parked engine spins a thousand times a second: neither its empty
    ``tick``/``schedule`` nor ``run()``'s ``idle`` reaches the ring, while
    the capture shows every instant of the thread under one of them."""
    import threading

    engine = make_engine(cfg, params)
    stop = threading.Event()
    thread = threading.Thread(target=engine.run, args=(stop,))
    profiling.start_trace(tmp_path)
    try:
        thread.start()
        time.sleep(0.05)
    finally:
        stop.set()
        thread.join(timeout=30)
        jax.profiler.stop_trace()
    # (what the ring holds is the constructor's one span, made before run())
    assert [s[NAME] for s in engine.tracer.by_track("engine")] == ["prepare_weights"]
    names = {n for n, *_ in host_events(tmp_path)}
    assert {"engine/tick", "engine/schedule", "engine/idle"} <= names


def test_profile_window_capture_is_usable(cfg, params, tmp_path):
    """``POST /admin/profile``'s window (``ProfileWindow.poll``) opens its
    capture through the one helper: annotations in, Python events out."""
    engine = make_engine(cfg, params, obs_dir=str(tmp_path))
    drive(engine, n=1)
    engine.request_profile(3)
    drive(engine)
    assert engine.profiles_completed
    events = host_events(tmp_path / "profiles")
    ticks = [st["tick"] for n, _, _, st in events if n == "engine/tick" and "tick" in st]
    assert len(set(ticks)) >= 3
    assert not [n for n, *_ in events if n.startswith("$")]


def test_every_capture_window_opens_with_python_tracing_off(monkeypatch, tmp_path):
    opened = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, profiler_options=None: opened.append(profiler_options))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    with obs.profile(tmp_path / "a"):
        pass
    window = profiling.ProfileWindow(str(tmp_path))
    window.request(1)
    window.poll(0)
    window.poll(1)
    assert len(opened) == 2 and window.completed
    for options in opened:
        assert options.python_tracer_level == 0 and options.host_tracer_level == 1


# ------------------------------------------------------------ the trainer


def test_trainer_window_shows_live_spans_and_no_estimates(tmp_path, devices):
    """``--profile-window`` goes through the same helper and shows the
    loop's phases as ``train/<name>`` annotations with their ``step``; the
    ring holds measured spans only — none carries ``estimate`` — while the
    bubble fraction stays a payload key."""
    from zero_transformer_tpu.config import (
        CheckpointConfig,
        Config,
        DataConfig,
        MeshConfig,
        ModelConfig,
        OptimizerConfig,
        TrainingConfig,
    )
    from zero_transformer_tpu.training.trainer import Trainer

    cfg = Config(
        model=ModelConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                          max_seq_len=16, dropout=0.0),
        mesh=MeshConfig(zero_stage=1),
        optimizer=OptimizerConfig(peak_learning_rate=1e-2, warmup_steps=2,
                                  total_steps=8),
        training=TrainingConfig(batch_size=8, train_context=16, total_steps=8,
                                evaluation_frequency=0,
                                maximum_evaluation_steps=1,
                                log_frequency=1, seed=0,
                                profile_start=3, profile_steps=2,
                                profile_dir=str(tmp_path / "profile")),
        data=DataConfig(source="synthetic", max_context=16),
        checkpoint=CheckpointConfig(directory=str(tmp_path / "run"),
                                    save_frequency=4, async_save=False),
    )
    trainer = Trainer(cfg)
    trainer._bubble_frac = 0.25  # what used to lay estimate spans over the window
    trainer.train()
    trainer.close()
    spans = trainer.tracer.by_track("train")
    names = {s[NAME] for s in spans}
    assert {"data_fetch", "dispatch", "device_sync", "checkpoint_save",
            "replica_audit"} <= names
    assert not names & {"grads_compute", "comm_exposed", "bubble_wait"}
    assert not [s for s in spans if s[ATTRS] and "estimate" in s[ATTRS]]
    # checkpoint_save only where a save happened
    saves = [s[ATTRS]["step"] for s in spans if s[NAME] == "checkpoint_save"]
    assert saves and all(step % 4 == 0 or step == 8 for step in saves)
    logged = [json.loads(line) for line in
              (tmp_path / "run" / "metrics.jsonl").read_text().splitlines()]
    assert any("train/bubble_frac" in row or "bubble_frac" in json.dumps(row)
               for row in logged)

    events = host_events(tmp_path / "profile")
    assert not [n for n, *_ in events if n.startswith("$")]
    steps = {n: {st.get("step") for m, _, _, st in events if m == n}
             for n in ("train/data_fetch", "train/dispatch", "train/device_sync")}
    assert {3, 4} <= steps["train/data_fetch"] and {3, 4} <= steps["train/dispatch"]
    assert 3 in steps["train/device_sync"]  # step 4's sync closes the window first

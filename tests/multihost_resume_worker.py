"""Worker for the 4-process kill+resume test (test_multihost.py).

The crash-recovery story the reference left manual (reference
``src/utils/pod_test.py:1-6`` "run this before training to check the pod";
recovery after a mid-run host loss meant restarting the job by hand,
``main_zero.py:291-313`` restore branch), driven end-to-end across REAL
process boundaries:

- ``straight``  — 4 processes train steps 1-4; steps 3-4 losses are the
  ground truth.
- ``interrupted`` — 4 processes train steps 1-2, write a (periodic)
  checkpoint, then process 3 dies abruptly (``os._exit`` — a host crash,
  no goodbye to the coordinator). The survivors attempt step 3 anyway: the
  collective can never complete with a dead member, so a watchdog converts
  the stall into a documented exit code instead of a silent hang.
- ``resume``    — a FRESH 4-process job restores the checkpoint (sharded,
  every host reads only its pieces), restores the loader position, and
  trains steps 3-4. Its losses must equal ``straight``'s exactly — the
  interruption is invisible in the trajectory.

ELASTIC modes (test_multihost.py::test_elastic_resume_across_world_sizes)
run under a VARIABLE process count — the topology that comes back after a
preemption is whatever the scheduler has:

- ``elastic_save``   — train steps 1-2 on THIS job's world, save step 2
  (with topology metadata) through the verified-save path.
- ``elastic_resume`` — a job with a DIFFERENT world size restores through
  ``CheckpointManager.restore_verified`` (digest-verified, elastic-compat
  checked), rebuilds the ZeRO plan for its own mesh, and trains steps 3-4.
  Losses must match a same-topology uninterrupted run to reduction-order
  ulps (the global batch stream is identical; only collective schedules
  differ).

Prints ``LOSS step=N <loss>`` lines and ``WORKER_OK`` on success.
"""
import os
import sys
import threading

import jax

jax.config.update("jax_platforms", "cpu")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=2"
)
# persistent compile cache, placed by the same helper as tests/conftest.py —
# suite-spawned and standalone runs both land in the host-correct directory. Three phases x four processes compile
# the SAME programs — without this the test's wall-clock is ~12 identical
# XLA compiles
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _compile_cache  # noqa: E402

_compile_cache.configure(jax)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from zero_transformer_tpu.parallel.bootstrap import maybe_initialize  # noqa: E402

VICTIM = 3  # the process that "loses its host" in interrupted mode


def main():
    mode = os.environ["WORKER_MODE"]
    assert maybe_initialize(), "coordinator env vars must trigger initialization"
    if mode.startswith("elastic"):
        # elastic phases run under whatever world the harness launched
        assert jax.device_count() == 2 * jax.process_count(), jax.device_count()
    else:
        assert jax.process_count() == 4, jax.process_count()
        assert jax.device_count() == 8, jax.device_count()

    # Warmup collective FIRST: gloo creates its context lazily at the first
    # cross-process collective, with a fixed 30s key-value rendezvous
    # deadline. Reaching that first collective straight after init keeps
    # inter-process skew at milliseconds; without this, the first collective
    # is the train step, whose per-process XLA compile can skew processes
    # past 30s on a loaded box (observed flake). The clique is then cached
    # for every later collective.
    from zero_transformer_tpu.utils.pod_check import pod_check

    assert pod_check(timeout=300.0), "pod warmup psum failed"

    from jax.sharding import NamedSharding, PartitionSpec as P

    from zero_transformer_tpu import checkpoint as ckpt_lib
    from zero_transformer_tpu.config import MeshConfig, OptimizerConfig, model_config
    from zero_transformer_tpu.data import DataLoader, SyntheticSource, device_put_batch
    from zero_transformer_tpu.models.gpt import Transformer
    from zero_transformer_tpu.parallel.mesh import make_mesh
    from zero_transformer_tpu.parallel.zero import (
        init_train_state,
        make_plan,
        make_train_step,
    )
    from zero_transformer_tpu.training.optimizer import make_optimizer

    cfg = model_config("test", dropout=0.0)
    mesh = make_mesh(MeshConfig(zero_stage=2))
    model = Transformer(cfg)
    tx = make_optimizer(OptimizerConfig(warmup_steps=2, total_steps=10))

    batch_size, seq = 8, 32
    plan = make_plan(model, tx, mesh, (batch_size, seq), zero_stage=2)
    state = init_train_state(
        model, tx, jax.random.PRNGKey(0), mesh, (batch_size, seq), plan
    )
    step = make_train_step(model, tx, mesh, plan, zero_stage=2)

    def fresh_loader():
        return DataLoader(
            SyntheticSource(cfg.vocab_size, seq, seed=1),
            batch_size=batch_size,
            train_context=seq,
        )

    loader = fresh_loader()
    batch_sharding = NamedSharding(mesh, P(None, *plan.batch.spec))
    rng = jax.random.PRNGKey(2)
    mgr = ckpt_lib.CheckpointManager(
        os.environ["WORKER_CKPT_DIR"], keep=2, async_save=False
    )

    # AOT-compile + KV barrier before the FIRST execution of each phase:
    # per-rank XLA compile of the train step can skew ranks by minutes on a
    # loaded box, and a rank that starts executing while a peer still
    # compiles hits gloo's fixed ~30s read timeout mid-collective. The
    # barrier rides the coordination service (KV store, long timeout), not
    # gloo, so it absorbs the skew; execution then starts aligned.
    from jax._src import distributed as _dist

    _client = getattr(_dist.global_state, "client", None)

    def run_steps(it, state, n, tag, barrier=True):
        compiled = None
        for _ in range(n):
            batch = device_put_batch(next(it), batch_sharding)
            if compiled is None:
                compiled = step.lower(state, batch, rng).compile()
                if barrier and _client is not None:
                    _client.wait_at_barrier(f"compiled_{mode}_{tag}", 600_000)
            state, metrics = compiled(state, batch, rng)
            loss = float(metrics["loss"])
            assert loss == loss, "non-finite loss"
            print(f"LOSS step={int(state.step)} {loss:.10f}", flush=True)
        return state

    abstract = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        jax.eval_shape(lambda s: s, state),
        plan.state,
    )
    # any restored state is donated by the train step below: force runtime-
    # owned buffers first (jax 0.4.37 CPU: donating an orbax zero-copy host
    # view corrupts the heap — glibc "corrupted double-linked list")
    from zero_transformer_tpu.utils.jax_compat import ensure_donatable

    if mode == "resume":
        state, meta = mgr.restore(abstract)
        state = ensure_donatable(state)
        assert int(state.step) == 2, int(state.step)
        loader.restore(meta["loader"])
        state = run_steps(iter(loader), state, 2, "resume")
    elif mode == "elastic_save":
        it = iter(loader)
        state = run_steps(it, state, 2, "warm")
        from zero_transformer_tpu.parallel.sharding import topology_summary

        mgr.save(
            2, state,
            meta={"loader": loader.state(),
                  "topology": topology_summary(mesh, 2),
                  "schedule": {"batch_size": batch_size, "train_context": seq}},
            force=True,
        )
        mgr.wait()
        print("SAVED step=2", flush=True)
    elif mode == "elastic_resume":
        # the trustworthy-restore path, across a topology change: digest
        # verification against the manifest, elastic-compat validation of
        # the saved topology vs THIS job's mesh, orbax native reshard into
        # the plan rebuilt for the new device count
        from zero_transformer_tpu.parallel.sharding import check_elastic_compat

        def check(meta):
            notes = check_elastic_compat(
                (meta or {}).get("topology"), mesh, 2, batch_size
            )
            for n in notes:
                print(f"ELASTIC {n}", flush=True)

        state, meta, report = mgr.restore_verified(abstract, check_meta=check)
        state = ensure_donatable(state)
        assert int(state.step) == 2, int(state.step)
        assert report.quarantined == [], report.quarantined
        loader.restore(meta["loader"])
        state = run_steps(iter(loader), state, 2, "elastic_resume")
    else:  # straight / interrupted
        it = iter(loader)
        state = run_steps(it, state, 2, "warm")
        mgr.save(2, state, meta={"loader": loader.state()}, force=True)
        mgr.wait()
        print("SAVED step=2", flush=True)
        if mode == "interrupted":
            if jax.process_index() == VICTIM:
                os._exit(9)  # host crash: no cleanup, no coordinator goodbye
            # survivors attempt the next step; with a dead member the
            # collective cannot complete — the watchdog documents the stall
            threading.Timer(90.0, lambda: os._exit(7)).start()
            try:
                # NO barrier here: it would wait on the dead victim and the
                # watchdog would fire before the collective is ever issued —
                # the property under test is the COLLECTIVE stalling with a
                # dead member (the clique already exists from steps 1-2)
                run_steps(it, state, 1, "survivor", barrier=False)
                print("SURVIVOR_STEP_COMPLETED_UNEXPECTEDLY", flush=True)
            except Exception as e:  # distributed runtime noticed the death
                print(f"SURVIVOR_ERROR {type(e).__name__}", flush=True)
            os._exit(7)
        else:
            state = run_steps(it, state, 2, "tail")

    mgr.close()
    print("WORKER_OK", flush=True)


if __name__ == "__main__":
    main()

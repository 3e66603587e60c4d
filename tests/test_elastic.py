"""Elastic ZeRO resume: restore a checkpoint onto a DIFFERENT mesh.

The paper's runs live on preemptible pods — the topology that comes back
after a preemption is whatever the scheduler has, not necessarily what the
checkpoint was saved under. These tests pin the trustworthy-restore
contract across topology changes:

- an 8-device checkpoint resumes on a 4-device mesh (and 4 -> 8), with the
  ZeRO partition spec rebuilt for the new world and orbax resharding the
  arrays natively (GSPMD makes the partitioned program a pure function of
  mesh + program — arXiv:2105.04663 — so the TRAJECTORY is preserved up to
  reduction-order ulps);
- the loader position is stored in GLOBAL batches, so the global-token
  trajectory continues exactly; geometry changes remap by token count,
  rounding DOWN to a batch boundary (replay, never skip);
- genuinely incompatible topologies refuse with a precise error BEFORE
  compilation, not deep inside pjit.

The real multi-process version (save under 4 hosts / 8 devices, resume
under 2 hosts / 4 devices) lives in test_multihost.py (slow lane).
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
from zero_transformer_tpu.parallel import sharding as shd
from zero_transformer_tpu.parallel.mesh import make_mesh
from zero_transformer_tpu.training.trainer import Trainer, remap_loader_state


def tiny_config(directory, total_steps=8, zero_stage=1, batch_size=8):
    return Config(
        model=ModelConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                          max_seq_len=16, dropout=0.0),
        mesh=MeshConfig(zero_stage=zero_stage),
        optimizer=OptimizerConfig(peak_learning_rate=1e-2, warmup_steps=2,
                                  total_steps=total_steps),
        training=TrainingConfig(batch_size=batch_size, train_context=16,
                                total_steps=total_steps,
                                evaluation_frequency=0,
                                log_frequency=2, seed=0),
        data=DataConfig(source="synthetic", max_context=16),
        checkpoint=CheckpointConfig(directory=str(directory),
                                    save_frequency=4, async_save=False),
        resilience=ResilienceConfig(),
    )


def params_close(a, b, atol):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=0,
                                   atol=atol)


def _elastic_roundtrip(tmp_path, devices, n_save, n_resume, zero_stage,
                       truth_params, tag, atol=5e-4):
    """Save at step 4 under n_save devices, resume to step 8 under n_resume;
    compare against an uninterrupted ``truth_params`` run."""
    ckpt_dir = tmp_path / f"run_{tag}"
    mesh_save = make_mesh(MeshConfig(zero_stage=zero_stage),
                          devices=devices[:n_save])
    mesh_resume = make_mesh(MeshConfig(zero_stage=zero_stage),
                            devices=devices[:n_resume])

    # ONE schedule (total_steps=8) across every phase: the first trainer
    # just stops early, so the LR trajectory is comparable run-to-run
    cfg_save = tiny_config(ckpt_dir, total_steps=8, zero_stage=zero_stage)
    t = Trainer(cfg_save, mesh=mesh_save)
    t.train(max_steps=4)
    t.close()

    cfg8 = dataclasses.replace(
        cfg_save,
        checkpoint=dataclasses.replace(cfg_save.checkpoint, resume=True),
    )
    t_el = Trainer(cfg8, mesh=mesh_resume)
    elastic = t_el.train()
    report = t_el._restore_report
    t_el.close()
    assert int(elastic.step) == 8
    assert report is not None and report.quarantined == []

    # the restored VALUES are bitwise those of the save-topology run (see
    # test_elastic_restore_values_bitwise); steps run on a different device
    # count use a different collective schedule, so per-step reduction-order
    # ulps — amplified by adam's per-param normalization — compound to
    # ~1e-4 ABSOLUTE drift. Relative tolerance is meaningless on near-zero
    # weights; the trajectory-preservation contract is pinned absolutely.
    params_close(truth_params, elastic.params, atol=atol)
    return elastic


@pytest.mark.chaos  # runs in `make elastic-chaos` + the nightly full lane;
@pytest.mark.slow   # three full trainer runs — out of the tier-1 budget
def test_elastic_resume_8_to_4_and_back(tmp_path, devices):
    """The acceptance roundtrips, sharing one uninterrupted 8-device ground
    truth: save on 8 devices -> resume on 4; save on 4 -> resume on 8.
    (Tier-1 still pins the elastic restore itself —
    test_elastic_restore_values_bitwise — and the compat/remap contracts.)"""
    cfg_clean = tiny_config(tmp_path / "clean", total_steps=8)
    t_cl = Trainer(cfg_clean, mesh=make_mesh(MeshConfig(), devices=devices))
    clean = t_cl.train()
    t_cl.close()
    _elastic_roundtrip(tmp_path, devices, n_save=8, n_resume=4, zero_stage=1,
                       truth_params=clean.params, tag="8to4")
    # the 4->8 leg diverges from the 8-device truth on BOTH sides of the
    # save (steps 1-4 ran on 4 devices too), so its drift bound doubles
    _elastic_roundtrip(tmp_path, devices, n_save=4, n_resume=8, zero_stage=1,
                       truth_params=clean.params, tag="4to8", atol=3e-3)


@pytest.mark.slow
def test_elastic_resume_zero2_8_to_4(tmp_path, devices):
    """The explicit ZeRO-2 shard_map core rebuilds its collective schedule
    for the new world size; the optimizer state reshards 8-way -> 4-way.
    Slow lane: compiles the explicit core for two mesh sizes."""
    cfg_clean = tiny_config(tmp_path / "clean", total_steps=8, zero_stage=2)
    t_cl = Trainer(cfg_clean, mesh=make_mesh(MeshConfig(zero_stage=2),
                                             devices=devices))
    clean = t_cl.train()
    t_cl.close()
    _elastic_roundtrip(tmp_path, devices, n_save=8, n_resume=4, zero_stage=2,
                       truth_params=clean.params, tag="z2")


def test_elastic_restore_values_bitwise(tmp_path, devices):
    """The RESTORE itself is bitwise across topologies (only subsequent
    compute differs): an 8-device save restored onto 4 devices yields
    byte-identical leaves."""
    from zero_transformer_tpu import checkpoint as ckpt_lib

    cfg = tiny_config(tmp_path / "run", total_steps=4)
    mesh8 = make_mesh(MeshConfig(), devices=devices)
    t = Trainer(cfg, mesh=mesh8)
    final = t.train()
    t.close()

    mesh4 = make_mesh(MeshConfig(), devices=devices[:4])
    cfg_r = dataclasses.replace(
        cfg, checkpoint=dataclasses.replace(cfg.checkpoint, resume=True)
    )
    t4 = Trainer(cfg_r, mesh=mesh4)
    restored = t4.init_state()
    for a, b in zip(jax.tree.leaves(final.params),
                    jax.tree.leaves(restored.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # ... and the digests the manifest verified are topology-invariant
    d8 = ckpt_lib.tree_digests(final.params)
    d4 = ckpt_lib.tree_digests(restored.params)
    assert d8 == d4
    t4.close()


# -- topology compatibility validation ---------------------------------------


def test_incompatible_batch_refused_before_compile(tmp_path, devices):
    """batch_size not divisible by the new DP world must fail with the
    precise elastic error, not a sharding error deep in pjit."""
    mesh3 = make_mesh(MeshConfig(), devices=devices[:3])  # DP world of 3
    with pytest.raises(ValueError, match="not\\s+divisible by the new data-parallel"):
        shd.check_elastic_compat(
            shd.topology_summary(make_mesh(MeshConfig(), devices=devices), 1),
            mesh3, 1, global_batch=8,
        )


def test_compat_notes_describe_topology_change(devices):
    mesh8 = make_mesh(MeshConfig(), devices=devices)
    mesh4 = make_mesh(MeshConfig(), devices=devices[:4])
    saved = shd.topology_summary(mesh8, 1)
    notes = shd.check_elastic_compat(saved, mesh4, 2, global_batch=8)
    joined = "\n".join(notes)
    assert "8 -> 4" in joined and "zero_stage 1 -> 2" in joined
    # same topology: silent
    assert shd.check_elastic_compat(saved, mesh8, 1, global_batch=8) == []
    # legacy checkpoint without topology metadata: no notes, no crash
    assert shd.check_elastic_compat(None, mesh4, 1, global_batch=8) == []


# -- loader position remap (batch-boundary semantics) ------------------------


def test_loader_remap_same_geometry_is_identity():
    meta = {"loader": {"steps_consumed": 7},
            "schedule": {"batch_size": 8, "train_context": 16}}
    assert remap_loader_state(meta, 8, 16) == {"steps_consumed": 7}


def test_loader_remap_by_token_count():
    # 7 batches of 8x16 = 896 tokens -> 3 whole batches of 16x16 (768
    # tokens), 128 tokens REPLAYED (round down to the batch boundary)
    meta = {"loader": {"steps_consumed": 7},
            "schedule": {"batch_size": 8, "train_context": 16}}
    assert remap_loader_state(meta, 16, 16) == {"steps_consumed": 3}
    # exact multiple: nothing replayed
    meta["loader"]["steps_consumed"] = 8
    assert remap_loader_state(meta, 16, 16) == {"steps_consumed": 4}


def test_loader_remap_accounts_for_grad_accum():
    # the canonical elastic move: half the devices, double the accumulation
    # — sequences per optimizer step unchanged, so the position is too
    meta = {"loader": {"steps_consumed": 6},
            "schedule": {"batch_size": 8, "train_context": 16,
                         "accum_steps": 1}}
    assert remap_loader_state(meta, 4, 16, 2) == {"steps_consumed": 6}
    # doubling accum at the SAME batch size doubles tokens per step:
    # 6 steps x 128 tok -> 3 steps x 256 tok, nothing replayed
    assert remap_loader_state(meta, 8, 16, 2) == {"steps_consumed": 3}


def test_loader_remap_legacy_meta_passthrough():
    # checkpoints from before the schedule block: geometry assumed unchanged
    meta = {"loader": {"steps_consumed": 5}}
    assert remap_loader_state(meta, 8, 16) == {"steps_consumed": 5}
    assert remap_loader_state({}, 8, 16) is None


# -- pp_schedule changes (PR 8: interleaved stores blocks pipe-replicated) ----


def test_compat_notes_describe_pp_schedule_change(devices):
    """A schedule change is elastic but must be visible in the resume log —
    especially gpipe <-> interleaved, which RELAYOUTS the stored block
    stack (pipe-sharded <-> pipe-replicated)."""
    mesh = make_mesh(MeshConfig(), devices=devices)
    saved = shd.topology_summary(mesh, 1, pp_schedule="gpipe")
    assert saved["pp_schedule"] == "gpipe"
    notes = shd.check_elastic_compat(
        saved, mesh, 1, global_batch=8, pp_schedule="interleaved"
    )
    joined = "\n".join(notes)
    assert "pp_schedule gpipe -> interleaved" in joined
    assert "reshards natively" in joined
    # gpipe -> 1f1b: same stored layout, still logged
    notes2 = shd.check_elastic_compat(
        saved, mesh, 1, global_batch=8, pp_schedule="1f1b"
    )
    assert "same stored layout" in "\n".join(notes2)
    # pre-PR-8 checkpoints have no pp_schedule key: treated as gpipe
    legacy = {k: v for k, v in saved.items() if k != "pp_schedule"}
    assert shd.check_elastic_compat(
        legacy, mesh, 1, global_batch=8, pp_schedule="gpipe"
    ) == []


def test_pp_schedule_relayout_restore_bitwise(tmp_path, devices):
    """Save under the gpipe plan (blocks pipe-SHARDED), restore into the
    interleaved plan (blocks pipe-REPLICATED) and back: orbax reshards
    natively and every leaf is byte-identical — the state relayout half of
    an elastic pp_schedule change, without executing the pipe engine (this
    image's jax cannot trace it; the trajectory half runs on modern jax in
    test_pipeline.py)."""
    from zero_transformer_tpu import checkpoint as ckpt_lib
    from zero_transformer_tpu.config import ModelConfig
    from zero_transformer_tpu.models import Transformer
    from zero_transformer_tpu.parallel.zero import init_train_state, make_plan
    from zero_transformer_tpu.training.optimizer import make_optimizer

    cfg = ModelConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=4,
                      max_seq_len=16, dropout=0.0)
    opt = OptimizerConfig(peak_learning_rate=1e-2, warmup_steps=2,
                          total_steps=8)
    mesh = make_mesh(MeshConfig(pipe=2, data=4), devices=devices)
    model = Transformer(cfg)
    tx = make_optimizer(opt)
    plan_gp = make_plan(model, tx, mesh, (2, 16), 1, pp_schedule="gpipe")
    plan_il = make_plan(model, tx, mesh, (2, 16), 1,
                        pp_schedule="interleaved")
    state = init_train_state(model, tx, jax.random.PRNGKey(0), mesh, (2, 16),
                             plan_gp)

    mgr = ckpt_lib.CheckpointManager(tmp_path / "ckpt", async_save=False)
    meta = {"topology": shd.topology_summary(mesh, 1, pp_schedule="gpipe")}
    assert mgr.save(4, state, meta=meta, force=True)

    abstract = ckpt_lib.abstract_state(model, tx, plan_il, (2, 16))
    restored, meta_r = mgr.restore(abstract)
    assert meta_r["topology"]["pp_schedule"] == "gpipe"
    notes = shd.check_elastic_compat(
        meta_r["topology"], mesh, 1, global_batch=8,
        pp_schedule="interleaved",
    )
    assert any("pp_schedule" in n for n in notes)

    # restored layout IS the interleaved plan's (blocks pipe-replicated)...
    blk = jax.tree.leaves(restored.params["blocks"])[0]
    assert "pipe" not in str(blk.sharding.spec)
    saved_blk = jax.tree.leaves(state.params["blocks"])[0]
    assert "pipe" in str(saved_blk.sharding.spec)
    # ...and every leaf is byte-identical through the relayout
    for a, b in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(restored.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    mgr.close()


@pytest.mark.slow
def test_elastic_resume_across_pp_schedule_change(tmp_path, devices):
    """Full trainer roundtrip: train 4 steps under gpipe, resume under
    interleaved — the loader position is in global batches so the token
    trajectory continues exactly, and the run completes to the target step.
    (Gated: the pipe engine doesn't trace on this image's jax; the state
    relayout half is pinned bitwise above, ungated.)"""
    ckpt_dir = tmp_path / "sched_change"
    mesh = make_mesh(MeshConfig(pipe=2, data=4), devices=devices)

    cfg = tiny_config(ckpt_dir, total_steps=8)
    cfg = dataclasses.replace(
        cfg,
        model=dataclasses.replace(cfg.model, n_layers=4),
        mesh=MeshConfig(pipe=2, data=4),
        training=dataclasses.replace(
            cfg.training, gradient_accumulation_steps=2
        ),
    )
    t = Trainer(cfg, mesh=mesh)
    t.train(max_steps=4)
    saved_loader = t.train_loader.state()
    t.close()

    cfg_r = dataclasses.replace(
        cfg,
        mesh=MeshConfig(pipe=2, data=4, pp_schedule="interleaved",
                        pp_interleave=2),
        checkpoint=dataclasses.replace(cfg.checkpoint, resume=True),
    )
    t_r = Trainer(cfg_r, mesh=mesh)
    final = t_r.train()
    resumed_from = t_r._restore_report
    t_r.close()
    assert int(final.step) == 8
    assert resumed_from is not None and resumed_from.quarantined == []
    # same geometry -> the loader position carried over verbatim (the token
    # trajectory continued exactly where the gpipe run stopped)
    assert saved_loader["steps_consumed"] > 0

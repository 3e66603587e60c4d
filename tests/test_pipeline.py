"""GPipe pipeline parallelism on the 8-device mesh.

Capability beyond the reference (SURVEY §2 checklist: PP = none). Exactness
is the contract: the pipelined wavefront must reproduce the plain fused
step's training trajectory bit-for-bit-ish (f32 tolerances), because it is
the same math on a different schedule.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zero_transformer_tpu.config import MeshConfig, ModelConfig, OptimizerConfig
from zero_transformer_tpu.models import Transformer
from zero_transformer_tpu.parallel import (
    make_mesh,
    make_plan,
    init_train_state,
    make_train_step,
)
from zero_transformer_tpu.parallel.mesh import PIPE_AXIS
from zero_transformer_tpu.parallel.pipeline import bubble_fraction, interleaved_slot
from zero_transformer_tpu.training.optimizer import make_optimizer, make_schedule


CFG = ModelConfig(
    name="t", vocab_size=256, d_model=64, n_heads=4, n_layers=4, max_seq_len=32,
    dropout=0.0, compute_dtype="float32",
)
OPT = OptimizerConfig(peak_learning_rate=1e-3, warmup_steps=4, total_steps=64)


def _setup(mesh_cfg, model_cfg=CFG, zero_stage=1, grad_accum_dtype="float32"):
    mesh = make_mesh(mesh_cfg)
    model = Transformer(model_cfg)
    tx = make_optimizer(OPT)
    plan = make_plan(model, tx, mesh, (2, 16), zero_stage)
    state = init_train_state(model, tx, jax.random.PRNGKey(0), mesh, (2, 16), plan)
    step = make_train_step(model, tx, mesh, plan, zero_stage, make_schedule(OPT),
                           pp_schedule=mesh_cfg.pp_schedule,
                           grad_accum_dtype=grad_accum_dtype)
    return mesh, state, step


def _batch(seed=0, accum=4, vocab=256):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, vocab, (accum, 8, 16)), jnp.int32)


@pytest.mark.parametrize("mesh_cfg", [
    MeshConfig(pipe=2, data=4),
    MeshConfig(pipe=4, data=2),
])
def test_pp_matches_dp_trajectory(devices, mesh_cfg):
    mesh_pp, s_pp, step_pp = _setup(mesh_cfg)
    mesh_dp, s_dp, step_dp = _setup(MeshConfig())
    rng = jax.random.PRNGKey(7)
    for i in range(3):
        s_pp, mp = step_pp(s_pp, _batch(i), rng)
        s_dp, md = step_dp(s_dp, _batch(i), rng)
    np.testing.assert_allclose(float(mp["loss"]), float(md["loss"]), rtol=2e-4)
    for a, b in zip(jax.tree.leaves(s_pp.params), jax.tree.leaves(s_dp.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_pp_blocks_sharded_over_pipe(devices):
    mesh, state, step = _setup(MeshConfig(pipe=2, data=4))
    wi = state.params["blocks"]["mlp"]["wi"]["kernel"]
    assert "pipe" in str(wi.sharding.spec), wi.sharding.spec
    # each stage holds half the layer stack
    assert wi.addressable_shards[0].data.shape[0] * 2 == wi.shape[0]


def test_pp_untied_head_and_rope(devices):
    cfg = dataclasses.replace(
        CFG, tie_embeddings=False, position="rope", norm="rmsnorm",
        activation="swiglu",
    )
    mesh_pp, s_pp, step_pp = _setup(MeshConfig(pipe=2, data=4), model_cfg=cfg)
    mesh_dp, s_dp, step_dp = _setup(MeshConfig(), model_cfg=cfg)
    rng = jax.random.PRNGKey(3)
    s_pp, mp = step_pp(s_pp, _batch(0), rng)
    s_dp, md = step_dp(s_dp, _batch(0), rng)
    np.testing.assert_allclose(float(mp["loss"]), float(md["loss"]), rtol=2e-4)


@pytest.mark.parametrize("policy", ["none", "qkv_mlp"])
def test_pp_with_remat_matches_dp(devices, policy):
    # the pipeline stage must honor cfg.remat (review finding: it was
    # silently ignored) and stay numerically identical — including under
    # the named-save policy, whose checkpoint_name sites sit inside the
    # scanned stage body under the pipe-manual shard_map (r5: the shared
    # resolve_remat_policy must not degrade to None here)
    cfg = dataclasses.replace(CFG, remat=True, remat_policy=policy)
    mesh_pp, s_pp, step_pp = _setup(MeshConfig(pipe=2, data=4), model_cfg=cfg)
    mesh_dp, s_dp, step_dp = _setup(MeshConfig(), model_cfg=cfg)
    rng = jax.random.PRNGKey(5)
    s_pp, mp = step_pp(s_pp, _batch(0), rng)
    s_dp, md = step_dp(s_dp, _batch(0), rng)
    np.testing.assert_allclose(float(mp["loss"]), float(md["loss"]), rtol=2e-4)


def test_pp_with_moe_trains(devices):
    cfg = dataclasses.replace(CFG, vocab_size=128, n_experts=4, moe_top_k=2)
    mesh, state, step = _setup(
        MeshConfig(pipe=2, data=2, expert=2), model_cfg=cfg
    )
    losses = []
    rng = jax.random.PRNGKey(1)
    batch = _batch(0, vocab=128)
    for _ in range(15):
        state, metrics = step(state, batch, rng)
        losses.append(float(metrics["loss"]))
        assert np.isfinite(losses[-1])
    assert losses[-1] < losses[0] - 0.5, losses


def test_pp_rejects_zero3_and_indivisible(devices):
    mesh = make_mesh(MeshConfig(pipe=2, data=4))
    model = Transformer(CFG)
    tx = make_optimizer(OPT)
    plan = make_plan(model, tx, mesh, (2, 16), 3)
    with pytest.raises(NotImplementedError, match="stage"):
        make_train_step(model, tx, mesh, plan, 3)
    bad = Transformer(dataclasses.replace(CFG, n_layers=3))
    plan3 = make_plan(bad, tx, mesh, (2, 16), 1)
    with pytest.raises(ValueError, match="divisible"):
        make_train_step(bad, tx, mesh, plan3, 1)
    # pipe x tensor: XLA SPMD partitioner crash — must refuse loudly
    mesh_tp = make_mesh(MeshConfig(pipe=2, data=2, tensor=2))
    plan_tp = make_plan(model, tx, mesh_tp, (2, 16), 1)
    with pytest.raises(NotImplementedError, match="tensor"):
        make_train_step(model, tx, mesh_tp, plan_tp, 1)


def test_pp_loss_chunk_matches_dp(devices):
    """Chunked CE through the pipeline engine: the last rank computes its
    loss tile-by-tile (no [b, T, vocab] logits) and the trajectory still
    matches the fused DP step running the same chunked loss."""
    cfg = dataclasses.replace(CFG, loss_chunk=5)
    mesh_pp, s_pp, step_pp = _setup(MeshConfig(pipe=2, data=4), model_cfg=cfg)
    mesh_dp, s_dp, step_dp = _setup(MeshConfig(), model_cfg=cfg)
    rng = jax.random.PRNGKey(7)
    for i in range(2):
        s_pp, mp = step_pp(s_pp, _batch(i), rng)
        s_dp, md = step_dp(s_dp, _batch(i), rng)
    np.testing.assert_allclose(float(mp["loss"]), float(md["loss"]), rtol=2e-4)
    for a, b in zip(jax.tree.leaves(s_pp.params), jax.tree.leaves(s_dp.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_pp_adafactor_zero2_rejected(devices):
    """Adafactor (factored stats) is ZeRO-axis-aware but not pipe-aware:
    pipe x stage>=2 must reject with the reason, not die in an internal
    shard_map assertion (r5 review finding). Stage <= 1 pipe adafactor and
    non-pipe adafactor x ZeRO-2/3 both work."""
    mesh = make_mesh(MeshConfig(pipe=2, data=4))
    model = Transformer(CFG)
    opt_af = dataclasses.replace(OPT, optimizer="adafactor")
    tx = make_optimizer(opt_af)
    plan = make_plan(model, tx, mesh, (2, 16), 2)
    with pytest.raises(NotImplementedError, match="adafactor"):
        make_train_step(
            model, tx, mesh, plan, 2,
            tx_factory=lambda norm_fn, zc=None: make_optimizer(
                opt_af, None, norm_fn, zero_collectives=zc
            ),
        )
    # plain 1-arg factory (un-sharded adafactor) is rejected the same way
    with pytest.raises(NotImplementedError, match="adafactor"):
        make_train_step(model, tx, mesh, plan, 2)


def test_pp_packed_matches_dp_trajectory(devices):
    """Packed-sequence training through the pipeline wavefront: every rank
    derives the microbatch's document ids from the (pipe-replicated) batch,
    so masking and boundary-ignored loss match the fused step exactly."""
    cfg = dataclasses.replace(CFG, doc_sep_token=0)
    mesh_pp, s_pp, step_pp = _setup(MeshConfig(pipe=2, data=4), model_cfg=cfg)
    mesh_dp, s_dp, step_dp = _setup(MeshConfig(), model_cfg=cfg)
    rng = jax.random.PRNGKey(11)
    for i in range(2):
        batch = np.array(_batch(i))  # writable copy
        batch[:, :, 5] = 0  # separators straddling rows: 2+ docs per row
        batch[:, 1::2, 11] = 0
        batch = jnp.asarray(batch)
        s_pp, mp = step_pp(s_pp, batch, rng)
        s_dp, md = step_dp(s_dp, batch, rng)
    np.testing.assert_allclose(float(mp["loss"]), float(md["loss"]), rtol=2e-4)
    for a, b in zip(jax.tree.leaves(s_pp.params), jax.tree.leaves(s_dp.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_pp_zero2_matches_dp_trajectory(devices):
    """Pipe x explicit ZeRO-2 (one shard_map manual over pipe+data: gradient
    psum_scatter, sharded optimizer, param all_gather) follows the same
    training trajectory as plain DP stage 0 — and its compiled HLO contains
    literal reduce-scatters with no gradient-sized all-reduce. Lifts the
    round-3 'pipe caps at ZeRO-1' composition block (VERDICT missing #4)."""
    mesh_pp = make_mesh(MeshConfig(pipe=2, data=4))
    model = Transformer(CFG)
    plan_pp = make_plan(model, make_optimizer(OPT), mesh_pp, (2, 16), 2)
    s_pp = init_train_state(
        model, make_optimizer(OPT), jax.random.PRNGKey(0), mesh_pp, (2, 16), plan_pp
    )
    # shard-aware clip norm, as the trainer wires it (trainer.py tx_factory)
    step_pp = make_train_step(
        model, make_optimizer(OPT), mesh_pp, plan_pp, 2, make_schedule(OPT),
        tx_factory=lambda norm_fn: make_optimizer(OPT, None, norm_fn),
    )
    mesh_dp, s_dp, step_dp = _setup(MeshConfig(), zero_stage=0)

    rng = jax.random.PRNGKey(7)
    for i in range(3):
        s_pp, mp = step_pp(s_pp, _batch(i), rng)
        s_dp, md = step_dp(s_dp, _batch(i), rng)
    np.testing.assert_allclose(float(mp["loss"]), float(md["loss"]), rtol=2e-4)
    # grad_norm must match too: adam + norm-clipping are scale-invariant, so
    # the param trajectory alone cannot catch a constant gradient-scale
    # error (found: differentiating the pipe-psum'd loss inside the manual
    # region scaled every grad by P via the psum transpose)
    np.testing.assert_allclose(
        float(mp["grad_norm"]), float(md["grad_norm"]), rtol=1e-3
    )
    for a, b in zip(jax.tree.leaves(s_pp.params), jax.tree.leaves(s_dp.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)

    txt = step_pp.lower(s_pp, _batch(9), rng).compile().as_text()
    assert "reduce-scatter" in txt, "no literal reduce-scatter in pipe ZeRO-2 HLO"


def test_pp_1f1b_matches_dp_trajectory(devices):
    """The 1F1B schedule (hand-placed vjp per tick, O(P) input stash +
    recompute) is the same math as GPipe and the fused step — identical
    training trajectory within float tolerance. Gradient accumulation ORDER
    differs (per-microbatch as backwards complete vs one reverse sweep), so
    exact bitwise equality is not the contract."""
    mesh_pp, s_pp, step_pp = _setup(MeshConfig(pipe=2, data=4, pp_schedule="1f1b"))
    mesh_dp, s_dp, step_dp = _setup(MeshConfig())
    rng = jax.random.PRNGKey(7)
    for i in range(3):
        s_pp, mp = step_pp(s_pp, _batch(i), rng)
        s_dp, md = step_dp(s_dp, _batch(i), rng)
    np.testing.assert_allclose(float(mp["loss"]), float(md["loss"]), rtol=2e-4)
    # scale check, not just direction: clipping+adam hide constant factors
    np.testing.assert_allclose(
        float(mp["grad_norm"]), float(md["grad_norm"]), rtol=1e-3
    )
    for a, b in zip(jax.tree.leaves(s_pp.params), jax.tree.leaves(s_dp.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_pp_1f1b_four_stages_and_remat(devices):
    cfg = dataclasses.replace(CFG, remat=True)
    mesh_pp, s_pp, step_pp = _setup(
        MeshConfig(pipe=4, data=2, pp_schedule="1f1b"), model_cfg=cfg
    )
    mesh_dp, s_dp, step_dp = _setup(MeshConfig(), model_cfg=cfg)
    rng = jax.random.PRNGKey(5)
    s_pp, mp = step_pp(s_pp, _batch(0), rng)
    s_dp, md = step_dp(s_dp, _batch(0), rng)
    np.testing.assert_allclose(float(mp["loss"]), float(md["loss"]), rtol=2e-4)


def test_pp_1f1b_zero2_matches_dp_trajectory(devices):
    """1F1B x explicit ZeRO-2 (round-4 VERDICT weak #3: the composition a
    large-model pipe run on small-HBM chips actually wants — O(P) stash AND
    sharded grads/optimizer). The 1F1B engine's (loss, grads) feed the same
    ZeroCollectives core as GPipe; trajectory, grad_norm (scale check —
    adam+clip hide constant factors), and literal reduce-scatters in the
    compiled HLO are the contract."""
    mesh_pp = make_mesh(MeshConfig(pipe=2, data=4, pp_schedule="1f1b"))
    model = Transformer(CFG)
    plan_pp = make_plan(model, make_optimizer(OPT), mesh_pp, (2, 16), 2)
    s_pp = init_train_state(
        model, make_optimizer(OPT), jax.random.PRNGKey(0), mesh_pp, (2, 16), plan_pp
    )
    step_pp = make_train_step(
        model, make_optimizer(OPT), mesh_pp, plan_pp, 2, make_schedule(OPT),
        tx_factory=lambda norm_fn: make_optimizer(OPT, None, norm_fn),
        pp_schedule="1f1b",
    )
    mesh_dp, s_dp, step_dp = _setup(MeshConfig(), zero_stage=0)

    rng = jax.random.PRNGKey(7)
    for i in range(3):
        s_pp, mp = step_pp(s_pp, _batch(i), rng)
        s_dp, md = step_dp(s_dp, _batch(i), rng)
    np.testing.assert_allclose(float(mp["loss"]), float(md["loss"]), rtol=2e-4)
    np.testing.assert_allclose(
        float(mp["grad_norm"]), float(md["grad_norm"]), rtol=1e-3
    )
    for a, b in zip(jax.tree.leaves(s_pp.params), jax.tree.leaves(s_dp.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)

    txt = step_pp.lower(s_pp, _batch(9), rng).compile().as_text()
    assert "reduce-scatter" in txt, "no literal reduce-scatter in 1F1B ZeRO-2 HLO"


def test_pp_1f1b_bf16_accum_matches_f32(devices):
    """grad_accum_dtype=bfloat16 composes with 1F1B (the knob's target
    regime: O(P) stash AND a half-size accumulator carry — the 16 GB
    large-model recipe, see ``zero.py::_accum_add``): trajectory tracks the
    f32-accumulator 1F1B run closely. GPipe's rejection is covered in
    ``test_zero.py::test_grad_accum_dtype_rejections``."""
    pp = MeshConfig(pipe=2, data=4, pp_schedule="1f1b")
    _, s32, step32 = _setup(pp, grad_accum_dtype="float32")
    _, sbf, stepbf = _setup(pp, grad_accum_dtype="bfloat16")
    rng = jax.random.PRNGKey(7)
    for i in range(3):
        s32, m32 = step32(s32, _batch(i), rng)
        sbf, mbf = stepbf(sbf, _batch(i), rng)
    np.testing.assert_allclose(float(mbf["loss"]), float(m32["loss"]), rtol=5e-3)
    for a, b in zip(jax.tree.leaves(sbf.params), jax.tree.leaves(s32.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)


# ------------------------------------------------------ interleaved schedule


def test_interleaved_slot_dataflow():
    """Prove the interleaved schedule's index arithmetic by simulating the
    ring with symbolic values: every valid (rank, tick) consuming chunk
    v > 0 of microbatch m must find EXACTLY chunk v-1's output in its inbox
    (invalid ticks produce garbage, as the real engine's clipped compute
    does — stale-but-right values can't mask a schedule bug), and every
    microbatch must retire through the final stage. This is the same
    ``interleaved_slot`` the traced engine runs, on concrete ints."""
    for P in (2, 4):
        for V in (2, 4):
            for M in (P, 2 * P, 4 * P):
                outbox = [("init", r) for r in range(P)]
                done = []
                for t in range(V * M + P - 1):
                    inbox = [outbox[(r - 1) % P] for r in range(P)]
                    new_out = [None] * P
                    for r in range(P):
                        valid, mb, v, chunk, first, final = (
                            x if isinstance(x, bool) else int(x)
                            for x in interleaved_slot(t, r, P, V, M)
                        )
                        if not valid:
                            new_out[r] = ("garbage", t, r)
                            continue
                        if not first:
                            assert inbox[r] == ("h", mb, chunk - 1), (
                                P, V, M, t, r, inbox[r], (mb, chunk),
                            )
                        new_out[r] = ("h", mb, chunk)
                        if final:
                            assert chunk == P * V - 1
                            done.append(mb)
                    outbox = new_out
                # final stage retires microbatches in order, all of them
                assert done == list(range(M)), (P, V, M, done)


def test_bubble_fraction_formulas():
    """The ONE analytic bubble formula (trainer gauge, memory_analysis, and
    the step bench all read this function — they must never disagree)."""
    assert bubble_fraction("gpipe", 4, 16) == pytest.approx(3 / 19)
    assert bubble_fraction("1f1b", 4, 16) == pytest.approx(6 / 22)
    assert bubble_fraction("interleaved", 4, 16, 2) == pytest.approx(3 / 35)
    assert bubble_fraction("interleaved", 4, 16, 4) == pytest.approx(3 / 67)
    # no pipe axis -> no bubble
    assert bubble_fraction("gpipe", 1, 16) == 0.0
    # deeper interleave monotonically shrinks the bubble
    fr = [bubble_fraction("interleaved", 8, 16, v) for v in (1, 2, 4)]
    assert fr[0] > fr[1] > fr[2]
    with pytest.raises(ValueError, match="pp_schedule"):
        bubble_fraction("zigzag", 4, 16)


def test_interleaved_config_validation():
    with pytest.raises(ValueError, match="pp_interleave"):
        MeshConfig(pipe=2, data=4, pp_schedule="interleaved", pp_interleave=0)
    with pytest.raises(ValueError, match="only applies"):
        MeshConfig(pipe=2, data=4, pp_schedule="gpipe", pp_interleave=2)
    with pytest.raises(ValueError, match="exactly gpipe"):
        MeshConfig(pipe=2, data=4, pp_schedule="interleaved", pp_interleave=1)
    with pytest.raises(ValueError, match="pipe > 1"):
        MeshConfig(pp_schedule="interleaved", pp_interleave=2)
    MeshConfig(pipe=2, data=4, pp_schedule="interleaved", pp_interleave=2)


def test_interleaved_plan_blocks_replicated(devices):
    """Interleaved stores the block stack pipe-REPLICATED (a rank's virtual
    chunks are a round-robin set no contiguous shard holds); gpipe keeps
    the contiguous pipe shard. The engine refuses a plan/schedule mismatch
    at build time, before any tracing."""
    mesh = make_mesh(MeshConfig(pipe=2, data=4))
    model = Transformer(CFG)
    tx = make_optimizer(OPT)
    plan_il = make_plan(model, tx, mesh, (2, 16), 1, pp_schedule="interleaved")
    plan_gp = make_plan(model, tx, mesh, (2, 16), 1, pp_schedule="gpipe")
    il_specs = [
        str(ns.spec) for ns in jax.tree.leaves(plan_il.state.params["blocks"])
    ]
    gp_specs = [
        str(ns.spec) for ns in jax.tree.leaves(plan_gp.state.params["blocks"])
    ]
    assert not any("pipe" in s for s in il_specs), il_specs
    assert all("pipe" in s for s in gp_specs), gp_specs
    # non-blocks leaves keep their layout either way
    assert str(
        jax.tree.leaves(plan_il.state.params["wte"])[0].spec
    ) == str(jax.tree.leaves(plan_gp.state.params["wte"])[0].spec)

    with pytest.raises(ValueError, match="pipe-REPLICATED"):
        make_train_step(
            model, tx, mesh, plan_gp, 1, make_schedule(OPT),
            pp_schedule="interleaved", pp_interleave=2,
        )
    with pytest.raises(ValueError, match="pipe-replicated"):
        make_train_step(
            model, tx, mesh, plan_il, 1, make_schedule(OPT),
            pp_schedule="gpipe",
        )


def test_interleaved_build_validation(devices):
    mesh = make_mesh(MeshConfig(pipe=2, data=4))
    tx = make_optimizer(OPT)
    model = Transformer(CFG)
    plan = make_plan(model, tx, mesh, (2, 16), 1, pp_schedule="interleaved")
    with pytest.raises(ValueError, match="pp_interleave >= 2"):
        make_train_step(
            model, tx, mesh, plan, 1, make_schedule(OPT),
            pp_schedule="interleaved", pp_interleave=1,
        )
    with pytest.raises(ValueError, match="only applies"):
        make_train_step(
            model, tx, mesh, plan, 1, make_schedule(OPT),
            pp_schedule="gpipe", pp_interleave=2,
        )
    # n_layers=4 over pipe*V = 2*4 = 8 virtual stages: indivisible
    with pytest.raises(ValueError, match="divisible"):
        make_train_step(
            model, tx, mesh, plan, 1, make_schedule(OPT),
            pp_schedule="interleaved", pp_interleave=4,
        )


def _setup_interleaved(pp_interleave=2, zero_stage=1):
    mesh_cfg = MeshConfig(
        pipe=2, data=4, pp_schedule="interleaved", pp_interleave=pp_interleave,
        zero_stage=zero_stage,
    )
    mesh = make_mesh(mesh_cfg)
    model = Transformer(CFG)
    tx = make_optimizer(OPT)
    plan = make_plan(
        model, tx, mesh, (2, 16), zero_stage, pp_schedule="interleaved"
    )
    state = init_train_state(model, tx, jax.random.PRNGKey(0), mesh, (2, 16), plan)
    step = make_train_step(
        model, tx, mesh, plan, zero_stage, make_schedule(OPT),
        pp_schedule="interleaved", pp_interleave=pp_interleave,
    )
    return mesh, state, step


def test_pp_interleaved_matches_gpipe_and_dp(devices):
    """Interleaved runs the same per-layer math on a different wavefront:
    the trajectory must track GPipe and plain DP at the suite's pipeline
    tolerances (same fixed seed, same batches)."""
    _, s_il, step_il = _setup_interleaved()
    _, s_gp, step_gp = _setup(MeshConfig(pipe=2, data=4))
    _, s_dp, step_dp = _setup(MeshConfig())
    rng = jax.random.PRNGKey(7)
    for i in range(3):
        s_il, mi = step_il(s_il, _batch(i), rng)
        s_gp, mg = step_gp(s_gp, _batch(i), rng)
        s_dp, md = step_dp(s_dp, _batch(i), rng)
    np.testing.assert_allclose(float(mi["loss"]), float(mg["loss"]), rtol=2e-4)
    np.testing.assert_allclose(float(mi["loss"]), float(md["loss"]), rtol=2e-4)
    for a, b in zip(jax.tree.leaves(s_il.params), jax.tree.leaves(s_dp.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_pp_interleaved_zero2_matches_dp(devices):
    _, s_il, step_il = _setup_interleaved(zero_stage=2)
    _, s_dp, step_dp = _setup(MeshConfig(), zero_stage=2)
    rng = jax.random.PRNGKey(7)
    for i in range(3):
        s_il, mi = step_il(s_il, _batch(i), rng)
        s_dp, md = step_dp(s_dp, _batch(i), rng)
    np.testing.assert_allclose(float(mi["loss"]), float(md["loss"]), rtol=2e-4)
    for a, b in zip(jax.tree.leaves(s_il.params), jax.tree.leaves(s_dp.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_pp_interleaved_rejects_indivisible_microbatches(devices):
    """M % P != 0 breaks the just-in-time wrap-around hop — refused when
    the wavefront traces, not silently mis-scheduled."""
    _, state, step = _setup_interleaved()
    with pytest.raises(ValueError, match="divisible by pipe"):
        step(state, _batch(0, accum=3), jax.random.PRNGKey(7))

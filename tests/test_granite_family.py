"""The hybrid state-space family (granite-4.0-h-micro's stack: Mamba-2
mixers among grouped-head attention layers with no position encoding) at a
small size, held to its plain reference ``benchmark/reference/
granite_hybrid.py`` on seeded weights from the family's own ``leaf_table``:
the full forward, the chunked and one-step forms of the recurrence against
the sequential scan, the state through slab and paged caches, and chunked
prefill + decode in the serving engine with the state beside the K/V pages
(a parked slot, a mid-prefill slot riding the decode ticks, a padded last
chunk, a padded row, a slot used twice).

Tolerances. Everything here runs in float32 at ``highest`` against a float32
reference whose sums differ only in their order (the chunked form's
cumulative decays against a product of steps): logits of size 0.02 (the
family's table is drawn small: ``leaf_table`` says why) agree to 1.6e-8;
``TOL`` 2e-7 is twelve times what is seen. The same forward in bfloat16
misses by 1.6e-4 and more (``test_bfloat16_would_fail_the_tolerance``), and
the two state controls by a hundred tolerances and more."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark import harness, weights  # noqa: E402
from zero_transformer_tpu.config import model_config  # noqa: E402
from zero_transformer_tpu.inference import SamplingConfig  # noqa: E402
from zero_transformer_tpu.inference.generate import decode_model, init_cache  # noqa: E402
from zero_transformer_tpu.models import Transformer, mamba  # noqa: E402

TOL = 2e-7
REF = harness.load_reference({"reference": "benchmark/reference/granite_hybrid.py"})
KEYS = ("vocab_size", "d_model", "n_heads", "n_kv_heads", "n_layers", "max_seq_len",
        "head_dim", "d_ff", "layer_pattern", "mamba_heads", "mamba_head_dim", "mamba_state",
        "mamba_conv", "attention_scale", "embedding_multiplier", "residual_multiplier",
        "logits_scaling", "norm_eps", "scan_layers", "param_dtype")


def _model_group(cfg) -> dict:
    """The reference's ``model`` group from a ``ModelConfig``."""
    return {k: getattr(cfg, k) for k in KEYS}


def _family():
    cfg = model_config("granite_hybrid_test", param_dtype="float32",
                       compute_dtype="float32")
    model = _model_group(cfg)
    table = REF.leaf_table(model)
    return cfg, model, table, weights.build(table, weights.seed_key(2**31 + 7, "weights"))


@pytest.fixture(scope="module")
def family():
    return _family()


def _reference(params, tokens, model):
    with jax.default_matmul_precision("highest"):
        return REF.logits(params, jnp.asarray(tokens), model, "f32")


# ---- (a) the full forward ---------------------------------------------------


def test_leaf_table_is_the_programs_tree_and_counts_agree(family):
    cfg, model, table, _ = family
    from zero_transformer_tpu.parallel.sharding import unbox

    abstract = jax.eval_shape(
        lambda: Transformer(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    weights.check_tree(table, unbox(abstract))
    held = sum(int(np.prod(s)) for s, _ in table.values())
    assert held == cfg.num_params == 405_576
    # what a token is multiplied by: the program also counts norm scales,
    # the conv's taps and the per-head vectors, which are elementwise
    mamba_small = 160 * 4 + 160 + 3 * 4 + 128
    small = 6 * mamba_small + 8 * 2 * 64 + 64
    assert REF.active_params(model) == cfg.params_per_token - small
    assert [cfg.layer_kind(i) for i in range(5)] == [
        "mamba", "mamba", "attention", "mamba", "mamba"]
    assert cfg.layers_of("mamba") == 6 and cfg.kv_entries == 2


def test_full_forward_matches_the_reference(family):
    cfg, model, _, params = family
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 256)
    with jax.default_matmul_precision("highest"):
        got = Transformer(cfg).apply({"params": params}, toks)
    assert float(jnp.max(jnp.abs(got - _reference(params, toks, model)))) < TOL


def test_bfloat16_would_fail_the_tolerance(family):
    cfg, model, _, params = family
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 256)
    low = Transformer(dataclasses.replace(cfg, compute_dtype="bfloat16"))
    got = low.apply({"params": params}, toks).astype(jnp.float32)
    assert float(jnp.max(jnp.abs(got - _reference(params, toks, model)))) > 100 * TOL


@pytest.mark.parametrize("mode", ["bf16", "fp8", "state_bf16"])
def test_the_references_controls_fail_the_tolerance(family, mode):
    """Matmul operands rounded to bfloat16 or float8, and (``state_bf16``) what a
    request KEEPS rounded to bfloat16 at every position with the matmuls
    left in float32: each is hundreds of times the tolerance away, so a
    program that narrowed either would fail the tests above."""
    cfg, model, _, params = family
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 256)
    with jax.default_matmul_precision("highest"):
        ctrl = REF.logits(params, toks, model, mode)
    assert float(jnp.max(jnp.abs(ctrl - _reference(params, toks, model)))) > 100 * TOL


# ---- (b) the recurrence's three forms ---------------------------------------


def _ssm_inputs(T, seed=0, B=2, H=4, P=8, N=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm, Cm = jax.random.normal(ks[3], (B, T, N)), jax.random.normal(ks[4], (B, T, N))
    D = jax.random.normal(ks[5], (H,))
    h0 = jax.random.normal(ks[6], (B, H, P, N))
    return x, dt, A, Bm, Cm, D, h0


def _sequential(x, dt, A, Bm, Cm, D, h0):
    """The recurrence a position at a time, by hand."""
    h, ys = h0, []
    for t in range(x.shape[1]):
        h = jnp.exp(dt[:, t] * A)[..., None, None] * h \
            + (dt[:, t, :, None] * x[:, t])[..., None] * Bm[:, t, None, None, :]
        ys.append(jnp.sum(h * Cm[:, t, None, None, :], -1) + D[:, None] * x[:, t])
    return jnp.stack(ys, axis=1), h


@pytest.mark.parametrize("chunks", [1, 2, 5])
def test_chunked_form_is_the_sequential_scan(chunks):
    """Over 1, 2 and 5 chunks (the last one ragged), from a state that is
    not zero."""
    args = _ssm_inputs(T=8 * chunks - (3 if chunks > 1 else 0), seed=chunks)
    with jax.default_matmul_precision("highest"):
        y, h = mamba.ssd_scan(*args, chunk=8)
        y_ref, h_ref = _sequential(*args)
    assert float(jnp.max(jnp.abs(y - y_ref))) < 2e-5
    assert float(jnp.max(jnp.abs(h - h_ref))) < 2e-5


def test_one_step_form_is_the_sequential_scan():
    from zero_transformer_tpu.ops.pallas.ssm_update import ssm_update_reference

    x, dt, A, Bm, Cm, D, h0 = _ssm_inputs(T=1, seed=9)
    y, h = ssm_update_reference(h0, x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D)
    y_ref, h_ref = _sequential(x, dt, A, Bm, Cm, D, h0)
    assert float(jnp.max(jnp.abs(y - y_ref[:, 0]))) < 1e-6
    assert float(jnp.max(jnp.abs(h - h_ref))) < 1e-6


def test_positions_past_valid_leave_the_state_and_the_conv_tail_alone():
    x, dt, A, Bm, Cm, D, h0 = _ssm_inputs(T=8, seed=3)
    real = jnp.arange(8)[None, :, None] < jnp.asarray([5, 0])[:, None, None]
    _, h = mamba.ssd_scan(x, jnp.where(real, dt, 0.0), A, Bm, Cm, D, h0, chunk=8)
    _, h_ref = _sequential(x[:1, :5], dt[:1, :5], A, Bm[:1, :5], Cm[:1, :5], D, h0[:1])
    assert float(jnp.max(jnp.abs(h[0] - h_ref[0]))) < 2e-5
    assert bool(jnp.all(h[1] == h0[1]))  # no real token: bit for bit
    xbc = jax.random.normal(jax.random.PRNGKey(4), (2, 8, 6))
    tail = jax.random.normal(jax.random.PRNGKey(5), (2, 3, 6))
    w, b = jnp.ones((4, 6)), jnp.zeros((6,))
    _, kept = mamba.causal_conv(xbc, tail, w, b, jnp.asarray([5, 0]))
    assert bool(jnp.all(kept[0] == xbc[0, 2:5])) and bool(jnp.all(kept[1] == tail[1]))
    _, kept = mamba.causal_conv(xbc, tail, w, b, jnp.asarray([2, 8]))
    assert bool(jnp.all(kept[0] == jnp.concatenate([tail[0, 2:], xbc[0, :2]])))
    assert bool(jnp.all(kept[1] == xbc[1, 5:]))


# ---- (e) through the caches -------------------------------------------------


@pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
def test_state_and_grouped_heads_through_the_cache_are_the_full_forward(family, paged):
    """Prefill 13 positions (chunked form from a zero state), then 7
    single-token steps (one-step form) through the cache, grouped heads with
    no position encoding and the 1/16 scale, against the full forward."""
    cfg, model, _, params = family
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 20), 0, 256)
    dm = decode_model(cfg, 32, kv_pages=(2 * 4 + 1, 8) if paged else None)
    cache = init_cache(dm, 2)
    if paged:
        table = 1 + jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
        cache = jax.tree_util.tree_map_with_path(
            lambda p, x: jnp.broadcast_to(table, x.shape) if "block_table" in str(p[-1]) else x,
            cache)
        assert cache["ssm_state"].shape == (6, 2, 4, 32, 16)
        assert cache["conv_state"].shape == (6, 2, 3 * 160)
        assert cache["cached_key"].shape == (2, 9, 8, 2 * 16)
    outs = []
    with jax.default_matmul_precision("highest"):
        for window in [toks[:, :13]] + [toks[:, t:t + 1] for t in range(13, 20)]:
            logits, out = dm.apply({"params": params, "cache": cache}, window, mutable=["cache"])
            cache = out["cache"]
            outs.append(logits)
    got = jnp.concatenate(outs, axis=1)
    assert float(jnp.max(jnp.abs(got - _reference(params, toks, model)))) < TOL


@pytest.mark.parametrize("field,value,factor", [
    ("embedding_multiplier", 1.0, 100), ("residual_multiplier", 1.0, 100),
    ("logits_scaling", 1.0, 100), ("attention_scale", None, 3), ("position", "alibi", 10)])
def test_each_multiplier_the_scale_and_no_position_are_read(family, field, value, factor):
    """Dropping any one of them moves the logits outside the tolerance (the
    scale least: two of eight small layers attend, and 1/4 for 1/16 only
    sharpens a softmax over near-equal scores)."""
    cfg, model, _, params = family
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0, 256)
    with jax.default_matmul_precision("highest"):
        got = Transformer(dataclasses.replace(cfg, **{field: value})).apply(
            {"params": params}, toks)
    assert float(jnp.max(jnp.abs(got - _reference(params, toks, model)))) > factor * TOL


# ---- (c), (d) the engine ----------------------------------------------------


def _engine(cfg, params, **over):
    from zero_transformer_tpu.serving import ServingEngine

    args = dict(n_slots=4, cache_len=64, eos_token_id=None,
                sampling=SamplingConfig(greedy=True, repetition_penalty=1.0),
                prefill_chunk=8, page_size=4, page_pool_tokens=256)
    return ServingEngine(cfg, params, **dict(args, **over))


def _serve(engine, prompts, n_new=6):
    """Serve ``prompts`` a tick at a time and return the handles with every
    logits row the engine held for a decoding slot after a tick: ``(request,
    positions consumed, row)``. A random tied-embedding model's greedy
    stream is all but constant, so tokens prove nothing: the LOGITS are
    compared."""
    rows = []
    with jax.default_matmul_precision("highest"):
        handles = [engine.submit(prompts[0], max_new_tokens=n_new, seed=0)]
        for tick in range(200):
            if tick == 2:
                # the first request decodes while the others are still
                # mid-prefill (19 tokens are three chunks), so their slots
                # ride its decode ticks
                handles += [engine.submit(p, max_new_tokens=n_new, seed=i + 1)
                            for i, p in enumerate(prompts[1:])]
            if not engine.step() and tick > 2:
                break
            held = np.asarray(engine._last_logits)
            for slot, act in enumerate(engine._active):
                if act is not None:
                    i = handles.index(act.handle)
                    rows.append((i, len(prompts[i]) + len(act.handle.tokens), held[slot]))
    return handles, rows


def _worst_miss(params, model, prompts, handles, rows, n_new=6):
    """The widest |held logits - the reference's| over every row a decoding
    slot held: the reference's full forward over prompt + served tokens."""
    served = [h.result() for h in handles]
    assert all(len(s) == n_new for s in served)
    ref = [np.asarray(_reference(params, [p + s], model)[0]) for p, s in zip(prompts, served)]
    assert len(rows) >= len(prompts) * (n_new - 1)
    return max(float(np.max(np.abs(row - ref[i][consumed - 1]))) for i, consumed, row in rows)


PROMPT_LENS = (5, 19, 8)


def _prompts(seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, 256, size=n)] for n in PROMPT_LENS]


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "interpret_kernel"])
def test_engine_chunked_prefill_then_decode_with_state_beside_the_pages(
        family, monkeypatch, kernel):
    """Three requests in four slots (one parked), prompts of one, three and
    one chunks of 8 (a padded last chunk; a padded ROW whenever one or three
    slots prefill in a tick), the second request mid-prefill while the first
    decodes: every served (greedy) token is the reference's first at its
    position, to the float32 tolerance on the logit gap."""
    cfg, model, _, params = family
    if kernel:
        monkeypatch.setenv("ZT_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("ZT_PALLAS_INTERPRET", raising=False)
    engine = _engine(cfg, params)
    prompts = _prompts()
    handles, rows = _serve(engine, prompts)
    assert _worst_miss(params, model, prompts, handles, rows) < TOL
    snap = engine.metrics_snapshot()
    assert snap["state_bytes_per_slot"] == cfg.state_bytes_per_slot == 6 * (4 * 32 * 16 * 4 + 3 * 160 * 4)
    assert snap["state_pool_bytes"] == 4 * cfg.state_bytes_per_slot
    assert snap["state_resets"] == 3 and snap["state_rows_in_use"] == 0
    assert snap["kv_bytes_per_token"] == 2 * 2 * 2 * 16 * 4  # two layers attend
    assert snap["kernel_paged_attention"] == int(kernel)
    steps = [a for _, track, name, _, _, a in engine.tracer.spans()
             if track == "engine" and name == "decode_step"]
    assert steps and all(a["state_rows"] == a["active"] for a in steps)
    # the gauge's count rides the span: decoding + mid-prefill
    assert all(a["state_rows_in_use"] >= a["state_rows"] for a in steps)
    assert any(a["state_rows_in_use"] > a["state_rows"] for a in steps)
    assert all(a["state_bytes"] == 2 * a["active"] * cfg.state_bytes_per_slot for a in steps)
    # a tick in which a slot was mid-prefill while another decoded
    assert any(a["active"] == 1 for a in steps) and any(a["active"] == 3 for a in steps)


def test_a_slot_used_by_a_second_request_reads_a_zero_state(family):
    """One slot, two requests one after the other: the second starts from
    zeros, not from what the first left (there is no reset program: its
    first chunk reads zeros in place of the slot's state)."""
    cfg, model, _, params = family
    engine = _engine(cfg, params, n_slots=1, page_pool_tokens=64)
    prompts = _prompts(seed=1)[:2]
    for prompt in prompts:
        assert engine.slots.free_count == 1
        handles, rows = _serve(engine, [prompt], n_new=4)
        assert _worst_miss(params, model, [prompt], handles, rows, 4) < TOL
        assert float(jnp.max(jnp.abs(engine.slots.cache["ssm_state"]))) > 0
    assert engine.metrics_snapshot()["state_resets"] == 2


@pytest.mark.parametrize("control", ["advance_on_padding", "ungated_decode", "bfloat16_state"])
def test_state_controls_are_far_outside(family, monkeypatch, control):
    """What the two gates are for. A chunk program that runs the state
    through its padded tail, and a fused step that advances every slot's
    state whoever decodes, each hold logits hundreds of times the tolerance
    from the reference's (3.7e-4 for ONE stray token in one slot, 1e-2 for a
    padded tail). And what the state's float32 is for: held in bfloat16
    between ticks it is 16 times the tolerance off after six tokens
    (3.3e-6). On the chip no statistic of the served gap holds that
    (PERF.md section 6, PR 33: bfloat16 matmuls drown it), so this test is
    what does."""
    from zero_transformer_tpu.models import gpt
    from zero_transformer_tpu.serving import engine as eng

    cfg, model, _, params = family
    real_apply = Transformer.apply
    real_leaves = mamba.mamba_state_leaves

    def narrow(cfg, rows, dtype):
        shape, _ = real_leaves(cfg, rows, dtype)[mamba.SSM_LEAF]
        return dict(real_leaves(cfg, rows, dtype), **{mamba.SSM_LEAF: (shape, jnp.bfloat16)})

    if control == "bfloat16_state":
        monkeypatch.setattr(mamba, "mamba_state_leaves", narrow)
        monkeypatch.setattr(gpt, "mamba_state_leaves", narrow)

    def blind(self, variables, tokens, *a, valid=None, **kw):
        T = tokens.shape[1]
        if valid is not None and (T > 1) == (control == "advance_on_padding"):
            valid = jnp.full_like(valid, T)
        return real_apply(self, variables, tokens, *a, valid=valid, **kw)

    monkeypatch.setattr(Transformer, "apply", blind)
    # the shared jitted programs hold the healthy trace, and jax keys a
    # trace by the function under the jit: the engine's own jits, traced anew
    monkeypatch.setattr(eng, "_FUSED_SHARED", eng._jit_fused_step(fresh=True))
    monkeypatch.setattr(eng, "_PAGED_CHUNK_SHARED", eng._jit_paged_chunk(fresh=True))
    engine = _engine(cfg, params)
    prompts = _prompts()
    handles, rows = _serve(engine, prompts)
    far = 10 if control == "bfloat16_state" else 100
    assert _worst_miss(params, model, prompts, handles, rows) > far * TOL


# ---- (f) published-size counts ----------------------------------------------


def test_published_size_counts_state_and_kv_bytes():
    from zero_transformer_tpu.analysis.memory import kv_bytes_per_token, state_bytes_per_slot

    cfg = model_config("granite_4_0_h_micro")
    mixer = 2048 * 8512 + 4352 * 4 + 4352 + 3 * 64 + 4096 + 4096 * 2048
    assert mixer == 25_847_232
    mlp = 3 * 2048 * 8192
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert mixer + mlp + 4096 == 76_182_976 and attn + mlp + 4096 == 60_821_504
    assert cfg.num_params == cfg.params_per_token == 3_191_396_096
    assert cfg.num_params == 36 * 76_182_976 + 4 * 60_821_504 + 100_352 * 2048 + 2048
    assert cfg.layers_of("mamba") == 36 and cfg.kv_entries == 4
    assert [i for i in range(40) if cfg.layer_kind(i) == "attention"] == [5, 15, 25, 35]
    # a request's state, whatever its length: 75.5 MB of float32 SSM state
    # and 0.94 MB of conv inputs; a cached position: 4 layers x 2 x 512 x 2 B
    assert 36 * 64 * 64 * 128 * 4 == 75_497_472
    assert state_bytes_per_slot(cfg) == cfg.state_bytes_per_slot == 75_497_472 + 36 * 3 * 4352 * 2
    assert kv_bytes_per_token(cfg) == 8_192
    model = dict(_model_group(cfg))
    assert REF.state_bytes_per_slot(model) == cfg.state_bytes_per_slot
    assert sum(int(np.prod(s)) for s, _ in REF.leaf_table(model).values()) == cfg.num_params


def test_every_other_zoo_entry_keeps_no_state_and_its_counts():
    from zero_transformer_tpu.config import load_model_zoo

    for name, cfg in load_model_zoo().items():
        if not name.startswith("granite"):
            assert cfg.state_bytes_per_slot == 0 and not cfg.hybrid, name
            assert cfg.kv_entries == cfg.n_loops * cfg.n_layers, name
    assert model_config("580m").num_params == 586_931_712
    assert model_config("1_3b").num_params == 1_311_082_496


# ---- (g) the refusals -------------------------------------------------------


@pytest.mark.parametrize("over,match", [
    (dict(layer_pattern=("mamba", "conv")), "one period"),
    (dict(n_layers=6), "multiple of len"),
    (dict(mamba_heads=0), "mamba_heads"),
    (dict(mamba_state=0), "mamba_state"),
    (dict(mamba_head_dim=0), "mamba_head_dim"),
    (dict(mamba_conv=1), "mamba_conv >= 2"),
    (dict(scan_layers=False), "needs scan_layers=True"),
    (dict(kv_cache_dtype="int8"), "recurrent state"),
    (dict(n_loops=2), "one pass"),
    (dict(n_experts=4), "dense MLPs"),
    (dict(position="sinusoid"), "invalid position"),
])
def test_configuration_refuses_what_the_family_has_not(over, match):
    with pytest.raises(ValueError, match=match):
        model_config("granite_hybrid_test", **over)


def test_pipeline_stages_refuse_a_hybrid_stack():
    from zero_transformer_tpu.parallel import pipeline

    with pytest.raises(NotImplementedError, match="hybrid"):
        pipeline.check_supported(model_config("granite_hybrid_test"))
    pipeline.check_supported(model_config("test"))


def test_engine_refuses_by_name_what_needs_the_state_at_another_position(family):
    cfg, _, _, params = family
    with pytest.raises(ValueError, match="draft_k > 0 is refused.*recurrent state"):
        _engine(cfg, params, draft_k=2)
    with pytest.raises(ValueError, match="multiple of prefill_chunk.*recurrent state"):
        _engine(cfg, params, cache_len=60, prefill_chunk=8)
    with pytest.raises(ValueError, match="role must be 'mixed'.*recurrent state"):
        _engine(cfg, params, role="prefill")
    engine = _engine(cfg, params, prefix_cache_chunks=256)
    assert engine._prefix_cache is None  # asked for, not built, said once
    events = [e for e in engine.flight.events() if "prefix_cache_refused" in e]
    assert len(events) == 1 and events[0][-1]["asked"] == 256
    handle = engine.submit([1, 2, 3], max_new_tokens=2)
    engine.step()
    assert engine.request_migration(handle.rid, "http://elsewhere") is False
    assert engine.request_migrate_all("http://elsewhere") == 0
    with pytest.raises(ValueError, match="export_page_span is refused.*recurrent state"):
        engine.slots.export_page_span(0, 3)
    rejected = engine.import_stream({"kind": "decode"})
    assert rejected.status == "rejected" and "recurrent state" in rejected.error
    snap = engine.metrics_snapshot()
    assert snap["state_refusals_prefix_cache"] == 1 and snap["state_refusals_page_span"] == 3
    engine.run_until_idle()
    assert len(handle.result()) == 2


def test_an_attention_only_model_is_refused_nothing(family):
    """The refusals go by what the model keeps, not by the engine."""
    cfg = model_config("test")
    params = Transformer(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    engine = _engine(cfg, params, cache_len=32, prefill_chunk=8, draft_k=2, page_pool_tokens=128)
    assert engine._prefix_cache is not None and not engine._has_state
    assert engine.metrics_snapshot()["state_pool_bytes"] == 0


def test_serving_form_keeps_what_the_mixer_reads_in_float32():
    """``serving_params`` converts the matrices a bfloat16 model multiplies
    and leaves what the mixer reads in float32 THE SAME ARRAYS: ``A_log``,
    ``dt_bias``, ``D``, the gated norm's scale, the conv's taps and bias."""
    from zero_transformer_tpu.inference.generate import serving_params
    from zero_transformer_tpu.parallel.sharding import unbox

    cfg = model_config("granite_hybrid_test")  # float32 weights, bfloat16 compute
    model = decode_model(cfg, 32, kv_pages=(9, 4))
    params = unbox(Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    held = serving_params(model, params)
    mixer, was = held["periods"]["block_0"]["mamba"], params["periods"]["block_0"]["mamba"]
    for name in ("A_log", "dt_bias", "D", "norm_scale", "conv_kernel", "conv_bias"):
        assert mixer[name] is was[name], name
    assert mixer["in_proj"]["kernel"].dtype == mixer["out_proj"]["kernel"].dtype == jnp.bfloat16
    assert held["periods"]["block_2"]["attn"]["key"]["kernel"].dtype == jnp.bfloat16
    assert held["periods"]["block_0"]["ln_attn"]["scale"] is params["periods"]["block_0"]["ln_attn"]["scale"]

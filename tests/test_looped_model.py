"""A looped stack (``n_loops`` passes over shared layers, sandwich norms, an
exit gate, one K/V entry per (pass, layer)) against its plain reference,
``benchmark/reference/ouro_looplm.py``, on seeded weights at a small size:
d 64, 3 layers, 3 passes, 4 heads of 16, vocab 256, float32 compute.

Every path that serves or trains the model is held to the SAME reference:
the full forward, chunked prefill then paged decode through
``ServingEngine``, the contiguous cache ``generate()`` uses, an exit threshold
under 1, and ``Trainer``'s first loss.

The tolerance on logits, ``TOL``: program and reference both compute in
float32 (eps 6e-8) what differs only in the order of sums of 64-128 terms
through 9 block applications; the largest difference seen is 6e-7 on logits
of magnitude 0.7, and ``TOL`` leaves ten times that. bfloat16 compute (eps
4e-3) misses it by a hundred times: the ``bf16`` case of the forward test
asserts that it does, so the tolerance cannot quietly grow loose.
"""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zero_transformer_tpu.config import (
    CheckpointConfig, Config, DataConfig, MeshConfig, ModelConfig,
    OptimizerConfig, TrainingConfig,
)
from zero_transformer_tpu.inference.generate import (
    decode_model, generate, init_cache, prefill,
)
from zero_transformer_tpu.inference.sampling import SamplingConfig
from zero_transformer_tpu.models import Transformer
from zero_transformer_tpu.parallel.sharding import unbox
from zero_transformer_tpu.serving import ServingEngine

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
from benchmark import harness, weights  # noqa: E402

TOL = 6e-6

MODEL = {
    "vocab_size": 256, "d_model": 64, "n_heads": 4, "n_kv_heads": 4, "head_dim": 16,
    "n_layers": 3, "n_loops": 3, "d_ff": 128, "max_seq_len": 64, "position": "rope",
    "rope_theta": 1e6, "norm": "rmsnorm", "activation": "swiglu",
    "tie_embeddings": False, "post_norm": True, "exit_gate": True,
    "exit_threshold": 1.0, "param_dtype": "float32", "compute_dtype": "float32",
}


REF = harness.load_reference({"reference": "benchmark/reference/ouro_looplm.py"})


def cfg_of(model=MODEL, **over) -> ModelConfig:
    return ModelConfig(name="looped_tiny", dropout=0.0, **{**model, **over})


def seeded_params(seed: int = 7, gate_scale: float = 1.0) -> dict:
    """The benchmark's own weights for the family's leaf table. The gate's
    0.02 init leaves every lam near 1/2; ``gate_scale`` spreads them so that
    an exit threshold under 1 picks different passes at different positions."""
    params = weights.build(REF.leaf_table(MODEL), weights.seed_key(seed, "weights"))
    params["exit_gate"]["kernel"] = params["exit_gate"]["kernel"] * gate_scale
    return params


def unrolled(params: dict) -> dict:
    """The scanned tree's stacked ``blocks`` as ``block_<i>`` subtrees."""
    out = {k: v for k, v in params.items() if k != "blocks"}
    for i in range(MODEL["n_layers"]):
        out[f"block_{i}"] = jax.tree.map(lambda a: a[i], params["blocks"])
    return out


def ref_logits(params, tokens, model=MODEL):
    with jax.default_matmul_precision("highest"):
        return REF.logits(params, jnp.asarray(tokens, jnp.int32), model)


TOKENS = np.random.default_rng(3).integers(0, 256, size=(2, 40))


# ------------------------------------------------------------ full forward


@pytest.mark.parametrize("case", ["scan", "unrolled", "threshold", "remat", "bf16"])
def test_full_forward_matches_the_reference(case):
    model = dict(MODEL, exit_threshold=0.6) if case == "threshold" else MODEL
    params = seeded_params(gate_scale=60.0 if case == "threshold" else 1.0)
    cfg = cfg_of(
        model, scan_layers=case != "unrolled", remat=case == "remat",
        compute_dtype="bfloat16" if case == "bf16" else "float32",
    )
    tree = unrolled(params) if case == "unrolled" else params
    got = Transformer(cfg).apply({"params": tree}, jnp.asarray(TOKENS, jnp.int32))
    want = ref_logits(params, TOKENS, model)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    if case == "bf16":
        # the control: the precision below the test's own misses TOL widely
        assert err > 30 * TOL, err
        return
    assert err <= TOL, err
    if case == "threshold":
        # the threshold chose different passes at different positions:
        # some rows of logits are the last pass's, some are not
        last = ref_logits(params, TOKENS)
        same = jnp.all(jnp.abs(want - last) <= TOL, axis=-1)
        assert 0.1 < float(jnp.mean(same)) < 0.9, float(jnp.mean(same))


def test_the_tree_is_the_references_leaf_table_and_counts_agree():
    cfg = cfg_of()
    abstract = jax.eval_shape(
        lambda: Transformer(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    table = REF.leaf_table(MODEL)
    weights.check_tree(table, unbox(abstract))
    assert cfg.num_params == sum(int(np.prod(s)) for s, _ in table.values())
    # what a token passes through: the shared layers once a pass
    layer = cfg.layer_params
    assert cfg.params_per_token == cfg.num_params + 2 * MODEL["n_layers"] * layer
    assert cfg.kv_entries == 9
    matrices = layer - 4 * MODEL["d_model"]
    assert REF.active_params(MODEL) == 9 * matrices + 64 * 256


# ------------------------------- caches: the model's own (slab), the page pool


def _slab_logits(cfg, params, prompt_len):
    """Prefill then token-by-token decode through the model's own
    contiguous [batch, cache_len] cache: the two programs ``generate()`` is made of. Returns logits at positions
    prompt_len-1 .. T-1."""
    toks = jnp.asarray(TOKENS, jnp.int32)
    model = decode_model(cfg, 64)
    cache = init_cache(model, toks.shape[0])
    rows = []
    lg, cache = prefill(model, params, toks[:, :prompt_len], cache)
    rows.append(lg)
    for i in range(prompt_len, toks.shape[1]):
        lg, cache = prefill(model, params, toks[:, i:i + 1], cache)
        rows.append(lg)
    return jnp.stack(rows, axis=1), cache


@pytest.mark.parametrize("scan", [True, False], ids=["scan", "unrolled"])
def test_slab_prefill_and_decode_match_the_reference(scan):
    params = seeded_params()
    cfg = cfg_of(scan_layers=scan)
    got, cache = _slab_logits(cfg, params if scan else unrolled(params), 17)
    want = ref_logits(params, TOKENS)[:, 16:]
    assert float(jnp.max(jnp.abs(got - want))) <= TOL
    # one K/V entry per (pass, layer); the position advanced once a token
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        name = path[-1].key
        if name == "cache_index":
            assert np.all(np.asarray(leaf) == TOKENS.shape[1])
        else:
            lead = (3, 3) if scan else (3,)
            assert leaf.shape == lead + (2, 64, 4, 16), (name, leaf.shape)


def _engine(cfg, params, **kw):
    kw = {"prefix_cache_chunks": 8, "prefill_chunk": 16,
          "page_pool_tokens": 192, **kw}
    return ServingEngine(
        cfg, params, n_slots=4, cache_len=64, page_size=8,
        sampling=SamplingConfig(greedy=True, repetition_penalty=1.0),
        eos_token_id=None, **kw,
    )


def _slot_of(engine, handle):
    return next((i for i, a in enumerate(engine._active)
                 if a is not None and a.handle is handle), None)


def _served_logits(engine, prompt, max_new):
    """Drive the engine tick by tick. After the tick that served token j,
    ``_last_logits`` of the request's slot is the row token j + 1 will be
    sampled from: the logits at position ``len(prompt) - 1 + j``. (The row
    of the FIRST token lives inside the tick that ends the prefill; the
    caller checks that token against the reference instead.)"""
    handle = engine.submit(list(prompt), max_new_tokens=max_new, seed=0)
    rows, seen = [], 0
    for _ in range(10_000):
        engine.step()
        slot = _slot_of(engine, handle)
        if len(handle.tokens) > seen and slot is not None:
            assert len(handle.tokens) == seen + 1
            rows.append(np.asarray(engine._last_logits[slot]))
        seen = len(handle.tokens)
        if handle.status == "done":
            break
    assert handle.status == "done", handle.status
    return np.stack(rows), handle.tokens


@pytest.mark.parametrize("scan", [True, False], ids=["scan", "unrolled"])
def test_engine_chunked_prefill_then_paged_decode_match_the_reference(scan):
    """A 40-token prompt is three prefill chunks of 16; every served token's
    logits row, prefill's last and each decode tick's, against the
    reference's full forward over prompt + served tokens."""
    params = seeded_params()
    cfg = cfg_of(scan_layers=scan)
    engine = _engine(cfg, params if scan else unrolled(params))
    prompt = TOKENS[0].tolist()
    got, served = _served_logits(engine, prompt, 12)
    seq = np.asarray([prompt + served])
    want = np.asarray(ref_logits(params, seq))[0, len(prompt) - 1: -1]
    assert got.shape == want[1:].shape  # every served token's row but the first
    assert float(np.max(np.abs(got - want[1:]))) <= TOL
    # the first: sampled (greedy) from prefill's last row, inside one tick
    assert want[0].max() - want[0, served[0]] <= TOL
    snap = engine.metrics_snapshot()
    assert snap["kv_bytes_per_token"] == 2 * 9 * 4 * 16 * 4  # K, V x 9 entries, f32
    assert snap["loop_passes"] == 3 * sum(
        1 for _, track, name, *_ in engine.tracer.spans()
        if track == "engine" and name == "decode_step")
    attrs = [a for _, track, name, _, _, a in engine.tracer.spans()
             if track == "engine" and name == "decode_step"]
    assert attrs and all(a["loops"] == 3 and a["pages_in_use"] > 0 for a in attrs)


def test_paged_engine_and_slab_generate_serve_the_same_tokens_bit_for_bit():
    """Greedy tokens through the paged engine (two requests interleaved, one
    of them retired and its slot reused, then a request that HITS the
    prefix index on the first one's two whole chunks) equal ``generate()``'s
    through the model's contiguous cache: paging and the pass axis change
    where bytes live, nothing else. The released slots' pages return to the
    pool, but for the three whole chunks the index holds (two of the first
    prompt, one of the third)."""
    params = seeded_params()
    cfg = cfg_of()
    engine = _engine(cfg, params)
    prompts = [TOKENS[0].tolist(), TOKENS[1, :9].tolist(), TOKENS[1, 5:30].tolist(),
               TOKENS[0, :33].tolist()]
    handles = [engine.submit(p, max_new_tokens=10, seed=i) for i, p in enumerate(prompts[:2])]
    for _ in range(6):
        engine.step()
    handles.append(engine.submit(prompts[2], max_new_tokens=10, seed=2))
    engine.run_until_idle()
    handles.append(engine.submit(prompts[3], max_new_tokens=10, seed=3))
    engine.run_until_idle()
    assert handles[3].prefix_hit_tokens == 32
    slab = decode_model(cfg, 64)
    for p, h in zip(prompts, handles):
        want = generate(slab, params, jnp.asarray([p], jnp.int32), 10,
                        jax.random.PRNGKey(0),
                        SamplingConfig(greedy=True, repetition_penalty=1.0))
        assert h.status == "done" and h.tokens == np.asarray(want)[0].tolist()
    assert engine.slots.free_count == 4 and sum(engine.slots.alloc_blocks) == 0
    assert engine.slots.pool.in_use == 3 * 16 // 8  # the three whole chunks the index holds


def test_admission_waits_for_pages_while_slots_are_free():
    """A pool of 8 pages under 4 slots: each request reserves 4 pages
    (20 + 10 tokens at 8 a page), so the third waits at the queue's head
    with two slots free, is counted in ``page_waits``, and is served once
    a retirement returns pages."""
    engine = _engine(cfg_of(), seeded_params(), page_pool_tokens=64, prefix_cache_chunks=0)
    handles = [engine.submit(TOKENS[0, i:i + 20].tolist(), max_new_tokens=10, seed=i)
               for i in range(3)]
    engine.step()
    assert engine.slots.free_count == 2 and engine.queue_depth == 1
    engine.run_until_idle()
    assert all(h.status == "done" and len(h.tokens) == 10 for h in handles)
    snap = engine.metrics_snapshot()
    assert snap["page_waits"] >= 1 and snap["preemptions"] == 0


def test_page_spans_carry_every_pass_and_layer():
    """A slot's exported pages hold all n_loops * n_layers entries, and an
    import into another pool gives them back byte for byte."""
    params = seeded_params()
    cfg = cfg_of()
    src = _engine(cfg, params, prefix_cache_chunks=0)
    prompt = TOKENS[0, :20].tolist()
    handle = src.submit(prompt, max_new_tokens=8, seed=0)
    while _slot_of(src, handle) is None:
        src.step()
    span = src.slots.export_page_span(_slot_of(src, handle), len(prompt))
    assert span["n_blocks"] == 3
    keys = span["leaves"]["['blocks']['attn']['cached_key']"]
    assert keys.shape == (3, 9, 8, 4, 16)  # [blocks, entries, page, KVH, D]
    assert all(np.any(keys[:2, e] != 0) for e in range(9))  # every entry written
    dst = _engine(cfg, params, prefix_cache_chunks=0)
    slot = dst.slots.acquire()
    assert dst.slots.import_page_span(slot, span)
    back = dst.slots.export_page_span(slot, len(prompt))
    for k, leaf in span["leaves"].items():
        assert np.array_equal(back["leaves"][k].view(np.uint8), leaf.view(np.uint8)), k


# ------------------------------------------------------------------ training


def test_trainer_first_loss_matches_the_reference(tmp_path, devices):
    """``Trainer`` trains the looped model through its normal path; the loss
    it logs for step 1 is the reference's on the same weights and batch
    (relative 2e-6: float32 on both sides, a mean over 8 x 31 positions)."""
    model = {k: v for k, v in MODEL.items()}
    cfg = Config(
        model=cfg_of(model, max_seq_len=32),
        mesh=MeshConfig(zero_stage=1),
        optimizer=OptimizerConfig(peak_learning_rate=1e-2, warmup_steps=1, total_steps=4),
        training=TrainingConfig(batch_size=8, train_context=32, total_steps=4,
                                evaluation_frequency=100, maximum_evaluation_steps=1,
                                log_frequency=1, seed=0),
        data=DataConfig(source="synthetic", max_context=32),
        checkpoint=CheckpointConfig(directory=str(tmp_path / "run"), save_frequency=100,
                                    async_save=False),
    )
    from zero_transformer_tpu.training.trainer import Trainer

    class Loader:
        def __init__(self):
            self.batches = []

        def __iter__(self):
            return self

        def __next__(self):
            rng = np.random.default_rng(len(self.batches))
            self.batches.append(rng.integers(0, 256, size=(1, 8, 32), dtype=np.int32))
            return self.batches[-1]

        def state(self):
            return {"steps_consumed": len(self.batches)}

        def fault_counters(self):
            return {}

    loader = Loader()
    trainer = Trainer(cfg, train_loader=loader)
    state = trainer.init_state()
    before = jax.tree.map(np.asarray, unbox(state.params))
    trainer.train(max_steps=1)
    logged = {t["step"]: t["loss"] for _, t in trainer.flight.ticks() if "loss" in t}
    with jax.default_matmul_precision("highest"):
        want = float(REF.loss(before, jnp.asarray(loader.batches[0][0]), MODEL))
    assert abs(logged[1] - want) <= 2e-6 * want, (logged[1], want)
    trainer.close()


def test_gradients_of_the_shared_weights_sum_over_the_passes():
    """Autodiff of the program's loss against autodiff of the reference's:
    each shared layer's gradient is the sum of its n_loops uses. Relative
    2e-4 of each leaf's largest entry: float32 sums of up to 3 x 2 x 39
    position terms in another order (seen: 2e-5). At the threshold of 1 the
    gate selects nothing, so its gradient is exactly nought on both sides."""
    params = seeded_params()
    toks = jnp.asarray(TOKENS, jnp.int32)
    model = Transformer(cfg_of())
    got = jax.grad(lambda p: model.apply({"params": p}, toks, labels=toks)[1])(params)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p: REF.loss(p, toks, MODEL))(params)
    flat_got, flat_want = weights.flatten(got), weights.flatten(want)
    assert set(flat_got) == set(flat_want)
    for path, w in flat_want.items():
        scale = float(jnp.max(jnp.abs(w)))
        err = float(jnp.max(jnp.abs(flat_got[path] - w)))
        if path.startswith("exit_gate"):
            assert scale == 0.0 and err == 0.0, path
        else:
            assert scale > 0 and err <= 2e-4 * scale, (path, err, scale)


# ------------------------------------------ the plain stack is the parent's


def test_one_pass_with_the_flags_off_is_the_plain_stack():
    """``n_loops`` 1, no post norms, no gate: the tree has no new leaf, the
    cache no new axis, and the logits are those of the same model built
    without naming the new fields at all."""
    plain = {k: v for k, v in MODEL.items()
             if k not in ("n_loops", "post_norm", "exit_gate", "exit_threshold")}
    a = ModelConfig(name="plain", dropout=0.0, **plain)
    b = dataclasses.replace(a, n_loops=1, post_norm=False, exit_gate=False)
    assert a == b and a.params_per_token == a.num_params and a.kv_entries == 3
    toks = jnp.asarray(TOKENS, jnp.int32)
    tree = unbox(Transformer(a).init(jax.random.PRNGKey(0), toks)["params"])
    assert set(tree) == {"wte", "blocks", "ln_f", "lm_head"}
    assert set(tree["blocks"]) == {"ln_attn", "attn", "ln_mlp", "mlp"}
    cache = init_cache(decode_model(a, 64, kv_pages=(9, 8)), 2)
    assert cache["cached_key"].shape == (3, 9, 8, 64)
    assert cache["blocks"]["attn"]["cache_index"].shape == (3,)


@pytest.mark.parametrize("what", ["exit_gate_needs_loops", "threshold_range", "n_loops"])
def test_config_refuses_what_it_cannot_mean(what):
    bad = {
        "exit_gate_needs_loops": dict(n_loops=1, exit_gate=True),
        "threshold_range": dict(exit_threshold=0.0),
        "n_loops": dict(n_loops=0, exit_gate=False),
    }[what]
    with pytest.raises(ValueError):
        cfg_of(**bad)


# ------------------------------------- what must refuse a looped model, loudly


def test_pipeline_stage_builder_refuses_a_looped_stack():
    from zero_transformer_tpu.parallel import pipeline

    with pytest.raises(NotImplementedError, match="n_loops"):
        pipeline.check_supported(cfg_of())


def test_export_refuses_a_looped_stack():
    from zero_transformer_tpu import export

    with pytest.raises(SystemExit, match="n_loops=3"):
        export.check_exportable(cfg_of())
    # a looped stack with the plain family's tree is still refused
    plain = ModelConfig(name="looped_gpt", n_loops=2)
    with pytest.raises(SystemExit, match="n_loops=2"):
        export.check_exportable(plain)
    export.check_exportable(ModelConfig(name="gpt"))

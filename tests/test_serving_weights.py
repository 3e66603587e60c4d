"""The serving form of a param tree (``inference.serving_params``): the
leaves the forward only reads through a cast to the compute dtype are that
cast's result, made once; everything else is the array that was loaded.

Held here by what it must not change: the engine's two programs — the paged
chunk prefill and the fused decode step — give BIT-equal logits from the
source tree and from its serving form, for a tiny model of every family the
registry serves, with the float32-read leaves (norm scales, exit gate,
router) drawn random so that rounding one of them would show.
"""
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zero_transformer_tpu.config import ModelConfig
from zero_transformer_tpu.inference import (
    SamplingConfig,
    decode_model,
    serve_mesh,
    serving_params,
    shard_for_inference,
)
from zero_transformer_tpu.parallel.sharding import unbox
from zero_transformer_tpu.serving import engine as eng
from zero_transformer_tpu.serving.slots import (
    INDEX_LEAVES, POOL_LEAVES, TABLE_LEAF, PagedKVCache, _leaf_name,
)

N_SLOTS, CACHE_LEN, PAGE, CHUNK = 2, 32, 4, 8
N_BLOCKS = CACHE_LEN // PAGE

FAMILIES = {
    "gpt_alibi_tied": dict(),
    "learned_positions": dict(position="learned"),
    "untied_head": dict(tie_embeddings=False),
    "unrolled_remat": dict(scan_layers=False, remat=True),
    "looped_sandwich_gate": dict(
        n_loops=2, post_norm=True, exit_gate=True, exit_threshold=0.6,
        norm="rmsnorm", activation="swiglu", position="rope",
        tie_embeddings=False,
    ),
    "moe": dict(n_experts=4, moe_top_k=2, activation="swiglu"),
    "int8_weights": dict(param_quant="int8"),
    "int8_moe": dict(param_quant="int8", n_experts=4),
}

# read in the dtype they are stored in, so never converted
KEPT = ("ln_", "exit_gate", "router")


def _model(param_dtype="float32", n_slots=N_SLOTS, **kw):
    cfg = ModelConfig(
        name="tiny", vocab_size=128, d_model=64, n_layers=2, n_heads=4,
        max_seq_len=CACHE_LEN, dropout=0.0, compute_dtype="bfloat16",
        param_dtype=param_dtype, **kw,
    )
    return decode_model(cfg, CACHE_LEN, kv_pages=(n_slots * N_BLOCKS + 1, PAGE))


def _random_params(model, seed=0):
    """An init whose every float leaf is redrawn: norm scales around 1 and
    NOT 1, gate and router wide enough to decide, int8 scales positive."""
    params = unbox(
        model.init(jax.random.PRNGKey(seed), jnp.zeros((1, 1), jnp.int32))["params"]
    )
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(flat))
    out = []
    for (path, x), key in zip(flat, keys):
        name = jax.tree_util.keystr(path)
        if not jnp.issubdtype(x.dtype, jnp.floating):
            out.append(x)
            continue
        draw = jax.random.normal(key, x.shape, jnp.float32)
        if "ln_" in name:
            draw = 1.0 + 0.3 * draw
        elif "exit_gate" in name or "router" in name:
            draw = 0.5 * draw
        elif "scale" in name:  # an int8 kernel's per-channel scale
            draw = 1e-3 * (1.0 + jnp.abs(draw))
        else:
            draw = 0.05 * draw
        out.append(draw.astype(x.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def _serve(model, params, steps=3):
    """One paged chunk prefill of two prompts, then ``steps`` fused decode
    steps, as the engine dispatches them. Returns every logits array."""
    cache = PagedKVCache(model, N_SLOTS).cache
    table = 1 + np.arange(N_SLOTS * N_BLOCKS, dtype=np.int32).reshape(N_SLOTS, N_BLOCKS)
    lens = np.array([7, 5], np.int32)
    tokens = np.zeros((N_SLOTS, CHUNK), np.int32)
    rng = np.random.default_rng(3)
    for s, n in enumerate(lens):
        tokens[s, :n] = rng.integers(1, model.cfg.vocab_size, n)
    cache, last, _ = jax.jit(eng._paged_chunk_prefill_impl, static_argnums=(0,))(
        model, params, cache, jnp.asarray(tokens), jnp.zeros(N_SLOTS, jnp.int32),
        jnp.asarray(lens), jnp.arange(N_SLOTS, dtype=jnp.int32), jnp.asarray(table),
        jnp.asarray(lens),
    )
    out = [last]
    V = model.cfg.vocab_size
    gen_mask = jnp.zeros((N_SLOTS, V), jnp.bool_)
    rngs = jnp.stack([jax.random.PRNGKey(0)] * N_SLOTS)
    step = jax.jit(eng._fused_step_impl, static_argnums=(0, 1))
    sampling = SamplingConfig(greedy=True, repetition_penalty=1.0)
    for _ in range(steps):
        _, last, cache, gen_mask, rngs, bad, _ = step(
            model, sampling, params, last, cache, gen_mask, rngs
        )
        assert not np.asarray(bad).any()
        out.append(last)
    return [np.asarray(x) for x in out]


def _leaves(tree):
    return [
        (jax.tree_util.keystr(p), x)
        for p, x in jax.tree_util.tree_leaves_with_path(tree)
    ]


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_serving_form_is_bit_equal_and_keeps_what_is_read_in_float32(family):
    model = _model(**FAMILIES[family])
    params = _random_params(model)
    held = serving_params(model, params)

    converted = 0
    for (name, src), (_, got) in zip(_leaves(params), _leaves(held)):
        assert got.shape == src.shape, name
        if any(k in name for k in KEPT) or src.dtype == jnp.int8:
            assert got is src, name  # the same array, not an equal one
        elif got is not src:
            assert src.dtype == jnp.float32 and got.dtype == jnp.bfloat16, name
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(src.astype(jnp.bfloat16)), err_msg=name
            )
            converted += 1
    # every matrix the model multiplies in bf16 was converted: a float32
    # leaf of rank >= 2 is left only where the model reads it in float32
    left = [
        name for name, x in _leaves(held)
        if x.dtype == jnp.float32 and x.ndim >= 2
        and not any(k in name for k in KEPT) and "scale" not in name
    ]
    assert not left, left
    assert converted > 0

    want, got = _serve(model, params), _serve(model, held)
    for i, (a, b) in enumerate(zip(want, got)):
        assert np.isfinite(a).all()
        np.testing.assert_array_equal(a, b, err_msg=f"{family}: logits {i}")

    # the control: rounding the WHOLE tree is not the same model
    rounded = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x, params
    )
    other = _serve(model, rounded)
    assert any((a != b).any() for a, b in zip(want, other)), (
        "casting every leaf to bfloat16 went unnoticed: the test cannot "
        "tell the serving form from a rounded tree"
    )


ROW_SLOTS = 5


@pytest.mark.parametrize("live", [(3,), (4, 0), (4, 0, 2)], ids=["1row", "2rows", "3rows"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_rows_that_prefill_are_bit_equal_to_the_whole_slot_program(family, live):
    """The chunk-prefill program over the slots that prefill, ``PREFILL_ROWS``
    to a dispatch (the last one padded; three slots are two dispatches, the
    second on the cache the first returned), against ONE program over every
    slot (row i = slot i, a slot that does not prefill a padded entry: what
    the engine ran until PR 32): the logits rows it installs, every pool
    page but the trash page, the table and the cursors are BIT-equal. Run on
    a cache that already holds every slot's first chunk, so a neighbour's
    pages are there to be damaged, and on the prompts' SECOND chunk, so the
    rows attend over cached positions through their own table rows."""
    S, R = ROW_SLOTS, eng.PREFILL_ROWS
    assert R == 2
    model = _model(n_slots=S, **FAMILIES[family])
    cfg = model.cfg
    params = serving_params(model, _random_params(model))
    table = 1 + np.arange(S * N_BLOCKS, dtype=np.int32).reshape(S, N_BLOCKS)
    rng = np.random.default_rng(5)
    prompts = rng.integers(1, cfg.vocab_size, (S, 2 * CHUNK - 3)).astype(np.int32)
    program = jax.jit(eng._paged_chunk_prefill_impl, static_argnums=(0,))

    def chunk(cache, rows, start, index_after):
        """``rows`` (slot ids, ``S`` a padded entry) through one chunk."""
        rows = np.asarray(rows, np.int32)
        real = rows < S
        tokens = np.zeros((len(rows), CHUNK), np.int32)
        window = prompts[rows[real], start:start + CHUNK]
        tokens[real, :window.shape[1]] = window
        return program(
            model, params, cache, jnp.asarray(tokens),
            jnp.asarray(np.where(real, start, 0).astype(np.int32)),
            jnp.asarray(np.where(real, prompts.shape[1], 0).astype(np.int32)),
            jnp.asarray(rows), jnp.asarray(table),
            jnp.asarray(index_after, jnp.int32),
        )

    cache, _, _ = chunk(PagedKVCache(model, S).cache, range(S), 0, [CHUNK] * S)
    after = [prompts.shape[1] if s in live else CHUNK for s in range(S)]
    got_cache, got_last = cache, 0.0
    for i in range(0, len(live), R):
        group = list(live[i:i + R])
        got_cache, last, _ = chunk(got_cache, group + [S] * (R - len(group)), CHUNK, after)
        assert not np.asarray(last)[[s for s in range(S) if s not in group]].any()
        got_last = got_last + np.asarray(last)
    want_cache, want_last, _ = chunk(
        cache, [s if s in live else S for s in range(S)], CHUNK, after)

    assert got_last.shape == want_last.shape == (S, cfg.vocab_size)
    assert np.isfinite(np.asarray(want_last)).all()
    np.testing.assert_array_equal(np.asarray(got_last), np.asarray(want_last))
    assert np.asarray(want_last)[list(live)].any(axis=1).all()
    pools = 0
    with_path = jax.tree_util.tree_leaves_with_path
    for (path, got), (_, want), (_, before) in zip(
            with_path(got_cache), with_path(want_cache), with_path(cache)):
        name, leaf = jax.tree_util.keystr(path), _leaf_name(path)
        assert got.shape == want.shape == before.shape, name
        got, want = np.asarray(got), np.asarray(want)
        if leaf in POOL_LEAVES:
            page_axis = got.ndim - 3
            got, want = (np.delete(x, 0, axis=page_axis) for x in (got, want))
            pools += 1
            # the chunk wrote the live rows' pages, and nobody else's
            changed = np.delete(np.asarray(before), 0, axis=page_axis) != want
            assert changed.any(), name
        else:
            assert leaf == TABLE_LEAF or leaf in INDEX_LEAVES, name
        np.testing.assert_array_equal(got, want, err_msg=name)
    assert pools >= 1


@pytest.mark.parametrize("family", ["gpt_alibi_tied", "looped_sandwich_gate"])
def test_a_tree_in_the_compute_dtype_is_returned_as_it_is(family):
    model = _model(param_dtype="bfloat16", **FAMILIES[family])
    params = _random_params(model)
    held = serving_params(model, params)
    assert held is params
    for (name, src), (_, got) in zip(_leaves(params), _leaves(held)):
        assert got is src, name


def test_a_second_conversion_converts_nothing():
    model = _model()
    held = serving_params(model, _random_params(model))
    again = serving_params(model, held)
    for (name, a), (_, b) in zip(_leaves(held), _leaves(again)):
        assert a is b, name


def test_partitioned_boxes_pass_through():
    model = _model(tie_embeddings=False)
    boxed = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32))["params"]
    held = serving_params(model, boxed)
    is_box = lambda x: isinstance(x, nn.Partitioned)  # noqa: E731
    src = jax.tree_util.tree_leaves(boxed, is_leaf=is_box)
    got = jax.tree_util.tree_leaves(held, is_leaf=is_box)
    assert len(src) == len(got) and any(is_box(x) for x in src)
    for a, b in zip(src, got):
        assert is_box(a) == is_box(b)
        if is_box(a):
            assert a.names == b.names
    assert unbox(held)["lm_head"]["kernel"].dtype == jnp.bfloat16
    assert unbox(held)["ln_f"]["scale"] is unbox(boxed)["ln_f"]["scale"]


def test_a_tensor_parallel_layout_passes_through(devices):
    model = _model()
    mesh = serve_mesh(2)
    sharded = shard_for_inference(model, _random_params(model), mesh)
    held = serving_params(model, sharded)
    split = 0
    for (name, src), (_, got) in zip(_leaves(sharded), _leaves(held)):
        assert got.sharding.is_equivalent_to(src.sharding, src.ndim), name
        split += not src.sharding.is_fully_replicated
    assert split > 0
    assert held["wte"]["embedding"].dtype == jnp.bfloat16

"""Paged KV cache + speculative serving: the ISSUE 6 parity and
refcount suite.

Two load-bearing claims:

- **Paged ≡ generate(), bitwise.** Block-table paging only changes where
  K/V bytes live, so the engine's token trajectories must be byte-identical
  to single-request ``generate()`` over the model's own contiguous cache —
  across position schemes (ALiBi / RoPE / learned), the int8 KV cache, prefix-
  cache hits (which are page-refcount bumps, not span copies), and chunked
  prefill whose chunks cross page boundaries.
- **Greedy speculation ≡ plain decode, token-for-token.** The batched
  draft-and-verify step only ever keeps a draft the model itself would
  have emitted, so speculation changes throughput, never output; k=1
  degenerates to normal decode (plus one verified draft).

The refcount half pins what the allocator may never do: free a page a live
slot or a cached prefix still maps, or evict an LRU entry that a deeper
cached chunk depends on. Everything runs the ``test`` zoo model on CPU in
float32 (bitwise claims need a deterministic backend).
"""
import jax
import jax.numpy as jnp
import pytest

from zero_transformer_tpu.config import model_config
from zero_transformer_tpu.inference.generate import decode_model, generate
from zero_transformer_tpu.inference.sampling import SamplingConfig
from zero_transformer_tpu.models import Transformer
from zero_transformer_tpu.serving import PagedPrefixIndex, ServingEngine
from zero_transformer_tpu.serving.slots import PagePool

CACHE_LEN = 48
SAMPLING = SamplingConfig(temperature=0.9, top_k=20)
GREEDY = SamplingConfig(greedy=True, temperature=0.9, top_k=20)


@pytest.fixture(scope="module")
def cfg():
    return model_config("test", dropout=0.0, compute_dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    model = Transformer(cfg)
    return model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]


@pytest.fixture(scope="module")
def reference(cfg, params):
    model = decode_model(cfg, CACHE_LEN)

    def run(prompt, seed, max_new=8, sampling=SAMPLING, p=params):
        toks = generate(
            model, p, jnp.asarray([prompt], jnp.int32), max_new,
            jax.random.PRNGKey(seed), sampling,
        )
        return jax.device_get(toks)[0].tolist()

    return run


def make_engine(cfg, params, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("cache_len", CACHE_LEN)
    kw.setdefault("sampling", SAMPLING)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("page_size", 4)
    return ServingEngine(cfg, params, **kw)


def _prompt(length, offset=0):
    return [(3 + offset + i) % 250 + 1 for i in range(length)]


# ------------------------------------------------------------------- parity


def test_paged_equals_slab_and_generate(cfg, params, reference):
    """5 mixed-length requests into 2 slots: the paged engine's every
    trajectory is byte-identical to single-request generate() over the
    model's contiguous cache. Lengths 9/17/31 make chunks cross page
    boundaries (chunk 8 = 2 pages of 4) and span multiple chunk ticks."""
    prompts = [_prompt(n, offset=i) for i, n in enumerate((2, 5, 9, 17, 31))]
    engine = make_engine(cfg, params)
    handles = [
        engine.submit(p, max_new_tokens=8, seed=i)
        for i, p in enumerate(prompts)
    ]
    engine.run_until_idle()
    assert all(h.status == "done" for h in handles)
    for i, (p, h) in enumerate(zip(prompts, handles)):
        assert h.tokens == reference(p, i)


@pytest.mark.parametrize("position", ["rope", "learned"])
def test_paged_parity_other_positions(position):
    """RoPE rotation and the learned-position decode_pos vector both ride
    the per-slot index through the paged write/gather path unchanged."""
    pcfg = model_config(
        "test", dropout=0.0, compute_dtype="float32", position=position
    )
    pparams = Transformer(pcfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    cache_len = pcfg.max_seq_len if position == "learned" else CACHE_LEN
    model = decode_model(pcfg, cache_len)
    prompt = _prompt(13)
    ref = jax.device_get(
        generate(model, pparams, jnp.asarray([prompt], jnp.int32), 6,
                 jax.random.PRNGKey(5), SAMPLING)
    )[0].tolist()
    engine = make_engine(pcfg, pparams, cache_len=cache_len, prefill_chunk=4)
    handle = engine.submit(prompt, max_new_tokens=6, seed=5)
    engine.run_until_idle()
    assert handle.status == "done" and handle.tokens == ref


def test_paged_int8_kv_parity(params):
    """int8 K/V + f32 scale leaves pool-shaped: quantize on write, dequant
    on the gathered view — still token-identical to generate()."""
    qcfg = model_config(
        "test", dropout=0.0, compute_dtype="float32", kv_cache_dtype="int8"
    )
    model = decode_model(qcfg, CACHE_LEN)
    prompt = _prompt(11)
    ref = jax.device_get(
        generate(model, params, jnp.asarray([prompt], jnp.int32), 8,
                 jax.random.PRNGKey(3), SAMPLING)
    )[0].tolist()
    engine = make_engine(qcfg, params, prefill_chunk=4, prefix_cache_chunks=8)
    handle = engine.submit(prompt, max_new_tokens=8, seed=3)
    engine.run_until_idle()
    assert handle.status == "done" and handle.tokens == ref
    # and a prefix hit over int8 PAGES stays exact too
    again = engine.submit(prompt, max_new_tokens=8, seed=3)
    engine.run_until_idle()
    assert again.prefix_hit_tokens > 0 and again.tokens == ref


def test_paged_prefix_hit_is_refcount_not_copy(cfg, params, reference):
    """A shared-prefix admission maps the CACHED pages into the new slot's
    block table (refcounts bump) instead of copying spans — and the
    trajectory stays byte-identical to generate()."""
    engine = make_engine(cfg, params, prefix_cache_chunks=16)
    prefix = _prompt(16, offset=40)
    a = engine.submit(prefix + _prompt(3, offset=7), max_new_tokens=6, seed=0)
    engine.run_until_idle()
    # the banked pages are held by BOTH the index and nothing else now
    banked = [
        p for pages in engine._prefix_cache._entries.values() for p in pages
    ]
    assert banked and all(engine.slots.pool.refs[p] >= 1 for p in banked)
    b = engine.submit(prefix + _prompt(4, offset=90), max_new_tokens=6, seed=1)
    engine.step()  # admit: the hit shares pages with the index
    shared = [
        p for p in banked if engine.slots.pool.refs[p] >= 2
    ]
    assert shared, "prefix hit did not bump any page refcount"
    engine.run_until_idle()
    assert b.prefix_hit_tokens == 16
    assert a.tokens == reference(prefix + _prompt(3, offset=7), 0, max_new=6)
    assert b.tokens == reference(prefix + _prompt(4, offset=90), 1, max_new=6)
    snap = engine.metrics_snapshot()
    assert snap["prefix_hits"] == 2 and snap["cow_copies"] == 0


# --------------------------------------------------------------- refcounts


def test_release_never_frees_cache_held_pages(cfg, params):
    """Retiring a slot decrefs its pages; pages the prefix index still
    holds survive (refcount 1) and serve a later hit — the satellite's
    'never free a page a longer-lived reference still maps'."""
    engine = make_engine(cfg, params, n_slots=1, prefix_cache_chunks=16)
    prompt = _prompt(16, offset=3) + [7, 8]
    h = engine.submit(prompt, max_new_tokens=4, seed=0)
    engine.run_until_idle()
    assert h.status == "done"
    banked = [
        p for pages in engine._prefix_cache._entries.values() for p in pages
    ]
    # the slot retired, so ONLY the index holds these pages now
    assert banked and all(engine.slots.pool.refs[p] == 1 for p in banked)
    in_use_before = engine.slots.pool.in_use
    assert in_use_before >= len(banked)
    # flush drops the index's references -> pages return to the free list
    engine._prefix_cache.flush()
    assert all(engine.slots.pool.refs[p] == 0 for p in banked)
    assert engine.slots.pool.in_use == in_use_before - len(banked)


def test_index_eviction_is_refcount_aware(cfg, params):
    """Reclaim under allocation pressure never frees (or even evicts) an
    entry whose pages a live slot still maps — evicting it would gain zero
    capacity and cost the hit. Once the slot retires, the pages become
    index-only and reclaim frees them."""
    engine = make_engine(
        cfg, params, n_slots=1, prefix_cache_chunks=2
    )
    prompt = _prompt(16, offset=11) + [9]
    hog = engine.submit(prompt, max_new_tokens=20, seed=0)
    # run prefill to completion (banks 2 chunks), then stay mid-decode
    for _ in range(4):
        engine.step()
    assert hog.status == "running"
    banked = [
        p for pages in engine._prefix_cache._entries.values() for p in pages
    ]
    assert banked and all(engine.slots.pool.refs[p] == 2 for p in banked)
    freed = engine._prefix_cache.reclaim(len(banked))
    # nothing freeable: every page is slot-mapped, so the HOT entries stay
    assert freed == 0 and len(engine._prefix_cache) == 2
    assert all(engine.slots.pool.refs[p] == 2 for p in banked)
    engine.run_until_idle()
    assert hog.status == "done"  # the slot kept valid K/V throughout
    # slot retired -> pages are index-only; now reclaim really frees
    assert all(engine.slots.pool.refs[p] == 1 for p in banked)
    freed = engine._prefix_cache.reclaim(len(banked))
    assert freed == len(banked)
    assert all(engine.slots.pool.refs[p] == 0 for p in banked)


def test_prefix_lru_evicts_leaves_before_parents():
    """After a lookup touches chunks 1..k in order, the LRU front is the
    SHALLOWEST chunk — evicting it orphans every deeper entry. Eviction
    must take the least-recent LEAF instead, and return its page."""
    pool = PagePool(6)
    pc = PagedPrefixIndex(chunk_tokens=4, capacity=3, pool=pool)
    p1 = list(range(1, 14))  # chunks at 4, 8, 12
    c1, c2, c3, x1 = ((pool.alloc(),) for _ in range(4))
    pc.store_pages(p1, 1, c1)
    pc.store_pages(p1, 2, c2)
    pc.store_pages(p1, 3, c3)
    fill, entries = pc.lookup(p1)  # LRU order now: c1, c2, c3 (front = c1)
    assert fill == 12
    other = [99] + p1[1:]
    pc.store_pages(other, 1, x1)  # forces one eviction
    assert pc.evictions == 1
    # the chain c1 -> c2 survives intact: the LEAF c3 was evicted, not c1,
    # and its page went back to the pool
    fill, entries = pc.lookup(p1)
    assert fill == 8 and entries == [c1, c2]
    assert pool.refs[c3[0]] == 0 and pool.free_count == 2


def test_paged_admission_waits_when_pool_exhausted(cfg, params):
    """Admission reserves a request's worst case up front: when the pool
    cannot cover it, the request WAITS (no preemption, no mid-decode
    fault) and admits once a retirement frees pages."""
    # pool of 32 tokens = 8 pages; each request needs ~6 pages
    engine = make_engine(
        cfg, params, n_slots=4, page_pool_tokens=32, prefill_chunk=4,
    )
    a = engine.submit(_prompt(8), max_new_tokens=12, seed=0)
    b = engine.submit(_prompt(8, offset=30), max_new_tokens=12, seed=1)
    for _ in range(3):
        engine.step()
    # only one fits: the other waits in the queue despite 4 free slots
    assert a.status == "running" and b.status == "queued"
    assert engine.queue_depth == 1
    engine.run_until_idle()
    assert a.status == "done" and b.status == "done"
    assert engine.stats["preemptions"] == 0


# ------------------------------------------------------------- speculation


@pytest.mark.parametrize("draft_k", [1, 4])
def test_spec_greedy_matches_plain_decode(cfg, params, reference, draft_k):
    """Greedy speculative serving is token-for-token identical to plain
    greedy decode (and therefore to generate()); draft_k=1 is the
    degenerate single-draft case."""
    prompts = [_prompt(n, offset=i) for i, n in enumerate((3, 7, 12))]
    engine = make_engine(cfg, params, sampling=GREEDY, draft_k=draft_k)
    handles = [
        engine.submit(p, max_new_tokens=12, seed=i)
        for i, p in enumerate(prompts)
    ]
    engine.run_until_idle()
    for i, (p, h) in enumerate(zip(prompts, handles)):
        assert h.status == "done", (h.status, h.error)
        assert h.tokens == reference(p, i, max_new=12, sampling=GREEDY)
    snap = engine.metrics_snapshot()
    assert snap["spec_ticks"] > 0 and snap["draft_tokens"] > 0


def test_spec_stochastic_completes_and_respects_budget(cfg, params):
    """Stochastic speculation (rejection rule) completes every request at
    its exact budget; trajectories are distribution- not byte-preserving,
    so only structure is pinned here (the rule's math in
    test_speculative.py)."""
    engine = make_engine(cfg, params, draft_k=3)
    handles = [
        engine.submit(_prompt(4, offset=i), max_new_tokens=9, seed=i)
        for i in range(3)
    ]
    engine.run_until_idle()
    assert all(h.status == "done" and len(h.tokens) == 9 for h in handles)


def test_spec_eos_mid_block_truncates(cfg, params, reference):
    """An EOS accepted mid-block ends the stream AT the EOS token — the
    remaining accepted drafts are discarded, matching generate()'s
    contract."""
    plain = reference(_prompt(5), 0, max_new=12, sampling=GREEDY)
    eos = plain[3]
    # greedy output may repeat: the stream ends at the FIRST occurrence
    want = plain[: plain.index(eos) + 1]
    engine = make_engine(
        cfg, params, sampling=GREEDY, draft_k=4, eos_token_id=eos
    )
    h = engine.submit(_prompt(5), max_new_tokens=12, seed=0)
    engine.run_until_idle()
    assert h.status == "done" and h.tokens == want


def test_spec_headroom_validation(cfg, params):
    """The verify forward writes draft_k positions past the cursor before
    rewinding; a request whose worst case would clamp into its own tail
    rejects at submit."""
    engine = make_engine(cfg, params, sampling=GREEDY, draft_k=4)
    bad = engine.submit(_prompt(8), max_new_tokens=CACHE_LEN - 8)
    assert bad.status == "rejected" and "draft_k" in bad.error


def test_custom_draft_fn_is_clamped(cfg, params, reference):
    """A pluggable draft source that misbehaves (wrong length, out-of-vocab
    ids) degrades acceptance, never correctness."""
    engine = make_engine(
        cfg, params, sampling=GREEDY, draft_k=3,
        draft_fn=lambda hist, k: [10 ** 9, -5],  # garbage on purpose
    )
    h = engine.submit(_prompt(6), max_new_tokens=8, seed=0)
    engine.run_until_idle()
    assert h.status == "done"
    assert h.tokens == reference(_prompt(6), 0, max_new=8, sampling=GREEDY)


def test_spec_requires_no_repetition_penalty(cfg, params):
    with pytest.raises(ValueError, match="repetition_penalty"):
        make_engine(
            cfg, params, draft_k=2,
            sampling=SamplingConfig(repetition_penalty=1.2),
        )


# ------------------------------------------------- pool layout and identity


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernel"])
def test_paged_parity_unrolled_layers(monkeypatch, kernel):
    """``scan_layers: False``: every layer owns its ``[n_pages, page,
    KVH * D]`` pool leaf and runs the stacked path's code with no layer
    index — token-identical to generate(), through the gather path and
    through the kernel (interpret mode)."""
    if kernel:
        monkeypatch.setenv("ZT_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("ZT_PALLAS_INTERPRET", raising=False)
    ucfg = model_config(
        "test", dropout=0.0, compute_dtype="float32", scan_layers=False
    )
    uparams = Transformer(ucfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    prompts = [_prompt(n, offset=i) for i, n in enumerate((5, 17))]
    model = decode_model(ucfg, CACHE_LEN)
    engine = make_engine(ucfg, uparams)
    pools = [
        leaf.shape for path, leaf in
        jax.tree_util.tree_leaves_with_path(engine.slots.cache)
        if path[-1].key == "cached_key"
    ]
    assert pools == [(engine.slots.n_pages, 4, 4 * 16)] * ucfg.n_layers
    handles = [
        engine.submit(p, max_new_tokens=8, seed=i) for i, p in enumerate(prompts)
    ]
    engine.run_until_idle()
    assert engine.metrics_snapshot()["kernel_paged_attention"] == int(kernel)
    for i, (p, h) in enumerate(zip(prompts, handles)):
        ref = generate(
            model, uparams, jnp.asarray([p], jnp.int32), 8,
            jax.random.PRNGKey(i), SAMPLING,
        )
        assert h.status == "done"
        assert h.tokens == jax.device_get(ref)[0].tolist()


@pytest.mark.parametrize("scan", [True, False], ids=["scan", "unrolled"])
def test_index_updates_leave_the_pools_where_they_are(scan):
    """Retiring a slot and installing a migrated cursor touch the int32
    cursors only: the programs take and return the INDEX_LEAVES, and every
    pool leaf of the cache is afterwards the SAME array — not a copy the
    program made because it was handed the tree."""
    from zero_transformer_tpu.serving.slots import (
        INDEX_LEAVES, POOL_LEAVES, PagedKVCache, _leaf_name,
    )

    pcfg = model_config("test", dropout=0.0, scan_layers=scan)
    kv = PagedKVCache(decode_model(pcfg, 16, kv_pages=(9, 4)), n_slots=2)

    def leaves(names):
        return [
            leaf for path, leaf in jax.tree_util.tree_leaves_with_path(kv.cache)
            if _leaf_name(path) in names
        ]

    pools = leaves(POOL_LEAVES)
    where = [p.unsafe_buffer_pointer() for p in pools]
    assert len(pools) == (2 if scan else 2 * pcfg.n_layers)
    a, b = kv.acquire(), kv.acquire()
    kv.set_cursor(a, 7)
    kv.set_cursor(b, 3)
    assert all(bool((i[..., a] == 7).all() and (i[..., b] == 3).all())
               for i in leaves(INDEX_LEAVES))
    kv.release([a])
    assert all(bool((i[..., a] == 0).all() and (i[..., b] == 3).all())
               for i in leaves(INDEX_LEAVES))
    after = leaves(POOL_LEAVES)
    assert all(x is y for x, y in zip(after, pools))
    assert [p.unsafe_buffer_pointer() for p in after] == where


@pytest.mark.parametrize(
    "tag,kw",
    [("scan_bf16", {}), ("scan_int8", {"kv_cache_dtype": "int8"}),
     ("unrolled_f32", {"scan_layers": False, "compute_dtype": "float32"})],
)
def test_page_span_from_before_the_lane_merged_pool_imports_bit_exactly(tag, kw):
    """The wire format is not the pool's layout. ``tests/fixtures/
    page_span_pr24_*.bin`` were exported by the commit BEFORE the pool was
    re-laid-out (pools ``[(L,) n_pages, page, KVH, D]``, the stacked ones
    under ``['blocks']['attn']``): they import, land head ``h`` of layer
    ``l`` in lanes ``[h * D, (h + 1) * D)`` of that layer's pool, and
    export again to the same bytes."""
    import pathlib

    import numpy as np

    from zero_transformer_tpu.serving.slots import (
        PagedKVCache, page_span_from_wire, page_span_to_wire,
    )

    blob = (
        pathlib.Path(__file__).parent / "fixtures" / f"page_span_pr24_{tag}.bin"
    ).read_bytes()
    payload = page_span_from_wire(blob)
    pcfg = model_config("test", dropout=0.0, **kw)
    kv = PagedKVCache(decode_model(pcfg, 16, kv_pages=(9, 4)), n_slots=2)
    slot = kv.acquire()
    assert kv.import_page_span(slot, payload)
    assert page_span_to_wire(kv.export_page_span(slot, payload["n_tokens"])) == blob

    pages = kv.table[slot, : payload["n_blocks"]]
    wire = payload["leaves"]
    if pcfg.scan_layers:
        sent = wire["['blocks']['attn']['cached_key']"]  # [blocks, L, page, KVH, D]
        pool = np.asarray(kv.cache["cached_key"])  # [L, n_pages, page, KVH * D]
        for l in range(pcfg.n_layers):
            np.testing.assert_array_equal(
                pool[l, pages].reshape(sent[:, l].shape), sent[:, l]
            )
    else:
        sent = wire["['block_1']['attn']['cached_value']"]  # [blocks, page, KVH, D]
        pool = np.asarray(kv.cache["block_1"]["attn"]["cached_value"])
        np.testing.assert_array_equal(pool[pages].reshape(sent.shape), sent)
        h, D = 2, pcfg.head_width
        np.testing.assert_array_equal(
            pool[pages][:, :, h * D:(h + 1) * D], sent[:, :, h]
        )


# ---------------------------------------------------------------- allocator


def test_kernel_page_counters_add_up(cfg, params):
    """What the paged decode kernel walks against what it is handed, from
    the host's own mirrors: ``kernel_pages_table`` is slots x blocks a
    decode tick (the ``decode_step`` span's ``table_pages``), and
    ``kernel_pages_live`` the pages of the rows' live extents at each of
    those ticks, an unmapped row counting as the one page the kernel
    does for it. Two short requests in three slots of 12 blocks: most of
    the table is dead at every tick."""
    engine = make_engine(cfg, params, n_slots=3, sampling=GREEDY)
    walked = []
    flush = engine.slots.flush_tables

    def recording_flush():  # called once a decode tick, inside its span
        walked.append(sum(max(1, n) for n in engine.slots.alloc_blocks))
        flush()

    engine.slots.flush_tables = recording_flush
    handles = [engine.submit(_prompt(n, i), max_new_tokens=6, seed=i)
               for i, n in enumerate((5, 13))]
    engine.run_until_idle()
    assert all(h.status == "done" for h in handles)
    attrs = [a for _, track, name, _, _, a in engine.tracer.spans()
             if track == "engine" and name == "decode_step"]
    table = 3 * (CACHE_LEN // 4)
    assert attrs and all(a["table_pages"] == table for a in attrs)
    snap = engine.metrics_snapshot()
    assert snap["kernel_pages_table"] == table * len(attrs)
    assert snap["kernel_pages_live"] == sum(walked) and len(walked) == len(attrs)
    # every row is at least one page; 5 + 6 and 13 + 6 tokens are at most
    # 3 + 5 pages of the 36
    assert 3 * len(attrs) <= snap["kernel_pages_live"] <= (1 + 3 + 5) * len(attrs)
    text = engine.prometheus_text()
    assert "kernel_pages_live" in text and "kernel_pages_table" in text


def test_page_pool_unit():
    from zero_transformer_tpu.serving.slots import PagePool

    pool = PagePool(5)  # trash + 4 real
    assert pool.free_count == 4 and pool.in_use == 0
    a, b = pool.alloc(), pool.alloc()
    assert pool.in_use == 2
    pool.incref([a])
    assert pool.decref([a]) == 0  # still slot-held
    assert pool.decref([a]) == 1  # last reference frees
    with pytest.raises(ValueError):
        pool.decref([a])
    pool.reserved = 2
    assert pool.available == pool.free_count - 2
    assert pool.decref([b]) == 1

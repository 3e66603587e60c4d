"""Latent pages (``models.mla``: one row a position a layer) through what
pages go through: the latent decode kernel in interpret mode against its
gather path, to the exactness contract ``ops/pallas/paged_attention.py``
states for its own (1 ulp bf16 / 4 ulp f32 at the output's scale); a page
span exported, put on the wire, read back and imported byte for byte;
copy-on-write; and the prefix index over latent pages in the engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zero_transformer_tpu.config import model_config
from zero_transformer_tpu.inference import SamplingConfig
from zero_transformer_tpu.inference.generate import decode_model
from zero_transformer_tpu.models import Transformer
from zero_transformer_tpu.ops.pallas import latent_attention as la
from zero_transformer_tpu.ops.pallas.parity import latent_vs_gather
from zero_transformer_tpu.parallel.sharding import unbox
from zero_transformer_tpu.serving.slots import (
    PagedKVCache, page_span_from_wire, page_span_to_wire,
)


@pytest.mark.parametrize("T", [1, 5])
@pytest.mark.parametrize("dtype,ulps", [(jnp.bfloat16, 1.0), (jnp.float32, 4.0)],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("B,H,R,V,page,n_blocks", [(4, 5, 128, 96, 16, 24), (3, 20, 256, 128, 8, 40)],
                         ids=["three_buckets", "twenty_heads"])
def test_latent_kernel_is_its_gather_path(B, H, R, V, page, n_blocks, dtype, ulps, T):
    got = latent_vs_gather(B=B, T=T, H=H, R=R, value_width=V, page=page,
                           n_blocks=n_blocks, dtype=dtype, interpret=True)
    assert got["finite"] and got["ulps"] <= ulps < got["control_ulps"], got


def test_gather_path_by_row_is_the_batched_one():
    """A prefill chunk's queries go a batch row at a time (``by_row``):
    the same numbers as all rows at once."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (3, 8, 4, 128), jnp.float32)
    pool = jax.random.normal(ks[1], (2, 13, 8, 128), jnp.float32)
    table = (1 + jax.random.permutation(ks[2], 12)).reshape(3, 4).astype(jnp.int32)
    offs = jnp.asarray([0, 9, 24], jnp.int32)
    kw = dict(value_width=96, causal=True, softmax_scale=0.1, layer=jnp.int32(1))
    a = la.gather_attention(q, pool, table, offs, **kw)
    b = la.gather_attention(q, pool, table, offs, by_row=True, **kw)
    assert float(jnp.max(jnp.abs(a - b))) < 1e-6


def test_gate_declines_what_mosaic_cannot_address(monkeypatch):
    kw = dict(T=1, H=20, S=5120, page_size=16, dtype=jnp.bfloat16)
    assert not la.supported("xla", R=640, **kw)
    assert not la.supported("auto", R=640, **kw)  # the CPU, no interpret mode
    assert la.supported("auto", R=640, interpret=True, **kw)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert la.supported("auto", R=640, **kw)
    assert not la.supported("auto", R=576, **kw)  # 4.5 lane tiles
    assert not la.supported("auto", R=640, **dict(kw, T=9))
    assert not la.supported("auto", R=640, **dict(kw, S=1 << 17))  # VMEM


@pytest.fixture(scope="module")
def glm():
    cfg = model_config("glm_test", param_dtype="float32", compute_dtype="float32")
    params = unbox(Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    return cfg, params


def _filled(cfg, n_slots=3):
    """A page cache of the latent model whose pool holds random bytes."""
    cache = PagedKVCache(decode_model(cfg, 32, kv_pages=(25, 4)), n_slots)
    rng = np.random.default_rng(3)
    cache.cache = jax.tree_util.tree_map_with_path(
        lambda p, x: jnp.asarray(rng.standard_normal(x.shape), x.dtype)
        if str(p[-1].key) == "cached_latent" else x, cache.cache)
    return cache


def _pool(cache):
    return np.stack([x for p, x in jax.tree_util.tree_leaves_with_path(cache.cache)
                     if str(p[-1].key) == "cached_latent"])


def test_latent_page_span_goes_over_the_wire_byte_for_byte(glm):
    cfg, _ = glm
    src, dst = _filled(cfg), _filled(cfg)
    names = [f"['block_{i}']['attn']['cached_latent']" for i in range(3)]
    assert list(src.wire_leaves) == names
    # a pool a block (the stack is unrolled), a row of 128 lanes (16 latent
    # + 8 key + padding) as ONE head on the wire: [page, 1, 128]
    assert all(src.wire_leaves[n][1] == (4, 1, 128) for n in names)
    slot = src.acquire()
    assert src.ensure(slot, 10)  # 3 pages
    payload = src.export_page_span(slot, 10)
    back = page_span_from_wire(page_span_to_wire(payload))
    assert back["n_blocks"] == 3 and back["page_size"] == 4
    to = dst.acquire()
    assert dst.import_page_span(to, back)
    want = _pool(src)[:, src.table[slot, :3]]
    got = _pool(dst)[:, dst.table[to, :3]]
    assert want.tobytes() == got.tobytes()
    # a span of another geometry is a wrong-fleet bug, not a capacity one
    bad = dict(back, leaves={k: v[..., :64] for k, v in back["leaves"].items()})
    with pytest.raises(ValueError, match="page-span leaf"):
        _filled(cfg).import_page_span(0, bad)


def test_copy_on_write_copies_a_latent_page(glm):
    cfg, _ = glm
    cache = _filled(cfg)
    a, b = cache.acquire(), cache.acquire()
    assert cache.ensure(a, 8)
    pages = cache.bank(a, 2)
    cache.pool.decref(pages)  # only the two slots hold them
    cache.share(b, pages)
    before = _pool(cache)[:, pages[1]].copy()
    assert cache.cow(b, 1) and cache.cow_copies == 1
    fresh = int(cache.table[b, 1])
    assert fresh != pages[1] and cache.pool.refs[pages[1]] == 1
    assert _pool(cache)[:, fresh].tobytes() == before.tobytes()
    assert _pool(cache)[:, pages[1]].tobytes() == before.tobytes()


def test_prefix_index_serves_latent_pages(glm):
    """Two requests share a 16-token prefix: the second's pages are the
    first's (a refcount bump) and its tokens are what it gets without a
    prefix cache."""
    from zero_transformer_tpu.serving import ServingEngine

    cfg, params = glm
    rng = np.random.default_rng(5)
    shared = [int(t) for t in rng.integers(0, 256, size=16)]
    prompts = [shared + [int(t) for t in rng.integers(0, 256, size=n)] for n in (3, 5)]

    def serve(prefix_cache_chunks):
        engine = ServingEngine(
            cfg, params, n_slots=2, cache_len=64, eos_token_id=None,
            sampling=SamplingConfig(greedy=True, repetition_penalty=1.0),
            prefill_chunk=8, page_size=4, prefix_cache_chunks=prefix_cache_chunks,
        )
        out = []
        for i, p in enumerate(prompts):  # one after the other: the second finds the first's
            h = engine.submit(p, max_new_tokens=5, seed=i)
            engine.run_until_idle()
            out.append(h.result())
        return out, engine.metrics_snapshot()

    with_index, snap = serve(16)
    without, cold = serve(0)
    assert with_index == without
    assert snap["prefix_hits"] > 0 and cold["prefix_hits"] == 0

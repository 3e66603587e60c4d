"""Paged-attention decode kernel parity (ops/pallas/paged_attention.py).

The kernel runs the op sequence of the gather-to-slab reference it replaces
(``jnp.take(pool, table)`` + ``ops.attention.xla_attention``'s per-row
branch) in the layouts the chip's compiler lowers, so the two may differ
only in how a backend orders a sum. The interpret-mode bar (the kernel
module's exactness contract): within 1 ulp (bf16) / 4 ulp (f32) at the
output's scale — pinned across page sizes {8, 64}, ragged block tables,
trash-page rows, int8 KV scales, chunk-boundary offsets, and the
spec-verify window. Then the ENGINE integration: a serving run with
the kernels enabled (interpret mode on this CPU image) emits byte-identical
streams to the gather engine, under strict-mode dispatch sanitizers at one
compile signature per site.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zero_transformer_tpu.ops.attention import xla_attention
from zero_transformer_tpu.ops.pallas import paged_attention as pa

CACHE_LEN = 48


def _case(B, T, H, KVH, D, page, n_blocks, dtype, alibi, int8=False, seed=0,
          offsets=None, table=None, layer=None):
    """Build (q, pools, table, offsets) and both attention paths. With
    ``layer``, the kernel reads a stacked pool at that layer index, and the
    third value returned is what a WRONG layer index reads."""
    n_pages = B * n_blocks + 4
    S = page * n_blocks
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    q = jax.random.normal(ks[0], (B, T, H, D), dtype)
    if int8:
        k_pool = jax.random.randint(
            ks[1], (n_pages, page, KVH, D), -127, 128, jnp.int32
        ).astype(jnp.int8)
        v_pool = jax.random.randint(
            ks[2], (n_pages, page, KVH, D), -127, 128, jnp.int32
        ).astype(jnp.int8)
        k_sc = jax.random.uniform(ks[5], (n_pages, page, KVH, 1), jnp.float32, 1e-3, 2e-2)
        v_sc = jax.random.uniform(ks[6], (n_pages, page, KVH, 1), jnp.float32, 1e-3, 2e-2)
    else:
        k_pool = jax.random.normal(ks[1], (n_pages, page, KVH, D), dtype)
        v_pool = jax.random.normal(ks[2], (n_pages, page, KVH, D), dtype)
        k_sc = v_sc = None
    if table is None:
        table = jax.random.randint(ks[3], (B, n_blocks), 1, n_pages, jnp.int32)
    if offsets is None:
        offsets = jax.random.randint(ks[4], (B,), 0, S - T + 1, jnp.int32)
    offsets = jnp.asarray(offsets, jnp.int32)
    table = jnp.asarray(table, jnp.int32)

    def reference(q, kp, vp, tbl, off):
        """The gather-to-slab path the kernel replaces, verbatim."""
        if int8:
            g = (jnp.take(kp, tbl, axis=0).astype(jnp.float32)
                 * jnp.take(k_sc, tbl, axis=0)).astype(dtype).reshape(B, S, KVH, D)
            gv = (jnp.take(vp, tbl, axis=0).astype(jnp.float32)
                  * jnp.take(v_sc, tbl, axis=0)).astype(dtype).reshape(B, S, KVH, D)
        else:
            g = jnp.take(kp, tbl, axis=0).reshape(B, S, KVH, D)
            gv = jnp.take(vp, tbl, axis=0).reshape(B, S, KVH, D)
        kv_valid = (jnp.arange(S)[None, :] < (off[:, None] + T)).astype(jnp.int32)
        return xla_attention(
            q, g, gv, causal=T > 1, alibi=alibi, q_offset=off,
            segment_ids=kv_valid,
        )

    ref = jax.jit(reference)(q, k_pool, v_pool, table, offsets)

    def lanes(pool):
        """The engine's pool layout: heads merged into the lane axis."""
        return None if pool is None else pool.reshape(n_pages, page, -1)

    pools = [lanes(p) for p in (k_pool, v_pool, k_sc, v_sc)]
    if layer is not None:
        # the stacked entry: layer `layer` of three, the others other bytes
        pools = [
            None if p is None else jnp.stack(
                [p if l == layer else jnp.flip(p, axis=0) for l in range(3)]
            )
            for p in pools
        ]

    @jax.jit
    def kernel(q, kp, vp, ks, vs, tbl, off, lyr):
        return pa.paged_attention(
            q, kp, vp, tbl, off, causal=T > 1, alibi=alibi, layer=lyr,
            k_scale=ks, v_scale=vs, interpret=True,
        )

    def run(lyr):
        lyr = None if lyr is None else jnp.int32(lyr)
        return np.asarray(kernel(q, *pools, table, offsets, lyr))

    if layer is None:
        return np.asarray(ref), run(None)
    return np.asarray(ref), run(layer), run((layer + 1) % 3)


def _assert_contract(ref, out):
    """Within 1 ulp (bf16) / 4 ulp (f32; observed 1-2) at the output's
    scale — summation order only, see the kernel module's docstring."""
    ulps, eps = (4, np.finfo(np.float32).eps) if ref.dtype == np.float32 else (1, 2.0**-7)
    ref, out = ref.astype(np.float32), out.astype(np.float32)
    np.testing.assert_allclose(
        out, ref, rtol=0, atol=ulps * eps * np.abs(ref).max()
    )


@pytest.mark.parametrize("page,n_blocks", [(8, 6), (64, 2)])
@pytest.mark.parametrize("alibi", [True, False])
def test_parity_vs_gather_page_sizes(page, n_blocks, alibi):
    ref, out = _case(3, 1, 4, 2, 64, page, n_blocks, jnp.float32, alibi)
    _assert_contract(ref, out)


def test_bitwise_bf16_and_gqa():
    """bf16 (the serving dtype) with grouped queries: here the f32 partial
    sums round to the same bf16 values in either order, so this case still
    holds the original bit-equal contract."""
    ref, out = _case(2, 1, 8, 2, 64, 8, 4, jnp.bfloat16, True)
    np.testing.assert_array_equal(out, ref)


def test_parity_mha_single_token():
    """MHA (G=1) single-token decode — the shape that exposed the per-head
    2-D-dot lowering divergence: XLA routes an M=1 gemv differently from
    the reference's batched einsum, so the kernel must keep the kv-head
    axis INSIDE the contraction. Pinned so a grid refactor can't silently
    reintroduce the per-head dot."""
    ref, out = _case(2, 1, 4, 4, 64, 16, 4, jnp.float32, True, seed=11)
    _assert_contract(ref, out)


def test_parity_spec_verify_window_causal():
    """T = 1 + draft_k: the spec-verify block attends causally within its
    window at each row's own offset."""
    ref, out = _case(2, 5, 4, 4, 64, 8, 4, jnp.float32, False)
    _assert_contract(ref, out)
    ref, out = _case(2, 4, 6, 6, 64, 8, 3, jnp.float32, True)
    _assert_contract(ref, out)


def test_parity_int8_kv_scales():
    """int8 pages dequantize in-register exactly like the gathered view:
    (int8 -> f32) * scale -> compute dtype, elementwise."""
    ref, out = _case(2, 1, 4, 2, 64, 8, 4, jnp.float32, True, int8=True)
    _assert_contract(ref, out)
    ref, out = _case(2, 3, 4, 2, 64, 8, 4, jnp.float32, True, int8=True, seed=7)
    _assert_contract(ref, out)


def test_parity_ragged_tables_and_trash_rows():
    """Rows at wildly different fills — including a fully-parked row whose
    zeroed table routes every read to the trash page — and offsets landing
    exactly ON and one-before page boundaries (the chunk-boundary cases)."""
    page, n_blocks = 8, 6
    B = 5
    # offsets: 0 (empty-ish), page-1, page (boundary), mid, full-1
    offsets = [0, page - 1, page, 3 * page + 5, page * n_blocks - 1]
    table = np.random.default_rng(0).integers(1, B * n_blocks + 3, (B, n_blocks))
    table[0, :] = 0  # parked row: trash page everywhere
    ref, out = _case(
        B, 1, 4, 2, 64, page, n_blocks, jnp.float32, True,
        offsets=offsets, table=table,
    )
    _assert_contract(ref, out)


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("layer", [1, 2])
def test_stacked_pool_reads_its_layer(layer, int8):
    """The scanned stack's entry: the pool is ``[n_layers, n_pages, page,
    KVH * D]`` and the layer index a scalar-prefetch operand of the page
    fetch. A non-zero layer reads exactly what the unstacked kernel reads
    from that layer's pool; another layer's index reads other pages."""
    ref, out, wrong = _case(
        2, 1, 4, 2, 64, 8, 4, jnp.float32, True, int8=int8, seed=3,
        layer=layer,
    )
    _assert_contract(ref, out)
    flat = _case(2, 1, 4, 2, 64, 8, 4, jnp.float32, True, int8=int8, seed=3)[1]
    np.testing.assert_array_equal(out, flat)
    assert np.abs(wrong - ref).max() > 1e-2


# ---- the walk is bounded by each row's own length ---------------------------

R_PAGE, R_BLOCKS = 8, 48  # S = 384: buckets 128 / 256 / 384, two staged groups
R_S = R_PAGE * R_BLOCKS


def _live_lengths(T):
    """Live lengths (``offset + T``) at every edge the walk has: the window
    alone, one short of a page edge, on it, one over; each bucket's edge and
    one over; a staged group's edge; the whole cache."""
    group = pa.stage_pages(
        page=R_PAGE, n_blocks=R_BLOCKS, KVH=2, D=64, pool_dtype=jnp.float32
    )
    widths = pa.bucket_widths(page=R_PAGE, n_blocks=R_BLOCKS)
    assert widths == (128, 256, R_S) and 1 < group < R_BLOCKS
    return {
        "window": T, "page-1": 3 * R_PAGE - 1, "page": 3 * R_PAGE,
        "page+1": 3 * R_PAGE + 1, "bucket": widths[0], "bucket+1": widths[0] + 1,
        "bucket2": widths[1], "group+1": group * R_PAGE + 1, "full": R_S,
    }


def _poisoned_case(lengths, T, H, KVH, dtype, int8, stacked, alibi=True, seed=5):
    """Rows live up to ``lengths`` positions each, on distinct pages. The
    reference is the gather path on CLEAN pools. The kernel reads pools in
    which everything it must not touch is NaN: every dead table entry
    addresses an all-NaN page, and every live page's positions past the
    row's live length are NaN (int8 pages carry the NaN in their scales)."""
    D, B = 64, len(lengths)
    poison = B * R_BLOCKS + 1  # the all-NaN page; page 0 stays the trash page
    n_pages = poison + 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (B, T, H, D), dtype)
    if int8:
        k_pool, v_pool = (
            jax.random.randint(k, (n_pages, R_PAGE, KVH, D), -127, 128, jnp.int32)
            .astype(jnp.int8) for k in ks[1:3]
        )
        k_sc, v_sc = (
            jax.random.uniform(k, (n_pages, R_PAGE, KVH, 1), jnp.float32, 1e-3, 2e-2)
            for k in ks[3:5]
        )
    else:
        k_pool, v_pool = (
            jax.random.normal(k, (n_pages, R_PAGE, KVH, D), dtype) for k in ks[1:3]
        )
        k_sc = v_sc = None
    table = 1 + np.asarray(jax.random.permutation(ks[5], B * R_BLOCKS)).reshape(B, R_BLOCKS)
    offsets = jnp.asarray([n - T for n in lengths], jnp.int32)

    def gather(pool, scale):
        x = jnp.take(pool, jnp.asarray(table), axis=0)
        if scale is not None:
            x = (x.astype(jnp.float32) * jnp.take(scale, jnp.asarray(table), axis=0)).astype(dtype)
        return x.reshape(B, R_S, KVH, D)

    kv_valid = (jnp.arange(R_S)[None, :] < (offsets[:, None] + T)).astype(jnp.int32)
    ref = xla_attention(
        q, gather(k_pool, k_sc), gather(v_pool, v_sc), causal=T > 1, alibi=alibi,
        q_offset=offsets, segment_ids=kv_valid,
    )

    dead = np.zeros((n_pages, R_PAGE), bool)  # (page, position) the kernel must not read
    dead[poison] = True
    walked = table.copy()
    for b, n in enumerate(lengths):
        live_pages = -(-n // R_PAGE)
        walked[b, live_pages:] = poison
        if n % R_PAGE:
            dead[table[b, live_pages - 1], n % R_PAGE:] = True
    dead = jnp.asarray(dead)[:, :, None, None]
    if int8:
        k_sc, v_sc = (jnp.where(dead, jnp.nan, x) for x in (k_sc, v_sc))
    else:
        k_pool, v_pool = (jnp.where(dead, jnp.nan, x).astype(dtype) for x in (k_pool, v_pool))
    pools = [
        None if x is None else x.reshape(n_pages, R_PAGE, -1)
        for x in (k_pool, v_pool, k_sc, v_sc)
    ]
    layer = None
    if stacked:  # layer 1 of three; the others hold NaN throughout
        layer = jnp.int32(1)
        pools = [
            None if x is None else jnp.stack([
                x if l == 1 else (jnp.full_like(x, jnp.nan) if x.dtype != jnp.int8 else jnp.flip(x, 0))
                for l in range(3)
            ])
            for x in pools
        ]
    out = jax.jit(
        lambda q, kp, vp, ksc, vsc, tbl, off, lyr: pa.paged_attention(
            q, kp, vp, tbl, off, causal=T > 1, alibi=alibi, layer=lyr,
            k_scale=ksc, v_scale=vsc, interpret=True,
        )
    )(q, *pools, jnp.asarray(walked, jnp.int32), offsets, layer)
    return np.asarray(ref), np.asarray(out)


@pytest.mark.parametrize("T", [1, 5])
@pytest.mark.parametrize(
    "edge",
    ["window", "page-1", "page", "page+1", "bucket", "bucket+1", "bucket2", "group+1", "full"],
)
def test_row_walks_its_live_pages_only(edge, T):
    """One row at each edge of the walk beside an idle row (offset 0) and a
    full one: what lies past a row's live length — dead table entries, the
    last live page's tail, the scratch the row before left — is all NaN or
    another row's, and none of it reaches the output."""
    n = _live_lengths(T)[edge]
    ref, out = _poisoned_case([R_S, n, T], T, 4, 2, jnp.float32, False, False)
    assert np.isfinite(out).all()
    _assert_contract(ref, out)


@pytest.mark.parametrize("stacked", [False, True], ids=["flat", "stacked"])
@pytest.mark.parametrize("T", [1, 5])
@pytest.mark.parametrize("H,KVH", [(4, 4), (4, 2)], ids=["mha", "gqa"])
@pytest.mark.parametrize(
    "dtype,int8",
    [(jnp.float32, False), (jnp.bfloat16, False), (jnp.float32, True), (jnp.bfloat16, True)],
    ids=["f32", "bf16", "f32-int8", "bf16-int8"],
)
def test_mixed_batch_of_every_edge(dtype, int8, H, KVH, T, stacked):
    """Every edge in ONE batch, short rows after long ones (each row's
    scratch and staging follow another's), for both dtypes, int8 pages,
    grouped heads, the spec-verify window and the stacked pool."""
    edges = _live_lengths(T)
    lengths = [edges[e] for e in
               ("full", "window", "bucket+1", "page-1", "bucket2", "page", "group+1", "bucket", "page+1")]
    ref, out = _poisoned_case(lengths, T, H, KVH, dtype, int8, stacked)
    assert np.isfinite(out).all()
    _assert_contract(ref, out)


def test_poison_reaches_the_output_when_it_is_live():
    """The control of the two tests above: one NaN position INSIDE a row's
    live length does reach that row's output (so the poisoned pools would
    show a read past it), and no other row's."""
    ref, out = _poisoned_case([40, 40], 1, 4, 2, jnp.float32, False, False)
    _assert_contract(ref, out)
    ref, out = _poisoned_case([40, 41], 1, 4, 2, jnp.float32, False, False)
    assert np.isfinite(out).all()
    # row 0 told it is one longer than what its last page holds clean
    D, B, n_pages = 64, 2, 2 * R_BLOCKS + 2
    k_pool = jnp.ones((n_pages, R_PAGE, 2 * D)).at[7, 3].set(jnp.nan)
    table = jnp.zeros((B, R_BLOCKS), jnp.int32).at[0, 0].set(7).at[1, 0].set(8)
    q = jnp.ones((B, 1, 4, D))
    run = lambda offs: np.asarray(pa.paged_attention(
        q, k_pool, k_pool, table, jnp.asarray(offs, jnp.int32), causal=False,
        alibi=True, interpret=True))
    assert np.isfinite(run([2, 7])).all()
    leaked = run([3, 7])
    assert np.isnan(leaked[0]).all() and np.isfinite(leaked[1]).all()


def test_gate_decisions():
    """The ONE gate both the model trace and the engine gauge consult."""
    common = dict(T=1, H=4, KVH=4, D=64, S=64, page_size=16, dtype=jnp.float32)
    assert pa.supported("auto", interpret=True, **common)
    assert pa.supported("flash", interpret=True, **common)
    assert not pa.supported("xla", interpret=True, **common)
    # decode windows only
    assert not pa.supported(
        "auto", interpret=True, **{**common, "T": pa.MAX_DECODE_T + 1}
    )
    # off-TPU without interpret: decline (the gather path is the fallback)
    if jax.default_backend() != "tpu":
        assert not pa.supported("auto", **common)
    # f16 never
    assert not pa.supported(
        "auto", interpret=True, **{**common, "dtype": jnp.float16}
    )


# ---------------------------------------------------------------- engine e2e


def test_engine_kernel_parity_and_one_signature(monkeypatch):
    """Serving run with the Pallas kernels enabled (interpret mode): every
    stream byte-identical to the gather-path engine, decode AND spec-verify
    dispatch sites at ONE compile signature under strict-mode sanitizers,
    and the paged-kernel gauge honest about what traced."""
    from zero_transformer_tpu.analysis import runtime as rt
    from zero_transformer_tpu.config import model_config
    from zero_transformer_tpu.inference.sampling import SamplingConfig
    from zero_transformer_tpu.models import Transformer
    from zero_transformer_tpu.serving import ServingEngine

    cfg = model_config("test", dropout=0.0, compute_dtype="float32")
    params = Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    prompts = [
        [(3 + i + j) % 250 + 1 for j in range(n)]
        for i, n in enumerate((2, 7, 17))
    ]

    def run(greedy, draft_k):
        sampling = SamplingConfig(greedy=True) if greedy else SamplingConfig(
            temperature=0.9, top_k=20
        )
        engine = ServingEngine(
            cfg, params, n_slots=2, cache_len=CACHE_LEN, sampling=sampling,
            prefill_chunk=8, page_size=8, draft_k=draft_k,
        )
        handles = [
            engine.submit(p, max_new_tokens=8, seed=i)
            for i, p in enumerate(prompts)
        ]
        engine.run_until_idle()
        assert all(h.status == "done" for h in handles)
        return [h.tokens for h in handles], engine

    monkeypatch.delenv("ZT_PALLAS_INTERPRET", raising=False)
    gather_plain, _ = run(greedy=False, draft_k=0)
    gather_spec, _ = run(greedy=True, draft_k=3)

    monkeypatch.setenv("ZT_PALLAS_INTERPRET", "1")
    rt.set_strict(True)
    try:
        kernel_plain, e1 = run(greedy=False, draft_k=0)
        kernel_spec, e2 = run(greedy=True, draft_k=3)
    finally:
        rt.set_strict(None)
    assert kernel_plain == gather_plain
    assert kernel_spec == gather_spec
    for engine in (e1, e2):
        snap = engine.metrics_snapshot()
        assert snap["kernel_paged_attention"] == 1
        assert snap["dispatch_paged_attention_signatures"] == 1
        assert snap["dispatch_paged_attention_violations"] == 0
        assert snap["dispatch_decode_step_violations"] == 0
        assert snap["dispatch_spec_verify_violations"] == 0


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
def test_engine_kernel_under_a_tensor_mesh(devices, monkeypatch, int8):
    """``serve --tensor 2``: the pools are allocated sharded on their merged
    lane axis (heads are contiguous in it), each device's kernel walks its
    own kv heads' lanes, and the streams are the single-device engine's."""
    from zero_transformer_tpu.config import model_config
    from zero_transformer_tpu.inference import serve_mesh, shard_for_inference
    from zero_transformer_tpu.inference.generate import decode_model
    from zero_transformer_tpu.inference.sampling import SamplingConfig
    from zero_transformer_tpu.models import Transformer
    from zero_transformer_tpu.serving import ServingEngine

    monkeypatch.setenv("ZT_PALLAS_INTERPRET", "1")
    cfg = model_config(
        "test", dropout=0.0, compute_dtype="float32",
        kv_cache_dtype="int8" if int8 else "auto",
    )
    params = Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    prompts = [[(3 + i + j) % 250 + 1 for j in range(n)]
               for i, n in enumerate((3, 11))]

    def run(mesh):
        p = params if mesh is None else shard_for_inference(
            decode_model(cfg, CACHE_LEN), params, mesh
        )
        engine = ServingEngine(
            cfg, p, n_slots=2, cache_len=CACHE_LEN, prefill_chunk=8,
            sampling=SamplingConfig(greedy=True), page_size=8, mesh=mesh,
        )
        handles = [
            engine.submit(p, max_new_tokens=8, seed=i)
            for i, p in enumerate(prompts)
        ]
        engine.run_until_idle()
        assert all(h.status == "done" for h in handles)
        assert engine.metrics_snapshot()["kernel_paged_attention"] == 1
        return [h.tokens for h in handles], engine

    single, _ = run(None)
    sharded, engine = run(serve_mesh(2))
    assert sharded == single
    for name in ("cached_key", "key_scale") if int8 else ("cached_key",):
        pool = engine.slots.cache[name]  # [L, n_pages, page, lanes]
        assert tuple(pool.sharding.spec) == (None, None, None, "tensor")


def test_flash_is_flash_or_raise_on_the_paged_decode_path(monkeypatch):
    """``attention_impl: flash`` never gets the gather fallback on a paged
    decode window: where the gate declines (here: off the TPU, interpret
    mode off) the dispatch raises and the request fails loudly; ``auto``
    takes the gather path, and with the kernels available ``flash`` serves."""
    from zero_transformer_tpu.config import model_config
    from zero_transformer_tpu.inference.sampling import SamplingConfig
    from zero_transformer_tpu.models import Transformer
    from zero_transformer_tpu.serving import ServingEngine

    auto = model_config("test", dropout=0.0, compute_dtype="float32")
    params = Transformer(auto).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]

    def serve(impl):
        cfg = model_config(
            "test", dropout=0.0, compute_dtype="float32", attention_impl=impl
        )
        engine = ServingEngine(
            cfg, params, n_slots=2, cache_len=CACHE_LEN,
            sampling=SamplingConfig(greedy=True), prefill_chunk=8,
            page_size=8,
        )
        handle = engine.submit([1, 2, 3, 4, 5], max_new_tokens=4, seed=0)
        engine.run_until_idle()
        return handle

    monkeypatch.delenv("ZT_PALLAS_INTERPRET", raising=False)
    refused = serve("flash")
    assert refused.status == "failed"
    assert "paged attention kernel unsupported" in refused.error
    gathered = serve("auto")
    assert gathered.status == "done"
    monkeypatch.setenv("ZT_PALLAS_INTERPRET", "1")
    served = serve("flash")
    assert served.status == "done" and served.tokens == gathered.tokens

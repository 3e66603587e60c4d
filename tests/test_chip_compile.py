"""The chip's compiler, without the chip: the main path's kernels compiled
for a DESCRIBED TPU v5e (``topologies.get_topology_desc``) at real widths.

Interpret mode cannot see what Mosaic refuses — a bf16 matmul accumulator, a
shape cast with no vector layout, a kernel GSPMD is asked to partition — and
all three had passed every interpret-mode test. These compiles cost about
two seconds each and guard every later PR at no chip time. Nothing runs:
a compile that passes is not a chip run (``chip_smoke.py`` is).

The topology is described inside a module-scoped fixture (never at import:
only one process at a time may load the TPU library, and every xdist worker
imports every test file); the compiles happen in the test's own process,
with the persistent compilation cache off around them (an entry compiled
for a described device cannot be read back without one).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from zero_transformer_tpu.ops import flash_attention as dispatch
from zero_transformer_tpu.ops.pallas import flash, paged_attention as pa


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or it logs under /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def data_mesh(topo):
    return Mesh(np.array(topo.devices).reshape(4), ("data",))


def _compiled_kernels(fn, *args) -> int:
    """Compile for the described device; how many Mosaic calls it holds."""
    return jax.jit(fn).lower(*args).compile().as_text().count("tpu_custom_call")


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("T", [1, 5])
@pytest.mark.parametrize("H,D", [(12, 64), (16, 128)], ids=["d64", "d128"])
def test_paged_decode_kernel_compiles(one_chip, H, D, T, int8):
    """The serving default's decode step (paged KV, page 16, a 1024 cache,
    plain decode and the 1 + draft_k verify window, bf16 and int8 pages) at
    580M / 1.3B head shapes — refused on the parent commit with "Expected
    matmul acc to be 32-bit"."""
    B, page, n_blocks = 8, 16, 64
    n_pages = B * n_blocks + 1

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((n_pages, page, H * D), jnp.int8 if int8 else jnp.bfloat16)
    scales = (sds((n_pages, page, H), jnp.float32),) * 2 if int8 else ()

    def step(q, k_pool, v_pool, table, offsets, *scales):
        k_scale, v_scale = scales or (None, None)
        return pa.paged_attention(
            q, k_pool, v_pool, table, offsets, causal=T > 1, alibi=True,
            k_scale=k_scale, v_scale=v_scale,
        )

    assert _compiled_kernels(
        step, sds((B, T, H, D), jnp.bfloat16), pool, pool,
        sds((B, n_blocks), jnp.int32), sds((B,), jnp.int32), *scales,
    ) == 1


# ---- the engine's real programs: where does the KV page pool go? -----------

N_SLOTS, PAGE, CACHE_LEN, CHUNK, DEPTH = 16, 16, 2048, 64, 2
N_PAGES = N_SLOTS * CACHE_LEN // PAGE + 1  # 2049: in no other shape here


LANES = 12 * 128  # one K/V pool row; the int8 scale pools have 12 lanes

# what the compiler's own prefetch into the core's fast memory ("S(1)" in a
# layout) is made of; a `copy` / `copy-start` whose destination is there
# belongs to it, one that lands in HBM is a copy of the pool
PREFETCH = {"slice-start", "slice-done", "ConcatBitcast", "copy-done"}


def _pool_ops(hlo: str, n_pages: int = N_PAGES, page: int = PAGE, lanes: int = LANES):
    """Every operation of an optimised HLO module that MATERIALIZES a
    pool-sized value (a result whose element count is a multiple of
    pages x page size: 2049 = 3 x 683 divides no other shape here), as
    ``(opcode, name, kv, in_loop)``: the instructions of the entry, loop
    and branch computations, and for a fusion the pool-sized operations
    inside it (a fusion is one kernel writing its result once; what it is
    made of says whether that is an in-place scatter or a copy). ``kv``
    tells a K/V pool from an int8 scale pool, ``in_loop`` the layer loop's
    body from the program's edge. Parameters, tuples and bitcasts move no
    byte and are left out."""
    import math
    import re

    comps, fused, entry, name = {}, set(), None, None
    for line in hlo.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if head and not line.startswith(" "):
            name = head.group(2)
            comps[name] = []
            entry = name if head.group(1) else entry
        elif name and " = " in line:
            comps[name].append(line)
            fused.update(re.findall(r" fusion\(.*calls=%?([\w.\-]+)", line))
    free = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
            "conditional", "call", "constant", "broadcast", "iota", "reshape"}

    def pool_sized(comp):
        for line in comps[comp]:
            m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(", line)
            if not m:
                continue
            op_name, result, opcode = m.groups()
            shapes = re.findall(r"[a-z0-9]+\[([\d,]+)\](\{[^}]*\})?", result)
            sizes = [math.prod(int(d) for d in dims.split(",")) for dims, _ in shapes]
            sizes = [n for n in sizes if n % (n_pages * page) == 0]
            if not sizes:
                continue
            if opcode == "custom-call":
                opcode = re.search(r'custom_call_target="(\w+)"', line).group(1)
            if opcode in ("copy", "copy-start") and "S(1)" in shapes[0][1]:
                opcode = "copy-done"  # destination in fast memory: prefetch
            kv = any(n % (n_pages * page * lanes) == 0 for n in sizes)
            if opcode == "fusion":
                inner = re.search(r"calls=%?([\w.\-]+)", line).group(1)
                for op, _, _ in pool_sized(inner):
                    yield op, op_name, kv
            elif opcode not in free:
                yield opcode, op_name, kv

    return [
        (op, op_name, kv, comp != entry)
        for comp in comps if comp not in fused
        for op, op_name, kv in pool_sized(comp)
    ]


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize(
    "H,n_blocks,n_pages", [(12, 128, N_PAGES), (16, 32, 161)], ids=["580m", "looped"]
)
def test_paged_kernel_takes_the_cells_pools_where_they_lie(one_chip, H, n_blocks, n_pages, int8):
    """The kernel as the two serving cells call it — 16 slots, page 16,
    heads of 128, a 2048 / 512 cache, the STACKED pool with a traced layer
    index — with its pools handed over in ``pl.ANY``: one Mosaic call, and
    no operation of the program makes a K/V-pool-sized value on the way
    to it (its own DMAs read the parameter). The int8 scale pools are
    gathered by row ([16, S, KVH]: not pool-sized) before the call."""
    B, page, D, L = N_SLOTS, PAGE, 128, 2

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((L, n_pages, page, H * D), jnp.int8 if int8 else jnp.bfloat16)
    scales = (sds((L, n_pages, page, H), jnp.float32),) * 2 if int8 else ()

    def step(q, k_pool, v_pool, table, offsets, layer, *scales):
        k_scale, v_scale = scales or (None, None)
        return pa.paged_attention(
            q, k_pool, v_pool, table, offsets, causal=False, layer=layer,
            alibi=True, k_scale=k_scale, v_scale=v_scale,
        )

    text = jax.jit(step).lower(
        sds((B, 1, H, D), jnp.bfloat16), pool, pool,
        sds((B, n_blocks), jnp.int32), sds((B,), jnp.int32), sds((), jnp.int32),
        *scales,
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    # (a pool small enough for the core's fast memory may be staged there
    # by the compiler's own prefetch: the looped cell's int8 pool cut to 2
    # layers is)
    made = [o for o in _pool_ops(text, n_pages, page, H * D)
            if o[2] and o[0] not in PREFETCH]
    assert not made, made


def _aliases(hlo: str) -> int:
    """How many outputs of a compiled program alias one of its inputs."""
    return hlo.split("input_output_alias={", 1)[1].split("}, entry", 1)[0].count("-alias")


def _cfg_580m_cut(int8: bool, scan: bool):
    """The 580M serving model's structure (d 1536, 12 heads of 128, float32
    weights, bf16 compute; depth cut to keep the compile in seconds)."""
    from zero_transformer_tpu.config import ModelConfig

    return ModelConfig(
        name="serve_580m_cut", d_model=1536, n_layers=DEPTH, n_heads=12,
        head_dim=128, d_ff=6144, vocab_size=50304, max_seq_len=CACHE_LEN,
        position="alibi", norm="layernorm", activation="gelu",
        tie_embeddings=True, param_dtype="float32", compute_dtype="bfloat16",
        dropout=0.0, attention_impl="auto", scan_layers=scan,
        kv_cache_dtype="int8" if int8 else "auto",
    )


def _serving_programs(one_chip, monkeypatch, cfg, cache_len=CACHE_LEN, n_pages=N_PAGES,
                      held=False, rows=None):
    """``cfg`` at a benchmark cell's engine shapes — 16 slots, page 16,
    chunk 64, the cell's cache length and pool — as the engine's own jitted
    decode step and paged chunk prefill, compiled for the described chip,
    from the tree a checkpoint gives or (``held``) from the serving form
    the engine holds of it. Returns their optimised HLO and the number of
    pool leaves; the prefill program computes ``rows`` rows: by default
    the engine's ``PREFILL_ROWS``."""
    from zero_transformer_tpu.inference.generate import decode_model, serving_params
    from zero_transformer_tpu.inference.sampling import SamplingConfig
    from zero_transformer_tpu.serving import engine as eng
    from zero_transformer_tpu.serving.slots import (
        POOL_LEAVES, _cache_struct, _leaf_name, vectorize_index,
    )

    # the kernel gates ask the backend, and the backend here is the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("ZT_PALLAS_INTERPRET", raising=False)
    model = decode_model(cfg, cache_len, kv_pages=(n_pages, PAGE))

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree
        )

    shapes = jax.eval_shape(
        lambda r: model.init(r, jnp.zeros((N_SLOTS, 1), jnp.int32)),
        jax.random.PRNGKey(0),
    )
    from zero_transformer_tpu.parallel.sharding import unbox

    params = unbox(shapes["params"])
    if held:
        params = jax.eval_shape(lambda p: serving_params(model, p), params)
    params = on_chip(params)
    cache = on_chip(jax.eval_shape(
        lambda: vectorize_index(
            jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         _cache_struct(model, N_SLOTS)), N_SLOTS)
    ))
    n_pools = sum(
        _leaf_name(p) in POOL_LEAVES
        for p, _ in jax.tree_util.tree_leaves_with_path(cache)
    )

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    V = cfg.vocab_size
    decode = eng._jit_fused_step().lower(
        model, SamplingConfig(greedy=True, repetition_penalty=1.0), params,
        sds((N_SLOTS, V), jnp.float32), cache, sds((N_SLOTS, V), jnp.bool_),
        sds((N_SLOTS, 2), jnp.uint32),
    ).compile().as_text()
    rows = rows or eng.PREFILL_ROWS
    prefill = eng._jit_paged_chunk().lower(
        model, params, cache, sds((rows, CHUNK), jnp.int32),
        sds((rows,), jnp.int32), sds((rows,), jnp.int32), sds((rows,), jnp.int32),
        sds((N_SLOTS, cache_len // PAGE), jnp.int32), sds((N_SLOTS,), jnp.int32),
    ).compile().as_text()
    return decode, prefill, n_pools


@pytest.mark.parametrize("scan", [True, False], ids=["scan", "unrolled"])
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_serving_programs_never_copy_the_page_pool(one_chip, monkeypatch, int8, scan):
    """The counter of "the pool has ONE layout from allocation to kernel and
    every program updates it in place". In the decode program AND in the
    chunk-prefill program, each as the engine jits it (the cache donated),
    the only operations with a K/V-pool-sized result are the in-place
    scatters, and every pool leaf aliases its input. Neither slices a pool
    out of anything, and the layer loop's body holds scatters alone. On the
    parent commit of PR 25 (pool scanned over as xs/ys, declared
    [.., KVH, D]) the decode program held 8 copies, 2 re-layouts and 2
    slices of the pool per layer; until PR 34 the chunk-prefill program,
    not donated, held one whole-pool copy per K/V leaf (two thirds of its
    time in the chat cell).

    The int8 scale pools (12 lanes of f32) are held to "never sliced"
    alone: the chip's default layout for so narrow an array is not the
    row-major one a Mosaic call takes, so each program re-lays them out on
    the way in and on the way out (at 18 layers, at its edge; at this cut
    depth they fit the core's fast memory and the compiler also moves them
    there and back)."""
    decode, prefill, n_pools = _serving_programs(
        one_chip, monkeypatch, _cfg_580m_cut(int8, scan))
    assert decode.count("tpu_custom_call") >= 1  # the paged kernel is on the path
    assert _aliases(decode) >= n_pools and _aliases(prefill) >= n_pools

    for ops in (_pool_ops(decode), _pool_ops(prefill)):
        sliced = {"dynamic-slice", "dynamic-update-slice", "gather", "AllocateBuffer"}
        assert not [o for o in ops if o[0] in sliced], ops
        assert {op for op, _, kv, in_loop in ops if kv and in_loop} <= {"scatter"}, ops
    on_kv = [op for op, _, kv, _ in _pool_ops(decode) if kv]
    assert set(on_kv) == {"scatter"}, on_kv
    on_kv = [op for op, _, kv, _ in _pool_ops(prefill) if kv and op not in PREFETCH]
    assert set(on_kv) == {"scatter"}, on_kv


@pytest.mark.parametrize("rows", [2, N_SLOTS], ids=["engine", "every_slot"])
def test_the_prefill_program_gathers_the_rows_it_is_handed(one_chip, monkeypatch, rows):
    """The 580M cell's chunk-prefill program at the engine's row count
    (``PREFILL_ROWS``), as the engine jits it: every pool leaf aliases its
    input and nothing K/V-pool-sized is made but the in-place scatters, and
    no value of the program is ``[16, 2048, ...]``-shaped or holds a row's
    2,048 cached positions sixteen times: the gather, its heads-first
    re-layout and the attention over it are of the rows it is handed.
    (Handed every slot it is the control: the same search finds its
    ``[16, .., 2048, ..]``.)"""
    import re

    from zero_transformer_tpu.serving import engine as eng

    assert eng.PREFILL_ROWS == 2
    _, hlo, n_pools = _serving_programs(
        one_chip, monkeypatch, _cfg_580m_cut(False, True), rows=rows)
    shapes = set(re.findall(r"[a-z0-9]+\[(\d+(?:,\d+)+)\]", hlo))
    # shapes that lead with the slot count and carry the cache length
    whole_slot_rows = sorted(
        dims for dims in shapes
        if dims.split(",")[0] == str(N_SLOTS) and str(CACHE_LEN) in dims.split(",")[1:]
    )
    on_kv = [op for op, _, kv, _ in _pool_ops(hlo) if kv and op not in PREFETCH]
    assert set(on_kv) == {"scatter"}, on_kv
    assert _aliases(hlo) >= n_pools
    assert f"[{rows},{CACHE_LEN},12,128]" in hlo  # the gathered rows
    assert bool(whole_slot_rows) == (rows == N_SLOTS), whole_slot_rows


def _weight_reads(hlo: str, cfg):
    """(float32 entry parameters of a weight's shape — a scanned stack's
    norm scales [L, d] are rank 2 and are not — and the shapes of bf16
    values of a weight's shape — a whole stack, a layer's slice or
    the embedding table — that a conversion makes, anywhere in the
    program). The compiled text gives no operand types, so a conversion is
    known by its name (``convert.N``, ``convert_element_type.N``) and a
    weight by its shape, which no activation shares."""
    import re

    d, f = str(cfg.d_model), str(cfg.ff_dim)
    pairs = {(d, d), (d, f), (f, d), (str(cfg.vocab_size), d)}
    entry = hlo.split("\nENTRY ", 1)[1]
    params = re.findall(r"%(params\w*)\S* = f32\[(\d+(?:,\d+)+)\]\S* parameter\(", entry)
    made = re.findall(r"%convert[\w.\-]* = bf16\[(\d+(?:,\d+)+)\]", hlo)
    weight = lambda dims: tuple(dims.split(",")[-2:]) in pairs  # noqa: E731
    return [p for p in params if weight(p[1])], [dims for dims in made if weight(dims)]


@pytest.mark.parametrize("scan", [True, False], ids=["scan", "unrolled"])
def test_serving_programs_multiply_the_weights_as_held(one_chip, monkeypatch, scan):
    """The counter of "the engine holds the weights in the dtype the model
    multiplies in": from the serving form, the 580M cell's fused step and
    chunk-prefill program take no float32 weight matrix as an argument and
    convert none to bf16 (the float32 parameters left are the norm
    scales). From the checkpoint's own float32 tree — what the engine
    dispatched until PR 29 — every program of every tick holds one
    conversion per weight, which is what the same search finds."""
    cfg = _cfg_580m_cut(False, scan)
    decode, prefill, _ = _serving_programs(one_chip, monkeypatch, cfg, held=True)
    for hlo in (decode, prefill):
        assert _weight_reads(hlo, cfg) == ([], [])
    decode, prefill, _ = _serving_programs(one_chip, monkeypatch, cfg)
    for hlo in (decode, prefill):
        params, converted = _weight_reads(hlo, cfg)
        assert len(params) >= 7, params  # wte + q, k, v, out, wi, wo
        assert len(converted) >= 7, converted


LOOP_CACHE_LEN, LOOP_POOL_TOKENS = 512, 2560
LOOP_N_PAGES = LOOP_POOL_TOKENS // PAGE + 1  # 161 = 7 x 23: in no other shape there


@pytest.mark.parametrize("scan", [True, False], ids=["scan", "unrolled"])
def test_looped_serving_programs_never_copy_the_page_pool(one_chip, monkeypatch, scan):
    """The looped 2.6B cell's structure (d 2048, 16 heads of 128, SwiGLU
    5632, sandwich norms, 4 passes, the exit gate, bfloat16 weights; depth
    cut to 2 layers, so 8 K/V entries) at its engine shapes: 16 slots x 512
    over a 2,560-token pool. The pass axis changes nothing of what the 580M
    case allows: the stacked pool [n_loops * n_layers, ...] (unrolled: each
    layer's own [n_loops, ...]) rides the layer loops, every pass's scatter
    is in place, and decode and chunk prefill alike alias every pool leaf
    and hold nothing else pool-sized."""
    from zero_transformer_tpu.config import ModelConfig

    cfg = ModelConfig(
        name="serve_looped_cut", d_model=2048, n_layers=DEPTH, n_loops=4,
        n_heads=16, n_kv_heads=16, head_dim=128, d_ff=5632, vocab_size=49152,
        max_seq_len=LOOP_CACHE_LEN, position="rope", rope_theta=1e6,
        norm="rmsnorm", activation="swiglu", tie_embeddings=False,
        post_norm=True, exit_gate=True, exit_threshold=1.0,
        param_dtype="bfloat16", compute_dtype="bfloat16", dropout=0.0,
        attention_impl="auto", scan_layers=scan,
    )
    decode, prefill, n_pools = _serving_programs(
        one_chip, monkeypatch, cfg, LOOP_CACHE_LEN, LOOP_N_PAGES)
    assert n_pools == (2 if scan else 2 * DEPTH)
    # the paged kernel is on the path once a (pass, layer) unless the passes'
    # layer loops stay rolled: at least once a pass
    assert decode.count("tpu_custom_call") >= 4
    assert _aliases(decode) >= n_pools and _aliases(prefill) >= n_pools

    def ops(hlo):
        return _pool_ops(hlo, LOOP_N_PAGES, PAGE, 16 * 128)

    for found in (ops(decode), ops(prefill)):
        sliced = {"dynamic-slice", "dynamic-update-slice", "gather", "AllocateBuffer"}
        assert not [o for o in found if o[0] in sliced], found
        assert {op for op, _, kv, in_loop in found if kv and in_loop} <= {"scatter"}, found
    if not scan:
        # an unrolled layer's own pool [n_loops, 161, 16, 2048] is 42 MB and
        # fits the core's fast memory: the compiler stages it there around
        # every scatter and writes it back (slice-start .. copy-start), at
        # any depth. That is its prefetch, not a copy the program asked for;
        # the count below is held on the stacked pool the cell runs.
        return
    on_kv = [op for op, _, kv, _ in ops(decode) if kv]
    assert set(on_kv) == {"scatter"}, on_kv
    on_kv = [op for op, _, kv, _ in ops(prefill) if kv and op not in PREFETCH]
    assert set(on_kv) == {"scatter"}, on_kv


GLM_CACHE_LEN, GLM_N_PAGES, GLM_ROW = 5120, 81920 // PAGE + 1, 640  # 5121 = 9 x 569


@pytest.mark.parametrize("T", [1, 5])
def test_latent_decode_kernel_compiles(one_chip, T):
    """The latent decode kernel at the GLM cell's shapes: 16 slots, 20 heads
    over ONE cached row of 640 lanes (512 latent + 64 key + 64 of padding to
    whole tiles: the unpadded 576 is 4.5 tiles, which no DMA addresses), page
    16, a 5,120 cache, the stacked pool handed over in ``pl.ANY`` with a
    traced layer index: one Mosaic call, nothing pool-sized made on the way."""
    from zero_transformer_tpu.ops.pallas import latent_attention as la

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(q, pool, table, offsets, layer):
        return la.latent_paged_attention(
            q, pool, table, offsets, value_width=512, causal=T > 1,
            softmax_scale=1 / 16.0, layer=layer,
        )

    text = jax.jit(step).lower(
        sds((N_SLOTS, T, 20, GLM_ROW), jnp.bfloat16),
        sds((3, GLM_N_PAGES, PAGE, GLM_ROW), jnp.bfloat16),
        sds((N_SLOTS, GLM_CACHE_LEN // PAGE), jnp.int32), sds((N_SLOTS,), jnp.int32),
        sds((), jnp.int32),
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert not _pool_ops(text, GLM_N_PAGES, PAGE, GLM_ROW)


def test_latent_serving_programs_never_copy_the_page_pool(one_chip, monkeypatch):
    """GLM-4.7-Flash's structure at published widths (latent attention, one
    dense layer, then 64 sigmoid-routed experts and a shared one; depth cut
    to the dense layer + two routed ones) at its cell's engine shapes: 16
    slots x 5,120 over an 81,920-token pool of latent rows. What the K/V
    cases allow and no more: the stack is unrolled, so each block keeps its
    own pool [5121, 16, 640], the latent kernel and XLA's grouped matmuls are
    on the decode path, and decode and chunk prefill alike alias every pool
    and hold nothing else pool-sized but their in-place scatters."""
    from zero_transformer_tpu.config import model_config

    cfg = model_config("glm_4_7_flash_7l", n_layers=3, attention_impl="auto")
    decode, prefill, n_pools = _serving_programs(
        one_chip, monkeypatch, cfg, GLM_CACHE_LEN, GLM_N_PAGES)
    assert n_pools == 3
    assert "latent_paged_attention" in decode and "latent_paged_attention" not in prefill
    assert "ragged-dot" in decode and "ragged-dot" in prefill
    assert _aliases(decode) >= n_pools and _aliases(prefill) >= n_pools

    def ops(hlo):
        return _pool_ops(hlo, GLM_N_PAGES, PAGE, GLM_ROW)

    for found in (ops(decode), ops(prefill)):
        sliced = {"dynamic-slice", "dynamic-update-slice", "gather", "AllocateBuffer"}
        assert not [o for o in found if o[0] in sliced], found
        assert {op for op, _, kv, in_loop in found if kv and in_loop} <= {"scatter"}, found
    on_kv = [op for op, _, kv, _ in ops(decode) if kv]
    assert set(on_kv) == {"scatter"}, on_kv
    on_kv = [op for op, _, kv, _ in ops(prefill) if kv and op not in PREFETCH]
    assert set(on_kv) == {"scatter"}, on_kv


def _flash_grads(docs: bool, entry=flash.flash_attention):
    def loss(q, k, v, ids):
        out = entry(
            q, k, v, causal=True, alibi=True, doc_ids=ids if docs else None
        )
        return jnp.sum(out.astype(jnp.float32))

    return jax.grad(loss, argnums=(0, 1, 2))


@pytest.mark.parametrize("docs", [False, True], ids=["plain", "doc_ids"])
def test_flash_train_kernel_compiles(one_chip, docs):
    """Flash forward + backward (ALiBi, with and without packed-document
    masking) at the 1.3B training shape: forward, dq and dk/dv kernels."""
    B, T, H, D = 8, 1024, 16, 128
    qkv = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=one_chip)
    assert _compiled_kernels(_flash_grads(docs), qkv, qkv, qkv, ids) == 3


@pytest.mark.parametrize("window", [64, 5])
def test_flash_serving_kernel_compiles(one_chip, window):
    """The serving entry: a chunked-prefill window (64) and a spec-verify
    window (5) over a 1024 cache, per-row offsets and kv validity."""
    B, S, H, D = 8, 1024, 16, 128

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(q, k, v, offsets, valid):
        return flash.flash_serving(
            q, k, v, causal=True, alibi=True, q_offset=offsets,
            segment_ids=valid,
        )

    kv = sds((B, S, H, D), jnp.bfloat16)
    assert _compiled_kernels(
        step, sds((B, window, H, D), jnp.bfloat16), kv, kv,
        sds((B,), jnp.int32), sds((B, S), jnp.int32),
    ) == 1


@pytest.mark.parametrize("docs", [False, True], ids=["plain", "doc_ids"])
def test_flash_compiles_under_a_four_device_data_mesh(data_mesh, docs):
    """ZeRO data-parallel training's attention call: batch sharded over a
    4-device ``data`` mesh, the default ``attention_impl``. Refused on the
    parent commit — "Mosaic kernels cannot be automatically partitioned" —
    now the dispatch site (``ops.flash_attention``) has each device run the
    kernel on its batch rows (``shard_kernel``) and no collective is needed."""
    B, T, H, D = 8, 1024, 16, 128
    rows = NamedSharding(data_mesh, P("data"))
    qkv = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16, sharding=rows)
    ids = jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=rows)
    with jax.set_mesh(data_mesh):
        grads = _flash_grads(docs, entry=dispatch.flash_attention)
        text = jax.jit(grads).lower(qkv, qkv, qkv, ids).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    assert "all-gather" not in text and "all-reduce" not in text


# ---- recurrent state beside the pages (granite-4.0-h-micro's cell) ---------

SSM_SLOTS, SSM_HEADS, SSM_P, SSM_N = 32, 64, 64, 128


def test_state_update_kernel_compiles_at_the_cells_shapes(one_chip, monkeypatch):
    """The decode state-update kernel at the state-space cell's shapes (32
    slots, 64 heads of 64 x 128, the stacked state of 36 layers with a traced
    layer index, donated): one Mosaic call, its state aliased to its output,
    and no operation of the program makes a state-pool-sized value."""
    from zero_transformer_tpu.ops.pallas import ssm_update as su

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("ZT_PALLAS_INTERPRET", raising=False)
    assert su.supported(heads=SSM_HEADS, head_dim=SSM_P, d_state=SSM_N)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    S, H, P, N = SSM_SLOTS, SSM_HEADS, SSM_P, SSM_N
    text = jax.jit(su.ssm_update, donate_argnums=(0,)).lower(
        sds((36, S, H, P, N)), sds((S, H, P)), sds((S, H)), sds((H,)), sds((S, N)),
        sds((S, N)), sds((H,)), sds((S,), jnp.bool_), sds((), jnp.int32),
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "ssm_state_update" in text
    assert not _state_ops(text, 36)


def _state_ops(hlo: str, layers: int, slots: int = SSM_SLOTS, in_place=()):
    """Every operation of an optimised HLO module whose result is the whole
    stacked SSM state ``f32[layers, slots, 64, 64, 128]`` and which is not
    the state itself passing through (a parameter, a tuple's element, a
    loop, the aliased kernel; ``in_place`` names further opcodes that
    write into the buffer they are handed): ``[(opcode, name)]``."""
    import re

    shape = rf"f32\[{layers},{slots},{SSM_HEADS},{SSM_P},{SSM_N}\]"
    free = {"parameter", "get-tuple-element", "tuple", "bitcast", "while", "conditional",
            "call", "custom-call", *in_place}
    out = []
    for line in hlo.splitlines():
        m = re.match(rf"\s*(?:ROOT )?%?([\w.\-]+) = {shape}\S* ([a-z][\w\-]*)\(", line)
        if m and m.group(2) not in free:
            out.append((m.group(2), m.group(1)))
    return out


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "jnp_twin"])
def test_hybrid_serving_programs_never_copy_the_state_pool(one_chip, monkeypatch, kernel):
    """granite-4.0-h-micro's structure at its cell's engine shapes (32 slots
    x 512, page 16, chunk 64, published widths; depth cut to two periods of
    (mamba, attention) to keep the compile in seconds), both programs as
    the engine jits them (the cache donated): the decode program holds one
    state-update kernel a period's mamba block and the paged kernel with 8
    K/V heads under 32 query heads, and NO copy of the stacked state; the
    chunk-prefill program aliases every pool and state leaf and sets the
    rows' new state into the stack it was handed, in place: no copy either
    (until PR 34 it held one, the longest operation of the cell's
    capture). Where the kernel's gate says no, the ``jax.numpy`` step it
    falls back to updates the stack in place too (a fusion rooted in a
    dynamic-update-slice of the loop's carry): no copy either, and 2.3 ms a
    decode program slower in the cell (PERF.md section 6, PR 33)."""
    from zero_transformer_tpu.config import model_config
    from zero_transformer_tpu.inference.generate import decode_model
    from zero_transformer_tpu.inference.sampling import SamplingConfig
    from zero_transformer_tpu.ops.attention import paged_kernel_supported
    from zero_transformer_tpu.parallel.sharding import unbox
    from zero_transformer_tpu.serving import engine as eng
    from zero_transformer_tpu.serving.slots import (
        POOL_LEAVES, STATE_LEAVES, _cache_struct, _leaf_name, vectorize_index,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("ZT_PALLAS_INTERPRET", raising=False)
    if not kernel:
        from zero_transformer_tpu.ops.pallas import ssm_update as su

        monkeypatch.setattr(su, "supported", lambda **kw: False)
    cfg = model_config("granite_4_0_h_micro", n_layers=4, max_seq_len=512,
                       layer_pattern=("mamba", "attention"))
    S, cache_len = SSM_SLOTS, 512
    assert paged_kernel_supported("auto", T=1, H=32, KVH=8, D=64, S=cache_len,
                                  page_size=PAGE, dtype=jnp.bfloat16)
    model = decode_model(cfg, cache_len, kv_pages=(S * cache_len // PAGE + 1, PAGE))

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = jax.eval_shape(
        lambda r: model.init(r, jnp.zeros((S, 1), jnp.int32)), jax.random.PRNGKey(0))
    params = on_chip(unbox(shapes["params"]))
    cache = on_chip(jax.eval_shape(lambda: vectorize_index(
        jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), _cache_struct(model, S)), S)))
    assert cache["ssm_state"].shape == (2, S, SSM_HEADS, SSM_P, SSM_N)
    V = cfg.vocab_size
    # (jax keys a trace by the function under the jit: the two cases ask
    # for a trace of their own, or the second would be handed the first's)
    decode = eng._jit_fused_step(fresh=True).lower(
        model, SamplingConfig(greedy=True, repetition_penalty=1.0), params,
        sds((S, V), jnp.float32), cache, sds((S, V), jnp.bool_), sds((S, 2), jnp.uint32),
        sds((S,), jnp.bool_),
    ).compile().as_text()
    R = eng.PREFILL_ROWS
    prefill = eng._jit_paged_chunk(fresh=True).lower(
        model, params, cache, sds((R, CHUNK), jnp.int32), sds((R,), jnp.int32),
        sds((R,), jnp.int32), sds((R,), jnp.int32),
        sds((S, cache_len // PAGE), jnp.int32), sds((S,), jnp.int32),
    ).compile().as_text()
    # a scanned period's body holds one mamba and one attention block
    kernels = 2 if kernel else 1
    assert decode.count("tpu_custom_call") == kernels
    assert decode.count('custom_call_target="tpu_custom_call"') == kernels
    assert ("ssm_state_update" in decode) == kernel and "paged_attention" in decode
    moved = _state_ops(decode, 2, in_place=() if kernel else ("dynamic-update-slice", "fusion"))
    assert not moved, moved
    # (the rows' new state is scattered into the stack it was handed by a
    # fusion or a short loop of in-place updates)
    copies = _state_ops(prefill, 2, in_place=("scatter", "dynamic-update-slice", "fusion"))
    assert not copies, copies
    carried = sum(_leaf_name(p) in POOL_LEAVES + STATE_LEAVES
                  for p, _ in jax.tree_util.tree_leaves_with_path(cache))
    assert carried == 4 and _aliases(prefill) >= carried  # K, V, SSM and conv state
    assert "ssm_state_update" not in prefill  # a chunk is the chunked form

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

    pool = sds((n_pages, page, H, D), jnp.int8 if int8 else jnp.bfloat16)
    scales = (sds((n_pages, page, H, 1), jnp.float32),) * 2 if int8 else ()

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

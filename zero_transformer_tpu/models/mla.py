"""Multi-head latent attention (MLA; DeepSeek-V2 2405.04434, the V3 block).

Per position the layer keeps ONE row, not a key and a value a head:

    c_q            = RMSNorm(x W_qa)                      [q_lora_rank]
    q_h            = c_q W_qb,h = [q_nope_h | q_pe_h]     [nope + rope]
    [c_kv | k_pe]  = x W_kva                              [kv_lora_rank | rope]
    c_kv           = RMSNorm(c_kv);  k_pe, q_pe_h = RoPE(.)   (ONE k_pe for all heads)
    [k_nope_h | v_h] = c_kv W_kvb,h
    score_h        = (q_nope_h . k_nope_h + q_pe_h . k_pe) / sqrt(nope + rope)
    out            = concat_h(softmax(score_h) v_h) W_o

With ``q_lora_rank`` None the query is ONE direct projection, ``q_h = x
W_q,h``; with ``position`` "none" the rope lanes are kept and rotated by
nothing (Kimi-Linear's ``mla_use_nope``).

**The cache holds the normed ``c_kv`` and the rotated ``k_pe`` only**, in one
row of ``cfg.latent_row`` lanes (``[c_kv | k_pe | 0...]``, whole lane tiles),
paged exactly as K/V pages are: a pool ``[n_pages, page, row]`` (stacked
``[n_layers, ...]`` on the scanned stack's carry), per-row block tables,
page 0 the trash page. One layout from allocation to kernel.

Two orders of the same sums. A full forward (training, scoring, the decode
model's init trace) is the NAIVE form: every position's K and V are
up-projected and attended over as full heads (the flash / XLA dispatch).
Through the cache the projections are ABSORBED, so no position is ever
up-projected again: ``q_lat_h = q_nope_h W_kvb,K,h^T``, the score is
``[q_lat_h | q_pe_h | 0] . row``, and ``o_h = (sum p row[:kv_lora_rank])
W_kvb,V,h``. The decode / spec-verify window reads the pool through
``ops.pallas.latent_attention`` (block table walked inside the kernel); a
prefill chunk, and any dispatch the kernel's gate declines, gathers each
row's pages and attends in XLA, a batch row at a time. Up-projecting the
gathered rows instead (K and V of full heads, as flash has them) would cost
``rows x cache_len x kv_lora_rank x heads x (nope + v) x 2`` operations and
their temporaries a layer a chunk: 0.75 TFLOP and 1.5 GB at 16 x 5,120
rows of the published widths, against 0.24 TFLOP absorbed (measured at the
16 rows the chunk program had until PR 32: 6.85 ms against 1.98).
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.nn import initializers

from zero_transformer_tpu.config import ModelConfig, resolve_dtype
from zero_transformer_tpu.ops.attention import (
    dot_product_attention,
    latent_decode_attention,
    latent_kernel_supported,
)
from zero_transformer_tpu.ops.positions import apply_rope

LATENT_LEAF = "cached_latent"


def latent_pool_leaves(cfg: ModelConfig, kv_pages: Tuple[int, int], dtype) -> dict:
    """``{leaf name: (shape, dtype)}`` of ONE entry of the latent page pool,
    in the layout the latent kernel's DMAs read."""
    n_pages, page = kv_pages
    return {LATENT_LEAF: ((n_pages, page, cfg.latent_row), dtype)}


class _Matrix(nn.Module):
    """A bias-free projection whose kernel is also readable on its own (the
    absorbed form multiplies by slices of it). Param path ``<name>/kernel``."""

    d_in: int
    features: int
    axes: Tuple
    std: float
    dtype: Any
    param_dtype: Any

    def setup(self):
        self.kernel = self.param(
            "kernel",
            nn.with_partitioning(initializers.normal(stddev=self.std), self.axes),
            (self.d_in, self.features),
            self.param_dtype,
        )

    def __call__(self, x: jax.Array) -> jax.Array:
        return x.astype(self.dtype) @ jnp.asarray(self.kernel, self.dtype)


class LatentAttention(nn.Module):
    """Causal latent attention with RoPE (or none) and a latent-row cache. Same call
    contract as ``models.gpt.Attention`` (``pools`` / ``layer``: the stacked
    pool riding the layer loop's carry, indexed in place)."""

    cfg: ModelConfig
    deterministic: bool = True
    decode: bool = False
    cache_len: Optional[int] = None
    mesh: Optional[Any] = None
    kv_pages: Optional[Tuple[int, int]] = None

    @nn.compact
    def __call__(self, x, doc_ids=None, pools=None, layer=None, step=None):
        cfg = self.cfg
        if self.mesh is not None:
            raise NotImplementedError("latent attention has no context-parallel path")
        dtype = x.dtype
        param_dtype = resolve_dtype(cfg.param_dtype)
        B, T, d = x.shape
        H, r, rq = cfg.n_heads, cfg.kv_lora_rank, cfg.q_lora_rank
        nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        qk, R = nope + rope, cfg.latent_row
        resid_std = 0.02 / (2 * cfg.n_layers) ** 0.5

        def matrix(d_in, features, axes, name, std=0.02):
            return _Matrix(d_in, features, axes, std, dtype, param_dtype, name=name)

        def rms(name):
            return nn.RMSNorm(
                epsilon=cfg.norm_eps, dtype=dtype, param_dtype=param_dtype,
                scale_init=nn.with_partitioning(initializers.ones, (None,)),
                name=name,
            )

        with jax.named_scope("mla_project"):
            if rq is None:
                q = matrix(d, H * qk, ("embed", "qheads"), "q")(x).reshape(B, T, H, qk)
            else:
                c_q = rms("q_a_norm")(matrix(d, rq, ("embed", None), "q_a")(x))
                q = matrix(rq, H * qk, (None, "qheads"), "q_b")(c_q).reshape(B, T, H, qk)
            kv = matrix(d, r + rope, ("embed", None), "kv_a")(x)
            c_kv = rms("kv_norm")(kv[..., :r])
            k_pe = kv[..., r:].reshape(B, T, 1, rope)
            kv_b = matrix(r, H * (nope + vd), (None, "qheads"), "kv_b")
        out_proj = matrix(H * vd, d, ("qheads", "embed"), "out", resid_std)

        use_cache = False
        offset = 0
        if self.decode:
            max_len = self.cache_len or cfg.max_seq_len
            is_init = self.is_initializing() or not self.has_variable(
                "cache", "cache_index"
            )
            paged = self.kv_pages is not None
            if paged:
                n_pages, page = self.kv_pages
                if max_len % page:
                    raise ValueError(
                        f"cache_len ({max_len}) must be a multiple of "
                        f"page_size ({page}) for the paged KV cache"
                    )
                table = self.variable(
                    "cache", "block_table", jnp.zeros, (B, max_len // page), jnp.int32
                ).value
            else:
                # a slab is a pool of one page a row: same writes, same reads
                n_pages, page = B, max_len
                table = jnp.arange(B, dtype=jnp.int32)[:, None]
            n_blocks = max_len // page
            own = None
            if pools is None:
                own = self.variable(
                    "cache", LATENT_LEAF, jnp.zeros, (n_pages, page, R), dtype
                )
            pool = own.value if own is not None else pools[LATENT_LEAF]
            idx = self.variable("cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
            use_cache = not is_init
            if use_cache:
                offset = idx.value

        per_slot = getattr(offset, "ndim", 0) == 1
        if per_slot:
            pos = offset[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        else:
            pos = offset + jnp.arange(T, dtype=jnp.int32)
        q_nope, q_pe = q[..., :nope], q[..., nope:]
        if cfg.position == "rope":
            q_pe = apply_rope(q_pe, pos, cfg.rope_theta, cfg.rope_interleaved)
            k_pe = apply_rope(k_pe, pos, cfg.rope_theta, cfg.rope_interleaved)

        if not use_cache:
            # the naive form: full heads of every position
            with jax.named_scope("mla_project"):
                up = kv_b(c_kv).reshape(B, T, H, nope + vd)
            k = jnp.concatenate(
                [up[..., :nope], jnp.broadcast_to(k_pe, (B, T, H, rope))], axis=-1
            )
            v = up[..., nope:]
            if vd > qk:
                raise NotImplementedError("v_head_dim wider than a query head")
            # the dispatch attends with one head width: zero lanes add nothing
            v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qk - vd)))
            impl = "auto" if (self.decode and cfg.attention_impl == "flash") else cfg.attention_impl
            out = dot_product_attention(
                jnp.concatenate([q_nope, q_pe], axis=-1), k, v, causal=True,
                doc_ids=doc_ids, impl=impl,
            )[..., :vd]
            out = out_proj(out.reshape(B, T, H * vd))
            out = nn.Dropout(cfg.dropout, deterministic=self.deterministic)(out)
            return out if pools is None else (out, pools)

        # ---- through the cache: the absorbed form --------------------------
        if not per_slot:
            pos = jnp.broadcast_to(pos, (B, T))
        page_ids = jnp.take_along_axis(
            table, jnp.clip(pos // page, 0, n_blocks - 1), axis=1
        )  # [B, T]
        at_entry = () if (own is not None or layer is None) else (layer,)
        row = jnp.concatenate(
            [c_kv, k_pe[:, :, 0, :], jnp.zeros((B, T, R - r - rope), dtype)], axis=-1
        )
        # a stacked pool is indexed by its entry in the SAME scatter / gather
        pool = pool.at[at_entry + (page_ids, pos % page)].set(row.astype(pool.dtype))
        if own is not None:
            own.value = pool
        idx.value = offset + T
        with jax.named_scope("mla_project"):
            w = jnp.asarray(kv_b.kernel, dtype).reshape(r, H, nope + vd)
            q_lat = jnp.einsum("bthn,rhn->bthr", q_nope, w[..., :nope])
        q_row = jnp.concatenate(
            [q_lat, q_pe, jnp.zeros((B, T, H, R - r - rope), dtype)], axis=-1
        )
        # writing past capacity clamps onto the last table entry: poison the
        # overflowing ROW instead, as the K/V cache does
        overflow = offset + T > max_len
        if per_slot:
            overflow = overflow[:, None, None, None]
        q_row = jnp.where(overflow, jnp.nan, 1.0).astype(dtype) * q_row
        from zero_transformer_tpu.ops.pallas.latent_attention import gather_attention
        from zero_transformer_tpu.ops.pallas.paged_attention import MAX_DECODE_T

        impl = "auto" if cfg.attention_impl == "flash" else cfg.attention_impl
        use_kernel = paged and latent_kernel_supported(
            impl, T=T, H=H, R=R, S=max_len, page_size=page, dtype=dtype
        )
        if paged and not use_kernel and cfg.attention_impl == "flash" and T <= MAX_DECODE_T:
            raise NotImplementedError(
                f"latent paged attention kernel unsupported for T={T} H={H} "
                f"row={R} cache_len={max_len} page={page} dtype={dtype} on "
                f"{jax.default_backend()}"
            )
        kwargs = dict(
            value_width=r, causal=T > 1, softmax_scale=1.0 / qk ** 0.5,
            layer=at_entry[0] if at_entry else None,
        )
        if use_kernel:
            att = latent_decode_attention(q_row, pool, table, offset, **kwargs)
        else:
            att = gather_attention(
                q_row, pool, table, offset, by_row=T > MAX_DECODE_T, **kwargs
            )
        with jax.named_scope("mla_project"):
            out = jnp.einsum("bthr,rhv->bthv", att, w[..., nope:])
        out = out_proj(out.reshape(B, T, H * vd))
        out = nn.Dropout(cfg.dropout, deterministic=self.deterministic)(out)
        if pools is None:
            return out
        return out, {**pools, LATENT_LEAF: pool}

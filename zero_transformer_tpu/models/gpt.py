"""Decoder-only transformer LM, TPU-first.

Covers the reference's GPT-2+ALiBi family (reference ``src/models/GPT.py``,
``src/models/layers.py``), the Llama family (RoPE/RMSNorm/SwiGLU/GQA), looped
stacks and, with latent attention (``models/mla.py``) and a dropless routed
layer (``models/moe.py``) after leading dense ones, the DeepSeek-V3 block,
from one module tree, with:

- logical-axis sharding metadata on every parameter (``nn.with_partitioning``),
  which the reference only gestured at (reference ``layers.py:13-14``, unused);
- optional ``nn.scan`` over layers → O(1) compile time in depth and stacked
  [n_layers, ...] params that ZeRO shards cleanly;
- optional ``nn.remat`` per block (rematerialization: FLOPs for HBM);
- a fixed-shape jit-able KV-cache decode path — the capability the reference
  only has on its CUDA side (reference ``torch_compatability/GPT2.py:175-245``);
- float32 softmax and residual-projection init std 0.02/sqrt(2N) preserved
  (reference ``layers.py:72,184,167-173``).

API kept reference-compatible: ``Transformer.__call__(x, labels=None, train=False)``
returns logits or (logits, loss) (reference ``GPT.py:67-113``).
"""
from __future__ import annotations

import contextlib
from typing import Any, Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.nn import initializers

from zero_transformer_tpu.config import ModelConfig, resolve_dtype
from zero_transformer_tpu.models.mamba import Mamba2Mixer, mamba_state_leaves
from zero_transformer_tpu.models.mla import LatentAttention, latent_pool_leaves
from zero_transformer_tpu.models.moe import DroplessMoE, MoEMLP
from zero_transformer_tpu.parallel.sharding import (
    constrain_activation,
    replicate_activation,
)
from zero_transformer_tpu.ops.attention import (
    dot_product_attention,
    paged_decode_attention,
    paged_kernel_supported,
)
from zero_transformer_tpu.ops.losses import chunked_next_token_loss, next_token_loss
from zero_transformer_tpu.ops.positions import apply_rope

Dtype = Any


def _dense(
    features: int, axes: Tuple, std: float, dtype, param_dtype, name: str,
    quant: bool = False,
):
    if quant:  # weight-only int8 inference path (models/quant.py)
        from zero_transformer_tpu.models.quant import QuantDense

        return QuantDense(
            features=features, axes=axes, std=std, dtype=dtype, name=name
        )
    return nn.Dense(
        features,
        use_bias=False,
        dtype=dtype,
        param_dtype=param_dtype,
        kernel_init=nn.with_partitioning(initializers.normal(stddev=std), axes),
        name=name,
    )


def doc_ids_from_tokens(x: jax.Array, sep_token: int) -> jax.Array:
    """[B, T] tokens -> [B, T] document ids for packed-sequence masking.

    The separator closes its own document (exclusive cumsum): the sep token
    attends within the doc it terminates, the token after it starts a fresh
    segment. ONE rule shared by the fused model and the pipeline engine —
    they must never diverge (the pipeline trajectory test pins this)."""
    is_sep = (x == sep_token).astype(jnp.int32)
    return jnp.cumsum(is_sep, axis=1) - is_sep


def mask_boundary_labels(labels: jax.Array, doc_ids: jax.Array) -> jax.Array:
    """Set labels to -1 (the loss ignore_index) where the document changes:
    never predict the first token of the NEXT document from the previous
    one. Shared by the fused model and the pipeline engine."""
    boundary = doc_ids[:, 1:] != doc_ids[:, :-1]
    return jnp.concatenate(
        [labels[:, :1], jnp.where(boundary, -1, labels[:, 1:])], axis=1
    )


def _quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 quantization over the head dim: [B, T, KVH, D] ->
    (int8 values, f32 scale [B, T, KVH, 1]). Round-to-nearest; scale floored
    so all-zero rows stay exactly zero after dequant."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def kv_pool_leaves(cfg: ModelConfig, kv_pages: Tuple[int, int], dtype) -> dict:
    """``{leaf name: (shape, dtype)}`` of ONE entry of the paged K/V pool
    (a layer's; a looped stack keeps ``n_loops`` a layer, on a leading
    axis), in the layout the paged kernel's ``BlockSpec`` reads: K/V
    ``[n_pages, page, KVH * D]`` and, for int8 pages, f32 scales
    ``[n_pages, page, KVH]``.
    The heads are merged into the lane axis at allocation: with
    ``[..., KVH, D]`` minor dims a TPU tile pads 12 heads to 16, XLA picks
    another physical layout for the pool than the Mosaic call and the
    scatter want, and converts between them with pool-sized copies in
    every layer of every tick. One layout from allocation to kernel, so
    no program re-lays-out a pool-sized value.
    The layer's attention declares what it keeps: latent attention one
    latent row a position (``models.mla.latent_pool_leaves``)."""
    if cfg.latent_attention:
        return latent_pool_leaves(cfg, kv_pages, dtype)
    n_pages, page = kv_pages
    KVH, D = cfg.kv_heads, cfg.head_width
    int8 = cfg.kv_cache_dtype == "int8"
    kv = ((n_pages, page, KVH * D), jnp.int8 if int8 else dtype)
    leaves = {"cached_key": kv, "cached_value": kv}
    if int8:
        scale = ((n_pages, page, KVH), jnp.float32)
        leaves.update(key_scale=scale, value_scale=scale)
    return leaves


def kv_pool_wire_heads(cfg: ModelConfig) -> int:
    """Heads a pool row's lanes split into in a page span on the wire
    (``serving.slots``): the kv heads, or one for a latent row."""
    return 1 if cfg.latent_attention else cfg.kv_heads


def resolve_remat_policy(cfg: ModelConfig):
    """cfg.remat_policy → jax.checkpoint saveable-policy.

    The ONE mapping, shared by the plain Transformer and the pipeline stage
    builder (parallel/pipeline.py) so the two step paths cannot diverge.

    - "none": save nothing — max HBM savings, the whole block re-forwards in
      the backward (minus dead code: the out/wo projection OUTPUTS are never
      needed, so they are not recomputed even here).
    - "dots": save every no-batch-dim matmul output
      (``dots_with_no_batch_dims_saveable``).
    - "qkv_mlp": save only the named q/k/v and MLP pre-activation tensors
      (``checkpoint_name`` sites in Attention/MLP/MoEMLP) — roughly a third
      of the dots footprint while still skipping ~85% of the re-forward
      matmul FLOPs, which are dominated by the qkv and wi projections.
    """
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if cfg.remat_policy == "qkv_mlp":
        return jax.checkpoint_policies.save_only_these_names(
            "attn_q", "attn_k", "attn_v", "mlp_wi", "mlp_gate"
        )
    return None


def _norm(cfg: ModelConfig, dtype, name: str):
    kwargs = dict(
        epsilon=cfg.norm_eps,
        dtype=dtype,
        param_dtype=resolve_dtype(cfg.param_dtype),
        scale_init=nn.with_partitioning(initializers.ones, ("embed",)),
        name=name,
    )
    if cfg.norm == "rmsnorm":
        return nn.RMSNorm(**kwargs)
    return nn.LayerNorm(use_bias=False, **kwargs)


def _select_exit(states, gates, threshold: float) -> jax.Array:
    """The state each position decodes from, of a looped stack's per-pass
    normed states ``[B, T, d]`` and gate values ``[B, T]`` (float32).

    ``lam_t = sigmoid(g_t)``; pass ``t`` is the exit with probability
    ``p_t = lam_t * prod_{j<t}(1 - lam_j)``, the last pass with what is
    left. A position exits at the first pass whose CUMULATIVE ``p`` reaches
    ``threshold``, else at the last: at threshold 1 that is the last unless
    a gate saturates."""
    out = states[-1]
    remaining = jnp.ones_like(gates[0])
    cum = jnp.zeros_like(gates[0])
    open_ = jnp.ones(gates[0].shape, jnp.bool_)  # no earlier pass was chosen
    for state, g in zip(states[:-1], gates[:-1]):
        lam = jax.nn.sigmoid(g)
        cum = cum + lam * remaining
        remaining = remaining * (1.0 - lam)
        take = open_ & (cum >= threshold)
        out = jnp.where(take[..., None], state, out)
        open_ = open_ & ~take
    return out


class LMHead(nn.Module):
    """Untied output projection: a bias-free Dense whose kernel is ALSO
    directly readable (``head.kernel`` — the chunked-loss path projects the
    hidden states tile-by-tile and must not call the full-width matmul).
    Same param path (``lm_head/kernel``), shape, init, and dtype semantics
    as the ``nn.Dense`` it replaces, so existing checkpoints load
    unchanged."""

    d_in: int
    features: int
    dtype: Dtype
    param_dtype: Dtype

    def setup(self):
        self.kernel = self.param(
            "kernel",
            nn.with_partitioning(initializers.normal(stddev=0.02), ("embed", "vocab")),
            (self.d_in, self.features),
            self.param_dtype,
        )

    def __call__(self, x: jax.Array) -> jax.Array:
        return x.astype(self.dtype) @ jnp.asarray(self.kernel, self.dtype)


class Attention(nn.Module):
    """Causal MHA/GQA with ALiBi or RoPE and a fixed-shape KV cache.

    ``kv_pages=(n_pages, page_size)`` switches the decode cache to a PAGED
    layout (vLLM-style, Kwon et al. 2309.06180): K/V live in a global page
    pool ``[n_pages, page_size, KVH * D]`` shared by every row (heads merged
    into the lane axis — ``kv_pool_leaves`` says why), and each row owns an
    int32 ``block_table`` ``[B, cache_len // page_size]`` mapping its
    logical sequence blocks to pool pages. Reads gather the row's pages and
    reshape the GATHERED view to the ``[B, cache_len, KVH, D]`` the slab
    path attends over; writes scatter each token's K/V to
    ``pool[table[b, pos // P], pos % P]``. Nothing reshapes or transposes
    the pool itself. Position math, validity masks,
    the int8 path, and the overflow poison guard are IDENTICAL to the slab
    cache — paging only changes where the bytes live, so paged decode is
    bit-exact vs slab decode (tested). Page 0 is the serving layer's trash
    page: a zeroed block table routes writes somewhere harmless, which is
    how parked rows ride along in fixed-shape dispatches.

    Called with ``pools`` (the layer-STACKED ``[n_layers, ...]`` pool leaves
    the scanned ``Transformer`` carries through its layer loop) and this
    layer's index, the module indexes the stack at ``layer`` in the scatter,
    the gather and the kernel's page fetch, and returns ``(out, pools)``:
    the pool is updated in place through the loop, never sliced out of it.
    Without ``pools`` it owns per-layer pool leaves itself — same code, no
    layer index.

    ``step`` is the pass of a looped stack (``cfg.n_loops > 1``; None
    otherwise). The projections are the same at every pass, the K/V are
    not: every K/V leaf gains an entry axis in front — the stack's
    ``[n_loops * n_layers, ...]``, entry ``step * n_layers + layer``, or
    this module's own ``[n_loops, ...]`` — and this call reads and writes
    entry ``step`` alone. ``cache_index`` and ``block_table`` have no such
    axis: a token sits at ONE position and in one page, whatever the pass,
    and the index advances at the last pass."""

    cfg: ModelConfig
    deterministic: bool = True
    decode: bool = False
    cache_len: Optional[int] = None  # KV cache capacity; defaults to cfg.max_seq_len
    # mesh with an active `sequence` axis → ring attention (context parallel)
    mesh: Optional[Any] = None
    kv_pages: Optional[Tuple[int, int]] = None  # (n_pages, page_size)

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        doc_ids: Optional[jax.Array] = None,
        pools: Optional[dict] = None,
        layer: Optional[jax.Array] = None,
        step=None,
    ):
        cfg = self.cfg
        dtype = x.dtype
        param_dtype = resolve_dtype(cfg.param_dtype)
        H, KVH, D = cfg.n_heads, cfg.kv_heads, cfg.head_width
        B, T, _ = x.shape
        resid_std = 0.02 / (2 * cfg.n_layers) ** 0.5
        quant = cfg.param_quant == "int8"

        q = _dense(H * D, ("embed", "qheads"), 0.02, dtype, param_dtype, "query", quant)(x)
        k = _dense(KVH * D, ("embed", "kvheads"), 0.02, dtype, param_dtype, "key", quant)(x)
        v = _dense(KVH * D, ("embed", "kvheads"), 0.02, dtype, param_dtype, "value", quant)(x)
        q = constrain_activation(q.reshape(B, T, H, D), "batch", "seq", "heads", "head_dim")
        k = constrain_activation(k.reshape(B, T, KVH, D), "batch", "seq", "kvheads", "head_dim")
        v = constrain_activation(v.reshape(B, T, KVH, D), "batch", "seq", "kvheads", "head_dim")
        # remat_policy="qkv_mlp" saves these three (plus the MLP
        # pre-activations) across the forward: the flash kernel's backward
        # needs q/k/v as residuals anyway, so saving them skips the qkv
        # projections' recompute — the bulk of the attention-side re-forward
        # — for ~38 MB/layer (bf16, batch 4 x 1024 x d1536). Outside remat
        # checkpoint_name is a no-op.
        q = checkpoint_name(q, "attn_q")
        k = checkpoint_name(k, "attn_k")
        v = checkpoint_name(v, "attn_v")

        use_cache = False
        offset = 0
        int8_cache = cfg.kv_cache_dtype == "int8"
        paged = self.decode and self.kv_pages is not None
        # impl="flash" downgrades to "auto" for the DECODE variant only:
        # flash-or-raise guards against silently taking the O(T^2) path on
        # training shapes, but the decode model's fallbacks — the T=1
        # cache-init trace, single-token slab decode, paged-gate declines —
        # are O(S) reads that are XLA/paged by design, and raising would
        # crash cache allocation and every decode tick of a
        # flash-configured model.
        impl = "auto" if (self.decode and cfg.attention_impl == "flash") else cfg.attention_impl
        bt = None
        if self.decode:
            max_len = self.cache_len or cfg.max_seq_len
            # (a looped stack calls this module once a pass: every call of
            # an init trace declares, none writes)
            is_init = self.is_initializing() or not self.has_variable(
                "cache", "cache_index"
            )
            if paged:
                n_pages, page = self.kv_pages
                if max_len % page:
                    raise ValueError(
                        f"cache_len ({max_len}) must be a multiple of "
                        f"page_size ({page}) for the paged KV cache"
                    )
                n_blocks = max_len // page
                leaves = kv_pool_leaves(cfg, self.kv_pages, dtype)
                bt = self.variable(
                    "cache", "block_table", jnp.zeros, (B, n_blocks), jnp.int32
                )
            else:
                slab = ((B, max_len, KVH, D), jnp.int8 if int8_cache else dtype)
                leaves = {"cached_key": slab, "cached_value": slab}
                if int8_cache:
                    # per-(token, head) symmetric scales; f32 so tiny magnitudes
                    # don't underflow the dequant product
                    scale = ((B, max_len, KVH, 1), jnp.float32)
                    leaves.update(key_scale=scale, value_scale=scale)
            # K/V leaves: this module's own variables, or — a stacked pool
            # riding the layer loop's carry — the caller's
            own = None
            entry = layer
            if pools is None:
                passes = () if step is None else (cfg.n_loops,)
                own = {
                    name: self.variable(
                        "cache", name, jnp.zeros, passes + shape, dt
                    )
                    for name, (shape, dt) in leaves.items()
                }
                entry = step
            elif step is not None:
                entry = step * cfg.n_layers + layer
            kv = dict(pools) if own is None else {n: v.value for n, v in own.items()}
            if entry is not None and not paged:
                # a slab with a pass axis: this pass's entry is taken out,
                # written and attended over as the plain slab is, and put
                # back below
                slabs = kv
                kv = {
                    n: jax.lax.dynamic_index_in_dim(v, entry, 0, keepdims=False)
                    for n, v in slabs.items()
                }
            idx = self.variable("cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
            use_cache = not is_init
            if use_cache:
                offset = idx.value

        # A [B]-vector cache_index (installed by serving.slots for the
        # continuous-batching engine) means every row sits at its OWN
        # position: writes, masks, and position-dependent biases all go
        # per-row. The scalar path is untouched — a fresh init_cache gives
        # scalar indices and generate()/prefill() keep compiling the same
        # programs.
        per_slot = getattr(offset, "ndim", 0) == 1

        if cfg.position == "rope":
            if per_slot:
                pos = offset[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
            else:
                pos = offset + jnp.arange(T, dtype=jnp.int32)
            q = apply_rope(q, pos, cfg.rope_theta)
            k = apply_rope(k, pos, cfg.rope_theta)  # cache stores rotated keys
        if cfg.attention_scale is not None:
            # every attention path scales by 1 / sqrt(D): the query carries
            # the rest (granite's 1/64 at D 64 is a factor of 1/8, exact)
            q = q * jnp.asarray(cfg.attention_scale * D ** 0.5, q.dtype)

        if use_cache:
            if paged:
                n_pages, page = self.kv_pages
                n_blocks = (self.cache_len or cfg.max_seq_len) // page
                # global positions per (row, token) -> (pool page, in-page
                # slot) through each row's block table. Out-of-range blocks
                # clip to the last table entry: overflow is already made
                # loud by the NaN poison guard below, and a parked row's
                # zeroed table routes the write to the trash page.
                if per_slot:
                    pos = offset[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
                else:
                    pos = jnp.broadcast_to(
                        offset + jnp.arange(T, dtype=jnp.int32), (B, T)
                    )
                page_ids = jnp.take_along_axis(
                    bt.value, jnp.clip(pos // page, 0, n_blocks - 1), axis=1
                )  # [B, T]
                in_page = pos % page
                # a stacked pool is indexed by its entry in the SAME scatter
                # / gather — `pool[entry]` first would slice a pool-sized
                # value out of the loop's carry every layer
                at_entry = () if entry is None else (entry,)

                def write(buf, upd):
                    # upd [B, T, KVH, D | 1] -> the pool's merged lane axis
                    return buf.at[at_entry + (page_ids, in_page)].set(
                        upd.reshape(B, T, -1).astype(buf.dtype)
                    )

                def gather(buf):
                    # pool pages -> the row-major [B, cache_len, KVH, D | 1]
                    # view the slab path attends over; only the GATHERED
                    # view is reshaped, never the pool
                    g = buf[at_entry + (bt.value,)]  # [B, n_blocks, page, lanes]
                    return g.reshape(B, n_blocks * page, KVH, -1)

            else:
                if per_slot:
                    # per-row dynamic_update_slice at each slot's own offset
                    def write(buf, upd):
                        return jax.vmap(
                            lambda c, u, o: jax.lax.dynamic_update_slice(
                                c, u, (o,) + (0,) * (c.ndim - 1)
                            )
                        )(buf, upd, offset)

                else:
                    def write(buf, upd):
                        return jax.lax.dynamic_update_slice(
                            buf, upd, (0, offset) + (0,) * (buf.ndim - 2)
                        )

                def gather(buf):
                    return buf

            if int8_cache:
                kq, k_scale = _quantize_kv(k)
                vq, v_scale = _quantize_kv(v)
                kv["cached_key"] = write(kv["cached_key"], kq)
                kv["cached_value"] = write(kv["cached_value"], vq)
                kv["key_scale"] = write(kv["key_scale"], k_scale)
                kv["value_scale"] = write(kv["value_scale"], v_scale)
            else:
                kv["cached_key"] = write(kv["cached_key"], k)
                kv["cached_value"] = write(kv["cached_value"], v)
            if own is not None:
                for name, var in own.items():
                    var.value = kv[name] if paged or entry is None else (
                        jax.lax.dynamic_update_index_in_dim(
                            slabs[name], kv[name], entry, 0
                        )
                    )
            idx.value = offset + (
                T if step is None else jnp.where(step == cfg.n_loops - 1, T, 0)
            )
            max_len_b = self.cache_len or cfg.max_seq_len
            if per_slot:
                kv_valid = (
                    jnp.arange(max_len_b)[None, :] < (offset[:, None] + T)
                ).astype(jnp.int32)
            else:
                kv_valid = jnp.broadcast_to(
                    (jnp.arange(max_len_b) < offset + T).astype(jnp.int32)[None, :],
                    (B, max_len_b),
                )
            # Writing past capacity would silently clamp onto the last slot
            # (dynamic_update_slice semantics). Poison the output with NaN
            # instead so overflow is loud even under jit; generate() also
            # guards statically. Per-slot, only the overflowing ROW is
            # poisoned — a parked slot must not corrupt its neighbors.
            overflow = offset + T > max_len_b
            if per_slot:
                overflow = overflow[:, None, None, None]
            q = jnp.where(overflow, jnp.nan, 1.0).astype(q.dtype) * q
            from zero_transformer_tpu.ops.pallas.paged_attention import MAX_DECODE_T

            use_kernel = paged and paged_kernel_supported(
                impl, T=T, H=H, KVH=KVH, D=D, S=max_len_b,
                page_size=self.kv_pages[1], dtype=dtype,
            )
            if (
                paged and not use_kernel
                and cfg.attention_impl == "flash" and T <= MAX_DECODE_T
            ):
                # flash-or-raise holds on the paged decode path too: an
                # explicit kernel request never gets the gather fallback
                raise NotImplementedError(
                    f"paged attention kernel unsupported for T={T} H={H} "
                    f"KVH={KVH} D={D} cache_len={max_len_b} "
                    f"page={self.kv_pages[1]} dtype={dtype} on "
                    f"{jax.default_backend()}"
                )
            if use_kernel:
                # paged-attention kernel: the block table is walked INSIDE
                # the kernel grid (page fetch per grid step), so the
                # gather-pages-to-slab view below never materializes; how
                # close it stays to that gather path is the kernel
                # module's exactness contract
                out = paged_decode_attention(
                    q, kv["cached_key"], kv["cached_value"], bt.value, offset,
                    layer=entry,
                    causal=T > 1,
                    alibi=cfg.position == "alibi",
                    k_scale=kv.get("key_scale"),
                    v_scale=kv.get("value_scale"),
                )
            else:
                if int8_cache:
                    # dequant fuses into the attention reads; the cache is
                    # a loop carry of the decode while_loop, so XLA cannot
                    # hoist this out — HBM traffic stays at int8 + one f32
                    # scale per (token, head) instead of bf16 K/V (paged:
                    # the gather moves int8 bytes + scales, dequant happens
                    # on the gathered view) multiply in f32 (scales are
                    # stored f32 for exactly this), round once at the end
                    k_all = (gather(kv["cached_key"]).astype(jnp.float32) * gather(kv["key_scale"])).astype(dtype)
                    v_all = (gather(kv["cached_value"]).astype(jnp.float32) * gather(kv["value_scale"])).astype(dtype)
                else:
                    k_all, v_all = gather(kv["cached_key"]), gather(kv["cached_value"])
                # dispatching entry point: chunked-prefill / spec-verify
                # windows route to the flash kernel where the gate accepts
                # them (TPU or interpret mode); single-token decode and CPU
                # keep the XLA path (impl downgrade above)
                out = dot_product_attention(
                    q,
                    k_all,
                    v_all,
                    causal=T > 1,
                    alibi=cfg.position == "alibi",
                    q_offset=offset,
                    segment_ids=kv_valid,
                    impl=impl,
                )
        elif self.mesh is not None:
            if cfg.cp_impl == "ulysses":
                from zero_transformer_tpu.ops.ulysses import ulysses_attention as cp_attn
            else:
                from zero_transformer_tpu.ops.ring_attention import ring_attention as cp_attn

            out = cp_attn(
                q, k, v, self.mesh, causal=True,
                alibi=cfg.position == "alibi", doc_ids=doc_ids,
            )
        else:
            # `impl` (not cfg.attention_impl): identical for training
            # models; for the decode variant this branch is the T=1
            # cache-init trace, which must not flash-or-raise
            out = dot_product_attention(
                q, k, v, causal=True, alibi=cfg.position == "alibi",
                doc_ids=doc_ids, impl=impl,
            )

        out = out.reshape(B, T, H * D)
        out = _dense(cfg.d_model, ("qheads", "embed"), resid_std, dtype, param_dtype, "out", quant)(out)
        out = nn.Dropout(cfg.dropout, deterministic=self.deterministic)(out)
        if pools is None:
            return out
        # init traces (no cache yet) hand the stack back untouched
        return out, (kv if use_cache else pools)


class MLP(nn.Module):
    cfg: ModelConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        dtype = x.dtype
        param_dtype = resolve_dtype(cfg.param_dtype)
        resid_std = 0.02 / (2 * cfg.n_layers) ** 0.5
        f = cfg.ff_dim
        quant = cfg.param_quant == "int8"
        h = constrain_activation(
            _dense(f, ("embed", "mlp"), 0.02, dtype, param_dtype, "wi", quant)(x),
            "batch", "seq", "mlp",
        )
        # saved under remat_policy="qkv_mlp": wo's weight gradient needs
        # act(h) — saving the pre-activation skips the wi (and gate) matmul
        # recompute, the largest single matmul in the block's re-forward
        h = checkpoint_name(h, "mlp_wi")
        if cfg.activation == "swiglu":
            g = checkpoint_name(
                _dense(f, ("embed", "mlp"), 0.02, dtype, param_dtype, "gate", quant)(x),
                "mlp_gate",
            )
            h = nn.silu(g) * h
        else:
            h = nn.gelu(h)
        out = _dense(cfg.d_model, ("mlp", "embed"), resid_std, dtype, param_dtype, "wo", quant)(h)
        return nn.Dropout(cfg.dropout, deterministic=self.deterministic)(out)


class Block(nn.Module):
    """Pre-norm transformer block (reference ``GPT.py:16-50``).

    Carry is ``(x, aux)``: MoE blocks add their router auxiliary loss to
    ``aux`` as it threads through the layer scan; dense blocks pass it
    through unchanged. Called with a ``layer`` index (the scanned paged
    decode stack), the carry's third element is the stacked K/V pool, which
    ``Attention`` updates in place at that layer. ``step`` is the pass of a
    looped stack (see ``Attention``). With ``cfg.post_norm`` each sublayer's
    output is normed once more before it joins the residual stream.

    ``kind`` is ``cfg.layer_kind`` of the block's place in an unrolled
    stack: "dense" (the MLP) or "moe" (the routed layer of
    ``cfg.moe_dispatch``); None, in a scanned stack, is the one kind all its
    layers have. The attention is ``cfg``'s (full heads or latent), and
    declares the cache state it keeps. A dropless routed block sows
    ``expert_counts`` ``[B, n_experts]`` into the ``routing`` collection
    where the caller makes it mutable: how many of each batch row's
    positions it sent to each expert (the engine's decode step sums them
    over the rows that decode).

    In a hybrid stack (``cfg.layer_pattern``) ``kind`` names the MIXER,
    "mamba" (``models.mamba.Mamba2Mixer``, which keeps a recurrent state a
    row) or "attention", before the dense MLP; ``layer`` is then the block's
    place among the blocks of its kind (the entry of the stacked state or
    K/V pool it reads and writes), and ``valid`` ``[B]`` how many of the
    window's positions are real (``models.mamba``). ``cfg.
    residual_multiplier`` scales each sublayer's output as it joins the
    residual stream."""

    cfg: ModelConfig
    deterministic: bool = True
    decode: bool = False
    cache_len: Optional[int] = None
    mesh: Optional[Any] = None
    kv_pages: Optional[Tuple[int, int]] = None
    kind: Optional[str] = None

    @nn.compact
    def __call__(self, carry, layer=None, step=None, valid=None):
        cfg = self.cfg
        # packed-sequence models thread the document ids as a third carry
        # element (constant through the layer scan); the decode path never
        # packs, so its carry stays (x, aux) — plus the pool when stacked
        packed = cfg.doc_sep_token is not None and not self.decode
        doc_ids = pools = None
        if packed:
            x, aux, doc_ids = carry
        elif layer is not None:
            x, aux, pools = carry
        else:
            x, aux = carry
        kind = self.kind or cfg.layer_kind(0)
        if kind == "mamba":
            attn = Mamba2Mixer(cfg, self.decode, name="mamba")(
                _norm(cfg, x.dtype, "ln_attn")(x), valid, pools, layer
            )
        else:
            attn = (LatentAttention if cfg.latent_attention else Attention)(
                cfg, self.deterministic, self.decode, self.cache_len, self.mesh,
                self.kv_pages, name="attn"
            )(
                _norm(cfg, x.dtype, "ln_attn")(x), doc_ids, pools, layer, step
            )
        if pools is not None:
            attn, pools = attn
        if cfg.post_norm:
            attn = _norm(cfg, x.dtype, "ln_attn_post")(attn)
        if cfg.residual_multiplier != 1.0:
            attn = attn * jnp.asarray(cfg.residual_multiplier, attn.dtype)
        x = x + attn
        # pin the residual stream: batch/seq sharded, replicated over tensor
        # (Megatron layout) — GSPMD must not invent another layout for it
        x = constrain_activation(x, "batch", "seq", "embed")
        if kind == "moe" and cfg.moe_dispatch == "dropless":
            layer_aux = None  # no auxiliary loss: the bias balances the load
            mo, counts = DroplessMoE(cfg, name="moe")(
                _norm(cfg, x.dtype, "ln_mlp")(x)
            )
            self.sow("routing", "expert_counts", counts)
        elif kind == "moe":
            mo, layer_aux = MoEMLP(cfg, self.deterministic, name="moe")(
                _norm(cfg, x.dtype, "ln_mlp")(x)
            )
        else:
            layer_aux = None
            mo = MLP(cfg, self.deterministic, name="mlp")(
                _norm(cfg, x.dtype, "ln_mlp")(x)
            )
        if cfg.post_norm:
            mo = _norm(cfg, x.dtype, "ln_mlp_post")(mo)
        if cfg.residual_multiplier != 1.0:
            mo = mo * jnp.asarray(cfg.residual_multiplier, mo.dtype)
        x = x + mo
        if layer_aux is not None:
            aux = aux + layer_aux
        x = constrain_activation(x, "batch", "seq", "embed")
        if packed:
            return (x, aux, doc_ids), None
        return ((x, aux) if pools is None else (x, aux, pools)), None


class Period(nn.Module):
    """One period of a hybrid stack (``cfg.layer_pattern``), its blocks
    unrolled, each its own kind: what ``scan_layers`` scans over, so the
    program holds one period's blocks whatever the depth. ``period`` is the
    period's index (None without a stacked cache): block ``j``'s entry in
    the stacked state or K/V pool of its kind is ``period x (blocks of the
    kind a period) + (blocks of the kind before j)``."""

    cfg: ModelConfig
    deterministic: bool = True
    decode: bool = False
    cache_len: Optional[int] = None
    mesh: Optional[Any] = None
    kv_pages: Optional[Tuple[int, int]] = None

    @nn.compact
    def __call__(self, carry, period=None, valid=None):
        pattern = self.cfg.layer_pattern
        for j, kind in enumerate(pattern):
            entry = None
            if period is not None:
                entry = period * pattern.count(kind) + pattern[:j].count(kind)
            carry, _ = Block(
                self.cfg, self.deterministic, self.decode, self.cache_len,
                self.mesh, self.kv_pages, kind, name=f"block_{j}",
            )(carry, entry, None, valid)
        return carry, None


class Transformer(nn.Module):
    """Full decoder LM. ``decode=True`` builds the KV-cache variant."""

    cfg: ModelConfig
    decode: bool = False
    cache_len: Optional[int] = None
    # mesh with sequence axis > 1 routes attention through ring attention
    # (context parallelism); None = single-chip / GSPMD-only layouts
    mesh: Optional[Any] = None
    # (n_pages, page_size): paged KV cache for the serving engine — K/V in
    # a global page pool addressed through per-row block tables (see
    # Attention). None = the classic [B, cache_len] slab. Under
    # ``scan_layers`` the pool leaves are declared HERE, stacked
    # [n_layers, n_pages, page, KVH * D], and carried through the layer
    # loop; unrolled layers each own theirs.
    kv_pages: Optional[Tuple[int, int]] = None

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        labels: Optional[jax.Array] = None,
        train: bool = False,
        valid: Optional[jax.Array] = None,
    ) -> Union[jax.Array, Tuple[jax.Array, jax.Array]]:
        """``valid`` ``[B]`` int32 (a model with recurrent state, through
        its cache): how many of each row's ``T`` positions are real; the
        rest leave the row's state as it was (``models.mamba``)."""
        cfg = self.cfg
        dtype = resolve_dtype(cfg.compute_dtype)
        param_dtype = resolve_dtype(cfg.param_dtype)
        B, T = x.shape
        quant = cfg.param_quant == "int8"

        if quant:
            # weight-only int8 (inference only — the trainer rejects it):
            # int8 rows + per-row scales through both the lookup and the
            # tied head's attend (models/quant.py)
            if labels is not None:
                raise NotImplementedError(
                    "param_quant='int8' is an inference configuration; the "
                    "loss paths (incl. chunked CE's direct kernel reads) "
                    "run on full-precision params"
                )
            from zero_transformer_tpu.models.quant import QuantEmbed

            embed = QuantEmbed(
                num_embeddings=cfg.vocab_size,
                features=cfg.d_model,
                dtype=dtype,
                name="wte",
            )
        else:
            embed = nn.Embed(
                num_embeddings=cfg.vocab_size,
                features=cfg.d_model,
                embedding_init=nn.with_partitioning(
                    initializers.normal(stddev=0.02), ("vocab", "embed")
                ),
                dtype=dtype,
                param_dtype=param_dtype,
                name="wte",
            )
        if self.decode or quant:
            # decode gathers [B, <=few] ids per step; replicating the table
            # inside the decode while_loop would all-gather it every token.
            # (The quant prefill/eval path also gathers directly: its table
            # reads are int8, and quant serving meshes are pure-TP where
            # the replicated-view rewrite below is not needed.)
            h = embed(x)
        else:
            # Token lookup runs on an explicitly REPLICATED view of the
            # table: with wte sharded over vocab (tensor) and/or embed
            # (ZeRO-3), the gather output inherits an embed-sharded layout
            # that GSPMD can only reshard to the batch/seq activation layout
            # via "[SPMD] Involuntary full rematerialization" (round-4
            # MULTICHIP finding). One up-front all-gather is the efficient
            # form of the same data movement — and matches the reference's
            # trivially-replicated wte (reference ``src/models/GPT.py:75-83``).
            # The tied head (``embed.attend``) still consumes the sharded
            # table, so the vocab-parallel logits matmul is unaffected.
            table = replicate_activation(jnp.asarray(embed.embedding, dtype))
            h = jnp.take(table, x, axis=0)
        if cfg.embedding_multiplier != 1.0:
            h = h * jnp.asarray(cfg.embedding_multiplier, h.dtype)
        h = constrain_activation(h, "batch", "seq", "embed")

        if cfg.position == "learned":
            if T > cfg.max_seq_len:
                raise ValueError(
                    f"sequence length {T} > max_seq_len {cfg.max_seq_len}: learned "
                    "positions cannot extrapolate (use position='alibi' for that)"
                )
            wpe = nn.Embed(
                num_embeddings=cfg.max_seq_len,
                features=cfg.d_model,
                embedding_init=nn.with_partitioning(
                    initializers.normal(stddev=0.02), (None, "embed")
                ),
                dtype=dtype,
                param_dtype=param_dtype,
                name="wpe",
            )
            offset = 0
            if self.decode:
                is_init = not self.has_variable("cache", "decode_pos")
                pos_var = self.variable("cache", "decode_pos", lambda: jnp.zeros((), jnp.int32))
                if not is_init:
                    offset = pos_var.value
                    pos_var.value = offset + T
            if getattr(offset, "ndim", 0) == 1:
                # [B]-vector decode positions (continuous-batching slots)
                positions = offset[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
            else:
                positions = offset + jnp.arange(T, dtype=jnp.int32)
            h = h + wpe(positions)

        h = nn.Dropout(cfg.dropout, deterministic=not train)(h)

        block_cls = Block
        if cfg.remat:
            block_cls = nn.remat(
                Block, prevent_cse=not cfg.scan_layers,
                policy=resolve_remat_policy(cfg),
            )
        aux = jnp.zeros((), jnp.float32)  # MoE router losses, summed over layers
        packed = cfg.doc_sep_token is not None and not self.decode
        doc_ids = None
        if packed:
            # composes with ring attention too (the kv doc ids ride the
            # ppermute ring)
            doc_ids = doc_ids_from_tokens(x, cfg.doc_sep_token)
        carry = (h, aux, doc_ids) if packed else (h, aux)
        layers = pool_vars = None
        if cfg.scan_layers and self.decode and self.kv_pages is not None:
            # the paged K/V pool rides the layer loop's CARRY, stacked
            # [n_loops * n_layers, ...] and indexed by its entry inside
            # Attention.
            # Scanned over like the rest of the cache (`variable_axes`), it
            # would enter the loop as one buffer and leave as another:
            # every layer would slice its pool out of the first and copy
            # it into the second, whatever the caller donates.
            pool_vars = {
                name: self.variable(
                    "cache", name, jnp.zeros, (cfg.kv_entries,) + shape, dt
                )
                for name, (shape, dt) in kv_pool_leaves(
                    cfg, self.kv_pages, dtype
                ).items()
            }
            if cfg.recurrent:
                # a hybrid stack's recurrent state rides the carry beside
                # the pool, stacked over its mamba layers, a row a batch row
                pool_vars.update({
                    name: self.variable(
                        "cache", name, jnp.zeros,
                        (cfg.layers_of("mamba"),) + shape, dt,
                    )
                    for name, (shape, dt) in mamba_state_leaves(cfg, B, dtype).items()
                })
            layers = jnp.arange(cfg.n_layers, dtype=jnp.int32)
            carry = carry + ({n: v.value for n, v in pool_vars.items()},)
        members = (cfg, not train, self.decode, self.cache_len, self.mesh,
                   self.kv_pages)
        if cfg.hybrid:
            # scanned over the PERIODS of the pattern (``scan_layers`` is
            # required of a hybrid stack), a period's blocks unrolled; a
            # block's cache entry is its place among its kind
            periods = cfg.n_layers // len(cfg.layer_pattern)
            stack = nn.scan(
                Period,
                variable_axes={"params": 0, "cache": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=(0, nn.broadcast),
                length=periods,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(*members, name="periods")
            layers = jnp.arange(periods, dtype=jnp.int32) if pool_vars else None
        elif cfg.scan_layers:
            stack = nn.scan(
                block_cls,
                variable_axes={"params": 0, "cache": 0},
                split_rngs={"params": True, "dropout": True},
                length=cfg.n_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(*members, name="blocks")
        else:
            # unrolled, each block is its own kind (``cfg.layer_kind``): the
            # only stack whose layers may differ
            blocks = [
                block_cls(*members, cfg.layer_kind(i), name=f"block_{i}")
                for i in range(cfg.n_layers)
            ]
        ln_f = _norm(cfg, h.dtype, "ln_f")
        # A looped stack runs the SAME blocks n_loops times: one module,
        # called once a pass, so the weights are shared (and their gradients
        # sum over the passes) while each pass reads and writes its own K/V
        # entries. The final norm closes every pass: the next one starts
        # from the normed state.
        looped = cfg.n_loops > 1
        states, gates = [], []
        if cfg.exit_gate:
            gate = nn.Dense(
                1, dtype=jnp.float32, param_dtype=param_dtype,
                kernel_init=nn.with_partitioning(
                    initializers.normal(stddev=0.02), ("embed", None)
                ),
                name="exit_gate",
            )
        for t in range(cfg.n_loops):
            step = t if looped else None
            with jax.named_scope("loop_pass") if looped else contextlib.nullcontext():
                if cfg.hybrid:
                    carry, _ = stack(carry, layers, valid)
                elif cfg.scan_layers:
                    steps = jnp.full((cfg.n_layers,), t, jnp.int32) if looped else None
                    carry, _ = stack(carry, layers, steps)
                else:
                    for block in blocks:
                        carry, _ = block(carry, None, step)
                h = ln_f(carry[0])
            carry = (h,) + carry[1:]
            if cfg.exit_gate:
                states.append(h)
                with jax.named_scope("exit_gate"):
                    gates.append(gate(h)[..., 0])
        if pool_vars is not None:
            for name, var in pool_vars.items():
                var.value = carry[2][name]
        aux = carry[1]
        if cfg.exit_gate:
            with jax.named_scope("exit_gate"):
                h = _select_exit(states, gates, cfg.exit_threshold)

        if cfg.tie_embeddings:
            head = None
        elif quant:
            head = _dense(
                cfg.vocab_size, ("embed", "vocab"), 0.02, dtype, param_dtype,
                "lm_head", quant=True,
            )
        else:
            head = LMHead(cfg.d_model, cfg.vocab_size, dtype, param_dtype, name="lm_head")

        if labels is not None and cfg.loss_chunk and not self.decode:
            # chunked CE: the [B, T, vocab] logits never materialize —
            # the loss-bearing return is (None, loss); labels-free calls
            # below still produce full logits (eval scoring needs them)
            ignore = None
            if packed:
                labels = mask_boundary_labels(labels, doc_ids)
                ignore = -1
            w_dv = (
                jnp.asarray(embed.embedding, dtype).T
                if cfg.tie_embeddings
                else jnp.asarray(head.kernel, dtype)
            )
            if cfg.logits_scaling != 1.0:
                h = h / jnp.asarray(cfg.logits_scaling, h.dtype)
            loss = chunked_next_token_loss(
                h, w_dv, labels, cfg.loss_chunk, ignore_index=ignore
            )
            if train and cfg.n_experts > 0:
                loss = loss + aux
            return None, loss

        logits = embed.attend(h) if cfg.tie_embeddings else head(h)
        if cfg.logits_scaling != 1.0:
            logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)

        if labels is None:
            return logits
        if packed:
            labels = mask_boundary_labels(labels, doc_ids)
            loss = next_token_loss(logits, labels, ignore_index=-1)
        else:
            loss = next_token_loss(logits, labels)
        if train and cfg.n_experts > 0:
            # router losses steer TRAINING only; eval loss stays pure CE so
            # perplexities remain comparable to dense models
            loss = loss + aux
        return logits, loss

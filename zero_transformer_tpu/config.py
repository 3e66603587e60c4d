"""Typed configuration system.

Replaces the reference's dual OmegaConf YAML zoos (reference:
``conf/model_config.yaml`` + ``torch_compatability/model_config.yaml`` —
duplicated per SURVEY.md §2) with a single typed dataclass hierarchy loaded
from one YAML file. Everything the reference hardcoded in ``main_zero.py``
(decay_steps at :211, shuffle seed :393, PRNGKey(0) :215, adam b2 :166,
keep=5 :70) is a field here.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Any, Optional, Tuple

import jax.numpy as jnp
import yaml

_DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
}


def resolve_dtype(name: str):
    if name not in _DTYPES:
        raise ValueError(f"Invalid dtype {name!r}; expected one of {sorted(_DTYPES)}")
    return _DTYPES[name]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters.

    Covers the reference's GPT-2+ALiBi family (reference ``src/models/GPT.py:53-113``,
    ``conf/model_config.yaml``) and extends it to the Llama family (RoPE, RMSNorm,
    SwiGLU, GQA) via the ``position``, ``norm``, ``activation``, ``n_kv_heads`` axes.
    """

    name: str = "test"
    vocab_size: int = 256
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 2
    max_seq_len: int = 32
    dropout: float = 0.0
    # "alibi" (train-short/test-long extrapolation, reference layers.py:17-44),
    # "rope" (llama family), "learned" (plain GPT-2), or "none" (no position
    # encoding at all: a hybrid stack's attention reads order off its
    # recurrent layers).
    position: str = "alibi"
    rope_theta: float = 10000.0
    n_kv_heads: Optional[int] = None  # GQA; None -> MHA
    head_dim: Optional[int] = None  # None -> d_model // n_heads
    d_ff: Optional[int] = None  # None -> 4*d_model (gelu) or 8/3*d_model (swiglu)
    activation: str = "gelu"  # "gelu" | "swiglu"
    norm: str = "layernorm"  # "layernorm" | "rmsnorm"
    tie_embeddings: bool = True
    # Looped ("universal") stack: the n_layers blocks run n_loops times over
    # the same weights, the final norm closing every pass, and the KV cache
    # keeps one entry per (pass, layer) — n_loops * n_layers in all, entry
    # t * n_layers + l — while a token's position advances once. 1 = the
    # plain stack.
    n_loops: int = 1
    # "sandwich" norms: a second norm on each sublayer's OUTPUT, before the
    # residual add (ln_attn_post / ln_mlp_post beside ln_attn / ln_mlp)
    post_norm: bool = False
    # Exit gate of a looped stack: a Dense(1, bias) on each pass's normed
    # state. lam_t = sigmoid(gate), p_t = lam_t * prod_{j<t}(1 - lam_j), the
    # last pass taking what is left; a position decodes from the first pass
    # whose cumulative p reaches exit_threshold, else from the last. Every
    # pass always runs (the cache stays whole): the gate only selects the
    # state the head reads.
    exit_gate: bool = False
    exit_threshold: float = 1.0
    # Compilation shape: scan over layers gives O(1) compile time in depth and a
    # stacked [n_layers, ...] param layout that ZeRO shards cleanly.
    scan_layers: bool = True
    remat: bool = False  # jax.checkpoint each block: trade FLOPs for HBM
    # what the per-block checkpoint SAVES: "none" = save nothing (max HBM
    # savings, recomputes the whole block in bwd); "dots" = save matmul
    # outputs, recompute only elementwise/norm/softmax (jax
    # dots_with_no_batch_dims_saveable — cheaper bwd for ~1 extra
    # activations-worth of HBM per block); "qkv_mlp" = save only the named
    # q/k/v + MLP pre-activation tensors (models/gpt.py checkpoint_name) —
    # ~1/3 the dots footprint, still skips most of the re-forward matmuls
    remat_policy: str = "none"
    attention_impl: str = "auto"  # "auto" | "xla" | "flash" (pallas)
    # Context-parallel engine when the mesh's `sequence` axis is active:
    # "ring" (ppermute KV rotation, ops/ring_attention.py — any head count,
    # best at very long T) or "ulysses" (two all-to-all reshards + one local
    # flash call at full T, ops/ulysses.py — needs the sequence axis to
    # divide the per-tensor-shard head counts).
    cp_impl: str = "ring"
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    # Decode KV cache storage: "auto" stores at compute dtype; "int8" stores
    # symmetric per-(token, head) int8 + f32 scales — halves cache HBM
    # traffic and doubles servable context; dequant fuses into the attention
    # reads inside the decode loop. Training paths ignore this.
    kv_cache_dtype: str = "auto"
    # Weight-only int8 for the INFERENCE path (serve --quantize int8):
    # Dense kernels and the token table become int8 + per-output-channel /
    # per-vocab-row f32 scales (models/quant.py); HBM weight reads halve —
    # decode is bandwidth-bound, and this is what fits 8B-class models on
    # one 16 GB chip. Training rejects it (build_training); loss paths
    # raise.
    param_quant: str = "none"  # "none" | "int8"
    # Packed-sequence training: rows hold multiple documents separated by
    # this token id. Attention is masked so documents cannot see each other
    # (segments derived in-graph from the separator — no loader changes) and
    # the loss never predicts across a boundary. None = rows are single
    # documents (the reference's setup).
    doc_sep_token: Optional[int] = None
    # Mixture-of-Experts (0 = dense MLP everywhere). With n_experts > 0 every
    # block's MLP becomes a top-k routed expert mixture with capacity-based
    # dispatch; expert weights shard over the mesh's `expert` axis (EP).
    n_experts: int = 0
    moe_top_k: int = 2
    # Chunked cross entropy: compute the LM loss `loss_chunk` sequence
    # positions at a time so the [B, T, vocab] logits — the step's single
    # largest activation at real scale (1.6 GB f32 for 1.3B/50k-vocab at
    # 8x1024 tokens, paid again in backward) — are never materialized; the
    # loss-bearing forward then returns (None, loss). None = full logits
    # (needed whenever the caller wants logits, e.g. eval scoring; labels-
    # free calls always produce logits regardless).
    loss_chunk: Optional[int] = None
    # per-expert buffer = capacity_factor * top_k * tokens / n_experts
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01  # load-balance aux loss weight
    router_z_coef: float = 1e-3  # router z-loss weight
    # How a routed layer moves tokens (models/moe.py). "capacity": softmax
    # top-1/2 over fixed per-expert buffers, overflow dropped (the training
    # recipes' MoEMLP). "dropless": sigmoid scores, the top ``moe_top_k`` of
    # score + a selection bias, weights from the scores alone (normalised,
    # times ``moe_routed_scale``), rows sorted by expert into a grouped
    # matmul: no token is dropped and a row's result depends on that row
    # alone, which chunked prefill + decode against a full forward needs.
    # Its grouped matmuls are custom calls, which take whole buffers: a layer
    # sliced out of a SCANNED stack of expert weights is first copied (three
    # copies of 201 MB a layer a program at GLM-4.7-Flash's widths, 22 of a
    # 30 ms decode program; my chip run, PR 31), so a dropless stack is
    # unrolled (``scan_layers`` false): each block's weights are buffers.
    moe_dispatch: str = "capacity"  # "capacity" | "dropless"
    moe_d_ff: Optional[int] = None  # width of ONE routed / shared expert; None -> ff_dim
    moe_shared_experts: int = 0  # always-on experts beside the routed ones (dropless)
    moe_routed_scale: float = 1.0
    # leading layers that keep the dense MLP (width ``d_ff``) in a routed
    # model (dropless, so unrolled: block i is dense where i < this)
    moe_dense_layers: int = 0
    # Latent attention (MLA, DeepSeek-V2/V3), set by ``kv_lora_rank`` with all
    # five widths: queries through a rank ``q_lora_rank`` bottleneck; keys and values up-projected from ONE normed
    # latent row of ``kv_lora_rank`` values a position, plus one rotated key of
    # ``qk_rope_head_dim`` shared by all heads. A head's query and key are
    # ``qk_nope_head_dim + qk_rope_head_dim`` wide (= ``head_dim``), its value
    # ``v_head_dim``. The cache holds the latent row and the rotated key only
    # (models/mla.py). None = full multi-head / grouped attention.
    kv_lora_rank: Optional[int] = None
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    # RoPE pairs lanes (2i, 2i+1) instead of (i, i + D/2)
    rope_interleaved: bool = False
    norm_eps: float = 1e-6
    # Hybrid stack (Mamba-2 state-space mixers among attention layers;
    # models/mamba.py): ONE period of the stack, each entry "mamba" or
    # "attention" (which MIXER the block has; every block keeps the dense
    # MLP), repeated n_layers / len(layer_pattern) times. None = every block
    # attends. A mamba block keeps a recurrent state a batch row, whatever
    # the row's length, where an attention block keeps K/V a position.
    layer_pattern: Optional[Tuple[str, ...]] = None
    mamba_heads: int = 0  # SSM heads; inner width = mamba_heads * mamba_head_dim
    mamba_head_dim: int = 64
    mamba_state: int = 128  # state values a (head, channel): d_state
    mamba_conv: int = 4  # causal depthwise conv taps over [x | B | C]
    mamba_chunk: int = 256  # positions a step of the chunked scan
    # scores = (q . k) * attention_scale; None -> 1 / sqrt(head_dim)
    attention_scale: Optional[float] = None
    # h0 = embedding_multiplier * Embed(tokens); each sublayer's output joins
    # the residual stream times residual_multiplier; logits / logits_scaling
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads if self.n_kv_heads is not None else self.n_heads

    @property
    def head_width(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def ff_dim(self) -> int:
        if self.d_ff is not None:
            return self.d_ff
        if self.activation == "swiglu":
            # keep ~same params as 4x gelu: 2/3 * 4 * d, rounded to 128
            return ((8 * self.d_model // 3) + 127) // 128 * 128
        return 4 * self.d_model

    @property
    def latent_attention(self) -> bool:
        return self.kv_lora_rank is not None

    @property
    def latent_row(self) -> int:
        """Lanes of one cached latent row: the ``kv_lora_rank`` latent
        values and the ``qk_rope_head_dim`` rotated key, padded to whole
        128-lane tiles (a page DMA moves whole tiles of a pool row)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def moe_ff_dim(self) -> int:
        return self.moe_d_ff if self.moe_d_ff is not None else self.ff_dim

    def layer_kind(self, i: int) -> str:
        """What block ``i`` of the stack is: "moe" (the routed layer of
        ``moe_dispatch``) or "dense" (the MLP; the ``moe_dense_layers``
        leading blocks of a routed stack, every block of a dense one); in a
        hybrid stack which mixer it has before its MLP, "mamba" or
        "attention", as ``layer_pattern`` repeats."""
        if self.layer_pattern is not None:
            return self.layer_pattern[i % len(self.layer_pattern)]
        return "moe" if self.n_experts > 0 and i >= self.moe_dense_layers else "dense"

    @property
    def hybrid(self) -> bool:
        return self.layer_pattern is not None

    @property
    def recurrent(self) -> bool:
        """Does a block keep a recurrent state (a hybrid stack with mamba
        blocks)? Such a model's cache holds a state a batch row beside its
        K/V, right only at the position the row has reached."""
        return self.hybrid and "mamba" in self.layer_pattern

    def layers_of(self, kind: str) -> int:
        """Blocks of ``kind`` in the whole stack."""
        return sum(self.layer_kind(i) == kind for i in range(self.n_layers))

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        """Channels the causal conv runs over: ``[x | B | C]``."""
        return self.mamba_inner + 2 * self.mamba_state

    @property
    def state_bytes_per_slot(self) -> int:
        """Bytes of recurrent state ONE batch row (a serving slot) keeps,
        whatever its length: a float32 SSM state ``[heads, head_dim,
        state]`` and the conv's last ``mamba_conv - 1`` inputs at the
        compute dtype, a mamba block. 0 for a stack that only attends."""
        if not self.recurrent:
            return 0
        ssm = self.mamba_inner * self.mamba_state * 4
        itemsize = jnp.dtype(resolve_dtype(self.compute_dtype)).itemsize
        conv = (self.mamba_conv - 1) * self.mamba_conv_dim * itemsize
        return self.layers_of("mamba") * (ssm + conv)

    @property
    def _mamba_params(self) -> int:
        d, inner, h = self.d_model, self.mamba_inner, self.mamba_heads
        conv = self.mamba_conv_dim
        return (
            d * (inner + conv + h)  # in_proj: [z | xBC | dt]
            + conv * self.mamba_conv + conv  # depthwise taps and bias
            + 3 * h  # dt_bias, A_log, D
            + inner  # the gated norm's scale
            + inner * d  # out_proj
        )

    @property
    def _attention_params(self) -> int:
        d, h = self.d_model, self.n_heads
        if self.latent_attention:
            r, rq = self.kv_lora_rank, self.q_lora_rank
            qk, rope = self.head_width, self.qk_rope_head_dim
            q = d * rq + rq + rq * h * qk
            kv = d * (r + rope) + r + r * h * (self.qk_nope_head_dim + self.v_head_dim)
            return q + kv + h * self.v_head_dim * d
        kv, hd = self.kv_heads, self.head_width
        return d * h * hd + 2 * d * kv * hd + h * hd * d

    def _layer_params(self, kind: str, active: bool = False) -> int:
        """Parameters of ONE block of a kind (matrices, norm scales, router);
        with ``active``, of a routed block only what one token is multiplied
        by: ``moe_top_k`` of its experts."""
        d = self.d_model
        per = 3 if self.activation == "swiglu" else 2
        if kind == "moe":
            e = self.moe_top_k if active else self.n_experts
            mlp = per * d * self.moe_ff_dim * (e + self.moe_shared_experts) + d * self.n_experts
            if self.moe_dispatch == "dropless":
                mlp += self.n_experts  # the selection bias
        else:
            mlp = per * d * self.ff_dim
        norms = (4 if self.post_norm else 2) * d
        mixer = self._mamba_params if kind == "mamba" else self._attention_params
        return mixer + mlp + norms

    @property
    def layer_params(self) -> int:
        """Parameters of ONE block (matrices and norm scales); of a stack
        with leading dense layers, of a routed block."""
        return self._layer_params(self.layer_kind(self.n_layers - 1))

    def _stack_params(self, active: bool) -> int:
        d, v = self.d_model, self.vocab_size
        embed = v * d * (1 if self.tie_embeddings else 2)
        gate = d + 1 if self.exit_gate else 0
        layers = sum(
            self._layer_params(self.layer_kind(i), active) for i in range(self.n_layers)
        )
        return layers + embed + d + gate

    @property
    def num_params(self) -> int:
        """Approximate parameter count, what memory holds (embedding
        included once when tied; a looped stack's shared layers once; every
        expert of a routed layer)."""
        return self._stack_params(active=False)

    @property
    def params_per_token(self) -> int:
        """Parameters a token is multiplied through: ``num_params`` with a
        looped stack's shared layers counted once a PASS, a routed layer's
        experts as the ``moe_top_k`` a token is sent to, and an untied
        embedding table as the lookup it is. What FLOP arithmetic wants
        where ``num_params`` is what memory holds; the same number for a
        plain dense stack with tied embeddings."""
        n = self._stack_params(active=self.moe_dispatch == "dropless")
        n += (self.n_loops - 1) * self.n_layers * self.layer_params
        if self.moe_dispatch == "dropless" and not self.tie_embeddings:
            n -= self.vocab_size * self.d_model
        return n

    @property
    def kv_entries(self) -> int:
        """K/V cache entries a token keeps: one per (pass, layer) that
        attends."""
        if self.hybrid:
            return self.layers_of("attention")
        return self.n_loops * self.n_layers

    def __post_init__(self):
        if self.d_model % self.n_heads and self.head_dim is None:
            raise ValueError("d_model must be divisible by n_heads")
        if self.n_kv_heads is not None and self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be divisible by n_kv_heads")
        if self.position not in ("alibi", "rope", "learned", "none"):
            raise ValueError(f"invalid position {self.position!r}")
        if self.layer_pattern is not None:
            # a YAML list -> the hashable tuple a static jit argument needs
            object.__setattr__(self, "layer_pattern", tuple(self.layer_pattern))
            self._check_hybrid()
        if self.activation not in ("gelu", "swiglu"):
            raise ValueError(f"invalid activation {self.activation!r}")
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"invalid norm {self.norm!r}")
        if self.remat_policy not in ("none", "dots", "qkv_mlp"):
            raise ValueError(f"invalid remat_policy {self.remat_policy!r}")
        if self.doc_sep_token is not None and self.position == "learned":
            raise ValueError(
                "doc_sep_token packing requires a relative position scheme "
                "(alibi/rope): learned absolute positions break the "
                "packed==standalone logits contract"
            )
        if self.doc_sep_token is not None and not (
            0 <= self.doc_sep_token < self.vocab_size
        ):
            raise ValueError(
                f"doc_sep_token {self.doc_sep_token} outside vocab "
                f"[0, {self.vocab_size}): the separator could never appear, "
                "silently disabling document masking"
            )
        if self.n_loops < 1:
            raise ValueError("n_loops must be >= 1")
        if self.exit_gate and self.n_loops == 1:
            raise ValueError("exit_gate needs a looped stack (n_loops > 1)")
        if not 0.0 < self.exit_threshold <= 1.0:
            raise ValueError("exit_threshold must lie in (0, 1]")
        if self.loss_chunk is not None and self.loss_chunk <= 0:
            raise ValueError("loss_chunk must be a positive chunk size or None")
        if self.n_experts < 0:
            raise ValueError("n_experts must be >= 0")
        if self.moe_dispatch not in ("capacity", "dropless"):
            raise ValueError(f"invalid moe_dispatch {self.moe_dispatch!r}")
        dropless = self.moe_dispatch == "dropless"
        if self.n_experts > 0 and not dropless and self.moe_top_k not in (1, 2):
            raise ValueError("moe_top_k must be 1 or 2 (capacity dispatch)")
        if self.n_experts > 0 and self.moe_top_k < 1:
            raise ValueError("moe_top_k must be >= 1")
        if dropless and self.n_experts == 0:
            raise ValueError("moe_dispatch='dropless' needs n_experts > 0")
        if dropless and (self.activation != "swiglu" or self.param_quant != "none"):
            raise ValueError(
                "moe_dispatch='dropless' experts are SwiGLU, with no int8 weights"
            )
        if dropless and (self.scan_layers or self.n_loops > 1):
            raise ValueError(
                "moe_dispatch='dropless' needs scan_layers=False and one pass: a "
                "grouped matmul copies its expert weights out of a scanned stack"
            )
        if not dropless and (self.moe_shared_experts or self.moe_dense_layers):
            raise ValueError(
                "moe_shared_experts / moe_dense_layers belong to "
                "moe_dispatch='dropless'"
            )
        if not 0 <= self.moe_dense_layers < self.n_layers:
            raise ValueError("moe_dense_layers must lie in [0, n_layers)")
        if self.latent_attention:
            widths = (self.q_lora_rank, self.qk_nope_head_dim,
                      self.qk_rope_head_dim, self.v_head_dim)
            if None in widths or self.qk_rope_head_dim % 2:
                raise ValueError(
                    "latent attention needs q_lora_rank, qk_nope_head_dim, an "
                    "even qk_rope_head_dim and v_head_dim"
                )
            if self.head_width != self.qk_nope_head_dim + self.qk_rope_head_dim:
                raise ValueError(
                    "head_dim must be qk_nope_head_dim + qk_rope_head_dim "
                    "(the width of a query and key head)"
                )
            if self.position != "rope" or self.n_kv_heads is not None:
                raise ValueError(
                    "latent attention is RoPE on its own shared key; it has "
                    "no kv heads to group"
                )
            if self.kv_cache_dtype != "auto" or self.param_quant != "none" or self.n_loops > 1:
                raise ValueError(
                    "latent attention has no int8 pages, int8 weights or "
                    "looped stack"
                )
        if self.n_experts > 0 and self.moe_top_k > self.n_experts:
            raise ValueError("moe_top_k cannot exceed n_experts")
        if self.attention_impl not in ("auto", "xla", "flash"):
            raise ValueError(f"invalid attention_impl {self.attention_impl!r}")
        if self.cp_impl not in ("ring", "ulysses"):
            raise ValueError(f"invalid cp_impl {self.cp_impl!r}")
        if self.kv_cache_dtype not in ("auto", "int8"):
            raise ValueError(f"invalid kv_cache_dtype {self.kv_cache_dtype!r}")
        if self.param_quant not in ("none", "int8"):
            raise ValueError(f"invalid param_quant {self.param_quant!r}")
        resolve_dtype(self.param_dtype)
        resolve_dtype(self.compute_dtype)


    def _check_hybrid(self) -> None:
        pattern = self.layer_pattern
        if not pattern or set(pattern) - {"mamba", "attention"}:
            raise ValueError(
                f"layer_pattern {pattern!r}: one period of 'mamba' / 'attention'"
            )
        if self.n_layers % len(pattern):
            raise ValueError("n_layers must be a multiple of len(layer_pattern)")
        if self.recurrent and (
            self.mamba_heads < 1 or self.mamba_head_dim < 1 or self.mamba_state < 1
            or self.mamba_conv < 2
        ):
            raise ValueError(
                "a mamba block needs mamba_heads, mamba_head_dim, mamba_state "
                ">= 1 and mamba_conv >= 2"
            )
        if not self.scan_layers:
            raise ValueError(
                "a hybrid stack (layer_pattern) needs scan_layers=True: it is "
                "scanned over the periods of its pattern (unrolled it traces "
                "and compiles twice as long for 3.5% of a decode tick: "
                "PERF.md section 6, PR 33)"
            )
        if (
            self.n_experts or self.latent_attention or self.n_loops > 1
            or self.post_norm or self.doc_sep_token is not None
            or self.param_quant != "none" or self.remat
        ):
            raise ValueError(
                "a hybrid stack (layer_pattern) has dense MLPs and full-head "
                "attention, one pass, no sandwich norms, packing, remat or "
                "int8 weights"
            )
        if self.kv_cache_dtype != "auto" and self.recurrent:
            raise ValueError(
                "kv_cache_dtype='int8' is refused for a model with recurrent "
                "state: the state is summed over the whole request and stays "
                "float32"
            )


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Device-mesh layout. Axes: data (DP+ZeRO), fsdp (param shard for ZeRO-3),
    expert (MoE expert parallelism), tensor (Megatron TP), sequence
    (ring-attention context parallelism).

    The reference uses a 1-D ``("dp",)`` mesh only (reference ``main_zero.py:227-228``).
    """

    data: int = -1  # -1: use all remaining devices
    fsdp: int = 1
    expert: int = 1
    tensor: int = 1
    pipe: int = 1  # GPipe pipeline stages (layer sharding + ppermute wavefront)
    sequence: int = 1
    # Multi-slice / multi-pod placement: number of DCN-connected device
    # groups (TPU slices, or processes on platforms without slice_index)
    # that the GLOBAL data axis spans. The per-step gradient all-reduce is
    # the only collective that crosses groups; every model axis (fsdp,
    # expert, tensor, sequence, pipe) stays inside one ICI domain — the
    # scaling-book layout (DCN outermost, ICI inner). 1 = single slice
    # (plain topology-aware mesh); must divide `data`.
    dcn_data: int = 1
    # ZeRO stage: 0 = plain DP, 1 = opt-state sharded, 2 = +grad reduce-scatter,
    # 3 = +param sharded (FSDP). Reference implements stage 1 only (SURVEY §2).
    zero_stage: int = 1
    # pipeline schedule (pipe > 1): "gpipe" = fill-drain wavefront, activation
    # stash O(M) microbatches; "1f1b" = one-forward-one-backward ticks with
    # stash-and-recompute, activation stash O(P) — use when M (accumulation
    # depth) at the target context no longer fits HBM; "interleaved" = V
    # virtual stages per rank (`pp_interleave`) shrinking the bubble from
    # (P-1)/(M+P-1) toward (P-1)/(V*M+P-1) — use when the bubble, not HBM,
    # dominates step time. See docs/TRAINING.md.
    pp_schedule: str = "gpipe"
    # virtual pipeline stages per rank for pp_schedule="interleaved": each
    # microbatch makes V laps around the pipe ring, each lap running
    # n_layers/(pipe*V) layers per rank. Requires n_layers % (pipe*V) == 0
    # and accumulation depth M % pipe == 0 (microbatches flow in groups of
    # P so the wrap-around hop arrives exactly when needed — no stash).
    pp_interleave: int = 1
    # Overlapped ZeRO communication (parallel/overlap.py): the train step is
    # built around layer-granular comm buckets derived from the sharding
    # plan — the per-layer param all_gather and gradient psum_scatter are
    # issued INSIDE the blocks' layer scan (gather for layer l as its
    # iteration starts, scatter for layer l as its backward retires), so
    # XLA's latency-hiding scheduler can hide the collectives behind
    # adjacent layers' compute instead of exposing one monolithic
    # gather/scatter bracket around the whole step. Gradients are
    # bit-identical to the serial placement (tests/test_overlap.py).
    # Requires zero_stage >= 1, scan_layers, and no pipe axis (the pipeline
    # engine owns its own collective schedule).
    overlap_comm: bool = False

    def __post_init__(self):
        if self.dcn_data < 1:
            raise ValueError(f"dcn_data must be >= 1, got {self.dcn_data}")
        if self.pp_schedule not in ("gpipe", "1f1b", "interleaved"):
            raise ValueError(
                f"pp_schedule must be 'gpipe', '1f1b', or 'interleaved', "
                f"got {self.pp_schedule!r}"
            )
        if self.pp_schedule != "gpipe" and self.pipe == 1:
            # loud, not silent: without a pipe axis the schedule choice
            # would be ignored while the user expects 1F1B's O(P) memory
            # or interleaved's smaller bubble
            raise ValueError(
                f"pp_schedule={self.pp_schedule!r} requires pipe > 1 "
                f"(got pipe={self.pipe})"
            )
        if self.pp_interleave < 1:
            raise ValueError(
                f"pp_interleave must be >= 1, got {self.pp_interleave}"
            )
        if self.pp_interleave > 1 and self.pp_schedule != "interleaved":
            raise ValueError(
                f"pp_interleave={self.pp_interleave} only applies to "
                f"pp_schedule='interleaved' (got {self.pp_schedule!r})"
            )
        if self.pp_schedule == "interleaved" and self.pp_interleave < 2:
            raise ValueError(
                "pp_schedule='interleaved' needs pp_interleave >= 2 virtual "
                "stages per rank (pp_interleave=1 is exactly gpipe — ask "
                "for that by name)"
            )
        if self.overlap_comm and self.pipe > 1:
            raise ValueError(
                "overlap_comm applies to the non-pipeline ZeRO step; the "
                "pipeline engine owns its own collective schedule "
                "(pp_schedule) — drop one of overlap_comm / pipe > 1"
            )
        if self.overlap_comm and self.zero_stage < 1:
            raise ValueError(
                "overlap_comm requires zero_stage >= 1: at stage 0 there "
                "is no ZeRO collective schedule to overlap"
            )


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    peak_learning_rate: float = 3e-4
    end_learning_rate: float = 3e-5
    warmup_steps: int = 2000
    decay_steps: Optional[int] = None  # None -> total_steps - warmup_steps
    total_steps: int = 163000
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    schedule: str = "warmup_cosine"  # "warmup_cosine" | "warmup_linear" | "constant"
    # "adamw" (reference, main_zero.py:160-168) | "adafactor" (factored
    # second moments — classic TPU memory saver for the largest models) |
    # "lion" (momentum-only: one f32 buffer per param)
    optimizer: str = "adamw"

    def __post_init__(self):
        if self.optimizer not in ("adamw", "adafactor", "lion"):
            raise ValueError(f"invalid optimizer {self.optimizer!r}")


@dataclasses.dataclass(frozen=True)
class TrainingConfig:
    batch_size: int = 256  # global batch, in sequences
    gradient_accumulation_steps: int = 1
    train_context: int = 1024
    evaluation_frequency: int = 1000
    maximum_evaluation_steps: int = 250
    total_steps: int = 163000
    seed: int = 0
    log_frequency: int = 10
    # capture a jax.profiler trace of this many consecutive steps (0 = off),
    # starting after the first (compile) step; viewable in TensorBoard/XProf
    profile_steps: int = 0
    # absolute step at which the capture window opens (train.py
    # --profile-window START:LEN sets both fields); 0 keeps the legacy
    # "after the first step of this run" behavior
    profile_start: int = 0
    profile_dir: str = ""  # default: <checkpoint.directory>/profile
    # stop (after force-saving a checkpoint) when the loss goes NaN/inf —
    # checked at each log sync point, so it costs nothing extra. The
    # reference could burn days of pod time past a divergence.
    halt_on_nan: bool = True
    # dtype of the gradient-accumulation buffer ("float32" | "bfloat16").
    # bfloat16 halves the param-sized accumulator — the knob that lets the
    # 1.3B single-chip config fit 16 GB HBM (three f32 param-sized trees —
    # master params, accumulator, micro-grads — are 15.6 GB before
    # activations). Micro-step gradients are still computed in f32; only the
    # running sum rounds (once per add, upcast-add-round), and adafactor's
    # per-tensor normalization makes it insensitive to that scale of noise.
    # float32 is the default and is bit-identical to the pre-knob behavior.
    grad_accum_dtype: str = "float32"
    # path to a BENCH_step.json step-time decomposition artifact
    # (scripts/train_step_bench.py) measured for this config's platform.
    # When set, the trainer's obs track reports train/exposed_comm_frac
    # from the artifact's measured overlap A/B alongside the analytic
    # train/bubble_frac gauge. "" = bubble_frac only (it is analytic —
    # exact for the configured schedule).
    step_bench_artifact: str = ""

    def __post_init__(self):
        if self.grad_accum_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                "training.grad_accum_dtype must be 'float32' or 'bfloat16', "
                f"got {self.grad_accum_dtype!r}"
            )


@dataclasses.dataclass(frozen=True)
class DataConfig:
    # "synthetic" | "memmap" | "hf" (datasets streaming) | "tar" (webdataset-
    # style tar shards / *.index files — the reference's actual data path,
    # main_zero.py:389-421)
    source: str = "synthetic"
    train_path: str = ""
    validation_path: str = ""
    max_context: int = 2048
    shuffle_buffer: int = 10_000
    shuffle_seed: int = 23
    # batches decoded ahead of the train step by a background thread
    # (DataLoader.prefetch); 0 = fully synchronous. The reference used torch
    # DataLoader workers for the same overlap (main_zero.py:407-421).
    num_workers: int = 2
    # tar source: True crashes on any undecodable member / unreadable shard
    # (data validation); False warns, retries opens once, and skips
    strict: bool = False


@dataclasses.dataclass(frozen=True)
class ResilienceConfig:
    """Fault-tolerance policy (``zero_transformer_tpu/resilience/``).

    Three layers, all host-side except the anomaly guard:

    - **anomaly guard**: every train step is checked IN-GRAPH for non-finite
      loss/grad-norm (and, optionally, spikes against a running EMA); a
      flagged step's update is dropped inside the compiled step, so a
      divergent batch can never poison params — and detection costs no extra
      device→host sync on non-logging steps (the carry is a device array the
      host only reads at log points). This closes the ``halt_on_nan``
      blind spot where divergence between log points poisoned up to
      ``log_frequency - 1`` further updates.
    - **rollback snapshot**: state mirrored to host RAM every
      ``snapshot_frequency`` steps; on a sustained anomaly streak the last
      good snapshot is restored (no disk read) and the loader continues
      forward — the offending data window is never replayed.
    - **watchdog / supervisor**: hang detection and bounded-restart
      supervision of the whole run (``train.py --supervise``).
    """

    # in-graph per-step anomaly guard (non-finite loss/grad always flags)
    anomaly_detection: bool = True
    # escalation ceiling when an anomaly is detected: "skip_batch" only ever
    # drops flagged updates; "rollback" additionally restores the host-RAM
    # snapshot after `rollback_after` consecutive anomalies; "halt" raises at
    # the first detection (the historical halt_on_nan semantics).
    anomaly_response: str = "halt"
    # >0: flag loss > factor * EMA(loss) as an anomaly (0 = non-finite only)
    loss_spike_factor: float = 0.0
    # >0: flag grad_norm > factor * EMA(grad_norm)
    grad_spike_factor: float = 0.0
    ema_decay: float = 0.98
    # clean steps absorbed into the EMAs before spike checks arm
    spike_warmup_steps: int = 50
    # consecutive flagged steps before skip_batch escalates to halt (the
    # guard keeps params clean, but zero progress forever is its own failure)
    max_consecutive_anomalies: int = 25
    # rollback policy: restore the snapshot once a streak reaches this length
    rollback_after: int = 3
    snapshot_frequency: int = 200  # steps between host-RAM state mirrors
    max_rollbacks: int = 3  # budget per train() call; exceeding it halts
    # cross-replica divergence audit: every N steps the anomaly guard
    # checksums the state leaves that are REPLICATED over the ZeRO axes on
    # every DP replica (in-graph shard_map + scalar all_gather — no host
    # sync) and flags any bit-level disagreement. Catches silent data
    # corruption that desynced one replica within N steps instead of never
    # (XLA assumes replicated copies identical; a desync otherwise only
    # shows up when the loss curves fork). 0 disables. Escalation on a trip:
    # anomaly_response 'rollback' re-places the host snapshot (which
    # re-replicates identical copies — the desync is HEALED); anything else
    # halts (a desynced replica cannot be skipped past).
    audit_frequency: int = 0
    # hang watchdog: abort (retryably) when no step completes for this many
    # seconds; 0 disables. Must comfortably exceed worst-case compile +
    # checkpoint-write time.
    watchdog_timeout_s: float = 0.0
    # supervisor (train.py --supervise): restart budget + exponential backoff
    max_restarts: int = 3
    backoff_base_s: float = 2.0
    backoff_max_s: float = 300.0
    # multiplicative backoff jitter: each delay is spread uniformly over
    # [1-j, 1+j] so N workers restarting after a SHARED-cause failure (a
    # storage blip, a preemption wave) don't thundering-herd the checkpoint
    # store at the same instant. 0 disables (deterministic delays).
    backoff_jitter: float = 0.1

    def __post_init__(self):
        if self.anomaly_response not in ("skip_batch", "rollback", "halt"):
            raise ValueError(
                f"invalid anomaly_response {self.anomaly_response!r}; expected "
                "'skip_batch', 'rollback', or 'halt'"
            )
        if not 0.0 < self.ema_decay < 1.0:
            raise ValueError("ema_decay must be in (0, 1)")
        for name in ("loss_spike_factor", "grad_spike_factor"):
            if getattr(self, name) < 0:
                raise ValueError(f"{name} must be >= 0 (0 disables)")
        for name in (
            "rollback_after",
            "max_consecutive_anomalies",
        ):
            if getattr(self, name) < 1:
                raise ValueError(f"{name} must be >= 1")
        if self.snapshot_frequency < 0 or self.max_rollbacks < 0:
            raise ValueError("snapshot_frequency/max_rollbacks must be >= 0")
        if self.audit_frequency < 0:
            raise ValueError("audit_frequency must be >= 0 (0 disables)")
        if self.audit_frequency > 0 and not self.anomaly_detection:
            raise ValueError(
                "audit_frequency requires anomaly_detection: the replica "
                "audit rides the in-graph anomaly-guard carry (it would be "
                "silently inert with the guard disabled)"
            )
        if self.watchdog_timeout_s < 0 or self.max_restarts < 0:
            raise ValueError("watchdog_timeout_s/max_restarts must be >= 0")
        if self.backoff_base_s <= 0 or self.backoff_max_s < self.backoff_base_s:
            raise ValueError(
                "backoff_base_s must be > 0 and backoff_max_s >= backoff_base_s"
            )
        if not 0.0 <= self.backoff_jitter < 1.0:
            raise ValueError(
                "backoff_jitter must be in [0, 1): at 1.0 the jitter window "
                "touches a zero delay, which defeats the backoff entirely"
            )


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Continuous-batching serving policy (``zero_transformer_tpu/serving/``).

    The hot-path knobs ``serve --server`` exposes as flags, with the
    defaults defined ONCE here (the CLI reads them from this dataclass so a
    YAML deployment config and the flag surface can never drift):

    - **prefill_chunk**: prompts prefill ``prefill_chunk`` tokens per
      scheduler tick, written through the slot's block table into the KV
      page pool and interleaved with the fused decode step — one long
      prompt cannot stall every active stream for its full prefill
      (Sarathi-style chunked prefill). It is the only admission path.
    - **prefix_cache_chunks**: capacity (in chunk entries) of the
      chunk-aligned token-prefix index over page ids; repeated system
      prompts skip straight to the first novel chunk (vLLM-style block
      hashing), a hit being a refcount bump. 0 disables. Flushed on hot
      weight reload — cached K/V is only valid for the weights that
      produced it.
    - **page_size / page_pool_tokens**: K/V lives in a block-table paged
      pool (PagedAttention): HBM is ``page_pool_tokens`` positions
      regardless of slot count, so concurrency scales with ACTUAL sequence
      lengths instead of the worst case. ``page_pool_tokens = 0`` sizes the
      pool to ``slots x cache_len``.
    - **draft_k**: per-tick self-speculative decoding — every decode tick
      proposes ``draft_k`` tokens per slot (prompt-lookup n-grams) and
      verifies them in ONE batched forward; greedy output is bit-identical
      to plain decode, sampling follows the standard rejection rule.
      Requires repetition_penalty == 1.0. 0 disables.
    """

    slots: int = 4
    max_queue: int = 64
    prefill_chunk: int = 64
    prefix_cache_chunks: int = 256
    drain_deadline_s: float = 30.0
    page_size: int = 16
    page_pool_tokens: int = 0
    draft_k: int = 0
    # disaggregated fleets (PR 12): a "prefill" replica runs only chunked
    # prefill at max batch and ships every finished stream's KV pages to
    # the decode replica the request names; a "decode" replica serves
    # imported streams (and plain requests, as the recompute fallback);
    # "mixed" is the classic single-replica behavior.
    role: str = "mixed"

    def __post_init__(self):
        if self.slots < 1:
            raise ValueError("serving.slots must be >= 1")
        if self.max_queue < 1:
            raise ValueError("serving.max_queue must be >= 1")
        if self.prefill_chunk < 1:
            raise ValueError(
                "serving.prefill_chunk must be >= 1: one-shot prefill "
                "(prefill_chunk=0) was removed, chunked prefill is the only "
                "admission path"
            )
        if self.prefix_cache_chunks < 0:
            raise ValueError(
                "serving.prefix_cache_chunks must be >= 0 (0 disables)"
            )
        if self.drain_deadline_s < 0:
            raise ValueError("serving.drain_deadline_s must be >= 0")
        if self.page_size < 1:
            raise ValueError("serving.page_size must be >= 1")
        if self.prefill_chunk % self.page_size:
            raise ValueError(
                "serving.page_size must divide prefill_chunk (page-aligned "
                "chunk sharing)"
            )
        if self.page_pool_tokens < 0:
            raise ValueError(
                "serving.page_pool_tokens must be >= 0 (0 = slots x cache_len)"
            )
        if self.draft_k < 0:
            raise ValueError("serving.draft_k must be >= 0 (0 disables)")
        if self.role not in ("mixed", "prefill", "decode"):
            raise ValueError(
                f"serving.role must be mixed|prefill|decode, got {self.role!r}"
            )
        if self.role == "prefill" and self.draft_k:
            raise ValueError(
                "serving.role='prefill' replicas never decode; draft_k "
                "must be 0"
            )


@dataclasses.dataclass(frozen=True)
class CheckpointConfig:
    directory: str = "checkpoints"
    keep: int = 5
    save_frequency: int = 1000
    async_save: bool = True
    resume: bool = False
    # integrity manifests: every save writes a per-leaf content-digest item
    # (exact uint32 bit-sums, computed on device in one jit call — the
    # save-tick overhead is measured and reported as train/ckpt_verify_ms);
    # restore re-digests the restored leaves and QUARANTINES a
    # corrupt/truncated/mismatched step dir (renamed to *.quarantined),
    # falling back to the newest verified older step instead of crash-
    # looping on the same bad artifact. False = trust storage blindly
    # (the pre-manifest behavior).
    integrity: bool = True
    warm_init: bool = False
    warm_init_dir: str = ""
    # warm start from an exported params msgpack instead of a checkpoint dir;
    # depth is auto-extended (Gopher G.3.3) and the layer layout auto-converted
    warm_init_msgpack: str = ""


@dataclasses.dataclass(frozen=True)
class Config:
    model: ModelConfig = dataclasses.field(default_factory=ModelConfig)
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    optimizer: OptimizerConfig = dataclasses.field(default_factory=OptimizerConfig)
    training: TrainingConfig = dataclasses.field(default_factory=TrainingConfig)
    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    checkpoint: CheckpointConfig = dataclasses.field(default_factory=CheckpointConfig)
    resilience: ResilienceConfig = dataclasses.field(default_factory=ResilienceConfig)
    serving: ServingConfig = dataclasses.field(default_factory=ServingConfig)


def _build(cls, raw: dict) -> Any:
    fields = {f.name: f for f in dataclasses.fields(cls)}
    unknown = set(raw) - set(fields)
    if unknown:
        raise ValueError(f"Unknown keys for {cls.__name__}: {sorted(unknown)}")
    return cls(**raw)


_MODEL_ZOO_PATH = Path(__file__).resolve().parent.parent / "configs" / "models.yaml"


def load_model_zoo(path: str | Path = _MODEL_ZOO_PATH) -> dict[str, ModelConfig]:
    with open(path) as f:
        raw = yaml.safe_load(f)
    return {name: _build(ModelConfig, {"name": name, **(body or {})}) for name, body in raw.items()}


def model_config(name: str, path: str | Path = _MODEL_ZOO_PATH, **overrides) -> ModelConfig:
    """Look up a model by zoo name (reference ``model_getter``, GPT.py:116-137)."""
    zoo = load_model_zoo(path)
    if name not in zoo:
        raise ValueError(f"Invalid model name {name!r}; expected one of {sorted(zoo)}")
    cfg = zoo[name]
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def load_config(path: str | Path, **overrides) -> Config:
    """Load a full training Config from YAML.

    The ``model`` section may be either an inline mapping or ``{"size": <zoo name>}``
    (mirroring the reference's ``model.size`` lookup, ``conf/config.yaml:14``).
    """
    with open(path) as f:
        raw = yaml.safe_load(f) or {}
    raw.update(overrides)
    sections = {}
    model_raw = dict(raw.pop("model", {}) or {})
    if "size" in model_raw:
        size = model_raw.pop("size")
        base = model_config(size)
        valid = {f.name for f in dataclasses.fields(ModelConfig)}
        unknown = set(model_raw) - valid
        if unknown:
            raise ValueError(f"Unknown keys for ModelConfig: {sorted(unknown)}")
        sections["model"] = dataclasses.replace(base, **model_raw)
    elif model_raw:
        sections["model"] = _build(ModelConfig, model_raw)
    for key, cls in (
        ("mesh", MeshConfig),
        ("optimizer", OptimizerConfig),
        ("training", TrainingConfig),
        ("data", DataConfig),
        ("checkpoint", CheckpointConfig),
        ("resilience", ResilienceConfig),
        ("serving", ServingConfig),
    ):
        if key in raw:
            sections[key] = _build(cls, raw.pop(key) or {})
    if raw:
        raise ValueError(f"Unknown top-level config keys: {sorted(raw)}")
    return Config(**sections)


def apply_dotted_overrides(cfg: Config, overrides: dict[str, Any]) -> Config:
    """Apply ``{"section.field": value}`` overrides to a Config, revalidating
    every touched section (each ``dataclasses.replace`` re-runs the frozen
    dataclass' ``__post_init__``). One implementation for ``train.py --set``
    AND the autotuner's candidate-point construction
    (``analysis/autotune.py``) — the validity oracle that refuses an invalid
    knob combination is therefore exactly the validation a real run hits.

    ``model.size`` applies FIRST (a zoo lookup replaces the whole model
    section), so ``model.*`` overrides — wherever they appear — land on top
    of the zoo entry instead of being clobbered by it.

    All overrides for one section apply in a SINGLE ``replace`` so only the
    final combination is validated — applying ``serving.prefill_chunk=8``
    and ``serving.page_size=8`` one field at a time would refuse the valid
    pair whenever the intermediate state (new chunk against the old page
    size) happens to be invalid."""
    overrides = dict(overrides)
    if "model.size" in overrides:
        cfg = dataclasses.replace(
            cfg, model=model_config(str(overrides.pop("model.size")))
        )
    by_section: dict[str, dict[str, Any]] = {}
    for dotted, value in overrides.items():
        section_name, _, field = dotted.partition(".")
        section = getattr(cfg, section_name, None)
        if section is None or not field or not hasattr(section, field):
            raise ValueError(f"unknown config field {dotted!r}")
        by_section.setdefault(section_name, {})[field] = value
    for section_name, fields in by_section.items():
        cfg = dataclasses.replace(
            cfg,
            **{
                section_name: dataclasses.replace(
                    getattr(cfg, section_name), **fields
                )
            },
        )
    return cfg


def flatten_config(cfg: Config) -> dict[str, Any]:
    """Flatten for metric loggers (reference ``src/utils/configs.py:7-17``)."""
    out = {}
    for section in dataclasses.fields(cfg):
        val = getattr(cfg, section.name)
        for f in dataclasses.fields(val):
            out[f"{section.name}.{f.name}"] = getattr(val, f.name)
    return out

"""Weight-only int8 serving (models/quant.py; `serve --quantize int8`).

The quantized model must compute (x @ q) * s where the full model with
dequantized weights computes x @ (q * s) — identical up to float
associativity — and every quantized leaf must be an int8 tensor so the
claimed HBM halving is real, not cosmetic.
"""
import dataclasses

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp

from zero_transformer_tpu.config import ModelConfig, model_config
from zero_transformer_tpu.models.gpt import Transformer
from zero_transformer_tpu.models.quant import quantize_array, quantize_params

CFG = model_config("test", dropout=0.0, compute_dtype="float32",
                   param_dtype="float32")


def _dequantized(params_q, params_ref):
    """Rebuild full-precision params from the quantized tree: q * scale with
    the reference tree's structure (for the exactness cross-check)."""

    def walk(qt, rt):
        out = {}
        for k, v in rt.items():
            if isinstance(v, dict):
                out[k] = walk(qt[k], v)
            elif k == "embedding" and "embedding_q" in qt:
                out[k] = (
                    qt["embedding_q"].astype(np.float32)
                    * np.expand_dims(qt["scale"], -1)
                )
            elif k == "kernel" and "kernel_q" in qt:
                out[k] = (
                    qt["kernel_q"].astype(np.float32)
                    * np.expand_dims(qt["scale"], -2)
                )
            elif f"{k}_q" in qt:  # MoE expert weights (wi/wo/gate)
                scale = qt[f"{k}_scale"]
                out[k] = (
                    qt[f"{k}_q"].astype(np.float32)
                    * np.expand_dims(scale, -2)
                )
            else:
                out[k] = v
        return out

    return walk(params_q, params_ref)


def test_quantize_array_error_bound():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(16, 32)).astype(np.float32))
    q, scale = quantize_array(w, axis=-2)
    assert q.dtype == jnp.int8 and scale.shape == (32,)
    err = np.abs(np.asarray(w) - np.asarray(q, np.float32) * np.asarray(scale))
    # round-to-nearest: error <= scale/2 per element, columnwise
    assert (err <= np.asarray(scale) / 2 + 1e-7).all()


@pytest.mark.parametrize("tie", [True, False])
def test_quant_forward_matches_dequantized_full(tie):
    cfg = dataclasses.replace(CFG, tie_embeddings=tie)
    qcfg = dataclasses.replace(cfg, param_quant="int8")
    x = jnp.asarray([[1, 5, 9, 2, 7, 3, 4, 8]], jnp.int32)
    params = nn.meta.unbox(Transformer(cfg).init(jax.random.PRNGKey(0), x)["params"])
    params_q = quantize_params(jax.tree.map(np.asarray, params))
    # structure must match what the quant model expects
    expect = nn.meta.unbox(jax.eval_shape(
        lambda: Transformer(qcfg).init(jax.random.PRNGKey(0), x)
    )["params"])
    assert jax.tree.structure(jax.tree.map(lambda l: 0, params_q)) == \
        jax.tree.structure(jax.tree.map(lambda l: 0, expect))
    for lq, le in zip(jax.tree.leaves(params_q), jax.tree.leaves(expect)):
        assert lq.shape == le.shape and lq.dtype == le.dtype, (lq.shape, le.shape, lq.dtype, le.dtype)

    out_q = Transformer(qcfg).apply({"params": params_q}, x)
    full = _dequantized(params_q, params)
    out_f = Transformer(cfg).apply({"params": full}, x)
    np.testing.assert_allclose(
        np.asarray(out_q), np.asarray(out_f), rtol=2e-4, atol=2e-4
    )


def test_quant_decode_generates():
    from zero_transformer_tpu.inference.generate import decode_model, generate
    from zero_transformer_tpu.inference.sampling import SamplingConfig

    cfg = dataclasses.replace(CFG, param_quant="int8")
    x = jnp.asarray([[1, 5, 9, 2]], jnp.int32)
    model = decode_model(cfg, cache_len=12)
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    out = generate(model, params, x, 6, jax.random.PRNGKey(1),
                   SamplingConfig(greedy=True))
    out = np.asarray(out)
    assert out.shape == (1, 6)
    assert ((out >= 0) & (out < cfg.vocab_size)).all()


def test_quant_tree_is_half_the_bytes():
    x = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    params = nn.meta.unbox(Transformer(CFG).init(jax.random.PRNGKey(0), x)["params"])
    params_q = quantize_params(jax.tree.map(np.asarray, params))

    def nbytes(tree):
        return sum(l.size * l.dtype.itemsize for l in
                   map(np.asarray, jax.tree.leaves(tree)))

    # f32 source -> int8 + scales: ~0.25x (+ norm params untouched); the
    # bf16-serving ratio is 0.5x by the same leaf accounting
    assert nbytes(params_q) < 0.30 * nbytes(params)


def test_quant_moe_forward_matches_dequantized_full():
    """MoE expert tensors quantize too: per-(expert, out-channel) scales
    applied after each expert einsum must reproduce the dequantized-full
    model (same associativity argument as QuantDense)."""
    cfg = dataclasses.replace(
        CFG, n_experts=2, moe_top_k=1, activation="swiglu",
    )
    qcfg = dataclasses.replace(cfg, param_quant="int8")
    x = jnp.asarray([[1, 5, 9, 2, 7, 3, 4, 8]], jnp.int32)
    params = nn.meta.unbox(Transformer(cfg).init(jax.random.PRNGKey(0), x)["params"])
    params_q = quantize_params(jax.tree.map(np.asarray, params))
    expect = nn.meta.unbox(jax.eval_shape(
        lambda: Transformer(qcfg).init(jax.random.PRNGKey(0), x)
    )["params"])
    assert jax.tree.structure(jax.tree.map(lambda l: 0, params_q)) == \
        jax.tree.structure(jax.tree.map(lambda l: 0, expect))

    out_q = Transformer(qcfg).apply({"params": params_q}, x)
    out_f = Transformer(cfg).apply({"params": _dequantized(params_q, params)}, x)
    np.testing.assert_allclose(
        np.asarray(out_q), np.asarray(out_f), rtol=2e-4, atol=2e-4
    )


def test_quant_rejections():
    with pytest.raises(ValueError, match="param_quant"):
        ModelConfig(param_quant="int4")
    # loss paths are full-precision only
    qcfg = dataclasses.replace(CFG, param_quant="int8")
    x = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    with pytest.raises(NotImplementedError, match="inference"):
        Transformer(qcfg).init(jax.random.PRNGKey(0), x, x)
    # and the trainer refuses to build
    from zero_transformer_tpu.config import Config
    from zero_transformer_tpu.training.trainer import build_training

    with pytest.raises(ValueError, match="inference-only"):
        build_training(Config(model=qcfg))


def test_quant_tp2_decode_matches_single_device(devices):
    """Quantized serving composes with tensor parallelism: QuantDense /
    QuantEmbed carry the same logical axes as their bf16 twins, so
    shard_for_inference distributes the int8 leaves and TP=2 greedy decode
    must reproduce the single-device tokens exactly."""
    from zero_transformer_tpu.inference.generate import (
        decode_model,
        generate,
        serve_mesh,
        shard_for_inference,
    )
    from zero_transformer_tpu.inference.sampling import SamplingConfig

    cfg = dataclasses.replace(CFG, param_quant="int8")
    model = decode_model(cfg, 24)
    prompt = jnp.asarray(
        np.random.default_rng(3).integers(0, cfg.vocab_size, (2, 8)), jnp.int32
    )
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))["params"]
    greedy = SamplingConfig(greedy=True)
    out_single = generate(model, params, prompt, 8, jax.random.PRNGKey(1), greedy)

    mesh = serve_mesh(2)
    sharded = shard_for_inference(model, params, mesh)
    n_int8_sharded = sum(
        1 for l in jax.tree.leaves(sharded)
        if l.dtype == jnp.int8 and not l.sharding.is_fully_replicated
    )
    assert n_int8_sharded > 0, "no int8 kernel was tensor-sharded"
    out_tp = generate(model, sharded, prompt, 8, jax.random.PRNGKey(1), greedy,
                      mesh=mesh)
    np.testing.assert_array_equal(np.asarray(out_single), np.asarray(out_tp))


def test_quant_speculative_composes():
    """Prompt-lookup speculation runs the quant model unchanged (it only
    calls apply): greedy spec output must equal the quant plain loop's."""
    from zero_transformer_tpu.inference.generate import decode_model, generate
    from zero_transformer_tpu.inference.sampling import SamplingConfig
    from zero_transformer_tpu.inference.speculative import generate_speculative

    cfg = dataclasses.replace(CFG, param_quant="int8")
    piece = jnp.asarray([[1, 5, 9, 2] * 4], jnp.int32)  # periodic prompt
    model = decode_model(cfg, piece.shape[1] + 8 + 4)
    params = model.init(jax.random.PRNGKey(0), piece[:, :4])["params"]
    plain = generate(model, params, piece, 8, jax.random.PRNGKey(1),
                     SamplingConfig(greedy=True))
    spec = generate_speculative(model, params, piece, 8, draft_len=4)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(spec))


def test_export_quantize_cli_roundtrip(tmp_path):
    """`export quantize` writes a serving msgpack; quantize_params is
    idempotent on it (kernel_q/scale leaves match no conversion rule), so
    serve --quantize accepts both raw and pre-quantized artifacts."""
    from zero_transformer_tpu.checkpoint import (
        export_params_msgpack,
        import_params_msgpack,
    )
    from zero_transformer_tpu.export import main as export_main

    x = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    params = nn.meta.unbox(Transformer(CFG).init(jax.random.PRNGKey(0), x)["params"])
    src = tmp_path / "p.msgpack"
    dst = tmp_path / "q.msgpack"
    export_params_msgpack(jax.tree.map(np.asarray, params), src)
    export_main(["quantize", "--params", str(src), "--out", str(dst)])
    assert dst.stat().st_size < 0.35 * src.stat().st_size  # f32 -> int8+scales
    q = import_params_msgpack(dst)
    q2 = quantize_params(q)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        q, q2,
    )


def test_quant_llama8b_fits_one_v5e_chip():
    """The headline claim behind `serve --quantize int8`, as a test:
    llama3-8B's quantized serving footprint — int8 params + f32 scales +
    a bf16 4k-context KV cache — fits a 16 GB v5e chip with margin.
    Abstract shapes only (eval_shape); nothing materializes."""
    from zero_transformer_tpu.inference.generate import decode_model

    cfg = model_config(
        "llama3_8b", dropout=0.0, param_dtype="bfloat16",
        compute_dtype="bfloat16", param_quant="int8", kv_cache_dtype="int8",
    )
    B, cache_len = 1, 4096
    model = decode_model(cfg, cache_len)
    shapes = nn.meta.unbox(jax.eval_shape(
        lambda r: model.init(r, jnp.zeros((B, 8), jnp.int32)),
        jax.random.PRNGKey(0),
    ))

    def nbytes(tree):
        return sum(
            int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
            for l in jax.tree.leaves(tree)
        )

    param_b = nbytes(shapes["params"])
    cache_b = nbytes(shapes["cache"])
    total = param_b + cache_b
    # ~8B params -> ~8 GB int8 (+ scales); int8 KV at 4k ctx is small
    assert 7.5e9 < param_b < 9.5e9, param_b
    assert total < 12e9, (param_b, cache_b)  # 16 GB HBM minus headroom
    # and the bf16 UNquantized model provably does NOT fit — the contrast
    # that makes --quantize the enabling lever, not an optimization
    full = decode_model(
        model_config("llama3_8b", dropout=0.0, param_dtype="bfloat16",
                     compute_dtype="bfloat16"), cache_len
    )
    full_shapes = nn.meta.unbox(jax.eval_shape(
        lambda r: full.init(r, jnp.zeros((B, 8), jnp.int32)),
        jax.random.PRNGKey(0),
    ))
    assert nbytes(full_shapes["params"]) > 15e9


def test_quant_llama_family_matches_dequantized_full():
    """RoPE/GQA/RMSNorm/SwiGLU/untied (the Llama recipe) under int8: the
    rotation applies to activations after the quantized q/k projections and
    the untied head is a QuantDense, so the whole family must reproduce the
    dequantized-full model like the GPT family does."""
    cfg = model_config("llama3_test", dropout=0.0, compute_dtype="float32",
                       param_dtype="float32")
    qcfg = dataclasses.replace(cfg, param_quant="int8")
    x = jnp.asarray([[1, 5, 9, 2, 7, 3, 4, 8]], jnp.int32)
    params = nn.meta.unbox(Transformer(cfg).init(jax.random.PRNGKey(0), x)["params"])
    params_q = quantize_params(jax.tree.map(np.asarray, params))
    expect = nn.meta.unbox(jax.eval_shape(
        lambda: Transformer(qcfg).init(jax.random.PRNGKey(0), x)
    )["params"])
    assert jax.tree.structure(jax.tree.map(lambda l: 0, params_q)) == \
        jax.tree.structure(jax.tree.map(lambda l: 0, expect))

    out_q = Transformer(qcfg).apply({"params": params_q}, x)
    out_f = Transformer(cfg).apply({"params": _dequantized(params_q, params)}, x)
    np.testing.assert_allclose(
        np.asarray(out_q), np.asarray(out_f), rtol=2e-4, atol=2e-4
    )


def test_quantize_params_validates_against_quant_model():
    """With cfg, quantize_params cross-checks its by-name conversion
    against the quant model's eval_shape structure: a good conversion
    passes, a mangled tree fails AT CONVERSION with the offending paths
    named (the alternative was an opaque flax structure mismatch deep
    inside apply — ADVICE round 5)."""
    params = Transformer(CFG).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    params = nn.meta.unbox(params)
    host = jax.tree.map(np.asarray, params)

    # the honest conversion validates clean
    quantize_params(host, CFG)

    # a checkpoint with an unexpected leaf name sails through the by-name
    # walk unconverted — validation must name the stray path
    bad = dict(host)
    bad["blocks"] = dict(bad["blocks"])
    bad["blocks"]["stray_module"] = {"kernel_oddname": np.zeros((4, 4))}
    with pytest.raises(ValueError, match="stray_module"):
        quantize_params(bad, CFG)

    # a missing subtree must also fail with the path, not inside apply
    short = {k: v for k, v in host.items() if k != "ln_f"}
    with pytest.raises(ValueError, match="ln_f"):
        quantize_params(short, CFG)

    # without cfg: legacy behavior, no validation
    quantize_params(bad)


def test_serve_rejects_prequantized_artifact_without_flag(tmp_path):
    """Importing an already-int8 msgpack without --quantize int8 must fail
    fast with the remedy in the message, not as a flax structure mismatch
    (ADVICE round 5)."""
    from flax.serialization import msgpack_serialize

    from zero_transformer_tpu.serve import main

    params = Transformer(CFG).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    q = quantize_params(jax.tree.map(np.asarray, nn.meta.unbox(params)))
    path = tmp_path / "p_int8.msgpack"
    path.write_bytes(msgpack_serialize(q))

    with pytest.raises(SystemExit, match="already int8-quantized"):
        main(["--model", "test", "--params", str(path),
              "--prompt", "x", "--tokenizer", "bytes"])


# ------------------------------------------------ int8 weight SERVING (PR 11)


def test_quant_engine_parity_with_generate():
    """The continuous-batching engine runs the int8 weight model through
    the same fused decode/prefill programs as full precision: every greedy
    trajectory byte-identical to single-request generate() on the SAME
    quantized tree — int8 weights ride the fused step, not a side path."""
    from zero_transformer_tpu.inference.generate import decode_model, generate
    from zero_transformer_tpu.inference.sampling import SamplingConfig
    from zero_transformer_tpu.serving import ServingEngine

    qcfg = dataclasses.replace(CFG, param_quant="int8")
    params = nn.meta.unbox(
        Transformer(CFG).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    )
    qparams = jax.tree.map(jnp.asarray, quantize_params(jax.tree.map(np.asarray, params), qcfg))
    model_q = decode_model(qcfg, 48)
    greedy = SamplingConfig(greedy=True)
    prompts = [[(3 + i + j) % 250 + 1 for j in range(n)]
               for i, n in enumerate((4, 9, 13))]
    refs = [
        jax.device_get(generate(
            model_q, qparams, jnp.asarray([p], jnp.int32), 8,
            jax.random.PRNGKey(i), greedy,
        ))[0].tolist()
        for i, p in enumerate(prompts)
    ]
    engine = ServingEngine(
        qcfg, qparams, n_slots=2, cache_len=48, sampling=greedy,
        prefill_chunk=8, page_size=8,
    )
    handles = [engine.submit(p, max_new_tokens=8, seed=i)
               for i, p in enumerate(prompts)]
    engine.run_until_idle()
    assert all(h.status == "done" for h in handles)
    assert [h.tokens for h in handles] == refs


def test_quant_perplexity_budget():
    """The parity gate for int8 weight serving: per-channel int8 must cost
    at most a small perplexity premium over full precision on held-out
    tokens. On the test model the quantization noise is tiny relative to
    the CE floor; the 2% ceiling is the budget the serving flag advertises
    (a real checkpoint regenerates this on its own eval split)."""
    from zero_transformer_tpu.ops.losses import next_token_loss

    qcfg = dataclasses.replace(CFG, param_quant="int8")
    params = nn.meta.unbox(
        Transformer(CFG).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    )
    qparams = quantize_params(jax.tree.map(np.asarray, params), qcfg)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, CFG.vocab_size, (4, 24)), jnp.int32
    )
    labels = jnp.roll(tokens, -1, axis=1)
    logits_fp = Transformer(CFG).apply({"params": params}, tokens)
    logits_q = Transformer(qcfg).apply({"params": qparams}, tokens)
    ppl_fp = float(jnp.exp(next_token_loss(logits_fp, labels)))
    ppl_q = float(jnp.exp(next_token_loss(logits_q, labels)))
    assert ppl_q <= ppl_fp * 1.02, (ppl_q, ppl_fp)

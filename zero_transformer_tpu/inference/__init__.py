"""In-tree TPU inference: KV-cache decode + sampling (replaces the
reference's CUDA/PyTorch side-car, reference ``torch_compatability/`` +
``app.py``)."""
from zero_transformer_tpu.inference.speculative import (
    generate_speculative,
    ngram_propose,
)
from zero_transformer_tpu.inference.generate import (
    decode_model,
    generate,
    generate_tokens,
    init_cache,
    prefill,
    serve_mesh,
    serving_params,
    shard_for_inference,
    stream_tokens,
)
from zero_transformer_tpu.inference.sampling import (
    SamplingConfig,
    apply_repetition_penalty,
    process_logits,
    sample_token,
    top_k_filter,
    top_p_filter,
)

__all__ = [
    "SamplingConfig",
    "apply_repetition_penalty",
    "decode_model",
    "generate",
    "generate_speculative",
    "generate_tokens",
    "init_cache",
    "ngram_propose",
    "prefill",
    "process_logits",
    "sample_token",
    "serve_mesh",
    "serving_params",
    "shard_for_inference",
    "stream_tokens",
    "top_k_filter",
    "top_p_filter",
]

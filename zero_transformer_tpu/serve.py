"""Text-generation CLI / demo server on TPU.

Replaces the reference's CUDA-only Gradio app (reference ``app.py``: hard
``torch.cuda.is_available()`` gate at :23-24, per-token Python sampling loop
at :69-94) with the in-tree jitted decode path. Runs as:

  python -m zero_transformer_tpu.serve --model 1_3b --params params.msgpack \\
      [--tokenizer <hf name or local path>] [--prompt "..."] [--ui]

- with ``--prompt``: one-shot generation to stdout;
- without: an interactive REPL;
- with ``--server``: the continuous-batching HTTP server (slot-based KV
  cache + request scheduler + SSE streaming — ``zero_transformer_tpu.serving``);
- with ``--ui``: the same controls in a Gradio web UI when gradio is
  importable (it is not baked into this image — the CLI is the primary
  surface; the reference made the UI the only surface).

The sampling controls mirror the reference UI (``app.py:199-259``):
temperature, top-k, top-p, repetition penalty, max tokens, greedy toggle.
"""
from __future__ import annotations

import argparse
import sys
from typing import Any, Optional

import jax
import jax.numpy as jnp


class ByteTokenizer:
    """UTF-8 byte-level tokenizer: token id = byte value (vocab 256).

    ``--tokenizer bytes``: a zero-dependency, zero-download fallback so the
    serve surface works on air-gapped machines and with byte-vocab models
    (the ``test`` zoo entry). No EOS — generation runs to max_new_tokens."""

    eos_token_id = None

    def encode(self, text: str):
        return list(text.encode("utf-8"))

    def decode(self, toks, **kwargs) -> str:
        return bytes(t for t in toks if 0 <= t < 256).decode("utf-8", errors="replace")


def _load_tokenizer(name_or_path: str):
    """GPT-NeoX tokenizer by default (what the reference trained with,
    reference ``app.py:27``). Must resolve locally — this environment has no
    egress, so pass a local path when the HF cache is cold, or ``bytes`` for
    the built-in byte-level fallback."""
    if name_or_path == "bytes":
        return ByteTokenizer()
    from transformers import AutoTokenizer

    return AutoTokenizer.from_pretrained(name_or_path)


class TextGenerator:
    """Tokenizer + params + compiled decode loop behind one ``__call__``."""

    def __init__(self, cfg, params: Any, tokenizer, cache_len: Optional[int] = None,
                 speculative: int = 0, tensor: int = 1,
                 top_k_impl: str = "exact"):
        from zero_transformer_tpu.inference import decode_model

        self.cfg = cfg
        # server-level execution knob, not a per-request sampling semantic:
        # "approx" swaps the per-step vocab sort for lax.approx_max_k (TPU
        # partial-reduce; kept set can be slightly wider than k)
        self.top_k_impl = top_k_impl
        self.tokenizer = tokenizer
        self.cache_len = cache_len or cfg.max_seq_len
        self.model = decode_model(cfg, self.cache_len)
        # tensor>1: shard params/cache over a pure-TP mesh so models larger
        # than one chip's HBM serve (llama3_8b on 4-8 chips); outputs match
        # single-chip decode (tested argmax-identical)
        self.mesh = None
        if tensor > 1:
            from zero_transformer_tpu.inference import serve_mesh, shard_for_inference

            self.mesh = serve_mesh(tensor)
            params = shard_for_inference(self.model, params, self.mesh)
            if speculative:
                print(
                    "serve: --speculative is single-chip only and is "
                    "DISABLED under --tensor>1 (requests take the plain "
                    "decode path)",
                    flush=True,
                )
                speculative = 0
        self.params = params
        # draft length for prompt-lookup speculative decoding (greedy one-shot
        # generation only; 0 = off)
        self.speculative = speculative

    def _decode(self, toks) -> str:
        """Detokenize through the shared pinned decode (no
        clean_up_tokenization_spaces) so the one-shot path, the REPL stream,
        and the SSE server can never diverge on detok behavior."""
        from zero_transformer_tpu.serving.detok import decode_tokens

        return decode_tokens(self.tokenizer, toks)

    def __call__(
        self,
        prompt: str,
        max_new_tokens: int = 128,
        temperature: float = 0.8,
        top_k: int = 0,
        top_p: float = 0.9,
        repetition_penalty: float = 1.1,
        greedy: bool = False,
        seed: int = 0,
    ) -> str:
        from zero_transformer_tpu.inference import generate

        ids, sampling, eos = self._prepare(
            prompt, max_new_tokens, temperature, top_k, top_p,
            repetition_penalty, greedy,
        )
        # draft scratch must fit the cache (prompt + new + K); shrink K to
        # whatever fits rather than erroring at the budget edge. every greedy
        # configuration routes through speculation: top-k/top-p are exactly
        # argmax-neutral, and the temperature division + repetition penalty
        # are mirrored bit-exactly inside the acceptance walk.
        spec_k = min(self.speculative, self.cache_len - len(ids) - max_new_tokens)
        # speculation is single-chip only for now: its draft/verify loop does
        # not take a mesh (TP serving goes through the plain path)
        if spec_k > 0 and greedy and self.mesh is None:
            from zero_transformer_tpu.inference import generate_speculative

            out = generate_speculative(
                self.model, self.params, jnp.asarray([ids], jnp.int32),
                max_new_tokens, draft_len=spec_k,
                eos_token_id=eos, pad_token_id=eos if eos is not None else 0,
                repetition_penalty=repetition_penalty,
                temperature=temperature,
            )
            toks = [t for t in out[0].tolist() if t != eos]
            return self._decode(toks)
        out = generate(
            self.model,
            self.params,
            jnp.asarray([ids], jnp.int32),
            max_new_tokens,
            jax.random.PRNGKey(seed),
            sampling,
            eos_token_id=eos,
            # pad finished rows with EOS so stripping EOS below also strips
            # padding, whatever the tokenizer's ids are
            pad_token_id=eos if eos is not None else 0,
            mesh=self.mesh,
        )
        toks = [t for t in out[0].tolist() if t != eos]
        return self._decode(toks)

    def _prepare(
        self, prompt, max_new_tokens, temperature, top_k, top_p,
        repetition_penalty, greedy,
    ):
        """Shared encode/truncate/sampling preamble for __call__ and stream
        (one source of truth: the two paths must never diverge)."""
        from zero_transformer_tpu.inference import SamplingConfig

        ids = self.tokenizer.encode(prompt.strip())
        budget = self.cache_len - max_new_tokens
        if budget < 1:
            raise ValueError("max_new_tokens leaves no room for the prompt")
        ids = ids[-budget:]  # keep the tail (reference app.py:61-64)
        sampling = SamplingConfig(
            temperature=temperature, top_k=top_k, top_p=top_p,
            repetition_penalty=repetition_penalty, greedy=greedy,
            top_k_impl=self.top_k_impl,
        )
        return ids, sampling, self.tokenizer.eos_token_id

    def stream(
        self,
        prompt: str,
        max_new_tokens: int = 128,
        temperature: float = 0.8,
        top_k: int = 0,
        top_p: float = 0.9,
        repetition_penalty: float = 1.1,
        greedy: bool = False,
        seed: int = 0,
    ):
        """Yield decoded text increments as tokens generate (the reference
        UI's streaming behavior, ``app.py:42-94``, on the jitted step)."""
        from zero_transformer_tpu.inference import stream_tokens

        from zero_transformer_tpu.serving.detok import StreamDecoder

        ids, sampling, eos = self._prepare(
            prompt, max_new_tokens, temperature, top_k, top_p,
            repetition_penalty, greedy,
        )
        # committed-prefix decoding via the shared StreamDecoder (HF
        # TextStreamer pattern): only the UNCOMMITTED tail is re-decoded
        # each step — O(n) total, not O(n^2) — and output is held back while
        # the tail is an incomplete byte sequence (byte-level BPE chars can
        # span tokens; decode -> U+FFFD). One implementation with the SSE
        # server's stream path, so the two surfaces cannot diverge.
        decoder = StreamDecoder(self.tokenizer)
        for token in stream_tokens(
            self.model, self.params, jnp.asarray([ids], jnp.int32),
            max_new_tokens, jax.random.PRNGKey(seed), sampling,
            eos_token_id=eos, mesh=self.mesh,
        ):
            t = int(token[0])
            if eos is not None and t == eos:
                break
            piece = decoder.push(t)
            if piece is not None:
                yield piece
        tail = decoder.flush()  # a genuinely incomplete tail at stream end
        if tail is not None:
            yield tail


def _has_quantized_leaves(tree) -> bool:
    """True when the tree already carries int8-serving leaves
    (``kernel_q``/``embedding_q`` — the layout ``models/quant.py`` emits)."""
    if not isinstance(tree, dict):
        return False
    return any(
        k in ("kernel_q", "embedding_q") or _has_quantized_leaves(v)
        for k, v in tree.items()
    )


# the ServingConfig knobs the autotuner searches (scripts/autotune.py):
# argparse leaves them at a None sentinel so explicit flags are
# distinguishable from "use the default"
_TUNED_KNOBS = ("prefill_chunk", "page_size", "page_pool_tokens", "draft_k")


def _resolve_tuned_args(args):
    """Resolve the autotuner-covered serving knobs in priority order:
    explicit CLI flag > TUNE_serve.json winner (``--tuned``, gated) >
    ServingConfig hand default. A tuned artifact whose platform/model do
    not match THIS run is refused with a loud message and the hand
    defaults stand — tuning is per (model, hardware, workload), never
    portable by assumption."""
    from zero_transformer_tpu.config import ServingConfig
    from zero_transformer_tpu.utils.modload import load_script

    defaults = ServingConfig()
    tuned: dict = {}
    if args.tuned:
        bc = load_script("bench_common.py")
        artifact, reasons = bc.load_tuned(
            args.tuned, platform=bc.platform_block(), model=args.model,
            target="serve",
        )
        if artifact is None:
            print(
                f"serve: --tuned {args.tuned} REFUSED "
                f"({'; '.join(reasons)}); falling back to hand defaults",
                flush=True,
            )
        else:
            tuned = dict((artifact.get("winner") or {}).get("knobs") or {})
            if tuned.get("draft_k") and args.repetition_penalty != 1.0:
                # _server would disable speculation later with its generic
                # flag-conflict message — the headline tuned knob must be
                # dropped HERE instead, before the "applying tuned
                # defaults" banner, with the artifact-aware remedy
                print(
                    f"serve: tuned draft_k={tuned['draft_k']} DROPPED: "
                    f"--repetition-penalty {args.repetition_penalty} is "
                    "incompatible with speculative verify; pass "
                    "--repetition-penalty 1.0 to serve the tuned winner "
                    "(the artifact's workload was measured without the "
                    "penalty)",
                    flush=True,
                )
                tuned.pop("draft_k")
            print(
                f"serve: --tuned {args.tuned}: autotuned defaults {tuned} "
                f"(tuned on {artifact.get('platform')}, workload "
                f"{artifact.get('workload_hash')}, "
                f"{artifact.get('value')}x vs hand defaults)",
                flush=True,
            )
    for name in _TUNED_KNOBS:
        if getattr(args, name) is None:
            setattr(args, name, tuned.get(name, getattr(defaults, name)))
    return args


def _build_generator(args) -> TextGenerator:
    from zero_transformer_tpu.checkpoint import import_params_msgpack
    from zero_transformer_tpu.config import model_config

    cfg = model_config(
        args.model, compute_dtype=args.dtype, dropout=0.0,
        kv_cache_dtype=args.kv_cache_dtype, param_quant=args.quantize,
        attention_impl=args.attention_impl,
    )
    params = import_params_msgpack(args.params)
    if args.quantize != "int8" and _has_quantized_leaves(params):
        # caught here, at import time: letting this through used to surface
        # as an opaque flax param-structure mismatch deep in apply()
        raise SystemExit(
            f"{args.params} is already int8-quantized (kernel_q/embedding_q "
            "leaves found); pass --quantize int8 to serve it"
        )
    if args.quantize == "int8":
        from zero_transformer_tpu.models.quant import quantize_params

        # quantize on HOST numpy first: deviceing the full-precision tree
        # before shrinking it would put the ~2x bytes on the chip at peak —
        # the exact OOM the flag exists to avoid on 8B-class models
        # (a pre-quantized artifact passes through unchanged and is
        # validated against the quant model's structure)
        params = quantize_params(params, cfg)
    params = jax.tree.map(jnp.asarray, params)
    tokenizer = _load_tokenizer(args.tokenizer)
    # graftlint: allow[donation-safety] reason=params are never donated — generate/engine donate cache+logits+masks+rngs by argnum, params excluded; the TP path additionally seals inside shard_for_inference
    return TextGenerator(
        cfg, params, tokenizer, cache_len=args.cache_len,
        speculative=args.speculative, tensor=args.tensor,
        top_k_impl="approx" if args.approx_top_k else "exact",
    )


def _reload_loader(gen: "TextGenerator", args):
    """Zero-arg loader for hot weight reload (SIGHUP / POST /admin/reload):
    re-runs the STARTUP param path — msgpack import, optional int8
    quantization, TP sharding under the serving mesh — so a swapped tree is
    prepared exactly like the one it replaces. Runs in the reload thread,
    never the tick thread; ``reload_params`` validates before the swap."""

    def load(path: str = args.params):
        from zero_transformer_tpu.checkpoint import import_params_msgpack

        params = import_params_msgpack(path)
        if args.quantize == "int8" and not _has_quantized_leaves(params):
            from zero_transformer_tpu.models.quant import quantize_params

            params = quantize_params(params, gen.cfg)
        if gen.mesh is not None:
            from zero_transformer_tpu.inference import shard_for_inference

            return shard_for_inference(gen.model, params, gen.mesh)
        return jax.tree.map(jnp.asarray, params)

    # graftlint: allow[donation-safety] reason=the closure's product is consumed only by engine.reload_params, which applies ensure_donatable before the tick-boundary swap
    return load


def _server(gen: TextGenerator, args) -> None:
    """Continuous-batching server mode: N KV-cache slots, bounded admission
    queue, SSE token streaming (POST /generate, GET /healthz, GET /metrics).
    Sampling controls come from the CLI and are ENGINE-level (baked into the
    fused decode step); requests vary prompt/budget/seed/deadline.

    Hot-path defaults (docs/SERVING.md): prompts prefill CHUNKED
    (--prefill-chunk tokens per tick, interleaved with decode so long
    prompts never stall active streams) with a chunk-aligned prefix cache
    (--prefix-cache) that lets repeated system prompts skip straight to
    their first novel chunk.

    Resilience wiring: /healthz answers 503 until the engine is READY and
    while it drains; SIGTERM closes admission and finishes in-flight
    generations up to --drain-deadline before exiting 0; SIGHUP (or
    POST /admin/reload) hot-swaps a new checkpoint between decode ticks
    without dropping a slot."""
    from zero_transformer_tpu.inference import SamplingConfig
    from zero_transformer_tpu.serving import ServingEngine, run_server
    from zero_transformer_tpu.utils.monitoring import MetricsLogger

    sampling = SamplingConfig(
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        repetition_penalty=args.repetition_penalty, greedy=args.greedy,
        top_k_impl=gen.top_k_impl,
    )
    draft_k = args.draft_k
    if draft_k and args.repetition_penalty != 1.0:
        print(
            "serve: --draft-k requires --repetition-penalty 1.0 (the batched "
            "verify step cannot emulate the in-block penalty); speculation "
            "DISABLED for this run",
            flush=True,
        )
        draft_k = 0
    engine = ServingEngine(
        gen.cfg,
        gen.params,
        n_slots=args.slots,
        cache_len=gen.cache_len,
        sampling=sampling,
        eos_token_id=gen.tokenizer.eos_token_id,
        max_queue=args.max_queue,
        mesh=gen.mesh,
        metrics=MetricsLogger(directory=args.metrics_dir),
        metrics_interval=args.metrics_interval,
        prefill_chunk=args.prefill_chunk,
        prefix_cache_chunks=args.prefix_cache,
        page_size=args.page_size,
        page_pool_tokens=args.page_pool_tokens,
        draft_k=draft_k,
        role=args.role,
        obs_dir=args.obs_dir or args.metrics_dir,
        trace=not args.no_trace,
    )
    # the engine holds the weights in their serving form; the loaded tree
    # (a float32 checkpoint: twice those bytes) has no other reader
    gen.params = None
    run_server(
        engine, gen.tokenizer, host=args.host, port=args.port,
        reload_source=_reload_loader(gen, args),
        drain_deadline_s=args.drain_deadline,
        admin_token=args.admin_token,
    )


def _repl(gen: TextGenerator, args) -> None:
    print("zero_transformer_tpu generation REPL — empty line to exit")
    while True:
        try:
            prompt = input(">>> ")
        except EOFError:
            return
        if not prompt.strip():
            return
        # stream tokens as they decode (reference app.py behavior)
        for piece in gen.stream(
            prompt,
            max_new_tokens=args.max_new_tokens,
            temperature=args.temperature,
            top_k=args.top_k,
            top_p=args.top_p,
            repetition_penalty=args.repetition_penalty,
            greedy=args.greedy,
        ):
            print(piece, end="", flush=True)
        print()


def _ui(gen: TextGenerator) -> None:
    try:
        import gradio as gr
    except ImportError:
        raise SystemExit(
            "gradio is not installed in this environment; use the CLI/REPL "
            "surface instead (the reference's UI dependency made serving "
            "CUDA+gradio-only, app.py:192-261)"
        )
    # mirror of the reference's controls (app.py:199-259)
    demo = gr.Interface(
        fn=lambda prompt, steps, temp, tk, tp, rp, greedy: gen(
            prompt,
            max_new_tokens=int(steps),
            temperature=temp,
            top_k=int(tk),
            top_p=tp,
            repetition_penalty=rp,
            greedy=greedy,
        ),
        inputs=[
            gr.Textbox(label="Prompt"),
            gr.Slider(1, 512, value=128, label="Max new tokens"),
            gr.Slider(0.1, 2.0, value=0.8, label="Temperature"),
            gr.Slider(0, 100, value=0, label="Top-k (0 = off)"),
            gr.Slider(0.0, 0.99, value=0.9, label="Top-p (0 = off)"),
            gr.Slider(1.0, 2.0, value=1.1, label="Repetition penalty"),
            gr.Checkbox(label="Greedy"),
        ],
        outputs=gr.Textbox(label="Completion"),
    )
    demo.launch()


def main(argv=None) -> None:
    p = argparse.ArgumentParser(prog="zero_transformer_tpu.serve", description=__doc__)
    p.add_argument("--model", required=True, help="model zoo name (configs/models.yaml)")
    p.add_argument("--params", required=True, help="params msgpack (see export)")
    p.add_argument("--tokenizer", default="EleutherAI/gpt-neox-20b")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--quantize", default="none", choices=("none", "int8"),
                   help="weight-only int8 serving: kernels + token table "
                        "stored int8 with per-channel scales — halves the "
                        "weight HBM reads decode is bound by, and fits "
                        "8B-class models on one 16 GB chip")
    p.add_argument("--attention-impl", default="auto",
                   choices=("auto", "xla", "flash"),
                   help="attention dispatch: 'auto' (default) runs the "
                        "Pallas kernels — flash for prefill/verify windows, "
                        "the paged-attention kernel for block-table decode — "
                        "wherever the gate accepts (TPU, or interpret mode "
                        "under ZT_PALLAS_INTERPRET=1), XLA elsewhere; 'xla' "
                        "forces the reference path; 'flash' is flash-or-"
                        "raise (never silently O(T^2))")
    p.add_argument("--kv-cache-dtype", default="auto", choices=("auto", "int8"),
                   help="int8 halves KV-cache HBM traffic (doubles servable "
                        "context) at slight quantization cost")
    p.add_argument("--cache-len", type=int, default=None)
    p.add_argument("--tensor", type=int, default=1, metavar="N",
                   help="tensor-parallel serving over the first N chips "
                        "(params + KV cache shard over heads/features; "
                        "serves models larger than one chip's HBM)")
    p.add_argument("--speculative", type=int, default=0, metavar="K",
                   help="prompt-lookup speculative decoding with K-token "
                        "drafts (greedy one-shot generation; exact same "
                        "output — incl. under the repetition penalty — in "
                        "fewer model forwards)")
    p.add_argument("--prompt", default=None, help="one-shot generation")
    p.add_argument("--max-new-tokens", type=int, default=128)
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--approx-top-k", action="store_true",
                   help="use the TPU partial-reduce (lax.approx_max_k) for "
                        "the top-k cutoff instead of the exact vocab sort; "
                        "the kept set can be slightly wider than k")
    p.add_argument("--top-p", type=float, default=0.9)
    p.add_argument("--repetition-penalty", type=float, default=1.1)
    p.add_argument("--greedy", action="store_true")
    p.add_argument("--ui", action="store_true", help="launch the Gradio UI")
    p.add_argument("--server", action="store_true",
                   help="continuous-batching HTTP server: slot-based KV "
                        "cache, bounded admission queue, SSE streaming "
                        "(POST /generate, GET /healthz, GET /metrics)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000)
    from zero_transformer_tpu.config import ServingConfig

    serving_defaults = ServingConfig()
    p.add_argument("--slots", type=int, default=serving_defaults.slots,
                   help="concurrent decode slots (KV-cache rows); queued "
                        "requests admit as slots free up")
    p.add_argument("--max-queue", type=int, default=serving_defaults.max_queue,
                   help="admission-queue depth; beyond it /generate "
                        "returns 429 (backpressure)")
    p.add_argument("--tuned", nargs="?", const="TUNE_serve.json",
                   default=None, metavar="TUNE_JSON",
                   help="load autotuned serving defaults from a "
                        "scripts/autotune.py artifact (default: "
                        "TUNE_serve.json). Applied only when the artifact's "
                        "platform/model match this run — a mismatch is "
                        "refused with a loud warning and the hand defaults "
                        "stand; explicit flags always win over tuned values")
    # the autotuner-covered knobs default to None (sentinel): resolution is
    # explicit flag > TUNE_serve.json winner (--tuned, gated) > the
    # ServingConfig hand default — see _resolve_tuned_args
    p.add_argument("--prefill-chunk", type=int,
                   default=None,
                   help="prefill this many prompt tokens per scheduler tick, "
                        "written through the slot's block table into the KV "
                        "page pool and interleaved with decode — a long "
                        "prompt cannot stall every active stream for its "
                        "full prefill (>= 1; default "
                        f"{serving_defaults.prefill_chunk})")
    p.add_argument("--prefix-cache", type=int,
                   default=serving_defaults.prefix_cache_chunks,
                   metavar="CHUNKS",
                   help="capacity of the chunk-aligned token-prefix index "
                        "over KV pages: repeated system prompts skip "
                        "straight to their first novel chunk, a hit being a "
                        "page-refcount bump (0 = off; flushed on hot reload)")
    p.add_argument("--page-size", type=int,
                   default=None,
                   help="tokens per KV page; must divide "
                        "--prefill-chunk and the cache length (default "
                        f"{serving_defaults.page_size})")
    p.add_argument("--page-pool-tokens", type=int,
                   default=None,
                   help="total page-pool capacity in token positions "
                        "(0 = slots x cache_len); at a "
                        "fixed budget, more concurrent streams fit whenever "
                        "real sequences run shorter than cache_len")
    p.add_argument("--draft-k", type=int, default=None,
                   help="speculative serving: verify K prompt-lookup draft "
                        "tokens per slot per tick in one batched forward "
                        "(greedy = bit-identical output, sampling = exact "
                        "rejection rule; needs --repetition-penalty 1.0; "
                        f"0 = off; default {serving_defaults.draft_k})")
    p.add_argument("--role", default=serving_defaults.role,
                   choices=("mixed", "prefill", "decode"),
                   help="disaggregated fleet role: 'prefill' runs only "
                        "chunked prefill and ships finished KV pages to the "
                        "decode replica each request names (prefill_to); "
                        "'decode' serves imported streams plus the "
                        "recompute fallback; 'mixed' (default) is the "
                        "classic standalone replica")
    p.add_argument("--metrics-dir", default=None,
                   help="JSONL sink for serving metrics (TTFT/ITL "
                        "percentiles, tokens/s, occupancy)")
    p.add_argument("--obs-dir", default=None,
                   help="observability run directory: flight-recorder dumps "
                        "(breaker-open/drain post-mortems), on-demand "
                        "profiler captures (POST /admin/profile), and span "
                        "trace exports land here (defaults to --metrics-dir; "
                        "unset disables dumps/profiling, not recording)")
    p.add_argument("--no-trace", action="store_true",
                   help="disable span tracing (the bounded ring costs <2%% "
                        "decode tok/s — BENCH_serve.json obs_overhead is "
                        "the measured number); /metrics histograms stay on")
    p.add_argument("--metrics-interval", type=int, default=200,
                   help="log serving metrics every N scheduler ticks")
    p.add_argument("--admin-token", default=None,
                   help="bearer token for /admin/* from non-loopback peers "
                        "(loopback is always allowed; without a token, "
                        "remote admin requests get 403 — weight swapping "
                        "must not be open to any peer that can reach a "
                        "--host 0.0.0.0 port)")
    p.add_argument("--drain-deadline", type=float,
                   default=serving_defaults.drain_deadline_s,
                   help="graceful-drain budget on SIGTERM/shutdown: "
                        "admission closes immediately (503 + Retry-After), "
                        "in-flight generations get this many seconds to "
                        "finish, then are force-finished and the process "
                        "exits 0")
    args = _resolve_tuned_args(p.parse_args(argv))
    if args.prefill_chunk < 1:
        p.error(
            "--prefill-chunk must be >= 1: one-shot prefill "
            "(--prefill-chunk 0) was removed, chunked prefill is the only "
            "admission path"
        )
    from zero_transformer_tpu.utils import compile_cache

    compile_cache.configure()

    gen = _build_generator(args)
    if args.server:
        _server(gen, args)
    elif args.ui:
        _ui(gen)
    elif args.prompt is not None:
        sys.stdout.write(
            gen(
                args.prompt,
                max_new_tokens=args.max_new_tokens,
                temperature=args.temperature,
                top_k=args.top_k,
                top_p=args.top_p,
                repetition_penalty=args.repetition_penalty,
                greedy=args.greedy,
            )
            + "\n"
        )
    else:
        _repl(gen, args)


if __name__ == "__main__":
    main()

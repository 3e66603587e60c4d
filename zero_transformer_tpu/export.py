"""Checkpoint export / import / surgery CLI.

Torch-free replacement for the reference's two-step export pipeline
(reference ``torch_compatability/extract_msgpack.py:10-17`` pulls params out
of a TrainState checkpoint into msgpack; ``convert_to_torch.py:13-23`` turns
that into a CUDA-side state dict). Here the interchange format stays flax
msgpack — consumable by anything flax — and depth-extension surgery
(reference ``src/utils/extend_params.py``) is a subcommand instead of a
notebook ritual.

Usage:
  python -m zero_transformer_tpu.export extract  --checkpoint-dir ckpts [--step N] --out params.msgpack
  python -m zero_transformer_tpu.export extend   --params params.msgpack --layers 24 --out big.msgpack
  python -m zero_transformer_tpu.export inspect  --params params.msgpack
  python -m zero_transformer_tpu.export import-reference --params ref.msgpack --model 1_3b --out ours.msgpack
  python -m zero_transformer_tpu.export to-reference --params ours.msgpack --model 1_3b --out ref.msgpack
"""
from __future__ import annotations

import argparse
from pathlib import Path

import jax
import numpy as np

# Leaf renaming per reference block (reference ``src/models/GPT.py:16-50``
# auto-names its submodules; ``layers.py`` Dense layers are all
# use_bias=False, LayerNorms scale-only, qkv kernels share our [in, (head,
# head_dim)] channel order, so conversion is a pure rename + per-layer
# stack). Its key-position-only ALiBi bias differs from ours by a per-query
# constant, which softmax cancels — the converted model computes the same
# function.
_REF_BLOCK_MAP = {
    ("LayerNorm_0", "scale"): ("ln_attn", "scale"),
    ("LayerNorm_1", "scale"): ("ln_mlp", "scale"),
    ("CausalAttention_0", "query_proj", "kernel"): ("attn", "query", "kernel"),
    ("CausalAttention_0", "key_proj", "kernel"): ("attn", "key", "kernel"),
    ("CausalAttention_0", "value_proj", "kernel"): ("attn", "value", "kernel"),
    ("CausalAttention_0", "residual_out", "kernel"): ("attn", "out", "kernel"),
    ("MLPBlock_0", "fc_in", "kernel"): ("mlp", "wi", "kernel"),
    ("MLPBlock_0", "fc_residual", "kernel"): ("mlp", "wo", "kernel"),
}


def convert_reference_params(ref: dict, scan_layers: bool = True) -> dict:
    """Reference (fattorib/ZeRO-transformer) param tree -> this framework's.

    ``ref`` is the nested dict from the reference's extracted-params msgpack
    (``torch_compatability/extract_msgpack.py``); an outer ``params`` wrapper
    is tolerated. Every reference leaf must be consumed and every expected
    leaf present — unknown or missing names raise instead of silently
    dropping weights.
    """
    from flax.traverse_util import flatten_dict, unflatten_dict

    ref = dict(ref.get("params", ref))
    block_keys = sorted(
        (k for k in ref if k.startswith("TransformerBlock_")),
        key=lambda s: int(s.rsplit("_", 1)[1]),
    )
    if not block_keys:
        raise ValueError("no TransformerBlock_* entries: not a reference params tree")
    expected_top = set(block_keys) | {"wte", "LayerNorm_0"}
    unknown = set(ref) - expected_top
    if unknown:
        raise ValueError(f"unrecognized reference entries: {sorted(unknown)}")

    out = {
        ("wte", "embedding"): np.asarray(ref["wte"]["embedding"]),
        ("ln_f", "scale"): np.asarray(ref["LayerNorm_0"]["scale"]),
    }
    stacked: dict = {dst: [] for dst in _REF_BLOCK_MAP.values()}
    for bk in block_keys:
        flat = flatten_dict(ref[bk])
        extra = set(flat) - set(_REF_BLOCK_MAP)
        missing = set(_REF_BLOCK_MAP) - set(flat)
        if extra or missing:
            raise ValueError(
                f"{bk}: unrecognized leaves {sorted(extra)} / missing {sorted(missing)}"
            )
        for src, dst in _REF_BLOCK_MAP.items():
            stacked[dst].append(np.asarray(flat[src]))
    if scan_layers:
        for dst, arrs in stacked.items():
            out[("blocks",) + dst] = np.stack(arrs)
    else:
        for dst, arrs in stacked.items():
            for i, a in enumerate(arrs):
                out[(f"block_{i}",) + dst] = a
    return unflatten_dict(out)


def convert_to_reference_params(params: dict) -> dict:
    """This framework's param tree -> the reference's extracted-params
    layout (exact inverse of ``convert_reference_params``; round-tripping
    through it is the identity, tested).

    Completes the interchange symmetry: the reference exports its
    checkpoints outward (``torch_compatability/flax_to_pytorch.py:70-117``);
    this writes OUR checkpoints into the reference's msgpack layout —
    torch-free, loadable by the reference's own flax tooling.

    Only the reference's architecture family converts (GPT-2+ALiBi: tied
    embeddings, scale-only norms, bias-free square attention, dense
    gelu MLP). Leaves with no reference counterpart (swiglu gate, untied
    lm_head, MoE experts, learned-position wpe) raise — a silent drop
    would write a checkpoint that loads but computes a different function.
    NOTE the layout alone cannot distinguish RMSNorm from LayerNorm (both
    store one ``scale``); use the CLI's ``--model`` check (or your own
    config) to guard that.
    """
    from flax.traverse_util import flatten_dict, unflatten_dict

    params = dict(params.get("params", params))
    inv = {dst: src for src, dst in _REF_BLOCK_MAP.items()}
    flat = {k: np.asarray(v) for k, v in flatten_dict(params).items()}

    out: dict = {}
    consumed = set()
    for src, dst in (
        (("wte", "embedding"), ("wte", "embedding")),
        (("ln_f", "scale"), ("LayerNorm_0", "scale")),
    ):
        if src not in flat:
            raise ValueError(f"params tree has no {'/'.join(src)} leaf")
        out[dst] = flat[src]
        consumed.add(src)

    per_block: dict = {}

    def emit(i: int, sub: tuple, arr: np.ndarray) -> None:
        src = inv.get(sub)
        if src is None:
            raise ValueError(
                f"block leaf {'/'.join(sub)} has no reference counterpart "
                "(the reference family is GPT-2+ALiBi: tied embeddings, "
                "scale-only norms, dense gelu MLP)"
            )
        out[(f"TransformerBlock_{i}",) + src] = arr
        per_block.setdefault(i, set()).add(sub)

    n_layers = 0
    if any(k[0] == "blocks" for k in flat):  # stacked nn.scan layout
        for key, arr in flat.items():
            if key[0] != "blocks":
                continue
            for i in range(arr.shape[0]):
                emit(i, key[1:], arr[i])
            n_layers = max(n_layers, arr.shape[0])
            consumed.add(key)
    else:  # per-block layout
        for key, arr in flat.items():
            if not key[0].startswith("block_"):
                continue
            suffix = key[0].rsplit("_", 1)[1]
            if not suffix.isdigit():
                raise ValueError(
                    f"top-level entry {key[0]!r} is not a block_<i> layer "
                    "of this framework's per-block layout"
                )
            i = int(suffix)
            emit(i, key[1:], arr)
            n_layers = max(n_layers, i + 1)
            consumed.add(key)
    if n_layers == 0:
        raise ValueError("no blocks/block_i entries: not this framework's params tree")
    # per-block completeness: MISSING leaves (a truncated tree, a gap in the
    # block_i indices) must raise like extra ones do — an incomplete
    # reference checkpoint would load and compute a different function
    for i in range(n_layers):
        gap = set(inv) - per_block.get(i, set())
        if gap:
            names = sorted("/".join(s) for s in gap)
            raise ValueError(f"block {i}: missing leaves {names}")

    leftovers = set(flat) - consumed
    if leftovers:
        names = sorted("/".join(k) for k in leftovers)
        raise ValueError(
            f"leaves with no reference counterpart: {names} — only the "
            "GPT-2+ALiBi family (tied head, dense MLP) exports to the "
            "reference layout"
        )
    d = out[("wte", "embedding")].shape[1]
    for i in range(n_layers):
        for proj in ("query_proj", "key_proj", "value_proj", "residual_out"):
            shape = out[(f"TransformerBlock_{i}", "CausalAttention_0", proj, "kernel")].shape
            if shape != (d, d):
                raise ValueError(
                    f"TransformerBlock_{i}/{proj} kernel {shape} is not square "
                    f"[{d},{d}] — GQA/MQA models have no reference counterpart"
                )
    return unflatten_dict(out)


def _cmd_extract(args) -> None:
    import orbax.checkpoint as ocp

    from zero_transformer_tpu.checkpoint import export_params_msgpack

    directory = Path(args.checkpoint_dir).absolute()
    with ocp.CheckpointManager(directory) as mgr:
        step = args.step if args.step is not None else mgr.latest_step()
        if step is None:
            raise SystemExit(f"no checkpoints under {directory}")
        # structure-agnostic raw read; keep only params
        restored = mgr.restore(step, args=ocp.args.Composite(state=ocp.args.StandardRestore()))
    state = restored["state"]
    params = state["params"] if isinstance(state, dict) else state.params
    out = export_params_msgpack(params, args.out)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    print(f"wrote {n:,} params (step {step}) -> {out}")


def _cmd_extend(args) -> None:
    from flax.serialization import msgpack_serialize

    from zero_transformer_tpu.checkpoint import import_params_msgpack
    from zero_transformer_tpu.utils.surgery import extend_depth, num_layers

    params = import_params_msgpack(args.params)
    old = num_layers(params)
    params = extend_depth(params, args.layers)
    Path(args.out).write_bytes(msgpack_serialize(params))
    print(f"extended {old} -> {args.layers} layers -> {args.out}")


def _cmd_upcycle(args) -> None:
    import jax
    import numpy as np
    from flax.serialization import msgpack_serialize

    from zero_transformer_tpu.checkpoint import import_params_msgpack
    from zero_transformer_tpu.utils.surgery import is_stacked, stack_blocks, upcycle_moe

    params = import_params_msgpack(args.params)
    if not is_stacked(params):
        params = stack_blocks(params)
    params = upcycle_moe(params, args.experts)
    Path(args.out).write_bytes(
        msgpack_serialize(jax.tree.map(np.asarray, params))
    )
    print(f"upcycled dense -> {args.experts} experts -> {args.out}")


def _cmd_import_reference(args) -> None:
    import jax.numpy as jnp
    from flax.serialization import msgpack_restore, msgpack_serialize

    from zero_transformer_tpu.config import model_config
    from zero_transformer_tpu.models import Transformer
    from zero_transformer_tpu.parallel.sharding import unbox

    ref = msgpack_restore(Path(args.params).read_bytes())
    cfg = model_config(args.model)
    params = convert_reference_params(ref, scan_layers=cfg.scan_layers)

    # validate every leaf against the target architecture's init shapes —
    # a wrong --model (depth, width, vocab) fails HERE, not at load time
    shapes = jax.eval_shape(
        lambda r: Transformer(cfg).init(r, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0),
    )["params"]
    shapes = unbox(shapes)
    flat_got = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_want = dict(jax.tree_util.tree_flatten_with_path(shapes)[0])
    for path, leaf in flat_got:
        want = flat_want.get(path)
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if want is None:
            raise SystemExit(f"converted leaf {name} not in {args.model} params")
        if tuple(want.shape) != tuple(leaf.shape):
            raise SystemExit(
                f"{name}: shape {tuple(leaf.shape)} != {args.model}'s {tuple(want.shape)}"
            )
    missing = set(flat_want) - {p for p, _ in flat_got}
    if missing:
        names = sorted("/".join(str(getattr(k, 'key', k)) for k in m) for m in missing)
        raise SystemExit(f"{args.model} params missing from conversion: {names}")

    Path(args.out).write_bytes(msgpack_serialize(params))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    print(f"converted {n:,} reference params ({args.model}) -> {args.out}")


def check_exportable(cfg) -> None:
    """Exit unless ``cfg`` is of the reference's family. The layout alone
    cannot tell: an RMSNorm stores one ``scale`` like a LayerNorm, and a
    looped stack (``n_loops`` > 1) has the plain stack's tree — written out,
    it would load and compute a one-pass model."""
    bad = [
        f"{field}={got!r} (reference: {want!r})"
        for field, got, want in (
            ("norm", cfg.norm, "layernorm"),
            ("position", cfg.position, "alibi"),
            ("activation", cfg.activation, "gelu"),
            ("tie_embeddings", cfg.tie_embeddings, True),
            ("n_loops", cfg.n_loops, 1),
            ("post_norm", cfg.post_norm, False),
            ("exit_gate", cfg.exit_gate, False),
        )
        if got != want
    ]
    if bad:
        raise SystemExit(
            f"{cfg.name} is outside the reference family: {'; '.join(bad)}"
        )


def _cmd_to_reference(args) -> None:
    from flax.serialization import msgpack_serialize

    from zero_transformer_tpu.checkpoint import import_params_msgpack

    params = import_params_msgpack(args.params)
    if args.model:
        from zero_transformer_tpu.config import model_config

        check_exportable(model_config(args.model))
    # unwrap once HERE: the converter tolerates an outer "params" wrapper,
    # so the layout detection and round-trip comparison below must see the
    # same unwrapped tree it converts
    params = dict(params.get("params", params))
    ref = convert_to_reference_params(params)
    # round-trip safety: the emitted layout must read back to the SAME tree
    # through the importer — the two maps must stay exact inverses. A real
    # check, not an assert: it must survive python -O
    back = convert_reference_params(
        ref, scan_layers=any(k == "blocks" for k in params)
    )
    for (pa, a), (pb, b) in zip(
        jax.tree_util.tree_flatten_with_path(params)[0],
        jax.tree_util.tree_flatten_with_path(back)[0],
    ):
        if pa != pb or not np.array_equal(
            np.asarray(a), np.asarray(b), equal_nan=True
        ):  # equal_nan: a diverged run's NaN weights still convert exactly
            raise SystemExit(f"round-trip mismatch at {pa}: refusing to write")
    Path(args.out).write_bytes(msgpack_serialize(ref))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(ref))
    print(f"wrote {n:,} params in reference layout -> {args.out}")


def _cmd_quantize(args) -> None:
    from zero_transformer_tpu.checkpoint import (
        export_params_msgpack,
        import_params_msgpack,
    )
    from zero_transformer_tpu.models.quant import quantize_params

    params = import_params_msgpack(args.params)
    out = export_params_msgpack(quantize_params(params), args.out)
    before = Path(args.params).stat().st_size
    after = Path(args.out).stat().st_size
    print(f"quantized {before:,} -> {after:,} bytes ({after / before:.2f}x) -> {out}")


def _cmd_inspect(args) -> None:
    from zero_transformer_tpu.checkpoint import import_params_msgpack
    from zero_transformer_tpu.utils.surgery import is_stacked, num_layers

    params = import_params_msgpack(args.params)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    total = 0
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        print(f"{name:60s} {str(leaf.dtype):10s} {tuple(leaf.shape)}")
        total += int(np.prod(leaf.shape))
    print(
        f"-- {total:,} params, {num_layers(params)} layers "
        f"({'stacked' if is_stacked(params) else 'per-block'} layout)"
    )


def main(argv=None) -> None:
    p = argparse.ArgumentParser(prog="zero_transformer_tpu.export", description=__doc__)
    sub = p.add_subparsers(dest="cmd", required=True)

    ex = sub.add_parser("extract", help="orbax checkpoint -> params msgpack")
    ex.add_argument("--checkpoint-dir", required=True)
    ex.add_argument("--step", type=int, default=None)
    ex.add_argument("--out", required=True)
    ex.set_defaults(fn=_cmd_extract)

    et = sub.add_parser("extend", help="depth-extend params (Gopher G.3.3 warm start)")
    et.add_argument("--params", required=True)
    et.add_argument("--layers", type=int, required=True)
    et.add_argument("--out", required=True)
    et.set_defaults(fn=_cmd_extend)

    up = sub.add_parser(
        "upcycle", help="dense params -> MoE warm start (sparse upcycling)"
    )
    up.add_argument("--params", required=True)
    up.add_argument("--experts", type=int, required=True)
    up.add_argument("--out", required=True)
    up.set_defaults(fn=_cmd_upcycle)

    ins = sub.add_parser("inspect", help="list tensors in a params msgpack")
    ins.add_argument("--params", required=True)
    ins.set_defaults(fn=_cmd_inspect)

    qz = sub.add_parser(
        "quantize",
        help="params msgpack -> weight-only int8 serving msgpack (the "
             "conversion serve/evalharness --quantize run, paid once; "
             "~4x smaller artifact from f32, ~2x from bf16)",
    )
    qz.add_argument("--params", required=True)
    qz.add_argument("--out", required=True)
    qz.set_defaults(fn=_cmd_quantize)

    tr = sub.add_parser(
        "to-reference",
        help="this framework's params msgpack -> the reference's "
             "extracted-params layout (inverse of import-reference, "
             "round-trip-verified)",
    )
    tr.add_argument("--params", required=True)
    tr.add_argument("--model", default=None,
                    help="optional zoo name: reject configs outside the "
                         "reference family (rmsnorm/rope/swiglu/untied)")
    tr.add_argument("--out", required=True)
    tr.set_defaults(fn=_cmd_to_reference)

    ir = sub.add_parser(
        "import-reference",
        help="reference (fattorib/ZeRO-transformer) params msgpack -> this "
             "framework's layout, shape-validated against a zoo model",
    )
    ir.add_argument("--params", required=True,
                    help="the reference's extracted-params msgpack")
    ir.add_argument("--model", required=True, help="target zoo name")
    ir.add_argument("--out", required=True)
    ir.set_defaults(fn=_cmd_import_reference)

    args = p.parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    main()

"""End-to-end quality loop: prepare -> train -> eval -> serve, zero egress.

The reference's headline evidence is its published model-quality table
(reference ``README.md:53-57``: LAMBADA PPL/ACC + Pile BPB per model), which
required exporting to PyTorch and running lm-eval-harness on a GPU. This
script demonstrates the same capability IN-TREE at no-download scale:

1. gather a real-text corpus from the image (repo + reference markdown,
   package READMEs/licenses/doc trees) — natural English, deduplicated;
2. ``data.prepare`` it into tar shards with the built-in byte tokenizer
   (vocab 256, NUL document separator -> packed-sequence masking);
3. pretrain the ``byte_25m`` config (``configs/train_e2e_bytes.yaml``),
   recording train/val loss to ``metrics.jsonl``;
4. export msgpack params and score held-out text with the in-tree
   evalharness: byte perplexity, bits-per-byte, and a LAMBADA-style
   last-word completion task built from held-out paragraphs;
5. generate a sample from the checkpoint through ``serve.py`` (byte
   tokenizer, greedy).

Artifacts land in ``--out`` (default ``runs/e2e``): ``metrics.jsonl``,
``eval.json``, ``sample.txt``. Modes: ``--mode smoke`` (CPU, ~2 min, proves
the loop); ``--mode full`` (the real run — on the TPU chip this is ~10 min).

Usage::

  python scripts/e2e_quality.py --mode smoke
  python scripts/e2e_quality.py --mode full
"""
from __future__ import annotations

import argparse
import glob
import gzip
import hashlib
import json
import os
import re
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

# Ordered prose-first: the byte cap truncates from the END, so natural
# English survives in full and code fills the remainder (the reference's
# own training set was a pile + code mix, its data/index names say so).
TEXT_SOURCES = [
    "/root/repo/*.md",
    "/root/repo/docs/*.md",
    "/root/reference/*.md",
    "/root/reference/**/*.md",
    "/opt/venv/lib/python3.12/site-packages/**/README*",
    "/opt/venv/lib/python3.12/site-packages/**/*.rst",
    "/opt/venv/lib/python3.12/site-packages/**/LICENSE*",
    "/usr/share/doc/**/*.txt",
    "/usr/share/doc/**/copyright",
    "/usr/share/doc/**/changelog*",  # mostly .gz; gather decompresses
    "/usr/local/lib/python3.12/*.py",  # stdlib source = the code mix
    "/usr/local/lib/python3.12/[a-z]*/*.py",
    # site-packages source (numpy/jax/flax/...) last: the cap bounds it
    "/opt/venv/lib/python3.12/site-packages/[a-z]*/**/*.py",
]


def gather_corpus(out_dir: Path, cap_bytes: int, heldout_frac: float = 0.05):
    """Collect real text files into train/heldout doc lists (dedup by hash)."""
    seen: set = set()
    docs: list[str] = []
    total = 0

    def iter_paths():
        # glob lazily per pattern: once the cap is met, later (large, code)
        # patterns are never even walked — smoke mode stops at the prose
        for pattern in TEXT_SOURCES:
            if total >= cap_bytes:
                return
            yield from sorted(glob.glob(pattern, recursive=True))

    for p in iter_paths():
        if total >= cap_bytes:
            break
        try:
            raw = Path(p).read_bytes()
            if p.endswith(".gz"):
                raw = gzip.decompress(raw)
            text = raw.decode("utf-8", errors="strict")
        except Exception:
            continue  # binary / non-utf8 / unreadable: not corpus material
        if len(text) < 512:
            continue
        if "\x00" in text:
            continue  # NUL is the document separator; must not occur in-doc
        h = hashlib.sha256(text.encode()).hexdigest()
        if h in seen:  # identical LICENSE files appear dozens of times
            continue
        seen.add(h)
        docs.append(text)
        total += len(text)
    if total < 1 << 20:
        raise SystemExit(f"only {total} bytes of corpus text found — need >=1MB")
    # deterministic split by doc hash (stable across runs/machines)
    train, heldout = [], []
    for d in docs:
        frac = int(hashlib.sha256(d.encode()).hexdigest()[:8], 16) / 0xFFFFFFFF
        (heldout if frac < heldout_frac else train).append(d)
    out_dir.mkdir(parents=True, exist_ok=True)
    for name, split in (("train", train), ("heldout", heldout)):
        with open(out_dir / f"{name}.jsonl", "w") as f:
            for d in split:
                f.write(json.dumps({"text": d}) + "\n")
    print(f"corpus: {len(train)} train docs, {len(heldout)} heldout docs, "
          f"{total/1e6:.1f} MB", flush=True)
    return train, heldout


def build_eval_files(heldout: list[str], data_dir: Path, max_ppl_bytes: int,
                     max_lambada: int, ctx: int = 512):
    """Pre-tokenized (byte) eval JSONLs for the in-tree evalharness."""
    # ppl / bpb: one big token stream from held-out docs
    stream = "\n\n".join(heldout)[:max_ppl_bytes]
    tokens = list(stream.encode("utf-8"))
    with open(data_dir / "heldout_ppl.jsonl", "w") as f:
        f.write(json.dumps({"tokens": tokens, "num_bytes": len(tokens)}) + "\n")

    # LAMBADA-style last-word completion: context = paragraph minus final
    # word, target = " " + final word (the reference task's shape,
    # reference README.md:53-57, at byte granularity)
    n = 0
    with open(data_dir / "heldout_lastword.jsonl", "w") as f:
        for doc in heldout:
            for para in doc.split("\n\n"):
                para = para.strip()
                words = para.split()
                if not (12 <= len(words) <= 80) or len(para) > 1200:
                    continue
                last = words[-1]
                if not re.fullmatch(r"[A-Za-z][A-Za-z'\-]{2,}[.:,;]?", last):
                    continue  # target must be a real word, as in LAMBADA
                context = para[: len(para) - len(last) - 1]
                target = " " + last
                f.write(json.dumps({
                    "context": list(context.encode()),
                    "target": list(target.encode()),
                }) + "\n")
                n += 1
                if n >= max_lambada:
                    break
            if n >= max_lambada:
                break
    # PIQA/Winogrande-style choice task (the reference's other published
    # metric shape, reference README.md:53-57): pick the paragraph's TRUE
    # second half among distractor continuations taken from other
    # paragraphs. Gold position round-robins over the example index.
    paras = [
        p.strip() for doc in heldout for p in doc.split("\n\n")
        if 200 <= len(p.strip()) <= 900
    ]
    if len(paras) < 4:
        raise SystemExit(
            f"only {len(paras)} usable paragraphs — too few for the choice task"
        )
    cap = max(32, ctx // 2 - 8)  # scoring.py needs continuation BYTES < seq_len

    def second_half(s: str) -> tuple[str, str]:
        """Split at a whitespace boundary near the middle: a mid-word cut
        would let spelling alone identify the gold continuation."""
        cut = s.find(" ", len(s) // 2)
        cut = cut if cut != -1 else len(s) // 2
        return s[:cut], s[cut:]

    def cap_b(s: str) -> str:
        # cap in BYTES, not characters — multi-byte UTF-8 would otherwise
        # overflow the scoring window
        return s.encode()[:cap].decode("utf-8", errors="ignore")

    n_choice = 0
    with open(data_dir / "heldout_choice.jsonl", "w") as f:
        for i, para in enumerate(paras):
            context, true_cont = second_half(para)
            cands = [
                cap_b(true_cont),
                cap_b(second_half(paras[(i + 1) % len(paras)])[1]),
                cap_b(second_half(paras[(i + 2) % len(paras)])[1]),
            ]
            gold = i % 3  # round-robin gold position by example index
            cands[0], cands[gold] = cands[gold], cands[0]
            f.write(json.dumps({
                "context": list(context.encode()),
                "choices": [list(c.encode()) for c in cands],
                "gold": gold,
                "choice_bytes": [len(c.encode()) for c in cands],
            }) + "\n")
            n_choice += 1
            if n_choice >= (20 if len(paras) < 100 else 200):
                break
    print(f"eval files: {len(tokens)} ppl bytes, {n} last-word examples, "
          f"{n_choice} choice examples", flush=True)
    if n == 0:
        raise SystemExit("no last-word examples extracted")


def run(cmd: list[str], **kw) -> subprocess.CompletedProcess:
    print("+", " ".join(str(c) for c in cmd), flush=True)
    return subprocess.run([str(c) for c in cmd], check=True, **kw)


def _child_env(force_cpu: bool) -> dict:
    """``force_cpu`` pins a child to the CPU backend through its
    environment (jax reads ``JAX_PLATFORMS`` when it is imported)."""
    return dict(os.environ, JAX_PLATFORMS="cpu") if force_cpu else dict(os.environ)


def run_cli(module: str, argv: list, force_cpu: bool, **kw):
    """Invoke an in-tree CLI (``python -m module``) in a subprocess. One
    process per chip: this parent never imports jax and runs its children
    one after another, so each has the chip to itself."""
    argv = [str(a) for a in argv]
    print(f"+ [{module}]", " ".join(argv), flush=True)
    return subprocess.run(
        [sys.executable, "-m", module, *argv], check=True,
        env=_child_env(force_cpu), **kw,
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", choices=("smoke", "full"), default="smoke")
    ap.add_argument("--out", default="runs/e2e")
    ap.add_argument("--force-cpu", action="store_true",
                    help="pin the cpu platform (smoke defaults to this)")
    ap.add_argument("--on-chip", action="store_true",
                    help="smoke mode: run train/eval on the default (TPU) "
                         "backend instead of smoke's CPU pin — a ~3-minute "
                         "on-chip proof of the whole loop for windows too "
                         "short for the full byte_25m run")
    ap.add_argument("--steps", type=int, default=None,
                    help="override training.total_steps (full mode: right-size "
                         "the on-chip run to the available window)")
    ap.add_argument("--model", default=None,
                    help="full mode: zoo name overriding byte_25m for BOTH "
                         "train and eval (byte_2m = the CPU-scale sibling)")
    ap.add_argument("--extra-set", nargs="*", default=[], metavar="KEY=V",
                    help="extra train.py --set overrides appended LAST "
                         "(e.g. training.batch_size=4 for a CPU budget)")
    args = ap.parse_args()

    out = Path(args.out)
    data_dir = out / "data"
    smoke = args.mode == "smoke"
    if args.on_chip and not smoke:
        raise SystemExit(
            "--on-chip is a smoke-mode option (full mode already runs on the "
            "default backend); drop --mode full or drop --on-chip"
        )
    cap = 2 << 20 if smoke else 64 << 20

    # fresh run state: metrics.jsonl is an append-mode sink and orbax
    # refuses to overwrite existing steps — a rerun over a stale --out
    # would concatenate trajectories / fail the save
    import shutil

    shutil.rmtree(out / "ckpt", ignore_errors=True)

    ctx = 128 if smoke else 512
    train, heldout = gather_corpus(data_dir, cap_bytes=cap)
    build_eval_files(
        heldout, data_dir,
        max_ppl_bytes=(50_000 if smoke else 400_000),
        max_lambada=(40 if smoke else 400),
        ctx=ctx,
    )

    # --- prepare: tar shards + index for train AND a small val split
    for split, inp in (("train", data_dir / "train.jsonl"),
                       ("val", data_dir / "heldout.jsonl")):
        run_cli("zero_transformer_tpu.data.prepare",
                ["--input", inp, "--tokenizer", "bytes",
                 "--max-context", ctx, "--format", "tar", "--doc-sep", 0,
                 "--rows-per-shard", 512, "--out", data_dir / split],
                force_cpu=True, cwd=REPO)

    # --- train (the train.py CLI surface, exactly as a user would)
    overrides = [
        "--set", f"checkpoint.directory={out}/ckpt",
        "--set", f"data.train_path={data_dir}/train.index",
        "--set", f"data.validation_path={data_dir}/val.index",
    ]
    if smoke:
        overrides += [
            "--set", "model.size=test",
            "--set", "model.doc_sep_token=0",
            "--set", "model.max_seq_len=128",
            "--set", f"training.train_context={ctx}",
            "--set", f"data.max_context={ctx}",
            "--set", "training.batch_size=8",
            "--set", "training.total_steps=60",
            "--set", "training.evaluation_frequency=20",
            "--set", "training.maximum_evaluation_steps=4",
            "--set", "training.log_frequency=10",
            "--set", "optimizer.warmup_steps=10",
            "--set", "checkpoint.save_frequency=60",
        ]
    if args.steps is not None:
        if args.steps < 10:
            raise SystemExit("--steps must be >= 10 (warmup+decay need room)")
        # LAST so it wins in either mode (train.py --set: last occurrence
        # takes effect). warmup must shrink with the run or the cosine
        # schedule gets decay_steps <= 0 (config warmup is 200); eval
        # frequency must shrink too or short runs record no validation loss
        overrides += [
            "--set", f"training.total_steps={args.steps}",
            "--set", f"checkpoint.save_frequency={args.steps}",
            "--set", f"optimizer.warmup_steps={max(1, min(200, args.steps // 10))}",
            "--set", f"training.evaluation_frequency={max(10, args.steps // 10)}",
        ]
    if args.model:
        if smoke:
            raise SystemExit(
                "--model is a full-mode option (smoke always runs the 'test' "
                "zoo model); drop --mode smoke or drop --model"
            )
        overrides += ["--set", f"model.size={args.model}"]
    for kv in args.extra_set:
        overrides += ["--set", kv]
    # --on-chip lifts smoke's CPU pin (train + eval on the default backend);
    # an explicit --force-cpu still wins
    pin_cpu = (smoke and not args.on_chip) or args.force_cpu
    run([sys.executable, "train.py", "--cfg", "configs/train_e2e_bytes.yaml",
         *overrides], cwd=REPO, env=_child_env(pin_cpu))

    # --- export msgpack from the checkpoint (host-side work; always CPU)
    params = out / "params.msgpack"
    run_cli("zero_transformer_tpu.export",
            ["extract", "--checkpoint-dir", out / "ckpt", "--out", params],
            force_cpu=True, cwd=REPO)

    # --- eval: byte ppl, bits-per-byte, last-word accuracy
    model_name = "test" if smoke else (args.model or "byte_25m")
    force_cpu = pin_cpu
    results = {}
    eval_common = ["--model", model_name, "--params", params,
                   "--seq-len", ctx,
                   "--dtype", "float32" if smoke else "bfloat16"]
    for task, data in (("bpb", "heldout_ppl.jsonl"),
                       ("lambada", "heldout_lastword.jsonl"),
                       ("choice", "heldout_choice.jsonl")):
        proc = run_cli("zero_transformer_tpu.evalharness.cli",
                       eval_common + ["--task", task, "--data", data_dir / data],
                       force_cpu=force_cpu,
                       cwd=REPO, capture_output=True, text=True)
        lines = [l for l in proc.stdout.splitlines() if l.strip().startswith("{")]
        if not lines:
            raise SystemExit(
                f"evalharness {task} printed no JSON line.\n"
                f"stdout:\n{proc.stdout[-2000:]}\nstderr:\n{proc.stderr[-2000:]}"
            )
        results[task] = json.loads(lines[-1])
        print(task, "->", lines[-1], flush=True)
    (out / "eval.json").write_text(json.dumps(results, indent=2))

    # --- serve: one greedy sample through the real CLI
    new_tokens = 48 if smoke else 256
    prompt = "The license terms of this "
    proc = run_cli("zero_transformer_tpu.serve",
                   ["--model", model_name, "--params", params,
                    "--tokenizer", "bytes", "--greedy",
                    # ALiBi extrapolates, but the KV cache is fixed-shape:
                    # size it for prompt + continuation explicitly (the
                    # smoke model's max_seq_len would be too small)
                    "--cache-len", len(prompt) + new_tokens + 8,
                    "--max-new-tokens", new_tokens,
                    "--prompt", prompt],
                   force_cpu=force_cpu,
                   cwd=REPO, capture_output=True, text=True)
    (out / "sample.txt").write_text(proc.stdout)
    print("sample:", proc.stdout[-300:], flush=True)
    print(f"E2E {args.mode} loop complete -> {out}", flush=True)


if __name__ == "__main__":
    main()

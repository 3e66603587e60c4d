"""The flash attention kernels' share of their roofline: the least time the
chip could take for the calls the trace holds over the time of the events
named ``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv`` by the kernels'
own ``name=`` (JAX wraps the name in the transformation that made the call:
``jvp_flash_fwd_``, ``transpose_jvp_flash_bwd_dq__``).

Per call, whatever implements it, the causal half of the matmuls the kernel
must do: with W = rows x heads x T^2 x head_dim, forward 2W (q.k and p.v),
``dq`` 3W (q.k, do.v, ds.k), ``dkv`` 4W (q.k, do.v, p.do, ds.q) operations;
in bytes q, k, v and the output, and their gradients where read or written,
once. The least time of a call is the larger of operations over peak and
bytes over bandwidth; recomputed forwards are calls like any other."""
import re

from benchmark import arith

# kernel -> (matmuls over the causal half, arrays of [rows, T, heads, head_dim])
KERNELS = {
    "flash_fwd": (2.0, 4),       # reads q k v, writes o
    "flash_bwd_dq": (3.0, 5),    # reads q k v do, writes dq
    "flash_bwd_dkv": (4.0, 6),   # reads q k v do, writes dk dv
}


def pattern(kernel: str) -> re.Pattern:
    return re.compile(rf"^%?[\w.\-]*{kernel}[_.\d]*( = |$)")


def call_ops_bytes(kernel: str, rows: int, heads: int, seq_len: int, head_dim: int,
                   itemsize: int = 2) -> tuple:
    matmuls, arrays = KERNELS[kernel]
    w = float(rows) * heads * seq_len * seq_len * head_dim
    return matmuls * w, float(arrays) * rows * heads * seq_len * head_dim * itemsize


def read(ctx):
    tr = ctx.get("trace")
    if not tr or "rows_per_micro" not in ctx:
        return None
    m = ctx["model"]
    least = took = 0.0
    for kernel in KERNELS:
        rx = pattern(kernel)
        events = [e - s for evs in tr["events"].values() for name, s, e in evs
                  if rx.search(name)]
        if not events:
            continue
        ops, byts = call_ops_bytes(kernel, ctx["rows_per_micro"], m["n_heads"],
                                   ctx["seq_len"], m["head_dim"])
        n_devices = len(tr["events"])
        least += arith.roofline_seconds(ops, byts, ctx["peak"])[0] * len(events) / n_devices
        took += sum(events) / n_devices
    return 100.0 * least / took if took > 0 else None

"""The decode program's share of the chip's memory bandwidth: the least time
the bytes a decode tick MUST read could take, over the time the traced
decode programs took on the device (their ``XLA Modules`` events).

The bytes are the family's own count (``decode_read_bytes`` of the looped
family's reference module, beside its ``active_params``): the layer
matrices once a PASS and the head once, in the served dtype, plus K and V of
the live cached positions in every (pass, layer) entry. Pass t+1 needs pass
t's last layer and the layers do not stay on the chip, so no implementation
reads the weights less often: the share cannot pass 100%. How many passes a
tick ran is what the PROGRAM says it ran, the ``loops`` attribute of its
``decode_step`` spans: a program whose spans carry none (one that knows no
looped stack and serves such a configuration as a one-pass model) gives
nothing to read. The live context of each traced tick comes from the
requests' own records, as ``paged_attention_roofline`` finds it."""
from benchmark import harness, host_trace

PROGRAM = r"^jit__(fused_step|spec_step|forward_only)_impl\b"


def live_positions(ctx):
    """Per ``decode_step`` span inside the capture, ``(passes the tick ran,
    cached positions summed over the rows decoding in it)``."""
    ta, tb = ctx["traced"]
    out = []
    for _, track, name, s, e, attrs in ctx["spans"]:
        if track != "engine" or name != "decode_step" or s < ta or e > tb:
            continue
        live = 0
        for r in ctx["records"]:
            if r["prefill_done_at"] is None or not (r["prefill_done_at"] <= s < (r["finished_at"] or s + 1)):
                continue
            live += len(r["prompt"]) + sum(1 for t in r["token_times"] if t < s)
        out.append(((attrs or {}).get("loops"), live))
    return out


def read(ctx):
    m = ctx.get("model") or {}
    if not ctx.get("trace") or not ctx.get("spans") or not ctx.get("records") \
            or "traced" not in ctx or "n_loops" not in m:
        return None
    loaded = host_trace.load()
    took = host_trace.program_durations(loaded, PROGRAM) if loaded else []
    ticks = live_positions(ctx)
    if not took or not ticks or any(loops is None for loops, _ in ticks):
        return None
    family = harness.load_reference({"reference": "benchmark/reference/ouro_looplm.py"})
    least = [family.decode_read_bytes(dict(m, n_loops=loops), n) / ctx["peak"]["bytes_per_s"]
             for loops, n in ticks]
    return 100.0 * (sum(least) / len(least)) / (sum(took) / len(took))

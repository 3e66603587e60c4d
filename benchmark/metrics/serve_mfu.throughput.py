"""``serve_mfu`` for a serving cell judged on throughput alone: the same
reading, the whole model step's share of the chip's peak while the engine
works, declared apart because a per-layer metric moves one end-to-end metric,
and such a cell reports ``serve_tokens_per_s`` and ``setup_s``, not
``itl_p95_ms``."""
from benchmark import harness


def read(ctx):
    return harness.load_reader("serve_mfu").read(ctx)

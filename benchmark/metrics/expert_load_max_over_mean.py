"""How unevenly the router loads the experts while the engine decodes,
against what an even router would give at the same number of rows: the rows
of the busiest expert, summed over the routed layers and the window's decode
ticks (the ``moe_load_max`` attribute of the engine's ``decode_step`` spans,
from the counts the step program sums), over the same sum's EXPECTED value
when every row chooses its ``moe_top_k`` experts uniformly at random.

The plain ratio of the busiest expert's rows to the mean rows an expert
follows the number of rows that decode, not the router: with 8 rows a layer
routes 32 pairs over 64 experts, mean 0.5, and the busiest holds 3 whatever
the router does (7.7 at 0.96 requests/s, 10.1 at 0.63; my chip runs, PR 31).
Dividing by the expected maximum at each tick's own row count takes that
out: 1 is a router as even as chance, above 1 a skewed one. On ONE chip that
holds every expert a skewed router touches fewer experts a tick and reads
fewer bytes, so HIGHER is faster here; under an expert-parallel cut the
busiest chip would set the pace and lower would be."""
import numpy as np

from benchmark import moe_ticks

DRAWS = 4096


def expected_max(rows: int, n_experts: int, top_k: int, _memo={}) -> float:
    """E[rows of the busiest expert] when each of ``rows`` rows picks
    ``top_k`` distinct experts of ``n_experts`` uniformly: the mean over
    ``DRAWS`` seeded draws (the same number in every run)."""
    key = (rows, n_experts, top_k)
    if key not in _memo:
        rng = np.random.default_rng(0)
        picks = np.argsort(rng.random((DRAWS, rows, n_experts)), axis=-1)[..., :top_k]
        load = np.zeros((DRAWS, n_experts), np.int64)
        np.add.at(load, (np.arange(DRAWS)[:, None, None], picks), 1)
        _memo[key] = float(load.max(axis=1).mean())
    return _memo[key]


def read(ctx):
    ticks = moe_ticks.decode_ticks(ctx, within=(ctx.get("t0"), ctx.get("t_end")), live=False)
    m = ctx.get("model") or {}
    expected = sum(
        moe_ticks.routed_layers(m) * expected_max(round(t["rows"]), m["n_experts"], m["moe_top_k"])
        for t in ticks if round(t["rows"]) > 0)
    if not expected:
        return None
    return sum(t["load_max"] for t in ticks if round(t["rows"]) > 0) / expected

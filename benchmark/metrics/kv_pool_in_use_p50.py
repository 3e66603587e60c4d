"""Median share of the K/V page pool that is mapped while the engine
decodes: the ``pages_in_use`` attribute of the engine's ``decode_step``
spans (pages held by slots and by cached prefixes, the trash page not
counted) over the pool's pages, from the window's open to the end of its
drain. A program whose spans carry no such attribute gives nothing to read."""
from benchmark import arith


def read(ctx):
    spans, engine = ctx.get("spans"), (ctx.get("mix") or {}).get("engine") or {}
    pages = engine.get("page_pool_tokens", 0) // max(1, engine.get("page_size", 1))
    if not spans or pages <= 0:
        return None
    t0, t1 = ctx["t0"], ctx["t_end"]
    used = [a["pages_in_use"] for _, track, name, s, e, a in spans
            if track == "engine" and name == "decode_step" and a
            and "pages_in_use" in a and s >= t0 and e <= t1]
    return 100.0 * arith.percentile(used, 50) / pages if used else None

"""Share of the capture's device idle time that lies under a named span of
the engine thread: every idle gap of the device, shifted by the host-device
offset the capture itself yields, is split among the engine's
``"engine/<name>"`` annotations, innermost first (``host_trace.label_gaps``).
Idle time under ``engine/device_wait`` is launch and completion latency and
counts as attributed. The offset with its error and the split by name go to
standard error. Without the annotations, or without a launch paired on both
clocks, there is nothing to read."""
from benchmark import host_trace


def read(ctx):
    if not ctx.get("trace"):
        return None
    loaded = host_trace.load()
    if loaded is None:
        return None
    spans = host_trace.annotations(loaded, "engine/")
    found = host_trace.offset(loaded) if spans else None
    if found is None:
        host_trace.report(None, None)
        return None
    labelled = host_trace.label_gaps(host_trace.idle_gaps(loaded, ctx.get("chips")),
                                     spans, found["offset_s"])
    host_trace.report(found, labelled)
    return host_trace.attributed_percent(labelled)

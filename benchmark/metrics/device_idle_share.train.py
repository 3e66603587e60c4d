"""1 - (union of the device's operation intervals) / traced window, from the
trace of one training step, averaged over the chips used."""
from benchmark import trace


def read(ctx):
    return trace.idle_percent(ctx.get("trace"))

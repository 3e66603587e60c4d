"""1 - (union of the device's operation intervals) / traced window, over the
benchmark's own capture of some seconds inside the window, load on."""
from benchmark import trace


def read(ctx):
    return trace.idle_percent(ctx.get("trace"))

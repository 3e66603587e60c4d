"""Host time the trainer's loop spends per step before the device has the
work: its ``data_fetch`` + ``dispatch`` spans, mean over the window's steps."""


def read(ctx):
    steps = ctx.get("steps")
    if not steps:
        return None
    return 1e3 * sum(fetch + dispatch for _, _, fetch, dispatch in steps) / len(steps)

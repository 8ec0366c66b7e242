"""Device: milliseconds per step in which an op ran on the chip (the
union of op intervals in the traced window, over the steps in it);
nothing where the trace holds no op of a chip."""


def read(ctx):
    if not ctx.busy_s:
        return None
    return ctx.busy_s / ctx.steps * 1e3

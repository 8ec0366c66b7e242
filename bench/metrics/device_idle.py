"""Device: the share of the traced window in which no op ran on the
chip, in percent; nothing where the trace holds no op of a chip."""


def read(ctx):
    if not ctx.busy_s:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)

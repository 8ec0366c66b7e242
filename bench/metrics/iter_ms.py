"""Pipeline: milliseconds per iteration, the window (which ends with the
iteration that crosses ``--seconds``) over the iterations in it."""


def read(ctx):
    return ctx.elapsed_s / ctx.steps * 1e3

"""Staging (``plan.executor``): milliseconds per step in
``stage_compile`` spans: building a plan's ``jax.jit`` function, and its
first call, which traces, lowers, fetches the executable (from the
persistent cache when warm) and runs it."""
from lib.spans import per_step_ms


def read(ctx):
    return per_step_ms(ctx, ["stage_compile"])

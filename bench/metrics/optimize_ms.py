"""Optimizer (``core.optimizer``): milliseconds per step in top-level
``optimize`` spans, the memo search with its dry-lowered candidates."""
from lib.spans import per_step_ms


def read(ctx):
    return per_step_ms(ctx, ["optimize"])

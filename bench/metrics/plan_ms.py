"""Planner (``plan.builder``, ``plan.schemes``, ``plan.masks``):
milliseconds per step in ``lower``, ``schemes_dp`` and
``mask_propagation`` spans outside the optimizer's dry runs."""
from lib.spans import per_step_ms


def read(ctx):
    return per_step_ms(ctx, ["lower", "schemes_dp", "mask_propagation"],
                       not_under=["optimize"])

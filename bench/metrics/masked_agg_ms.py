"""Executor (``plan.executor``): milliseconds per step in the ``execute``
spans of staged plans that hold a ``MASKED_AGG`` node (their
``masked_agg`` attribute says ``kernel`` where the fused kernel runs and
``demoted`` where XLA's dot, multiply and reduce run instead); nothing
where no such span opened."""


def read(ctx):
    spans = [s for s in ctx.root.walk()
             if s.name == "execute" and "masked_agg" in s.attrs]
    if not spans:
        return None
    return sum(s.duration for s in spans) / ctx.steps * 1e3

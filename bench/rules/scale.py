"""The multiplicative update ``target ∘ num ⊘ den`` of PNMF: ``num`` is
the answer named by ``update["num"]``, shaped like the target, and
``den`` the answer named by ``update["den"]``, one value per index of
the target's axis ``update["den_axis"]``."""
from __future__ import annotations

import functools

import jax


def update(target, answers: dict, spec: dict):
    return _scale(target, answers[spec["num"]], answers[spec["den"]],
                  den_axis=spec["den_axis"])


@functools.partial(jax.jit, static_argnames=("den_axis",))
def _scale(target, num, den, den_axis: int):
    shape = [1, 1]
    shape[den_axis] = -1
    return target * num / den.reshape(shape)

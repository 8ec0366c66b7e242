"""How an answer is held against its reference: the Frobenius norm of
the difference over that of the reference (zero when both are zero,
infinite where the shapes differ or only the reference is zero)."""
from __future__ import annotations

import math

import jax.numpy as jnp


def rel_error(got, want) -> float:
    g = jnp.asarray(getattr(got, "value", got), jnp.float32)
    w = jnp.asarray(getattr(want, "value", want), jnp.float32)
    if g.shape != w.shape:
        return math.inf
    num = float(jnp.linalg.norm(g - w))
    den = float(jnp.linalg.norm(w))
    if den > 0:
        return num / den
    return 0.0 if num == 0 else math.inf

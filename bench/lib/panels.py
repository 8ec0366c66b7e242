"""The plain reference for aggregate queries over matrices too large to
hold a second copy of: ``lib.dsl`` aggregates evaluated in row panels,
with ``jax.numpy`` on the device and nothing of the program under test.

A query ``["sum" | "nnz", x, dim]`` is evaluated by computing ``x`` a
panel of rows at a time, reducing each panel at once, and adding the
panels up (``"c"``, ``"a"``) or stacking them (``"r"``). Within ``x``,
a row panel of ``["multiply", y, z]`` is the row panel of ``y`` times
the whole of ``z``, and element-wise operators take the same panel of
both sides, so no intermediate larger than a panel is ever held. Matrix
products run at ``highest`` precision (float32 on the TPU).
Semantics are those of ``lib.reference``: a zero entry is an absent
tuple, ``nnz`` counts non-zero entries.
"""
from __future__ import annotations

import json
from typing import Dict, List

import jax
import jax.numpy as jnp

from lib import dsl

AXES = {"r": 1, "c": 0, "a": None}


def evaluate(queries: Dict[str, dsl.Expr], env: Dict[str, jnp.ndarray],
             rows: int) -> Dict[str, jnp.ndarray]:
    """{name: answer} for aggregate ``queries`` over ``env``; queries
    over the same inner expression share one pass over its panels."""
    groups: Dict[str, List[str]] = {}
    for name, expr in queries.items():
        if isinstance(expr, str) or expr[0] not in ("sum", "nnz"):
            raise ValueError(f"not an aggregate: {expr!r}")
        groups.setdefault(json.dumps(expr[1]), []).append(name)
    out = {}
    for inner, names in groups.items():
        aggs = tuple((queries[n][0], queries[n][2]) for n in names)
        x = json.loads(inner)
        m = _rows(x, env)
        parts = [_panel(x, env, r0, min(r0 + rows, m), aggs)
                 for r0 in range(0, m, rows)]
        for k, name in enumerate(names):
            pieces = [p[k] for p in parts]
            if aggs[k][1] == "r":
                out[name] = jnp.concatenate(pieces, axis=0)
            else:
                out[name] = sum(pieces[1:], pieces[0])
    return out


def _panel(x, env, r0: int, r1: int, aggs):
    v = _rows_of(x, env, r0, r1)
    out = []
    for fn, dim in aggs:
        y = (v != 0).astype(jnp.float32) if fn == "nnz" else v
        out.append(jnp.sum(y, axis=AXES[dim], keepdims=True))
    return jax.block_until_ready(out)


def _rows(x, env) -> int:
    if isinstance(x, str):
        return env[x].shape[0]
    if x[0] == "t":
        return _whole(x[1], env).shape[1]
    return _rows(x[1], env)


def _whole(x, env):
    """A whole operand: a catalog matrix or its transpose (products and
    element-wise operators enter only by row panels)."""
    if isinstance(x, str):
        return env[x]
    if x[0] == "t" and isinstance(x[1], str):
        return env[x[1]].T
    raise ValueError(f"no whole-matrix evaluation of {x!r} here")


def _rows_of(x, env, r0: int, r1: int):
    if isinstance(x, str):
        return env[x][r0:r1]
    op, args = x[0], x[1:]
    if op == "t":
        return _whole(x, env)[r0:r1]
    if op == "multiply":
        return jnp.matmul(_rows_of(args[0], env, r0, r1),
                          _whole(args[1], env), precision="highest")
    a = _rows_of(args[0], env, r0, r1)
    b = _rows_of(args[1], env, r0, r1)
    if op == "emul":
        return a * b
    if op == "add":
        return a + b
    if op == "ediv":
        return jnp.where((a == 0) | (b == 0), 0,
                         a / jnp.where(b == 0, 1, b)).astype(a.dtype)
    raise ValueError(f"bad expression {x!r}")

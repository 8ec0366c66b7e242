"""The plain reference: ``lib.dsl`` expressions evaluated with
``jax.numpy`` on the device, independent of the program under test.

Semantics follow the paper's relational view of a matrix: a zero entry
is an absent tuple. Element-wise division yields zero where either side
is zero; ``nnz`` counts non-zero entries.

The reference proper computes in float32 with matrix products at
``highest``; the control is the same code one precision step below what
the configuration states.
"""
from __future__ import annotations

import json
from typing import Dict, Optional

import jax.numpy as jnp

from lib import dsl


class Reference:
    """Evaluates expressions in ``dtype``, with matrix products at
    ``precision``: ``"highest"`` (float32 on the TPU), ``"high"`` (three
    bfloat16 passes, computed here the same way on every platform) or
    None (the operands' own type)."""

    def __init__(self, dtype: str = "float32",
                 precision: Optional[str] = "highest"):
        self.dtype = jnp.dtype(dtype)
        self.precision = precision
        self.env: Dict[str, jnp.ndarray] = {}
        self._memo: Dict[str, object] = {}

    @classmethod
    def for_config(cls, cfg: dict, control: bool = False) -> "Reference":
        """The reference of a configuration: float32 with products at
        ``highest``; with ``control=True``, one step below what the
        configuration states: ``high`` where it states float32 at
        ``highest``, bfloat16 for other float32."""
        if cfg["dtype"] != "float32":
            raise ValueError(f"no reference for dtype {cfg['dtype']!r}")
        if not control:
            return cls("float32", "highest")
        if cfg["matmul_precision"] == "highest":
            return cls("float32", "high")
        return cls("bfloat16", None)

    def load(self, name: str, array) -> None:
        self.env[name] = jnp.asarray(array).astype(self.dtype)
        self._memo.clear()

    def eval(self, expr: dsl.Expr):
        key = json.dumps(expr)
        if key not in self._memo:
            self._memo[key] = self._eval(expr)
        return self._memo[key]

    def _eval(self, expr):
        if isinstance(expr, str):
            return self.env[expr]
        op, args = expr[0], expr[1:]
        if op == "t":
            return self.eval(args[0]).T
        if op == "multiply":
            x, y = self.eval(args[0]), self.eval(args[1])
            if self.precision == "high":
                return _matmul_bf16x3(x, y)
            return jnp.matmul(x, y, precision=self.precision)
        if op == "emul":
            return self.eval(args[0]) * self.eval(args[1])
        if op == "add":
            return self.eval(args[0]) + self.eval(args[1])
        if op == "ediv":
            x, y = self.eval(args[0]), self.eval(args[1])
            return jnp.where((x == 0) | (y == 0), 0,
                             x / jnp.where(y == 0, 1, y)).astype(self.dtype)
        if op in ("sum", "nnz"):
            x = self.eval(args[0])
            if op == "nnz":
                x = (x != 0).astype(jnp.float32)
            axis = {"r": 1, "c": 0, "a": None}[args[1]]
            return jnp.sum(x, axis=axis, keepdims=True).astype(self.dtype)
        raise ValueError(f"bad expression {expr!r}")


def _matmul_bf16x3(x, y):
    """x × y from three bfloat16 products, as the TPU's ``high``
    precision computes it: each operand split into a bfloat16 head and
    a bfloat16 tail, the tail × tail product left out."""
    def split(v):
        head = v.astype(jnp.bfloat16)
        return head, (v - head.astype(v.dtype)).astype(jnp.bfloat16)

    (xh, xl), (yh, yl) = split(x), split(y)

    def dot(a, b):
        return jnp.matmul(a, b, preferred_element_type=jnp.float32)

    return dot(xh, yh) + (dot(xh, yl) + dot(xl, yh))

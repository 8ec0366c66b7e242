"""Query expressions as data, and their translation into the program's
fluent API.

An expression is a catalog name (``"A"``) or a list ``[op, *args]``:

    ["t", x]                     transpose
    ["multiply", x, y]           matrix product
    ["emul", x, y] / ["ediv", x, y] / ["add", x, y]
                                 element-wise, zero read as NULL
    ["sum", x, dim] / ["nnz", x, dim]
                                 aggregation over dim "r", "c" or "a"

``lib.reference`` evaluates the same expressions without the program.
"""
from __future__ import annotations

from typing import Dict, Union

Expr = Union[str, list]

AGG_DIMS = ("r", "c", "a")
ARITY = {"t": 1, "multiply": 2, "emul": 2, "ediv": 2, "add": 2,
         "sum": 2, "nnz": 2}


def validate(expr: Expr) -> None:
    """Raise ValueError on an expression outside the language above."""
    if isinstance(expr, str):
        return
    op, args = expr[0], expr[1:]
    if op not in ARITY or len(args) != ARITY[op]:
        raise ValueError(f"bad expression {expr!r}")
    if op in ("sum", "nnz"):
        if args[1] not in AGG_DIMS:
            raise ValueError(f"bad aggregation dim in {expr!r}")
        args = args[:1]
    for a in args:
        validate(a)


def build(expr: Expr, catalog: Dict[str, "object"]):
    """The program's ``Matrix`` for ``expr`` over ``catalog`` (name →
    ``Matrix``)."""
    if isinstance(expr, str):
        return catalog[expr]
    op, args = expr[0], expr[1:]
    x = build(args[0], catalog)
    if op == "t":
        return x.t()
    if op in ("sum", "nnz"):
        return getattr(x, op)(args[1])
    if op in ("multiply", "emul", "ediv", "add"):
        return getattr(x, op)(build(args[1], catalog))
    raise ValueError(f"bad expression {expr!r}")

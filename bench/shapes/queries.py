"""A catalog loaded once and a set of named queries over it, closed loop,
from one client.

A traffic file of this shape (``"shape": "queries"``) lists ``queries``,
name → ``lib.dsl`` expression. Each pass collects every query once, in
order; warm-up runs one pass, and the window one pass per ``step()``.
The catalog is never rebound, so after warm-up every collect reuses its
optimized plan and its staged program.

The check holds the answers of the warm-up pass and of the window's last
pass against the plain reference run on the same catalog, which
evaluates the aggregates a panel of ``PANEL_ROWS`` rows at a time
(``lib.panels``), so that no whole-matrix intermediate is held beside
the catalog. Each query has its own number, ``answer_rel_err.<query>``:
the worse relative Frobenius error of its two answers.

With ``work_block`` set, warm-up counts from the catalog the work of the
fused block-skipping product ``Σ(X ∘ (Y × Z))`` (a ``sum`` over ``r``,
``c`` or ``a`` of that pattern over catalog matrices): the block triples
(I, K, J) at that granularity with X(I, J), Y(I, K) and Z(K, J) all
live, at 2·b³ flops each, and the three operands read once in float32.
``KERNEL_WORK`` keeps it, per dispatch, for the metric readers.

Every collect is wrapped in a ``jax.profiler.TraceAnnotation``
(``collect:<name>``), so that a device trace can say what the host was
doing in each idle gap.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from lib import compare, dsl, panels

# rows per panel of the reference
PANEL_ROWS = 2048

# {"flops", "bytes"} of one dispatch of the fused product, averaged over
# the pass's queries of that pattern; empty where none was counted
KERNEL_WORK: Dict[str, float] = {}


class Traffic:
    def __init__(self, spec: dict, backend):
        self.queries = spec["queries"]
        for expr in self.queries.values():
            dsl.validate(expr)
        self.work_block = spec.get("work_block")
        self.checked_steps = 1   # the check holds the window's last pass
        self.backend = backend
        self.done = 0            # window passes completed
        self.attempted = 0       # collects sent in the window
        self.stages = []         # answers of the warm-up and last passes

    def warm_up(self) -> None:
        KERNEL_WORK.clear()
        if self.work_block:
            KERNEL_WORK.update(kernel_work(self.queries,
                                           self.backend.arrays,
                                           self.work_block))
        self.stages.append(self._pass())

    def step(self) -> None:
        answers = self._pass()
        self.attempted += len(self.queries)
        self.done += 1
        self.stages[1:] = [answers]

    def finish(self) -> None:
        """Ends the window and lets go of the backend."""
        jax.block_until_ready(self.stages)
        self.backend = None

    def check(self, ref_backend) -> Tuple[Dict[str, float], list]:
        """({"answer_rel_err.<query>": its worse error}, every error in
        order)."""
        want = panels.evaluate(self.queries, ref_backend.arrays, PANEL_ROWS)
        numbers, errs = {}, []
        for stage in self.stages:
            for name in self.queries:
                err = compare.rel_error(stage[name], want[name])
                errs.append(err)
                key = f"answer_rel_err.{name}"
                numbers[key] = max(numbers.get(key, 0.0), err)
        return numbers, errs

    def _pass(self) -> Dict[str, object]:
        out = {}
        for name, expr in self.queries.items():
            with jax.profiler.TraceAnnotation(f"collect:{name}"):
                out[name] = self.backend.collect(expr)
        return out


def fused_operands(expr) -> Optional[Tuple[str, str, str]]:
    """(X, Y, Z) where ``expr`` sums ``X ∘ (Y × Z)`` over catalog
    matrices, else None."""
    if isinstance(expr, str) or expr[0] != "sum":
        return None
    inner = expr[1]
    if isinstance(inner, str) or inner[0] != "emul":
        return None
    for x, mm in ((inner[1], inner[2]), (inner[2], inner[1])):
        if (isinstance(x, str) and isinstance(mm, list)
                and mm[0] == "multiply" and isinstance(mm[1], str)
                and isinstance(mm[2], str)):
            return x, mm[1], mm[2]
    return None


@functools.partial(jax.jit, static_argnums=1)
def block_mask(a, block: int):
    """Which ``block`` × ``block`` blocks of ``a`` hold a non-zero, in one
    fused pass (the trailing blocks are padded with zeros)."""
    m, n = a.shape
    gm, gn = -(-m // block), -(-n // block)
    a = jnp.pad(a, ((0, gm * block - m), (0, gn * block - n)))
    return jnp.any(a.reshape(gm, block, gn, block) != 0, axis=(1, 3))


def live_triples(mx: np.ndarray, my: np.ndarray, mz: np.ndarray) -> int:
    """Block triples (I, K, J) with X(I, J), Y(I, K) and Z(K, J) live."""
    paths = np.asarray(my, np.int64) @ np.asarray(mz, np.int64)
    return int((np.asarray(mx, np.int64) * paths).sum())


def kernel_work(queries: dict, arrays: dict, block: int) -> Dict[str, float]:
    """{"flops", "bytes"} per dispatch of the fused product, averaged over
    the queries of that pattern; {} where there is none."""
    flops, nbytes = [], []
    for expr in queries.values():
        names = fused_operands(expr)
        if names is None:
            continue
        x, y, z = (arrays[n] for n in names)
        masks = [np.asarray(block_mask(v, block)) for v in (x, y, z)]
        flops.append(2.0 * block ** 3 * live_triples(*masks))
        nbytes.append(4.0 * (x.size + y.size + z.size))
    if not flops:
        return {}
    return {"flops": float(np.mean(flops)), "bytes": float(np.mean(nbytes))}

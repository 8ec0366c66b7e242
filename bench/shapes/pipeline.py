"""An iterative pipeline over the engine, closed loop, from one client.

A traffic file of this shape (``"shape": "pipeline"``) lists ``steps``.
Each iteration runs them in order: a step collects named queries,
computes a new value for one catalog matrix from their answers by an
update rule (``rules/<rule>.py``), and loads it back, as a pipeline over
the engine does. Warm-up runs ``warmup_iterations``; the window runs
one iteration per ``step()``.

The check holds every updated matrix after the warm-up and after the
window's ``check.window_iterations``-th iteration (its last, where it
holds fewer) against the reference run from the same catalog for as
many iterations, so what it compares does not change with the
program's speed. Its number is ``factor_rel_err``, the worst relative
Frobenius error of those matrices.

Every collect, update and load is wrapped in a
``jax.profiler.TraceAnnotation``, so that a device trace can say what
the host was doing in each idle gap.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax

from lib import compare, dsl, spec as specmod


class Traffic:
    def __init__(self, spec: dict, backend):
        self.steps = spec["steps"]
        self.rules = {}
        for step in self.steps:
            for expr in step["collect"].values():
                dsl.validate(expr)
            rule = step["update"]["rule"]
            self.rules[rule] = specmod.rule(rule)
        self.warmup_iterations = spec["warmup_iterations"]
        self.checked_steps = spec["check"]["window_iterations"]
        self.targets = [s["update"]["target"] for s in self.steps]
        self.backend = backend
        self.done = 0            # window iterations completed
        self.attempted = 0       # collects sent in the window
        # (iterations since the stage before, {target: value}) pairs
        self.stages: List[Tuple[int, Dict[str, object]]] = []

    def warm_up(self) -> None:
        for _ in range(self.warmup_iterations):
            self._iteration()
        self._stage(self.warmup_iterations)

    def step(self) -> None:
        self.attempted += self._iteration()
        self.done += 1
        if self.done == self.checked_steps:
            self._stage(self.done)

    def finish(self) -> None:
        """Ends the window and lets go of the backend."""
        if len(self.stages) < 2:
            self._stage(self.done)
        jax.block_until_ready([s[1] for s in self.stages])
        self.backend = None

    def check(self, ref_backend) -> Tuple[Dict[str, float], list]:
        """({"factor_rel_err": worst}, every error in order): the
        reference replays the pipeline and each stage is held against
        it."""
        errs = []
        for iterations, got in self.stages:
            for _ in range(iterations):
                iteration(self.steps, self.rules, ref_backend)
            errs += [compare.rel_error(got[n], ref_backend.arrays[n])
                     for n in got]
        return {"factor_rel_err": max(errs)}, errs

    def _stage(self, iterations: int) -> None:
        arrays = self.backend.arrays
        self.stages.append((iterations,
                            {t: arrays[t] for t in self.targets}))

    def _iteration(self) -> int:
        return iteration(self.steps, self.rules, self.backend)


def iteration(steps: list, rules: dict, b) -> int:
    """One iteration of ``steps`` on backend ``b``; the collects sent."""
    sent = 0
    for step in steps:
        answers = {}
        for key, expr in step["collect"].items():
            with jax.profiler.TraceAnnotation(f"collect:{key}"):
                answers[key] = b.collect(expr)
            sent += 1
        up = step["update"]
        target = up["target"]
        with jax.profiler.TraceAnnotation(f"update:{target}"):
            new = rules[up["rule"]].update(b.arrays[target], answers, up)
        with jax.profiler.TraceAnnotation(f"load:{target}"):
            b.load(target, new)
    return sent

"""Physical query planner: CSE'd operator DAG + plan-time strategy selection.

The layer between the logical optimizer (``repro.core.optimizer``) and the
kernels (``repro.kernels``):

    api → optimizer → **plan** (builder → PhysicalPlan → DAG executor) → kernels

``build_plan`` hash-conses the logical tree into a DAG (one node per
distinct subplan → shared subexpressions computed once), annotating every
node with estimated cost/sparsity, the chosen join strategy, the kernel
backend, and — on a multi-device mesh — the partition-scheme pair from the
communication cost model. ``execute_plan`` evaluates the DAG topologically
with memoization (jit-staging the whole plan where it can, into a program
cache keyed on what the trace reads); ``render`` is the physical EXPLAIN.
"""
from repro.plan.builder import (
    SharedBuildState, SharedLowering, build_plan, lower_shared,
)
from repro.plan.executor import (
    PlanExecutor, execute_plan, program_cache, staged_collective_bytes,
)
from repro.plan.explain import render
from repro.plan.ops import PhysicalNode, PhysicalPlan
from repro.plan.schemes import SchemeAssignment, propagate, transpose_scheme

__all__ = [
    "build_plan", "execute_plan", "lower_shared", "PlanExecutor",
    "PhysicalNode", "PhysicalPlan", "program_cache", "render",
    "SharedBuildState",
    "SharedLowering", "staged_collective_bytes",
    "SchemeAssignment", "propagate", "transpose_scheme",
]

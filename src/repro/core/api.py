"""User-facing fluent API mirroring the paper's Scala interface (Codes 1–5).

    X = matrel.load(x_array, name="X")
    tr = X.t().multiply(X).trace().collect()           # Code 1
    g11 = X.t().multiply(X).select("RID=1 AND CID=1")  # Code 2
    kron = A.cross_prod(B, lambda x, y: x * y)         # Code 3
    C = A.join(B, "RID=RID AND CID=CID", f)            # Code 4
    C = A.join(B, "VAL=VAL", f)                        # Code 5

``collect()`` runs the cost-based optimizer — a memoized search over the
paper's rewrite rules in which every candidate is costed by dry-lowering
it through the physical layer (``core.optimizer``, ``Session(search=
"greedy")`` keeps the original fixed-point rewriter as the oracle) —
lowers the winner into a hash-consed physical operator DAG
(``repro.plan``) and executes it: shared subexpressions are computed once
and every strategy decision (join algorithm, kernel backend, partition
schemes) is made at plan time. ``collect(optimize=False)`` skips the
logical rewrites (the paper's MatRel(w/o-opt)); ``collect(engine=
"tree")`` runs the legacy recursive tree-walk executor, kept as the
correctness oracle.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Union

import jax.numpy as jnp
import numpy as np

from repro.core import executor as exmod
from repro.core import optimizer as optmod
from repro.core.plancache import VersionedLRU
from repro import plan as planmod
from repro.core.expr import (
    Agg, AggDim, AggFn, ElemWise, EWOp, Expr, Inverse, Join, Leaf, MatMul,
    MatScalar, MergeFn, Select, Transpose,
)
from repro.core.matrix import BlockMatrix
from repro.core.predicates import parse_join, parse_select


class Session:
    """Holds named base matrices (the catalog) and execution settings.

    ``engine`` selects the default ``collect()`` path: ``"dag"`` (the
    physical planner, default) or ``"tree"`` (the legacy recursive
    executor, kept as the oracle the planner is tested against).
    """

    def __init__(self, block_size: int = 256, mode: str = "sparse",
                 use_bloom: bool = True, engine: str = "dag",
                 n_workers: Optional[int] = None, search: str = "memo",
                 ledger=None, cost_model=None, metrics=None):
        if engine not in ("dag", "tree"):
            raise ValueError(f"unknown engine {engine!r}")
        if search not in ("memo", "greedy"):
            raise ValueError(f"unknown search {search!r}")
        self.env: Dict[str, BlockMatrix] = {}
        self.block_size = block_size
        self.mode = mode
        self.use_bloom = use_bloom
        self.engine = engine
        self.search = search
        self.n_workers = n_workers
        # optional ``obs.ledger.CostLedger``: when set, every plan this
        # session executes through the DAG engine appends one
        # predicted-vs-actual row (the serving tier installs its own)
        self.ledger = ledger
        # optional ``core.calibrate.CostModel``: candidate costing blends
        # its calibrated wall-time prediction into ``physical_cost``
        # (analytic-only when unset or unfitted for this device key)
        self.cost_model = cost_model
        # optional ``obs.metrics.MetricsRegistry``: the DAG executor of
        # every ``collect`` mirrors its counters into it as
        # ``executor_<name>`` (staged / fallback / overflow counts)
        self.metrics = metrics
        self._auto = 0
        self._mesh = None
        self._env_version = 0
        self._plan_cache = VersionedLRU(_PLAN_CACHE_LIMIT)
        self._opt_cache = VersionedLRU(_PLAN_CACHE_LIMIT)
        # staged programs, keyed on what their trace reads rather than on
        # the plan: a rebind replans, yet keeps the program when the
        # trace is unchanged (``repro.plan.executor.program_key``)
        self._programs = planmod.program_cache()

    @property
    def workers(self) -> int:
        """Effective worker count (``n_workers`` or every local device)."""
        import jax
        return self.n_workers or jax.device_count()

    @property
    def mesh(self):
        """The session-owned 1-D worker mesh (None on a single worker).

        Built once per topology and threaded through planning, SPMD
        execution and EXPLAIN — the single source of device topology for
        this session. Changing ``n_workers`` rebuilds it, and the plan
        cache is keyed on it, so a topology change replans and restages.
        """
        w = self.workers
        if w <= 1:
            return None
        from repro.core.partitioner import mesh_workers, worker_mesh
        if self._mesh is None or mesh_workers(self._mesh) != w:
            self._mesh = worker_mesh(w)
        return self._mesh

    def _mesh_key(self):
        m = self.mesh
        if m is None:
            return None
        return (tuple(d.id for d in m.devices.flat), m.axis_names)

    def load(self, value, name: Optional[str] = None,
             sparsity: Optional[float] = None) -> "Matrix":
        if name is None:
            self._auto += 1
            name = f"_m{self._auto}"
        bm = value if isinstance(value, BlockMatrix) else \
            BlockMatrix.from_dense(jnp.asarray(value, jnp.float32),
                                   self.block_size)
        self.env[name] = bm
        # (re)binding a leaf invalidates memoized optimize results: the
        # memo search costs candidates against the bound leaf masks
        self._env_version += 1
        if sparsity is None:
            # the binding keeps the exact count, so the mask pass reads it
            # instead of counting again
            from repro.obs.trace import span
            with span("d2h", what="nnz", name=name):
                nnz = bm.nnz_count()
            sparsity = nnz / max(1, bm.value.size)
        return Matrix(self, Leaf(name, bm.shape, sparsity))

    def execute(self, plan: Expr, optimize: bool = True,
                engine: Optional[str] = None):
        from repro.obs.trace import span
        opt = None
        if optimize:
            opt = self.optimize_result(plan)
            plan = opt.plan
        engine = engine or self.engine
        if engine not in ("dag", "tree"):
            raise ValueError(f"unknown engine {engine!r}")
        if engine == "tree":
            with span("execute", path="tree"):
                return exmod.execute(plan, self.env, mode=self.mode,
                                     block_size=self.block_size,
                                     use_bloom=self.use_bloom)
        pplan = self.physical_plan(plan)
        ex = planmod.PlanExecutor(self.env, mesh=self.mesh,
                                  metrics=self.metrics,
                                  programs=self._programs)
        import time
        t0 = time.perf_counter()
        out = ex.run(pplan)
        if self.ledger is not None:
            import jax
            from repro.core.expr import signature
            from repro.obs.ledger import exec_path_of
            try:
                # dispatch is async: without a sync the recorded wall is
                # launch overhead, not execution — a fitting corpus built
                # from such rows sees every matmul cost the same 0.4ms
                jax.block_until_ready(getattr(out, "value", out))
            except Exception:
                pass                           # host-side results (COO etc.)
            self.ledger.record(
                query=signature(plan), plan=pplan,
                exec_path=exec_path_of(ex.stats),
                wall_s=time.perf_counter() - t0,
                compile_s=ex.timings["compile_s"],
                overflow=ex.stats["sparse_overflows"] > 0, opt=opt)
        return out

    def optimize_result(self, plan: Expr,
                        search: Optional[str] = None) -> optmod.OptimizeResult:
        """Session-aware optimization with a bounded per-session memo, so
        the hot repeated-``collect()`` path skips the search too. The memo
        search costs candidates against this session's mode / block size /
        mesh and bound leaf data (``core.cost.physical_cost``), so the
        cache key carries all of them — like the plan cache — plus the
        catalog version (bumped by ``load``): mutating a session setting
        or rebinding a leaf re-optimizes; value drift under an unchanged
        binding is caught downstream by the staged executor's overflow
        guard. The calibrated cost-model version is in the key too: a
        (background) refit re-optimizes instead of serving decisions
        made under retired coefficients."""
        search = search or self.search
        key = (plan, search, self._env_version, self.mode,
               self.block_size, self.use_bloom, self.n_workers,
               self._costmodel_key())
        return self._opt_cache.get_or_create(
            key, lambda: optmod.optimize(plan, search=search, session=self))

    def _costmodel_key(self):
        """Cache-key component for the calibrated cost model: identity +
        fit version (bumped per successful refit)."""
        if self.cost_model is None:
            return None
        return (id(self.cost_model), self.cost_model.version)

    def _optimized(self, plan: Expr) -> Expr:
        return self.optimize_result(plan).plan

    def physical_plan(self, plan: Expr) -> "planmod.PhysicalPlan":
        """Lower ``plan`` (assumed already optimized) into a physical DAG.

        Plans are cached per (expr, catalog version, mode, block_size,
        use_bloom, n_workers, mesh, kernel backend env): logical ``Expr``
        trees are frozen and hash structurally, and plan annotations
        derive from the expression, those settings, *and the bound leaf
        data* — mask/nnz propagation and COO capacity sizing read the
        catalog, so the key carries ``_env_version`` (bumped by ``load``)
        and a leaf rebind replans instead of serving a plan staged
        against stale masks. The mesh is in the key because the staged
        SPMD program and the scheme annotations are topology-specific.
        The cache is a bounded LRU (``core.plancache.VersionedLRU``):
        sessions issuing parameter-varying queries evict
        least-recently-used first.
        """
        import os
        # the calibrated cost model participates in backend choice
        # (registry.planned_backend prices candidates per fitted device
        # key), so its identity+version — and the kill switch — key the
        # cache: a refit or a flipped REPRO_BACKEND_CHOICE replans
        key = (plan, self._env_version, self.mode, self.block_size,
               self.use_bloom, self.n_workers, self._mesh_key(),
               os.environ.get("REPRO_KERNEL_BACKEND"),
               os.environ.get("REPRO_BACKEND_CHOICE"),
               self._costmodel_key())
        return self._plan_cache.get_or_create(
            key, lambda: planmod.build_plan(
                plan, mode=self.mode, block_size=self.block_size,
                use_bloom=self.use_bloom, n_workers=self.n_workers,
                cost_model=self.cost_model))


# Bounds the per-session physical-plan cache (each dense-tier entry can pin
# a compiled jit executable, so unbounded growth would leak memory on
# sessions issuing dynamically generated queries).
_PLAN_CACHE_LIMIT = 128


def _merge_of(f: Union[MergeFn, Callable], name: str = "f") -> MergeFn:
    return f if isinstance(f, MergeFn) else MergeFn(name, f)


@dataclasses.dataclass
class Matrix:
    session: Session
    plan: Expr

    # -- matrix operators (paper §2) -----------------------------------------
    def t(self) -> "Matrix":
        return Matrix(self.session, Transpose(self.plan))

    def multiply(self, other: "Matrix") -> "Matrix":
        return Matrix(self.session, MatMul(self.plan, other.plan))

    def add(self, other: Union["Matrix", float]) -> "Matrix":
        if isinstance(other, Matrix):
            return Matrix(self.session,
                          ElemWise(self.plan, other.plan, EWOp.ADD))
        return Matrix(self.session,
                      MatScalar(self.plan, EWOp.ADD, float(other)))

    def emul(self, other: Union["Matrix", float]) -> "Matrix":
        if isinstance(other, Matrix):
            return Matrix(self.session,
                          ElemWise(self.plan, other.plan, EWOp.MUL))
        return Matrix(self.session,
                      MatScalar(self.plan, EWOp.MUL, float(other)))

    def ediv(self, other: "Matrix") -> "Matrix":
        return Matrix(self.session, ElemWise(self.plan, other.plan, EWOp.DIV))

    def inverse(self) -> "Matrix":
        return Matrix(self.session, Inverse(self.plan))

    # -- relational operators (paper §3, §4) ----------------------------------
    def select(self, pred: str) -> "Matrix":
        return Matrix(self.session, Select(self.plan, parse_select(pred)))

    def agg(self, fn: str, dim: str) -> "Matrix":
        return Matrix(self.session,
                      Agg(self.plan, AggFn(fn), AggDim(dim)))

    def sum(self, dim: str = "a") -> "Matrix":
        return self.agg("sum", dim)

    def nnz(self, dim: str = "a") -> "Matrix":
        return self.agg("nnz", dim)

    def avg(self, dim: str = "a") -> "Matrix":
        return self.agg("avg", dim)

    def max(self, dim: str = "a") -> "Matrix":
        return self.agg("max", dim)

    def min(self, dim: str = "a") -> "Matrix":
        return self.agg("min", dim)

    def trace(self) -> "Matrix":
        return self.agg("sum", "d")

    def join(self, other: "Matrix", pred: str,
             f: Union[MergeFn, Callable]) -> "Matrix":
        return Matrix(self.session,
                      Join(self.plan, other.plan, parse_join(pred),
                           _merge_of(f)))

    def cross_prod(self, other: "Matrix",
                   f: Union[MergeFn, Callable]) -> "Matrix":
        return self.join(other, "CROSS", f)

    # -- execution -------------------------------------------------------------
    def optimized_plan(self,
                       search: Optional[str] = None) -> optmod.OptimizeResult:
        """Optimize against the owning session (its mode, mesh and bound
        leaves feed the memo search's physical cost model); ``search``
        overrides the session default ("memo" | "greedy")."""
        return self.session.optimize_result(self.plan, search=search)

    def physical_plan(self, optimize: bool = True) -> planmod.PhysicalPlan:
        plan = self.optimized_plan().plan if optimize else self.plan
        return self.session.physical_plan(plan)

    def explain(self, physical: bool = False,
                measure_comm: bool = False, trace: bool = False) -> str:
        """Logical EXPLAIN (rewrites + costs) or, with ``physical=True``,
        the physical DAG with per-node cost, strategy, backend and (on
        multi-worker sessions) propagated partition schemes + predicted
        comm, headed by the optimizer's decision record — the fired
        logical rules and the top rejected alternatives with their
        flops/comm/nnz cost breakdowns. ``measure_comm=True``
        additionally compiles the staged SPMD program and prints its
        HLO-measured collective bytes next to the prediction (dense
        jit-safe plans on a mesh only). ``trace=True`` additionally runs
        the query once under a forced-sample trace — bypassing the
        session's memoized optimize/plan caches so every lifecycle phase
        fires — and appends the rendered span tree with per-phase
        timings (``repro.obs.trace``)."""
        trace_txt = ""
        if trace:
            trace_txt = "\n" + self._traced_run().render()
        if physical:
            result = self.optimized_plan()
            plan = self.session.physical_plan(result.plan)
            if plan.mode == "sparse":
                # annotate propagated masks / nnz bounds / COO capacities
                # from the session catalog so EXPLAIN shows the numbers
                # the cost gates actually used (repro.plan.masks)
                from repro.plan import masks as masksmod
                try:
                    masksmod.annotate(plan, self.session.env)
                except KeyError:
                    pass  # unbound leaves: render the un-annotated plan
            measured = None
            if measure_comm:
                from repro.plan.executor import staged_collective_bytes
                measured = staged_collective_bytes(
                    plan, self.session.env, self.session.mesh,
                    self.session._programs)
            return planmod.render(plan, measured_bytes=measured,
                                  opt=result) + trace_txt
        return self.optimized_plan().describe(self.plan) + trace_txt

    def _traced_run(self):
        """Execute once under a forced-sample trace, hitting every
        lifecycle phase (the session memo caches are bypassed so the
        optimize / lower spans are not hidden by a warm cache)."""
        from repro.core.expr import signature
        from repro.obs.trace import TRACER
        s = self.session
        tr = TRACER.start("query", sample=True, query=signature(self.plan))
        with TRACER.activate(tr):
            opt = optmod.optimize(self.plan, search=s.search, session=s)
            pplan = planmod.build_plan(
                opt.plan, mode=s.mode, block_size=s.block_size,
                use_bloom=s.use_bloom, n_workers=s.n_workers,
                cost_model=s.cost_model)
            planmod.PlanExecutor(s.env, mesh=s.mesh).run(pplan)
        tr.finish()
        return tr

    def collect(self, optimize: bool = True, engine: Optional[str] = None):
        return self.session.execute(self.plan, optimize=optimize,
                                    engine=engine)

    def to_numpy(self, optimize: bool = True) -> np.ndarray:
        out = self.collect(optimize=optimize)
        if isinstance(out, BlockMatrix):
            return np.asarray(out.value)
        return out.to_dense()

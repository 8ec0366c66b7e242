"""Execute a physical operator DAG (the default ``collect()`` path).

Evaluation walks ``plan.nodes`` in order — the builder emits children
before parents, so the list *is* a topological order — and memoizes every
result by op id. Because hash-consing gives one node per distinct subplan,
each shared subexpression is computed exactly once (``stats`` records the
per-kind evaluation counts so tests can assert it).

Two paths:

* **eager** — per-node evaluation reusing the exact primitive semantics of
  the tree-walk oracle (``core.executor.agg_dense``/``select_dense``,
  ``core.joins``), so the DAG executor is value-equivalent by construction;
* **jit-staged dense** — when every node is jit-safe and the plan was built
  for ``mode="dense"``, the whole DAG is staged into one ``jax.jit``-ed
  function over the leaf arrays, letting XLA fuse across operators. A
  staged program is kept in a cache keyed on what its trace reads
  (``program_key``), not on the plan object: the session owns one, so a
  new plan whose trace is unchanged (a leaf rebound to new values of the
  same shape and block mask) reuses the program instead of restaging.

The staged path has an **SPMD variant**: given a worker mesh (session-owned,
``Session.mesh``) and a multi-worker plan, node outputs are pinned to the
schemes chosen by the plan-wide propagation pass (``repro.plan.schemes``)
via ``with_sharding_constraint`` — one GSPMD program for the whole plan, so
consecutive operators hand off partitioned data without host round-trips,
and the collectives XLA inserts are exactly the reshards the cost model
predicted (validated by ``measured_collective_bytes``).

* **jit-staged sparse** — sparse-tier plans stage too: overlay joins and
  masked matmuls are gated by the *plan-time propagated* block masks
  (``repro.plan.masks`` — static arrays, so dead blocks vanish from the
  trace as skipped gathers), and COO-producing joins run the
  device-resident tier (``repro.core.joins_device``) over static-capacity
  buffers sized from the propagated nnz bounds. Mixed sparse/dense plans
  therefore compile to ONE program (GSPMD on a mesh) with zero host
  round-trips inside the staged region. Guarded: a plan whose capacity
  bound exceeds ``masks.device_cap_limit()``, or whose buffers overflow
  at runtime (leaf values drifted under an unchanged block mask), falls
  back to the eager host oracle for that run.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import REGISTRY
from repro.obs.trace import TRACER, span
from repro.runtime import faults

from repro.core import joins as joinsmod
from repro.core import joins_device as joinsdev
# shared primitive semantics: defined once next to the tree-walk oracle so
# the two engines cannot drift
from repro.core.executor import (
    agg_dense, as_matrix, dense_join_result, ew_values, leaf_value,
    select_dense,
)
from repro.core.expr import (
    Agg, AggDim, ElemWise, EWOp, Expr, Join, Leaf, MatScalar, Select,
)
from repro.core.joins import COOTensor
from repro.core.matrix import BlockMatrix
from repro.core.plancache import VersionedLRU
from repro.plan import ops as P

Result = Union[BlockMatrix, COOTensor]

# kernel-facing spelling of the fusable aggregation dims (DIAG never fuses —
# the builder only emits MASKED_AGG for these three)
_AGG_DIM = {AggDim.ROW: "row", AggDim.COL: "col", AggDim.ALL: "all"}


class PlanExecutor:
    """Memoized topological evaluator for ``PhysicalPlan``s.

    ``mesh`` (session-owned) selects the SPMD staged path for jit-safe
    multi-worker dense plans: the whole DAG compiles to one GSPMD program
    with node outputs constrained to their propagated schemes.
    """

    def __init__(self, env: Dict[str, BlockMatrix], stage_jit: bool = True,
                 mesh=None, node_cache=None, metrics=None, programs=None):
        self.env = env
        self.stage_jit = stage_jit
        self.mesh = mesh
        # staged programs keyed on what their trace reads (``program_key``;
        # see ``program_cache``): a session passes its own, so a program
        # outlives the plan object it was built from; an executor built
        # without one keeps a private cache
        self.programs = programs if programs is not None \
            else program_cache()
        # cross-query materialized-result cache (the serving tier's
        # inter-query CSE): an object with ``get(plan, node)`` →
        # result-or-None and ``put(plan, node, result)``. Sharing happens
        # per *node*, so it composes with the eager path only — ``run``
        # skips jit staging when a cache is installed.
        self.node_cache = node_cache
        # optional ``obs.metrics.MetricsRegistry``: every counter bump
        # below mirrors into it as ``executor_<name>`` (the serving tier
        # passes its per-engine registry); ``stats`` remains the per-run
        # compatibility view the tests and engine read
        self.metrics = metrics
        self.stats: Dict[str, int] = {
            "node_evals": 0, "node_reuses": 0, "matmuls": 0,
            "masked_matmuls": 0, "masked_aggs": 0, "joins": 0,
            "staged": 0, "staged_spmd": 0, "staged_sparse": 0,
            "staged_sparse_spmd": 0, "sparse_fallbacks": 0,
            "sparse_overflows": 0, "blocks_skipped": 0, "blocks_total": 0,
        }
        # wall-clock split of the most recent ``run``: staged-path build +
        # first-call (XLA trace+compile) seconds vs steady-state execute
        # seconds — the ledger's compile-vs-execute attribution
        self.timings: Dict[str, float] = {"compile_s": 0.0, "execute_s": 0.0}

    def _bump(self, name: str, n: int = 1) -> None:
        """Single increment site: the per-run dict and (when installed)
        the registry counter move together."""
        self.stats[name] += n
        if self.metrics is not None:
            self.metrics.counter("executor_" + name).inc(n)

    # -- public ---------------------------------------------------------------
    def run(self, plan: P.PhysicalPlan) -> Result:
        if self.stage_jit and plan.jit_safe and self.node_cache is None:
            spmd = self.mesh is not None and plan.n_workers > 1
            mesh = self.mesh if spmd else None
            if plan.mode == "dense":
                return self._run_staged(plan, mesh)
            out = self._run_staged_sparse(plan, mesh)
            if out is not _FALLBACK:
                return out
        return self._run_eager(plan)

    # -- eager path -----------------------------------------------------------
    def _run_eager(self, plan: P.PhysicalPlan) -> Result:
        traced = TRACER.active()
        results: Dict[int, Result] = {}
        with span("execute", path="eager", nodes=plan.n_nodes):
            for node in plan.nodes:
                if self.node_cache is not None:
                    hit = self.node_cache.get(plan, node)
                    if hit is not None:
                        results[node.op_id] = hit
                        self._bump("node_reuses")
                        continue
                args = [results[c] for c in node.children]
                # per-node wall time: only traced runs synchronize (so
                # span times mean device work, not dispatch), untraced
                # runs keep async dispatch semantics untouched
                with span("node", op=node.label(), kind=node.kind):
                    out = self._eval(plan, node, args)
                    if traced:
                        _sync(out)
                results[node.op_id] = out
                self._bump("node_evals")
                if self.node_cache is not None:
                    self.node_cache.put(plan, node, results[node.op_id])
        return results[plan.root]

    def _eval(self, plan: P.PhysicalPlan, node: P.PhysicalNode,
              args: List[Result]) -> Result:
        bs = plan.block_size
        k = node.kind
        if k == P.LEAF:
            return leaf_value(node.expr, self.env, bs)
        if k == P.TRANSPOSE:
            return BlockMatrix.from_dense(as_matrix(args[0]).value.T, bs)
        if k == P.MATSCALAR:
            e: MatScalar = node.expr
            x = as_matrix(args[0]).value
            v = x + e.beta if e.op is EWOp.ADD else x * e.beta
            return BlockMatrix.from_dense(v, bs)
        if k == P.ELEMWISE:
            e: ElemWise = node.expr
            v = ew_values(e.op, as_matrix(args[0]).value,
                          as_matrix(args[1]).value)
            return BlockMatrix.from_dense(v, bs)
        if k == P.MASKED_ELEMWISE:
            return self._masked_elemwise(plan, node, args)
        if k == P.MASKED_AGG:
            return self._masked_agg(plan, node, args)
        if k == P.MATMUL:
            a, b = as_matrix(args[0]).value, as_matrix(args[1]).value
            self._bump("matmuls")
            v = jnp.dot(a, b, preferred_element_type=a.dtype)
            return BlockMatrix.from_dense(v, bs)
        if k == P.INVERSE:
            return BlockMatrix.from_dense(
                jnp.linalg.inv(as_matrix(args[0]).value), bs)
        if k == P.SELECT:
            e: Select = node.expr
            return BlockMatrix.from_dense(
                select_dense(as_matrix(args[0]).value, e.pred), bs)
        if k == P.AGG:
            e: Agg = node.expr
            return BlockMatrix.from_dense(
                agg_dense(as_matrix(args[0]).value, e.fn, e.dim), bs)
        if k == P.JOIN:
            return self._join(plan, node, args)
        raise TypeError(k)

    def _masked_elemwise(self, plan: P.PhysicalPlan, node: P.PhysicalNode,
                         args: List[Result]) -> BlockMatrix:
        e: ElemWise = node.expr
        flip = node.meta["flip"]
        sp = as_matrix(args[0])
        w, h = as_matrix(args[1]), as_matrix(args[2])
        from repro.kernels import registry
        prod = registry.dispatch(
            "masked_matmul", w.value, h.value, sp.block_mask,
            backend=node.backend, block_size=plan.block_size)
        self._bump("masked_matmuls")
        if e.op is EWOp.MUL:
            v = sp.value * prod
        else:
            num, den = (prod, sp.value) if flip else (sp.value, prod)
            v = jnp.where((num == 0) | (den == 0), 0.0,
                          num / jnp.where(den == 0, 1.0, den))
        return BlockMatrix(v, sp.block_mask, plan.block_size)

    def _masked_agg(self, plan: P.PhysicalPlan, node: P.PhysicalNode,
                    args: List[Result]) -> BlockMatrix:
        """Fused Σ(sp ∘ (W×H)): the factorized kernel reduces in-register
        and the m×n masked product never exists as a value."""
        e: Agg = node.expr
        sp = as_matrix(args[0])
        w, h = as_matrix(args[1]), as_matrix(args[2])
        from repro.kernels import registry
        v = registry.dispatch(
            "sddmm_agg", sp.value, w.value, h.value, sp.block_mask,
            backend=node.backend, dim=_AGG_DIM[e.dim],
            block_size=plan.block_size, w_mask=w.block_mask,
            h_mask=h.block_mask)
        self._bump("masked_aggs")
        return BlockMatrix.from_dense(v, plan.block_size)

    def _join(self, plan: P.PhysicalPlan, node: P.PhysicalNode,
              args: List[Result]) -> Result:
        e: Join = node.expr
        a, b = as_matrix(args[0]), as_matrix(args[1])
        self._bump("joins")
        if plan.mode == "dense":
            out = joinsmod.join_dense(a.value, b.value, e.pred, e.merge)
            return dense_join_result(out, plan.block_size)
        # node.strategy overrides use_bloom inside v2v_sparse; other join
        # kinds ignore both
        return joinsmod.join_sparse(
            a, b, e.pred, e.merge,
            kernel_backend=node.backend, strategy=node.strategy)

    # -- jit-staged dense path ------------------------------------------------
    def _run_staged(self, plan: P.PhysicalPlan, mesh=None) -> Result:
        path = "spmd" if mesh is not None else "plain"
        prog = self._program(plan, path, mesh, _stage)
        leaf_vals = self._leaf_values(prog)
        self._bump("staged_spmd" if mesh is not None else "staged")
        self._bump("node_evals", plan.n_nodes)
        out = self._call_staged(prog, leaf_vals, path)
        return dense_join_result(out, plan.block_size)

    def _program(self, plan: P.PhysicalPlan, path: str, mesh,
                 stage: Callable) -> "_Program":
        """The staged program of ``plan`` on ``path``: the cached one
        whose trace is ``plan``'s, else one built by ``stage`` under a
        ``stage_compile`` span. Bumps ``staged_program{source=built|
        cached}`` once a staged call."""
        key = (path, mesh, program_key(plan))
        prog = self.programs.get(key)
        source = "cached"
        if prog is None:
            spmd = mesh is not None
            with span("stage_compile", mode=plan.mode, spmd=spmd):
                faults.check("stage_compile", mode=plan.mode, spmd=spmd)
                t0 = time.perf_counter()
                prog = _Program(*stage(_frozen(plan), mesh))
                self.timings["compile_s"] += time.perf_counter() - t0
            self.programs.put(key, prog)
            source = "built"
        REGISTRY.counter("staged_program", source=source).inc()
        return prog

    def _leaf_values(self, prog: "_Program") -> tuple:
        for name in prog.leaf_names:
            if name not in self.env:
                raise KeyError(f"unbound matrix {name!r}")
        return tuple(self.env[name].value for name in prog.leaf_names)

    def _call_staged(self, prog: "_Program", leaf_vals, path: str,
                     **attrs):
        """Dispatch one staged call, attributing its wall time: the
        program's first call, on whichever plan, is dominated by XLA
        trace+compile (``jax.jit`` compiles lazily) and lands in
        ``compile_s``; later calls are steady-state and land in
        ``execute_s``. Traced runs synchronize so span/ledger times mean
        finished work. ``attrs`` go on the ``execute`` span."""
        first = prog.calls == 0
        traced = TRACER.active()
        outer = (TRACER.span("stage_compile", phase="xla-compile")
                 if first else _noop_ctx())
        with outer:
            with span("execute", path=f"staged-{path}", cold=first,
                      **attrs):
                t0 = time.perf_counter()
                out = prog.fn(*leaf_vals)
                if traced:
                    _sync(out)
                dt = time.perf_counter() - t0
        prog.calls += 1
        self.timings["compile_s" if first else "execute_s"] += dt
        return out

    # -- jit-staged sparse path -----------------------------------------------
    def _run_staged_sparse(self, plan: P.PhysicalPlan, mesh=None):
        """Stage a sparse-tier plan into one (GSPMD) program, or return
        ``_FALLBACK`` when the mask pass vetoes staging / buffers overflow."""
        from repro.plan import masks as masksmod
        masksmod.annotate(plan, self.env)
        if not masksmod.stageable(plan):
            self._bump("sparse_fallbacks")
            return _FALLBACK
        # the trace bakes in the propagated masks and the COO capacities
        # (expansion AND side buffers), which can change under an
        # unchanged expr: ``program_key`` holds all of them
        path = "sparse-spmd" if mesh is not None else "sparse"
        prog = self._program(plan, path, mesh, _stage_sparse)
        leaf_vals = self._leaf_values(prog)
        out = self._call_staged(prog, leaf_vals, path, **_masked_attrs(plan))
        root = plan.node(plan.root)
        if isinstance(out, joinsdev.DeviceCOO) and joinsdev.overflowed(out):
            # leaf values drifted under an unchanged block mask: the
            # exact plan-time capacity went stale. Recover on the host
            # oracle now (which counts its own evaluations) and force a
            # re-annotation for the next run.
            plan._mask_key = None
            self._bump("sparse_overflows")
            return _FALLBACK
        self._bump("staged_sparse_spmd" if mesh is not None
                   else "staged_sparse")
        self._bump("node_evals", plan.n_nodes)
        # the staged program computes every DAG node exactly once, so the
        # per-kind compute counters (the CSE evidence) stay meaningful
        self._bump("matmuls", plan.count(P.MATMUL))
        self._bump("masked_matmuls", plan.count(P.MASKED_ELEMWISE))
        self._bump("masked_aggs", plan.count(P.MASKED_AGG))
        self._bump("joins", plan.count(P.JOIN))
        self._bump("blocks_skipped", prog.skip_stats[0])
        self._bump("blocks_total", prog.skip_stats[1])
        if isinstance(out, joinsdev.DeviceCOO):
            return joinsdev.coo_to_host(out, root.shape)
        mask = root.meta.get("mask")
        if mask is not None:
            return BlockMatrix(out, jnp.asarray(mask), plan.block_size)
        return BlockMatrix.from_dense(out, plan.block_size)


_FALLBACK = object()  # sentinel: staged sparse declined; run the eager oracle


def _sync(x) -> None:
    """Wait for device work in ``x`` (traced runs only — see callers).
    Host-side results (COO etc.) have nothing to wait for; only the
    shape errors a non-pytree payload can produce are tolerated —
    anything else (including injected faults) propagates."""
    try:
        jax.block_until_ready(getattr(x, "value", x))
    except (TypeError, AttributeError):
        pass


# the block-skipping kernel an undemoted masked node dispatches
_MASKED_KERNELS = {P.MASKED_AGG: "sddmm_agg",
                   P.MASKED_ELEMWISE: "masked_matmul"}


def _masked_attrs(plan: P.PhysicalPlan) -> Dict[str, str]:
    """The ``execute`` span attributes of a staged sparse plan: the
    kernels its masked nodes dispatch (``kernels``), and which arm of the
    gate its ``MASKED_AGG`` nodes took (``masked_agg``: ``kernel`` or
    ``demoted``); neither where the plan has no such node."""
    kernels, arms = set(), set()
    for n in plan.nodes:
        if n.kind not in _MASKED_KERNELS:
            continue
        demoted = bool(n.meta.get("demote_dense"))
        if not demoted:
            kernels.add(_MASKED_KERNELS[n.kind])
        if n.kind == P.MASKED_AGG:
            arms.add("demoted" if demoted else "kernel")
    attrs = {}
    if kernels:
        attrs["kernels"] = ",".join(sorted(kernels))
    if arms:
        attrs["masked_agg"] = ",".join(sorted(arms))
    return attrs


class _noop_ctx:
    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


# Bounds a staged-program cache: each entry pins a jit-ed executable.
# Sessions alternating among a few leaf bindings, or serving many
# queries, stay compiled; churn evicts least-recently-used first.
PROGRAM_CACHE_LIMIT = 128


def program_cache() -> VersionedLRU:
    """A new staged-program cache, keyed ``(path, mesh, program_key)``
    (a ``Session`` and a ``ServeEngine`` each own one)."""
    return VersionedLRU(PROGRAM_CACHE_LIMIT)


class _Program:
    """One staged program: the jit-ed function, the catalog leaves it
    takes, the static block-gating totals of its trace (sparse tier),
    and how often it has been called. Its first call, on whichever plan,
    traces and compiles it (or fetches it from the persistent cache).
    Only whether ``calls`` is 0 is read, which racing increments from
    the serving tier's threads cannot undo."""

    __slots__ = ("fn", "leaf_names", "skip_stats", "calls")

    def __init__(self, fn, leaf_names: Tuple[str, ...],
                 skip_stats: Tuple[int, int] = (0, 0)):
        self.fn = fn
        self.leaf_names = leaf_names
        self.skip_stats = skip_stats
        self.calls = 0


# the flags of ``node.meta`` that ``_stage_sparse`` reads while tracing
_TRACED_META = ("flip", "demote_dense", "cap", "cap_sides")


def program_key(plan: P.PhysicalPlan) -> tuple:
    """What ``_stage`` / ``_stage_sparse`` read of ``plan`` while
    tracing, as a key: two plans with equal keys trace the same program.

    Per node: kind, operator payload, wiring, shape, backend, strategy,
    scheme, the traced ``meta`` flags and the propagated block mask,
    compared by its bytes (a checksum could collide and skip live
    blocks). Estimates the trace never reads (a leaf's sparsity,
    ``est_flops``, ``comm_est``) are left out, and a leaf enters by name
    and shape: ``jax.jit`` retraces by itself on a new dtype. Kept on the
    plan until the mask pass re-annotates it (it then writes new
    ``_mask_infos``), so a cached plan pays one attribute read."""
    memo = plan._program_key
    if memo is not None and memo[0] is plan._mask_infos:
        return memo[1]
    key = (plan.block_size, plan.mode, plan.root,
           tuple(_node_key(n) for n in plan.nodes))
    plan._program_key = (plan._mask_infos, key)
    return key


def _node_key(node: P.PhysicalNode) -> tuple:
    mask = node.meta.get("mask")
    if mask is not None:
        mask = (mask.shape, np.packbits(mask).tobytes())
    return (node.op_id, node.kind, _payload(node.expr), node.children,
            node.shape, node.backend, node.strategy, node.scheme,
            tuple(node.meta.get(k) for k in _TRACED_META), mask)


def _payload(e: Expr) -> tuple:
    """An operator's parameters (scalar, predicate, aggregation, merge):
    every field of ``e`` but its child expressions; a leaf's name alone."""
    if isinstance(e, Leaf):
        return (e.name,)
    fields = (getattr(e, f.name) for f in dataclasses.fields(e))
    return (type(e),) + tuple(v for v in fields if not isinstance(v, Expr))


def _frozen(plan: P.PhysicalPlan) -> P.PhysicalPlan:
    """A copy of ``plan`` as it reads now, for a program to trace from: a
    cached program outlives its plan and serves other plans with the same
    key, so a later re-annotation of this plan must not reach a retrace."""
    nodes = tuple(dataclasses.replace(n, meta=dict(n.meta))
                  for n in plan.nodes)
    return dataclasses.replace(plan, nodes=nodes)


def _stage(plan: P.PhysicalPlan, mesh=None):
    """Compile the whole DAG into one jit-ed function of the leaf arrays.

    Synthesized ``ones(...)`` leaves are constants and materialize inside
    the trace; only catalog leaves become function arguments (so shape
    changes in the session environment simply retrace).

    With ``mesh``, every node output is pinned to its propagated scheme
    (``node.scheme``) via ``with_sharding_constraint`` — the whole plan
    becomes one GSPMD program and XLA inserts exactly the reshards the
    scheme pass accounted for.
    """
    env_leaves = [n for n in plan.nodes
                  if n.kind == P.LEAF and not n.expr.name.startswith("ones(")]
    leaf_names = tuple(n.expr.name for n in env_leaves)
    arg_index = {n.op_id: i for i, n in enumerate(env_leaves)}

    constraint = None
    if mesh is not None:
        from repro.core.partitioner import sharding_for

        def constraint(node, v):
            if node.scheme is None:
                return v
            return jax.lax.with_sharding_constraint(
                v, sharding_for(mesh, node.scheme, v.ndim))

    def fn(*leaf_vals):
        vals: Dict[int, jnp.ndarray] = {}
        for node in plan.nodes:
            # device ops carry the plan node that issued them
            with jax.named_scope(f"{node.kind}{node.op_id}"):
                k = node.kind
                e = node.expr
                ch = [vals[c] for c in node.children]
                if k == P.LEAF:
                    if node.op_id in arg_index:
                        v = leaf_vals[arg_index[node.op_id]]
                    else:
                        v = jnp.ones(e.shape, jnp.float32)
                elif k == P.TRANSPOSE:
                    v = ch[0].T
                elif k == P.MATSCALAR:
                    v = ch[0] + e.beta if e.op is EWOp.ADD else ch[0] * e.beta
                elif k == P.ELEMWISE:
                    v = ew_values(e.op, ch[0], ch[1])
                elif k == P.MATMUL:
                    v = jnp.dot(ch[0], ch[1],
                                preferred_element_type=ch[0].dtype)
                elif k == P.INVERSE:
                    v = jnp.linalg.inv(ch[0])
                elif k == P.SELECT:
                    v = select_dense(ch[0], e.pred)
                elif k == P.AGG:
                    v = agg_dense(ch[0], e.fn, e.dim)
                elif k == P.JOIN:
                    v = joinsmod.join_dense(ch[0], ch[1], e.pred, e.merge)
                else:
                    raise TypeError(f"node kind {k!r} is not jit-stageable")
                if constraint is not None:
                    v = constraint(node, v)
            vals[node.op_id] = v
        return vals[plan.root]

    return jax.jit(fn), leaf_names


def _stage_sparse(plan: P.PhysicalPlan, mesh=None):
    """Compile a sparse-tier DAG into one jit-ed function of the leaves.

    Identical skeleton to ``_stage``, but sparsity-aware per node: overlay
    joins and masked matmuls are gated by the plan-time propagated block
    masks (static numpy arrays baked into the trace — dead blocks are
    *absent*, not branched over), and COO-producing joins lower to the
    device tier with their plan-time capacities. Returns
    ``(fn, leaf_names, (blocks_skipped, blocks_total))`` where the skip
    counts are the static block-gating totals of this trace.
    """
    from repro.core.sparsity import analyze_merge
    from repro.kernels import registry
    from repro.kernels.merge_join import mode_for
    from repro.kernels.sddmm_agg import panel_counts
    from repro.core import cost as costmod
    from repro.core.matrix import blocks_of, unblock
    from repro.core.predicates import JoinKind

    bs = plan.block_size
    env_leaves = [n for n in plan.nodes
                  if n.kind == P.LEAF and not n.expr.name.startswith("ones(")]
    leaf_names = tuple(n.expr.name for n in env_leaves)
    arg_index = {n.op_id: i for i, n in enumerate(env_leaves)}

    # static block-gating totals of this trace (masks are plan-time data)
    skipped = total = 0
    for n in plan.nodes:
        gated = (n.kind == P.MASKED_ELEMWISE
                 and not n.meta.get("demote_dense")) \
            or (n.kind == P.JOIN and n.expr.pred.kind in
                (JoinKind.DIRECT_OVERLAY, JoinKind.TRANSPOSE_OVERLAY))
        if gated and n.meta.get("mask") is not None:
            skipped += int(n.meta["mask"].size - n.meta["mask"].sum())
            total += int(n.meta["mask"].size)
        if n.kind == P.MASKED_AGG and not n.meta.get("demote_dense"):
            # the fused kernel's gate is the sparse child's mask (the
            # node's own mask is the tiny aggregation output)
            g, wm, hm = (plan.node(c).meta.get("mask") for c in n.children)
            if g is not None:
                skipped += int(g.size - g.sum())
                total += int(g.size)
            if g is not None and wm is not None and hm is not None:
                computed, dead = panel_counts(g, wm, hm)
                REGISTRY.counter("sddmm_agg_panels",
                                 state="computed").inc(computed)
                REGISTRY.counter("sddmm_agg_panels",
                                 state="skipped").inc(dead)
    skip_stats = (skipped, total)

    constraint = None
    if mesh is not None:
        from repro.core.partitioner import sharding_for

        def constraint(node, v):
            # COO buffers keep XLA's default placement: the paper's r/c/b
            # schemes describe dense matrix layouts, not entry sets
            if node.scheme is None or not isinstance(v, jnp.ndarray):
                return v
            return jax.lax.with_sharding_constraint(
                v, sharding_for(mesh, node.scheme, v.ndim))

    def _overlay(node, av, bv):
        e: Join = node.expr
        transpose = e.pred.kind is JoinKind.TRANSPOSE_OVERLAY
        bval = bv.T if transpose else bv
        out_mask = node.meta["mask"]
        prof = analyze_merge(e.merge)
        if out_mask.all():
            return e.merge.fn(av, bval)
        if out_mask.mean() > 0.5:
            # mostly-live: one block-masked kernel over the full matrices
            # (mirrors the host tier's adaptive cutover)
            ma = plan.node(node.children[0]).meta["mask"]
            mb = plan.node(node.children[1]).meta["mask"]
            if transpose:
                mb = mb.T
            return registry.dispatch(
                "merge_join", av, bval, jnp.asarray(ma), jnp.asarray(mb),
                backend=node.backend, merge=e.merge.fn,
                mode=mode_for(prof.inducing_x, prof.inducing_y),
                block_size=bs)
        # sparse: gather the live blocks (static indices — skipped blocks
        # never enter the trace), vmap the merge, scatter back. The
        # output carries the promoted input dtype so mask density never
        # changes the result dtype vs. the all-live / host paths.
        ib, jb = np.nonzero(out_mask)
        m, n = node.shape
        dt = jnp.result_type(av.dtype, bval.dtype)
        if ib.size == 0:
            return jnp.zeros((m, n), dt)
        at = blocks_of(av, bs)
        bt = blocks_of(bval, bs)
        merged = jax.vmap(e.merge.fn)(at[ib, jb], bt[ib, jb])
        full = jnp.zeros(at.shape, dt)
        full = full.at[ib, jb].set(merged.astype(dt))
        return unblock(full, m, n)

    def _coo_join(node, av, bv):
        e: Join = node.expr
        prof = analyze_merge(e.merge)
        cap = node.meta["cap"]
        k = e.pred.kind
        ca, cb = node.meta.get("cap_sides", (None, None))
        if k is JoinKind.CROSS:
            return joinsdev.cross_device(av, bv, e.merge.fn, prof, cap,
                                         cap_a=ca, cap_b=cb)
        if k is JoinKind.D2D:
            return joinsdev.d2d_device(av, bv, e.pred.left, e.pred.right,
                                       e.merge.fn, prof, cap,
                                       cap_a=ca, cap_b=cb,
                                       kernel_backend=node.backend)
        if k is JoinKind.V2V:
            return joinsdev.v2v_device(
                av, bv, e.merge.fn, prof, cap, cap_a=ca, cap_b=cb,
                use_bloom=(node.strategy == costmod.BLOOM_SORTMERGE),
                kernel_backend=node.backend)
        if k is JoinKind.D2V:
            return joinsdev.d2v_device(av, bv, e.pred.left, e.merge.fn,
                                       prof, cap, cap_a=ca)
        if k is JoinKind.V2D:
            # the line-matrix side of the mirror is B (child 1)
            return joinsdev.v2d_device(av, bv, e.pred.right, e.merge.fn,
                                       prof, cap, cap_a=cb)
        raise ValueError(k)

    def _masked_agg(node, sp, w, h):
        e: Agg = node.expr
        if node.meta.get("demote_dense"):
            # mostly-live gate: the fused kernel buys nothing over XLA's
            # own fusion of dot+mul+reduce — let the compiler have it
            return agg_dense(sp * jnp.dot(w, h,
                                          preferred_element_type=w.dtype),
                             e.fn, e.dim)
        # the plan-time masks are host arrays: the kernel lists its live
        # panel products from them while the program is traced
        gate, wm, hm = (plan.node(c).meta["mask"] for c in node.children)
        return registry.dispatch(
            "sddmm_agg", sp, w, h, gate, backend=node.backend,
            dim=_AGG_DIM[e.dim], block_size=bs, w_mask=wm, h_mask=hm)

    def _masked(node, sp, w, h):
        e: ElemWise = node.expr
        flip = node.meta["flip"]
        if node.meta.get("demote_dense"):
            prod = jnp.dot(w, h, preferred_element_type=w.dtype)
        else:
            gate = jnp.asarray(node.meta["mask"])  # static propagated mask
            prod = registry.dispatch("masked_matmul", w, h, gate,
                                     backend=node.backend, block_size=bs)
        if e.op is EWOp.MUL:
            return sp * prod
        num, den = (prod, sp) if flip else (sp, prod)
        return jnp.where((num == 0) | (den == 0), 0.0,
                         num / jnp.where(den == 0, 1.0, den))

    def fn(*leaf_vals):
        vals: Dict[int, Union[jnp.ndarray, joinsdev.DeviceCOO]] = {}
        for node in plan.nodes:
            # device ops carry the plan node that issued them
            with jax.named_scope(f"{node.kind}{node.op_id}"):
                k = node.kind
                e = node.expr
                ch = [vals[c] for c in node.children]
                if k == P.LEAF:
                    if node.op_id in arg_index:
                        v = leaf_vals[arg_index[node.op_id]]
                    else:
                        v = jnp.ones(e.shape, jnp.float32)
                elif k == P.TRANSPOSE:
                    v = ch[0].T
                elif k == P.MATSCALAR:
                    v = ch[0] + e.beta if e.op is EWOp.ADD else ch[0] * e.beta
                elif k == P.ELEMWISE:
                    v = ew_values(e.op, ch[0], ch[1])
                elif k == P.MASKED_ELEMWISE:
                    v = _masked(node, ch[0], ch[1], ch[2])
                elif k == P.MASKED_AGG:
                    v = _masked_agg(node, ch[0], ch[1], ch[2])
                elif k == P.MATMUL:
                    v = jnp.dot(ch[0], ch[1],
                                preferred_element_type=ch[0].dtype)
                elif k == P.INVERSE:
                    v = jnp.linalg.inv(ch[0])
                elif k == P.SELECT:
                    v = select_dense(ch[0], e.pred)
                elif k == P.AGG:
                    v = agg_dense(ch[0], e.fn, e.dim)
                elif k == P.JOIN:
                    pk = e.pred.kind
                    if pk in (JoinKind.DIRECT_OVERLAY,
                              JoinKind.TRANSPOSE_OVERLAY):
                        v = _overlay(node, ch[0], ch[1])
                    else:
                        # COO outputs have no matrix consumers (the builder
                        # un-stages any such plan), so this is the root
                        assert node.op_id == plan.root
                        v = _coo_join(node, ch[0], ch[1])
                else:
                    raise TypeError(f"node kind {k!r} is not jit-stageable")
                if constraint is not None:
                    v = constraint(node, v)
            vals[node.op_id] = v
        return vals[plan.root]

    return jax.jit(fn), leaf_names, skip_stats


def execute_plan(plan: P.PhysicalPlan, env: Dict[str, BlockMatrix],
                 stage_jit: bool = True, mesh=None) -> Result:
    return PlanExecutor(env, stage_jit=stage_jit, mesh=mesh).run(plan)


def staged_collective_bytes(plan: P.PhysicalPlan,
                            env: Dict[str, BlockMatrix],
                            mesh, programs=None) -> Optional[int]:
    """HLO-measured network-wide collective bytes of the whole-plan SPMD
    program, for validating the scheme pass's ``total_comm_est`` (same
    unit: entries moved × dtype bytes). ``None`` when the plan cannot
    stage (non-jit-safe or sparse tier). ``programs``, a session's or
    engine's program cache, supplies the program and keeps one it builds."""
    if plan.mode != "dense" or not plan.jit_safe or mesh is None:
        return None
    from repro.core.partitioner import measured_network_bytes
    key = ("spmd", mesh, program_key(plan))
    prog = programs.get(key) if programs is not None else None
    if prog is None:
        prog = _Program(*_stage(_frozen(plan), mesh))
        if programs is not None:
            programs.put(key, prog)
    leaf_vals = tuple(env[name].value for name in prog.leaf_names)
    return measured_network_bytes(prog.fn, *leaf_vals,
                                  n_workers=plan.n_workers)

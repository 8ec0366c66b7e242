"""Device-resident sparse tier ≡ host-COO oracle, and mask propagation.

Three layers:

* per-join parity — every COO family (D2D / V2V / CROSS / D2V / V2D)
  through ``join_sparse_device`` against ``join_sparse``, over randomized
  sparsity levels including the 0% and 100% extremes, with and without
  sparsity-inducing merges (and with the Bloom pre-filter on V2V);
* whole-plan staging — sparse and mixed sparse/dense plans compile into
  ONE program (``stats["staged_sparse"] == 1``, no per-node evaluation)
  and equal the tree-walk oracle; capacity overflow falls back to the
  eager host path and still returns the right answer;
* mask propagation — predicted block masks are conservative (never a
  false-negative skip) on randomized plans, and exactly equal to the
  computed result's nonzero blocks on a block-aligned golden case.
"""
import os

import jax
import numpy as np
import pytest

from repro.core import MergeFn, Session
from repro.core import joins as joinsmod
from repro.core.joins import join_sparse, join_sparse_device
from repro.core.matrix import BlockMatrix, compute_block_mask
from repro.core.predicates import parse_join
from repro.core.sparsity import product_merge, sum_merge
from repro.plan import PlanExecutor, program_cache
from repro.plan import masks as masksmod

BS = 8

MERGES = [product_merge(), sum_merge(),
          MergeFn("affdev", lambda x, y: 2 * x * y + x)]


def _sparse(rng, m, n, density, round_vals=False):
    v = rng.normal(size=(m, n)).astype(np.float32)
    out = np.where(rng.uniform(size=(m, n)) < density, v, 0)
    out = out.astype(np.float32)
    return np.round(out, 1) if round_vals else out


def _bm(a):
    return BlockMatrix.from_dense(np.asarray(a, np.float32), BS)


def _dimvals(rng, m, n, density, limit):
    """A matrix of valid dimension values (integers < limit) for D2V/V2D."""
    v = rng.integers(1, limit, size=(m, n)).astype(np.float32)
    return np.where(rng.uniform(size=(m, n)) < density, v, 0) \
        .astype(np.float32)


# ---------------------------------------------------------------------------
# Per-join parity: device ≡ host oracle.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("density", [0.0, 0.05, 0.3, 1.0])
@pytest.mark.parametrize("merge", MERGES, ids=lambda m: m.name)
@pytest.mark.parametrize("pred_s", ["RID=RID", "CID=CID", "VAL=VAL",
                                    "CROSS"])
def test_device_equals_host_oracle(rng, pred_s, merge, density):
    a = _sparse(rng, 24, 20, density, round_vals=True)
    b = _sparse(rng, 24 if "RID" in pred_s.split("=")[0] else 20,
                28, density, round_vals=True)
    if pred_s == "CID=CID":
        a, b = a.T.copy(), b.T.copy()
    pred = parse_join(pred_s)
    host = join_sparse(_bm(a), _bm(b), pred, merge)
    dev = join_sparse_device(_bm(a), _bm(b), pred, merge)
    assert dev.val.dtype == host.val.dtype
    np.testing.assert_allclose(dev.to_dense(), host.to_dense(), atol=1e-5)


@pytest.mark.parametrize("density", [0.0, 0.2, 1.0])
@pytest.mark.parametrize("pred_s", ["RID=VAL", "VAL=RID"])
def test_device_dimension_entry_joins(rng, pred_s, density):
    for merge in (product_merge(), sum_merge()):
        if pred_s == "RID=VAL":
            a = _sparse(rng, 24, 12, 0.4)
            b = _dimvals(rng, 6, 5, density, limit=24)
        else:
            a = _dimvals(rng, 6, 5, density, limit=24)
            b = _sparse(rng, 24, 12, 0.4)
        pred = parse_join(pred_s)
        host = join_sparse(_bm(a), _bm(b), pred, merge)
        dev = join_sparse_device(_bm(a), _bm(b), pred, merge)
        np.testing.assert_allclose(dev.to_dense(), host.to_dense(),
                                   atol=1e-5, err_msg=merge.name)


def test_device_v2v_bloom_matches_plain(rng):
    a = _sparse(rng, 48, 48, 0.3, round_vals=True)
    b = _sparse(rng, 48, 48, 0.3, round_vals=True)
    pred = parse_join("VAL=VAL")
    plain = join_sparse_device(_bm(a), _bm(b), pred, product_merge())
    bloom = join_sparse_device(_bm(a), _bm(b), pred, product_merge(),
                               use_bloom=True)
    host = join_sparse(_bm(a), _bm(b), pred, product_merge())
    assert plain.nnz == bloom.nnz == host.nnz > 0
    np.testing.assert_allclose(bloom.to_dense(), host.to_dense(), atol=1e-5)


def test_device_capacity_too_small_raises(rng):
    a = _sparse(rng, 16, 16, 0.5, round_vals=True)
    with pytest.raises(ValueError, match="capacity"):
        join_sparse_device(_bm(a), _bm(a), parse_join("RID=RID"),
                           sum_merge(), cap=8)


def test_cross_total_int32_wrap_still_overflows():
    """Regression: a dense 256×256 non-inducing cross has 2³² expansion
    slots — exactly the int32 wrap-to-zero case. The float32 shadow
    product must still flag the overflow instead of returning an empty
    result that looks valid."""
    a = np.ones((256, 256), np.float32)
    with pytest.raises(ValueError, match="capacity"):
        join_sparse_device(_bm(a), _bm(a), parse_join("CROSS"),
                           sum_merge(), cap=64)


def test_empty_join_dtype_matches_populated(rng):
    """Regression: the zero-row paths used to hardcode float64 while
    populated results carried the (float32) input dtype."""
    zero = np.zeros((16, 16), np.float32)
    some = _sparse(rng, 16, 16, 0.3)
    pred = parse_join("RID=RID")
    empty = joinsmod.d2d_sparse(_bm(zero), _bm(zero), pred.left, pred.right,
                                product_merge())
    full = joinsmod.d2d_sparse(_bm(some), _bm(some), pred.left, pred.right,
                               product_merge())
    assert empty.nnz == 0 and full.nnz > 0
    assert empty.val.dtype == full.val.dtype == np.float32
    for pred_s in ("VAL=VAL", "CROSS", "RID=VAL"):
        out = join_sparse(_bm(zero), _bm(zero), parse_join(pred_s),
                          product_merge())
        assert out.val.dtype == np.float32, pred_s


# ---------------------------------------------------------------------------
# Merge-profile cache (core.sparsity) — the profiles gate every mask rule.
# ---------------------------------------------------------------------------

def test_analyze_merge_cached_by_name():
    """The profile cache keys on the merge-fn name AND its callable: a
    second analysis of the same merge returns the cached profile without
    re-probing, while a different callable under the same name (every
    plain callable ``Matrix.join`` wraps is named "f") is probed anew."""
    from repro.core import sparsity as spmod
    from repro.core.sparsity import analyze_merge

    name = "cache_probe_test"
    calls = []

    def counting_mul(x, y):
        calls.append(1)
        return x * y

    p1 = analyze_merge(MergeFn(name, counting_mul))
    assert (name, counting_mul) in spmod._CACHE
    assert p1.inducing_x and p1.inducing_y
    probes = len(calls)
    assert probes > 0
    # same merge again: cache wins, no new probes
    assert analyze_merge(MergeFn(name, counting_mul)) is p1
    assert len(calls) == probes
    # same name, different (non-inducing) fn: its own profile
    add = lambda x, y: x + y  # noqa: E731
    p2 = analyze_merge(MergeFn(name, add))
    assert not p2.inducing_x and not p2.inducing_y
    spmod._CACHE.pop((name, counting_mul), None)
    spmod._CACHE.pop((name, add), None)


def test_analyze_merge_failing_fn_not_inducing():
    """A merge fn that raises under scalar probing is conservatively
    treated as non-inducing (no block may be skipped)."""
    from repro.core.sparsity import analyze_merge

    def bad(x, y):
        raise RuntimeError("no scalars")

    p = analyze_merge(MergeFn("cache_bad_fn", bad))
    assert not p.inducing_x and not p.inducing_y


# ---------------------------------------------------------------------------
# Whole-plan staging.
# ---------------------------------------------------------------------------

def _session(rng, n=24, density=0.2):
    s = Session(block_size=BS)
    s.load(_sparse(rng, n, n, density), "A")
    s.load(_sparse(rng, n, n, 0.3), "B")
    from repro.core.api import Matrix
    from repro.core.expr import Leaf
    a = Matrix(s, Leaf("A", (n, n), density))
    b = Matrix(s, Leaf("B", (n, n), 0.3))
    return s, a, b


def test_mixed_plan_stages_into_one_program(rng):
    """Sparse overlay → dense matmul → overlay → agg: one staged program,
    zero per-node evaluations, oracle-equal."""
    s, a, b = _session(rng)
    mul = MergeFn("sd_mul", lambda x, y: x * y)
    add = MergeFn("sd_add", lambda x, y: x + y)
    q = a.join(b, "RID=RID AND CID=CID", mul).multiply(b) \
         .join(a, "RID=RID AND CID=CID", add).sum("r")
    pplan = s.physical_plan(s._optimized(q.plan))
    assert pplan.jit_safe
    programs = program_cache()
    ex = PlanExecutor(s.env, programs=programs)
    out = ex.run(pplan)
    assert ex.stats["staged_sparse"] == 1  # ONE compiled program
    assert ex.stats["sparse_fallbacks"] == 0
    want = s.execute(q.optimized_plan().plan, optimize=False, engine="tree")
    np.testing.assert_allclose(np.asarray(out.value),
                               np.asarray(want.value), atol=1e-3, rtol=1e-3)
    # the staged program is cached: a second collect reuses it
    ex2 = PlanExecutor(s.env, programs=programs)
    ex2.run(pplan)
    assert len(programs) == 1
    assert ex2.stats["staged_sparse"] == 1
    assert ex2.timings["compile_s"] == 0.0


@pytest.mark.parametrize("pred_s", ["RID=RID", "VAL=VAL", "CROSS",
                                    "RID=VAL"])
def test_coo_root_plans_stage_and_match(rng, pred_s):
    s, a, b = _session(rng)
    if pred_s == "RID=VAL":
        s.env["B"] = _bm(_dimvals(rng, 6, 5, 0.5, limit=24))
        from repro.core.api import Matrix
        from repro.core.expr import Leaf
        b = Matrix(s, Leaf("B", (6, 5), 0.5))
    mul = MergeFn("sd_mul", lambda x, y: x * y)
    q = a.join(b, pred_s, mul)
    ex = PlanExecutor(s.env)
    out = ex.run(s.physical_plan(s._optimized(q.plan)))
    assert ex.stats["staged_sparse"] == 1
    want = s.execute(q.optimized_plan().plan, optimize=False, engine="tree")
    np.testing.assert_allclose(out.to_dense(), want.to_dense(), atol=1e-4)


def test_capacity_overflow_falls_back_to_host(rng):
    """Leaf values drifting under an unchanged block mask stale-ify an
    exact capacity: the staged run must detect the overflow, recover via
    the eager oracle, and force a re-annotation."""
    s, a, b = _session(rng, density=0.1)
    mul = MergeFn("sd_mul", lambda x, y: x * y)
    q = a.join(b, "RID=RID", mul)
    pplan = s.physical_plan(s._optimized(q.plan))
    ex = PlanExecutor(s.env)
    ex.run(pplan)
    assert ex.stats["staged_sparse"] == 1
    # densify A *within its live blocks only* (same mask, more entries)
    old = np.asarray(s.env["A"].value)
    mask = np.asarray(s.env["A"].block_mask)
    big = np.repeat(np.repeat(mask, BS, 0), BS, 1)[:24, :24]
    s.env["A"] = _bm(np.where(big, rng.normal(size=(24, 24)), 0)
                     .astype(np.float32))
    assert np.array_equal(np.asarray(s.env["A"].block_mask), mask)
    ex2 = PlanExecutor(s.env)
    out = ex2.run(pplan)
    assert ex2.stats["sparse_overflows"] == 1
    want = s.execute(q.optimized_plan().plan, optimize=False, engine="tree")
    np.testing.assert_allclose(out.to_dense(), want.to_dense(), atol=1e-4)
    # next run re-annotates with the new values and stages again
    ex3 = PlanExecutor(s.env)
    ex3.run(pplan)
    assert ex3.stats["staged_sparse"] == 1
    assert ex3.stats["sparse_overflows"] == 0
    del old


def test_noninducing_d2d_bound_covers_zero_cells(rng):
    """Regression: the mask-derived D2D capacity bound must count full
    bands on a non-inducing side (zero cells join too) — otherwise the
    staged program is undersized and every collect falls back."""
    v = np.zeros((32, 32), np.float32)
    v[:8, :8] = rng.normal(size=(8, 8))
    s = Session(block_size=8)
    x = s.load(v, "X")
    y = s.load(v.T.copy(), "Y")
    # emul(2.0) makes both join children non-leaf → mask-bound capacities
    q = x.emul(2.0).join(y.emul(2.0), "RID=RID", sum_merge())
    ex = PlanExecutor(s.env)
    out = ex.run(s.physical_plan(s._optimized(q.plan)))
    assert ex.stats["sparse_overflows"] == 0
    assert ex.stats["staged_sparse"] == 1
    want = s.execute(q.optimized_plan().plan, optimize=False, engine="tree")
    np.testing.assert_allclose(out.to_dense(), want.to_dense(), atol=1e-4)


def test_side_cap_change_restages(rng):
    """Regression: growing a side buffer under an unchanged mask AND
    unchanged expansion cap must converge — the overflow run falls back
    once, re-annotation grows the side caps, and the NEXT run restages
    (side caps are part of the staged-cache key) instead of reusing the
    stale program and overflowing forever."""
    a = np.zeros((16, 16), np.float32)
    a[0, :8] = np.arange(1, 9)          # 8 nonzeros, one live block
    b = np.zeros((16, 16), np.float32)
    b[0, 0] = 1000.0                    # no shared values → 0 matches
    s = Session(block_size=8)
    A = s.load(a, "A")
    B = s.load(b, "B")
    mul = MergeFn("sc_mul", lambda x, y: x * y)
    q = A.join(B, "VAL=VAL", mul)
    pplan = s.physical_plan(s._optimized(q.plan))
    ex = PlanExecutor(s.env)
    ex.run(pplan)
    assert ex.stats["staged_sparse"] == 1
    a2 = a.copy()
    a2[1, :2] = [20.0, 21.0]            # same live block, more entries
    s.env["A"] = _bm(a2)
    assert np.array_equal(np.asarray(s.env["A"].block_mask),
                          np.asarray(_bm(a).block_mask))
    ex2 = PlanExecutor(s.env, programs=ex.programs)
    out2 = ex2.run(pplan)               # stale side cap: one fallback
    assert ex2.stats["sparse_overflows"] == 1
    assert len(ex.programs) == 1        # the first program, reused
    ex3 = PlanExecutor(s.env, programs=ex.programs)
    out3 = ex3.run(pplan)               # re-annotated + restaged
    assert ex3.stats["staged_sparse"] == 1
    assert ex3.stats["sparse_overflows"] == 0
    assert len(ex.programs) == 2        # grown side caps, a new key
    want = s.execute(q.optimized_plan().plan, optimize=False, engine="tree")
    np.testing.assert_allclose(out2.to_dense(), want.to_dense(), atol=1e-4)
    np.testing.assert_allclose(out3.to_dense(), want.to_dense(), atol=1e-4)


def test_cap_limit_vetoes_staging(rng):
    s, a, b = _session(rng, density=0.5)
    mul = MergeFn("sd_mul", lambda x, y: x * y)
    q = a.join(b, "RID=RID", mul)
    os.environ["REPRO_SPARSE_CAP"] = "16"
    try:
        pplan = s.physical_plan(s._optimized(q.plan))
        ex = PlanExecutor(s.env)
        out = ex.run(pplan)
        assert ex.stats["sparse_fallbacks"] == 1
        assert ex.stats["staged_sparse"] == 0
    finally:
        del os.environ["REPRO_SPARSE_CAP"]
    want = s.execute(q.optimized_plan().plan, optimize=False, engine="tree")
    np.testing.assert_allclose(out.to_dense(), want.to_dense(), atol=1e-4)


def test_explain_renders_propagated_nnz(rng):
    s, a, b = _session(rng)
    mul = MergeFn("sd_mul", lambda x, y: x * y)
    out = a.join(b, "RID=RID AND CID=CID", mul).explain(physical=True)
    assert "nnz≈" in out and "mask=" in out
    coo = a.join(b, "VAL=VAL", mul).explain(physical=True)
    assert "cap=" in coo


# ---------------------------------------------------------------------------
# Mask propagation.
# ---------------------------------------------------------------------------

def test_mask_propagation_no_false_negative_skips(rng):
    """Property: a propagated mask of False certifies an all-zero block of
    the actual result — across randomized multi-op plans and densities."""
    mul = MergeFn("mk_mul", lambda x, y: x * y)
    for seed in range(6):
        r = np.random.default_rng(seed)
        density = float(r.choice([0.0, 0.1, 0.5, 1.0]))
        s = Session(block_size=BS)
        A = s.load(_sparse(r, 24, 24, density), "A")
        B = s.load(_sparse(r, 24, 24, 0.3), "B")
        q = A.join(B, "RID=RID AND CID=CID", mul).multiply(B.t()) \
             .join(A, "RID=RID AND CID=CID", mul)
        pplan = s.physical_plan(s._optimized(q.plan))
        masksmod.annotate(pplan, s.env)
        out = s.execute(q.optimized_plan().plan, optimize=False,
                        engine="tree")
        actual = np.asarray(compute_block_mask(out.value, BS))
        predicted = pplan.node(pplan.root).meta["mask"]
        assert not np.any(actual & ~predicted), \
            f"false-negative skip at seed {seed}"


def test_mask_propagation_golden_exact():
    """Block-aligned supports with a sparsity-inducing merge: the
    predicted mask must equal the actual nonzero blocks exactly."""
    a = np.zeros((32, 32), np.float32)
    b = np.zeros((32, 32), np.float32)
    a[:16, :] = 1.0          # top two block-rows live
    b[:, :16] = 1.0          # left two block-columns live
    s = Session(block_size=16)
    A = s.load(a, "A")
    B = s.load(b, "B")
    mul = MergeFn("mk_mul", lambda x, y: x * y)
    q = A.join(B, "RID=RID AND CID=CID", mul)
    pplan = s.physical_plan(s._optimized(q.plan))
    masksmod.annotate(pplan, s.env)
    predicted = pplan.node(pplan.root).meta["mask"]
    out = q.collect()
    actual = np.asarray(compute_block_mask(out.value, 16))
    assert np.array_equal(predicted, actual)
    assert predicted.sum() == 1          # only the top-left block survives
    # and the nnz bound is exact here: one full 16×16 block
    assert pplan.node(pplan.root).meta["nnz_bound"] == 16 * 16


def test_mask_fingerprint_caches_annotation(rng):
    s, a, b = _session(rng)
    mul = MergeFn("sd_mul", lambda x, y: x * y)
    q = a.join(b, "RID=RID AND CID=CID", mul)
    pplan = s.physical_plan(s._optimized(q.plan))
    infos1 = masksmod.annotate(pplan, s.env)
    infos2 = masksmod.annotate(pplan, s.env)
    assert infos1 is infos2              # fingerprint hit: no recompute
    # same-mask value changes keep the cache (the overflow guard covers
    # them); a *mask* change must re-annotate
    newa = np.ones((24, 24), np.float32)
    newa[:8, :8] = 0.0                   # kill one block
    s.env["A"] = _bm(newa)
    infos3 = masksmod.annotate(pplan, s.env)
    assert infos3 is not infos1          # mask changed: re-annotated


# ---------------------------------------------------------------------------
# Multi-worker: sparse plans stage into a single GSPMD program.
# ---------------------------------------------------------------------------

multi_device = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs >=8 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=8); runs in the CI multi-device job")


@multi_device
def test_sparse_plan_stages_spmd_on_mesh(rng):
    s = Session(block_size=BS, n_workers=8)
    s.load(_sparse(rng, 32, 32, 0.2), "A")
    s.load(_sparse(rng, 32, 32, 0.3), "B")
    from repro.core.api import Matrix
    from repro.core.expr import Leaf
    a = Matrix(s, Leaf("A", (32, 32), 0.2))
    b = Matrix(s, Leaf("B", (32, 32), 0.3))
    mul = MergeFn("sd_mul", lambda x, y: x * y)
    q = a.join(b, "RID=RID AND CID=CID", mul).multiply(b).sum("c")
    pplan = s.physical_plan(s._optimized(q.plan))
    ex = PlanExecutor(s.env, mesh=s.mesh)
    out = ex.run(pplan)
    assert ex.stats["staged_sparse_spmd"] == 1     # ONE GSPMD program
    assert [key[0] for key in ex.programs.keys()] == ["sparse-spmd"]
    assert pplan.node(pplan.root).scheme is not None  # schemes propagated
    want = s.execute(q.optimized_plan().plan, optimize=False, engine="tree")
    np.testing.assert_allclose(np.asarray(out.value),
                               np.asarray(want.value), atol=1e-3, rtol=1e-3)

"""Fused kernel tier parity: ``coo_expand`` and ``sddmm_agg`` dense
oracle ≡ pallas-interpret across densities × dtypes × merge modes, the
capacity-overflow and empty-input edges, and the plan-time MASKED_AGG
fusion that routes Σ(A ∘ (W×H)) through ``sddmm_agg`` instead of
materializing the m×n product (mirrors ``test_sparse_device.py``'s
device ≡ host structure, one level down the stack)."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Session
from repro.core.expr import (
    Agg, AggDim, AggFn, ElemWise, EWOp, Leaf, MatMul,
)
from repro.core.matrix import compute_block_mask
from repro.kernels import registry
from repro.kernels.sddmm_agg import panel_steps as sddmm_panel_steps
from repro.plan import build_plan
from repro.plan import masks as masksmod
from repro.plan import ops as P

DENSITIES = [0.0, 0.01, 0.05, 0.2, 1.0]
DTYPES = ["float32", "float64"]

# merge modes for the COO expansion (module-level so the jitted kernel
# caches by identity instead of retracing per test)
_MERGES = {
    "mul": lambda x, y: x * y,
    "add": lambda x, y: x + y,
    "affine": lambda x, y: 2.0 * x * y + x,
}


@contextlib.contextmanager
def _maybe_x64(dtype_s):
    """The suite runs with x64 off; float64 legs enable it locally (a
    disabled-x64 float64 array silently aliases float32, which would make
    the parity trivially true and the dtype assertions false)."""
    if dtype_s == "float64":
        old = jax.config.jax_enable_x64
        jax.config.update("jax_enable_x64", True)
        try:
            yield
        finally:
            jax.config.update("jax_enable_x64", old)
    else:
        yield


def _tol(dtype_s):
    return dict(atol=1e-5 if dtype_s == "float32" else 1e-10, rtol=1e-5)


# ---------------------------------------------------------------------------
# coo_expand: fused segment-expand + merge-intersect.
# ---------------------------------------------------------------------------

def _segments(rng, ns, density, nb_extra=5, max_run=3):
    """Synthetic per-segment match runs: ``density`` of the ``ns`` probe
    segments carry a 1..max_run-entry partner run; the rest are empty
    (exactly the shape joins_device's sort pass produces)."""
    counts = np.where(rng.uniform(size=ns) < density,
                      rng.integers(1, max_run + 1, size=ns), 0) \
        .astype(np.int32)
    ends = np.cumsum(counts).astype(np.int32)
    total = int(ends[-1]) if ns else 0
    nb = max(total + nb_extra, 1)
    starts = (ends - counts).astype(np.int32)
    base = np.array([rng.integers(0, nb - int(c) + 1) for c in counts],
                    np.int32)
    delta = base - starts  # slot t in segment s reads partner t + delta[s]
    return ends, delta, total, nb


def _coo_operands(rng, ns, nb, dtype_s):
    av = jnp.asarray(np.round(rng.normal(size=ns), 1), dtype_s)
    ac = jnp.asarray(rng.integers(0, 100, size=(ns, 2)), jnp.int32)
    bv = jnp.asarray(np.round(rng.normal(size=nb), 1), dtype_s)
    bc = jnp.asarray(rng.integers(0, 100, size=(nb, 2)), jnp.int32)
    return av, ac, bv, bc


def _coo_both(ends, delta, av, ac, bv, bc, merge, cap):
    outs = []
    for backend in (registry.DENSE, registry.INTERPRET):
        outs.append(registry.dispatch(
            "coo_expand", jnp.asarray(ends), jnp.asarray(delta),
            av, ac, bv, bc, backend=backend, merge=merge, cap=cap))
    return outs


@pytest.mark.parametrize("density", DENSITIES)
@pytest.mark.parametrize("dtype_s", DTYPES)
@pytest.mark.parametrize("merge_name", sorted(_MERGES))
def test_parity_coo_expand(rng, density, dtype_s, merge_name):
    with _maybe_x64(dtype_s):
        ends, delta, total, nb = _segments(rng, ns=37, density=density)
        av, ac, bv, bc = _coo_operands(rng, 37, nb, dtype_s)
        cap = max(total, 1)
        (idx_d, val_d), (idx_i, val_i) = _coo_both(
            ends, delta, av, ac, bv, bc, _MERGES[merge_name], cap)
        assert idx_i.shape == idx_d.shape == (cap, 4)
        assert val_i.shape == val_d.shape == (cap,)
        assert str(val_i.dtype) == dtype_s
        # parity is defined over valid slots only: past the true total
        # both backends hold clamped garbage the caller masks out
        np.testing.assert_allclose(
            np.asarray(val_i)[:total], np.asarray(val_d)[:total],
            **_tol(dtype_s))
        assert np.array_equal(np.asarray(idx_i)[:total],
                              np.asarray(idx_d)[:total])


def test_coo_expand_capacity_overflow_truncates_identically(rng):
    """cap below the true total (the stale-capacity overflow shape the
    staged executor detects): both backends fill exactly cap slots, and
    every one of those slots is valid, so parity covers all of them."""
    ends, delta, total, nb = _segments(rng, ns=40, density=1.0)
    assert total > 8
    av, ac, bv, bc = _coo_operands(rng, 40, nb, "float32")
    cap = total // 2
    (idx_d, val_d), (idx_i, val_i) = _coo_both(
        ends, delta, av, ac, bv, bc, _MERGES["mul"], cap)
    assert val_d.shape == val_i.shape == (cap,)
    np.testing.assert_allclose(np.asarray(val_i), np.asarray(val_d),
                               atol=1e-5)
    assert np.array_equal(np.asarray(idx_i), np.asarray(idx_d))


def test_coo_expand_empty_input_edge(rng):
    """All segments empty (a join that matches nothing): every slot is
    garbage-but-present; shapes and dtypes still hold on both backends."""
    ends = np.zeros(12, np.int32)
    delta = np.zeros(12, np.int32)
    av, ac, bv, bc = _coo_operands(rng, 12, 1, "float32")
    for backend in (registry.DENSE, registry.INTERPRET):
        idx, val = registry.dispatch(
            "coo_expand", jnp.asarray(ends), jnp.asarray(delta),
            av, ac, bv, bc, backend=backend, merge=_MERGES["add"], cap=4)
        assert idx.shape == (4, 4) and val.shape == (4,)
        assert val.dtype == jnp.float32


def test_coo_expand_unaligned_cap_pads_and_slices(rng):
    """cap not a multiple of any tile size: the registry wrapper must pad
    the grid and slice back to exactly cap slots."""
    ends, delta, total, nb = _segments(rng, ns=33, density=0.5)
    av, ac, bv, bc = _coo_operands(rng, 33, nb, "float32")
    cap = max(total, 1) + 7  # deliberately odd slack
    (idx_d, val_d), (idx_i, val_i) = _coo_both(
        ends, delta, av, ac, bv, bc, _MERGES["affine"], cap)
    assert val_d.shape == val_i.shape == (cap,)
    np.testing.assert_allclose(np.asarray(val_i)[:total],
                               np.asarray(val_d)[:total], atol=1e-5)


# ---------------------------------------------------------------------------
# sddmm_agg: fused SDDMM + SUM aggregation.
# ---------------------------------------------------------------------------

def _sddmm_case(rng, density, dtype_s, m=33, k=7, n=41, bs=16):
    sp = np.where(rng.uniform(size=(m, n)) < density,
                  rng.normal(size=(m, n)), 0.0)
    sp = jnp.asarray(sp, dtype_s)
    w = jnp.asarray(rng.normal(size=(m, k)), dtype_s)
    h = jnp.asarray(rng.normal(size=(k, n)), dtype_s)
    return sp, w, h, compute_block_mask(sp, bs), bs


@pytest.mark.parametrize("density", DENSITIES)
@pytest.mark.parametrize("dtype_s", DTYPES)
@pytest.mark.parametrize("dim", ["row", "col", "all"])
def test_parity_sddmm_agg(rng, density, dtype_s, dim):
    with _maybe_x64(dtype_s):
        sp, w, h, mask, bs = _sddmm_case(rng, density, dtype_s)
        m, n = sp.shape
        dense = registry.dispatch("sddmm_agg", sp, w, h, mask,
                                  backend=registry.DENSE, dim=dim,
                                  block_size=bs)
        interp = registry.dispatch("sddmm_agg", sp, w, h, mask,
                                   backend=registry.INTERPRET, dim=dim,
                                   block_size=bs)
        want_shape = {"row": (m, 1), "col": (1, n), "all": (1, 1)}[dim]
        assert dense.shape == interp.shape == want_shape
        assert str(interp.dtype) == dtype_s
        # and both equal the unfused materialize-then-aggregate oracle
        prod = np.asarray(sp, np.float64) * (
            np.asarray(w, np.float64) @ np.asarray(h, np.float64))
        axis = {"row": 1, "col": 0, "all": None}[dim]
        want = np.sum(prod, axis=axis, keepdims=axis is not None) \
            .reshape(want_shape)
        tol = dict(atol=5e-4, rtol=1e-4) if dtype_s == "float32" \
            else dict(atol=1e-9, rtol=1e-9)
        np.testing.assert_allclose(np.asarray(interp, np.float64), want,
                                   **tol)
        np.testing.assert_allclose(np.asarray(dense, np.float64), want,
                                   **tol)


def test_sddmm_agg_dead_blocks_do_not_leak(rng):
    """Block-structured sparsity: rows of sp that live only in dead
    blocks contribute exactly zero, and the masked pallas body (which
    never touches those blocks) agrees with the oracle bit-for-bit in
    shape and to tolerance in value."""
    m, k, n, bs = 32, 5, 32, 8
    sp = np.zeros((m, n), np.float32)
    sp[:8, :16] = rng.normal(size=(8, 16))   # two live blocks, fourteen dead
    sp = jnp.asarray(sp)
    w = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    h = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    mask = compute_block_mask(sp, bs)
    assert int(np.asarray(mask).sum()) == 2
    out = registry.dispatch("sddmm_agg", sp, w, h, mask,
                            backend=registry.INTERPRET, dim="row",
                            block_size=bs)
    ref = registry.dispatch("sddmm_agg", sp, w, h, mask,
                            backend=registry.DENSE, dim="row", block_size=bs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)
    assert not np.asarray(out)[8:].any()  # dead rows are exactly zero


def _paneled_case(rng, m=48, k=80, n=40, bs=16):
    """K = 80 splits into five panels of 16; about half of W's and H's
    blocks are dead, and a quarter of sp's."""
    def blocky(shape, keep, density=1.0):
        x = np.where(rng.uniform(size=shape) < density,
                     rng.normal(size=shape), 0.0)
        gr, gc = -(-shape[0] // bs), -(-shape[1] // bs)
        live = rng.uniform(size=(gr, gc)) < keep
        live[0, 0] = True
        return (x * np.kron(live, np.ones((bs, bs)))[:shape[0], :shape[1]]
                ).astype(np.float32)
    sp, w, h = blocky((m, n), 0.75, 0.3), blocky((m, k), 0.5), \
        blocky((k, n), 0.5)
    return [jnp.asarray(v) for v in (sp, w, h)], bs


@pytest.mark.parametrize("dim", ["row", "col", "all"])
@pytest.mark.parametrize("chunk", [None, 7])
def test_sddmm_agg_skips_dead_k_panels(rng, dim, chunk, monkeypatch):
    """K wider than a panel: the kernel walks only the (I, J, K) panel
    products whose three blocks are live, in chunks of steps where the
    list is long, and equals the plain product whether or not it is
    handed W's and H's masks."""
    import repro.kernels.sddmm_agg as sddmm
    if chunk is not None:
        monkeypatch.setattr(sddmm, "CHUNK_STEPS", chunk)
    (sp, w, h), bs = _paneled_case(rng)
    ms, mw, mh = (np.asarray(compute_block_mask(v, bs)) for v in (sp, w, h))
    computed, skipped = sddmm.panel_counts(ms, mw, mh)
    assert computed > 0 and skipped > 0        # dead panels exist
    assert len(sddmm.panel_steps(ms, mw, mh, dim)[0]) == computed
    prod = np.asarray(sp, np.float64) * (np.asarray(w, np.float64)
                                         @ np.asarray(h, np.float64))
    axis = {"row": 1, "col": 0, "all": None}[dim]
    want = np.sum(prod, axis=axis, keepdims=True).reshape(
        {"row": (48, 1), "col": (1, 40), "all": (1, 1)}[dim])
    for masks in ({"w_mask": mw, "h_mask": mh}, {}):
        got = registry.dispatch("sddmm_agg", sp, w, h, ms,
                                backend=registry.INTERPRET, dim=dim,
                                block_size=bs, **masks)
        # float32 sums of up to 80-term products: rounding only
        np.testing.assert_allclose(np.asarray(got, np.float64), want,
                                   atol=5e-4, rtol=1e-4)


def test_panel_steps_order_and_count():
    live = np.array([[1, 0], [1, 1]], bool)
    i, j, k = sddmm_panel_steps(live, live, live, "row")
    # (I, J, K) with live(I, J), live(I, K), live(K, J), sorted by I, J, K
    assert list(zip(i, j, k)) == [(0, 0, 0), (1, 0, 0), (1, 0, 1),
                                  (1, 1, 1)]
    i, j, k = sddmm_panel_steps(live, live, live, "col")
    assert list(zip(j, i, k)) == [(0, 0, 0), (0, 1, 0), (0, 1, 1),
                                  (1, 1, 1)]


# ---------------------------------------------------------------------------
# MASKED_AGG plan fusion: Σ(A ∘ (W×H)) never materializes the product.
# ---------------------------------------------------------------------------

def _masked_agg_expr(fn=AggFn.SUM, dim=AggDim.ROW, order="sp-first"):
    a = Leaf("A", (32, 32), 0.1)
    w, h = Leaf("W", (32, 4), 1.0), Leaf("H", (4, 32), 1.0)
    mm = MatMul(w, h)
    ew = ElemWise(a, mm, EWOp.MUL) if order == "sp-first" \
        else ElemWise(mm, a, EWOp.MUL)
    return Agg(ew, fn, dim)


@pytest.mark.parametrize("dim", [AggDim.ROW, AggDim.COL, AggDim.ALL])
@pytest.mark.parametrize("order", ["sp-first", "mm-first"])
def test_masked_agg_fused_at_plan_time(dim, order):
    plan = build_plan(_masked_agg_expr(dim=dim, order=order), mode="sparse",
                      kernel_backend="dense")
    root = plan.node(plan.root)
    assert root.kind == P.MASKED_AGG
    assert root.kernel == "sddmm_agg"
    assert root.backend == "dense"
    assert len(root.children) == 3    # sparse gate + both matmul factors
    assert plan.count(P.MATMUL) == 0          # no W×H product node
    assert plan.count(P.MASKED_ELEMWISE) == 0  # no orphan SDDMM node either


def test_masked_agg_fusion_gates():
    # non-SUM aggregations do not factorize → plain SDDMM + AGG
    p = build_plan(_masked_agg_expr(fn=AggFn.MAX), mode="sparse")
    assert p.count(P.MASKED_AGG) == 0
    assert p.count(P.MASKED_ELEMWISE) == 1
    # dense tier keeps the full elemwise + matmul shape
    d = build_plan(_masked_agg_expr(), mode="dense")
    assert d.count(P.MASKED_AGG) == 0
    assert d.count(P.MATMUL) == 1
    # a dense gate (sparsity above the mask-pattern cutoff) never fuses
    dense_gate = Agg(ElemWise(Leaf("A", (32, 32), 0.9),
                              MatMul(Leaf("W", (32, 4), 1.0),
                                     Leaf("H", (4, 32), 1.0)), EWOp.MUL),
                     AggFn.SUM, AggDim.ROW)
    g = build_plan(dense_gate, mode="sparse")
    assert g.count(P.MASKED_AGG) == 0


def _blocky(rng, n, bs):
    """Sparse data with genuinely dead blocks, so the annotated mask has
    skips (uniform sparsity at small block sizes leaves every block live
    and the demotion heuristic kicks in instead)."""
    sp = np.zeros((n, n), np.float32)
    sp[:n // 2, :n // 2] = np.where(
        rng.uniform(size=(n // 2, n // 2)) < 0.3,
        rng.normal(size=(n // 2, n // 2)), 0.0)
    assert not np.asarray(compute_block_mask(jnp.asarray(sp), bs)).all()
    return sp.astype(np.float32)


def test_masked_agg_end_to_end_matches_oracle(rng):
    """Session → plan → staged executor: the fused path (and its
    pallas-interpret twin) equals the plain NumPy Σ(A ∘ (W×H))."""
    from repro.core.executor import Executor
    n, bs = 32, 8
    sp = _blocky(rng, n, bs)
    w = rng.normal(size=(n, 6)).astype(np.float32)
    h = rng.normal(size=(6, n)).astype(np.float32)
    s = Session(block_size=bs)
    A, W, H = s.load(sp, "A"), s.load(w, "W"), s.load(h, "H")
    from repro.plan import PlanExecutor
    for dim, axis in (("r", 1), ("c", 0), ("a", None)):
        q = A.emul(W.multiply(H)).sum(dim)
        want = np.sum(sp * (w @ h), axis=axis,
                      keepdims=axis is not None)
        pplan = s.physical_plan(s._optimized(q.plan))
        pex = PlanExecutor(s.env)
        out = pex.run(pplan)
        assert pex.stats["masked_aggs"] == 1, dim  # the fused node ran
        np.testing.assert_allclose(
            np.asarray(out.value).reshape(want.shape), want,
            atol=1e-3, rtol=1e-3, err_msg=f"dim={dim}")
        # eager tree-walk parity, dense vs interpret pinned backends (the
        # tree walk sees the logical Agg∘ElemWise, i.e. the unfused SDDMM)
        outs = {}
        for backend in (registry.DENSE, registry.INTERPRET):
            ex = Executor(s.env, mode="sparse", block_size=bs,
                          kernel_backend=backend)
            outs[backend] = np.asarray(ex.run(q.plan).value)
            assert ex.stats["masked_matmuls"] == 1
        np.testing.assert_allclose(outs[registry.DENSE],
                                   outs[registry.INTERPRET], atol=1e-4)


def test_masked_agg_demotes_on_dense_masks(rng):
    """Uniform sparsity leaves every block live: annotation flips the
    fused node to the staged dense formula (demote_dense), and the
    answer still matches the oracle."""
    n, bs = 32, 8
    sp = np.where(rng.uniform(size=(n, n)) < 0.08,
                  rng.normal(size=(n, n)), 0.0).astype(np.float32)
    w = rng.normal(size=(n, 6)).astype(np.float32)
    h = rng.normal(size=(6, n)).astype(np.float32)
    s = Session(block_size=bs)
    A, W, H = s.load(sp, "A"), s.load(w, "W"), s.load(h, "H")
    q = A.emul(W.multiply(H)).sum("r")
    pplan = s.physical_plan(s._optimized(q.plan))
    masksmod.annotate(pplan, s.env)
    fused = [pplan.node(i) for i in range(pplan.n_nodes)
             if pplan.node(i).kind == P.MASKED_AGG]
    assert fused and all(nd.meta.get("demote_dense") for nd in fused)
    out = q.collect()
    want = np.sum(sp * (w @ h), axis=1, keepdims=True)
    np.testing.assert_allclose(np.asarray(out.value), want,
                               atol=1e-3, rtol=1e-3)

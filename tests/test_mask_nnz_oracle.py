"""The mask pass's per-node results against a reference that recounts
every leaf on the host.

The mask pass reads a leaf's nnz from its binding (``nnz_count``,
counted once on the device). The reference here counts the leaf's host
view with ``np.count_nonzero`` on every read, as the pass once did. On
random sparse catalogs both must give every node the same ``nnz_bound``,
``cap``, ``cap_sides``, ``demote_dense`` and V2V strategy — for leaves
loaded with a wrong ``sparsity``, for a leaf rebound to a value with
another count, and for COO joins.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import MergeFn, Session
from repro.core import cost as costmod
from repro.obs.metrics import REGISTRY
from repro.plan import build_plan
from repro.plan import masks as masksmod
from repro.plan import ops as P

BS = 8
MUL = MergeFn("oracle_mul", lambda x, y: x * y)
FIELDS = ("nnz_bound", "cap", "cap_sides", "demote_dense")


class _Recount(masksmod._Leaves):
    """Leaves whose nnz is a fresh host count of the bound value."""

    def nnz(self, node):
        name = node.expr.name
        if name in self.env:
            return int(np.count_nonzero(np.asarray(self.env[name].value)))
        return super().nnz(node)


def _sparse(rng, shape, density):
    v = rng.normal(size=shape).astype(np.float32)
    return np.where(rng.random(shape) < density, v, 0.0).astype(np.float32)


def _results(q, leaves_type):
    """Per-node mask-pass results of a fresh plan of ``q``."""
    s = q.session
    plan = build_plan(s._optimized(q.plan), mode=s.mode,
                      block_size=s.block_size, n_workers=1)
    masksmod.annotate(plan, s.env, leaves_type(s.env, s.block_size))
    out = []
    for node in plan.nodes:
        row = {f: node.meta.get(f) for f in FIELDS}
        if node.kind == P.JOIN:
            row["strategy"] = node.strategy
        out.append((node.kind, row))
    return out


def _assert_same_as_recount(q):
    got = _results(q, masksmod._Leaves)
    want = _results(q, _Recount)
    assert got == want
    return got


def _queries(m):
    """PNMF's SDDMM numerator, a masked aggregate, an overlay, and COO
    joins over leaves (exact capacities) and over derived inputs."""
    num = m["A"].ediv(m["W"].multiply(m["H"])).multiply(m["H"].t())
    return {
        "numerator": num,
        "masked_sum": m["A"].emul(m["W"].multiply(m["H"])).sum("a"),
        "overlay": m["A"].join(m["B"], "RID=RID AND CID=CID", MUL),
        "d2d": m["A"].join(m["B"], "RID=RID", MUL),
        "v2v": m["A"].join(m["B"], "VAL=VAL", MUL),
        "v2v_derived": m["A"].emul(m["B"]).join(m["B"], "VAL=VAL", MUL),
        "d2v": m["A"].t().join(m["B"], "RID=VAL", MUL),
    }


def _catalog(seed, given_sparsity=None):
    rng = np.random.default_rng(seed)
    s = Session(block_size=BS, mode="sparse", n_workers=1)
    users, movies, rank = 36 + seed % 5, 29 + seed % 3, 6
    values = {
        "A": _sparse(rng, (users, movies), 0.1 + 0.05 * (seed % 4)),
        "B": _sparse(rng, (users, movies), 0.3),
        "W": rng.uniform(0.5, 1.5, (users, rank)).astype(np.float32),
        "H": rng.uniform(0.5, 1.5, (rank, movies)).astype(np.float32),
    }
    m = {n: s.load(v, n, sparsity=given_sparsity) for n, v in values.items()}
    return s, m, rng


QUERIES = ["numerator", "masked_sum", "overlay", "d2d", "v2v",
           "v2v_derived", "d2v"]


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("query", QUERIES)
def test_binding_count_matches_recount(seed, query):
    _, m, _ = _catalog(seed)
    rows = _assert_same_as_recount(_queries(m)[query])
    assert any(row["nnz_bound"] is not None for _, row in rows)


@pytest.mark.parametrize("query", QUERIES)
def test_wrong_given_sparsity_does_not_reach_the_counts(query):
    """Loaded with a sparsity far from the truth: the load counts
    nothing, the mask pass counts each leaf on the device once and
    keeps it, and every result still equals the recount."""
    s, m, _ = _catalog(3, given_sparsity=0.97)
    assert all(bm._nnz is None for bm in s.env.values())
    device = REGISTRY.counter("mask_leaf_nnz", source="device")
    before = device.value
    _assert_same_as_recount(_queries(m)[query])
    counted = device.value - before
    assert 0 < counted <= len(s.env)
    assert all(bm._nnz == np.count_nonzero(np.asarray(bm.value))
               for bm in s.env.values() if bm._nnz is not None)
    again = device.value
    _assert_same_as_recount(_queries(m)[query])
    assert device.value == again


@pytest.mark.parametrize("query", QUERIES)
def test_rebound_leaf_is_counted_anew(query):
    s, m, rng = _catalog(4)
    first = _assert_same_as_recount(_queries(m)[query])
    shape = s.env["A"].shape
    old = s.env["A"].nnz_count()
    m["A"] = s.load(_sparse(rng, shape, 0.6), "A")
    assert s.env["A"].nnz_count() != old
    second = _assert_same_as_recount(_queries(m)[query])
    if query in ("numerator", "d2d", "v2v", "v2v_derived", "d2v"):
        assert first != second


def test_v2v_strategy_follows_the_exact_count_not_the_given_sparsity():
    """A Bloom filter is chosen for dense-looking value joins. Loaded as
    dense but nearly empty, the builder picks it from the given
    sparsity; the mask pass re-gates the join to plain sortmerge from
    the exact counts, as the recount does."""
    rng = np.random.default_rng(5)
    s = Session(block_size=BS, mode="sparse", n_workers=1)
    n = 512
    a = s.load(_sparse(rng, (n, n), 0.001), "A", sparsity=1.0)
    b = s.load(_sparse(rng, (n, n), 0.001), "B", sparsity=1.0)
    q = a.join(b, "VAL=VAL", MUL)
    plan = build_plan(s._optimized(q.plan), mode="sparse", block_size=BS,
                      n_workers=1)
    join, = [nd for nd in plan.nodes if nd.kind == P.JOIN]
    assert join.strategy == costmod.BLOOM_SORTMERGE
    rows = _assert_same_as_recount(q)
    (_, row), = [r for r in rows if r[0] == P.JOIN]
    assert row["strategy"] == costmod.SORTMERGE

"""Triangle counting as matrix joins: ``Γsum(A ∘ (A×A))`` over seeded
Graph500 Kronecker graphs, through ``Session.collect``, against a plain
NumPy reference, on both arms of the ``MASKED_AGG`` gate.

Degree-ordered labels pack the edges into the leading blocks, so fewer
than half of A's blocks are live and the fused node runs the paneled
``sddmm_agg`` kernel, skipping dead K panels; the specification's random
labels leave nearly every block live, so the node is demoted to XLA.

Every answer is compared exactly: A holds 0/1, so A×A, A ∘ (A×A) and
their sums are integers, all below 2**24 at these sizes, which float32
holds exactly whatever the order of the additions.
"""
import numpy as np
import pytest

from repro.core import Session
from repro.kernels import registry
from repro.kernels.sddmm_agg import panel_counts
from repro.obs.metrics import REGISTRY
from repro.obs.trace import TRACER

SCALE, BS = 9, 16      # 512 vertices, 32 x 32 blocks: 32 K panels


def kronecker(scale: int, seed: int, degree_order: bool,
              edgefactor: int = 16) -> np.ndarray:
    """The Graph500 Kronecker graph (initiator 0.57/0.19/0.19/0.05) as a
    symmetric 0/1 float32 matrix without self-loops; with
    ``degree_order`` relabelled by descending degree, ties broken by the
    specification's random labels."""
    rng = np.random.default_rng(seed)
    n, m = 1 << scale, edgefactor << scale
    a, b, c = 0.57, 0.19, 0.19
    i = np.zeros(m, np.int64)
    j = np.zeros(m, np.int64)
    for bit in range(scale):
        i_bit = rng.uniform(size=m) > a + b
        j_bit = rng.uniform(size=m) > np.where(i_bit, c / (1 - a - b),
                                               a / (a + b))
        i += i_bit << bit
        j += j_bit << bit
    label = rng.permutation(n)
    i, j = label[i], label[j]
    adj = np.zeros((n, n), np.float32)
    adj[i, j] = adj[j, i] = 1.0
    np.fill_diagonal(adj, 0.0)
    if degree_order:
        order = np.lexsort((label, -adj.sum(axis=1)))
        adj = adj[order][:, order]
    return adj


def triangle_aggregates(adj: np.ndarray) -> dict:
    """Σ, row sums and column sums of A ∘ (A×A), in int64: the plain
    reference. Σ/6 is the triangle count, a row sum /2 the triangles at
    that vertex."""
    a = adj.astype(np.int64)
    closed = a * (a @ a)
    return {"a": closed.sum().reshape(1, 1),
            "r": closed.sum(axis=1, keepdims=True),
            "c": closed.sum(axis=0, keepdims=True)}


def block_mask(adj: np.ndarray, bs: int) -> np.ndarray:
    n = adj.shape[0] // bs
    return adj.reshape(n, bs, n, bs).any(axis=(1, 3))


@pytest.fixture(scope="module")
def graphs():
    return {"kernel": kronecker(SCALE, 0, degree_order=True),
            "demoted": kronecker(SCALE, 0, degree_order=False)}


def test_reference_counts_known_graphs():
    # K4: 4 triangles, 3 at each vertex
    k4 = np.ones((4, 4), np.float32) - np.eye(4, dtype=np.float32)
    agg = triangle_aggregates(k4)
    assert agg["a"][0, 0] // 6 == 4
    assert (agg["r"][:, 0] // 2 == 3).all()
    assert (agg["c"] == agg["r"].T).all()
    # a 4-cycle has none
    c4 = np.zeros((4, 4), np.float32)
    for v in range(4):
        c4[v, (v + 1) % 4] = c4[(v + 1) % 4, v] = 1.0
    assert triangle_aggregates(c4)["a"][0, 0] == 0


def test_generator_makes_the_graph_the_arms_need(graphs):
    for arm, adj in graphs.items():
        assert (adj == adj.T).all() and not np.diag(adj).any()
        assert set(np.unique(adj)) == {0.0, 1.0}
    deg = graphs["kernel"].sum(axis=1)
    assert (np.diff(deg) <= 0).all()
    # the gate: at most half the blocks live takes the kernel arm
    assert block_mask(graphs["kernel"], BS).mean() <= 0.5
    assert block_mask(graphs["demoted"], BS).mean() > 0.5
    # relabelling changes no count
    want = triangle_aggregates(graphs["kernel"])["a"]
    assert (triangle_aggregates(graphs["demoted"])["a"] == want).all()


def _collect(adj, dim, trace=None):
    s = Session(block_size=BS)
    a = s.load(adj, "A")
    q = a.emul(a.multiply(a)).sum(dim)
    with TRACER.activate(trace):
        return np.asarray(q.collect().value)


@pytest.mark.parametrize("dim", ["a", "r", "c"])
@pytest.mark.parametrize("arm", ["kernel", "demoted"])
def test_collect_matches_reference(graphs, arm, dim, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", registry.INTERPRET)
    adj = graphs[arm]
    trace = TRACER.start("q", sample=True)
    got = _collect(adj, dim, trace)
    trace.finish()
    want = triangle_aggregates(adj)[dim]
    assert got.shape == want.shape
    np.testing.assert_array_equal(got.astype(np.int64), want)
    executes = [sp for sp in trace.root.walk() if sp.name == "execute"]
    assert [sp.attrs["masked_agg"] for sp in executes] == [arm]


def test_execute_span_names_the_kernel_and_the_arm(graphs, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", registry.INTERPRET)
    for arm, kernels in (("kernel", "sddmm_agg"), ("demoted", None)):
        s = Session(block_size=BS)
        a = s.load(graphs[arm], "A")
        q = a.emul(a.multiply(a)).sum("r")
        trace = TRACER.start("q", sample=True)
        with TRACER.activate(trace):
            q.collect()        # stages: the execute span lies under it
            q.collect()        # the cached program
        trace.finish()
        cold, warm = [sp for sp in trace.root.walk()
                      if sp.name == "execute"]
        assert cold.attrs["cold"] and not warm.attrs["cold"]
        for sp in (cold, warm):
            assert sp.attrs["masked_agg"] == arm
            assert sp.attrs.get("kernels") == kernels


def test_execute_span_names_the_masked_product_kernel(graphs, monkeypatch):
    # A ∘ (A×A) unaggregated: a MASKED_ELEMWISE node, no MASKED_AGG arm
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", registry.INTERPRET)
    s = Session(block_size=BS)
    a = s.load(graphs["kernel"], "A")
    trace = TRACER.start("q", sample=True)
    with TRACER.activate(trace):
        got = np.asarray(a.emul(a.multiply(a)).collect().value)
    trace.finish()
    adj = graphs["kernel"].astype(np.int64)
    np.testing.assert_array_equal(got.astype(np.int64), adj * (adj @ adj))
    (sp,) = [sp for sp in trace.root.walk() if sp.name == "execute"]
    assert sp.attrs.get("kernels") == "masked_matmul"
    assert "masked_agg" not in sp.attrs


def _panels():
    snap = REGISTRY.snapshot()
    return tuple(int(snap.get(f"sddmm_agg_panels{{state={state}}}", 0))
                 for state in ("computed", "skipped"))


def test_panel_counter_matches_the_masks(graphs):
    adj = graphs["kernel"]
    m = block_mask(adj, BS).astype(np.int64)
    computed = int((m * (m @ m)).sum())      # live (I, K, J) triples
    total = m.shape[0] ** 3
    assert panel_counts(m, m, m) == (computed, total - computed)
    assert 0 < computed < total
    before = _panels()
    _collect(adj, "a")
    after = _panels()
    assert (after[0] - before[0], after[1] - before[1]) == \
        (computed, total - computed)
    # the demoted arm runs no kernel and counts no panel
    _collect(graphs["demoted"], "a")
    assert _panels() == after

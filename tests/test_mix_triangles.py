"""The ``triangles`` mix's query forms through ``Session``, against a plain
NumPy reference.

The forms are those of ``bench/traffic/triangles.json``, over a graph's
0/1 adjacency A: T = Σ(A∘(A×A)), t = Γsum,r(A∘(A×A)) and d = Γnnz,r A.
Each runs on the engine's three ways of running a plan, over the two
Graph500 Kronecker graphs of ``test_triangles.py``: the specification's
random labels leave nearly every block live (the ``MASKED_AGG`` node's
demoted arm), degree-ordered labels fewer than half (its kernel arm, as
in the cell). T and t under the pallas-interpret backend are
``test_triangles.py::test_collect_matches_reference``'s cases, so they
are not repeated here. Every answer is an integer below 2**24, so each
is compared exactly.
"""
import numpy as np
import pytest

from repro.core import Session
from tests.conftest import ENGINES, collect
from tests.test_triangles import BS, SCALE, kronecker, triangle_aggregates

# labelling → the arm of the MASKED_AGG gate it takes
GRAPHS = {"random-labels": "demoted", "degree-ordered": "kernel"}


@pytest.fixture(scope="module")
def graphs():
    return {labels: kronecker(SCALE, 0,
                              degree_order=labels == "degree-ordered")
            for labels in GRAPHS}


def _run(adj, query, engine, monkeypatch):
    s = Session(block_size=BS)
    return collect(query(s.load(adj, "A")), engine, monkeypatch)


@pytest.mark.parametrize("engine", [e for e in ENGINES if e != "interpret"])
@pytest.mark.parametrize("labels", list(GRAPHS))
@pytest.mark.parametrize("dim", ["a", "r"], ids=["T", "t"])
def test_triangle_aggregates_match_reference(graphs, dim, labels, engine,
                                             monkeypatch):
    adj = graphs[labels]
    got, arms = _run(adj, lambda A: A.emul(A.multiply(A)).sum(dim), engine,
                     monkeypatch)
    want = triangle_aggregates(adj)[dim]
    assert got.shape == want.shape
    np.testing.assert_array_equal(got, want)
    if engine != "tree":
        assert arms == [GRAPHS[labels]]


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("labels", list(GRAPHS))
def test_degrees_match_reference(graphs, labels, engine, monkeypatch):
    adj = graphs[labels]
    got, _ = _run(adj, lambda A: A.nnz("r"), engine, monkeypatch)
    want = (adj != 0).sum(axis=1, keepdims=True)
    assert got.shape == want.shape
    np.testing.assert_array_equal(got, want)

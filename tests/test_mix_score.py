"""The ``score`` mix's query forms through ``Session``, against a plain
NumPy float64 reference.

The forms are those of ``bench/traffic/score.json``, over ratings A and
fixed factors W and H: S = Σ(W×H), O = Σ(A∘(W×H)),
U = Γsum,r(A∘(W×H)), I = Γsum,c(A∘(W×H)) and P = Γnnz,c A. Each runs on
the engine's three ways of running a plan, and each form that reads A on
both block patterns of A: every block live (the ``MASKED_AGG`` node's
demoted arm, as in the cell) and fewer than half live (its kernel arm).
Where the plan holds a ``MASKED_AGG`` node, the ``execute`` span says
which arm ran.
"""
import numpy as np
import pytest

from repro.core import Session
from tests.conftest import ENGINES, PATTERNS, collect, ratings

M, N, K, BS = 64, 48, 8, 8


def catalog(pattern):
    rng = np.random.default_rng(1)
    a = ratings(pattern, M, N, BS)
    w = rng.uniform(0.5, 1.5, size=(M, K)).astype(np.float32)
    h = rng.uniform(0.5, 1.5, size=(K, N)).astype(np.float32)
    return a, w, h


# name → (the query over Matrix A, W, H; its reference over float64 arrays)
FORMS_OF_A = {
    "O": (lambda A, W, H: A.emul(W.multiply(H)).sum("a"),
          lambda a, w, h: (a * (w @ h)).sum().reshape(1, 1)),
    "U": (lambda A, W, H: A.emul(W.multiply(H)).sum("r"),
          lambda a, w, h: (a * (w @ h)).sum(axis=1, keepdims=True)),
    "I": (lambda A, W, H: A.emul(W.multiply(H)).sum("c"),
          lambda a, w, h: (a * (w @ h)).sum(axis=0, keepdims=True)),
    "P": (lambda A, W, H: A.nnz("c"),
          lambda a, w, h: (a != 0).sum(axis=0, keepdims=True)),
}
FORMS_OF_FACTORS = {
    "S": (lambda A, W, H: W.multiply(H).sum("a"),
          lambda a, w, h: (w @ h).sum().reshape(1, 1)),
}
COUNTS = {"P"}
MASKED_AGGS = {"O", "U", "I"}


def _run(pattern, form, engine, monkeypatch):
    a, w, h = catalog(pattern)
    s = Session(block_size=BS)
    mats = s.load(a, "A"), s.load(w, "W"), s.load(h, "H")
    query, reference = form
    got, arms = collect(query(*mats), engine, monkeypatch)
    want = reference(*(x.astype(np.float64) for x in (a, w, h)))
    return got, want, arms


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("pattern", list(PATTERNS))
@pytest.mark.parametrize("name", list(FORMS_OF_A))
def test_forms_of_a_match_reference(name, pattern, engine, monkeypatch):
    got, want, arms = _run(pattern, FORMS_OF_A[name], engine, monkeypatch)
    assert got.shape == want.shape
    if name in COUNTS:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5)
    if name in MASKED_AGGS and engine != "tree":
        assert arms == [PATTERNS[pattern]]


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("name", list(FORMS_OF_FACTORS))
def test_forms_of_factors_match_reference(name, engine, monkeypatch):
    got, want, _ = _run("all-live", FORMS_OF_FACTORS[name], engine,
                        monkeypatch)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5)

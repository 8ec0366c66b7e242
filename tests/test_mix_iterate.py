"""The ``iterate`` mix's query forms through ``Session``, against a plain
NumPy float64 reference.

The forms are those of ``bench/traffic/iterate.json``, the paper's PNMF
update over ratings A and factors W and H:
N_W = (A ⊘ (W×H)) × Hᵀ, D_W = Γsum,r H, N_H = Wᵀ × (A ⊘ (W×H)) and
D_H = Γsum,c W. Each runs on the engine's three ways of running a plan,
and N_W and N_H on both block patterns of A (every block live, fewer
than half live). A last case runs two whole PNMF iterations in one
session, rebinding W and H after every update as the cell does, on
shapes that fill their blocks and shapes that do not.
"""
import numpy as np
import pytest

from repro.core import Session
from tests.conftest import ENGINES, PATTERNS, collect, ratings

M, N, K, BS = 64, 48, 8, 8


def catalog(pattern, m=M, n=N):
    rng = np.random.default_rng(2)
    a = ratings(pattern, m, n, BS)
    w = rng.uniform(0.5, 1.5, size=(m, K)).astype(np.float32)
    h = rng.uniform(0.5, 1.5, size=(K, n)).astype(np.float32)
    return a, w, h


def ratio(a, wh):
    """A ⊘ (W×H): zero where either side is zero."""
    return np.divide(a, wh, out=np.zeros_like(a), where=(a != 0) & (wh != 0))


# name → (the query over Matrix A, W, H; its reference over float64 arrays)
FORMS_OF_A = {
    "N_W": (lambda A, W, H: A.ediv(W.multiply(H)).multiply(H.t()),
            lambda a, w, h: ratio(a, w @ h) @ h.T),
    "N_H": (lambda A, W, H: W.t().multiply(A.ediv(W.multiply(H))),
            lambda a, w, h: w.T @ ratio(a, w @ h)),
}
FORMS_OF_FACTORS = {
    "D_W": (lambda A, W, H: H.sum("r"),
            lambda a, w, h: h.sum(axis=1, keepdims=True)),
    "D_H": (lambda A, W, H: W.sum("c"),
            lambda a, w, h: w.sum(axis=0, keepdims=True)),
}


def _check(pattern, form, engine, monkeypatch):
    a, w, h = catalog(pattern)
    s = Session(block_size=BS)
    mats = s.load(a, "A"), s.load(w, "W"), s.load(h, "H")
    query, reference = form
    got, _ = collect(query(*mats), engine, monkeypatch)
    want = reference(*(x.astype(np.float64) for x in (a, w, h)))
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5)


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("pattern", list(PATTERNS))
@pytest.mark.parametrize("name", list(FORMS_OF_A))
def test_forms_of_a_match_reference(name, pattern, engine, monkeypatch):
    _check(pattern, FORMS_OF_A[name], engine, monkeypatch)


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("name", list(FORMS_OF_FACTORS))
def test_forms_of_factors_match_reference(name, engine, monkeypatch):
    _check("all-live", FORMS_OF_FACTORS[name], engine, monkeypatch)


def pnmf_reference(a, w, h, iterations):
    """The paper's PNMF multiplicative updates in float64."""
    a, w, h = (x.astype(np.float64) for x in (a, w, h))
    for _ in range(iterations):
        w = w * (ratio(a, w @ h) @ h.T) / h.sum(axis=1)[None, :]
        h = h * (w.T @ ratio(a, w @ h)) / w.sum(axis=0)[:, None]
    return w, h


@pytest.mark.parametrize("m, n", [(64, 48), (60, 45)],
                         ids=["whole-blocks", "ragged"])
@pytest.mark.parametrize("pattern", list(PATTERNS))
def test_pnmf_rebinds_match_reference(pattern, m, n):
    a, w0, h0 = catalog(pattern, m, n)
    w, h = w0, h0
    s = Session(block_size=BS)
    A, W, H = s.load(a, "A"), s.load(w, "W"), s.load(h, "H")
    for _ in range(2):
        num = np.asarray(A.ediv(W.multiply(H)).multiply(H.t()).collect().value)
        den = np.asarray(H.sum("r").collect().value)
        w = w * num / den.reshape(1, -1)
        W = s.load(w, "W")
        num = np.asarray(W.t().multiply(A.ediv(W.multiply(H))).collect().value)
        den = np.asarray(W.sum("c").collect().value)
        h = h * num / den.reshape(-1, 1)
        H = s.load(h, "H")
    w_ref, h_ref = pnmf_reference(a, w0, h0, 2)
    np.testing.assert_allclose(w, w_ref, rtol=1e-5)
    np.testing.assert_allclose(h, h_ref, rtol=1e-5)

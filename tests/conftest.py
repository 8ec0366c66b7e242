import os
import sys

# the package lives under src/, and test modules import it without an install
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def sparse(rng, m, n, density, round_vals=False):
    v = rng.normal(size=(m, n)).astype(np.float32)
    keep = rng.uniform(size=(m, n)) < density
    out = np.where(keep, v, 0).astype(np.float32)
    if round_vals:
        out = np.round(out, 1)
    return out


# -- the cells' query forms (tests/test_mix_*.py) -----------------------------

# The engine's three ways of running a plan: the staged DAG under the dense
# or the pallas-interpret kernel backend, and the tree walk.
ENGINES = ("dense", "interpret", "tree")

# Two block patterns of a matrix that gates a MASKED_AGG node: every block
# live takes the demoted arm, fewer than half live the kernel arm.
PATTERNS = {"all-live": "demoted", "block-sparse": "kernel"}


def ratings(pattern, m, n, bs, seed=0):
    """An m × n matrix of 1–5 star ratings, float32, zero where unrated.

    ``all-live``: a quarter of the entries rated, and one at the corner of
    every bs-block, so that no block is dead. ``block-sparse``: a third of
    the blocks live, half their entries rated, the rest of the matrix
    empty (whole rows and columns among it)."""
    rng = np.random.default_rng(seed)
    stars = rng.integers(1, 6, size=(m, n)).astype(np.float32)
    gm, gn = -(-m // bs), -(-n // bs)
    if pattern == "all-live":
        live = np.ones((gm, gn), bool)
        keep = rng.uniform(size=(m, n)) < 0.25
    else:
        live = np.zeros(gm * gn, bool)
        live[rng.choice(gm * gn, size=gm * gn // 3, replace=False)] = True
        live = live.reshape(gm, gn)
        keep = (np.kron(live, np.ones((bs, bs), bool))[:m, :n]
                & (rng.uniform(size=(m, n)) < 0.5))
    keep[::bs, ::bs] |= live
    return np.where(keep, stars, 0).astype(np.float32)


def collect(q, engine, monkeypatch):
    """Collects the query ``q`` one of the engine's ways (``ENGINES``)
    under a sampled trace. Returns the answer as float64 and the
    ``masked_agg`` attribute of each ``execute`` span (None where a span
    has none)."""
    from repro.kernels import registry
    from repro.obs.trace import TRACER

    kwargs = {}
    if engine == "tree":
        monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
        kwargs["engine"] = "tree"
    else:
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", {
            "dense": registry.DENSE, "interpret": registry.INTERPRET}[engine])
    trace = TRACER.start("q", sample=True)
    with TRACER.activate(trace):
        out = q.collect(**kwargs)
    trace.finish()
    arms = [sp.attrs.get("masked_agg") for sp in trace.root.walk()
            if sp.name == "execute"]
    return np.asarray(out.value, np.float64), arms

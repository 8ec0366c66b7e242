"""End-to-end behaviour tests for the whole system.

1. The paper's PNMF pipeline (Table 6): sparsity-aware execution equals the
   dense pipeline, and the multiplicative updates decrease the objective.
2. The quickstart example runs.
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_pnmf_sparse_equals_dense(rng):
    sys.path.insert(0, ROOT)
    from benchmarks.bench_pnmf import BS, pnmf_naive_step, pnmf_opt_step
    from repro.core.matrix import compute_block_mask
    from tests.conftest import sparse
    n, k = 512, 8
    a = np.abs(sparse(rng, n, n, 5e-3))
    aj = jnp.asarray(a)
    mask = compute_block_mask(aj, BS)
    w = jnp.asarray(np.abs(rng.normal(size=(n, k))).astype(np.float32))
    h = jnp.asarray(np.abs(rng.normal(size=(k, n))).astype(np.float32))
    e = jnp.ones((n, n), jnp.float32)
    w1, h1 = pnmf_opt_step(aj, mask, w, h)
    w2, h2 = pnmf_naive_step(aj, w, h, e)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), rtol=1e-3,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-3,
                               atol=1e-4)


def test_pnmf_objective_decreases(rng):
    sys.path.insert(0, ROOT)
    from benchmarks.bench_pnmf import BS, objective, pnmf_opt_step
    from repro.core.matrix import compute_block_mask
    from tests.conftest import sparse
    n, k = 512, 8
    a = np.abs(sparse(rng, n, n, 5e-3))
    aj = jnp.asarray(a)
    mask = compute_block_mask(aj, BS)
    w = jnp.asarray(np.abs(rng.normal(size=(n, k))).astype(np.float32))
    h = jnp.asarray(np.abs(rng.normal(size=(k, n))).astype(np.float32))
    f0 = float(objective(aj, mask, w, h))
    for _ in range(4):
        w, h = pnmf_opt_step(aj, mask, w, h)
    assert float(objective(aj, mask, w, h)) < f0


@pytest.mark.slow
def test_quickstart_example():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(ROOT, "src") + os.pathsep + ROOT)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", "quickstart.py")],
        env=env, capture_output=True, text=True, timeout=500, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-1500:]
    assert "rows≠NULL" in out.stdout

"""Plan-wide SPMD execution: DAG-SPMD ≡ tree oracle on a worker mesh.

Two layers:

* a subprocess check that forces 8 virtual host devices via ``XLA_FLAGS``
  and runs the randomized equivalence property — executes even when this
  pytest process sees a single device (tier-1);
* in-process tests that run when the interpreter already has ≥2 devices
  (the CI multi-device tier), covering the staged-SPMD path, the session
  mesh lifecycle and the sparse-tier eager fallback.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

multi_device = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs >=8 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=8); covered by the subprocess check otherwise")


def test_spmd_equivalence_subprocess():
    """The 8-worker property must hold regardless of this process's
    topology: force host devices in a child interpreter."""
    env = dict(
        os.environ,
        PYTHONPATH=os.path.join(ROOT, "src"),
        XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                   + " --xla_force_host_platform_device_count=8").strip(),
    )
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "spmd_check.py"), "4"],
        env=env, capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK staged_spmd=" in out.stdout
    n = int(out.stdout.strip().rsplit("=", 1)[1])
    assert n > 0, "SPMD staged path never ran"


@multi_device
def test_spmd_equivalence_inprocess():
    from tests.spmd_check import run_check
    assert run_check(n_seeds=4, n_workers=8) > 0


@multi_device
def test_session_mesh_owned_and_cached():
    from repro.core import Session
    s = Session(mode="dense", n_workers=8)
    m1 = s.mesh
    assert m1 is s.mesh, "mesh must be built once per session"
    from repro.core.partitioner import mesh_workers
    assert mesh_workers(m1) == 8
    # plan cache keys on the mesh: two sessions with different worker
    # counts must not share staged programs
    s2 = Session(mode="dense", n_workers=2)
    assert s2._mesh_key() != s._mesh_key()


@multi_device
def test_spmd_staged_once_then_cached():
    from repro.core import Session
    from repro.core.api import Matrix
    from repro.core.expr import Leaf

    rng = np.random.default_rng(0)
    s = Session(block_size=8, mode="dense", n_workers=8)
    s.load(rng.normal(size=(24, 16)).astype(np.float32), "X")
    x = Matrix(s, Leaf("X", (24, 16), 1.0))
    q = x.t().multiply(x).add(2.0)
    q.collect()
    q.collect()
    # one SPMD program, built once; the plain path was never needed
    assert [key[0] for key in s._programs.keys()] == ["spmd"]


@multi_device
def test_sparse_tier_stages_spmd_on_mesh():
    """Since the device-resident sparse tier landed, sparse-mode plans no
    longer fall back to eager on a mesh: they stage into one GSPMD program
    like the dense tier (tests/test_sparse_device.py covers the rest)."""
    from repro.core import Session
    from repro.core.api import Matrix
    from repro.core.expr import Leaf
    from repro.plan import PlanExecutor

    rng = np.random.default_rng(1)
    v = np.where(rng.uniform(size=(24, 16)) < 0.3,
                 rng.normal(size=(24, 16)), 0).astype(np.float32)
    s = Session(block_size=8, mode="sparse", n_workers=8)
    s.load(v, "X")
    x = Matrix(s, Leaf("X", (24, 16), 0.3))
    q = x.join(x, "RID=RID AND CID=CID", lambda a, b: a + b)
    ex = PlanExecutor(s.env, mesh=s.mesh)
    out = ex.run(s.physical_plan(s._optimized(q.plan)))
    assert ex.stats["staged_sparse_spmd"] == 1
    want = s.execute(q.optimized_plan().plan, optimize=False, engine="tree")
    np.testing.assert_allclose(np.asarray(out.value),
                               np.asarray(want.value), atol=1e-4)

    # non-jit-safe sparse plans (value-predicate selects) still run eagerly
    q2 = x.select("VAL>0").join(x, "RID=RID AND CID=CID",
                                lambda a, b: a + b)
    ex2 = PlanExecutor(s.env, mesh=s.mesh)
    ex2.run(s.physical_plan(s._optimized(q2.plan)))
    assert ex2.stats["staged_sparse_spmd"] == 0
    assert ex2.stats["node_evals"] > 0


@multi_device
def test_explain_measured_comm_on_mesh():
    from repro.core import Session
    from repro.core.api import Matrix
    from repro.core.expr import Leaf

    rng = np.random.default_rng(2)
    s = Session(block_size=8, mode="dense", n_workers=8)
    s.load(rng.normal(size=(32, 16)).astype(np.float32), "X")
    x = Matrix(s, Leaf("X", (32, 16), 1.0))
    q = x.t().multiply(x)
    out = q.explain(physical=True, measure_comm=True)
    assert "scheme=" in out
    assert "predicted" in out and "measured" in out

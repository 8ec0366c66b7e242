"""Paper Fig. 11a–c: joins on a single dimension (D2D).

(a/b) sparse group-join vs dense straw man for RID=RID and CID=RID;
(c)   cost-model validation: the partitioner's predicted communication is
      compared against XLA-measured collective bytes of a distributed join
      compiled for a described 8-chip TPU v5e slice, in this process.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmarks.common import row, sparse, timeit
from repro.analysis.hlo import parse_hlo_module
from repro.core import cost as costmod
from repro.core.joins import d2d_dense, d2d_sparse
from repro.core.matrix import BlockMatrix
from repro.core.predicates import Field, parse_join
from repro.core.sparsity import product_merge


def _measure_collective_bytes(n_workers: int = 8, m: int = 4096) -> dict:
    """XLA-measured collective bytes of an aligned (r, r) and a
    mispartitioned (r, c) distributed join, compiled for a described
    ``n_workers``-chip TPU v5e slice (nothing runs, so the probe needs no
    chip and takes none from a process that holds one)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name=f"v5e:2x{n_workers // 2}")
    mesh = Mesh(np.array(topo.devices).reshape(n_workers), ("workers",))
    out = {}
    for tag, (spec_a, spec_b) in {
        "rr": (P("workers", None), P("workers", None)),
        "rc": (P("workers", None), P(None, "workers")),
    }.items():
        def join(a, b, spec_a=spec_a):
            b = jax.lax.with_sharding_constraint(
                b, NamedSharding(mesh, spec_a))
            return a * b
        sd_a = jax.ShapeDtypeStruct((m, m), jnp.float32,
                                    sharding=NamedSharding(mesh, spec_a))
        sd_b = jax.ShapeDtypeStruct((m, m), jnp.float32,
                                    sharding=NamedSharding(mesh, spec_b))
        comp = jax.jit(join).lower(sd_a, sd_b).compile()
        out[tag] = parse_hlo_module(comp.as_text()).collective_bytes
    return out


def _kernel_backends(rng) -> None:
    """Overlay-join kernel through the registry, per available backend
    (dense oracle vs pallas-interpret on CPU; pallas-tpu when present)."""
    from repro.kernels import registry
    from repro.kernels.merge_join import MODE_BOTH

    m = n = 512
    bs = 128
    a = jnp.asarray(sparse(rng, m, n, 0.05))
    b = jnp.asarray(sparse(rng, m, n, 0.05))
    ma = BlockMatrix.from_dense(a, bs).block_mask
    mb = BlockMatrix.from_dense(b, bs).block_mask

    def mul(x, y):  # one fn object: merge is a static jit arg — a fresh
        return x * y  # lambda per rep would retrace every timing call

    for backend in registry.available_backends():
        t = timeit(lambda: registry.dispatch(
            "merge_join", a, b, ma, mb, backend=backend,
            merge=mul, mode=MODE_BOTH, block_size=bs),
            repeats=2)
        row(f"fig11_merge_join_kernel_{backend}", t, f"{m}x{n} bs={bs}")


def run(rng) -> None:
    _kernel_backends(rng)
    m = n = 2500
    a = sparse(rng, m, n, 1e-3)
    b = sparse(rng, m, n, 1e-3)
    bma = BlockMatrix.from_dense(jnp.asarray(a), 256)
    bmb = BlockMatrix.from_dense(jnp.asarray(b), 256)
    merge = product_merge()

    # (a) RID_A = RID_B and (b) CID_A = RID_B
    for tag, (lf, rf) in (("rid_rid", (Field.RID, Field.RID)),
                          ("cid_rid", (Field.CID, Field.RID))):
        t_opt = timeit(lambda: d2d_sparse(bma, bmb, lf, rf, merge).val,
                       repeats=2)
        small = 400  # straw man materializes [d1, n, n]; keep it feasible
        t_naive = timeit(
            lambda: d2d_dense(jnp.asarray(a[:small, :small]),
                              jnp.asarray(b[:small, :small]), lf, rf,
                              merge.fn), repeats=2)
        row(f"fig11_{tag}_sparse_full", t_opt,
            f"naive_is_{small}x{small}_submatrix")
        row(f"fig11_{tag}_naive_sub", t_naive,
            f"dense scales as n^3: {m ** 3 / small ** 3:.0f}x more work")

    # (c) shuffle volume: optimizer schemes vs mispartitioned, model + XLA
    pred = parse_join("RID=RID")
    nnz_a, nnz_b = float((a != 0).sum()), float((b != 0).sum())
    n_workers = 8
    best = costmod.assign_schemes(pred, nnz_a, nnz_b, n_workers)
    worst = costmod.join_comm_cost(pred, "r", "c", nnz_a, nnz_b, n_workers)
    row("fig11c_model_entries_opt", None,
        f"predicted={best.comm_cost + best.conversion_cost:.3g} entries "
        f"schemes=({best.scheme_a},{best.scheme_b})")
    row("fig11c_model_entries_rc", None, f"predicted={worst:.3g} entries")

    measured = _measure_collective_bytes(n_workers)
    row("fig11c_xla_bytes_rr", None,
        f"measured={measured['rr']:.3g}B (aligned schemes)")
    row("fig11c_xla_bytes_rc", None,
        f"measured={measured['rc']:.3g}B (mispartitioned)")
    # the cost model's qualitative claim: aligned ≪ mispartitioned
    assert measured["rr"] <= measured["rc"] * 0.2 + 1e3, measured

#!/usr/bin/env python3
"""Smoke run of the relational engine on TPU, checked against plain references.

Drives the main path once, through the entry points a user calls:
``Session.collect()`` (memo optimizer → physical planner → staged executor
→ kernel registry) over a 16384² catalog, then the ``ServeEngine`` serving
path of ``repro.serve.workload`` over a zipf client stream. Every answer is
checked against a plain ``jax.numpy`` / NumPy reference written here,
independent of ``repro``; every fallback, retry, degradation and overflow
counter must stay 0; every kernel with a ``pallas-tpu`` registration must
resolve to it and run.

    python chip_smoke.py             # one chip: query + served phases
    python chip_smoke.py --chips 4   # four chips: the SPMD phase only

The data is generated on the device from ``--seed``. Earlier lines are
per-phase JSON reports; timings there are smoke timings, not benchmark
numbers. The last line is ``{"ok": true, "device": {...}}``. The script
exits non-zero, and prints no such line, when JAX finds no TPU or any
phase fails. ``JAX_COMPILATION_CACHE_DIR`` places the compile cache;
otherwise it is ``.jax_cache/`` at the repo root.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Full-size configuration. The 1%-dense feature matrices are
# block-structured so that every block-gated kernel has work to skip:
# X's live blocks are 40% of the grid (under the 50% SDDMM demotion gate)
# and Y's 70%, which the X ⋈ Y overlay's merge (x + 1)·y keeps live (over
# the 50% block-masked-kernel cutover). The join operands' nnz keeps both
# expected match counts (D2D ≈ 16384·16², V2V ≈ nnz²/20000 ≈ 3.4e6)
# under the device COO cap of 2²³ slots.
N = 16384
BLOCK = 256
FACTOR = 128
DENSITY = 0.01
JOIN_DENSITY = 0.001
SERVE_DIM = 8192
SERVE_CLIENTS = 300
SERVE_THREADS = 2

# counters that must read 0 after every phase
KERNEL_COUNTERS = ("kernel_dispatch_failures", "kernel_dispatch_fallbacks",
                   "kernel_dispatch_quarantined")
EXECUTOR_COUNTERS = ("executor_sparse_fallbacks", "executor_sparse_overflows")
ENGINE_COUNTERS = ("serve_errors", "serve_exec_retries",
                   "serve_degraded_eager", "serve_prewarm_failures",
                   "serve_batch_failures", "serve_worker_crashes")
# the kernels the registry compiles for TPU; each must run in the query phase
TPU_KERNELS = ("masked_matmul", "merge_join", "sddmm_agg")


# ---------------------------------------------------------------------------
# Data, generated on the device from the seed.
# ---------------------------------------------------------------------------

def make_data(n: int, bs: int, seed: int) -> dict:
    """X, Y: block-structured sparse (40% and 70% of the blocks live),
    integer values in ±[1, 8] (so every product and partial sum below
    2²⁴ is exact in f32). W, H: factors with integer values in [-4, 4].
    A, B: uniform sparse join operands whose values are rounded to one
    decimal in [0.1, 2000.0]."""
    import jax
    import jax.numpy as jnp

    g = n // bs
    n_blocks = g * g
    keys = jax.random.split(jax.random.key(seed), 6)
    perm = jax.random.permutation(keys[0], n_blocks)

    def live(lo, hi):
        return jnp.zeros((n_blocks,), bool).at[perm[lo:hi]].set(True) \
            .reshape(g, g)

    @jax.jit
    def blocked(key, live_blocks):
        in_block = DENSITY / jnp.mean(live_blocks)
        kk, kv, ks = jax.random.split(key, 3)
        keep = jax.random.uniform(kk, (n, n)) < in_block
        keep &= jnp.repeat(jnp.repeat(live_blocks, bs, 0), bs, 1)
        mag = jax.random.randint(kv, (n, n), 1, 9).astype(jnp.float32)
        sign = jnp.where(jax.random.bernoulli(ks, 0.5, (n, n)), 1.0, -1.0)
        return jnp.where(keep, mag * sign, 0.0)

    @jax.jit
    def join_operand(key):
        kk, kv = jax.random.split(key)
        keep = jax.random.uniform(kk, (n, n)) < JOIN_DENSITY
        tenths = jax.random.randint(kv, (n, n), 1, 20001)
        return jnp.where(keep, tenths.astype(jnp.float32) / 10.0, 0.0)

    data = {
        "X": blocked(keys[1], live(0, int(0.4 * n_blocks))),
        "Y": blocked(keys[2], live(int(0.3 * n_blocks), n_blocks)),
        "W": jax.random.randint(keys[3], (n, FACTOR), -4, 5)
        .astype(jnp.float32),
        "H": jax.random.randint(keys[4], (FACTOR, n), -4, 5)
        .astype(jnp.float32),
        "A": join_operand(keys[5]),
        "B": join_operand(jax.random.fold_in(keys[5], 1)),
    }
    jax.block_until_ready(data)
    return data


def _shift_mul(x, y):
    return (x + 1.0) * y


def _mul(x, y):
    return x * y


def queries(session, mats: dict) -> list:
    """The query phase's (name, Matrix) pairs."""
    from repro.core.expr import MergeFn
    shift_mul, mul = MergeFn("shift_mul", _shift_mul), MergeFn("mul", _mul)
    X, Y, W, H = mats["X"], mats["Y"], mats["W"], mats["H"]
    A, B = mats["A"], mats["B"]
    gram = X.t().multiply(X)
    sddmm = X.emul(W.multiply(H))
    return [
        ("trace_gram", gram.trace()),
        ("select_gram_1_1", gram.select("RID=1 AND CID=1")),
        ("overlay_x_y", X.join(Y, "RID=RID AND CID=CID", shift_mul)),
        ("sddmm", sddmm),
        ("sddmm_sum_all", sddmm.sum("a")),
        ("sddmm_sum_rows", sddmm.sum("r")),
        ("sddmm_sum_cols", sddmm.sum("c")),
        ("join_d2d", A.join(B, "RID=RID", mul)),
        ("join_v2v", A.join(B, "VAL=VAL", mul)),
    ]


# ---------------------------------------------------------------------------
# Plain references: jax.numpy on the device, NumPy on the host.
# ---------------------------------------------------------------------------

def references(data: dict) -> dict:
    """Expected answers, computed without ``repro``. Exact-integer data
    makes the device-side sums exact; the host finishes in float64."""
    import jax.numpy as jnp
    import numpy as np

    X, Y, W, H, A, B = (data[k] for k in "XYWHAB")

    def product():
        return X * jnp.dot(W, H, precision="highest")

    prod = product()
    ref = {
        "trace_gram": float(np.asarray(jnp.sum(X * X, axis=1),
                                       np.float64).sum()),
        "select_gram_1_1": float(np.asarray(jnp.sum(X[:, 1] ** 2))),
        # full matrices are rebuilt on demand, to keep the device free
        "overlay_x_y": lambda: (X + 1.0) * Y,
        "sddmm": product,
        "sddmm_sum_rows": np.asarray(jnp.sum(prod, axis=1), np.float64),
        "sddmm_sum_cols": np.asarray(jnp.sum(prod, axis=0), np.float64),
        "abs_rows": np.asarray(jnp.sum(jnp.abs(prod), axis=1), np.float64),
        "abs_cols": np.asarray(jnp.sum(jnp.abs(prod), axis=0), np.float64),
    }
    del prod
    ref["sddmm_sum_all"] = float(ref["sddmm_sum_rows"].sum())
    ref["abs_all"] = float(ref["abs_rows"].sum())
    # D2D on RID: every (A[r,i], B[r,j]) pair of nonzeros in row r
    na = np.asarray(jnp.sum(A != 0, axis=1), np.int64)
    nb = np.asarray(jnp.sum(B != 0, axis=1), np.int64)
    sa = np.asarray(jnp.sum(A, axis=1), np.float64)
    sb = np.asarray(jnp.sum(B, axis=1), np.float64)
    ref["join_d2d"] = (int((na * nb).sum()), float((sa * sb).sum()))
    # V2V: every pair of equal nonzero values; Σ a·b = Σ_v cA(v)·cB(v)·v²
    va, ca = np.unique(np.asarray(A[A != 0]), return_counts=True)
    vb, cb = np.unique(np.asarray(B[B != 0]), return_counts=True)
    common, ia, ib = np.intersect1d(va, vb, return_indices=True)
    pairs = ca[ia].astype(np.int64) * cb[ib]
    v = common.astype(np.float64)
    ref["join_v2v"] = (int(pairs.sum()), float((pairs * v * v).sum()))
    return ref


def check(name: str, out, ref: dict) -> dict:
    """Compare one answer with its reference; raises on a mismatch."""
    import jax.numpy as jnp
    import numpy as np

    if name in ("join_d2d", "join_v2v"):
        want_n, want_sum = ref[name]
        got_n = int(out.val.shape[0])
        got_sum = float(np.asarray(out.val, np.float64).sum())
        require(got_n == want_n, (name, got_n, want_n))
        require(abs(got_sum - want_sum) <= 1e-5 * abs(want_sum),
                (name, got_sum, want_sum))
        return {"matches": got_n, "checksum": got_sum}
    value = out.value
    if name in ("trace_gram", "select_gram_1_1", "sddmm_sum_all"):
        got = float(np.asarray(value).reshape(-1)[0])
        want = ref[name]
        scale = ref["abs_all"] if name == "sddmm_sum_all" else abs(want)
        require(abs(got - want) <= 1e-6 * max(scale, 1.0), (name, got, want))
        return {"value": got}
    if name in ("sddmm_sum_rows", "sddmm_sum_cols"):
        got = np.asarray(value, np.float64).reshape(-1)
        want = ref[name]
        scale = ref["abs_rows" if name.endswith("rows") else "abs_cols"]
        err = np.abs(got - want)
        require(got.shape == want.shape, (name, got.shape, want.shape))
        require((err <= 1e-6 * np.maximum(scale, 1.0)).all(),
                (name, float(err.max())))
        return {"max_abs_err": float(err.max())}
    # full matrices: compared on the device
    want = ref[name]()
    require(value.shape == want.shape, (name, value.shape, want.shape))
    err = float(jnp.max(jnp.abs(value - want)))
    require(err <= 1e-6 * max(float(jnp.max(jnp.abs(want))), 1.0), (name, err))
    return {"max_abs_err": err}


# ---------------------------------------------------------------------------
# Counters and plan annotations.
# ---------------------------------------------------------------------------

def require(ok: bool, detail) -> None:
    """The smoke's check: raises (unlike ``assert``, also under ``-O``)."""
    if not ok:
        raise RuntimeError(f"chip smoke check failed: {detail}")


def _sum_series(snapshot: dict, name: str) -> int:
    return sum(int(v) for k, v in snapshot.items()
               if k == name or k.startswith(name + "{"))


def kernel_counters(before: dict) -> dict:
    """Process-wide kernel counters accumulated since ``before``."""
    from repro.obs.metrics import REGISTRY
    now = REGISTRY.snapshot()
    out = {name: _sum_series(now, name) - _sum_series(before, name)
           for name in KERNEL_COUNTERS}
    out["kernel_dispatches"] = {
        k[len("kernel_dispatches"):]: int(v) - int(before.get(k, 0))
        for k, v in now.items() if k.startswith("kernel_dispatches{")
        and int(v) > int(before.get(k, 0))}
    return out


def expected_backend(kernel: str, n_workers: int = 1) -> str:
    """The backend a kernel must resolve to here: the pinned one, else the
    compiled TPU kernel wherever one is registered (on a mesh, only where
    GSPMD can partition it), else the dense path."""
    import jax

    from repro.kernels import registry
    pinned = os.environ.get("REPRO_KERNEL_BACKEND")
    if pinned:
        return pinned
    if (jax.default_backend() == "tpu"
            and registry.TPU in registry.get(kernel).impls
            and registry.accepts(registry.TPU, BLOCK, n_workers)):
        return registry.TPU
    return registry.DENSE


def plan_backends(matrix) -> dict:
    """``kernel → backend`` over the executed plan's kernel nodes; raises
    when a node's planned backend is not the expected one."""
    out = {}
    plan = matrix.physical_plan()
    for node in plan.nodes:
        if node.kernel is None:
            continue
        want = expected_backend(node.kernel, plan.n_workers)
        require(node.backend == want, (node.label(), node.backend, want))
        out[node.kernel] = node.backend
    return out


def assert_zero(counters: dict, names) -> None:
    bad = {k: counters[k] for k in names if counters[k]}
    require(not bad, f"non-zero failure counters: {bad}")


def assert_kernels_ran(dispatches: dict, n_workers: int = 1) -> None:
    for kernel in TPU_KERNELS:
        want = expected_backend(kernel, n_workers)
        key = f"{{backend={want},kernel={kernel}}}"
        require(dispatches.get(key, 0) > 0, (kernel, want, dispatches))


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# Phases.
# ---------------------------------------------------------------------------

def run_queries(session, data: dict, ref: dict, keep=None) -> dict:
    """Collect every query once and check it; answers are handed to
    ``keep(name, answer)`` when given, else dropped."""
    import jax

    from repro.core.matrix import BlockMatrix
    mats = {name: session.load(value, name) for name, value in data.items()}
    report = {}
    for name, m in queries(session, mats):
        t0 = time.perf_counter()
        out = m.collect()
        if isinstance(out, BlockMatrix):     # join outputs are host COO
            jax.block_until_ready(out.value)
        wall = time.perf_counter() - t0
        report[name] = dict(check(name, out, ref), wall_s=wall,
                            backends=plan_backends(m))
        if keep is not None:
            keep(name, out)
    return report


def query_phase(n: int = N, bs: int = BLOCK, seed: int = 0) -> dict:
    """Every query through ``Session.collect()`` on one worker."""
    from repro.core import Session
    from repro.obs.metrics import REGISTRY, MetricsRegistry

    t0 = time.perf_counter()
    data = make_data(n, bs, seed)
    ref = references(data)
    t_data = time.perf_counter() - t0
    before = REGISTRY.snapshot()
    metrics = MetricsRegistry()
    session = Session(block_size=bs, n_workers=1, metrics=metrics)
    report = run_queries(session, data, ref)
    counters = kernel_counters(before)
    snap = metrics.snapshot()
    for name in EXECUTOR_COUNTERS + ("executor_staged_sparse",):
        counters[name] = _sum_series(snap, name)
    assert_zero(counters, KERNEL_COUNTERS + EXECUTOR_COUNTERS)
    assert_kernels_ran(counters["kernel_dispatches"])
    return {"phase": "query", "n": n, "block_size": bs,
            "catalog_bytes": sum(int(v.nbytes) for v in data.values()),
            "data_and_reference_s": t_data, "queries": report,
            "counters": counters}


def served_phase(dim: int = SERVE_DIM, bs: int = BLOCK,
                 clients: int = SERVE_CLIENTS, seed: int = 0) -> dict:
    """The relational serving path: a zipf client stream over the ten
    templates through ``ServeEngine`` with cross-query CSE on; each
    template's served answer must equal its serial ``collect()``."""
    import numpy as np

    from repro.core import Session
    from repro.obs.metrics import REGISTRY, MetricsRegistry
    from repro.serve import workload as wl

    rng = np.random.default_rng(seed)
    session = Session(block_size=bs, n_workers=1)
    mats = wl.synthetic_catalog(session, rng, n=dim)
    templates = wl.query_templates(mats)
    stream = wl.client_stream(rng, templates, n_clients=clients)
    before = REGISTRY.snapshot()
    metrics = MetricsRegistry()
    r = wl.run_workload(session, stream, cse=True, n_threads=SERVE_THREADS,
                        registry=metrics)
    counters = kernel_counters(before)
    snap = metrics.snapshot()
    for name in EXECUTOR_COUNTERS + ENGINE_COUNTERS + (
            "serve_submitted", "serve_completed", "serve_root_hits"):
        counters[name] = _sum_series(snap, name)
    assert_zero(counters, KERNEL_COUNTERS + EXECUTOR_COUNTERS
                + ENGINE_COUNTERS)
    require(r["failures"] == 0 and r["hung"] == 0, r)
    require(counters["serve_completed"] == counters["serve_submitted"],
            counters)
    # TPU's default f32 matmul rounds its inputs to bf16, and the engine's
    # eager per-node path and the serial staged program may contract the
    # same product through different kernels: equal up to that rounding
    parity = {}
    for name, expr in templates:
        if name not in r["answers"]:
            continue                     # not drawn by this stream
        want = np.asarray(session.execute(expr).value)
        got = np.asarray(r["answers"][name].value)
        require(got.shape == want.shape, (name, got.shape, want.shape))
        rel = float(np.linalg.norm(got - want)
                    / max(np.linalg.norm(want), 1e-30))
        require(rel <= 1e-2, (name, rel))
        parity[name] = rel
    return {"phase": "served", "dim": dim, "block_size": bs,
            "clients": len(stream), "templates_served": len(parity),
            "qps": r["qps"], "p50_ms": r["p50_ms"], "p99_ms": r["p99_ms"],
            "wall_s": r["wall_s"], "parity_rel_err": parity,
            "counters": counters}


def spmd_phase(chips: int, n: int = N, bs: int = BLOCK,
               seed: int = 0) -> dict:
    """The query phase's plans on a ``chips``-worker mesh (whole-plan
    GSPMD staging under the paper's partition schemes), compared with the
    same plans on one worker in the same process."""
    import numpy as np

    from repro.core import Session
    from repro.core.joins import COOTensor
    from repro.obs.metrics import REGISTRY, MetricsRegistry

    data = make_data(n, bs, seed)
    ref = references(data)
    before = REGISTRY.snapshot()
    m1, mk = MetricsRegistry(), MetricsRegistry()
    one = Session(block_size=bs, n_workers=1, metrics=m1)
    many = Session(block_size=bs, n_workers=chips, metrics=mk)
    mesh_ids = sorted({d.id for d in many.mesh.devices.flat})
    require(len(mesh_ids) == chips, mesh_ids)
    # the one-worker answers are kept on the host, the mesh's compared
    # with them as they come
    host = {}
    spread = set()

    def keep_host(name, out):
        host[name] = out if isinstance(out, COOTensor) \
            else np.asarray(out.value)

    def compare(name, out):
        if isinstance(out, COOTensor):
            require(np.array_equal(out.idx, host[name].idx), name)
            require(np.array_equal(out.val, host[name].val), name)
            return
        spread.update(d.id for d in out.value.sharding.device_set)
        got, want = np.asarray(out.value), host[name]
        tol = 1e-6 * max(float(np.abs(want).max()), 1.0)
        require(float(np.abs(got - want).max()) <= tol, name)

    rep1 = run_queries(one, data, ref, keep=keep_host)
    repk = run_queries(many, data, ref, keep=compare)
    require(len(spread) == chips, spread)
    counters = kernel_counters(before)
    snap = mk.snapshot()
    for name in EXECUTOR_COUNTERS + ("executor_staged_spmd",
                                     "executor_staged_sparse_spmd"):
        counters[name] = _sum_series(snap, name)
    assert_zero(counters, KERNEL_COUNTERS + EXECUTOR_COUNTERS)
    assert_kernels_ran(counters["kernel_dispatches"], chips)
    staged = (counters["executor_staged_spmd"]
              + counters["executor_staged_sparse_spmd"])
    require(staged > 0, counters)
    return {"phase": "spmd", "chips": chips, "mesh_devices": mesh_ids,
            "result_devices": sorted(spread), "n": n, "block_size": bs,
            "one_worker": rep1, "mesh": repk, "counters": counters}


# ---------------------------------------------------------------------------
# Entry point.
# ---------------------------------------------------------------------------

class CompileStats:
    """Backend compile seconds and persistent-cache hits, from JAX's
    monitoring events."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.hits = self.writes = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1    # a miss that was written to the cache

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def take(self) -> dict:
        out = {"compile_s": self.compile_s, "cache_hits": self.hits,
               "cache_writes": self.writes}
        self.compile_s = 0.0
        self.hits = self.writes = 0
        return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip (SPMD) phase")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    # before anything compiles
    from repro.launch.compile_cache import setup_compile_cache
    cache_dir = setup_compile_cache()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(json.dumps({"device": device, "compile_cache": cache_dir,
                      "cache_from_env": bool(os.environ.get(
                          "JAX_COMPILATION_CACHE_DIR"))}), flush=True)

    stats = CompileStats()
    if args.chips == 1:
        phases = (lambda: query_phase(seed=args.seed),
                  lambda: served_phase(seed=args.seed))
    else:
        phases = (lambda: spmd_phase(args.chips, seed=args.seed),)
    for run in phases:
        t0 = time.perf_counter()
        report = run()
        report["smoke_wall_s"] = time.perf_counter() - t0
        report.update(stats.take())
        report["peak_bytes_in_use"] = peak_bytes()
        print(json.dumps(report, default=str), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

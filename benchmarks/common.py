"""Shared benchmark utilities: timing, synthetic matrices, CSV rows.

Scale note: the paper benchmarks 100K–200K-dim sparse matrices on a 6-node
cluster with 1-hour timeouts; these benches run on one CPU core at reduced
dims with the same sparsity regimes and validate the paper's *relative*
claims (optimized ≪ naive). Cases the paper reports as OOM/>1h become
'skipped(cost-model)' rows here — the cost model itself predicts
infeasibility.
"""
from __future__ import annotations

import sys
import time
from typing import Callable, List, Optional

import jax
import numpy as np

ROWS: List[str] = []


def timeit(fn: Callable, repeats: int = 3, warmup: int = 1) -> float:
    """Median wall time per call in microseconds."""
    for _ in range(warmup):
        r = fn()
        jax.block_until_ready(r) if r is not None else None
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        r = fn()
        if r is not None:
            jax.block_until_ready(r)
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


def paired(fn_a: Callable, fn_b: Callable, repeats: int = 3,
           warmup: int = 1):
    """Interleaved A/B timing (seconds): runs alternate A,B,A,B,… so
    drift (thermal, cache, background load) hits both arms equally — the
    tracer-overhead bench compares traced vs untraced this way. A
    callable that returns a float reports its own measured seconds (e.g.
    a workload's internally-timed steady-state phase, excluding setup);
    otherwise the whole call is wall-timed. Returns
    ``(median_a_s, median_b_s)``."""
    def sample(fn) -> float:
        # collect before each arm: otherwise whichever run crosses the
        # gen-2 GC threshold absorbs the whole pause (~2x on the serving
        # workload) and the pairing is meaningless
        import gc
        gc.collect()
        t0 = time.perf_counter()
        r = fn()
        return r if isinstance(r, float) else time.perf_counter() - t0

    for _ in range(warmup):
        fn_a()
        fn_b()
    ta, tb = [], []
    for i in range(repeats):
        # alternate which arm goes first: position-in-iteration effects
        # (GC debt from the previous run, cache warmth) cancel out
        if i % 2 == 0:
            ta.append(sample(fn_a))
            tb.append(sample(fn_b))
        else:
            tb.append(sample(fn_b))
            ta.append(sample(fn_a))
    return float(np.median(ta)), float(np.median(tb))


def row(name: str, us: Optional[float], derived: str = "") -> None:
    us_s = f"{us:.1f}" if us is not None else "skipped"
    line = f"{name},{us_s},{derived}"
    ROWS.append(line)
    print(line, flush=True)


def sparse(rng, m, n, density, round_vals=False) -> np.ndarray:
    v = rng.normal(size=(m, n)).astype(np.float32)
    keep = rng.uniform(size=(m, n)) < density
    out = np.where(keep, v, 0).astype(np.float32)
    if round_vals:
        out = np.round(out, 1)
    return out

"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Mapping to the paper:
    bench_agg_gram      — Fig. 7a/7b  (sum/trace over Gram matrices)
    bench_select_lr     — Figs. 8, 9  (selection pushdown, LR)
    bench_cross_product — Table 5     (Kronecker / cross-product)
    bench_join_dims     — Fig. 10     (direct/transpose overlay)
    bench_join_single   — Fig. 11a–c  (D2D joins + cost-model validation)
    bench_join_entries  — Fig. 11d    (V2V Bloom vs sparsity)
    bench_pnmf          — Table 6     (PNMF pipeline)
    bench_plan_cse      — (beyond paper) planned DAG vs tree-walk CSE
    bench_optimizer     — (beyond paper) greedy oracle vs memo search
                          (plan cost + end-to-end wall clock)
    bench_sparse_join   — (beyond paper) host-COO vs device-resident
                          sparse joins + staged block-skip ratio
    bench_serve         — (beyond paper) multi-query serving tier:
                          sustained qps + p50/p99 with/without
                          cross-query CSE (1k-client zipf workload)
    bench_obs           — (beyond paper) tracer overhead on the serving
                          workload (paired traced vs untraced timing)
    bench_robust        — (beyond paper) fault-injection guard overhead
                          (paired armed-silent vs off) + chaos storm
                          completeness/p99
    bench_cost_model    — (beyond paper) calibrated cost model: held-out
                          prediction accuracy vs analytic, plan-flip
                          gate, online-refit p50 overhead
    bench_kernels_fused — (beyond paper) fused SDDMM+agg vs materialize-
                          then-aggregate (wall + peak intermediate
                          bytes) and the autotune warm-start proof
    bench_dist_comm     — (beyond paper) per-join jit vs whole-plan SPMD
                          (needs XLA_FLAGS=--xla_force_host_platform_
                          device_count=8 on CPU)
    bench_roofline      — (beyond paper) dry-run roofline table

Usage: ``python benchmarks/run.py [substring] [--json | --json=path]``

``substring`` filters modules by name; ``--json`` additionally writes the
rows as machine-readable records to ``results/bench.json`` (or the
``--json=path`` override — ``=`` form only, so a following substring
filter can never be mistaken for the output path).
"""
import json
import os
import sys
import time

import numpy as np

DEFAULT_JSON = os.path.join("results", "bench.json")


def _parse_args(argv):
    only, json_path = None, None
    for a in argv:
        if a == "--json":
            json_path = DEFAULT_JSON
        elif a.startswith("--json="):
            json_path = a.split("=", 1)[1] or DEFAULT_JSON
        elif a.startswith("-"):
            raise SystemExit(f"unknown flag {a!r}; "
                             "usage: run.py [substring] [--json[=path]]")
        else:
            only = a
    return only, json_path


def _write_json(path: str, rows, only, wall_s: float) -> None:
    records = []
    for line in rows:
        name, us, derived = line.split(",", 2)
        records.append({
            "name": name,
            "us_per_call": None if us == "skipped" else float(us),
            "derived": derived,
        })
    out = {
        "schema": 1,
        "created_unix": time.time(),
        "filter": only,
        "wall_s": wall_s,
        "rows": records,
    }
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(f"# wrote {path} ({len(records)} rows)", flush=True)


def main() -> None:
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    from benchmarks import (
        bench_agg_gram, bench_cost_model, bench_cross_product,
        bench_dist_comm, bench_join_dims, bench_join_entries,
        bench_join_single, bench_kernels_fused, bench_obs, bench_optimizer,
        bench_plan_cse, bench_pnmf, bench_robust, bench_roofline,
        bench_select_lr, bench_serve, bench_sparse_join,
    )
    from benchmarks.common import ROWS, row

    mods = [bench_agg_gram, bench_select_lr, bench_cross_product,
            bench_join_dims, bench_join_single, bench_join_entries,
            bench_pnmf, bench_plan_cse, bench_optimizer, bench_sparse_join,
            bench_serve, bench_obs, bench_robust, bench_cost_model,
            bench_kernels_fused, bench_dist_comm, bench_roofline]
    only, json_path = _parse_args(sys.argv[1:])
    print("name,us_per_call,derived")
    t0 = time.time()
    for mod in mods:
        if only and only not in mod.__name__:
            continue
        rng = np.random.default_rng(0)
        t = time.time()
        mod.run(rng)
        row(f"_{mod.__name__.split('.')[-1]}_wall", (time.time() - t) * 1e6,
            "")
    wall_s = time.time() - t0
    row("_total_wall", wall_s * 1e6, "")
    if json_path is not None:
        _write_json(json_path, ROWS, only, wall_s)


if __name__ == '__main__':
    main()

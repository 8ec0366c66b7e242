"""Recompute roofline stats from saved (zstd-compressed) HLO dumps.

Lets the §Perf loop iterate on the analysis model without recompiling, and
regenerates every cell JSON after parser improvements:

    PYTHONPATH=src python -m repro.analysis.reanalyze results/dryrun
"""
from __future__ import annotations

import json
import os
import sys

import zstandard

from repro.analysis import roofline as rl
from repro.analysis.hlo import parse_hlo_module


def reanalyze_cell(json_path: str) -> bool:
    with open(json_path) as f:
        res = json.load(f)
    if res.get("status") != "ok" or not res.get("hlo_path"):
        return False
    hp = res["hlo_path"]
    if not os.path.exists(hp):
        return False
    with open(hp, "rb") as f:
        text = zstandard.ZstdDecompressor().decompress(f.read()).decode()
    stats = parse_hlo_module(text)
    mf = rl.model_flops(res["params"], res["active_params"],
                        res["tokens_per_step"],
                        "train" if res["shape"].startswith("train")
                        else ("prefill" if res["shape"].startswith("prefill")
                              else "decode"))
    roof = rl.analyze(stats, mf, res["n_chips"],
                      device_kind=res["device_kind"])
    res["hlo"] = dict(
        flops=stats.flops, dot_flops=stats.dot_flops,
        bytes_accessed=stats.bytes_accessed,
        collective_bytes=stats.collective_bytes,
        collective_breakdown=stats.collective_breakdown,
        while_trip_counts=stats.while_trip_counts,
        warnings=stats.warnings[:5])
    res["roofline"] = roof.as_dict()
    with open(json_path, "w") as f:
        json.dump(res, f, indent=2)
    return True


def main(argv=None) -> int:
    args = argv if argv is not None else sys.argv[1:]
    d = args[0] if args else "results/dryrun"
    n = 0
    for fn in sorted(os.listdir(d)):
        if fn.endswith(".json"):
            if reanalyze_cell(os.path.join(d, fn)):
                n += 1
                r = json.load(open(os.path.join(d, fn)))["roofline"]
                print(f"[reanalyzed] {fn[:-5]} dom={r['dominant']} "
                      f"mfu={r['mfu']:.3f}")
    print(f"{n} cells reanalyzed")
    return 0


if __name__ == "__main__":
    sys.exit(main())

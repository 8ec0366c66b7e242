#!/usr/bin/env python3
"""The readings a cell's limits are set from, one JSON line per seed.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--program]

By default it reads the control: the plain reference put in the
program's place, one precision step below what the configuration states
(``Reference.for_config(cfg, control=True)``), which sets the upper end
of each limit. With ``--program`` it reads the program itself, through
the same traffic with no timed window, which gives more seeds for the
lower end. Either replays the cell's traffic through its warm-up and as
many steps as a run's check holds, and is held against the reference
proper by the same numbers as a run. The benchmark's own runs never run
this. Needs the chip, as a run does.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from lib import spec as specmod  # noqa: E402


def readings(cfg: dict, cfg_mod, traffic: dict, seed: int,
             program: bool = False) -> dict:
    """The numbers of the control (or of the program) for one seed."""
    from lib.backends import ProgramBackend, ReferenceBackend, load_all
    from lib.reference import Reference
    from run import seed_key

    key = seed_key(seed)
    if program:
        from repro.core import Session
        backend = ProgramBackend(Session(block_size=cfg["block_size"],
                                         mode=cfg["mode"], n_workers=1))
    else:
        backend = ReferenceBackend(Reference.for_config(cfg, control=True))
    load_all(backend, cfg_mod.catalog(cfg, key))
    gen = specmod.shape(traffic["shape"]).Traffic(traffic, backend)
    gen.warm_up()
    for _ in range(gen.checked_steps):
        gen.step()
    gen.finish()
    del backend
    gc.collect()
    ref = ReferenceBackend(Reference.for_config(cfg))
    load_all(ref, cfg_mod.catalog(cfg, key))
    numbers, errs = gen.check(ref)
    return dict(numbers, errors=errs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true",
                    help="read the program instead of the control")
    args = ap.parse_args(argv)
    import run
    bench = specmod.load_benchmark()
    cell = specmod.cell(bench, args.workload)
    run.check_chips(cell["chips"])
    cfg = specmod.config(cell["config"])
    run.setup_jax(cfg)
    cfg_mod = specmod.config_module(cell["config"])
    traffic = specmod.traffic(cell["traffic"])
    side = "program" if args.program else "control"
    for seed in args.seeds:
        out = readings(cfg, cfg_mod, traffic, seed, args.program)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          side: out}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

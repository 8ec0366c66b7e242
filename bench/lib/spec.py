"""Finds what belongs to a cell by name.

``BENCHMARK.json`` at the checkout root lists the cells and metrics. A
configuration is ``configs/<name>.json`` (its sizes, source and limits
of scale) with ``configs/<name>.py`` beside it (its data, generated on
the device from the seed). A traffic mix is ``traffic/<name>.json``,
data read by the generator of its ``shape`` (``shapes/<shape>.py``),
which finds a pipeline's update rules in ``rules/<rule>.py``. A metric
``<base>`` or ``<base>.<suffix>``, end-to-end or per-layer, is read by
``metrics/<base>.py``. Adding a cell or a metric adds files and
entries; it edits none.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def config(name: str) -> dict:
    return _json(BENCH / "configs" / f"{name}.json")


def config_module(name: str) -> ModuleType:
    return _module(BENCH / "configs" / f"{name}.py", f"bench_config_{name}")


def traffic(name: str) -> dict:
    return _json(BENCH / "traffic" / f"{name}.json")


def shape(name: str) -> ModuleType:
    return _module(BENCH / "shapes" / f"{name}.py", f"bench_shape_{name}")


def rule(name: str) -> ModuleType:
    return _module(BENCH / "rules" / f"{name}.py", f"bench_rule_{name}")


def metric_base(metric: str) -> str:
    """``optimize_ms.iter`` → ``optimize_ms``: the reader's file name."""
    return metric.split(".", 1)[0]


def metric_reader(metric: str) -> ModuleType:
    base = metric_base(metric)
    return _module(BENCH / "metrics" / f"{base}.py", f"bench_metric_{base}")


def cell_metrics(bench: dict, cell_name: str, section: str) -> list:
    """The ``section`` ("end_to_end" or "per_layer") metrics this cell
    reports: those that list it under ``workloads``, or carry no such
    key."""
    return [m for m in bench[section]
            if cell_name in m.get("workloads", [cell_name])]


@functools.lru_cache(maxsize=None)
def _module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        name.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

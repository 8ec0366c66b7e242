#!/usr/bin/env python3
"""Runs one benchmark cell once on the chip, checks what it produced, and
prints one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``configs/<name>``)
and a traffic mix (``traffic/<name>.json``), which the generator of its
shape (``shapes/<shape>.py``) drives. The run makes the catalog on the
device from ``--seed``, loads it into a ``Session``, warms up every
program the traffic uses (set-up, timed from process start), then steps
the traffic through ``Session.collect`` for ``--seconds``. With
``--trace 0`` it reports the cell's end-to-end metrics; with
``--trace 1`` it records a ``jax.profiler`` trace and the program's own
spans over the window and reports the cell's per-layer metrics. Each
metric is read by ``metrics/<name>.py``.

After the window it frees the program's state, replays the traffic
through the plain reference (``lib/reference.py``) from the same
catalog, and compares. ``correct`` is true when no query failed and
every number compared is within its limit; the numbers and limits are
printed last on standard error and under ``checks``, last in the
result line.

The run refuses to start without a TPU with as many chips as the cell
asks for. The persistent compile cache is ``$JAX_COMPILATION_CACHE_DIR``
where set, else ``.jax_cache/`` at the checkout root.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is timed from process start

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from lib import spec as specmod  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"
# counters of the program that must not move in a sound run
FAILURE_COUNTERS = ("kernel_dispatch_failures", "kernel_dispatch_fallbacks",
                    "kernel_dispatch_quarantined",
                    "executor_sparse_fallbacks", "executor_sparse_overflows")


class NoChip(RuntimeError):
    pass


def check_chips(chips: int) -> None:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")


def setup_jax(cfg: dict) -> None:
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    # cache sub-second programs too: a rebind re-stages its plan, and
    # only a cache hit keeps that from compiling inside the window
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if cfg["matmul_precision"] != "default":
        jax.config.update("jax_default_matmul_precision",
                          cfg["matmul_precision"])


def seed_key(seed: int):
    """A PRNG key from any whole number, 64 bits of it."""
    import jax
    seed %= 1 << 64
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def counter_totals(*registries) -> dict:
    out = {name: 0 for name in FAILURE_COUNTERS}
    for reg in registries:
        for key, value in reg.snapshot().items():
            name = key.split("{", 1)[0]
            if name in out:
                out[name] += int(value)
    return out


def memory_peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def run_cell(bench: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, *, cfg: dict = None, require_chip: bool = True,
             hooks=None):
    """One run of one cell; returns the result line as a dict, and a
    dict of diagnostics for standard error.

    ``cfg`` replaces the cell's configuration (tests run cells at a
    tiny size); ``hooks.wrap_backend(backend)`` may put a broken backend
    in the program's place (tests of the check)."""
    cell = specmod.cell(bench, cell_name)
    if require_chip:
        check_chips(cell["chips"])
    cfg = cfg or specmod.config(cell["config"])
    cfg_mod = specmod.config_module(cell["config"])
    traffic = specmod.traffic(cell["traffic"])
    shape = specmod.shape(traffic["shape"])
    setup_jax(cfg)

    import jax

    from lib.backends import ProgramBackend, ReferenceBackend, load_all
    from lib.jaxevents import CompileStats
    from repro.core import Session
    from repro.obs.metrics import REGISTRY, MetricsRegistry

    compiles = CompileStats()
    key = seed_key(seed)
    session_metrics = MetricsRegistry()
    session = Session(block_size=cfg["block_size"], mode=cfg["mode"],
                      n_workers=1, metrics=session_metrics)
    backend = ProgramBackend(session)
    if hooks is not None:
        backend = hooks.wrap_backend(backend)
    load_all(backend, cfg_mod.catalog(cfg, key))
    setup = {"catalog_s": time.perf_counter() - T_START}
    gen = shape.Traffic(traffic, backend)
    t = time.perf_counter()
    gen.warm_up()
    setup["warmup_s"] = time.perf_counter() - t
    ctx = types.SimpleNamespace(setup_s=time.perf_counter() - T_START)
    compiles_before = compiles.compiles
    counters_before = counter_totals(REGISTRY, session_metrics)

    program_trace = None
    if trace:
        from repro.obs.trace import TRACER
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        program_trace = TRACER.start("window", sample=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0    # the program's spans say it
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
        activation = TRACER.activate(program_trace)
        activation.__enter__()
    try:
        with jax.profiler.TraceAnnotation("window"):
            t0 = time.perf_counter()
            deadline = t0 + seconds
            while time.perf_counter() < deadline:
                gen.step()
            ctx.elapsed_s = time.perf_counter() - t0
    finally:
        if trace:
            activation.__exit__(None, None, None)
            program_trace.finish()
            jax.profiler.stop_trace()
    gen.finish()
    ctx.steps = gen.done

    window_compiles = compiles.compiles - compiles_before
    counters_after = counter_totals(REGISTRY, session_metrics)
    moved = {k: counters_after[k] - counters_before[k]
             for k in FAILURE_COUNTERS
             if counters_after[k] != counters_before[k]}
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak_bytes()}
    result = {"correct": False, "attempted": gen.attempted,
              "failed": sum(moved.values())}

    if trace:
        breakdown = _read_trace(ctx, device)
        ctx.root = program_trace.root
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in specmod.cell_metrics(bench, cell_name, section):
        value = specmod.metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if trace:
        result["breakdown"] = breakdown

    # the program's state goes before the reference runs
    del backend, session, program_trace, ctx
    gc.collect()

    from lib.reference import Reference
    ref_backend = ReferenceBackend(Reference.for_config(cfg))
    load_all(ref_backend, cfg_mod.catalog(cfg, key))
    numbers, errs = gen.check(ref_backend)
    limits = traffic["check"]["limits"]
    checks = {"failed": {"value": result["failed"], "limit": 0}}
    for name, value in numbers.items():
        checks[name] = {"value": value, "limit": limits[name]}
    result["correct"] = all(
        isinstance(c["value"], (int, float)) and math.isfinite(c["value"])
        and c["value"] <= c["limit"] for c in checks.values())
    result["checks"] = checks
    diag = {"setup": setup, "window_compiles": window_compiles,
            "steps": gen.done, "errors": errs, "counters_moved": moved,
            "kernel_dispatches": {k: int(v) for k, v in
                                  REGISTRY.snapshot().items()
                                  if k.startswith("kernel_dispatches")}}
    return result, diag


def _read_trace(ctx, device) -> dict:
    """Reads the device trace of the window into ``ctx`` and ``device``;
    returns the breakdown."""
    from lib import devtrace
    events = devtrace.read(str(TRACE_DIR))
    lo, hi = devtrace.window(events)
    ctx.busy_s = devtrace.busy_ns(events, lo, hi) * 1e-9
    ctx.window_s = (hi - lo) * 1e-9
    device["busy_s"] = ctx.busy_s
    device["window_s"] = ctx.window_s
    breakdown = {
        "device_ops": devtrace.top(devtrace.op_times(events, lo, hi)),
        "idle_gaps": devtrace.top(devtrace.idle_by_label(events, lo, hi)),
    }
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return breakdown


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not args.seconds > 0:
        ap.error("--seconds must be above 0")
    bench = specmod.load_benchmark()
    try:
        result, diag = run_cell(bench, args.workload, args.seed,
                                args.seconds, bool(args.trace))
    except NoChip as exc:
        print(f"bench: {exc}; nothing was run", file=sys.stderr)
        return 2
    print(f"bench: {json.dumps(diag)}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Fleet-shared block-size autotuner for registry kernels.

Entries are keyed by ``(kernel, shape-bucket, dtype, backend, device
kind)`` — shapes are bucketed to the next power of two per dimension so
one timing run covers a neighborhood of problem sizes instead of every
exact shape, and the device-kind segment makes one artifact safely
mergeable across heterogeneous machines: tiles tuned on an H100 never
serve a TPU pod or a CPU runner. Results live in an in-process dict
backed by an on-disk JSON cache so tuning survives process restarts —
and, merged across CI runs and deployments, becomes a *fleet-shared
warm-start artifact*: a process that boots with the artifact performs
zero tuning trials on covered buckets (``tune_stats()`` proves it).

Three entry points:

* ``best_tiles`` — full lookup: in-process cache → disk cache → run the
  timing search over the kernel's tile grid (when a ``runner`` is given) →
  fall back to the kernel's default tiles. Timing failures (e.g. a tile
  shape the backend rejects) skip that candidate; if every candidate fails,
  the default tiles are returned and nothing is cached.
* ``cached_tiles`` — cache-only lookup used by ``registry.dispatch`` on the
  hot path: never times, returns None on miss.
* ``merge_files`` / the ``merge`` CLI — combine artifacts from many
  machines/runs into one (later inputs win on key collisions; mismatched
  schema versions are rejected, not silently dropped)::

      python -m repro.kernels.autotune merge a.json b.json -o out.json

Cache invalidation: the JSON schema is versioned (``_schema``); bumping
``_SCHEMA`` orphans old files. Deleting the file (or pointing
``REPRO_AUTOTUNE_CACHE`` elsewhere) retunes from scratch. Writers are
concurrency-tolerant: every save/merge writes a temp file in the target
directory and ``os.replace``s it, so a reader never observes a torn file
and the last writer wins whole-file.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

Tiles = Dict[str, int]

# schema 2: the cache key grew a device-kind segment (fleet merging);
# schema-1 files are orphaned wholesale — their keys are ambiguous
# across machines, which is exactly what the segment exists to fix
_SCHEMA = 2
_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
_CACHE: Dict[str, Tiles] = {}
_DISK_LOADED_FROM: Optional[str] = None
_DEVICE_KIND: Optional[str] = None

# tuning-effort accounting: ``trials`` counts kernel invocations made by
# the timing search (warmup/rejection + timed samples); ``warm_hits``
# counts lookups served from the cache. A server booting with a complete
# fleet artifact shows trials == 0 — the warm-start acceptance proof.
_STATS = {"trials": 0, "warm_hits": 0}


def tune_stats() -> Dict[str, int]:
    return dict(_STATS)


def reset_stats() -> None:
    _STATS["trials"] = 0
    _STATS["warm_hits"] = 0


def cache_path() -> str:
    # CWD-relative results/ by default, matching REPRO_DRYRUN_OUT's
    # convention; deployments point REPRO_AUTOTUNE_CACHE at a shared file
    return os.environ.get(_CACHE_ENV,
                          os.path.join("results", "autotune.json"))


def device_kind() -> str:
    """``platform:device_kind`` of the first local device — the artifact
    key segment that keeps per-machine tiles from cross-serving. Memoized
    per process (jax.devices() is not free); '|' is the key delimiter so
    it is scrubbed from free-form device-kind strings."""
    global _DEVICE_KIND
    if _DEVICE_KIND is None:
        import jax
        d = jax.devices()[0]
        kind = f"{d.platform}:{d.device_kind}"
        _DEVICE_KIND = kind.replace("|", "/").replace(" ", "_")
    return _DEVICE_KIND


def shape_bucket(shapes: Sequence[Sequence[int]]) -> Tuple[Tuple[int, ...],
                                                           ...]:
    """Round every dim up to the next power of two (min 1)."""
    def up(d: int) -> int:
        d = max(int(d), 1)
        return 1 << (d - 1).bit_length()

    return tuple(tuple(up(d) for d in s) for s in shapes)


def cache_key(kernel: str, shapes: Sequence[Sequence[int]], dtype: str,
              backend: str) -> str:
    bucket = "x".join(",".join(map(str, s)) for s in shape_bucket(shapes))
    return f"{kernel}|{bucket}|{dtype}|{backend}|{device_kind()}"


# ---------------------------------------------------------------------------
# Disk round-trip.
# ---------------------------------------------------------------------------

def load_cache(path: Optional[str] = None) -> Dict[str, Tiles]:
    """Merge the on-disk cache into the in-process one (disk wins on miss
    only; in-process entries are fresher). Corrupt/mismatched files are
    ignored — the tuner just re-times."""
    global _DISK_LOADED_FROM
    path = path or cache_path()
    _DISK_LOADED_FROM = path
    try:
        with open(path) as f:
            blob = json.load(f)
        if blob.get("_schema") != _SCHEMA:
            return _CACHE
        for k, v in blob.get("entries", {}).items():
            _CACHE.setdefault(k, {str(n): int(b) for n, b in v.items()})
    except (OSError, ValueError):
        pass
    return _CACHE


def _write_atomic(path: str, entries: Dict[str, Tiles]) -> str:
    """Temp-in-target-dir + ``os.replace``: concurrent writers race to
    whole-file wins, readers never see a torn JSON."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump({"_schema": _SCHEMA, "entries": entries}, f, indent=1,
                  sort_keys=True)
    os.replace(tmp, path)
    return path


def save_cache(path: Optional[str] = None) -> str:
    return _write_atomic(path or cache_path(), _CACHE)


def clear_cache(in_process_only: bool = True) -> None:
    global _DISK_LOADED_FROM
    _CACHE.clear()
    _DISK_LOADED_FROM = None  # next cache-only lookup re-reads the disk
    if not in_process_only:
        try:
            os.remove(cache_path())
        except OSError:
            pass


def merge_files(paths: Sequence[str], out: str) -> Tuple[str, int]:
    """Merge many autotune artifacts into ``out`` (the fleet CI step).

    Every input must carry the current ``_schema`` — a version mismatch
    raises instead of silently shipping keys the reader would ignore (or
    worse, misread). Later inputs win on key collisions, so callers order
    inputs oldest→newest. Returns ``(out, n_entries)``.
    """
    merged: Dict[str, Tiles] = {}
    for p in paths:
        with open(p) as f:
            blob = json.load(f)
        if blob.get("_schema") != _SCHEMA:
            raise ValueError(
                f"{p}: schema {blob.get('_schema')!r} != {_SCHEMA} — "
                f"refusing to merge across schema versions")
        for k, v in blob.get("entries", {}).items():
            merged[k] = {str(n): int(b) for n, b in v.items()}
    return _write_atomic(out, merged), len(merged)


# ---------------------------------------------------------------------------
# Lookup / search.
# ---------------------------------------------------------------------------

def cached_tiles(kernel: str, shapes: Sequence[Sequence[int]], dtype: str,
                 backend: str) -> Optional[Tiles]:
    """Cache-only lookup (in-process, then disk once per process)."""
    key = cache_key(kernel, shapes, dtype, backend)
    if key not in _CACHE and _DISK_LOADED_FROM != cache_path():
        load_cache()
    hit = _CACHE.get(key)
    if hit is None:
        return None
    _STATS["warm_hits"] += 1
    return dict(hit)  # callers may mutate


def _timed_once(fn: Callable[[], object]) -> float:
    """One wall-clock sample of ``fn()``, gc-collected first: without the
    collect, whichever sample crosses the gen-2 GC threshold absorbs the
    whole pause and the comparison between candidates (and the wall times
    fed to the calibrated cost model's corpus) is polluted — the same
    hardening as ``benchmarks.common.paired``."""
    import gc

    import jax
    gc.collect()
    t0 = time.perf_counter()
    r = fn()
    if r is not None:
        jax.block_until_ready(r)
    return time.perf_counter() - t0


def time_candidate(fn: Callable[[], object], repeats: int = 2,
                   warmup: int = 1) -> float:
    """Median wall seconds of ``fn()`` (which must block until ready),
    with a gc.collect before every timed sample (``_timed_once``)."""
    import jax
    for _ in range(warmup):
        r = fn()
        if r is not None:
            jax.block_until_ready(r)
    ts = [_timed_once(fn) for _ in range(repeats)]
    ts.sort()
    return ts[len(ts) // 2]


def best_tiles(kernel: str, shapes: Sequence[Sequence[int]], dtype: str,
               backend: str, *,
               runner: Optional[Callable[[Tiles], object]] = None,
               grid: Optional[Sequence[Tiles]] = None,
               default: Optional[Tiles] = None,
               repeats: int = 2,
               persist: bool = True,
               force_retune: bool = False) -> Tiles:
    """Resolve the best tile sizes for one (kernel, shapes, dtype, backend).

    ``runner(tiles)`` executes the kernel once with the candidate tiles and
    returns its (blockable) output; candidates whose runner raises are
    skipped. With no runner — or when every candidate fails — the kernel's
    ``default`` tiles are returned unchanged and NOT cached, so a later
    caller that can time still gets the chance to.
    """
    from repro.kernels import registry
    spec = registry.get(kernel) if grid is None or default is None else None
    if grid is None:
        grid = spec.tile_grid if spec else ()
    if default is None:
        default = dict(spec.default_tiles or {}) if spec else {}

    key = cache_key(kernel, shapes, dtype, backend)
    if not force_retune:
        hit = cached_tiles(kernel, shapes, dtype, backend)
        if hit is not None:
            return hit
    if runner is None or not grid:
        return dict(default)

    cands = []
    seen = set()
    for cand in grid:
        cand = dict(cand)
        fp = tuple(sorted(cand.items()))
        if fp in seen:  # duplicate candidate (e.g. a pre-clamped grid)
            continue
        seen.add(fp)
        cands.append(cand)
    # warmup pass doubles as the rejection filter: a tile shape this
    # backend/problem rejects drops out before any timing
    alive = []
    for cand in cands:
        try:
            import jax
            _STATS["trials"] += 1
            r = runner(cand)
            if r is not None:
                jax.block_until_ready(r)
            alive.append(cand)
        except Exception:
            continue
    if not alive:
        return dict(default)
    # interleaved timing (the paired-timing hardening from
    # ``benchmarks.common.paired``): one gc-collected sample per candidate
    # per round, visit order reversed every round, so drift — thermal,
    # background load, GC debt — hits every candidate equally instead of
    # biasing whichever happened to be timed during a quiet stretch
    samples: list = [[] for _ in alive]
    for rnd in range(max(repeats, 1)):
        order = range(len(alive)) if rnd % 2 == 0 \
            else range(len(alive) - 1, -1, -1)
        for i in order:
            cand = alive[i]
            try:
                _STATS["trials"] += 1
                samples[i].append(_timed_once(lambda: runner(cand)))
            except Exception:
                samples[i].append(float("inf"))

    def median(ts) -> float:
        ts = sorted(ts)
        return ts[len(ts) // 2]

    best_i = min(range(len(alive)), key=lambda i: median(samples[i]))
    if median(samples[best_i]) == float("inf"):
        return dict(default)
    best = alive[best_i]
    _CACHE[key] = best
    if persist:
        try:
            save_cache()
        except OSError:
            pass  # read-only FS: keep the in-process entry
    return dict(best)


# ---------------------------------------------------------------------------
# CLI: fleet artifact maintenance (CI merges per-run caches here).
# ---------------------------------------------------------------------------

def _main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="python -m repro.kernels.autotune")
    sub = ap.add_subparsers(dest="cmd", required=True)
    mg = sub.add_parser("merge", help="merge autotune artifacts "
                                      "(later inputs win; same schema only)")
    mg.add_argument("inputs", nargs="+", help="artifact JSON files")
    mg.add_argument("-o", "--out", required=True, help="merged output path")
    args = ap.parse_args(argv)

    if args.cmd == "merge":
        try:
            path, n = merge_files(args.inputs, args.out)
        except (OSError, ValueError) as e:
            print(f"[autotune] merge failed: {e}")
            return 1
        print(f"[autotune] merged {len(args.inputs)} artifacts "
              f"→ {path} ({n} entries)")
        return 0
    return 2


if __name__ == "__main__":
    raise SystemExit(_main())

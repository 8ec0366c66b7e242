"""Chaos gate: validate a faulted serving run against a clean baseline.

Usage:
    python benchmarks/check_chaos.py CLEAN.json CHAOS.json \
        [--p99-factor=25] [--expect-restart] [--expect-drops]

Both inputs are ``launch.serve --metrics-out`` dumps (the
``engine`` / ``perf`` / ``faults`` / ``ledger`` sections). The gate
asserts the robustness contract the chaos CI job exists to enforce:

* the fault schedule actually executed (fires > 0 — a chaos run whose
  faults never fired proves nothing);
* zero hung tickets and zero lost completions in every arm of the chaos
  run (``completed + errors == submitted``);
* with ``--expect-restart``: at least one worker crash was detected AND
  a replacement worker was spawned;
* with ``--expect-drops``: ledger IO faults were absorbed as dropped
  writes (drop-and-count, not query failures);
* p99 latency under faults stays within ``--p99-factor`` of the clean
  run's p99 (bounded degradation, not collapse into timeouts).

Exit code 0 = all gates pass; 1 = violation (message on stdout).
"""
from __future__ import annotations

import json
import sys

DEFAULT_P99_FACTOR = 25.0


def _fail(msg: str) -> int:
    print(f"[check_chaos] FAIL: {msg}")
    return 1


def check(clean: dict, chaos: dict, p99_factor: float = DEFAULT_P99_FACTOR,
          expect_restart: bool = False, expect_drops: bool = False) -> int:
    fired = sum(v.get("fires", 0)
                for v in chaos.get("faults", {}).values())
    if fired <= 0:
        return _fail("no faults fired in the chaos run "
                     "(is REPRO_FAULTS set?)")

    arms = chaos.get("engine", {})
    if not arms:
        return _fail("chaos dump has no engine snapshots")
    restarts = drops = 0
    for arm, st in arms.items():
        if st["completed"] + st["errors"] != st["submitted"]:
            return _fail(
                f"{arm}: lost completions — completed({st['completed']}) "
                f"+ errors({st['errors']}) != submitted({st['submitted']})")
        restarts += st.get("worker_restarts", 0)
        perf = chaos.get("perf", {}).get(arm, {})
        if perf.get("hung", 0):
            return _fail(f"{arm}: {perf['hung']} hung ticket(s)")
    if expect_restart:
        crashes = sum(st.get("worker_crashes", 0) for st in arms.values())
        if not crashes:
            return _fail("expected a worker kill; no crash was detected")
        if not restarts:
            return _fail(f"{crashes} worker crash(es) but no restarts — "
                         "supervision did not replace the worker")
    if expect_drops:
        drops = (chaos.get("ledger", {}).get("summary", {})
                 .get("dropped_writes", 0))
        if not drops:
            return _fail("expected ledger IO faults to be absorbed as "
                         "dropped writes; none were counted")

    clean_p99 = max(p["p99_ms"]
                    for p in clean.get("perf", {}).values())
    chaos_p99 = max(p["p99_ms"]
                    for p in chaos.get("perf", {}).values())
    if clean_p99 <= 0:
        return _fail("clean run has no p99 to compare against")
    ratio = chaos_p99 / clean_p99
    if ratio > p99_factor:
        return _fail(f"p99 inflated {ratio:.1f}x under faults "
                     f"(bound: {p99_factor:.0f}x; clean={clean_p99:.2f}ms "
                     f"chaos={chaos_p99:.2f}ms)")

    print(f"[check_chaos] OK: {fired} fault(s) fired, no hung tickets, "
          f"no lost completions, worker_restarts={restarts}, "
          f"dropped_writes={drops}, p99 {ratio:.1f}x clean "
          f"(bound {p99_factor:.0f}x)")
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    p99_factor = DEFAULT_P99_FACTOR
    expect_restart = expect_drops = False
    paths = []
    for a in argv:
        if a.startswith("--p99-factor="):
            p99_factor = float(a.split("=", 1)[1])
        elif a == "--expect-restart":
            expect_restart = True
        elif a == "--expect-drops":
            expect_drops = True
        elif a.startswith("-"):
            print(__doc__)
            return 2
        else:
            paths.append(a)
    if len(paths) != 2:
        print(__doc__)
        return 2
    with open(paths[0]) as f:
        clean = json.load(f)
    with open(paths[1]) as f:
        chaos = json.load(f)
    return check(clean, chaos, p99_factor=p99_factor,
                 expect_restart=expect_restart, expect_drops=expect_drops)


if __name__ == "__main__":
    raise SystemExit(main())

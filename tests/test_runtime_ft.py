"""Runtime fault-tolerance trio as the serving tier uses it.

``ServeEngine`` workers heartbeat into a ``HeartbeatMonitor``, the
``FaultCoordinator``'s replace policy names replacement workers, and the
``StragglerDetector`` hands persistent latency outliers to the monitor as
SUSPECT. These tests drive exactly those interactions on a simulated
clock — no sleeps, no real threads — so the state machine the engine's
supervisor depends on is pinned independently of scheduling jitter.
"""
import numpy as np

from repro.runtime.fault_tolerance import (
    FaultCoordinator, HeartbeatMonitor, NodeState,
)
from repro.runtime.straggler import StragglerDetector


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _mon(clock, nodes=("w0", "w1"), suspect=10.0, fail=30.0):
    return HeartbeatMonitor(list(nodes), suspect_after=suspect,
                            fail_after=fail, clock=clock)


# ---------------------------------------------------------------------------
# heartbeat transitions


def test_silence_walks_healthy_suspect_failed():
    clock = Clock()
    m = _mon(clock)
    m.beat("w0")
    clock.t = 12.0                   # w1 silent past suspect_after
    assert m.sweep() == []
    assert m.nodes["w0"].state is NodeState.SUSPECT  # beat at t=0, silent 12
    clock.t = 15.0
    m.beat("w0")
    assert m.nodes["w0"].state is NodeState.HEALTHY  # a beat resets SUSPECT
    clock.t = 31.0
    m.beat("w0")                     # w0 keeps beating; w1 stays silent
    assert m.sweep() == ["w1"]       # 31s of silence → FAILED
    assert m.nodes["w1"].state is NodeState.FAILED
    assert m.healthy() == ["w0"]


def test_force_fail_skips_the_wall_clock_wait():
    # a dead worker thread is proof of failure: the engine force-fails it
    # instead of waiting fail_after real seconds
    clock = Clock()
    m = _mon(clock)
    clock.t = 1.0
    m.force_fail("w1")
    assert m.sweep() == ["w1"]
    assert m.nodes["w0"].state is NodeState.HEALTHY
    m.force_fail("nonexistent")      # unknown node: no-op, no KeyError


def test_external_suspect_is_sticky_until_beat_but_never_unfails():
    clock = Clock()
    m = _mon(clock)
    m.suspect("w0")                  # straggler hand-off
    assert m.nodes["w0"].state is NodeState.SUSPECT
    m.beat("w0")
    assert m.nodes["w0"].state is NodeState.HEALTHY
    m.force_fail("w1")
    m.sweep()
    m.suspect("w1")                  # FAILED is terminal
    assert m.nodes["w1"].state is NodeState.FAILED


def test_add_node_starts_fresh():
    clock = Clock()
    m = _mon(clock)
    clock.t = 29.0
    m.add_node("w2")                 # replacement joins mid-silence-window
    clock.t = 31.0
    assert m.sweep() == ["w0", "w1"]
    assert m.nodes["w2"].state is NodeState.HEALTHY


# ---------------------------------------------------------------------------
# restart policy as the engine drives it


def test_replace_policy_names_replacements_and_rebinds_monitor():
    clock = Clock()
    m = _mon(clock)
    coord = FaultCoordinator(m, reserves=["w2"], min_world=1)
    m.force_fail("w0")
    m.sweep()
    plan = coord.plan(last_ckpt_step=7)
    assert plan.action == "replace"
    assert plan.failed == ["w0"] and plan.replacements == ["w2"]
    assert plan.restore_step == 7
    assert set(m.nodes) == {"w1", "w2"}       # monitor rebound atomically
    assert coord.reserves == []               # reserve consumed
    assert coord.plan().action == "none"      # idempotent after recovery


def test_engine_style_topped_up_reserves_always_replace():
    # the engine tops reserves up to len(failed) before planning, so the
    # policy can never shrink a serving pool
    clock = Clock()
    m = _mon(clock)
    coord = FaultCoordinator(m, reserves=[], min_world=1)
    m.force_fail("w0")
    m.force_fail("w1")
    m.sweep()
    failed = [n for n, i in m.nodes.items() if i.state is NodeState.FAILED]
    nxt = 2
    while len(coord.reserves) < len(failed):
        coord.reserves.append(f"w{nxt}")
        nxt += 1
    plan = coord.plan()
    assert plan.action == "replace"
    assert plan.replacements == ["w2", "w3"]
    assert plan.new_world_size == 2


# ---------------------------------------------------------------------------
# straggler detection feeding SUSPECT


def _feed(det, times_by_host, n=8):
    for _ in range(n):
        for host, t in times_by_host.items():
            det.record(host, t)


def test_persistent_outlier_detected_and_handed_to_monitor():
    clock = Clock()
    m = _mon(clock, nodes=("w0", "w1", "w2", "w3"))
    det = StragglerDetector(["w0", "w1", "w2", "w3"], window=16, persist=3)
    times = {"w0": 0.10, "w1": 0.11, "w2": 0.09, "w3": 0.95}
    slow = []
    for _ in range(4):               # persist=3: needs repeated detection
        _feed(det, times, n=4)
        rep = det.detect()
        slow = rep.slow_hosts
    assert slow == ["w3"]
    assert rep.z_scores["w3"] > det.z
    # the engine's supervisor hand-off:
    for host in slow:
        m.suspect(host)
    assert m.nodes["w3"].state is NodeState.SUSPECT
    assert m.nodes["w0"].state is NodeState.HEALTHY


def test_add_drop_host_follow_worker_replacement():
    det = StragglerDetector(["w0", "w1"], window=8)
    det.record("w0", 0.1)
    det.drop_host("w0")              # retired by the restart policy
    det.record("w0", 0.1)            # late report from the dead worker: ignored
    det.add_host("w2")               # replacement starts a cold window
    assert set(det.times) == {"w1", "w2"}
    assert det.strikes["w2"] == 0
    det.add_host("w2")               # idempotent
    assert det.hosts.count("w2") == 1


def test_too_few_hosts_reports_nothing():
    det = StragglerDetector(["w0"], window=8)
    det.record("w0", 5.0)
    rep = det.detect()
    assert rep.slow_hosts == [] and rep.reassignment == {}


def test_reassignment_prefers_fastest_helper():
    det = StragglerDetector(["w0", "w1", "w2"], window=16, persist=1)
    _feed(det, {"w0": 0.05, "w1": 0.10, "w2": 2.0}, n=8)
    rep = det.detect()
    if rep.slow_hosts:               # robust-z with 3 hosts can be shy
        assert rep.reassignment[rep.slow_hosts[0]] in ("w0", "w1")


def test_recovered_host_strikes_reset():
    det = StragglerDetector(["w0", "w1", "w2", "w3"], window=4, persist=2)
    _feed(det, {"w0": 0.1, "w1": 0.1, "w2": 0.1, "w3": 1.0}, n=4)
    det.detect()
    assert det.strikes["w3"] >= 1
    _feed(det, {"w0": 0.1, "w1": 0.1, "w2": 0.1, "w3": 0.1}, n=4)
    det.detect()
    assert det.strikes["w3"] == 0


# -- heartbeats / restart policy ----------------------------------------------

def test_heartbeat_failure_detection():
    t = [0.0]
    mon = HeartbeatMonitor(["a", "b", "c"], suspect_after=5, fail_after=10,
                           clock=lambda: t[0])
    t[0] = 6.0
    mon.beat("a")
    mon.sweep()
    assert mon.nodes["b"].state is NodeState.SUSPECT
    t[0] = 11.0
    mon.beat("a")
    failed = mon.sweep()
    assert set(failed) == {"b", "c"}
    assert mon.nodes["a"].state is NodeState.HEALTHY


def test_restart_policy_replace_then_shrink():
    t = [0.0]
    mon = HeartbeatMonitor(["a", "b", "c", "d"], fail_after=1,
                           clock=lambda: t[0])
    co = FaultCoordinator(mon, reserves=["r0"], mesh_granularity=1)
    t[0] = 2.0
    mon.beat("a")
    mon.beat("b")
    mon.beat("c")
    mon.sweep()
    plan = co.plan(last_ckpt_step=42)
    assert plan.action == "replace" and plan.replacements == ["r0"]
    assert plan.restore_step == 42
    # second failure: no reserves left → shrink
    t[0] = 4.0
    mon.beat("a")
    mon.beat("b")
    mon.beat("r0")
    mon.sweep()   # c fails
    plan2 = co.plan()
    assert plan2.action == "shrink"
    assert plan2.new_world_size == 3


# -- straggler -----------------------------------------------------------------

def test_straggler_detection():
    hosts = [f"h{i}" for i in range(8)]
    det = StragglerDetector(hosts, z_threshold=3.0, persist=2)
    for step in range(6):
        for h in hosts:
            det.record(h, 1.0 if h != "h3" else 3.0)
        rep = det.detect()
    assert rep.slow_hosts == ["h3"]
    assert "h3" in rep.reassignment


def test_straggler_no_false_positive():
    hosts = [f"h{i}" for i in range(8)]
    det = StragglerDetector(hosts)
    for _ in range(6):
        for i, h in enumerate(hosts):
            det.record(h, 1.0 + 0.01 * i)
    assert det.detect().slow_hosts == []

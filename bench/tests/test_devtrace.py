import glob

import jax
import jax.numpy as jnp
import pytest

from lib import devtrace
from lib.devtrace import TraceEvents

MS = 1_000_000


def events():
    # one chip: ops at [10,20), [15,30) (overlapping), [50,60), [95,120)
    # ms; the window is [0,100) ms
    ops = [("%fusion.1 = f32[8]{0} fusion(x)", 10 * MS, 10 * MS),
           ("%fusion.1 = f32[8]{0} fusion(x)", 15 * MS, 15 * MS),
           ("%sort.2 = (s32[4]{0}, pred[4]{0}) sort(y)", 50 * MS, 10 * MS),
           ("%copy = f32[8]{0} copy(z)", 95 * MS, 25 * MS)]
    host = [("window", 0, 100 * MS),
            ("collect:a", 0, 40 * MS),
            ("update:W", 40 * MS, 20 * MS),
            ("load:W", 60 * MS, 40 * MS)]
    return TraceEvents({"/device:TPU:0": ops}, host)


def test_busy_is_the_union_inside_the_window():
    ev = events()
    lo, hi = devtrace.window(ev)
    assert (lo, hi) == (0, 100 * MS)
    # [10,30) + [50,60) + [95,100)
    assert devtrace.busy_ns(ev, lo, hi) == 35 * MS


def test_busy_averages_over_chips():
    ev = events()
    ev.devices["/device:TPU:1"] = [("%x = f32[1]{0} x()", 0, 100 * MS)]
    assert devtrace.busy_ns(ev, 0, 100 * MS) == (35 + 100) / 2 * MS


def test_op_times_are_clipped_and_grouped():
    t = devtrace.op_times(events(), 0, 100 * MS)
    assert t["%fusion.1 = f32[8]"] == pytest.approx(0.025)
    assert t["%sort.2 = (s32[4], pred[4])"] == pytest.approx(0.010)
    assert t["%copy = f32[8]"] == pytest.approx(0.005)


def test_idle_gaps_are_labelled_by_the_harness():
    ev = events()
    gaps = devtrace.idle_gaps(ev, 0, 100 * MS)
    assert gaps == [(0, 10 * MS), (30 * MS, 50 * MS), (60 * MS, 95 * MS)]
    idle = devtrace.idle_by_label(ev, 0, 100 * MS)
    assert idle == pytest.approx({"collect:a": 0.010, "update:W": 0.020,
                                  "load:W": 0.035})
    assert devtrace.top(idle, 2) == [["load:W", pytest.approx(0.035)],
                                     ["update:W", pytest.approx(0.020)]]


def test_read_finds_the_window_in_a_recorded_trace(tmp_path):
    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("collect:q"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    assert glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    ev = devtrace.read(str(tmp_path))
    lo, hi = devtrace.window(ev)
    assert hi > lo
    assert any(n == "collect:q" for n, _, _ in ev.host)
    # the CPU has no TPU plane: nothing ran on a chip
    assert ev.devices == {}
    assert devtrace.busy_ns(ev, lo, hi) == 0.0

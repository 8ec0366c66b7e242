import types

import jax
import numpy as np
import pytest

from lib import devtrace, spec


class S:
    def __init__(self, name, duration, children=(), **attrs):
        self.name, self.duration = name, duration
        self.children, self.attrs = list(children), attrs


def d2h(ms, view_bytes, what="leaf"):
    return S("d2h", ms / 1e3, what=what, view_bytes=view_bytes)


def scan(ms, elements):
    return S("host_scan", ms / 1e3, what="nnz", elements=elements)


def tree():
    """One window: an optimizer run whose candidates copy and scan under
    ``physical_cost`` / ``mask_propagation``, the executor's own pass,
    and a rebind's nnz read."""
    return S("window", 10.0, [
        d2h(1.0, 4, what="nnz"),
        S("optimize", 5.0, [
            S("physical_cost", 2.0, [
                d2h(0.5, 48, what="mask"),
                S("mask_propagation", 1.5, [d2h(400.0, 3_000_000),
                                            scan(300.0, 750_000)])]),
            S("physical_cost", 1.0, [
                S("mask_propagation", 0.5, [scan(200.0, 750_000)])])]),
        S("mask_propagation", 1.0, [d2h(350.0, 3_000_000),
                                    scan(250.0, 750_000)]),
        S("stage_compile", 0.1),
    ])


def read(metric, root, steps):
    ctx = types.SimpleNamespace(root=root, steps=steps)
    return spec.metric_reader(metric).read(ctx)


def test_copy_time_summed_across_nested_spans_per_step():
    assert read("d2h_ms.iter", tree(), 2) == \
        pytest.approx((1.0 + 0.5 + 400.0 + 350.0) / 2)


def test_scan_time_summed_across_nested_spans_per_step():
    assert read("host_scan_ms.iter", tree(), 4) == \
        pytest.approx((300.0 + 200.0 + 250.0) / 4)


@pytest.mark.parametrize("metric", ["d2h_ms.iter", "host_scan_ms.iter"])
def test_scaling_with_steps(metric):
    one = read(metric, tree(), 1)
    assert read(metric, tree(), 5) == pytest.approx(one / 5)


@pytest.mark.parametrize("metric", ["d2h_ms.iter", "host_scan_ms.iter"])
def test_nothing_without_spans(metric):
    bare = S("window", 10.0, [S("optimize", 5.0, [S("lower", 1.0)])])
    assert read(metric, bare, 3) is None


def test_declared_for_the_pipeline_cell(bench):
    declared = {m["name"]: m for m in bench["per_layer"]}
    for metric in ("d2h_ms.iter", "host_scan_ms.iter"):
        assert declared[metric]["workloads"] == ["netflix-pnmf.iterate"]
        assert declared[metric]["moves"] == "iter_ms"


def test_program_spans_leave_the_harness_labels_alone(tmp_path):
    """The program mirrors its spans onto the profiler. Under a capture
    opened as the harness opens it, the trace still holds one ``window``
    and no label but the harness's own ``collect:N``, so the breakdown
    reads what it read before."""
    from repro.core import Session
    from repro.obs.trace import TRACER

    rng = np.random.default_rng(0)
    a = np.where(rng.random((60, 45)) < 0.2, 3.0, 0.0).astype(np.float32)
    w = rng.uniform(0.5, 1.5, (60, 8)).astype(np.float32)
    h = rng.uniform(0.5, 1.5, (8, 45)).astype(np.float32)
    s = Session(block_size=8, mode="sparse", n_workers=1)
    m = {"A": s.load(a, "A"), "W": s.load(w, "W"), "H": s.load(h, "H")}

    def numerator():
        return m["A"].ediv(m["W"].multiply(m["H"])) \
            .multiply(m["H"].t()).collect()

    numerator()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        tr = TRACER.start("window", sample=True)
        with jax.profiler.TraceAnnotation("window"), TRACER.activate(tr):
            with jax.profiler.TraceAnnotation("collect:N"):
                m["W"] = s.load(w * 1.01, "W")
                numerator()
        tr.finish()
    finally:
        jax.profiler.stop_trace()

    assert {sp.name for sp in tr.spans()} >= {"optimize", "d2h",
                                              "host_scan"}
    events = devtrace.read(str(tmp_path))
    devtrace.window(events)            # exactly one window, or it raises
    assert sorted({n for n, _, _ in events.host}) == ["collect:N", "window"]

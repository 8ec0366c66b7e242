"""Device→host copies and host scans on the query path, and the tracer's
mirror onto the profiler clock.

``d2h`` spans time every host view (``np.asarray``) of a catalog leaf, a
block mask, a binding's nnz or a device COO result: each blocks on the
device, and copies where the array holds no host copy yet. ``host_scan``
spans time every host pass over a fetched leaf: only a COO join of two
leaves makes one (its exact capacity), since a leaf's nnz comes from its
binding. Both are
no-ops unless a trace is active. While one is, every span is also a
``jax.profiler.TraceAnnotation`` of its name, so a profiler capture shows
the program's phases beside the device's ops. Staged plans name each
node's ops with a ``<kind><op_id>`` scope.
"""
from __future__ import annotations

import collections
import glob
import math
import os

import jax
import numpy as np
import pytest

from repro.core import MergeFn, Session
from repro.obs.metrics import REGISTRY
from repro.obs import trace as tracemod
from repro.obs.trace import TRACER, Tracer
from repro.plan import masks as masksmod
from repro.plan import ops as P

BS = 8
USERS, MOVIES, RANK = 60, 45, 8   # not whole blocks, as real sizes are not


def _catalog():
    rng = np.random.default_rng(0)
    a = np.where(rng.random((USERS, MOVIES)) < 0.2,
                 rng.integers(1, 6, (USERS, MOVIES)), 0).astype(np.float32)
    w = rng.uniform(0.5, 1.5, (USERS, RANK)).astype(np.float32)
    h = rng.uniform(0.5, 1.5, (RANK, MOVIES)).astype(np.float32)
    return {"A": a, "W": w, "H": h}


def _numerator(m):
    """PNMF's ``(A ⊘ (W×H)) × Hᵀ`` over the catalog's Matrix handles."""
    return m["A"].ediv(m["W"].multiply(m["H"])).multiply(m["H"].t())


@pytest.fixture
def pnmf():
    """A sparse session over the PNMF catalog, its numerator collected
    once (compiled, memo caches warm), and a rebind-then-collect step:
    the rebind bumps the catalog version, so optimizer, planner and
    executor all run again."""
    cat = _catalog()
    s = Session(block_size=BS, mode="sparse", n_workers=1)
    m = {name: s.load(value, name) for name, value in cat.items()}
    _numerator(m).collect()

    def step(scale=1.01):
        m["W"] = s.load(cat["W"] * scale, "W")
        return _numerator(m).collect()

    return cat, step


def _traced(fn):
    tr = TRACER.start("window", sample=True)
    with TRACER.activate(tr):
        fn()
    tr.finish()
    return tr


def _with_ancestors(root):
    """(span, names of the spans above it) for every span under root."""
    out = []

    def walk(span, above):
        for child in span.children:
            out.append((child, above))
            walk(child, above + (child.name,))

    walk(root, ())
    return out


class _CountingNumpy:
    """``numpy`` for ``plan.masks``, counting the elements it scans."""

    def __init__(self):
        self.elements = 0

    def __getattr__(self, name):
        return getattr(np, name)

    def count_nonzero(self, a, *args, **kwargs):
        self.elements += np.asarray(a).size
        return np.count_nonzero(a, *args, **kwargs)


def _grid(shape):
    return math.ceil(shape[0] / BS) * math.ceil(shape[1] / BS)


def _leaf_nnz_reads():
    return {src: REGISTRY.counter("mask_leaf_nnz", source=src).value
            for src in ("cached", "device")}


def test_rebind_and_collect_read_leaf_nnz_from_the_binding(pnmf,
                                                            monkeypatch):
    cat, step = pnmf
    counting = _CountingNumpy()
    monkeypatch.setattr(masksmod, "np", counting)
    before = _leaf_nnz_reads()
    tr = _traced(step)
    after = _leaf_nnz_reads()
    spans = _with_ancestors(tr.root)
    d2h = [s for s, _ in spans if s.name == "d2h"]
    # no host view of a leaf and no host pass over one: the mask pass
    # reads each leaf's nnz from its binding
    assert {s.attrs["what"] for s in d2h} == {"mask", "nnz"}
    assert not [s for s, _ in spans if s.name == "host_scan"]
    assert counting.elements == 0

    def by(what):
        return [s for s in d2h if s.attrs["what"] == what]

    # block masks (one bool per block): the optimizer's shared Leaves and
    # the executor's own
    assert sum(s.attrs["view_bytes"] for s in by("mask")) == \
        2 * sum(_grid(v.shape) for v in cat.values())
    # the rebind's count, taken on the device by the load
    assert [s.attrs["name"] for s in by("nnz")] == ["W"]
    for s, above in spans:
        if s.name == "d2h" and s.attrs["what"] == "nnz":
            assert "mask_propagation" not in above
    # every leaf count the mask pass read was already kept
    assert after["device"] == before["device"]
    assert after["cached"] > before["cached"]


def test_untraced_collect_opens_no_span(pnmf, monkeypatch):
    cat, step = pnmf
    made = []

    class CountingSpan(tracemod.Span):
        __slots__ = ()

        def __init__(self, name, *args, **kwargs):
            made.append(name)
            super().__init__(name, *args, **kwargs)

    class CountingAnnotation:
        def __init__(self, name):
            made.append(name)

    monkeypatch.setattr(tracemod, "Span", CountingSpan)
    monkeypatch.setattr(tracemod, "TraceAnnotation", CountingAnnotation)
    out = step()
    assert out.shape == (USERS, RANK)
    assert made == []


def _coo_join():
    """A value join whose device COO result comes back to the host."""
    rng = np.random.default_rng(1)

    def sparse(m, n):
        v = rng.normal(size=(m, n)).astype(np.float32)
        return np.where(rng.uniform(size=(m, n)) < 0.3, v, 0.0) \
            .astype(np.float32)

    s = Session(block_size=BS, mode="sparse", n_workers=1)
    a = s.load(sparse(20, 12), "A")
    b = s.load(sparse(20, 9), "B")
    return a.join(b, "RID=RID", MergeFn("xfer_mul", lambda x, y: x * y))


def test_untraced_coo_join_opens_no_span(monkeypatch):
    q = _coo_join()
    made = []
    monkeypatch.setattr(tracemod, "Span",
                        lambda name, *a, **k: made.append(name))
    monkeypatch.setattr(tracemod, "TraceAnnotation", made.append)
    q.collect()                        # cold: copies, scans, COO result
    assert made == []


def test_load_with_given_sparsity_reads_no_nnz():
    s = Session(block_size=BS, mode="sparse", n_workers=1)
    value = _catalog()["W"]
    tr = _traced(lambda: s.load(value, "W", sparsity=1.0))
    assert tr.root.children == []
    tr = _traced(lambda: s.load(value, "W"))
    assert [(sp.name, sp.attrs["what"]) for sp in tr.root.children] == \
        [("d2h", "nnz")]


def test_leaf_view_requested_once_per_leaves():
    cat = _catalog()
    s = Session(block_size=BS, mode="sparse", n_workers=1)
    m = {name: s.load(value, name) for name, value in cat.items()}
    plan = s.physical_plan(s._optimized(_numerator(m).plan))
    leaf, = [n for n in plan.nodes
             if n.kind == P.LEAF and n.expr.name == "A"]
    leaves = masksmod._Leaves(s.env, BS)
    tr = _traced(lambda: [leaves.array(leaf) for _ in range(3)])
    assert [(sp.name, sp.attrs["view_bytes"])
            for sp in tr.root.children] == [("d2h", cat["A"].nbytes)]


def test_coo_join_copies_its_result_and_scans_its_leaves():
    q = _coo_join()
    tr = _traced(q.collect)            # cold: the mask pass runs
    d2h = [s for s in tr.spans() if s.name == "d2h"]
    coo = [s for s in d2h if s.attrs["what"] == "coo"]
    assert len(coo) == 1 and coo[0].attrs["view_bytes"] > 0
    scans = collections.Counter()
    for sp in tr.spans():
        if sp.name == "host_scan":
            scans[sp.attrs["what"]] += sp.attrs["elements"]
    # the exact capacity reads both leaves whole; the side buffers take
    # the leaves' counts from their bindings
    assert scans["exact_cap"] % (20 * 12 + 20 * 9) == 0
    assert set(scans) == {"exact_cap"} and scans["exact_cap"] > 0


def test_mirror_names_only_opened_spans(monkeypatch):
    entered = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(tracemod, "TraceAnnotation", Recorder)
    tracer = Tracer()
    tr = tracer.start("query", sample=True)
    with tracer.activate(tr):
        with tracer.span("optimize", search="memo"):
            tracer.add_event("queue_wait", 0.0, 1.0)
            with tracer.span("d2h", what="leaf", name="A"):
                tracer.annotate(view_bytes=4)
    tr.finish()
    # the root and the after-the-fact event stay off the profiler
    assert entered == ["optimize", "d2h"]
    assert tr.root.children[0].children[1].attrs == \
        {"what": "leaf", "name": "A", "view_bytes": 4}


def test_profiler_capture_nests_spans_in_the_callers_step(pnmf, tmp_path):
    cat, step = pnmf
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        tr = TRACER.start("window", sample=True)
        with jax.profiler.TraceAnnotation("window"):
            with TRACER.activate(tr):
                with jax.profiler.TraceAnnotation("collect:N"):
                    step()
        tr.finish()
    finally:
        jax.profiler.stop_trace()

    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    host = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]
    (_, lo, hi), = [e for e in host if e[0] == "collect:N"]
    inside = {n for n, s, t in host if lo <= s and t <= hi}
    assert {"optimize", "mask_propagation", "d2h"} <= inside
    assert "host_scan" not in inside


def _staged_program(mode):
    cat = _catalog()
    s = Session(block_size=BS, mode=mode, n_workers=1)
    m = {name: s.load(value, name) for name, value in cat.items()}
    q = _numerator(m)
    q.collect()
    plan = s.physical_plan(s._optimized(q.plan))
    key, = s._programs.keys()
    prog = s._programs.get(key)
    lowered = prog.fn.lower(*(s.env[n].value for n in prog.leaf_names))
    return plan, lowered.as_text(debug_info=True)


@pytest.mark.parametrize("mode", ["sparse", "dense"])
def test_staged_ops_carry_their_plan_node(mode):
    plan, text = _staged_program(mode)
    nodes = [n for n in plan.nodes if n.kind != P.LEAF]
    assert nodes
    for node in nodes:
        assert f"/{node.kind}{node.op_id}/" in text, node.label()


def test_mirror_leaves_the_annotation_when_the_span_raises(monkeypatch):
    seen = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, exc_type, *exc):
            seen.append(("exit", self.name, exc_type))

    monkeypatch.setattr(tracemod, "TraceAnnotation", Recorder)
    tracer = Tracer()
    tr = tracer.start("query", sample=True)
    with tracer.activate(tr):
        with pytest.raises(KeyError):
            with tracer.span("d2h", what="leaf"):
                raise KeyError("A")
        assert tracer.current() is tr.root
    tr.finish()
    assert seen == [("enter", "d2h"), ("exit", "d2h", KeyError)]
    assert tr.root.children[0].attrs["error"] == "KeyError"

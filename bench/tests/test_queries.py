"""The ``queries`` shape and the graph500-tc configuration, on the CPU
at tiny sizes: the generator's graph, the check (a planted wrong answer
fails it; the control fails where the program passes) and the work count
the kernel roofline reads."""
import importlib.util

import numpy as np
import pytest

import control
import run
from conftest import TINY, tiny_config
from lib import spec
from lib.backends import ProgramBackend

# graph500-tc at a tiny size. The harness tests that run every cell
# (test_control, test_faults, test_reference) read TINY when they run,
# so in a run that collects this file they find it too; the entry
# belongs in conftest.TINY, which only a benchmark change may edit.
TINY.setdefault("graph500-tc", {"scale": 8, "block_size": 16})

NEW_CELLS = ("graph500-tc.triangles", "netflix-pnmf.score")


def _graph(scale: int, seed: int) -> np.ndarray:
    mod = spec.config_module("graph500-tc")
    cfg = dict(spec.config("graph500-tc"), scale=scale)
    return np.asarray(mod.catalog(cfg, run.seed_key(seed))["A"])


@pytest.mark.parametrize("seed", [1, 2**33 + 7])
def test_generator_makes_a_degree_ordered_simple_graph(seed):
    a = _graph(7, seed)
    assert a.shape == (128, 128) and a.dtype == np.float32
    assert set(np.unique(a)) == {0.0, 1.0}
    assert (a == a.T).all()
    assert not np.diag(a).any()
    degree = a.sum(axis=1)
    assert (np.diff(degree) <= 0).all()
    # at most the 2^7 x 16 generated edges: duplicates collapse, loops go
    assert 0 < a.sum() / 2 <= 16 * 128


def test_generator_draws_a_graph_per_seed():
    assert (_graph(7, 1) != _graph(7, 2)).any()
    assert (_graph(7, 3) == _graph(7, 3)).all()


class Altered:
    """The program's backend with every answer 1% off."""

    def __init__(self, inner):
        self.inner = inner

    @property
    def arrays(self):
        return self.inner.arrays

    def load(self, name, array):
        self.inner.load(name, array)

    def collect(self, expr):
        return self.inner.collect(expr) * 1.01


@pytest.mark.parametrize("planted", [False, True])
@pytest.mark.parametrize("cell", NEW_CELLS)
def test_the_check_sees_a_wrong_answer(bench, cell, planted):
    class Hooks:
        @staticmethod
        def wrap_backend(b):
            assert isinstance(b, ProgramBackend)
            return Altered(b)
    c = spec.cell(bench, cell)
    result, diag = run.run_cell(
        bench, cell, 2**31 + 5, 0.3, False, cfg=tiny_config(c["config"]),
        require_chip=False, hooks=Hooks if planted else None)
    assert result["correct"] is not planted, result["checks"]
    assert diag["steps"] > 0


def test_a_dropped_panel_product_fails_the_check(monkeypatch):
    """The kernel arm with its first live panel product left out of
    every dispatch, the hub tile's first K panel: T and t come out
    short, and the check says so."""
    from repro.kernels import registry, sddmm_agg
    real = sddmm_agg.panel_steps
    dropped = []

    def faulty(*args, **kw):
        steps = real(*args, **kw)
        dropped.append(steps[0].size)
        return tuple(v[1:] for v in steps)
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", registry.INTERPRET)
    # at SCALE 8 in blocks of 8 under half of A's blocks are live, so
    # the fused node keeps the kernel arm
    cfg = dict(tiny_config("graph500-tc"), scale=8, block_size=8)
    traffic = spec.traffic("triangles")
    limits = traffic["check"]["limits"]
    mod = spec.config_module("graph500-tc")
    sound = control.readings(cfg, mod, traffic, 2**31 + 5, program=True)
    assert all(sound[k] <= v for k, v in limits.items()), sound
    monkeypatch.setattr(sddmm_agg, "panel_steps", faulty)
    broken = control.readings(cfg, mod, traffic, 2**31 + 5, program=True)
    assert dropped, "the kernel arm never ran"
    for name in ("answer_rel_err.T", "answer_rel_err.t"):
        assert broken[name] > limits[name], broken
    assert broken["answer_rel_err.d"] == 0.0


@pytest.mark.parametrize("cell", NEW_CELLS)
def test_the_control_fails_where_the_program_passes(bench, cell):
    c = spec.cell(bench, cell)
    cfg = tiny_config(c["config"])
    traffic = spec.traffic(c["traffic"])
    limits = traffic["check"]["limits"]
    assert set(limits) == {f"answer_rel_err.{q}" for q in traffic["queries"]}
    mod = spec.config_module(c["config"])
    for seed in (1, 2**31 + 3):
        program = control.readings(cfg, mod, traffic, seed, program=True)
        ctrl = control.readings(cfg, mod, traffic, seed, program=False)
        assert all(program[k] <= v for k, v in limits.items()), \
            (seed, program)
        assert any(ctrl[k] > v for k, v in limits.items()), (seed, ctrl)


def _queries():
    path = spec.BENCH / "shapes" / "queries.py"
    s = importlib.util.spec_from_file_location("queries_under_test", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def test_work_count_equals_brute_force():
    q = _queries()
    a = _graph(8, 5)
    b = 16
    g = a.shape[0] // b
    live = a.reshape(g, b, g, b).any(axis=(1, 3))
    brute = sum(1 for i in range(g) for k in range(g) for j in range(g)
                if live[i, j] and live[i, k] and live[k, j])
    assert np.array_equal(np.asarray(q.block_mask(a, b)), live)
    assert q.live_triples(live, live, live) == brute
    traffic = spec.traffic("triangles")
    work = q.kernel_work(traffic["queries"], {"A": a}, b)
    assert work == {"flops": 2.0 * b ** 3 * brute,
                    "bytes": 3.0 * a.size * 4}
    # a ragged matrix pads its last blocks with zeros
    assert np.asarray(q.block_mask(a[:100, :70], b)).shape == (7, 5)


def test_roofline_reads_the_kernel_spans(monkeypatch):
    from types import SimpleNamespace

    from lib import peaks
    from repro.obs.trace import Span
    q = spec.shape("queries")
    reader = spec.metric_reader("sddmm_agg_roofline.tri")
    root = Span("window")
    for kernels, dt in (("sddmm_agg", 0.2), ("sddmm_agg", 0.2), (None, 5.0)):
        sp = Span("execute", t0=0.0,
                  attrs={"kernels": kernels} if kernels else {})
        sp.t1 = dt
        root.children.append(sp)
    ctx = SimpleNamespace(root=root, steps=1)
    monkeypatch.setattr(peaks, "for_device",
                        lambda kind=None: peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(q, "KERNEL_WORK", {})
    assert reader.read(ctx) is None      # no work counted: nothing read
    # 10 ms of compute a dispatch at the peak, two dispatches in 0.4 s
    monkeypatch.setattr(q, "KERNEL_WORK", {"flops": 197e12 * 0.01,
                                           "bytes": 1.0})
    assert reader.read(ctx) == pytest.approx(100.0 * 2 * 0.01 / 0.4)
    masked = spec.metric_reader("masked_agg_ms.pass")
    assert masked.read(ctx) is None      # no span names a MASKED_AGG arm
    root.children[0].attrs["masked_agg"] = "kernel"
    assert masked.read(ctx) == pytest.approx(200.0)


def test_panel_reference_equals_the_whole_matrix_reference():
    from lib import panels
    from lib.reference import Reference
    cfg = tiny_config("netflix-pnmf")
    data = spec.config_module("netflix-pnmf").catalog(cfg, run.seed_key(4))
    ref = Reference.for_config(cfg)
    for name, value in data.items():
        ref.load(name, value)
    queries = {
        "a": ["sum", ["emul", "A", ["multiply", "W", "H"]], "a"],
        "r": ["sum", ["emul", "A", ["multiply", "W", "H"]], "r"],
        "c": ["sum", ["ediv", "A", ["multiply", "W", "H"]], "c"],
        "n": ["nnz", ["t", "A"], "r"],
        "s": ["sum", ["multiply", "W", "H"], "a"],
        "d": ["nnz", ["add", "A", ["multiply", "W", "H"]], "c"],
    }
    # 60 rows in panels of 7: the last panel is ragged
    got = panels.evaluate(queries, ref.env, 7)
    for name, expr in queries.items():
        want = ref.eval(expr)
        assert got[name].shape == want.shape, name
        # float32 sums in another order: rounding only
        np.testing.assert_allclose(np.asarray(got[name]),
                                   np.asarray(want), rtol=1e-5, atol=1e-5)

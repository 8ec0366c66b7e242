"""Staged programs are kept by what their trace reads, not by plan object.

A PNMF-shaped session at a tiny size: A sparse (under half its blocks
live, so the masked nodes keep the block-skipping arm), W and H dense and
positive, and the four collects of a PNMF iteration. Rebinding W or H
makes new plans; the programs their traces need are the ones already
built, so none is built or traced again. A rebind that moves a block
mask, or a side capacity, builds anew; and masks are told apart by their
bytes, even where their crc32 checksums collide.
"""
import zlib

import numpy as np
import pytest

from repro.core import Session
from repro.core.matrix import BlockMatrix
from repro.obs.metrics import REGISTRY
from repro.obs.trace import TRACER
from repro.plan import PlanExecutor, program_cache
from repro.plan.executor import program_key

BS = 8
M, N, RANK = 64, 48, 4          # 8 x 6 blocks of A


def _counter(name: str, **labels) -> int:
    tag = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
    return int(REGISTRY.snapshot().get(f"{name}{{{tag}}}", 0))


def _built() -> int:
    return _counter("staged_program", source="built")


def _traces() -> int:
    """How often a staged trace dispatched the masked product: the
    dispatch runs once per trace, never per call."""
    snap = REGISTRY.snapshot()
    return sum(int(v) for k, v in snap.items()
               if k.startswith("kernel_dispatches{")
               and "kernel=masked_matmul" in k)


def _panels() -> tuple:
    return tuple(_counter("sddmm_agg_panels", state=state)
                 for state in ("computed", "skipped"))


def _ratings(rng, live: np.ndarray) -> np.ndarray:
    """A (M, N) matrix whose live blocks are those of ``live``, each
    holding ratings 1-5 at about half its entries."""
    a = np.zeros((M, N), np.float32)
    for i, j in zip(*np.nonzero(live)):
        block = rng.integers(1, 6, size=(BS, BS)).astype(np.float32)
        block[rng.uniform(size=(BS, BS)) < 0.5] = 0.0
        block[0, 0] = 1.0
        a[i * BS:(i + 1) * BS, j * BS:(j + 1) * BS] = block
    return a


def _live(rng, share: float = 0.3) -> np.ndarray:
    return rng.uniform(size=(M // BS, N // BS)) < share


def _factors(rng):
    return (rng.uniform(0.5, 1.5, size=(M, RANK)).astype(np.float32),
            rng.uniform(0.5, 1.5, size=(RANK, N)).astype(np.float32))


def _queries(m):
    """PNMF's four collects: the numerator and denominator of W's update,
    then of H's."""
    a, w, h = m["A"], m["W"], m["H"]
    ratio = a.ediv(w.multiply(h))
    return {"NW": ratio.multiply(h.t()), "DW": h.sum("r"),
            "NH": w.t().multiply(ratio), "DH": w.sum("c")}


def _load(s, arrays, m=None):
    m = dict(m or {})
    for name, value in arrays.items():
        m[name] = s.load(value, name)
    return m


def _collect_all(m) -> dict:
    return {k: np.asarray(q.collect().value) for k, q in _queries(m).items()}


def _check_all(s, m, got=None):
    """Collect every query (unless ``got`` holds the answers) and hold it
    to the tree-walk oracle."""
    got = got or _collect_all(m)
    for k, q in _queries(m).items():
        want = s.execute(q.optimized_plan().plan, optimize=False,
                         engine="tree")
        np.testing.assert_allclose(got[k], np.asarray(want.value),
                                   rtol=1e-5, atol=1e-5)


@pytest.fixture
def pnmf():
    rng = np.random.default_rng(11)
    w, h = _factors(rng)
    s = Session(block_size=BS)
    m = _load(s, {"A": _ratings(rng, _live(rng)), "W": w, "H": h})
    return rng, s, m


def test_rebinding_factors_builds_and_traces_nothing(pnmf):
    rng, s, m = pnmf
    traces = _traces()
    _check_all(s, m, _collect_all(m))
    assert _traces() > traces                    # the first builds traced
    programs, built = len(s._programs), _built()
    cached = _counter("staged_program", source="cached")
    assert programs == 4
    for _ in range(2):
        w, h = _factors(rng)
        m = _load(s, {"W": w}, m)
        m = _load(s, {"H": h}, m)
        # the oracle dispatches too: count the staged collects alone
        traces = _traces()
        got = _collect_all(m)
        assert _traces() == traces
        _check_all(s, m, got)
    assert _built() == built
    assert len(s._programs) == programs
    assert _counter("staged_program", source="cached") == cached + 8


def test_rebinding_to_another_block_mask_builds_anew(pnmf):
    rng, s, m = pnmf
    _check_all(s, m)
    built = _built()
    live = _live(rng)
    assert not np.array_equal(live, np.asarray(s.env["A"].block_mask))
    m = _load(s, {"A": _ratings(rng, live)}, m)
    _check_all(s, m)
    # A's mask reaches the two numerators' traces; the denominators read
    # only W and H, so their programs stay
    assert _built() == built + 2


def _crc_null_bits(nbits: int) -> np.ndarray:
    """A nonzero bit vector d of ``nbits`` (a multiple of 8) with
    crc32(x ^ d) == crc32(x) for every x of that length, found by
    elimination over GF(2): crc32 is affine in its input, so any
    dependent set of the single-bit differences sums to no change."""
    nbytes = nbits // 8
    zero = zlib.crc32(bytes(nbytes))
    basis = {}                       # leading bit -> (value, bits used)
    for i in range(nbits):
        e = bytearray(nbytes)
        e[i // 8] |= 0x80 >> (i % 8)          # np.packbits' bit order
        v, used = zlib.crc32(bytes(e)) ^ zero, 1 << i
        while v:
            top = v.bit_length() - 1
            if top not in basis:
                basis[top] = (v, used)
                break
            bv, bu = basis[top]
            v, used = v ^ bv, used ^ bu
        else:
            return np.array([(used >> k) & 1 for k in range(nbits)], bool)
    raise AssertionError("no dependent set")  # pragma: no cover


def _colliding_masks():
    d = _crc_null_bits((M // BS) * (N // BS))
    on = np.flatnonzero(d)
    m1, m2 = np.zeros_like(d), np.zeros_like(d)
    m1[on[::2]] = True
    m2[on[1::2]] = True
    return (m1.reshape(M // BS, N // BS), m2.reshape(M // BS, N // BS))


def test_masks_whose_crc32_collide_get_their_own_programs(pnmf):
    rng, s, m = pnmf
    m1, m2 = _colliding_masks()
    assert not np.array_equal(m1, m2)
    assert zlib.crc32(np.packbits(m1).tobytes()) == \
        zlib.crc32(np.packbits(m2).tobytes())
    assert 0 < m1.mean() <= 0.5 and 0 < m2.mean() <= 0.5
    a1, a2 = _ratings(rng, m1), _ratings(rng, m2)

    # two plans, one a binding: each gets its own program
    m = _load(s, {"A": a1}, m)
    _check_all(s, m)
    built = _built()
    m = _load(s, {"A": a2}, m)
    _check_all(s, m)
    assert _built() == built + 2

    # one plan object, its leaf swapped under it: the mask pass sees the
    # new mask, and the program key follows it
    q = _queries(m)["NW"]
    plan = s.physical_plan(s._optimized(q.plan))
    programs = program_cache()
    s.env["A"] = BlockMatrix.from_dense(a1, BS)
    PlanExecutor(s.env, programs=programs).run(plan)
    key1 = program_key(plan)
    s.env["A"] = BlockMatrix.from_dense(a2, BS)
    got = PlanExecutor(s.env, programs=programs).run(plan)
    assert program_key(plan) != key1
    assert len(programs) == 2
    want = s.execute(q.optimized_plan().plan, optimize=False, engine="tree")
    np.testing.assert_allclose(np.asarray(got.value),
                               np.asarray(want.value), rtol=1e-5, atol=1e-5)


def test_reused_program_on_a_new_plan_is_warm(pnmf):
    rng, s, m = pnmf
    q = _queries(m)["NW"]
    q.collect()
    plan = s.physical_plan(s._optimized(q.plan))
    w, _ = _factors(rng)
    m = _load(s, {"W": w}, m)
    q = _queries(m)["NW"]
    trace = TRACER.start("q", sample=True)
    with TRACER.activate(trace):
        q.collect()
    trace.finish()
    assert s.physical_plan(s._optimized(q.plan)) is not plan
    spans = list(trace.root.walk())
    executes = [sp for sp in spans if sp.name == "execute"]
    assert [sp.attrs["cold"] for sp in executes] == [False]
    assert "stage_compile" not in {sp.name for sp in spans}


def test_panel_counter_moves_once_per_build(pnmf):
    rng, s, m = pnmf
    a, w, h = m["A"], m["W"], m["H"]
    before = _panels()
    a.emul(w.multiply(h)).sum("a").collect()
    once = _panels()
    assert once[0] > before[0]
    w2, _ = _factors(rng)
    w = s.load(w2, "W")
    a.emul(w.multiply(h)).sum("a").collect()     # same trace: no build
    assert _panels() == once
    a = s.load(_ratings(rng, _live(rng)), "A")
    a.emul(w.multiply(h)).sum("a").collect()     # A's mask moved: a build
    assert _panels()[0] > once[0]

"""HLO parser: scan trip-count scaling, dot flops, collective bytes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo import parse_hlo_module
from repro.analysis.roofline import analyze, model_flops


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_scan_flops_scaled_by_trip_count():
    def body(c, w):
        return jnp.dot(c, w, preferred_element_type=jnp.float32), None

    def f(x, ws):
        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((6, 128, 128), jnp.float32)
    stats = parse_hlo_module(_compile(f, x, ws).as_text())
    want = 6 * 2 * 128 ** 3
    assert abs(stats.dot_flops - want) / want < 0.01
    assert 6 in stats.while_trip_counts.values()


def test_plain_dot_flops():
    a = jax.ShapeDtypeStruct((64, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((32, 48), jnp.float32)
    stats = parse_hlo_module(
        _compile(lambda x, y: x @ y, a, b).as_text())
    assert stats.dot_flops == 2 * 64 * 32 * 48


def test_nested_scan_multiplies():
    def inner(c, w):
        return jnp.dot(c, w, preferred_element_type=jnp.float32), None

    def outer(c, ws):
        y, _ = jax.lax.scan(inner, c, ws)
        return y, None

    def f(x, ws):
        y, _ = jax.lax.scan(lambda c, _: outer(c, ws), x, None, length=3)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((4, 64, 64), jnp.float32)
    stats = parse_hlo_module(_compile(f, x, ws).as_text())
    want = 3 * 4 * 2 * 64 ** 3
    assert abs(stats.dot_flops - want) / want < 0.02


def test_collective_bytes_from_synthetic_hlo():
    text = """
HloModule test

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %sum = f32[] add(%a, %b)
}

ENTRY %main (p0: f32[1024,256]) -> f32[1024,256] {
  %p0 = f32[1024,256]{1,0} parameter(0)
  %ar = f32[1024,256]{1,0} all-reduce(%p0), replica_groups={}, to_apply=%add
  %ag = f32[2048,256]{1,0} all-gather(%ar), dimensions={0}
  ROOT %out = f32[1024,256]{1,0} slice(%ag), slice={[0:1024], [0:256]}
}
"""
    stats = parse_hlo_module(text)
    assert stats.collective_breakdown["all-reduce"] == 1024 * 256 * 4
    assert stats.collective_breakdown["all-gather"] == 1024 * 256 * 4
    assert stats.collective_bytes == 2 * 1024 * 256 * 4


def test_traffic_fusion_model_chains():
    """An elementwise chain is one group: traffic ≈ inputs + final output,
    not per-op."""
    def f(x):
        return jnp.tanh(x * 2.0 + 1.0) * x  # multi-consumer x, one group

    x = jax.ShapeDtypeStruct((1 << 20,), jnp.float32)
    stats = parse_hlo_module(_compile(f, x).as_text())
    nbytes = (1 << 20) * 4
    # read x once + write output once (within 3x slack for backend noise)
    assert stats.bytes_accessed <= 3 * 2 * nbytes


def test_roofline_terms():
    from repro.analysis.hlo import HloStats
    st = HloStats(flops=197e12, bytes_accessed=819e9,
                  collective_bytes=25e9)
    r = analyze(st, model_flops_total=197e12 * 256, n_chips=256,
                device_kind="TPU v5 lite")
    assert abs(r.compute_s - 1.0) < 1e-6
    assert abs(r.memory_s - 1.0) < 1e-6
    assert abs(r.collective_s - 0.5) < 1e-6
    assert r.dominant in ("compute", "memory")
    assert abs(r.mfu - 1.0) < 1e-3


def test_roofline_unknown_device_kind_is_an_error():
    from repro.analysis.hlo import HloStats
    st = HloStats(flops=1.0, bytes_accessed=1.0, collective_bytes=0.0)
    with pytest.raises(KeyError):
        analyze(st, model_flops_total=1.0, n_chips=1, device_kind="cpu")


def test_model_flops_moe_discount():
    from repro.analysis.roofline import active_param_count
    from repro.configs import get_config
    from repro.models import api as mapi
    cfg = get_config("mixtral-8x7b")
    sp = mapi.spec(cfg)
    total = active_param_count(sp)
    active = active_param_count(sp, cfg.moe.top_k, cfg.moe.n_experts)
    assert active < total * 0.45  # 2-of-8 experts + shared attention


# ---------------------------------------------------------------------------
# Golden feature-vector extraction (the calibrated cost model's inputs).
# ---------------------------------------------------------------------------

GOLDEN_DOT = """
HloModule t

ENTRY %main (a: f32[64,32], b: f32[32,48]) -> f32[64,48] {
  %a = f32[64,32]{1,0} parameter(0)
  %b = f32[32,48]{1,0} parameter(1)
  ROOT %d = f32[64,48]{1,0} dot(%a, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""

GOLDEN_FUSION = """
HloModule t

%fused (p0: f32[128,128]) -> f32[128,128] {
  %p0 = f32[128,128]{1,0} parameter(0)
  %e = f32[128,128]{1,0} exponential(%p0)
  ROOT %a = f32[128,128]{1,0} add(%e, %p0)
}

ENTRY %main (x: f32[128,128]) -> f32[128,128] {
  %x = f32[128,128]{1,0} parameter(0)
  ROOT %f = f32[128,128]{1,0} fusion(%x), kind=kLoop, calls=%fused
}
"""

GOLDEN_WHILE = """
HloModule t

%body (p: (s32[], f32[64,64])) -> (s32[], f32[64,64]) {
  %p = (s32[], f32[64,64]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[64,64]{1,0} get-tuple-element(%p), index=1
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  %t = f32[64,64]{1,0} tanh(%x)
  ROOT %r = (s32[], f32[64,64]) tuple(%ni, %t)
}

%cond (p: (s32[], f32[64,64])) -> pred[] {
  %p = (s32[], f32[64,64]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (x: f32[64,64]) -> (s32[], f32[64,64]) {
  %x = f32[64,64]{1,0} parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[64,64]) tuple(%zero, %x)
  ROOT %w = (s32[], f32[64,64]) while(%init), condition=%cond, body=%body
}
"""

GOLDEN_ALLREDUCE = """
HloModule test

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %sum = f32[] add(%a, %b)
}

ENTRY %main (p0: f32[1024,256]) -> f32[1024,256] {
  %p0 = f32[1024,256]{1,0} parameter(0)
  ROOT %ar = f32[1024,256]{1,0} all-reduce(%p0), replica_groups={}, to_apply=%add
}
"""


def test_feature_schema_matches_calibrate():
    """The HLO extractor and the cost model must agree on the feature
    schema — a silent rename would corrupt every fitted coefficient."""
    from repro.analysis.hlo import FEATURE_NAMES, HloStats
    from repro.core.calibrate import FEATURES
    assert FEATURE_NAMES == FEATURES
    assert tuple(HloStats().feature_vector()) == FEATURES


def test_features_golden_dot():
    fv = parse_hlo_module(GOLDEN_DOT).feature_vector()
    assert fv["dot_flops"] == 2 * 64 * 32 * 48
    assert fv["ew_flops"] == 0.0
    assert fv["transcendentals"] == 0.0
    assert fv["comm_bytes"] == 0.0
    assert fv["ops"] == 1.0       # the dot; parameters are free


def test_features_golden_fusion():
    """A fusion is ONE launch; its internals contribute flops and
    transcendentals but not op count."""
    fv = parse_hlo_module(GOLDEN_FUSION).feature_vector()
    n = 128 * 128
    assert fv["ops"] == 1.0
    assert fv["transcendentals"] == n          # the fused exponential
    assert fv["ew_flops"] == 2 * n             # exp + add, 1 flop/elem
    assert fv["dot_flops"] == 0.0


def test_features_golden_while_trip_scaling():
    """Body features scale by the detected trip count (5): tanh elements,
    flops and the per-iteration launches."""
    stats = parse_hlo_module(GOLDEN_WHILE)
    fv = stats.feature_vector()
    n = 64 * 64
    assert 5 in stats.while_trip_counts.values()
    assert fv["transcendentals"] == 5 * n
    # per iteration: tanh (n) + s32 add (1); plus nothing at top level
    # but the while op itself
    assert fv["ew_flops"] == 5 * (n + 1)
    assert fv["ops"] == 1 + 5 * 2              # while + (add, tanh) x 5


def test_features_golden_allreduce():
    fv = parse_hlo_module(GOLDEN_ALLREDUCE).feature_vector()
    assert fv["comm_bytes"] == 1024 * 256 * 4
    assert fv["nnz"] == 0.0                    # no HLO counterpart


def test_features_stable_across_parses():
    """Same text → identical vector (the corpus must be reproducible)."""
    a = parse_hlo_module(GOLDEN_FUSION).feature_vector()
    b = parse_hlo_module(GOLDEN_FUSION).feature_vector()
    assert a == b

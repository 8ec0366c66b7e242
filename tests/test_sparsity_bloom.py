"""Sparsity-inducing merge detection (§4.7) + Bloom filter properties."""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import bloom
from repro.core.expr import MergeFn
from repro.core.sparsity import (
    analyze_merge, product_merge, safe_div_merge, sum_merge,
)


def test_product_is_inducing_both_sides():
    p = analyze_merge(product_merge())
    assert p.inducing_x and p.inducing_y


def test_sum_is_not_inducing():
    p = analyze_merge(sum_merge())
    assert not p.inducing_x and not p.inducing_y


def test_left_linear_combination():
    """f(x,y) = g(x)·y + h(x) with g(0)=h(0)=0 ⇒ inducing on x."""
    f = MergeFn("gxy", lambda x, y: (3 * x) * y + 2 * x)
    p = analyze_merge(f)
    assert p.inducing_x and not p.inducing_y


def test_same_name_different_merges_profiled_apart():
    """``Matrix.join`` names every plain callable "f": the profile of one
    must not be served for another."""
    add = analyze_merge(MergeFn("f", lambda x, y: x + y))
    mul = analyze_merge(MergeFn("f", lambda x, y: x * y))
    assert not add.inducing_x and not add.inducing_y
    assert mul.inducing_x and mul.inducing_y


def test_safe_div_inducing_on_numerator():
    p = analyze_merge(safe_div_merge())
    assert p.inducing_x


@settings(max_examples=100, deadline=None)
@given(g0=st.floats(-5, 5), g1=st.floats(-5, 5), h0=st.floats(-5, 5),
       h1=st.floats(-5, 5))
def test_linear_family_sampling_exact(g0, g1, h0, h1):
    """For f(x,y) = (g0 + g1·x)·y + (h0 + h1·x), the sampling test must
    equal the analytic condition g(0)=h(0)=0 ⟺ g0=0 ∧ h0=0 (paper §4.7)."""
    name = f"lin_{g0}_{g1}_{h0}_{h1}"
    f = MergeFn(name, lambda x, y: (g0 + g1 * x) * y + (h0 + h1 * x))
    p = analyze_merge(f)
    assert p.inducing_x == (g0 == 0 and h0 == 0)


# -- bloom --------------------------------------------------------------------

def test_bloom_no_false_negatives(rng):
    vals = jnp.asarray(np.round(rng.normal(size=4096), 2).astype(np.float32))
    nz = vals[vals != 0]
    params = bloom.BloomParams(log2_bits=16, num_hashes=3)
    words = bloom.build(vals, params)
    hits = bloom.probe(words, nz, params)
    assert bool(jnp.all(hits))  # every inserted value must probe positive


def test_bloom_false_positive_rate(rng):
    members = jnp.asarray(rng.normal(size=2048).astype(np.float32))
    others = jnp.asarray(rng.normal(size=4096).astype(np.float32) + 100.0)
    params = bloom.BloomParams(log2_bits=16, num_hashes=3)
    words = bloom.build(members, params)
    fp = float(jnp.mean(bloom.probe(words, others, params)))
    # 2048·3 bits in 65536: theoretical fp ≈ (1−e^(−3·2048/65536))³ ≈ 6e-4
    assert fp < 0.05


def test_bloom_skip_zeros():
    vals = jnp.asarray(np.array([0.0, 1.0, 2.0], np.float32))
    params = bloom.BloomParams(log2_bits=12, num_hashes=2)
    w_skip = bloom.build(vals, params, skip_zeros=True)
    assert not bool(bloom.probe(w_skip, jnp.zeros((1,)), params)[0])
    w_keep = bloom.build(vals, params, skip_zeros=False)
    assert bool(bloom.probe(w_keep, jnp.zeros((1,)), params)[0])


def test_pack_bits_roundtrip(rng):
    bits = jnp.asarray(rng.uniform(size=4096) < 0.3)
    words = bloom.pack_bits(bits)
    # unpack and compare
    shifts = jnp.arange(32, dtype=jnp.uint32)
    unpacked = ((words[:, None] >> shifts) & 1).astype(bool).reshape(-1)
    assert bool(jnp.all(unpacked == bits))

"""BlockMatrix storage invariants — the lazy mask and nnz caches under
tracing.

Regression for the cache-poisoning bug: ``block_mask`` assigned ``_mask``
on first access, so a first access inside ``jit``/``vmap`` cached a tracer
on the instance; if that instance outlived the trace (captured by any
Python-side structure), later eager access returned a leaked tracer. The
exact nnz cache keeps the same rule, and stays out of the pytree."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.matrix import BlockMatrix, compute_block_mask, nnz_partials


def test_block_mask_eager_access_caches():
    bm = BlockMatrix.from_dense(jnp.eye(16), 8)
    m = bm.block_mask
    assert bm._mask is not None
    assert m is bm.block_mask  # second access hits the cache


def test_block_mask_not_cached_under_tracing():
    captured = []

    def f(v):
        bm = BlockMatrix(v, None, 8)
        captured.append(bm)
        return bm.block_mask.astype(jnp.float32).sum()

    out = jax.jit(f)(jnp.eye(16))
    assert float(out) == 2.0  # only the two diagonal blocks are live
    # the instance created under the trace must not retain a tracer
    assert captured[0]._mask is None
    assert isinstance(captured[0].value, jax.core.Tracer)


def test_block_mask_correct_inside_and_outside_jit():
    v = jnp.zeros((16, 16)).at[0, 0].set(1.0)

    def nnz_blocks(arr):
        return BlockMatrix(arr, None, 8).block_mask.sum()

    eager = BlockMatrix.from_dense(v, 8).block_mask
    jitted = jax.jit(lambda a: BlockMatrix(a, None, 8).block_mask)(v)
    np.testing.assert_array_equal(np.asarray(eager), np.asarray(jitted))
    assert int(jax.jit(nnz_blocks)(v)) == 1


def test_block_mask_vmap_first_then_eager():
    """First access under vmap tracing, then eager use of a *fresh* mask
    computation on the same values — must agree and stay concrete."""
    vals = jnp.stack([jnp.eye(16), jnp.zeros((16, 16))])

    def f(v):
        return BlockMatrix(v, None, 8).block_mask

    batched = jax.vmap(f)(vals)
    assert batched.shape == (2, 2, 2)
    single = compute_block_mask(vals[0], 8)
    np.testing.assert_array_equal(np.asarray(batched[0]),
                                  np.asarray(single))


def _sparse(seed, shape=(20, 13), density=0.3):
    rng = np.random.default_rng(seed)
    v = rng.normal(size=shape).astype(np.float32)
    return np.where(rng.random(shape) < density, v, 0.0).astype(np.float32)


def test_nnz_count_exact_and_kept():
    v = _sparse(0)
    bm = BlockMatrix.from_dense(v, 8)
    assert bm._nnz is None
    assert bm.nnz_count() == np.count_nonzero(v)
    assert isinstance(bm.nnz_count(), int)
    assert bm._nnz == np.count_nonzero(v)


def test_nnz_cache_stays_out_of_the_pytree():
    a = BlockMatrix.from_dense(_sparse(1, density=0.2), 8)
    b = BlockMatrix.from_dense(_sparse(2, density=0.6), 8)
    assert a.nnz_count() != b.nnz_count()
    # neither a child nor aux data: one treedef for both counts
    assert a.tree_flatten()[1] == b.tree_flatten()[1] == (8, "xi")
    assert jax.tree_util.tree_structure(a) == \
        jax.tree_util.tree_structure(b)
    traces = []

    @jax.jit
    def total(bm):
        traces.append(bm)
        return bm.value.sum()

    for bm in (a, b, a):
        np.testing.assert_allclose(float(total(bm)),
                                   float(np.asarray(bm.value).sum()),
                                   rtol=1e-5)
    assert len(traces) == 1
    leaf = jax.tree_util.tree_map(lambda x: x, a)
    assert leaf._nnz is None and leaf.nnz_count() == a.nnz_count()


def test_nnz_count_not_cached_under_tracing():
    v = _sparse(3)
    captured = []

    def f(value):
        bm = BlockMatrix(value, None, 8)
        captured.append(bm)
        return bm.nnz_count()

    assert int(jax.jit(f)(v)) == np.count_nonzero(v)
    assert captured[0]._nnz is None
    # a concrete binding asked for its count while another function is
    # traced keeps nothing either
    outer = BlockMatrix.from_dense(v, 8)
    assert int(jax.jit(lambda x: x + outer.nnz_count())(0)) == \
        np.count_nonzero(v)
    assert outer._nnz is None
    assert outer.nnz_count() == np.count_nonzero(v)


def test_nnz_count_kept_exact_by_with_scheme_and_refreshed():
    v = _sparse(4)
    bm = BlockMatrix.from_dense(v, 8)
    bm.nnz_count()
    moved = bm.with_scheme("r")
    assert moved.scheme == "r" and moved._nnz == np.count_nonzero(v)
    fresh = bm.refreshed()
    assert fresh.nnz_count() == np.count_nonzero(v)
    untouched = BlockMatrix.from_dense(v, 8).with_scheme("c")
    assert untouched._nnz is None
    assert untouched.nnz_count() == np.count_nonzero(v)


@pytest.mark.parametrize("limit", [1, 7, 13, 40, 2 ** 31 - 1])
@pytest.mark.parametrize("shape", [(20, 13), (1, 5), (9, 1), (0, 4)])
def test_nnz_partials_split_rows_under_the_limit(shape, limit):
    v = _sparse(5, shape, density=0.7)
    parts = np.asarray(nnz_partials(jnp.asarray(v), limit=limit))
    assert int(parts.sum(dtype=np.int64)) == np.count_nonzero(v)
    # a group is whole rows, as many as fit under the limit
    per = max(1, limit // max(1, shape[1]))
    assert len(parts) == -(-shape[0] // per)
    if shape[1] <= limit:
        assert (parts <= limit).all()

"""Block matrix storage (paper §5.1), adapted to JAX.

A ``BlockMatrix`` stores a dense backing array plus an explicit block-level
nonzero mask — the TPU-native analogue of the paper's CSR/CSC local blocks
(DESIGN.md §2): zero blocks are never touched by the sparsity-aware kernels,
while nonzero blocks stay dense so the MXU sees aligned tiles. NULL ≡ implicit
zero, matching the paper's sparse-overlay semantics (Fig. 4; Γnnz counts
nonzeros, Γavg divides by nnz).

The class is a pytree, so BlockMatrix flows through jit/vmap/shard_map.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_BLOCK = 256  # MXU-aligned (multiple of 128); paper used 1000 for CPU


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BlockMatrix:
    """Dense value + block nonzero mask + partitioning scheme tag.

    The mask is computed LAZILY on first access: dense-only pipelines never
    pay the O(mn) mask scan, while the sparsity-aware paths (block-skip
    joins, masked matmul) get it cached. The exact nonzero count
    (``nnz_count``) is cached the same way, as a host int; it stays out
    of the pytree, so staged programs never see it.
    """

    value: jnp.ndarray            # [m, n]
    _mask: Optional[jnp.ndarray] = None   # [mb, nb] bool (lazy cache)
    block_size: int = DEFAULT_BLOCK
    scheme: str = "xi"            # paper partitioning scheme tag (r/c/b/xi)
    _nnz: Optional[int] = dataclasses.field(  # exact nnz (lazy host cache)
        default=None, repr=False, compare=False)

    @property
    def block_mask(self) -> jnp.ndarray:
        if self._mask is None:
            mask = compute_block_mask(self.value, self.block_size)
            if isinstance(self.value, jax.core.Tracer):
                # first access under jit/vmap tracing: caching would leak
                # the tracer into later eager use of this (leaked) instance
                return mask
            self._mask = mask
        return self._mask

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        return (self.value, self._mask), (self.block_size, self.scheme)

    @classmethod
    def tree_unflatten(cls, aux, children):
        value, block_mask = children
        return cls(value, block_mask, aux[0], aux[1])

    # -- constructors ---------------------------------------------------------
    @classmethod
    def from_dense(cls, value, block_size: int = DEFAULT_BLOCK,
                   scheme: str = "xi") -> "BlockMatrix":
        value = jnp.asarray(value)
        assert value.ndim == 2
        return cls(value, None, block_size, scheme)

    @classmethod
    def random_sparse(cls, key, m: int, n: int, sparsity: float,
                      block_size: int = DEFAULT_BLOCK,
                      scheme: str = "xi") -> "BlockMatrix":
        """Uniform sparse matrix à la the paper's u* datasets."""
        kv, km = jax.random.split(key)
        vals = jax.random.normal(kv, (m, n), jnp.float32)
        keep = jax.random.uniform(km, (m, n)) < sparsity
        return cls.from_dense(jnp.where(keep, vals, 0.0), block_size, scheme)

    # -- shape helpers --------------------------------------------------------
    @property
    def shape(self) -> Tuple[int, int]:
        return tuple(self.value.shape)  # type: ignore[return-value]

    @property
    def grid(self) -> Tuple[int, int]:
        return tuple(self.block_mask.shape)  # type: ignore[return-value]

    @property
    def dtype(self):
        return self.value.dtype

    def nnz(self) -> jnp.ndarray:
        return jnp.sum(self.value != 0)

    def nnz_count(self) -> int:
        """Exact nonzero count as a Python int, counted on the device on
        first use and kept: ``value`` never changes, so the count holds
        for the instance's life. Asked for under a trace, it is the
        traced ``nnz()`` and nothing is kept."""
        if self._nnz is None:
            partials = nnz_partials(self.value)
            if isinstance(partials, jax.core.Tracer):
                return self.nnz()
            self._nnz = int(np.asarray(partials).sum(dtype=np.int64))
        return self._nnz

    def nnz_blocks(self) -> jnp.ndarray:
        return jnp.sum(self.block_mask)

    def density(self) -> float:
        return float(self.nnz()) / max(1, self.value.size)

    def with_scheme(self, scheme: str) -> "BlockMatrix":
        return BlockMatrix(self.value, self._mask, self.block_size,
                           scheme, self._nnz)

    def to_dense(self) -> jnp.ndarray:
        return self.value

    # -- mask-consistent rebuild ----------------------------------------------
    def refreshed(self) -> "BlockMatrix":
        return BlockMatrix.from_dense(self.value, self.block_size, self.scheme)


_INT32_MAX = 2 ** 31 - 1


@functools.partial(jax.jit, static_argnames="limit")
def nnz_partials(value: jnp.ndarray, limit: int = _INT32_MAX) -> jnp.ndarray:
    """Nonzero counts of ``value`` [m, n] in groups of whole rows, each
    group small enough that its count is at most ``limit``; the groups
    add up to the exact nnz on the host in int64. With x64 off the
    device sums in int32, which a single sum over a matrix of 2**31
    entries or more would wrap (a float32 matrix filling a 16-GB chip
    has 4.3e9)."""
    m, n = value.shape
    rows = jnp.sum(value != 0, axis=1)
    per = max(1, limit // max(1, n))      # rows per group
    groups = _ceil_div(m, per)
    rows = jnp.pad(rows, (0, groups * per - m))
    return rows.reshape(groups, per).sum(axis=1)


def compute_block_mask(value: jnp.ndarray, block_size: int) -> jnp.ndarray:
    m, n = value.shape
    mb, nb = _ceil_div(m, block_size), _ceil_div(n, block_size)
    pm, pn = mb * block_size - m, nb * block_size - n
    padded = jnp.pad(value, ((0, pm), (0, pn)))
    tiles = padded.reshape(mb, block_size, nb, block_size)
    return jnp.any(tiles != 0, axis=(1, 3))


def blocks_of(value: jnp.ndarray, block_size: int) -> jnp.ndarray:
    """Reshape [m, n] (padded) into [mb, nb, bs, bs] tiles."""
    m, n = value.shape
    mb, nb = _ceil_div(m, block_size), _ceil_div(n, block_size)
    padded = jnp.pad(value, ((0, mb * block_size - m),
                             (0, nb * block_size - n)))
    return padded.reshape(mb, block_size, nb, block_size).transpose(0, 2, 1, 3)


def unblock(tiles: jnp.ndarray, m: int, n: int) -> jnp.ndarray:
    """Inverse of ``blocks_of``: [mb, nb, bs, bs] → [m, n]."""
    mb, nb, bs, _ = tiles.shape
    full = tiles.transpose(0, 2, 1, 3).reshape(mb * bs, nb * bs)
    return full[:m, :n]


# ---------------------------------------------------------------------------
# Block-mask algebra (plan-time, host numpy): the closed set of rules by
# which block nonzero masks propagate through operators. A mask is a
# CONSERVATIVE certificate — ``mask[i, j] == False`` guarantees block
# (i, j) is all zeros; True only means "possibly nonzero". Every rule
# below preserves that invariant (no false negatives), which is what lets
# the staged executor skip dead blocks and size COO capacities soundly
# (``repro.plan.masks`` runs these over the physical DAG).
# ---------------------------------------------------------------------------

def mask_grid(shape: Tuple[int, int], block_size: int) -> Tuple[int, int]:
    return (_ceil_div(shape[0], block_size), _ceil_div(shape[1], block_size))


def mask_ones(shape: Tuple[int, int], block_size: int) -> np.ndarray:
    return np.ones(mask_grid(shape, block_size), bool)


def mask_matmul(ma: np.ndarray, mb: np.ndarray) -> np.ndarray:
    """Block mask of A×B: out[i,j] = ∨_k (ma[i,k] ∧ mb[k,j])."""
    return (ma.astype(np.int64) @ mb.astype(np.int64)) > 0


def mask_overlay(inducing_x: bool, inducing_y: bool, ma: np.ndarray,
                 mb: np.ndarray) -> np.ndarray:
    """Block mask of an overlay f(A, B) under f's sparsity profile:
    inducing on both sides ⇒ ma ∧ mb; on one ⇒ that side's mask;
    non-inducing f can be nonzero anywhere (f(0,0) ≠ 0 is allowed)."""
    if inducing_x and inducing_y:
        return ma & mb
    if inducing_x:
        return ma.copy()
    if inducing_y:
        return mb.copy()
    return np.ones_like(ma)


def _block_extents(dim: int, blocks: int, block_size: int) -> np.ndarray:
    """Entry count of each block along one axis (the last one is ragged)."""
    ext = np.full(blocks, block_size, np.int64)
    if blocks:
        ext[-1] = dim - (blocks - 1) * block_size
    return ext


def mask_nnz_cap(mask: np.ndarray, shape: Tuple[int, int],
                 block_size: int) -> float:
    """Upper bound on nnz implied by a block mask (ragged edges counted)."""
    rh = _block_extents(shape[0], mask.shape[0], block_size)
    cw = _block_extents(shape[1], mask.shape[1], block_size)
    return float((rh[:, None] * cw[None, :])[mask].sum())


def mask_band_nnz_caps(mask: np.ndarray, shape: Tuple[int, int],
                       block_size: int) -> np.ndarray:
    """Per-block-row nnz upper bounds (for keyed-join capacity bounds)."""
    rh = _block_extents(shape[0], mask.shape[0], block_size)
    cw = _block_extents(shape[1], mask.shape[1], block_size)
    return (mask * cw[None, :]).sum(axis=1) * rh


# ---------------------------------------------------------------------------
# Tensors (join outputs of order 3/4): dense backing + COO view (paper §5.1
# stores tensors as matrix-block slices keyed by a non-aggregated dimension;
# our dense layout keeps D1 leading for the same locality reason).
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BlockTensor:
    value: jnp.ndarray            # order-3 or order-4 dense backing
    dim_names: Tuple[str, ...]    # e.g. ("D1", "D2", "D3")

    def tree_flatten(self):
        return (self.value,), (self.dim_names,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux[0])

    @property
    def shape(self):
        return tuple(self.value.shape)

    @property
    def order(self):
        return self.value.ndim

    def to_dense(self):
        return self.value

    def to_coo(self) -> Tuple[np.ndarray, np.ndarray]:
        """Materialize (indices [nnz, order], values [nnz]) on host."""
        host = np.asarray(self.value)
        idx = np.argwhere(host != 0)
        return idx, host[tuple(idx.T)]

    def aggregate(self, fn: str, axis: int) -> jnp.ndarray:
        v = self.value
        if fn == "sum":
            return jnp.sum(v, axis=axis)
        if fn == "max":
            return jnp.max(v, axis=axis)
        if fn == "min":
            return jnp.min(v, axis=axis)
        if fn == "nnz":
            return jnp.sum((v != 0), axis=axis)
        raise ValueError(fn)

"""Fused SDDMM + aggregation Pallas kernel.

The PNMF-style pipeline ``Agg(sp ∘ (W × H))`` (paper §6) previously ran in
two materializing stages: a block-masked matmul producing the full masked
m×n product, then a dense aggregation pass re-reading it. For SUM
aggregation the product is only ever consumed by the reduction, so this
kernel computes each unmasked (bm, bn) output tile of ``sp ∘ (W·H)``
in-register and folds it straight into the (row / column / scalar)
accumulator — the m×n masked product never exists in memory.

Two implementations share the contract:

* ``sddmm_agg_ref`` — the *factorized* dense oracle. Algebra, not tiling:
  ``rowsum(sp ∘ (W·H)) = rowsum(W ∘ (sp·Hᵀ))`` (and the transposed
  identity for columns), so even the reference path peaks at an m×k / k×n
  intermediate instead of m×n. This is also the fast CPU path the
  benchmark's ≥1.3× claim measures against materialize-then-aggregate.
* ``sddmm_agg_pallas`` — the tiled kernel. The contraction width K is
  split into panels of ``bk``, and the grid walks only the *live panel
  products*: the block triples (I, J, K) for which sp(I, J), W(I, K) and
  H(K, J) are all live. They are listed on the host from the plan-time
  block masks (``panel_steps``), sorted so that the steps of one output
  tile are consecutive, and ride in SMEM as scalar-prefetch tables; the
  index maps read them, so a dead panel is neither fetched nor
  multiplied, and a dead output tile costs no grid step at all. Each
  tile's W·H products accumulate in a VMEM f32 scratch and fold as
  ``sp ∘ tile`` into the row, column or scalar accumulator at the tile's
  last panel. Where K ≤ ``bk`` (a factor rank, as in PNMF) a tile has one
  panel: one dot over the whole width, then the fold.

``dim`` is one of ``"row"`` (out [m, 1]), ``"col"`` (out [1, n]),
``"all"`` (out [1, 1]) — matching ``core.executor.agg_dense``'s output
shapes for ``AggFn.SUM``. Only SUM fuses: the other aggregates mask by
*presence* (``v != 0``), which needs the materialized product.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DIMS = ("row", "col", "all")

# Most grid steps per pallas_call. The two int32 step tables of a call
# ride in SMEM, which holds 1 MiB on a TPU v5e: 2 × 32768 × 4 B = 256 KiB
# of it. Longer step lists run as a scan over calls of equal length.
CHUNK_STEPS = 32768


def sddmm_agg_ref(sp: jnp.ndarray, w: jnp.ndarray, h: jnp.ndarray,
                  dim: str) -> jnp.ndarray:
    """Factorized oracle: never forms the m×n product.

    ``rowsum_j sp[i,j]·(W·H)[i,j] = Σ_k W[i,k]·(sp·Hᵀ)[i,k]`` — one
    sp-shaped matmul down to the k-width panel, then an elementwise
    reduce. Rounding differs from materialize-then-aggregate (different
    summation order), so parity checks use tolerances.
    """
    if dim == "row":
        return jnp.sum(w * (sp @ h.T), axis=1)[:, None]
    if dim == "col":
        return jnp.sum(h * (w.T @ sp), axis=0)[None, :]
    if dim == "all":
        return jnp.sum(w * (sp @ h.T)).reshape(1, 1)
    raise ValueError(f"dim {dim!r} not in {DIMS}")


def panel_steps(sp_live: np.ndarray, w_live: np.ndarray,
                h_live: np.ndarray, dim: str
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The live panel products as ``(i, j, k)`` int arrays, one entry per
    block triple with sp(i, j), W(i, k) and H(k, j) all live.

    ``sp_live`` [gm, gn], ``w_live`` [gm, gk], ``h_live`` [gk, gn] are host
    bool masks at panel granularity. Triples are sorted by (i, j, k), or
    by (j, i, k) for ``dim="col"``, so each output tile's panels, and each
    accumulator block's tiles, are consecutive."""
    sp_live = np.asarray(sp_live, bool)
    w_live = np.asarray(w_live, bool)
    hT = np.asarray(h_live, bool).T
    out_i, out_j, out_k = [], [], []
    for i in np.flatnonzero(sp_live.any(axis=1)):
        live = sp_live[i][:, None] & w_live[i][None, :] & hT
        j, k = np.nonzero(live)
        out_i.append(np.full(j.size, i, np.int64))
        out_j.append(j)
        out_k.append(k)
    if not out_i:
        return (np.zeros(0, np.int64),) * 3
    i, j, k = (np.concatenate(v).astype(np.int64)
               for v in (out_i, out_j, out_k))
    if dim == "col":
        order = np.lexsort((k, i, j))
        i, j, k = i[order], j[order], k[order]
    return i, j, k


def panel_counts(sp_live: np.ndarray, w_live: np.ndarray,
                 h_live: np.ndarray) -> Tuple[int, int]:
    """(computed, skipped) panel products of one dispatch: of the
    gm·gn·gk block triples, those with sp, W and H all live, and the
    rest."""
    sp = np.asarray(sp_live, np.int64)
    total = sp.size * np.asarray(w_live).shape[1]
    computed = int((sp * (np.asarray(w_live, np.int64)
                          @ np.asarray(h_live, np.int64))).sum())
    return computed, total - computed


def _kernel(tile_ref, k_ref, n_ref, sp_ref, w_ref, h_ref, out_ref, acc_ref,
            *, dim: str, steps: int, minor: int):
    """One live panel product. ``tile_ref[s]`` is the output tile of step
    s (``major * minor + minor_index``), ``k_ref[s]`` its K panel, and
    ``n_ref[0]`` the number of real steps; steps past it repeat the last
    real one (nothing is fetched) and do nothing."""
    s = pl.program_id(0)
    n = n_ref[0]
    tile = tile_ref[s]
    prev = tile_ref[jnp.maximum(s - 1, 0)]
    nxt = tile_ref[jnp.minimum(s + 1, steps - 1)]
    real = s < n
    new_tile = (s == 0) | (tile != prev)
    end_tile = (s == n - 1) | (tile != nxt)
    if dim == "all":
        new_block = s == 0
    else:
        new_block = (s == 0) | (tile // minor != prev // minor)

    @pl.when(new_block)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    prod = functools.partial(jnp.dot, w_ref[...], h_ref[...],
                             preferred_element_type=acc_ref.dtype)

    @pl.when(real & new_tile)
    def _first():
        acc_ref[...] = prod()

    @pl.when(real & jnp.logical_not(new_tile))
    def _accum():
        acc_ref[...] += prod()

    @pl.when(real & end_tile)
    def _fold():
        x = sp_ref[...].astype(acc_ref.dtype) * acc_ref[...]
        if dim == "row":
            out_ref[...] += jnp.sum(x, axis=1, keepdims=True)
        elif dim == "col":
            out_ref[...] += jnp.sum(x, axis=0, keepdims=True)
        else:
            out_ref[...] += jnp.sum(x).reshape(1, 1)


@functools.partial(
    jax.jit, static_argnames=("dim", "bm", "bn", "bk", "interpret"))
def _paneled_call(tiles, ks, nreal, visited, sp, w, h, *, dim: str,
                  bm: int, bn: int, bk: int, interpret: bool):
    """Scan over chunks of steps, one pallas_call each; a chunk's output
    is kept only on the accumulator blocks its steps visited."""
    m, n = sp.shape
    gm, gn = m // bm, n // bn
    steps = tiles.shape[1]
    acc = jnp.promote_types(sp.dtype, jnp.float32)
    minor = gm if dim == "col" else gn

    def ij(t):
        major, mnr = t // minor, t % minor
        return (mnr, major) if dim == "col" else (major, mnr)

    def sp_map(s, t, k, c):
        return ij(t[s])

    def w_map(s, t, k, c):
        return ij(t[s])[0], k[s]

    def h_map(s, t, k, c):
        return k[s], ij(t[s])[1]

    out_shape, out_block, out_map, repeat = {
        "row": ((m, 1), (bm, 1), lambda s, t, k, c: (ij(t[s])[0], 0), bm),
        "col": ((1, n), (1, bn), lambda s, t, k, c: (0, ij(t[s])[1]), bn),
        "all": ((1, 1), (1, 1), lambda s, t, k, c: (0, 0), 1),
    }[dim]
    call = pl.pallas_call(
        functools.partial(_kernel, dim=dim, steps=steps, minor=minor),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(steps,),
            in_specs=[
                pl.BlockSpec((bm, bn), sp_map),     # sp tile
                pl.BlockSpec((bm, bk), w_map),      # W panel
                pl.BlockSpec((bk, bn), h_map),      # H panel
            ],
            out_specs=pl.BlockSpec(out_block, out_map),
            scratch_shapes=[pltpu.VMEM((bm, bn), acc)],
        ),
        out_shape=jax.ShapeDtypeStruct(out_shape, acc),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )

    def body(total, chunk):
        t, k, c, seen = chunk
        out = call(t, k, c, sp, w, h)
        keep = jnp.repeat(seen, repeat).reshape(out.shape)
        return total + jnp.where(keep, out, 0), None

    total, _ = jax.lax.scan(body, jnp.zeros(out_shape, acc),
                            (tiles, ks, nreal, visited))
    return total


def sddmm_agg_pallas(sp: jnp.ndarray, w: jnp.ndarray, h: jnp.ndarray,
                     mask, w_mask=None, h_mask=None, *, dim: str,
                     bm: int = 256, bn: int = 256, bk: int = 256,
                     interpret: bool = False) -> jnp.ndarray:
    """SUM-aggregate ``sp ∘ (W·H)`` over the live panel products, fused.

    Shapes: sp [M, N], w [M, K], h [K, N] (M, N multiples of bm/bn and K
    of bk — the registry wrapper pads). ``mask`` [M/bm, N/bn] is sp's
    block mask over the output tile grid, ``w_mask`` [M/bm, K/bk] and
    ``h_mask`` [K/bk, N/bn] those of W and H at panel granularity (None:
    every panel live). Masks must be host-readable: the step tables are
    built from them on the host, and become constants of the program.
    """
    m, n = sp.shape
    k = w.shape[1]
    assert w.shape[0] == m and h.shape == (k, n), (sp.shape, w.shape,
                                                   h.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (sp.shape, bm, bn,
                                                         bk)
    if dim not in DIMS:
        raise ValueError(f"dim {dim!r} not in {DIMS}")
    gm, gn, gk = m // bm, n // bn, k // bk
    sp_live = np.asarray(mask, bool)
    w_live = np.ones((gm, gk), bool) if w_mask is None \
        else np.asarray(w_mask, bool)
    h_live = np.ones((gk, gn), bool) if h_mask is None \
        else np.asarray(h_mask, bool)
    assert sp_live.shape == (gm, gn), (sp_live.shape, (gm, gn))
    assert w_live.shape == (gm, gk) and h_live.shape == (gk, gn), (
        w_live.shape, h_live.shape, (gm, gk, gn))
    out_shape = {"row": (m, 1), "col": (1, n), "all": (1, 1)}[dim]
    i, j, kk = panel_steps(sp_live, w_live, h_live, dim)
    if i.size == 0:
        return jnp.zeros(out_shape, sp.dtype)
    major, mnr, minor, blocks = (j, i, gm, gn) if dim == "col" \
        else (i, j, gn, gm)
    tile = major * minor + mnr
    # chunks of equal length, so that padding adds fewer steps than
    # there are chunks; a padding step repeats the last real one, so
    # nothing new is fetched, but it still costs a grid step
    chunks = -(-i.size // CHUNK_STEPS)
    steps = -(-i.size // chunks)
    pad = chunks * steps - i.size
    tiles = np.concatenate([tile, np.full(pad, tile[-1])]) \
        .reshape(chunks, steps).astype(np.int32)
    ks = np.concatenate([kk, np.full(pad, kk[-1])]) \
        .reshape(chunks, steps).astype(np.int32)
    nreal = np.full((chunks, 1), steps, np.int32)
    nreal[-1, 0] = steps - pad
    visited = np.zeros((chunks, 1 if dim == "all" else blocks), bool)
    for c in range(chunks):
        lo, hi = c * steps, min((c + 1) * steps, i.size)
        visited[c, 0 if dim == "all" else major[lo:hi]] = True
    out = _paneled_call(tiles, ks, nreal, visited, sp, w, h, dim=dim,
                        bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out.astype(sp.dtype)

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
* ``sddmm_agg_pallas`` — the tiled kernel: grid over the output block
  grid, the reduction axis innermost ("arbitrary"), ``pl.when`` zero-init
  on the first reduction step and block-mask-gated accumulate — the same
  revisiting-accumulator idiom as ``masked_matmul``.

``dim`` is one of ``"row"`` (out [m, 1]), ``"col"`` (out [1, n]),
``"all"`` (out [1, 1]) — matching ``core.executor.agg_dense``'s output
shapes for ``AggFn.SUM``. Only SUM fuses: the other aggregates mask by
*presence* (``v != 0``), which needs the materialized product.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DIMS = ("row", "col", "all")


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


def _kernel(live_ref, sp_ref, w_ref, h_ref, out_ref, *, dim: str,
            gn: int):
    """One (bm, bn) tile of ``sp ∘ (W·H)`` folded into the accumulator.
    ``col`` walks the grid transposed (j outer, i inner) so that, for every
    dim, the accumulator is revisited only along the innermost axis."""
    if dim == "col":
        j, i = pl.program_id(0), pl.program_id(1)
    else:
        i, j = pl.program_id(0), pl.program_id(1)
    first = pl.program_id(1) == 0
    if dim == "all":
        first = first & (pl.program_id(0) == 0)

    @pl.when(first)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(live_ref[i * gn + j] != 0)
    def _accum():
        s = sp_ref[...] * jnp.dot(w_ref[...], h_ref[...],
                                  preferred_element_type=out_ref.dtype)
        if dim == "row":
            out_ref[...] += jnp.sum(s, axis=1, keepdims=True)
        elif dim == "col":
            out_ref[...] += jnp.sum(s, axis=0, keepdims=True)
        else:
            out_ref[...] += jnp.sum(s).reshape(1, 1)


@functools.partial(
    jax.jit, static_argnames=("dim", "bm", "bn", "interpret"))
def sddmm_agg_pallas(sp: jnp.ndarray, w: jnp.ndarray, h: jnp.ndarray,
                     mask: jnp.ndarray, *, dim: str, bm: int = 256,
                     bn: int = 256, interpret: bool = False) -> jnp.ndarray:
    """SUM-aggregate ``sp ∘ (W·H)`` over masked tiles, fused.

    Shapes: sp [M, N], w [M, K], h [K, N], mask [M/bm, N/bn] bool over the
    output tile grid (M, N multiples of bm/bn — the registry wrapper
    pads). K rides whole into each tile: it is the factor width (small by
    construction in the PNMF pipeline), and keeping it unsplit leaves the
    grid's sole revisiting axis the reduction axis. The mask rides in SMEM
    as a flat int32 scalar-prefetch operand.
    """
    m, n = sp.shape
    k = w.shape[1]
    assert w.shape[0] == m and h.shape == (k, n), (sp.shape, w.shape,
                                                   h.shape)
    assert m % bm == 0 and n % bn == 0, (sp.shape, bm, bn)
    gm, gn = m // bm, n // bn
    assert mask.shape == (gm, gn), (mask.shape, (gm, gn))
    if dim not in DIMS:
        raise ValueError(f"dim {dim!r} not in {DIMS}")
    live = jnp.asarray(mask).reshape(-1).astype(jnp.int32)
    out_dtype = jnp.promote_types(sp.dtype, jnp.float32)

    if dim == "col":
        grid = (gn, gm)

        def ij(a, b):
            return b, a
    else:
        grid = (gm, gn)

        def ij(a, b):
            return a, b
    out_shape, out_block, out_map = {
        "row": ((m, 1), (bm, 1), lambda i, j: (i, 0)),
        "col": ((1, n), (1, bn), lambda i, j: (0, j)),
        "all": ((1, 1), (1, 1), lambda i, j: (0, 0)),
    }[dim]
    sem = ("arbitrary", "arbitrary") if dim == "all" \
        else ("parallel", "arbitrary")

    def spec(block, index):
        return pl.BlockSpec(block, lambda a, b, live: index(*ij(a, b)))

    out = pl.pallas_call(
        functools.partial(_kernel, dim=dim, gn=gn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                spec((bm, bn), lambda i, j: (i, j)),    # sp tile
                spec((bm, k), lambda i, j: (i, 0)),     # W row panel
                spec((k, bn), lambda i, j: (0, j)),     # H column panel
            ],
            out_specs=spec(out_block, out_map),
        ),
        out_shape=jax.ShapeDtypeStruct(out_shape, out_dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=sem),
    )(live, sp, w, h)
    return out.astype(sp.dtype)

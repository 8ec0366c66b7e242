"""Block-skip overlay-join Pallas kernel (TPU target).

Direct/transpose overlay joins (paper §4.3) evaluate an elementwise merge
function over two matrices. With a sparsity-inducing merge (paper §4.7) whole
blocks can be skipped: the kernel receives the live-tile mask derived from
both block masks and a static ``mode`` describing which side(s) the merge is
inducing on, and zeroes skipped tiles without computing the merge.

Grid (mi, ni); tiles (bm, bn) in VMEM. The merge function is traced into the
kernel body, so any jnp-expressible f(x, y) works. The live-tile mask rides
in SMEM as a flat int32 scalar-prefetch operand.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# compute-gating modes derived from the sparsity profile of the merge fn
MODE_BOTH = 0   # inducing on x and y: compute where maskA & maskB
MODE_X = 1      # inducing on x:       compute where maskA
MODE_Y = 2      # inducing on y:       compute where maskB
MODE_ALL = 3    # not inducing:        compute everywhere


def mode_for(inducing_x: bool, inducing_y: bool) -> int:
    """The single profile→mode rule (``core.matrix.mask_overlay`` is its
    block-mask twin — keep the two in lockstep)."""
    if inducing_x and inducing_y:
        return MODE_BOTH
    if inducing_x:
        return MODE_X
    if inducing_y:
        return MODE_Y
    return MODE_ALL


def live_tiles(mode: int, mask_a: jnp.ndarray,
               mask_b: jnp.ndarray) -> jnp.ndarray:
    """The tiles a ``mode`` merge must compute, as a bool tile grid."""
    if mode == MODE_BOTH:
        return jnp.logical_and(mask_a, mask_b)
    if mode == MODE_X:
        return jnp.asarray(mask_a, bool)
    if mode == MODE_Y:
        return jnp.asarray(mask_b, bool)
    return jnp.ones(jnp.shape(mask_a), bool)


def _kernel(live_ref, a_ref, b_ref, out_ref, *, merge: Callable, gn: int):
    live = live_ref[pl.program_id(0) * gn + pl.program_id(1)] != 0

    @pl.when(live)
    def _compute():
        out_ref[...] = merge(a_ref[...], b_ref[...]).astype(out_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _skip():
        out_ref[...] = jnp.zeros_like(out_ref)


@functools.partial(jax.jit,
                   static_argnames=("merge", "mode", "bm", "bn", "interpret"))
def merge_join_pallas(a: jnp.ndarray, b: jnp.ndarray,
                      mask_a: jnp.ndarray, mask_b: jnp.ndarray, *,
                      merge: Callable, mode: int = MODE_ALL,
                      bm: int = 256, bn: int = 256,
                      interpret: bool = False) -> jnp.ndarray:
    m, n = a.shape
    assert b.shape == (m, n)
    assert m % bm == 0 and n % bn == 0
    gm, gn = m // bm, n // bn
    assert mask_a.shape == (gm, gn) and mask_b.shape == (gm, gn)
    live = live_tiles(mode, mask_a, mask_b).reshape(-1).astype(jnp.int32)

    tile = pl.BlockSpec((bm, bn), lambda mi, ni, live: (mi, ni))
    return pl.pallas_call(
        functools.partial(_kernel, merge=merge, gn=gn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(gm, gn),
            in_specs=[tile, tile],        # A tile, B tile
            out_specs=tile,
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(live, a, b)

"""Block-masked matrix multiplication Pallas kernel (TPU target).

The paper's PNMF optimization: for sparse A, evaluate ``A ∘ (W × H)`` by
computing **only the blocks of W×H that land under nonzero blocks of A**
(§6, PNMF). On TPU this is an SDDMM-shaped kernel: a block-level output mask
gates the MXU work per (i, j) output tile, skipping both the compute and the
HBM→VMEM streaming of the K panels for masked-out tiles.

Tiling: grid (mi, ni, ki); A tile (bm, bk), B tile (bk, bn), out tile
(bm, bn) accumulated in-place in VMEM across the ki loop (the K dimension is
the innermost, "arbitrary" grid axis; mi/ni are parallel). Block sizes are
MXU-aligned (multiples of 128 for f32/bf16 inputs).

The block mask rides in SMEM as a flat int32 scalar-prefetch operand: the
kernel body reads it to gate the MXU work, and the A/B index maps read it
to pin a dead tile's K panels to panel 0, so consecutive dead steps fetch
nothing new.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(live_ref, a_ref, b_ref, out_ref, *, gn: int):
    mi, ni, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(live_ref[mi * gn + ni] != 0)
    def _accum():
        out_ref[...] += jnp.dot(
            a_ref[...], b_ref[...], preferred_element_type=out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "interpret"))
def masked_matmul_pallas(a: jnp.ndarray, b: jnp.ndarray, mask: jnp.ndarray,
                         *, bm: int = 256, bn: int = 256, bk: int = 256,
                         interpret: bool = False) -> jnp.ndarray:
    """C[i·bm:(i+1)·bm, j·bn:(j+1)·bn] = (A×B) tile if mask[i, j] else 0.

    Shapes: a [M, K], b [K, N], mask [M/bm, N/bn] bool. M, N, K must be
    multiples of the block sizes (callers pad; see ``ops.masked_matmul``).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (a.shape, b.shape)
    gm, gn, gk = m // bm, n // bn, k // bk
    assert mask.shape == (gm, gn), (mask.shape, (gm, gn))
    live = jnp.asarray(mask).reshape(-1).astype(jnp.int32)

    def panel(mi, ni, ki, live):
        return jnp.where(live[mi * gn + ni] != 0, ki, 0)

    out_dtype = jnp.promote_types(a.dtype, jnp.float32)
    return pl.pallas_call(
        functools.partial(_kernel, gn=gn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(gm, gn, gk),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda mi, ni, ki, live:
                             (mi, panel(mi, ni, ki, live))),    # A panel
                pl.BlockSpec((bk, bn), lambda mi, ni, ki, live:
                             (panel(mi, ni, ki, live), ni)),    # B panel
            ],
            out_specs=pl.BlockSpec((bm, bn),
                                   lambda mi, ni, ki, live: (mi, ni)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(live, a, b).astype(a.dtype)

"""Fused segment-expand + merge-intersect COO join Pallas kernel.

The device join tier (``repro.core.joins_device``) unrolls per-key match
runs into a static ``cap``-slot buffer. As plain XLA that inner loop is a
chain of separate ops — ``repeat`` (segment ids), several cap-sized
gathers (operand values, output coordinates), the merge elementwise, and a
``stack`` — each materializing its own cap-sized intermediate in HBM. This
kernel fuses the whole expansion: one pass over the output slots computes
the segment id by binary search over the segment end offsets, gathers both
operands and their coordinates from the compacted (nnz-sized,
cache-resident) side buffers, applies the merge function in-register, and
writes only the final ``idx``/``val`` buffers.

Inputs (all device arrays; ``ns`` = probe-side entries, ``nb`` = partner
side entries, ``cap`` = static output capacity):

* ``ends   [ns] int32``  — inclusive prefix sum of per-segment match counts;
* ``delta  [ns] int32``  — partner-run base minus own segment start: slot
  ``t`` in segment ``s`` reads partner position ``t + delta[s]``;
* ``a_vals [ns]``, ``a_coords [ns, ca]`` — probe-side values + out coords;
* ``b_vals [nb]``, ``b_coords [nb, cb]`` — partner values + out coords.

Returns ``(idx [cap, ca+cb], val [cap])``. Slots at or past the true total
hold clamped garbage — the caller masks them with its ``valid`` vector
(exactly the contract ``joins_device._finish`` already enforces).

The dense oracle keeps the historical ``repeat``-then-gather formulation
(fastest on XLA CPU); the Pallas body replaces ``repeat`` with an unrolled
binary search per slot, which needs no cap-sized intermediate at all. The
two agree on every slot below the true total; above it they may clamp to
different (masked) segments, so parity is defined over valid slots only.

The body runs under the interpreter and the Triton lowering. It has no
TPU registration: its binary search and operand fetches are 1-D gathers
at data-dependent indices, and Mosaic lowers only 2-D gathers ("Only 2D
gather is supported", JAX 0.9.0), so on TPU the expansion runs as the
dense oracle's XLA chain.
"""
from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def coo_expand_ref(ends: jnp.ndarray, delta: jnp.ndarray,
                   a_vals: jnp.ndarray, a_coords: jnp.ndarray,
                   b_vals: jnp.ndarray, b_coords: jnp.ndarray,
                   merge: Callable, cap: int
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dense oracle: the repeat-based expansion the device joins used
    inline before this kernel existed (bit-identical to that path)."""
    ns = ends.shape[0]
    counts = ends - jnp.concatenate(
        [jnp.zeros((1,), ends.dtype), ends[:-1]])
    sa = jnp.repeat(jnp.arange(ns, dtype=jnp.int32), counts,
                    total_repeat_length=cap)
    nb = b_vals.shape[0]
    t = jnp.arange(cap, dtype=jnp.int32)
    sb = jnp.clip(t + delta[sa], 0, nb - 1)
    val = merge(a_vals[sa], b_vals[sb])
    idx = jnp.concatenate([a_coords[sa], b_coords[sb]], axis=1)
    return idx, val


def _search_kernel(ends_ref, delta_ref, av_ref, ac_ref, bv_ref, bc_ref,
                   idx_ref, val_ref, *, bt: int, ns: int, nb: int,
                   merge: Callable):
    """One ``bt``-slot output tile: binary-search segment ids, gather,
    merge, write. The search is the bitwise form — ``pos`` accumulates
    set bits high-to-low so every slot runs the same static
    ``ns.bit_length()`` iterations (no data-dependent control flow)."""
    i = pl.program_id(0)
    t = i * bt + jax.lax.broadcasted_iota(jnp.int32, (bt,), 0)
    ends = ends_ref[...]
    # pos := #(ends <= t)  — searchsorted-right over the end offsets
    pos = jnp.zeros((bt,), jnp.int32)
    for bit in range(max(ns, 1).bit_length() - 1, -1, -1):
        trial = pos + (1 << bit)
        probe = jnp.take(ends, jnp.clip(trial - 1, 0, ns - 1))
        ok = (trial <= ns) & (probe <= t)
        pos = jnp.where(ok, trial, pos)
    seg = jnp.clip(pos, 0, ns - 1)
    sb = jnp.clip(t + jnp.take(delta_ref[...], seg), 0, nb - 1)
    val_ref[...] = merge(jnp.take(av_ref[...], seg),
                         jnp.take(bv_ref[...], sb))
    idx_ref[...] = jnp.concatenate(
        [jnp.take(ac_ref[...], seg, axis=0),
         jnp.take(bc_ref[...], sb, axis=0)], axis=1)


@functools.partial(
    jax.jit, static_argnames=("merge", "cap", "bt", "interpret"))
def coo_expand_pallas(ends: jnp.ndarray, delta: jnp.ndarray,
                      a_vals: jnp.ndarray, a_coords: jnp.ndarray,
                      b_vals: jnp.ndarray, b_coords: jnp.ndarray,
                      *, merge: Callable, cap: int, bt: int = 1024,
                      interpret: bool = False
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused expansion over a (cap/bt,) grid of output-slot tiles.

    Side buffers ride whole into every tile (they are nnz-bounded and
    already the cache-resident operands of the unfused path); only the
    two outputs are tiled. ``cap`` must be a multiple of ``bt`` (the
    registry wrapper pads and slices).
    """
    ns, nb = ends.shape[0], b_vals.shape[0]
    ca, cb = a_coords.shape[1], b_coords.shape[1]
    assert cap % bt == 0, (cap, bt)
    grid = (cap // bt,)
    whole = [
        pl.BlockSpec((ns,), lambda i: (0,)),            # ends
        pl.BlockSpec((ns,), lambda i: (0,)),            # delta
        pl.BlockSpec((ns,), lambda i: (0,)),            # a_vals
        pl.BlockSpec((ns, ca), lambda i: (0, 0)),       # a_coords
        pl.BlockSpec((nb,), lambda i: (0,)),            # b_vals
        pl.BlockSpec((nb, cb), lambda i: (0, 0)),       # b_coords
    ]
    out_dtype = jnp.promote_types(a_vals.dtype, b_vals.dtype)
    idx, val = pl.pallas_call(
        functools.partial(_search_kernel, bt=bt, ns=ns, nb=nb, merge=merge),
        grid=grid,
        in_specs=whole,
        out_specs=[
            pl.BlockSpec((bt, ca + cb), lambda i: (i, 0)),
            pl.BlockSpec((bt,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((cap, ca + cb), a_coords.dtype),
            jax.ShapeDtypeStruct((cap,), out_dtype),
        ],
        interpret=interpret,
    )(ends, delta, a_vals, a_coords, b_vals, b_coords)
    return idx, val

"""Public kernel wrappers + registration of the built-in registry entries.

Each logical kernel is registered under the pure-jnp ``dense`` oracle from
``ref.py``, the Pallas body under the interpreter (``pallas-interpret``)
and the Triton lowering (``pallas-gpu``) — see ``repro.kernels.registry``.
The compiled Mosaic kernel (``pallas-tpu``) is registered for the bodies
Mosaic compiles — ``masked_matmul``, ``merge_join`` and ``sddmm_agg``,
planned where ``registry.accepts`` allows. ``bloom_probe`` and ``coo_expand``
have none: their bodies gather at data-dependent indices from 1-D arrays,
which Mosaic does not lower, so on TPU they resolve to the dense (XLA)
path (``docs/kernels.md``). The module-level functions keep the
historical call-sites working (``force="ref"/"pallas"``) by translating
``force`` to a backend and going through ``registry.dispatch``.

Padding/alignment lives here, not in the kernel bodies: callers hand
arbitrary shapes, the backend impls pad to tile multiples and slice back.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as refmod
from repro.kernels import registry
from repro.kernels.bloom_probe import bloom_probe_pallas
from repro.kernels.coo_join import coo_expand_pallas, coo_expand_ref
from repro.kernels.masked_matmul import masked_matmul_pallas
from repro.kernels.merge_join import (
    MODE_ALL, MODE_BOTH, MODE_X, MODE_Y, merge_join_pallas,
)
from repro.kernels.sddmm_agg import sddmm_agg_pallas, sddmm_agg_ref

Tiles = Optional[Dict[str, int]]


def _force_to_backend(name: str, force: Optional[str]) -> Optional[str]:
    """Translate the historical ``force`` arg to a registry backend:
    ``"pallas"`` is the compiled kernel where this kernel has one for this
    process, else its body under the interpreter."""
    if force is None:
        return None  # registry default: pallas-tpu on TPU, else dense
    if force == "ref":
        return registry.DENSE
    if force == "pallas":
        compiled = (jax.default_backend() == "tpu"
                    and registry.TPU in registry.get(name).impls)
        return registry.TPU if compiled else registry.INTERPRET
    return force  # already a backend name


def _pad_to(x: jnp.ndarray, mult0: int, mult1: int) -> jnp.ndarray:
    p0 = (-x.shape[0]) % mult0
    p1 = (-x.shape[1]) % mult1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


# ---------------------------------------------------------------------------
# masked_matmul — block-gated A×B (the PNMF SDDMM pattern, paper §6).
# ---------------------------------------------------------------------------

_MM_TILE_GRID = ({"bk": 64}, {"bk": 128}, {"bk": 256}, {"bk": 512})
_MM_DEFAULT_TILES = {"bk": 256}


@registry.register("masked_matmul", registry.DENSE,
                   tile_grid=_MM_TILE_GRID, default_tiles=_MM_DEFAULT_TILES)
def _masked_matmul_dense(a, b, out_block_mask, *, block_size: int = 256,
                         tiles: Tiles = None):
    return refmod.masked_matmul_ref(a, b, out_block_mask, block_size,
                                    block_size)


def _masked_matmul_pallas(a, b, out_block_mask, *, block_size: int,
                          tiles: Tiles, interpret: bool):
    m, k = a.shape
    _, n = b.shape
    bs = block_size
    # bm/bn are pinned to the mask granularity; bk (the K panel depth) is
    # the free, autotunable tile dimension — K is padded up to a multiple.
    bk = int((tiles or {}).get("bk", _MM_DEFAULT_TILES["bk"]))
    bk = min(bk, max(k, 1))
    ap = _pad_to(a, bs, bk)
    bp = _pad_to(b, bk, bs)
    gm, gn = ap.shape[0] // bs, bp.shape[1] // bs
    mk = out_block_mask
    if mk.shape != (gm, gn):
        mk = jnp.pad(mk, ((0, gm - mk.shape[0]), (0, gn - mk.shape[1])))
    out = masked_matmul_pallas(ap, bp, mk, bm=bs, bn=bs, bk=bk,
                               interpret=interpret)
    return out[:m, :n]


@registry.register("masked_matmul", registry.INTERPRET)
def _masked_matmul_interpret(a, b, out_block_mask, *, block_size: int = 256,
                             tiles: Tiles = None):
    return _masked_matmul_pallas(a, b, out_block_mask, block_size=block_size,
                                 tiles=tiles, interpret=True)


@registry.register("masked_matmul", registry.TPU)
def _masked_matmul_tpu(a, b, out_block_mask, *, block_size: int = 256,
                       tiles: Tiles = None):
    return _masked_matmul_pallas(a, b, out_block_mask, block_size=block_size,
                                 tiles=tiles, interpret=False)


@registry.register("masked_matmul", registry.GPU)
def _masked_matmul_gpu(a, b, out_block_mask, *, block_size: int = 256,
                       tiles: Tiles = None):
    # same compiled body: pallas_call picks the Triton lowering on GPU
    return _masked_matmul_pallas(a, b, out_block_mask, block_size=block_size,
                                 tiles=tiles, interpret=False)


# ---------------------------------------------------------------------------
# merge_join — block-skip overlay join (paper §4.3/§4.7).
# ---------------------------------------------------------------------------

@registry.register("merge_join", registry.DENSE)
def _merge_join_dense(a, b, mask_a, mask_b, *, merge: Callable,
                      mode: int = MODE_ALL, block_size: int = 256,
                      tiles: Tiles = None):
    return refmod.merge_join_ref(a, b, mask_a, mask_b, merge, mode,
                                 block_size, block_size)


def _merge_join_pallas(a, b, mask_a, mask_b, *, merge, mode, block_size,
                       interpret):
    bs = block_size
    ap, bp = _pad_to(a, bs, bs), _pad_to(b, bs, bs)
    gm, gn = ap.shape[0] // bs, ap.shape[1] // bs

    def padm(mk):
        mk = jnp.asarray(mk)
        return jnp.pad(mk, ((0, gm - mk.shape[0]), (0, gn - mk.shape[1])))

    out = merge_join_pallas(ap, bp, padm(mask_a), padm(mask_b),
                            merge=merge, mode=mode, bm=bs, bn=bs,
                            interpret=interpret)
    return out[: a.shape[0], : a.shape[1]]


@registry.register("merge_join", registry.INTERPRET)
def _merge_join_interpret(a, b, mask_a, mask_b, *, merge: Callable,
                          mode: int = MODE_ALL, block_size: int = 256,
                          tiles: Tiles = None):
    return _merge_join_pallas(a, b, mask_a, mask_b, merge=merge, mode=mode,
                              block_size=block_size, interpret=True)


@registry.register("merge_join", registry.TPU)
def _merge_join_tpu(a, b, mask_a, mask_b, *, merge: Callable,
                    mode: int = MODE_ALL, block_size: int = 256,
                    tiles: Tiles = None):
    return _merge_join_pallas(a, b, mask_a, mask_b, merge=merge, mode=mode,
                              block_size=block_size, interpret=False)


@registry.register("merge_join", registry.GPU)
def _merge_join_gpu(a, b, mask_a, mask_b, *, merge: Callable,
                    mode: int = MODE_ALL, block_size: int = 256,
                    tiles: Tiles = None):
    return _merge_join_pallas(a, b, mask_a, mask_b, merge=merge, mode=mode,
                              block_size=block_size, interpret=False)


# ---------------------------------------------------------------------------
# bloom_probe — V2V Bloom-join membership probe (paper §4.7).
# ---------------------------------------------------------------------------

_BLOOM_TILE_GRID = ({"bs": 1024}, {"bs": 2048}, {"bs": 4096}, {"bs": 8192})
_BLOOM_DEFAULT_TILES = {"bs": 4096}


@registry.register("bloom_probe", registry.DENSE,
                   tile_grid=_BLOOM_TILE_GRID,
                   default_tiles=_BLOOM_DEFAULT_TILES)
def _bloom_probe_dense(words, vals, *, num_hashes: int = 3,
                       log2_bits: int = 20, tiles: Tiles = None):
    return refmod.bloom_probe_ref(words, vals, num_hashes, log2_bits)


def _bloom_probe_pallas(words, vals, *, num_hashes, log2_bits, tiles,
                        interpret):
    n = vals.shape[0]
    bs = int((tiles or {}).get("bs", _BLOOM_DEFAULT_TILES["bs"]))
    pad = (-n) % bs
    vp = jnp.pad(vals, (0, pad), constant_values=np.nan)  # NaN never matches
    out = bloom_probe_pallas(words, vp, num_hashes=num_hashes,
                             log2_bits=log2_bits, bs=bs, interpret=interpret)
    return out[:n]


@registry.register("bloom_probe", registry.INTERPRET)
def _bloom_probe_interpret(words, vals, *, num_hashes: int = 3,
                           log2_bits: int = 20, tiles: Tiles = None):
    return _bloom_probe_pallas(words, vals, num_hashes=num_hashes,
                               log2_bits=log2_bits, tiles=tiles,
                               interpret=True)


@registry.register("bloom_probe", registry.GPU)
def _bloom_probe_gpu(words, vals, *, num_hashes: int = 3,
                     log2_bits: int = 20, tiles: Tiles = None):
    return _bloom_probe_pallas(words, vals, num_hashes=num_hashes,
                               log2_bits=log2_bits, tiles=tiles,
                               interpret=False)


# ---------------------------------------------------------------------------
# coo_expand — fused segment-expand + merge-intersect COO join inner loop
# (paper §4.4–§4.5; the D2D/V2V expansion in core.joins_device).
# ---------------------------------------------------------------------------

_COO_TILE_GRID = ({"bt": 256}, {"bt": 512}, {"bt": 1024}, {"bt": 2048})
_COO_DEFAULT_TILES = {"bt": 1024}


@registry.register("coo_expand", registry.DENSE,
                   tile_grid=_COO_TILE_GRID,
                   default_tiles=_COO_DEFAULT_TILES)
def _coo_expand_dense(ends, delta, a_vals, a_coords, b_vals, b_coords, *,
                      merge: Callable, cap: int, tiles: Tiles = None):
    return coo_expand_ref(ends, delta, a_vals, a_coords, b_vals, b_coords,
                          merge, cap)


def _coo_expand_pl(ends, delta, a_vals, a_coords, b_vals, b_coords, *,
                   merge, cap, tiles, interpret):
    bt = int((tiles or {}).get("bt", _COO_DEFAULT_TILES["bt"]))
    bt = min(bt, max(cap, 1))
    cap_p = -(-cap // bt) * bt  # pad to a whole tile; extra slots clamp
    idx, val = coo_expand_pallas(ends, delta, a_vals, a_coords, b_vals,
                                 b_coords, merge=merge, cap=cap_p, bt=bt,
                                 interpret=interpret)
    return idx[:cap], val[:cap]


@registry.register("coo_expand", registry.INTERPRET)
def _coo_expand_interpret(ends, delta, a_vals, a_coords, b_vals, b_coords,
                          *, merge: Callable, cap: int, tiles: Tiles = None):
    return _coo_expand_pl(ends, delta, a_vals, a_coords, b_vals, b_coords,
                          merge=merge, cap=cap, tiles=tiles, interpret=True)


@registry.register("coo_expand", registry.GPU)
def _coo_expand_gpu(ends, delta, a_vals, a_coords, b_vals, b_coords, *,
                    merge: Callable, cap: int, tiles: Tiles = None):
    return _coo_expand_pl(ends, delta, a_vals, a_coords, b_vals, b_coords,
                          merge=merge, cap=cap, tiles=tiles, interpret=False)


# ---------------------------------------------------------------------------
# sddmm_agg — fused SDDMM + SUM aggregation (paper §6, PNMF pipelines).
# ---------------------------------------------------------------------------

@registry.register("sddmm_agg", registry.DENSE)
def _sddmm_agg_dense(sp, w, h, out_block_mask, *, dim: str,
                     block_size: int = 256, tiles: Tiles = None):
    # the factorized form needs no mask: sp's zeros already gate it
    return sddmm_agg_ref(sp, w, h, dim)


def _sddmm_agg_pl(sp, w, h, out_block_mask, *, dim, block_size, tiles,
                  interpret):
    m, n = sp.shape
    bs = block_size
    spp = _pad_to(sp, bs, bs)
    wp = jnp.pad(w, ((0, spp.shape[0] - m), (0, 0)))
    hp = jnp.pad(h, ((0, 0), (0, spp.shape[1] - n)))
    gm, gn = spp.shape[0] // bs, spp.shape[1] // bs
    mk = jnp.asarray(out_block_mask)
    if mk.shape != (gm, gn):
        mk = jnp.pad(mk, ((0, gm - mk.shape[0]), (0, gn - mk.shape[1])))
    out = sddmm_agg_pallas(spp, wp, hp, mk, dim=dim, bm=bs, bn=bs,
                           interpret=interpret)
    if dim == "row":
        return out[:m]
    if dim == "col":
        return out[:, :n]
    return out


@registry.register("sddmm_agg", registry.INTERPRET)
def _sddmm_agg_interpret(sp, w, h, out_block_mask, *, dim: str,
                         block_size: int = 256, tiles: Tiles = None):
    return _sddmm_agg_pl(sp, w, h, out_block_mask, dim=dim,
                         block_size=block_size, tiles=tiles, interpret=True)


@registry.register("sddmm_agg", registry.TPU)
def _sddmm_agg_tpu(sp, w, h, out_block_mask, *, dim: str,
                   block_size: int = 256, tiles: Tiles = None):
    return _sddmm_agg_pl(sp, w, h, out_block_mask, dim=dim,
                         block_size=block_size, tiles=tiles, interpret=False)


@registry.register("sddmm_agg", registry.GPU)
def _sddmm_agg_gpu(sp, w, h, out_block_mask, *, dim: str,
                   block_size: int = 256, tiles: Tiles = None):
    return _sddmm_agg_pl(sp, w, h, out_block_mask, dim=dim,
                         block_size=block_size, tiles=tiles, interpret=False)


# ---------------------------------------------------------------------------
# Public wrappers (historical API; ``force`` maps onto registry backends).
# ---------------------------------------------------------------------------

def masked_matmul(a: jnp.ndarray, b: jnp.ndarray, out_block_mask: jnp.ndarray,
                  *, block_size: int = 256, force: Optional[str] = None,
                  tiles: Tiles = None) -> jnp.ndarray:
    """(A×B) with whole output blocks gated by ``out_block_mask``.

    ``out_block_mask`` is [ceil(M/bs), ceil(N/bs)] bool over the OUTPUT tile
    grid — the paper's "compute only the W×H blocks under nonzero A blocks".
    """
    return registry.dispatch("masked_matmul", a, b, out_block_mask,
                             backend=_force_to_backend(
                                 "masked_matmul", force),
                             block_size=block_size, tiles=tiles)


def merge_join(a: jnp.ndarray, b: jnp.ndarray, mask_a: jnp.ndarray,
               mask_b: jnp.ndarray, merge: Callable, mode: int = MODE_ALL,
               *, block_size: int = 256, force: Optional[str] = None,
               tiles: Tiles = None) -> jnp.ndarray:
    return registry.dispatch("merge_join", a, b, mask_a, mask_b,
                             backend=_force_to_backend(
                                 "merge_join", force),
                             merge=merge, mode=mode, block_size=block_size,
                             tiles=tiles)


def bloom_probe(words: jnp.ndarray, vals: jnp.ndarray, *,
                num_hashes: int = 3, log2_bits: int = 20,
                force: Optional[str] = None,
                tiles: Tiles = None) -> jnp.ndarray:
    return registry.dispatch("bloom_probe", words, vals,
                             backend=_force_to_backend(
                                 "bloom_probe", force),
                             num_hashes=num_hashes, log2_bits=log2_bits,
                             tiles=tiles)


def coo_expand(ends: jnp.ndarray, delta: jnp.ndarray, a_vals: jnp.ndarray,
               a_coords: jnp.ndarray, b_vals: jnp.ndarray,
               b_coords: jnp.ndarray, *, merge: Callable, cap: int,
               force: Optional[str] = None, tiles: Tiles = None):
    """Fused COO join expansion → ``(idx [cap, ca+cb], val [cap])``.

    Slots at or past the caller's true total hold clamped garbage and
    must stay masked by the caller's ``valid`` vector (the
    ``joins_device`` wrappers do this).
    """
    return registry.dispatch("coo_expand", ends, delta, a_vals, a_coords,
                             b_vals, b_coords,
                             backend=_force_to_backend(
                                 "coo_expand", force),
                             merge=merge, cap=cap, tiles=tiles)


def sddmm_agg(sp: jnp.ndarray, w: jnp.ndarray, h: jnp.ndarray,
              out_block_mask: jnp.ndarray, *, dim: str,
              block_size: int = 256, force: Optional[str] = None,
              tiles: Tiles = None) -> jnp.ndarray:
    """SUM-aggregate ``sp ∘ (W×H)`` without materializing the product.

    ``dim``: ``"row"`` → [m, 1], ``"col"`` → [1, n], ``"all"`` → [1, 1]
    (the shapes ``core.executor.agg_dense`` produces for ``AggFn.SUM``).
    """
    return registry.dispatch("sddmm_agg", sp, w, h, out_block_mask,
                             backend=_force_to_backend(
                                 "sddmm_agg", force),
                             dim=dim, block_size=block_size, tiles=tiles)

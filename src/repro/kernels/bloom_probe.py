"""Bloom-filter probe Pallas kernel for V2V Bloom-joins (§4.7).

The bitset (uint32 words, ≤512 KiB) lives fully in fast memory; probe
values stream through in tiles. Hashing is the same multiply-shift family
as ``repro.core.bloom`` so filters built on one path probe on the other.

The body runs under the interpreter and the Triton lowering. It has no
TPU registration: the probe gathers bitset words at data-dependent
indices from a 1-D array, and Mosaic lowers only 2-D gathers
("Only 2D gather is supported", JAX 0.9.0), so on TPU the probe runs as
the dense oracle's XLA gather. Correctness is validated against that
oracle in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bloom import _MULTIPLIERS


def _hash(keys: jnp.ndarray, i: int, log2_bits: int) -> jnp.ndarray:
    h = keys * jnp.uint32(_MULTIPLIERS[i % len(_MULTIPLIERS)])
    h = h ^ (h >> jnp.uint32(15))
    h = h * jnp.uint32(0x2C1B3C6D)
    h = h ^ (h >> jnp.uint32(12))
    return (h >> jnp.uint32(32 - log2_bits)).astype(jnp.uint32)


def _kernel(words_ref, vals_ref, out_ref, *, num_hashes: int,
            log2_bits: int):
    vals = vals_ref[...]
    keys = jax.lax.bitcast_convert_type(vals.astype(jnp.float32), jnp.uint32)
    words = words_ref[...]
    hit = jnp.ones(keys.shape, jnp.bool_)
    for i in range(num_hashes):
        idx = _hash(keys, i, log2_bits)
        word_idx = (idx // 32).astype(jnp.int32)
        bit = (idx % 32).astype(jnp.uint32)
        w = jnp.take(words, word_idx.reshape(-1), axis=0).reshape(idx.shape)
        hit = hit & (((w >> bit) & jnp.uint32(1)) == 1)
    out_ref[...] = hit


@functools.partial(jax.jit,
                   static_argnames=("num_hashes", "log2_bits", "bs",
                                    "interpret"))
def bloom_probe_pallas(words: jnp.ndarray, vals: jnp.ndarray, *,
                       num_hashes: int = 3, log2_bits: int = 20,
                       bs: int = 4096, interpret: bool = False
                       ) -> jnp.ndarray:
    """vals: [n] float; returns bool[n] may-be-member mask. n % bs == 0."""
    (n,) = vals.shape
    assert n % bs == 0, (n, bs)
    n_words = (1 << log2_bits) // 32
    assert words.shape == (n_words,)
    vals2 = vals.reshape(n // bs, bs)
    out = pl.pallas_call(
        functools.partial(_kernel, num_hashes=num_hashes,
                          log2_bits=log2_bits),
        grid=(n // bs,),
        in_specs=[
            pl.BlockSpec((n_words,), lambda i: (0,)),   # full bitset in VMEM
            pl.BlockSpec((1, bs), lambda i: (i, 0)),    # value tile
        ],
        out_specs=pl.BlockSpec((1, bs), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n // bs, bs), jnp.bool_),
        interpret=interpret,
    )(words, vals2)
    return out.reshape(n)

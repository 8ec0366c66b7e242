"""The graph500-tc catalog: A, the adjacency matrix of a Graph500
Kronecker graph, made on the device from the seed in one jitted call.
See ``graph500-tc.json`` for the sizes and what was assumed.

The generator follows the Graph500 specification: each of the
2^SCALE x edgefactor edges picks, bit by bit, one quadrant of the
adjacency matrix with the initiator's probabilities, and the vertex
labels are permuted at random. Self-loops are dropped and every edge is
stored in both directions as a 0/1 entry. The vertices are then
relabelled in descending order of degree, ties broken by that random
permutation. Degrees come from the sorted, de-duplicated edge list, so
A is the only n x n array the build holds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def catalog(cfg: dict, key) -> dict:
    return {"A": make(key, cfg["scale"], cfg["edgefactor"],
                      tuple(cfg["initiator"]))}


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def make(key, scale: int, edgefactor: int, initiator: tuple):
    n, m = 1 << scale, edgefactor << scale
    a, b, c, _ = initiator
    k_bits, k_perm = jax.random.split(key)
    # the specification's quadrant choice, one bit of i and of j per level
    u = jax.random.uniform(k_bits, (2, scale, m))
    i_bit = u[0] > a + b
    j_bit = u[1] > jnp.where(i_bit, c / (1.0 - a - b), a / (a + b))
    weights = (1 << jnp.arange(scale, dtype=jnp.int32))[:, None]
    i = jnp.sum(i_bit * weights, axis=0)
    j = jnp.sum(j_bit * weights, axis=0)
    perm = jax.random.permutation(k_perm, n).astype(jnp.int32)
    i, j = perm[i], perm[j]
    # each undirected edge once: (low, high), self-loops out, duplicates out
    lo, hi = jnp.minimum(i, j), jnp.maximum(i, j)
    keys = jnp.sort(lo * n + hi)
    fresh = jnp.concatenate([jnp.ones(1, bool), keys[1:] != keys[:-1]])
    lo, hi = keys // n, keys % n
    keep = fresh & (lo != hi)
    degree = jnp.zeros(n, jnp.int32).at[lo].add(keep).at[hi].add(keep)
    # rank by degree, high first; ties by the random label
    order = jnp.lexsort((jnp.arange(n), -degree))
    label = jnp.zeros(n, jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    lo = jnp.where(keep, label[lo], n)      # n: out of range, dropped
    hi = jnp.where(keep, label[hi], n)
    rows = jnp.concatenate([lo, hi])
    cols = jnp.concatenate([hi, lo])
    return jnp.zeros((n, n), jnp.float32).at[rows, cols].set(
        1.0, mode="drop")

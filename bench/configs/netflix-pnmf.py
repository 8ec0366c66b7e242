"""The netflix-pnmf catalog, made on the device from the seed in one
jitted call: A, the users × movies ratings (zero where a user has not
rated a movie), and W, H, the initial PNMF factors. See
``netflix-pnmf.json`` for the sizes and what was assumed."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

ZIPF_EXPONENT = 0.45
MEDIAN_RATINGS = 96.0
MEAN_RATINGS = 209.0


def catalog(cfg: dict, key) -> dict:
    a, w, h = _make(key, cfg["users"], cfg["movies"], cfg["rank"])
    return {"A": a, "W": w, "H": h}


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, users: int, movies: int, rank: int):
    k_count, k_perm, k_keep, k_val, k_w, k_h = jax.random.split(key, 6)
    # ratings per user: log-normal, median 96, mean 209
    sigma = math.sqrt(2.0 * math.log(MEAN_RATINGS / MEDIAN_RATINGS))
    draws = jnp.exp(math.log(MEDIAN_RATINGS)
                    + sigma * jax.random.normal(k_count, (users, 1)))
    draws = jnp.clip(draws, 1.0, float(movies))
    # movie popularity: Zipf over the movies, in a seeded order
    pop = jnp.arange(1, movies + 1, dtype=jnp.float32) ** -ZIPF_EXPONENT
    pop = jax.random.permutation(k_perm, pop / jnp.sum(pop))[None, :]
    rated_p = 1.0 - jnp.exp(-draws * pop)
    keep = jax.random.uniform(k_keep, (users, movies)) < rated_p
    stars = jax.random.randint(k_val, (users, movies), 1, 6)
    a = jnp.where(keep, stars.astype(jnp.float32), 0.0)
    scale = math.sqrt(3.0 / rank)
    w = jax.random.uniform(k_w, (users, rank), jnp.float32, 0.5, 1.5) * scale
    h = jax.random.uniform(k_h, (rank, movies), jnp.float32, 0.5, 1.5) * scale
    return a, w, h

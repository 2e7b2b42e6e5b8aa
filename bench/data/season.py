"""Season series: z-normalised random walks overlaid with a season.

The construction of arXiv:2105.14867, section 4.2, for the Season
(Large) efficiency set: each series is a z-normalised random walk with
its own seasonal content removed, mixed with a zero-mean, unit-variance
mask of length ``season_len`` tiled over the series, at a strength
drawn per series uniformly from ``strength +- spread``, then
z-normalised again.  It follows ``repro.data.synthetic.season_dataset``
(``per_series_strength=True``) step for step, written for the device so
that a corpus of millions of rows is made in seconds; it is a copy so
that a change to the program's own generator cannot move this one.
The per-phase means and the tiling are products with 0/1 matrices, so
no array has the season length as its minor axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _znorm(x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    sd = jnp.std(x, axis=-1, keepdims=True)
    return (x - mu) / jnp.maximum(sd, 1e-12)


def series(key, n: int, T: int, *, season_len: int, strength: float,
           spread: float):
    """(n, T) float32 series from ``key``."""
    L = season_len
    k_walk, k_mask, k_str = jax.random.split(key, 3)
    # tile[l, t] = 1 where t is at phase l: (n, L) @ tile repeats a mask
    # over the series, (n, T) @ tile.T / (T // L) averages each phase
    tile = (jnp.arange(T)[None, :] % L
            == jnp.arange(L)[:, None]).astype(jnp.float32)
    base = _znorm(jnp.cumsum(jax.random.normal(k_walk, (n, T), jnp.float32),
                             axis=1))
    mask = jax.random.normal(k_mask, (n, L), jnp.float32)
    mask = mask - jnp.mean(mask, axis=1, keepdims=True)
    mask = mask / jnp.maximum(jnp.std(mask, axis=1, keepdims=True), 1e-12)
    seas = jnp.matmul(mask, tile, precision=_HI)
    s = jax.random.uniform(k_str, (n, 1), jnp.float32,
                           max(0.01, strength - spread),
                           min(0.99, strength + spread))
    phase_mean = jnp.matmul(base, tile.T, precision=_HI) / (T // L)
    base_clean = _znorm(base - jnp.matmul(phase_mean, tile, precision=_HI))
    return _znorm(jnp.sqrt(s) * seas + jnp.sqrt(1.0 - s) * base_clean)

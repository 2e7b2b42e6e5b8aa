"""Z-normalised random walks: the synthetic data set of the similarity
search literature (Echihabi et al., "The Lernaean Hydra of Data Series
Similarity Search", PVLDB 12(2), 2018, section 4.1): each point is the
previous one plus a step drawn from N(0, 1), and every series is
z-normalised."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def series(key, n: int, T: int):
    """(n, T) float32 series from ``key``."""
    x = jnp.cumsum(jax.random.normal(key, (n, T), jnp.float32), axis=1)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    sd = jnp.std(x, axis=-1, keepdims=True)
    return (x - mu) / jnp.maximum(sd, 1e-12)

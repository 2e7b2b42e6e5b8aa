"""1d-SAX (Malinowski et al., IDA 2013) — the only SAX extension with the
same representation size, used as the trend-aware baseline on Economy.

Each segment is summarized by (mean at segment midpoint, slope) from a
per-segment linear regression; both are quantized — the mean against
N(0,1) quantiles (alphabet A_a), the slope against N(0, sigma_s^2)
quantiles with sigma_s^2 = 0.03 / seg_len (the paper's recommended
heuristic).  The distance reconstructs the per-segment line from symbol
centroids and sums squared differences — faithful to the original; as the
survey table notes, it is *not* proven lower-bounding (we measure this
empirically in the TLB benchmark).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.scipy.special import ndtri

from repro.core.breakpoints import discretize, gaussian_breakpoints


def _centroids(alphabet: int, sd: float):
    """Gaussian cell centroids (median of each equiprobable cell)."""
    qs = (jnp.arange(alphabet, dtype=jnp.float32) + 0.5) / alphabet
    return sd * ndtri(qs)


def segment_regression(x, W: int):
    """Per-segment (midpoint value, slope).  x: (..., T) -> two (..., W)."""
    T = x.shape[-1]
    assert T % W == 0
    n = T // W
    xs = x.reshape(*x.shape[:-1], W, n)
    s = jnp.arange(n, dtype=x.dtype)
    s_bar = (n - 1) / 2.0
    den = jnp.sum(jnp.square(s - s_bar))
    slope = jnp.sum(xs * (s - s_bar), axis=-1) / jnp.maximum(den, 1e-12)
    mid = jnp.mean(xs, axis=-1)           # value of the fit at the midpoint
    return mid, slope


@dataclass(frozen=True)
class OneDSAX:
    T: int
    W: int
    A_a: int          # mean alphabet
    A_s: int          # slope alphabet

    @property
    def seg_len(self) -> int:
        return self.T // self.W

    @property
    def sd_slope(self) -> float:
        return math.sqrt(0.03 / self.seg_len)

    @property
    def bits(self) -> float:
        return self.W * (math.log2(self.A_a) + math.log2(self.A_s))

    def encode(self, x):
        mid, slope = segment_regression(x, self.W)
        sa = discretize(mid, gaussian_breakpoints(self.A_a, 1.0))
        ss = discretize(slope, gaussian_breakpoints(self.A_s, self.sd_slope))
        return sa, ss

    def reconstruct(self, rep):
        """Symbol centroids -> per-timestep reconstruction (..., T)."""
        sa, ss = rep
        mid = _centroids(self.A_a, 1.0)[sa]            # (..., W)
        slope = _centroids(self.A_s, self.sd_slope)[ss]
        n = self.seg_len
        s = jnp.arange(n, dtype=jnp.float32) - (n - 1) / 2.0
        vals = mid[..., None] + slope[..., None] * s   # (..., W, n)
        return vals.reshape(*sa.shape[:-1], self.T)

    def distance(self, ra, rb):
        va = self.reconstruct(ra)
        vb = self.reconstruct(rb)
        return jnp.sqrt(jnp.sum(jnp.square(va - vb), axis=-1))

    def pairwise_distance(self, rq, rx):
        vq = self.reconstruct(rq)                       # (Q, T)
        vx = self.reconstruct(rx)                       # (N, T)
        # full f32 cross term (a TPU's default matmul precision is bf16)
        d2 = jnp.sum(vq * vq, -1)[:, None] + jnp.sum(vx * vx, -1)[None, :] \
            - 2.0 * jnp.matmul(vq, vx.T, precision=jax.lax.Precision.HIGHEST)
        return jnp.sqrt(jnp.maximum(d2, 0.0))

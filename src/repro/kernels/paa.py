"""Pallas kernel: PAA segmentation front-end (Eq. 5).

(N, T) -> (N, W) segment means.  Grid tiles candidates; within a tile the
(BLK_N, T) slab is contracted on the MXU against the (T, W) averaging
matrix ``A[t, w] = 1/E if t // E == w`` (E = T / W), at f32 contraction
precision.  Splitting the lane axis into (W, E) segments in VMEM would be
the direct formulation, but the TPU lowering cannot reshape a lane axis
into two, so the segment reduction is expressed as a contraction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLK_N = 128


def _kernel(x_ref, avg_ref, out_ref):
    out_ref[...] = jnp.dot(x_ref[...].astype(jnp.float32), avg_ref[...],
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def paa_pallas(x, n_segments: int, *, interpret: bool = False):
    """x: (N, T) -> (N, W) f32 segment means."""
    N, T = x.shape
    W = n_segments
    assert T % W == 0, (T, W)
    E = T // W
    blk_n = min(BLK_N, N)
    assert N % blk_n == 0, (N, blk_n)
    seg = jnp.arange(T)[:, None] // E == jnp.arange(W)[None, :]
    avg = jnp.where(seg, jnp.float32(1.0 / E), jnp.float32(0.0))
    return pl.pallas_call(
        _kernel,
        grid=(N // blk_n,),
        in_specs=[pl.BlockSpec((blk_n, T), lambda i: (i, 0)),
                  pl.BlockSpec((T, W), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((blk_n, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, W), jnp.float32),
        interpret=interpret,
    )(x, avg)

"""Pallas kernel: batched sSAX cell^2 sweep (Eq. 20, max form).

Stages per candidate tile (BLK_N):
  1. gather the four query-conditioned terms via one-hot MXU contractions:
         c1/c2 (BLK_N, L) from season symbols and t1/t2 (L, A_seas),
         d1/d2 (BLK_N, W) from residual symbols and u1/u2 (W, A_res);
  2. VPU cross-term:  cell[n,l,w] = max(0, c1+d1, c2+d2),
     accumulate sum of squares over (l, w).

The (L, W) cross never leaves VMEM; HBM traffic per candidate is L + W
symbol bytes.  The output block is a (BLK_N, 1) column (the TPU lowering
has no layout for a 1-D output block).  This replaces the paper's 4*W*L scalar lookups with
L+W gathers + an L*W fused VPU loop (same math — DESIGN.md §3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLK_N = 128


def _kernel(seas_ref, res_ref, t1_ref, t2_ref, u1_ref, u2_ref, out_ref, *,
            A_seas: int, A_res: int):
    seas = seas_ref[...]                      # (BLK_N, L)
    res = res_ref[...]                        # (BLK_N, W)
    t1, t2 = t1_ref[...], t2_ref[...]         # (L, A_seas)
    u1, u2 = u1_ref[...], u2_ref[...]         # (W, A_res)

    oh_s = (seas[:, :, None] ==
            jax.lax.broadcasted_iota(jnp.int32, (1, 1, A_seas), 2))
    c1 = jnp.sum(oh_s * t1[None], axis=2, dtype=jnp.float32)   # (BLK_N, L)
    c2 = jnp.sum(oh_s * t2[None], axis=2, dtype=jnp.float32)
    oh_r = (res[:, :, None] ==
            jax.lax.broadcasted_iota(jnp.int32, (1, 1, A_res), 2))
    d1 = jnp.sum(oh_r * u1[None], axis=2, dtype=jnp.float32)   # (BLK_N, W)
    d2 = jnp.sum(oh_r * u2[None], axis=2, dtype=jnp.float32)

    cell = jnp.maximum(0.0,
                       jnp.maximum(c1[:, :, None] + d1[:, None, :],
                                   c2[:, :, None] + d2[:, None, :]))
    per_l = jnp.sum(cell * cell, axis=2)                       # (BLK_N, L)
    out_ref[...] = jnp.sum(per_l, axis=1, keepdims=True)       # (BLK_N, 1)


def ssax_dist_pallas(seas_syms, res_syms, t1, t2, u1, u2, *,
                     interpret: bool = False):
    """(N, L) x (N, W) symbol arrays + four query tables -> (N,) f32."""
    N, L = seas_syms.shape
    _, W = res_syms.shape
    A_seas = t1.shape[1]
    A_res = u1.shape[1]
    blk = min(BLK_N, N)
    assert N % blk == 0, (N, blk)
    grid = (N // blk,)
    return pl.pallas_call(
        functools.partial(_kernel, A_seas=A_seas, A_res=A_res),
        grid=grid,
        in_specs=[
            pl.BlockSpec((blk, L), lambda i: (i, 0)),
            pl.BlockSpec((blk, W), lambda i: (i, 0)),
            pl.BlockSpec((L, A_seas), lambda i: (0, 0)),
            pl.BlockSpec((L, A_seas), lambda i: (0, 0)),
            pl.BlockSpec((W, A_res), lambda i: (0, 0)),
            pl.BlockSpec((W, A_res), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((blk, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 1), jnp.float32),
        interpret=interpret,
    )(seas_syms, res_syms, t1, t2, u1, u2)[:, 0]

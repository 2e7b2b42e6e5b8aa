"""Pallas kernel: batched SAX MINDIST^2 sweep.

Per candidate the math is  d2[n] = sum_w LUT2[q_w, x[n, w]]  — a W-way
gather per candidate in the paper's C code.  TPU formulation: the
query-conditioned squared table M = LUT2[q] (W, A) sits in VMEM, the
candidate symbols are one-hot expanded in-register and contracted on the
MXU:

    d2[n] = sum_{w,a} onehot(x[n, w])[a] * M[w, a]

i.e. a (N_blk, W*A) x (W*A,) dot — HBM traffic is the int8/int32 symbol
tile only (W bytes/candidate at int8), which is the whole point of the
symbolic representation on TPU (DESIGN.md §3).

Block layout: grid over candidate tiles; symbols tile (BLK_N, W) and the
full (W, A) table per step; the output block is a (BLK_N, 1) column (the
TPU lowering has no layout for a 1-D output block).  VMEM budget: BLK_N*W*4 + W*A*4; for the
paper-max A=1024, W<=96 the table is <= 384 KB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLK_N = 256


def _kernel(sym_ref, table_ref, out_ref, *, A: int):
    syms = sym_ref[...]                       # (BLK_N, W) int32
    table = table_ref[...]                    # (W, A) f32
    # one-hot contraction on the MXU: (BLK_N, W, A) x (W, A) -> (BLK_N,)
    onehot = (syms[:, :, None] ==
              jax.lax.broadcasted_iota(jnp.int32, (1, 1, A), 2))
    per_w = jnp.sum(onehot * table[None, :, :], axis=2, dtype=jnp.float32)
    out_ref[...] = jnp.sum(per_w, axis=1, keepdims=True)      # (BLK_N, 1)


def sax_dist_pallas(symbols, query_table, *, interpret: bool = False):
    """symbols: (N, W) int32; query_table: (W, A) f32 -> (N,) f32."""
    N, W = symbols.shape
    Wt, A = query_table.shape
    assert Wt == W
    blk = min(BLK_N, N)
    assert N % blk == 0, (N, blk)
    grid = (N // blk,)
    return pl.pallas_call(
        functools.partial(_kernel, A=A),
        grid=grid,
        in_specs=[
            pl.BlockSpec((blk, W), lambda i: (i, 0)),
            pl.BlockSpec((W, A), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((blk, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 1), jnp.float32),
        interpret=interpret,
    )(symbols, query_table)[:, 0]

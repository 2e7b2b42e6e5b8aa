"""Pallas kernel: MASS-style z-normalized windowed squared distances.

For a (Q, m) batch of z-normalized queries and an (N, T) raw corpus,
computes

    d2[qi, n, s] = || znorm(x[n, s*stride : s*stride + m]) - q[qi] ||^2

for every window start ``s`` — the distance profile that subsequence
matching brute-forces — WITHOUT materializing the N * S windows.  Each
window's mean / std come from its sum and sum of squares (as in MASS,
Mueen et al.), and the sliding dot product is computed directly, by an
m-step accumulation, instead of by an FFT, which Pallas does not
provide.  With window mean mu and std sigma (clamped at ``EPS`` exactly
like :func:`repro.core.normalize.znormalize`), the distance expands to

    d2 = sum(q^2) + (S2 - m*mu^2)/sigma_c^2 - 2*(dot - mu*sum(q))/sigma_c

so only the three window reductions are needed.

TPU layout: the corpus is handed to the kernel transposed, (T, N), so
corpus rows run along the 128 vector lanes and time along sublanes.  A
program instance owns ``BLK_N`` rows and ``blk_s`` window starts: it
copies the time slab its windows cover from HBM into VMEM (one DMA at
a dynamic offset), then accumulates the three reductions over the m
window offsets with sublane-strided ref loads (``pl.ds(t, blk_s,
stride)``) — dynamic and strided slicing happen on refs, never on
values, which the TPU lowering does not support.  The queries are
broadcast across the lanes in the wrapper, so each step reads
``q[qi, t]`` as one (1, BLK_N) row.  Grid: (queries x row-blocks x
window-tiles); ragged N / S pad to block multiples and the padded rows /
window starts are sliced out of the result, and the time axis is
zero-padded so the last tile's slab stays in bounds.  Slabs start and
end on multiples of 8 time steps, the sublane tile of the DMA.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLK_N = 128        # corpus rows per program (the lane width)
BLK_S = 512        # window starts per program (at most)
SLAB_ROWS = 8192   # time steps of one VMEM slab (4 MiB) before blk_s shrinks

EPS = 1e-12        # must match repro.core.normalize.znormalize


def n_windows(T: int, m: int, stride: int) -> int:
    """Number of length-m windows of a length-T series at ``stride``."""
    if m > T:
        raise ValueError(f"window m={m} longer than series T={T}")
    if stride < 1:
        raise ValueError(f"stride must be >= 1, got {stride}")
    return (T - m) // stride + 1


def _kernel(x_hbm, q_ref, out_ref, slab, sem, *, m: int, stride: int,
            blk_s: int):
    i = pl.program_id(1)
    j = pl.program_id(2)
    # a tile start is a multiple of blk_s * stride, and blk_s is a
    # multiple of 8 whenever there is more than one tile
    t0 = pl.multiple_of(j * (blk_s * stride), 8)
    cp = pltpu.make_async_copy(
        x_hbm.at[pl.ds(t0, slab.shape[0]), pl.ds(i * BLK_N, BLK_N)],
        slab, sem)
    cp.start()
    cp.wait()

    # window s of the tile covers slab rows [s*stride, s*stride + m):
    # offset t of every window is one strided (blk_s, BLK_N) load
    def body(t, acc):
        s1, s2, dot = acc
        xt = slab[pl.ds(t, blk_s, stride=stride), :]
        qt = q_ref[0, pl.ds(t, 1), :]                 # (1, BLK_N)
        return s1 + xt, s2 + xt * xt, dot + qt * xt

    zero = jnp.zeros((blk_s, BLK_N), jnp.float32)
    s1, s2, dot = jax.lax.fori_loop(0, m, body, (zero, zero, zero))

    q = q_ref[0]                                      # (m, BLK_N)
    q_sum = jnp.sum(q, axis=0, keepdims=True)
    q_ss = jnp.sum(q * q, axis=0, keepdims=True)
    mu = s1 / m
    var = s2 / m - mu * mu
    sig = jnp.maximum(jnp.sqrt(jnp.maximum(var, 0.0)), EPS)
    norm2 = jnp.maximum(s2 - m * mu * mu, 0.0) / (sig * sig)
    d2 = q_ss + norm2 - 2.0 * (dot - mu * q_sum) / sig
    # a zero-variance window z-normalizes to the zero vector (znormalize's
    # eps guard), so its distance is exactly sum(q^2); the expanded form
    # would divide rounding noise by eps instead
    d2 = jnp.where(var > 0.0, d2, q_ss)
    out_ref[0] = jnp.maximum(d2, 0.0)                 # (blk_s, BLK_N)


def _tile_starts(S: int, m: int, stride: int) -> int:
    """Window starts per program: all of them when they fit one tile,
    else the largest multiple of 8 (the sublane tile) that is at most
    ``BLK_S`` and keeps the slab within ``SLAB_ROWS``."""
    fit = (SLAB_ROWS - m) // stride + 1
    blk = max(8, min(BLK_S, fit) // 8 * 8)
    return S if S <= blk else blk


def windowed_euclid_pallas(x, q, *, stride: int = 1,
                           interpret: bool = False):
    """x: (N, T) raw rows; q: (m,) or (Q, m) z-normalized queries ->
    (N, S) or (Q, N, S) f32 squared distances to every z-normalized
    window, S = (T - m) // stride + 1.

    Accepts ragged N / S (padded internally to block multiples; padded
    rows and window starts are sliced out of the result).
    """
    squeeze = q.ndim == 1
    if squeeze:
        q = q[None, :]
    N, T = x.shape
    Q, m = q.shape
    S = n_windows(T, m, stride)
    blk_s = _tile_starts(S, m, stride)
    sp = S + (-S) % blk_s
    np_ = N + (-N) % BLK_N
    # a slab covers its tile's windows, rounded up to the sublane tile
    slab_len = (blk_s - 1) * stride + m
    slab_len += (-slab_len) % 8
    tp = max((sp - blk_s) * stride + slab_len, T)
    xt = jnp.pad(x.astype(jnp.float32).T, ((0, tp - T), (0, np_ - N)))
    qb = jnp.broadcast_to(q.astype(jnp.float32)[:, :, None], (Q, m, BLK_N))
    grid = (Q, np_ // BLK_N, sp // blk_s)
    out = pl.pallas_call(
        functools.partial(_kernel, m=m, stride=stride, blk_s=blk_s),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec((1, m, BLK_N), lambda qi, i, j: (qi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_s, BLK_N),
                               lambda qi, i, j: (qi, j, i)),
        out_shape=jax.ShapeDtypeStruct((Q, sp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((slab_len, BLK_N), jnp.float32),
                        pltpu.SemaphoreType.DMA],
        interpret=interpret,
    )(xt, qb)
    out = jnp.swapaxes(out, 1, 2)[:, :N, :S]
    return out[0] if squeeze else out

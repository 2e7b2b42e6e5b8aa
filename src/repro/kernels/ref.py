"""Pure-jnp oracles for every kernel (the allclose ground truth)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def sax_dist_ref(symbols, query_table):
    """SAX MINDIST^2 sweep.

    symbols: (N, W) int32 dataset symbols; query_table: (W, A) f32 with
    query_table[w, a] = cell(q_w, a)^2 (query-conditioned squared cells).
    Returns (N,) f32 = sum_w query_table[w, symbols[:, w]].
    """
    N, W = symbols.shape
    w_idx = jnp.arange(W)[None, :]
    return jnp.sum(query_table[w_idx, symbols], axis=-1)


def ssax_dist_ref(seas_syms, res_syms, t1, t2, u1, u2):
    """sSAX cell^2 sweep (Eq. 20 collapsed to max form).

    seas_syms: (N, L) int32; res_syms: (N, W) int32.
    t1/t2: (L, A_seas) query-conditioned season terms
        t1[l, a] = lower(q_l) - upper(a),  t2[l, a] = lower(a) - upper(q_l)
    u1/u2: (W, A_res) residual terms, same construction.
    Returns (N,) f32 = sum_{l,w} max(0, c1_l + d1_w, c2_l + d2_w)^2.
    """
    l_idx = jnp.arange(t1.shape[0])[None, :]
    w_idx = jnp.arange(u1.shape[0])[None, :]
    c1 = t1[l_idx, seas_syms]          # (N, L)
    c2 = t2[l_idx, seas_syms]
    d1 = u1[w_idx, res_syms]           # (N, W)
    d2 = u2[w_idx, res_syms]
    cell = jnp.maximum(
        0.0, jnp.maximum(c1[:, :, None] + d1[:, None, :],
                         c2[:, :, None] + d2[:, None, :]))
    return jnp.sum(jnp.square(cell), axis=(1, 2))


def paa_ref(x, n_segments: int):
    """(N, T) -> (N, W) segment means."""
    N, T = x.shape
    W = n_segments
    return jnp.mean(x.reshape(N, W, T // W), axis=-1)


def euclid_ref(x, q):
    """(N, T) vs (T,) -> (N,) squared Euclidean distances."""
    d = x - q[None, :]
    return jnp.sum(jnp.square(d), axis=-1)


def sliding_dot_ref(x, q, stride: int = 1):
    """(N, T) rows vs (Q, m) queries -> (Q, N, S) sliding dot products
    ``sum_i x[n, s*stride + i] * q[qi, i]``, windows materialized
    explicitly — the ground truth for both the m-step accumulation and
    the FFT paths in ``kernels.fft_dot``."""
    m = q.shape[-1]
    T = x.shape[-1]
    S = (T - m) // stride + 1
    starts = jnp.arange(S) * stride
    idx = starts[:, None] + jnp.arange(m)[None, :]     # (S, m)
    w = x[:, idx]                                      # (N, S, m)
    return jnp.einsum("nsm,qm->qns", w, q,
                      precision=jax.lax.Precision.HIGHEST)


def windowed_euclid_ref(x, q, stride: int = 1):
    """(N, T) raw rows vs (Q, m) z-normalized queries -> (Q, N, S)
    squared distances to every z-normalized length-m window at ``stride``
    (S = (T - m) // stride + 1), windows materialized explicitly."""
    from repro.core.normalize import znormalize
    m = q.shape[-1]
    T = x.shape[-1]
    S = (T - m) // stride + 1
    starts = jnp.arange(S) * stride
    idx = starts[:, None] + jnp.arange(m)[None, :]     # (S, m)
    w = znormalize(x[:, idx])                          # (N, S, m)
    d = w[None] - q[:, None, None, :]                  # (Q, N, S, m)
    return jnp.sum(jnp.square(d), axis=-1)

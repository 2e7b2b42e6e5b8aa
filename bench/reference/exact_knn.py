"""Plain exact k-nearest-neighbour reference: every row, Euclidean
distance, float64, ties broken toward the smaller row id.

It imports nothing of the system under test.  A brute-force float64 scan
of every query against millions of rows would take minutes on the host,
so the scan runs in two steps that together are exact:

1. a float32 filter on the device, in row blocks: d2 = |q|^2 + |x|^2 -
   2 q.x at the highest matmul precision, keeping each query's ``k +
   MARGIN`` smallest values;
2. float64 distances on the host for those candidates only.

The filter's error in any d2 is at most ``eps = ERR_UNITS * T * 2**-24 *
(|q|^2 + max |x|^2)``.  Every true top-k row then has a filter value at
most ``F_k + 2 eps``, where ``F_k`` is the k-th smallest filter value; so
where the last kept value exceeds ``F_k + 2 eps`` the candidates hold the
true top k.  A query for which that does not hold is scanned in float64
on the host instead.

``answers(..., dtype="bfloat16")`` is the control: the same scan with
the inputs rounded to bfloat16 and no float64 step, put where the system
under test would stand.
"""

from __future__ import annotations

import numpy as np

#: units of float32 roundoff per term allowed in a filtered d2
ERR_UNITS = 16.0
#: candidates kept per query beyond k
MARGIN = 128
ROW_BLOCK = 1 << 16
Q_BLOCK = 1024
#: rows per block of the float64 host scan
HOST_BLOCK = 1 << 15


def _block_fn(width: int, dtype: str):
    import jax
    import jax.numpy as jnp

    low = dtype == "bfloat16"
    prec = jax.lax.Precision.DEFAULT if low else jax.lax.Precision.HIGHEST

    @jax.jit
    def step(q, x, live, base, best_d, best_i):
        if low:
            q = q.astype(jnp.bfloat16)
            x = x.astype(jnp.bfloat16)
        qq = jnp.sum(jnp.square(q.astype(jnp.float32)), axis=1)
        xx = jnp.sum(jnp.square(x.astype(jnp.float32)), axis=1)
        qx = jnp.matmul(q, x.T, precision=prec,
                        preferred_element_type=jnp.float32)
        d2 = qq[:, None] + xx[None, :] - 2.0 * qx
        rows = jnp.arange(x.shape[0], dtype=jnp.int32)
        d2 = jnp.where((rows < live)[None, :], d2, jnp.inf)
        ids = jnp.broadcast_to(base + rows, d2.shape)
        cat_d = jnp.concatenate([best_d, d2], axis=1)
        cat_i = jnp.concatenate([best_i, ids], axis=1)
        neg, pos = jax.lax.top_k(-cat_d, width)
        xmax = jnp.max(jnp.where(rows < live, xx, 0.0))
        return -neg, jnp.take_along_axis(cat_i, pos, axis=1), xmax

    return step


def _filter(corpus: np.ndarray, queries: np.ndarray, width: int,
            dtype: str):
    """(ids (Q, width) int64, d2 (Q, width) float64 ascending, max |x|^2)
    of the ``width`` smallest filter values per query."""
    import jax
    import jax.numpy as jnp

    n, T = corpus.shape
    step = _block_fn(width, dtype)
    q_blocks = [jnp.asarray(queries[lo:lo + Q_BLOCK], jnp.float32)
                for lo in range(0, queries.shape[0], Q_BLOCK)]
    best = [(jnp.full((qb.shape[0], width), jnp.inf, jnp.float32),
             jnp.full((qb.shape[0], width), -1, jnp.int32))
            for qb in q_blocks]
    xmax = jnp.float32(0.0)
    for lo in range(0, n, ROW_BLOCK):
        blk = corpus[lo:lo + ROW_BLOCK]
        live = blk.shape[0]
        if live < ROW_BLOCK:
            blk = np.concatenate(
                [blk, np.zeros((ROW_BLOCK - live, T), corpus.dtype)])
        x = jax.device_put(np.asarray(blk, np.float32))
        for j, qb in enumerate(q_blocks):
            d, i, m = step(qb, x, jnp.int32(live), jnp.int32(lo), *best[j])
            best[j] = (d, i)
            xmax = jnp.maximum(xmax, m)
        del x
    ids = np.concatenate([np.asarray(i, np.int64) for _, i in best])
    d2 = np.concatenate([np.asarray(d, np.float64) for d, _ in best])
    return ids, d2, float(xmax)


def _f64_dist(corpus, ids, query) -> np.ndarray:
    diff = corpus[ids].astype(np.float64) - query.astype(np.float64)
    return np.sqrt(np.sum(diff * diff, axis=-1))


def _host_scan(corpus, query, k):
    """Float64 top k of one query over every row (ids, distances)."""
    q = query.astype(np.float64)
    d = np.empty(corpus.shape[0], np.float64)
    for lo in range(0, corpus.shape[0], HOST_BLOCK):
        x = corpus[lo:lo + HOST_BLOCK].astype(np.float64) - q
        d[lo:lo + x.shape[0]] = np.sqrt(np.einsum("ij,ij->i", x, x))
    ids = np.nonzero(d <= np.partition(d, k - 1)[k - 1])[0]
    ids = ids[np.lexsort((ids, d[ids]))[:k]]
    return ids, d[ids]


def exact_topk(corpus: np.ndarray, queries: np.ndarray, k: int):
    """Exact float64 top k of every query: (ids (Q, k) int64, distances
    (Q, k) float64), each row ascending with ties toward the smaller id,
    and the number of queries that needed the host scan."""
    n, T = corpus.shape
    k = min(k, n)
    width = min(n, k + MARGIN)
    cand, d2, xmax = _filter(corpus, queries, width, "float32")
    qq = np.einsum("ij,ij->i", queries.astype(np.float64),
                   queries.astype(np.float64))
    eps = ERR_UNITS * T * 2.0 ** -24 * (qq + xmax)
    sure = (width == n) | (d2[:, -1] > d2[:, k - 1] + 2.0 * eps)
    out_i = np.empty((queries.shape[0], k), np.int64)
    out_d = np.empty((queries.shape[0], k), np.float64)
    scanned = 0
    for r in range(queries.shape[0]):
        if sure[r]:
            c = cand[r][cand[r] >= 0]
            d = _f64_dist(corpus, c, queries[r])
            order = np.lexsort((c, d))[:k]
            out_i[r], out_d[r] = c[order], d[order]
        else:
            out_i[r], out_d[r] = _host_scan(corpus, queries[r], k)
            scanned += 1
    return out_i, out_d, scanned


def answers(corpus: np.ndarray, queries: np.ndarray, k: int,
            dtype: str = "bfloat16"):
    """The control: top k by the filter alone at ``dtype``, with the
    distances it computed, (ids (Q, k), distances (Q, k))."""
    ids, d2, _ = _filter(corpus, queries, min(k, corpus.shape[0]), dtype)
    return ids, np.sqrt(np.maximum(d2, 0.0))

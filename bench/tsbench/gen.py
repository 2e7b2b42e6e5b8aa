"""Inputs of a run, made on the device.

The configuration's generator (``bench/data/<name>.py``) makes series in
blocks of ``BLOCK`` rows, one jitted call per block, each fetched to the
host.  Disjoint streams come from a seed: the corpus, the query pool of
the window, and the queries that warm up set-up.

The corpus comes from ``--seed``.  The queries come from the traffic
mix's own ``query_seed``, the same for every run, and ``--seed`` only
reorders them within each block of ``clients`` consecutive queries:
every run is asked the same questions, in another order, over another
corpus.  How many verification rounds a query takes varies several-fold
from query to query, and a window answers a few hundred; drawing them
anew for every run would make the seed, not the program, set the pace.
The same seed gives the same inputs, bit for bit.
"""

from __future__ import annotations

import numpy as np

BLOCK = 1 << 16
#: blocks generated and in transfer at once (1 GB at 960 values a row)
IN_FLIGHT = 4
CORPUS, POOL, WARM = 0, 1, 2


def root_key(seed: int):
    """A PRNG key from any whole seed (wider than 32 bits too)."""
    import jax
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def queries(gen_module, args: dict, traffic: dict, seed: int,
            T: int) -> np.ndarray:
    """The traffic's query pool in ``seed``'s order: each block of
    ``clients`` consecutive queries permuted."""
    n = int(traffic["pool"])
    pool = series(gen_module, args, int(traffic["query_seed"]), POOL, n, T)
    block = int(traffic["clients"])
    rng = np.random.default_rng(int(seed))
    order = np.arange(n)
    for lo in range(0, n, block):
        order[lo:lo + block] = lo + rng.permutation(min(block, n - lo))
    return pool[order]


def series(gen_module, args: dict, seed: int, stream: int, n: int,
           T: int) -> np.ndarray:
    """(n, T) float32 host array: block ``b`` of stream ``stream`` is
    ``gen_module.series(fold_in(fold_in(key, stream), b), BLOCK, T)``.
    Up to ``IN_FLIGHT`` blocks are made and copied to the host at once."""
    import jax
    from functools import partial

    key = jax.random.fold_in(root_key(seed), stream)
    rows = min(BLOCK, n)
    fn = jax.jit(partial(gen_module.series, n=rows, T=T, **args))
    out = np.empty((n, T), np.float32)
    pending = []

    def land():
        lo, blk = pending.pop(0)
        out[lo:lo + rows] = np.asarray(blk)[:n - lo]

    for b, lo in enumerate(range(0, n, rows)):
        blk = fn(jax.random.fold_in(key, b))
        blk.copy_to_host_async()
        pending.append((lo, blk))
        if len(pending) > IN_FLIGHT:
            land()
    while pending:
        land()
    return out

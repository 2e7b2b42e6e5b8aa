"""Unified batched k-NN matching engine.

``MatchEngine`` answers batched multi-query **top-k** matching — exact
(lower-bound pruned scan) and approximate (representation top-k then
verify) — over any encoder with ``encode`` + ``pairwise_distance``
(SAX, sSAX, tSAX, stSAX, 1d-SAX) and a ``RawStore`` for raw
verification.

API
---
::

    engine = MatchEngine(encoder, RawStore.ssd(D))
    res = engine.topk(queries, k=32)                  # exact k-NN
    res = engine.topk(queries, k=32, exact=False)     # approximate
    res = engine.verify_candidates(queries, cand_idx) # external candidates

``res`` is a :class:`TopKResult`: per-query ``indices``/``distances``
(Q, k), per-query ``raw_accesses`` / ``pruned_fraction``, and the
store-level deduplicated access count + modeled I/O seconds.
``verify_candidates`` is the hook for distributed serving:
``core.distributed.repr_topk_sharded`` produces the candidate frontier,
the engine verifies it against raw storage
(``core.distributed.make_engine_service`` wires the two together).

Batched-verification correctness argument
-----------------------------------------
The paper's sequential exact scan visits candidates in representation-
distance order and stops when best-so-far ED <= the next representation
distance; since every representation distance lower-bounds d_ED
(Appendix A.1–A.5), no pruned candidate can be the NN.  The engine
generalizes this to top-k and to fixed-size batches:

* Per query it maintains a best-k *frontier* (the k smallest verified
  true distances so far, with their indices).  The pruning threshold is
  the k-th best frontier distance — ``inf`` until k candidates are
  verified, so the first ceil(k / batch) batches are never pruned.
* Candidates are consumed in representation-distance order in batches
  of ``batch_size``.  Before verifying a batch, the engine checks
  ``kth_best < repr_dist(next unseen)``; because the candidate order is
  sorted, that single comparison lower-bounds *every* unseen candidate,
  so stopping there cannot drop a true top-k member (any unseen c has
  d_ED(q, c) >= d_repr(q, c) >= repr_dist(next) > kth_best).  The
  comparison is strict: a candidate whose bound exactly equals the k-th
  best could still TIE the k-th member's true distance and win on the
  (distance, dataset index) tie-break, so boundary-equal candidates are
  verified rather than pruned.
* Therefore the surviving frontier equals the sequential scan's result
  exactly; batching only over-fetches by at most one batch per query
  (the batch in flight when the threshold crossed).

Two loops run the rounds, with the same comparisons in the same order:

* The **host loop** of :func:`topk_verify`: per round, the surviving
  candidate rows of *all* active queries are fetched from the store in
  one call (one modeled seek per round instead of one per row) and
  distanced via the Pallas kernel ``kernels.euclid.euclid_pallas`` —
  natively on TPU, ``interpret=True`` elsewhere — or handed to a
  device-resident ``dist_fn``; the frontier merge is a lexicographic
  sort on (distance, index), in numpy or on device.  The numpy path is
  bit-identical to a numpy brute-force scan because each row's distance
  is reduced over the same T values in the same order regardless of
  batch shape.
* The **device loop** (``core.distributed.verify_stream_rr``): when the
  candidates come from a device-ordered stream and are verified against
  the raw device mirror, every round — peek, take, row distances,
  square root, merge — runs inside one device program (a
  ``lax.while_loop``): one launch and one fetch per call instead of
  several host round trips per round.  It answers, and schedules its
  rounds, exactly as the host loop over the same stream does.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import numpy as np

from repro.core.matching import RawStore


# ---------------------------------------------------------------------------
# Results
# ---------------------------------------------------------------------------

@dataclass
class TopKResult:
    """Batched top-k matches.  Rows padded with index -1 / distance inf
    when fewer than k candidates exist."""

    indices: np.ndarray          # (Q, k) int64 dataset rows, best first
    distances: np.ndarray        # (Q, k) true d_ED (verifier dtype)
    raw_accesses: np.ndarray     # (Q,) candidates verified per query
    pruned_fraction: np.ndarray  # (Q,) 1 - raw_accesses / N
    store_accesses: int          # deduplicated physical row reads
    store_fetches: int           # batched fetch() calls (modeled seeks)
    io_seconds: float            # batch-accounted modeled I/O
    device_loop: bool = False    # the rounds ran as one device program


# ---------------------------------------------------------------------------
# Verifiers: (union_rows (U, T), queries (Qa, T), gather (Qa, B)) -> (Qa, B)
# ---------------------------------------------------------------------------

def numpy_verifier(rows: np.ndarray, qs: np.ndarray,
                   gather: np.ndarray) -> np.ndarray:
    """Host verification, bit-identical to a numpy brute-force scan (each
    row's sum runs over the same contiguous T values)."""
    per_q = rows[gather]                             # (Qa, B, T)
    d2 = np.sum(np.square(per_q - qs[:, None, :]), axis=-1)
    return np.sqrt(d2)


def kernel_verifier(rows: np.ndarray, qs: np.ndarray,
                    gather: np.ndarray) -> np.ndarray:
    """Device verification through the Pallas euclid kernel (interpret
    mode off-TPU).  Each query is distanced against its own candidate
    rows only — one kernel launch per active query, all with the same
    (B, T) shape so repeated rounds hit the jit cache."""
    import jax.numpy as jnp
    from repro.kernels import ops

    per_q = rows[gather]                             # (Qa, B, T)
    out = np.empty(gather.shape, np.float32)
    for r in range(qs.shape[0]):
        d2 = np.asarray(ops.euclid_batch(
            jnp.asarray(per_q[r], jnp.float32),
            jnp.asarray(qs[r], jnp.float32)))
        out[r] = np.sqrt(np.maximum(d2, 0.0))
    return out


def make_verifier(mode: str) -> Callable:
    if mode == "numpy":
        return numpy_verifier
    if mode in ("kernel", "host"):
        # "host" is the host-side fallback of the device-resident
        # verification path: raw rows are fetched from the store (modeled
        # I/O oracle) but distanced through the SAME Pallas kernel math
        # the sharded device path uses, so the two are bit-identical
        return kernel_verifier
    if mode == "auto":
        import jax
        return kernel_verifier if jax.default_backend() == "tpu" \
            else numpy_verifier
    raise ValueError(f"unknown verify mode {mode!r}")


# ---------------------------------------------------------------------------
# Frontier merge: keep the k smallest of (frontier ++ batch) per query
# ---------------------------------------------------------------------------

def merge_topk_numpy(all_d: np.ndarray, all_i: np.ndarray, k: int):
    """(Qa, M) -> (Qa, k); ties broken by smaller dataset index, matching
    a stable argsort of the full distance array."""
    n_big = np.int64(np.iinfo(np.int64).max)
    tie = np.where(all_i < 0, n_big, all_i)
    out_d = np.empty((all_d.shape[0], k), all_d.dtype)
    out_i = np.empty((all_i.shape[0], k), np.int64)
    for r in range(all_d.shape[0]):
        sel = np.lexsort((tie[r], all_d[r]))[:k]
        out_d[r] = all_d[r][sel]
        out_i[r] = all_i[r][sel]
    return out_d, out_i


def merge_topk_device(all_d: np.ndarray, all_i: np.ndarray, k: int):
    """Device merge with the host tie-break contract: a lexicographic
    sort on the stable composite key (distance, dataset index), so ties
    at exactly-equal distances resolve to the smaller dataset index —
    same contract as ``merge_topk_numpy`` (padding index -1 sorts last).
    Runs at device precision (f32 when x64 is off): the returned
    distances are the ones the sort saw, keeping the frontier ascending
    and the k-th-best pruning threshold consistent — distances that are
    distinct in f64 but equal in f32 count as ties, the device merge's
    documented precision contract."""
    import jax.numpy as jnp
    d = jnp.asarray(all_d)
    i = jnp.asarray(all_i)
    tie = jnp.where(i < 0, jnp.iinfo(jnp.int32).max, i)
    sel = jnp.lexsort((tie, d), axis=-1)[:, :k]
    return (np.asarray(jnp.take_along_axis(d, sel, axis=1)),
            np.asarray(jnp.take_along_axis(i, sel, axis=1)).astype(np.int64))


# ---------------------------------------------------------------------------
# Core batched scan
# ---------------------------------------------------------------------------

def topk_verify(queries_raw, repr_dists, store: RawStore, *, k: int = 1,
                batch_size: int = 64, verifier: Callable = numpy_verifier,
                merge: Callable = merge_topk_numpy,
                init_d=None, init_i=None, col_ids=None,
                dist_fn: Optional[Callable] = None,
                on_verified: Optional[Callable] = None,
                stream=None, trace=None) -> TopKResult:
    """Exact top-k under d_ED for a query batch given lower-bounding
    representation distances (Q, N).  See the module docstring for the
    correctness argument.

    ``init_d`` / ``init_i``: optional (Q, <=k) already-verified frontier
    (sorted ascending, ties by index) to seed the best-k with — used by
    the index candidate source (``repro.index.candidates``) so tree seed
    candidates are not verified twice.  Seeded
    candidates must carry +inf in ``repr_dists`` (or be absent), otherwise
    they would enter the merge a second time.

    ``col_ids``: optional (N,) dataset row ids, one per ``repr_dists``
    column, STRICTLY INCREASING — lets a sparse caller pass only the
    surviving candidates instead of a full-corpus-width matrix (column j
    means row ``col_ids[j]``; ``pruned_fraction`` is then relative to the
    candidate set, not the corpus).

    ``dist_fn``: optional device-resident verification hook
    (``core.distributed``): ``dist_fn(q_idx, cand) -> (Qa, B) true
    distances`` for the active-query id batch, computed WITHOUT moving
    raw rows to the host — the store is never fetched (its accounting
    stays untouched: zero rows moved to host is the device path's
    truthful I/O).  ``-1`` candidate entries may return anything; they
    are masked to +inf here.

    ``on_verified``: optional ``on_verified(qi, ids, dists)`` callback
    fired once per verification round per active query with exactly the
    (dataset/window ids, true distances) that round verified — the hook
    exclusion widening uses to accumulate the every-id-verified-once
    frontier (``repro.subseq.SubseqEngine``).

    ``stream``: optional device-ordered candidate stream
    (``core.distributed.DeviceOrderedStream`` duck type: ``peek() ->
    (Q,) next unverified bound``, ``take(aq, batch) -> (len(aq), batch)
    GLOBAL ids, -1-padded, self-advancing``, ``width``) replacing
    ``repr_dists`` entirely — the (Q, N) bound matrix then never
    materializes on the host.  The stream already yields dataset ids,
    so it is mutually exclusive with ``col_ids``; the verification
    schedule is identical to the matrix path when the stream's order is
    (bound, id)-sorted, and the result is exact for ANY valid-bound
    order.

    Which loop runs the rounds (module docstring): the device loop when
    a ``stream`` is given, ``dist_fn`` carries ``verify_loop`` (the
    sharded sweep's) and there is no ``on_verified``; the host loop
    otherwise — matrix sources, host verifiers, window verification,
    exclusion widening.
    Both give the same frontier, accesses and rounds (tested in
    tests/test_device_loop.py); ``TopKResult.device_loop`` says which
    ran.

    ``trace``: optional ``repro.obs.Trace``.  On the host loop each
    round records its ``peek``, ``take``, ``dist`` and ``merge`` steps
    as child spans (the closing ``peek`` that finds no active query is
    one more); on the device loop one ``loop`` span covers every round
    up to the fetched result, and each round is recorded afterwards
    from the program's per-round records (its ``wall_s`` an even share
    of the span).  The counter ``device_loop`` says which loop ran.
    Every recording site is guarded by ``trace is None`` (or
    ``maybe_span``) and records copies after the round's computation —
    with no trace the host loop executes the pre-observability
    instruction stream plus four null-context entries a round, the
    device loop makes no per-round host call at all, and with one the
    results and store accounting stay bit-identical (property-tested
    in tests/test_obs_neutrality.py)."""
    import time as _time
    from repro.obs.trace import maybe_span
    qs = np.asarray(queries_raw)        # native dtype: the host verifier
    if qs.ndim == 1:                    # stays bit-identical to brute force
        qs = qs[None]
    if stream is not None:
        assert repr_dists is None and col_ids is None, \
            "stream replaces the bound matrix and yields global ids"
        rd = None
        q_n, n = qs.shape[0], int(stream.width)
    else:
        rd = np.asarray(repr_dists)
        if rd.ndim == 1:
            rd = rd[None]
        q_n, n = rd.shape
        if col_ids is not None:
            col_ids = np.asarray(col_ids, np.int64)
            assert col_ids.shape == (n,), (col_ids.shape, n)

    init_w = 0
    if init_d is not None:
        init_d = np.asarray(init_d, np.float64)
        init_i = np.asarray(init_i, np.int64)
        if init_d.ndim == 1:
            init_d, init_i = init_d[None], init_i[None]
        init_w = init_d.shape[1]
    k = min(k, n + init_w)
    front_d = np.full((q_n, k), np.inf, np.float64)
    front_i = np.full((q_n, k), -1, np.int64)
    if init_w:
        m = min(k, init_w)
        front_d[:, :m] = init_d[:, :m]
        front_i[:, :m] = init_i[:, :m]
    if n == 0:                          # nothing to scan: seeded frontier
        return TopKResult(indices=front_i, distances=front_d,
                          raw_accesses=np.zeros(q_n, np.int64),
                          pruned_fraction=np.ones(q_n),
                          store_accesses=0, store_fetches=0, io_seconds=0.0)
    if stream is None:
        order = np.argsort(rd, axis=1, kind="stable")
        sorted_d = np.take_along_axis(rd, order, axis=1)
        # +inf bounds mark non-candidates (e.g. another query's rows in a
        # sparse sweep, or already-seeded members): they must never enter a
        # verification batch, even as over-fetch — a seeded member verified
        # again would enter the merge twice
        n_fin = np.isfinite(rd).sum(axis=1)
    pos = np.zeros(q_n, np.int64)
    acc = np.zeros(q_n, np.int64)
    start_acc, start_fetch = store.accesses, store.fetches
    # the device loop runs every round of a device-ordered stream in one
    # program; the host loop below serves everything else
    loop = (getattr(dist_fn, "verify_loop", None)
            if stream is not None and on_verified is None else None)
    if trace is not None:                # candidates handed to this scan
        if stream is None:
            gen = n_fin.astype(np.int64)
            # id layer behind the accumulated count: exclusion widening
            # re-hands surviving candidates every round, so the summed
            # "generated" over-counts — the noted ids dedup it into the
            # per-query "generated_unique" the engines finalize
            note = getattr(trace, "note_ids", None)
            if note is not None:
                for qi in range(q_n):
                    fin = np.nonzero(np.isfinite(rd[qi]))[0]
                    note("generated", qi,
                         col_ids[fin] if col_ids is not None else fin)
        else:
            nf = getattr(stream, "n_finite", None)
            gen = (np.asarray(nf, np.int64) if nf is not None
                   else np.full(q_n, n, np.int64))
            # a stream never re-hands an id, so its count is already a
            # dedup count — no host-side id materialization needed
            note = getattr(trace, "note_counts", None)
            if note is not None:
                note("generated", gen)
        trace.add("generated", gen)
        trace.add("device_loop", int(loop is not None))

    if loop is not None:
        t_loop = _time.perf_counter() if trace is not None else 0.0
        with maybe_span(trace, "loop"):         # ends in the fetch
            front_d, front_i, acc, n_rounds, per_round = loop(
                stream, front_d, front_i, batch_size,
                rounds=trace is not None)
        if trace is not None:                   # the loop's time, shared
            wall = (_time.perf_counter() - t_loop) / max(n_rounds, 1)
            for active, examined, kth in per_round:
                trace.record_round(phase="scan", active=active,
                                   examined=examined,
                                   kth=kth.astype(np.float64), wall_s=wall)

    while loop is None:
        # >= (not >): a candidate whose bound ties the k-th best verified
        # distance may tie it in true distance too and then win on the
        # smaller dataset index — it must be verified, not pruned.  The
        # finite guard keeps +inf-bound candidates (e.g. the masked rows
        # of a seeded index sweep) out of the scan entirely; a stream
        # peeks +inf past its finite frontier, so the guard doubles as
        # its exhaustion check.
        # each round's four steps are spans when traced: "peek" (next
        # bounds), "take" (candidate ids), "dist" (true distances) and
        # "merge" (the best-k frontier); on the device path each ends
        # in a fetch to the host, so none needs a fence
        with maybe_span(trace, "peek"):
            if stream is None:
                nxt = sorted_d[np.arange(q_n), np.minimum(pos, n - 1)]
                active = ((pos < n) & np.isfinite(nxt)
                          & (front_d[:, -1] >= nxt))
            else:
                nxt = stream.peek()
                active = np.isfinite(nxt) & (front_d[:, -1] >= nxt)
        if not active.any():
            break
        aq = np.nonzero(active)[0]
        t_round = _time.perf_counter() if trace is not None else 0.0
        with maybe_span(trace, "take"):
            if stream is None:
                cand = np.full((len(aq), batch_size), -1, np.int64)
                for r, qi in enumerate(aq):
                    c = order[qi,
                              pos[qi]:min(pos[qi] + batch_size, n_fin[qi])]
                    cand[r, :len(c)] = c
                if col_ids is not None:  # column -> dataset row ids
                    cand = np.where(cand >= 0, col_ids[cand], -1)
            else:                        # global ids straight off device
                cand = np.asarray(stream.take(aq, batch_size), np.int64)
        mask = cand >= 0
        with maybe_span(trace, "dist"):
            if dist_fn is not None:      # device-resident: no host fetch
                d = np.asarray(dist_fn(aq, cand))
            else:
                ids = np.unique(cand[mask])          # sorted
                rows = store.fetch(ids)              # one physical fetch
                gather = np.searchsorted(ids, np.where(mask, cand, ids[0]))
                d = verifier(rows, qs[aq], gather)
        d = np.where(mask, d, np.inf)
        if on_verified is not None:
            for r, qi in enumerate(aq):
                on_verified(int(qi), cand[r][mask[r]],
                            np.asarray(d[r][mask[r]], np.float64))

        with maybe_span(trace, "merge"):
            new_d, new_i = merge(
                np.concatenate([front_d[aq], d], axis=1),
                np.concatenate([front_i[aq], cand], axis=1), k)
        front_d[aq] = new_d
        front_i[aq] = new_i
        n_real = mask.sum(axis=1)
        acc[aq] += n_real
        if stream is None:               # a stream advances its own cursor
            pos[aq] += n_real
        if trace is not None:            # round telemetry: the k-th-best
            trace.record_round(          # threshold AFTER this merge
                phase="scan", active=int(len(aq)),
                examined=int(n_real.sum()), kth=front_d[aq, -1].copy(),
                wall_s=_time.perf_counter() - t_round)

    total = store.accesses - start_acc
    n_fetch = store.fetches - start_fetch
    io_s = store.modeled_io_seconds(total, n_fetch)
    if trace is not None:
        trace.add("examined", acc)
        trace.add("verified", acc)
        trace.add("rows_fetched", int(total))
        trace.add("seeks", int(n_fetch))
        trace.add("modeled_io_s", float(io_s))
    return TopKResult(indices=front_i, distances=front_d,
                      raw_accesses=acc,
                      pruned_fraction=1.0 - acc / n,
                      store_accesses=total, store_fetches=n_fetch,
                      io_seconds=io_s, device_loop=loop is not None)


def verify_candidates(queries_raw, cand_idx, store: RawStore, *,
                      k: Optional[int] = None,
                      verifier: Callable = numpy_verifier,
                      merge: Callable = merge_topk_numpy,
                      dist_fn: Optional[Callable] = None,
                      on_verified: Optional[Callable] = None,
                      trace=None, trace_phase: str = "seed") -> TopKResult:
    """Approximate top-k: verify an externally supplied candidate set
    (e.g. the sharded representation top-k) and rank by true d_ED.
    cand_idx: (Q, C) dataset rows; -1 entries are padding.  ``dist_fn``
    / ``on_verified``: same contracts as :func:`topk_verify` — with a
    ``dist_fn`` the store is never fetched (device-resident
    verification).  ``trace`` records this call as one verification
    round labelled ``trace_phase`` ("seed" for the tree seed walk,
    "approx" for the approximate path)."""
    import time as _time
    t0 = _time.perf_counter() if trace is not None else 0.0
    qs = np.asarray(queries_raw)
    if qs.ndim == 1:
        qs = qs[None]
    cand = np.asarray(cand_idx, np.int64)
    if cand.ndim == 1:
        cand = cand[None]
    q_n, c = cand.shape
    k = c if k is None else min(k, c)
    # candidate-id space size: windows for a WindowView (``n``), rows
    # for a raw/symbolic store
    n = getattr(store, "n", None)
    if n is None:
        n = store.data.shape[0]
    mask = cand >= 0
    ids = np.unique(cand[mask])
    if ids.size == 0:
        return TopKResult(indices=np.full((q_n, k), -1, np.int64),
                          distances=np.full((q_n, k), np.inf),
                          raw_accesses=np.zeros(q_n, np.int64),
                          pruned_fraction=np.ones(q_n),
                          store_accesses=0, store_fetches=0,
                          io_seconds=0.0)
    start_acc, start_fetch = store.accesses, store.fetches
    if dist_fn is not None:                      # device-resident path
        d = np.asarray(dist_fn(np.arange(q_n), cand))
    else:
        rows = store.fetch(ids)                  # one batched fetch
        gather = np.searchsorted(ids, np.where(mask, cand, ids[0]))
        d = verifier(rows, qs, gather)
    d = np.where(mask, d, np.inf)
    if on_verified is not None:
        for r in range(q_n):
            on_verified(r, cand[r][mask[r]],
                        np.asarray(d[r][mask[r]], np.float64))
    out_d, out_i = merge(d, cand, k)
    total = store.accesses - start_acc
    n_fetch = store.fetches - start_fetch
    acc = mask.sum(axis=1)
    io_s = store.modeled_io_seconds(total, n_fetch)
    if trace is not None:
        trace.add("generated", acc.astype(np.int64))
        note = getattr(trace, "note_ids", None)
        if note is not None:
            for r in range(q_n):
                note("generated", r, cand[r][mask[r]])
        trace.add("examined", acc.astype(np.int64))
        trace.add("verified", acc.astype(np.int64))
        trace.add("rows_fetched", int(total))
        trace.add("seeks", int(n_fetch))
        trace.add("modeled_io_s", float(io_s))
        trace.record_round(phase=trace_phase, active=q_n,
                           examined=int(acc.sum()),
                           kth=out_d[:, -1].copy(),
                           wall_s=_time.perf_counter() - t0)
    return TopKResult(indices=out_i, distances=out_d, raw_accesses=acc,
                      pruned_fraction=1.0 - acc / n,
                      store_accesses=total, store_fetches=n_fetch,
                      io_seconds=io_s)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

class DeviceRepCache:
    """Device-resident copy of a live representation — anything with the
    ``rep_view()`` + ``version`` protocol (``SymbolicStore``,
    ``subseq.WindowView``) — refreshed only when the version changes, so
    appends are served without paying a host->device transfer per query."""

    def __init__(self, store):
        self._store = store
        self._val = None
        self._version = -1

    def get(self):
        if self._version != self._store.version:
            import jax.numpy as jnp
            view = self._store.rep_view()
            leaves = view if isinstance(view, tuple) else (view,)
            dev = tuple(jnp.asarray(l) for l in leaves)
            self._val = dev if isinstance(view, tuple) else dev[0]
            self._version = self._store.version
        return self._val


class MatchEngine:
    """Batched multi-query top-k matcher over one encoder + store.

    Parameters
    ----------
    encoder:    SAX / SSAX / TSAX / STSAX / OneDSAX instance.
    store:      preferably a ``repro.store.SymbolicStore`` — it already
                owns the live representation, so construction is free and
                rows appended to it are served by the very next query
                (streaming ingestion).  A bare ``RawStore`` over the
                (N, T) raw dataset is still accepted; the engine then
                pays a one-shot encode at construction (the legacy
                static-corpus behaviour).
    batch_size: verification batch per query per round.
    verify:     "auto" (kernel on TPU, numpy host elsewhere), "kernel"
                (always route through euclid_pallas; interpret off-TPU),
                "numpy" (bit-identical to a host brute-force scan),
                "host" (alias of "kernel": the host-side fallback of the
                device-resident path — store fetch + modeled I/O, same
                kernel distance math as "device"), or "device"
                (device-resident sharded verification: raw rows never
                move to the host; requires ``dist_factory``, wired by
                ``core.distributed.make_engine_service``; bit-identical
                to "host").
    rep:        precomputed dataset representation (skips encode), e.g.
                the sharded output of ``distributed.encode_sharded``.
    repr_fn:    override for representation distances
                (queries_raw -> (Q, N)); used by the sharded service.
    cand_fn:    override for approximate candidates
                (queries_raw, k -> (Q, k) indices).
    stream_factory: override producing a device-ordered candidate
                stream for exact top-k (queries_raw ->
                ``distributed.DeviceOrderedStream``); when set, the
                linear sweep and the index source feed ``topk_verify``
                through the stream — the (Q, N) bound matrix never
                materializes on the host.  Wired by
                ``core.distributed.make_engine_service``.

    Candidate sources: exact ``topk`` consumes candidates from a
    ``repro.index.candidates.CandidateSource``.  The default is the
    linear lower-bound sweep; pass ``source="index"`` (or any source
    object) to generate candidates sublinearly from the backing store's
    split-tree index (``store.build_index()``) — bit-identical results,
    same k-th-best early-stop verification.
    """

    def __init__(self, encoder, store, *, batch_size: int = 64,
                 verify: str = "auto", pairwise: Callable | None = None,
                 rep=None, repr_fn: Callable | None = None,
                 cand_fn: Callable | None = None,
                 device_merge: bool = False,
                 dist_factory: Callable | None = None,
                 stream_factory: Callable | None = None,
                 metrics=None):
        self.encoder = encoder
        self.store = store
        self.batch_size = batch_size
        self.verify_mode = verify
        # opt-in repro.obs.MetricsRegistry: per-query counters and
        # latency histograms; None (the default) records nothing
        self.metrics = metrics
        self.device_verify = verify == "device"
        if self.device_verify and dist_factory is None:
            raise ValueError(
                'verify="device" needs a dist_factory (device-resident '
                "sharded verification; build the engine through "
                "core.distributed.make_engine_service)")
        self._dist_factory = dist_factory
        # the device path's host twin is the kernel verifier: same f32
        # distance definition, so "device" and "host" are bit-identical
        self.verifier = (kernel_verifier if self.device_verify
                         else make_verifier(verify))
        self.merge = (merge_topk_device
                      if device_merge or self.device_verify
                      else merge_topk_numpy)
        self._pw = pairwise or encoder.pairwise_distance
        self._repr_fn = repr_fn
        self._cand_fn = cand_fn
        self._stream_factory = stream_factory
        self._sym = store if hasattr(store, "rep_view") else None
        if self._sym is not None and self._sym.encoder != encoder:
            raise ValueError("SymbolicStore was built for a different "
                             "encoder configuration than this engine's")
        self._rep_cache = (DeviceRepCache(self._sym)
                           if self._sym is not None else None)
        if rep is not None or repr_fn is not None:
            self._rep = rep
        elif self._sym is not None:
            self._rep = None             # live view, refreshed on append
        else:
            import jax.numpy as jnp
            self._rep = encoder.encode(jnp.asarray(store.data))

    @property
    def rep(self):
        """Dataset representation: when backed by a ``SymbolicStore``, a
        device-resident copy of the store's live representation
        (``DeviceRepCache``); else the construction-time (or explicitly
        passed) representation."""
        if self._rep is not None:
            return self._rep
        if self._rep_cache is None:
            return None
        return self._rep_cache.get()

    def append(self, rows) -> np.ndarray:
        """Ingest rows into the backing ``SymbolicStore`` (incremental
        encode); they are matchable on the next ``topk`` call."""
        if self._sym is None:
            raise TypeError("append() needs a SymbolicStore-backed engine; "
                            "this one wraps a static RawStore")
        return self._sym.append(rows)

    # -- representation sweep -------------------------------------------
    def encode_queries(self, queries_raw):
        import jax.numpy as jnp
        return self.encoder.encode(jnp.asarray(queries_raw, jnp.float32))

    def repr_distances(self, queries_raw) -> np.ndarray:
        """(Q, N) lower-bounding representation distances."""
        if self._repr_fn is not None:
            return np.asarray(self._repr_fn(queries_raw))
        return np.asarray(self._pw(self.encode_queries(queries_raw),
                                   self.rep))

    def candidates(self, queries_raw, k: int) -> np.ndarray:
        """(Q, k) approximate candidates by representation distance."""
        if self._cand_fn is not None:
            return np.asarray(self._cand_fn(queries_raw, k))
        rd = self.repr_distances(queries_raw)
        k = min(k, rd.shape[1])
        if k == 0:
            return np.empty((rd.shape[0], 0), np.int64)
        part = np.argpartition(rd, k - 1, axis=1)[:, :k]
        part_d = np.take_along_axis(rd, part, axis=1)
        return np.take_along_axis(part, np.argsort(part_d, axis=1,
                                                   kind="stable"), axis=1)

    def index_source(self, epoch=None):
        """The backing store's split-tree index as a candidate source
        (``store.build_index()`` first).  With a ``stream_factory``
        present the tree's union bounds are device-ordered too
        (``device_order=True``).  ``epoch`` restricts generation to the
        items indexed before that frontier."""
        idx = getattr(self.store, "index", None)
        if idx is None:
            raise ValueError("store has no index; call "
                             "store.build_index() first")
        return idx.source(device_order=self._stream_factory is not None,
                          epoch=epoch)

    # -- matching --------------------------------------------------------
    def topk(self, queries_raw, k: int = 1, *, exact: bool = True,
             batch_size: Optional[int] = None, expand: int = 4,
             source=None, trace=None, explain: bool = False,
             epoch=None) -> TopKResult:
        """Top-k matches for a (Q, T) query batch (or a single (T,) query).

        exact=True:  pruned scan, provably identical to brute force.
                     ``source`` picks the candidate generator: None for
                     the linear lower-bound sweep, "index" for the
                     store's split-tree index, or any
                     ``CandidateSource`` — all bit-identical.
        exact=False: verify the top ``k * expand`` representation
                     candidates only (the paper's approximate matching,
                     generalized to k-NN); ``source`` is ignored.

        epoch: pin the answer to a published corpus frontier
        (``repro.store.CorpusEpoch`` or a plain row count).  Only rows
        with id < ``epoch.n_rows`` are generated, verified or returned
        — exact results are bit-identical to a frozen copy of the store
        truncated to that epoch, regardless of concurrent ``append`` /
        ``ingest`` (the store is append-only, so the epoch prefix is
        immutable).  None (the default) serves the live frontier.
        Sources passed as OBJECTS must already carry their own epoch
        (``SeriesIndex.source(epoch=...)``); the string/None forms are
        epoch-wired here.

        trace / explain: ``trace`` records a per-query ``repro.obs``
        query trace into the given object; ``explain=True`` creates one
        and attaches it to the result as ``res.trace`` (render with
        ``repro.obs.render_trace``).  Tracing never changes results or
        store accounting (observability neutrality, property-tested).
        """
        import time as _time
        from repro.store.symbolic import epoch_rows
        qs = np.asarray(queries_raw)
        if qs.ndim == 1:
            qs = qs[None]
        if explain and trace is None:
            from repro.obs import Trace
            trace = Trace("match.topk")
        total = getattr(self.store, "n", None)
        if total is None:
            total = self.store.data.shape[0]
        n_e = epoch_rows(epoch)
        if n_e is not None:
            total = min(total, n_e)
        observing = trace is not None or self.metrics is not None
        t0 = _time.perf_counter() if observing else 0.0
        sweep = getattr(self, "sweep", None)
        if trace is not None:
            approx_src = bool(getattr(source, "is_approx", False))
            src_name = ("index" if source == "index" else
                        "linear" if source is None else
                        "index-approx" if approx_src else
                        type(source).__name__)
            trace.meta.update(engine="match", k=int(k),
                              exact=bool(exact) and not approx_src,
                              q_n=int(qs.shape[0]), total=int(total),
                              source=src_name, verify=self.verify_mode)
            if n_e is not None:
                trace.meta["epoch_rows"] = int(n_e)
        hob0 = sweep.host_order_bytes if sweep is not None else 0
        h2d0 = sweep.h2d_bytes if sweep is not None else 0
        dfn = self._make_dist_fn(qs)
        if exact:
            from repro.index.candidates import LinearSweep, topk_from_source
            if source is None:
                if n_e is None:
                    source = LinearSweep(self.repr_distances,
                                         stream_fn=self._stream_factory)
                else:
                    # epoch-clamped linear sweep: the stream masks rows
                    # past the frontier to +inf ON DEVICE (they never
                    # reach verification); the host matrix path trims
                    # columns to the epoch prefix — both are exactly
                    # the sweep a store truncated at the epoch would run
                    stream_fn = None
                    if self._stream_factory is not None:
                        def stream_fn(q, _n=n_e):
                            return self._stream_factory(
                                q, mask_fn=lambda ids: ids >= _n)
                    source = LinearSweep(
                        lambda q, _n=n_e: self.repr_distances(q)[:, :_n],
                        stream_fn=stream_fn)
            elif source == "index":
                source = self.index_source(epoch=n_e)
            res = topk_from_source(
                qs, source, self.store, k=k,
                batch_size=batch_size or self.batch_size,
                verifier=self.verifier, merge=self.merge, total=total,
                dist_fn=dfn, trace=trace)
        else:
            from repro.obs.trace import maybe_span
            with maybe_span(trace, "order"):
                cand = self.candidates(qs, k * max(expand, 1))
                if n_e is not None:
                    # epoch filter on the approximate frontier: rows
                    # past the pinned frontier are dropped (-1 padding,
                    # ignored by verification), never returned
                    cand = np.where(cand < n_e, cand, -1)
            with maybe_span(trace, "verify"):
                res = verify_candidates(
                    qs, cand, self.store, k=k, verifier=self.verifier,
                    merge=self.merge, dist_fn=dfn, trace=trace,
                    trace_phase="approx")
        if observing:
            self._observe(trace, res, sweep, total, qs.shape[0],
                          _time.perf_counter() - t0, hob0, h2d0)
        if trace is not None:
            res.trace = trace
        return res

    def topk_approx(self, queries_raw, k: int = 1, *,
                    collect: Optional[int] = None, trace=None,
                    explain: bool = False, epoch=None) -> TopKResult:
        """Anytime/approximate top-k with a per-query error bar.

        When the backing store carries a split-tree index, routes
        through ``TreeCandidates`` approximate mode: the exact seed walk
        runs in full, then the collect phase keeps only the ``collect``
        best-bound survivors (default ``max(4 * k, 32)``).  The result
        carries ``res.kth_lb`` (the k-th smallest of verified true
        distances and the DROPPED candidates' lower bounds — a certified
        lower bound on the true k-th-NN distance) and ``res.error_bar``
        (``d_k - kth_lb``, >= 0; zero proves the answer exact).  Without
        an index, falls back to the representation-top-k approximate
        path (``exact=False``), which has no dropped-bound certificate —
        ``kth_lb`` / ``error_bar`` are then absent."""
        idx = getattr(self.store, "index", None)
        if idx is None:
            return self.topk(queries_raw, k=k, exact=False, trace=trace,
                             explain=explain, epoch=epoch)
        src = idx.source(device_order=self._stream_factory is not None,
                         approx_collect=(collect if collect is not None
                                         else max(4 * k, 32)),
                         epoch=epoch)
        return self.topk(queries_raw, k=k, source=src, trace=trace,
                         explain=explain, epoch=epoch)

    def _observe(self, trace, res: TopKResult, sweep, total: int,
                 q_n: int, wall_s: float, hob0: int, h2d0: int) -> None:
        """Post-call recording: transfer deltas, pruning power, registry
        metrics.  Runs only when a trace or a registry is attached and
        only AFTER the result exists — it cannot perturb matching."""
        hob = (sweep.host_order_bytes - hob0) if sweep is not None else None
        h2d = (sweep.h2d_bytes - h2d0) if sweep is not None else None
        # the device path never fetches the store; any store accesses
        # during a device-verified call ARE rows moved to the host
        rth = int(res.store_accesses) if self.device_verify else None
        if trace is not None:
            trace.set("wall_s", wall_s)
            trace.set("pruning_power", res.pruned_fraction.copy())
            gu = trace.unique_counts("generated", q_n) \
                if hasattr(trace, "unique_counts") else None
            if gu is not None:
                trace.set("generated_unique", gu)
            if sweep is not None:
                trace.set("host_order_bytes", int(hob))
                trace.set("h2d_bytes", int(h2d))
            if rth is not None:
                trace.set("rows_to_host", rth)
        if self.metrics is not None:
            m = self.metrics
            m.counter("match.queries").inc(q_n)
            m.counter("match.candidates_verified").inc(
                int(res.raw_accesses.sum()))
            m.counter("match.rows_fetched").inc(int(res.store_accesses))
            m.counter("match.seeks").inc(int(res.store_fetches))
            m.counter("match.modeled_io_s").inc(float(res.io_seconds))
            m.gauge("match.pruning_power").set(
                float(res.pruned_fraction.mean()))
            m.histogram("match.topk_latency_s").observe(wall_s)
            if hob is not None:
                m.counter("match.host_order_bytes").inc(int(hob))
                m.counter("match.h2d_bytes").inc(int(h2d))
            if rth is not None:
                m.counter("match.rows_to_host").inc(rth)

    def _make_dist_fn(self, qs) -> Optional[Callable]:
        """Device-resident verification closure for this query batch
        (None outside verify="device")."""
        if not self.device_verify:
            return None
        return self._dist_factory(qs)

    def verify_candidates(self, queries_raw, cand_idx,
                          k: Optional[int] = None) -> TopKResult:
        """Rank an external candidate frontier by true d_ED (one batched
        raw fetch; device-resident under verify="device")."""
        qs = np.asarray(queries_raw)
        if qs.ndim == 1:
            qs = qs[None]
        return verify_candidates(qs, cand_idx, self.store, k=k,
                                 verifier=self.verifier, merge=self.merge,
                                 dist_fn=self._make_dist_fn(qs))

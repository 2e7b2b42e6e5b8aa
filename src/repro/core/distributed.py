"""Distributed matching engine: the paper's pipeline mapped onto a JAX mesh.

The dataset of N series is sharded over the ("pod","data") axes; queries
are replicated.  One ``shard_map`` pass per stage:

  1. ``encode_sharded`` — representation construction (one pass/series,
     exactly the paper's "Representation Time = 1 pass" property, batched).
  2. ``repr_topk_sharded`` — symbolic distances on the local shard
     (Pallas ``sax_dist`` kernel where available, jnp otherwise), local
     top-k, then a global candidate merge via ``all_gather`` of k
     candidates per shard (collective volume independent of N — the
     property that scales to 1000+ nodes, DESIGN.md §3).
  3. Raw verification of the surviving candidates against the cold store
     via the batched k-NN engine (``core.engine.MatchEngine``):
     ``repr_topk_sharded`` produces the candidate frontier for
     approximate top-k, the sharded bound sweep the exact frontier —
     ``make_engine_service`` wires both into an engine whose raw
     verification is one batched fetch per round (host path) or never
     leaves the devices (``verify="device"``).

Shard layout contract (device mirrors)
--------------------------------------
Every device mirror (``RoundRobinMirror``) is laid out ROUND-ROBIN:
global row ``i`` lives on shard ``i % n_shards`` at local slot
``i // n_shards``, in a ``(n_shards * capacity, *rest)`` buffer whose
row axis is sharded over the data axes — shard ``s`` holds the
contiguous block ``[s * capacity, (s + 1) * capacity)``, so each
shard's local block is ``(capacity, *rest)`` in its natural layout
(a size-1 leading shard axis made the TPU compiler relayout the whole
local block on every call).  Every row of the corpus is on the device,
whatever the row count: an append lands in the slots from the first
one not yet full on EVERY shard (re-uploading the < n_shards rows that
slot already held, with the same values) — host->device traffic is
O(chunk) and the resident corpus is never re-laid-out, unlike a
contiguous-range layout where each append shifts every shard boundary
(O(corpus) collective re-layout).  Capacity doubles device-side
(``jnp.pad``, no host traffic), so amortized append cost stays O(chunk).
Every program masks a slot as dead by its global id
(``slot * n_shards + shard >= live``).

The ON-DISK layout is deliberately NOT the mirror layout: snapshots
(``store.snapshot``) keep contiguous per-host row ranges
(``_shard_ranges``) as their manifest unit — ``ShardedRepSweep.
shard_ranges()`` still reports those manifest ranges, while
``owned_rows()`` / ``mirror_layout`` describe the device placement.
Matching results are layout-independent (bit-identical either way)
because every per-(query, row) quantity is computed element-wise.

Device-resident candidate ORDER: the bound matrix never materializes on
the host for the exact path.  ``candidate_stream`` sorts the blocked
round-robin bound matrix by ``(bound, id)`` once, on
device, and hands ``core.engine.topk_verify`` a
:class:`DeviceOrderedStream` — ``peek``/``take`` move only O(Q) /
O(Q·batch) scalars and ids per round, never the (Q, N) matrix
(``host_order_bytes`` stays 0; the legacy ``repr_distances`` matrix
path counts every byte it assembles there).

Device-resident verification (``verify="device"``): a verification
round hands the candidate id batch to every shard; each shard distances
its OWN candidates (ownership is ``id % n_shards``) through the
multi-query Pallas euclid kernel (``kernels.euclid``) and a device-side
min-merge combines shards.  The distance definition is the kernel's f32
reduction — identical math to the host ``verify="host"`` fallback
(store fetch + the same kernel), so the two paths are bit-identical;
the host ``verify="numpy"`` path stays the brute-force oracle with
modeled I/O.  The device path moves zero raw rows device->host, and a
whole stream's rounds run as one device program
(:func:`verify_stream_rr`): peek, take, distances, square root and
merge in a ``lax.while_loop``, one launch and one fetch per call.

The helpers take any encoder with ``encode`` + ``pairwise_distance`` —
SAX, sSAX, tSAX and 1d-SAX all plug in.
"""

from __future__ import annotations

import math
import threading
from functools import lru_cache
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _data_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _n_shards(mesh: Mesh) -> int:
    """Number of shards over the data axes."""
    return math.prod(mesh.shape[a] for a in _data_axes(mesh))


# The shard_map'd sweep callables are built once per (mesh, encoder /
# pairwise, pytree structure) and jitted: rebuilding the closure per
# call used to defeat jax's trace cache entirely, paying a full XLA
# recompile on EVERY sweep (tens of seconds for the richer encoders).
# The cached callables compile once per input shape and are shared by
# every engine over the same mesh.  The compiled body is unchanged, so
# results are unchanged.  Each body is named for its role, which names
# its compiled module (``jit_rr_bounds``, ``jit_rr_rows_verify``, ...)
# on the profiler's timeline.

@lru_cache(maxsize=64)
def _encode_fn(mesh: Mesh, encoder, out_def, out_ndims):
    axes = _data_axes(mesh)
    # representation leaves keep their leading N axis sharded; trailing
    # axes replicated
    spec_out = jax.tree.unflatten(
        out_def, [P(axes, *([None] * (nd - 1))) for nd in out_ndims])
    def encode_rows(x):
        return encoder.encode(x)

    return jax.jit(jax.shard_map(
        encode_rows, mesh=mesh, in_specs=(P(axes, None),),
        out_specs=spec_out, check_vma=False))


def encode_sharded(encoder, dataset, mesh: Mesh):
    """Encode a dataset sharded over the data axes.  dataset: (N, T)."""
    rep_struct = jax.eval_shape(encoder.encode,
                                jax.ShapeDtypeStruct(dataset.shape,
                                                     dataset.dtype))
    leaves, out_def = jax.tree.flatten(rep_struct)
    fn = _encode_fn(mesh, encoder, out_def,
                    tuple(len(l.shape) for l in leaves))
    return fn(dataset)


def rowwise_sharded(obj, method: str, rows, mesh: Mesh):
    """Run ``getattr(obj, method)`` — any pure row-wise device map with a
    (N, T) input — over ``rows`` sharded on the mesh data axes (pad to a
    shard multiple, trim) and return the same pytree of host arrays.

    The map runs EAGERLY on the sharded array (the row sharding
    propagates through every row-parallel op), deliberately NOT under
    ``jit(shard_map(...))``: eager dispatch executes the exact op-by-op
    kernels the host path runs, so the float output is bitwise identical
    to the unsharded call.  A jitted variant fuses differently and
    drifts by ulps — harmless for the QUANTIZED symbols
    :func:`encode_sharded` produces, fatal for the float features the
    split tree stores and compares (``index.features``)."""
    rows = np.asarray(rows, np.float32)
    if rows.ndim == 1:
        rows = rows[None]
    m = rows.shape[0]
    fn = getattr(obj, method)
    if m == 0:
        return jax.tree.map(np.asarray, fn(jnp.asarray(rows)))
    pad = (-m) % _n_shards(mesh)
    if pad:
        rows = np.concatenate([rows, rows[-1:].repeat(pad, axis=0)])
    sharded = jax.device_put(rows,
                             NamedSharding(mesh, P(_data_axes(mesh), None)))
    return jax.tree.map(lambda l: np.asarray(l)[:m], fn(sharded))


def _rep_specs(rep_query, rep_data):
    """Hashable (treedefs, ndims) cache key for a (query, data) rep
    pair — enough to rebuild the P-specs (query replicated, data
    sharded on its leading axis)."""
    ql, q_def = jax.tree.flatten(rep_query)
    xl, x_def = jax.tree.flatten(rep_data)
    return (q_def, x_def, tuple(l.ndim for l in ql),
            tuple(l.ndim for l in xl))


@lru_cache(maxsize=64)
def _repr_dists_fn(mesh: Mesh, pw, q_def, x_def, q_ndims, x_ndims):
    axes = _data_axes(mesh)
    in_q = jax.tree.unflatten(q_def, [P(*([None] * nd)) for nd in q_ndims])
    in_x = jax.tree.unflatten(
        x_def, [P(axes, *([None] * (nd - 1))) for nd in x_ndims])
    def repr_dists(rq, rx):
        return pw(rq, rx)

    return jax.jit(jax.shard_map(
        repr_dists, mesh=mesh, in_specs=(in_q, in_x),
        out_specs=P(None, axes), check_vma=False))


def repr_distances_sharded(encoder, rep_query, rep_data, mesh: Mesh,
                           pairwise: Callable | None = None):
    """(Q, N) representation distances, N sharded.  Output replicated-Q,
    N-sharded."""
    pw = pairwise or encoder.pairwise_distance
    fn = _repr_dists_fn(mesh, pw, *_rep_specs(rep_query, rep_data))
    return fn(rep_query, rep_data)


@lru_cache(maxsize=64)
def _repr_topk_fn(mesh: Mesh, pw, k: int, q_def, x_def, q_ndims, x_ndims):
    axes = _data_axes(mesh)

    def repr_topk(rq, rx):
        d = pw(rq, rx)                                 # (Q, n_local)
        n_local = d.shape[1]
        kk = min(k, n_local)
        neg, idx = jax.lax.top_k(-d, kk)               # smallest distances
        gidx = idx + _shard_index(axes) * n_local      # global offset
        cand_d = jax.lax.all_gather(-neg, axes, axis=1, tiled=True)
        cand_i = jax.lax.all_gather(gidx, axes, axis=1, tiled=True)
        best_neg, best_pos = jax.lax.top_k(-cand_d, min(k, cand_d.shape[1]))
        best_i = jnp.take_along_axis(cand_i, best_pos, axis=1)
        return -best_neg, best_i

    in_q = jax.tree.unflatten(q_def, [P(*([None] * nd)) for nd in q_ndims])
    in_x = jax.tree.unflatten(
        x_def, [P(axes, *([None] * (nd - 1))) for nd in x_ndims])
    return jax.jit(jax.shard_map(
        repr_topk, mesh=mesh, in_specs=(in_q, in_x),
        out_specs=(P(None, None), P(None, None)), check_vma=False))


def repr_topk_sharded(encoder, rep_query, rep_data, mesh: Mesh, *,
                      k: int = 64, pairwise: Callable | None = None):
    """Global top-k candidate (distance, index) per query.

    Local shard computes distances + local top-k; k*shards candidates are
    all-gathered and reduced — collective volume O(Q*k*shards), never O(N).
    Returns (dists (Q, k), global indices (Q, k)).  Data is contiguously
    sharded on its leading axis (the :func:`encode_sharded` layout).
    """
    pw = pairwise or encoder.pairwise_distance
    fn = _repr_topk_fn(mesh, pw, int(k),
                       *_rep_specs(rep_query, rep_data))
    return fn(rep_query, rep_data)


# ---------------------------------------------------------------------------
# Round-robin device mirror
# ---------------------------------------------------------------------------

def _shard_index(axes):
    """Linear shard id of the executing program over the data axes."""
    sid = jax.lax.axis_index(axes[0])
    if len(axes) == 2:
        sid = sid * jax.lax.axis_size(axes[1]) + jax.lax.axis_index(axes[1])
    return sid


def _slot_ids(cap: int, n_shards: int, axes):
    """(cap,) global row ids of the executing shard's local slots."""
    return jnp.arange(cap) * n_shards + _shard_index(axes)


#: Rows per sharded encode call during ingest (256 MB of f32 at T=960)
_ENCODE_ROWS = 1 << 16

#: TPU vector lane width: raw-row mirrors pad their row width to a
#: multiple of it (see ``RoundRobinMirror``)
_LANES = 128


def _row_spec(mesh: Mesh, ndim: int):
    """Row-sharded spec of a ``(n_shards * cap, *rest)`` mirror buffer."""
    return P(_data_axes(mesh), *([None] * (ndim - 1)))


@lru_cache(maxsize=64)
def _rr_place_fn(mesh: Mesh, ndim: int):
    """Jitted in-place slot write: every shard writes its ``d`` delta
    rows at local slot ``start``, donating the old buffer — the
    per-append device work is O(chunk) window writes, never a
    corpus-wide concatenate."""
    spec = _row_spec(mesh, ndim)

    def rr_place(buf, delta, start):
        zeros = (0,) * (buf.ndim - 1)
        return jax.lax.dynamic_update_slice(buf, delta, (start,) + zeros)

    return jax.jit(jax.shard_map(
        rr_place, mesh=mesh, in_specs=(spec, spec, P()), out_specs=spec,
        check_vma=False), donate_argnums=0)


@lru_cache(maxsize=64)
def _rr_grow_fn(mesh: Mesh, ndim: int, new_cap: int):
    """Jitted capacity growth: every shard zero-pads its local block to
    ``new_cap`` slots on device."""
    spec = _row_spec(mesh, ndim)

    def rr_grow(buf):
        pad = [(0, new_cap - buf.shape[0])] + [(0, 0)] * (buf.ndim - 1)
        return jnp.pad(buf, pad)

    return jax.jit(jax.shard_map(
        rr_grow, mesh=mesh, in_specs=(spec,), out_specs=spec,
        check_vma=False), donate_argnums=0)


class RoundRobinMirror:
    """Append-local device mirror of host rows, sharded round-robin.

    Global row ``i`` lives on shard ``i % n_shards`` at local slot
    ``i // n_shards``; the device buffer is ``(n_shards * capacity,
    *rest)`` with its row axis sharded over the mesh data axes, so shard
    ``s`` holds rows ``[s * capacity, (s + 1) * capacity)`` of it.  The
    mirror holds every one of the ``live`` rows, whatever their count.
    ``sync`` uploads only the rows from the first slot not yet full —
    the new rows plus the < n_shards already in that slot, with the same
    values (O(chunk) host->device, counted in ``h2d_bytes``), each
    shard's share straight to its own device — so the resident corpus
    is never re-uploaded or re-laid-out, unlike a contiguous-range
    layout where every append shifts every shard boundary.  Capacity
    doubles device-side when exhausted (``jnp.pad``, no host traffic),
    so amortized append cost stays O(chunk).  A slot whose global id
    ``slot * n_shards + shard`` is ``>= live`` is dead (zeros); every
    consumer masks it via the ``live`` scalar.

    ``lanes``: when set, 2-D rows are zero-padded on the trailing axis
    to a multiple of ``lanes`` at upload.  The raw-row mirrors use the
    TPU lane width (128): for a row width that is not a multiple of it
    (T = 960) the chip's default layout of the buffer is column-major,
    and every row gather then relayouts the whole mirror first.  With
    the padded width the default layout is row-major and a gather
    reads only the gathered rows.  Consumers slice the padding off."""

    def __init__(self, mesh: Mesh, n_shards: int, *, lanes: int = 0):
        self.mesh = mesh
        self.n_shards = int(n_shards)
        self.lanes = int(lanes)
        self.buf = None                  # (S * cap, *rest) device array
        self.live = 0                    # rows held (global count)
        self.h2d_bytes = 0               # host->device upload accounting

    @property
    def cap(self) -> int:
        return 0 if self.buf is None else self.buf.shape[0] // self.n_shards

    def sync(self, rows, dtype=None) -> None:
        """Mirror ``rows``: the whole corpus so far, in global row order,
        whose first ``live`` rows are those already mirrored (a
        prefix-stable host array; only its unmirrored slots are read,
        and cast to ``dtype`` if given)."""
        S = self.n_shards
        n = rows.shape[0]
        if n <= self.live:
            return
        j0 = self.live // S              # first slot not yet full
        d = -(-n // S) - j0              # slots rewritten on every shard
        src = np.asarray(rows[j0 * S:n], dtype)
        lane = (-src.shape[-1]) % self.lanes if self.lanes and \
            src.ndim == 2 else 0
        rest = (src.shape[1] + lane,) if lane else src.shape[1:]

        # row j0*S + j*S + s -> shard s, slot j0 + j: shard s's block is
        # the strided slice src[s::S], zero-filled past the last row
        def block(idx):
            blk = src[(idx[0].start or 0) // d::S]
            if blk.shape[0] == d and not lane:
                return np.ascontiguousarray(blk)
            pad = [(0, d - blk.shape[0])] + [(0, 0)] * (src.ndim - 1)
            if lane:
                pad[-1] = (0, lane)
            return np.pad(blk, pad)

        sh = NamedSharding(self.mesh, _row_spec(self.mesh, src.ndim))
        dev = jax.make_array_from_callback((S * d,) + rest, sh, block)
        self.h2d_bytes += src.nbytes
        if self.buf is None:
            self.buf = dev
        else:
            if j0 + d > self.cap:
                self.buf = _rr_grow_fn(self.mesh, self.buf.ndim,
                                       max(2 * self.cap, j0 + d))(self.buf)
            self.buf = _rr_place_fn(self.mesh, self.buf.ndim)(
                self.buf, dev, jnp.int32(j0))
        self.live = n


# ---------------------------------------------------------------------------
# Round-robin sweeps (bounds, top-k, verification)
# ---------------------------------------------------------------------------

@lru_cache(maxsize=64)
def _rr_bounds_fn(mesh: Mesh, pw, q_def, x_def, q_ndims, x_ndims):
    """(Q, S*cap) blocked bound matrix over round-robin mirrors: the
    block column ``s*cap + j`` holds global row ``j*S + s``; dead slots
    (global id ``>= live``) are +inf.  Output stays column-sharded on
    device — the host unpermute (``ShardedRepSweep.repr_distances``) is
    the legacy matrix path only."""
    axes = _data_axes(mesh)
    n_shards = _n_shards(mesh)
    in_q = jax.tree.unflatten(q_def, [P(*([None] * nd)) for nd in q_ndims])
    in_x = jax.tree.unflatten(
        x_def, [P(axes, *([None] * (nd - 1))) for nd in x_ndims])

    def rr_bounds(rq, rx, live):
        d = pw(rq, rx)                                 # (Q, cap)
        dead = _slot_ids(d.shape[1], n_shards, axes)[None, :] >= live
        return jnp.where(dead, jnp.inf, d)

    return jax.jit(jax.shard_map(
        rr_bounds, mesh=mesh, in_specs=(in_q, in_x, P()),
        out_specs=P(None, axes), check_vma=False))


@lru_cache(maxsize=64)
def _rr_topk_fn(mesh: Mesh, pw, k: int, n_shards: int,
                q_def, x_def, q_ndims, x_ndims):
    """Global top-k (distance, GLOBAL id) over round-robin mirrors.
    Local top-k ids ``slot*S + shard`` are all-gathered and merged with
    the same (distance, smallest-id) lexicographic tie-break the host
    ``merge_topk_numpy`` applies — a plain ``top_k`` over the gathered
    pool would break that on ties because round-robin global ids are
    not monotone in gather position."""
    axes = _data_axes(mesh)
    in_q = jax.tree.unflatten(q_def, [P(*([None] * nd)) for nd in q_ndims])
    in_x = jax.tree.unflatten(
        x_def, [P(axes, *([None] * (nd - 1))) for nd in x_ndims])

    def rr_topk(rq, rx, live):
        d = pw(rq, rx)                                 # (Q, cap)
        cap = d.shape[1]
        d = jnp.where(_slot_ids(cap, n_shards, axes)[None, :] >= live,
                      jnp.inf, d)
        kk = min(k, cap)
        neg, idx = jax.lax.top_k(-d, kk)
        cd = -neg
        gidx = idx * n_shards + _shard_index(axes)
        gidx = jnp.where(jnp.isfinite(cd), gidx, -1)
        cand_d = jax.lax.all_gather(cd, axes, axis=1, tiled=True)
        cand_i = jax.lax.all_gather(gidx, axes, axis=1, tiled=True)
        tie = jnp.where(cand_i < 0, jnp.iinfo(jnp.int32).max, cand_i)
        best = jnp.lexsort((tie, cand_d), axis=-1)[:, :min(k,
                                                           cand_d.shape[1])]
        return (jnp.take_along_axis(cand_d, best, axis=1),
                jnp.take_along_axis(cand_i, best, axis=1))

    return jax.jit(jax.shard_map(
        rr_topk, mesh=mesh, in_specs=(in_q, in_x, P()),
        out_specs=(P(None, None), P(None, None)), check_vma=False))


def _kernel_cand_d2(rows, qs):
    """rows (Qa, B, T) x qs (Qa, T) -> (Qa, B) squared distances through
    the multi-query Pallas euclid kernel — one launch per query row, all
    with the same (B, T) shape so repeated rounds hit the jit cache.
    Per (query, candidate) the reduction order over T is the kernel's,
    independent of batch shape — the shared distance definition that
    makes the device and host-kernel paths bit-identical."""
    from repro.kernels import ops
    return jnp.stack([ops.euclid_batch(rows[r], qs[r])
                      for r in range(rows.shape[0])])


@lru_cache(maxsize=64)
def _rr_rows_verify_fn(mesh: Mesh, n_shards: int):
    """Jitted sharded row-verification over a round-robin raw mirror
    (ownership: ``id % n_shards``), cached per mesh (the jit cache then
    folds repeated (Qa, B, T) round shapes)."""
    axes = _data_axes(mesh)

    def rr_rows_verify(x, q, c, live):
        cap = x.shape[0]                              # x: (cap, T_pad) local
        valid = ((c >= 0) & (c % n_shards == _shard_index(axes))
                 & (c < live))
        rows = x[jnp.clip(c // n_shards, 0, cap - 1), :q.shape[-1]]
        d2 = _kernel_cand_d2(rows, q)
        # each candidate is owned by exactly one shard: min-merge
        return jax.lax.pmin(jnp.where(valid, d2, jnp.inf), axes)

    return jax.jit(jax.shard_map(
        rr_rows_verify, mesh=mesh,
        in_specs=(P(axes, None), P(None, None), P(None, None), P()),
        out_specs=P(None, None), check_vma=False))


def _sqrt_rn(x, guess=None):
    """f32 square root rounded to nearest, as IEEE 754 (and numpy on the
    host) defines it, whatever the backend's own ``sqrt`` rounds to (a
    v5e's is up to 3 ulps off).

    ``guess`` (default ``jnp.sqrt(x)``) need only lie within ``_ULPS``
    ulps of the answer: the answer is the largest float around it whose
    lower rounding midpoint ``m`` has ``m * m < x`` (a square root never
    falls on a midpoint), so it is the lowest candidate plus the count of
    candidates above it that pass.  The test runs on the integer
    significands, ``X * 2**sh > N * N`` in two 32-bit words, so it is
    exact on any backend.  Zero, subnormals (XLA flushes them, so the
    kernel's squared distances hold none), +inf and NaN pass through
    ``jnp.sqrt``."""
    u32 = jnp.uint32
    s = jnp.sqrt(x) if guess is None else guess
    xb = jax.lax.bitcast_convert_type(x, u32)
    ex = ((xb >> 23) & 0xFF).astype(jnp.int32)
    big_x = (xb & 0x7FFFFF) | 0x800000

    def below(cb):
        """x > (the midpoint below c) ** 2, for a positive normal c with
        bits ``cb``."""
        cb = cb.astype(u32)
        ec = ((cb >> 23) & 0xFF).astype(jnp.int32)
        mc = (cb & 0x7FFFFF) | 0x800000
        bottom = (cb & 0x7FFFFF) == 0      # c_prev is half an ulp away
        n = jnp.where(bottom, 4 * mc - 1, 2 * mc - 1)        # < 2**25
        sh = (ex - 150 - 2 * jnp.where(bottom, ec - 152, ec - 151)
              ).astype(u32)                                   # 23..30
        x_lo, x_hi = big_x << sh, big_x >> (32 - sh)
        a, b = n >> 16, n & 0xFFFF
        m = 2 * a * b
        lo = b * b + ((m & 0xFFFF) << 16)
        hi = a * a + (m >> 16) + (lo < b * b).astype(u32)
        return ((x_hi > hi) | ((x_hi == hi) & (x_lo > lo))).astype(
            jnp.int32)

    sb = jax.lax.bitcast_convert_type(s, jnp.int32) - _ULPS
    r = sb + sum(below(sb + j) for j in range(1, 2 * _ULPS + 1))
    normal = (x >= np.finfo(np.float32).tiny) & jnp.isfinite(x)
    return jnp.where(normal, jax.lax.bitcast_convert_type(r, jnp.float32),
                     jnp.sqrt(x))


#: how far (in ulps) :func:`_sqrt_rn`'s guess may lie from the answer
_ULPS = 8


def cand_dists_rows_rr(raw_buf, q_dev, cand, mesh: Mesh, n_shards: int,
                       live: int) -> np.ndarray:
    """True d_ED of candidate ROW ids against a round-robin raw mirror.

    raw_buf: the mirror's (S * cap, T) device buffer, holding rows
    ``[0, live)``.  q_dev: (Qa, T) replicated queries.  cand: (Qa, B)
    int ids, -1 padding, which return +inf.  Raw rows never leave the
    devices; only the (Qa, B) squared distances do, and the square root
    is taken on the host exactly as ``core.engine.kernel_verifier``
    takes it, so the device and host paths cannot differ by a backend's
    sqrt rounding."""
    d2 = np.asarray(_rr_rows_verify_fn(mesh, int(n_shards))(
        raw_buf, q_dev, jnp.asarray(cand), jnp.int32(live)))
    return np.sqrt(np.maximum(d2, 0.0))


@lru_cache(maxsize=64)
def _rr_verify_loop_fn(mesh: Mesh, n_shards: int, k: int, batch: int,
                       n_rounds: int):
    """Jitted sharded verification of a whole device-ordered stream: the
    round loop of ``core.engine.topk_verify`` as one ``lax.while_loop``,
    each round its peek, take, row verification (as
    :func:`_rr_rows_verify_fn`), square root (:func:`_sqrt_rn`, numpy's
    rounding) and (distance, id) lexsort merge, with the host loop's
    comparisons in the same order.  The carry is the cursors, the (Q, k)
    frontier, the round counter and three per-round records
    (``n_rounds`` long): active queries, rows examined and each query's
    k-th best after the merge (NaN where inactive).  The mirror is read
    in place; temporaries are about one (Q, batch) row gather.  Named
    ``rr_rows_verify``: in the benchmark's terms it is the row-verify
    program, whatever else its rounds do."""
    axes = _data_axes(mesh)
    big = jnp.iinfo(jnp.int32).max

    def rr_rows_verify(x, q, sb, si, n_fin, pos, front_d, front_i, live):
        cap = x.shape[0]                              # x: (cap, T_pad) local
        last = sb.shape[1] - 1
        sid = _shard_index(axes)
        cols = jnp.arange(batch, dtype=jnp.int32)[None, :]

        def peek(pos, fd):
            nxt = jnp.take_along_axis(sb, jnp.minimum(pos, last)[:, None],
                                      axis=1)[:, 0]
            nxt = jnp.where(pos < n_fin, nxt, jnp.inf)
            # >= (not >): see topk_verify
            return jnp.isfinite(nxt) & (fd[:, -1] >= nxt)

        def body(c):
            r, pos, fd, fi, active, n_act, n_exa, kth = c
            at = pos[:, None] + cols
            real = active[:, None] & (at < n_fin[:, None])
            cand = jnp.where(real, jnp.take_along_axis(
                si, jnp.minimum(at, last), axis=1), -1)
            valid = real & (cand % n_shards == sid) & (cand < live)
            rows = x[jnp.clip(cand // n_shards, 0, cap - 1), :q.shape[-1]]
            d2 = jax.lax.pmin(jnp.where(valid, _kernel_cand_d2(rows, q),
                                        jnp.inf), axes)
            d = jnp.where(real, _sqrt_rn(jnp.maximum(d2, 0.0)), jnp.inf)
            all_d = jnp.concatenate([fd, d], axis=1)
            all_i = jnp.concatenate([fi, cand], axis=1)
            sel = jnp.lexsort((jnp.where(all_i < 0, big, all_i), all_d),
                              axis=-1)[:, :k]
            keep = active[:, None]
            fd = jnp.where(keep, jnp.take_along_axis(all_d, sel, axis=1), fd)
            fi = jnp.where(keep, jnp.take_along_axis(all_i, sel, axis=1), fi)
            n_real = real.sum(axis=1, dtype=jnp.int32)
            pos = pos + n_real
            n_act = n_act.at[r].set(active.sum(dtype=jnp.int32))
            n_exa = n_exa.at[r].set(n_real.sum())
            kth = kth.at[r].set(jnp.where(active, fd[:, -1], jnp.nan))
            return r + 1, pos, fd, fi, peek(pos, fd), n_act, n_exa, kth

        q_n = sb.shape[0]
        zeros = jnp.zeros(n_rounds, jnp.int32)
        init = (jnp.int32(0), pos, front_d, front_i, peek(pos, front_d),
                zeros, zeros, jnp.full((n_rounds, q_n), jnp.nan, jnp.float32))
        r, pos, fd, fi, _, n_act, n_exa, kth = jax.lax.while_loop(
            lambda c: c[4].any(), body, init)
        return r, pos, fd, fi, n_act, n_exa, kth

    rep = P()
    return jax.jit(jax.shard_map(
        rr_rows_verify, mesh=mesh,
        in_specs=(P(axes, None),) + (rep,) * 8,
        out_specs=(rep,) * 7, check_vma=False))


def verify_stream_rr(raw_buf, q_dev, stream, front_d, front_i, mesh: Mesh,
                     n_shards: int, live: int, batch: int, *,
                     rounds: bool = False):
    """Every verification round of ``stream`` (a non-empty
    :class:`DeviceOrderedStream` of ids the mirror holds) in one
    device program (:func:`_rr_verify_loop_fn`): one launch, one fetch.
    Returns ``(front_d, front_i, taken, n_rounds, per_round)``: the final
    (Q, k) frontier (float64 / int64 on the host, as the host loop keeps
    it), the (Q,) ids each query verified, the round count, and — only
    with ``rounds=True``, which fetches the per-round records — a list of
    ``(active, examined, kth of the active queries)`` per round.  The
    stream's cursors advance past everything verified, as ``take``
    advances them."""
    pos0 = stream._pos
    k = front_d.shape[1]
    n_rounds = -(-stream._C // batch) + 1
    fn = _rr_verify_loop_fn(mesh, int(n_shards), int(k), int(batch),
                            int(n_rounds))
    out = fn(raw_buf, q_dev, stream._b, stream._i,
             stream._n_fin.astype(np.int32), pos0.astype(np.int32),
             np.asarray(front_d, np.float32), np.asarray(front_i, np.int32),
             np.int32(live))
    r, pos, fd, fi = jax.device_get(out[:4])
    r = int(r)
    stream._pos = pos.astype(np.int64)
    per_round = None
    if rounds:
        n_act, n_exa, kth = jax.device_get(out[4:])
        per_round = [(int(n_act[i]), int(n_exa[i]),
                      kth[i][~np.isnan(kth[i])]) for i in range(r)]
    return (fd.astype(np.float64), fi.astype(np.int64),
            stream._pos - pos0, r, per_round)


@lru_cache(maxsize=64)
def _rr_windows_gather_fn(mesh: Mesh, n_shards: int, nw: int, stride: int,
                          m: int):
    """Jitted sharded window extraction over a round-robin SOURCE-row
    mirror: each shard slices windows of its own rows (pure gather —
    bit-exact), off-shard entries contribute zeros and a psum
    re-assembles the full batch (x + 0 is exact in f32)."""
    axes = _data_axes(mesh)

    def rr_windows_gather(x, c, live):
        cap = x.shape[0]                   # x: (cap, T_src [+ lane pad])
        row = jnp.where(c >= 0, c // nw, -1)
        start = (c % nw) * stride          # in-bounds even for c == -1
        valid = ((c >= 0) & (row % n_shards == _shard_index(axes))
                 & (row < live))
        slab = x[jnp.clip(row // n_shards, 0, cap - 1)]   # (Qa, B, T_pad)
        gat = start[..., None] + jnp.arange(m)[None, None, :]
        w = jnp.take_along_axis(slab, gat, axis=2)    # (Qa, B, m)
        return jax.lax.psum(jnp.where(valid[..., None], w, 0.0), axes)

    return jax.jit(jax.shard_map(
        rr_windows_gather, mesh=mesh,
        in_specs=(P(axes, None), P(None, None), P()),
        out_specs=P(None, None, None), check_vma=False))


def cand_dists_windows_rr(raw_buf, q_dev, cand, mesh: Mesh, *,
                          n_shards: int, n_rows: int, nw: int,
                          stride: int, m: int) -> np.ndarray:
    """True z-normalized d_ED of candidate WINDOW ids against windows of
    round-robin-mirrored SOURCE rows (``repro.subseq.WindowView``
    geometry: ``wid = row * nw + j`` covers
    ``source[row, j*stride : j*stride+m]``); the mirror holds rows
    ``[0, n_rows)``.

    Each shard extracts its own rows' windows on device (sharded
    gather); the assembled device batch is then z-normalized and
    distanced through the SAME eagerly-dispatched ``znormalize`` +
    jitted euclid-kernel pipeline the host ``WindowView.fetch`` +
    kernel-verifier path runs — z-normalization must not be fused into
    a larger jit graph, or XLA re-associates its reductions and the
    device path drifts from the host path by an ulp.  Padding ids (-1)
    return +inf; window values never reach the host."""
    from repro.core.normalize import znormalize
    fn = _rr_windows_gather_fn(mesh, int(n_shards), int(nw), int(stride),
                               int(m))
    w = fn(raw_buf, jnp.asarray(cand), jnp.int32(n_rows))
    wz = znormalize(w)                   # eager: host-identical dispatch
    d2 = np.asarray(_kernel_cand_d2(wz, q_dev))  # one host transfer
    out = np.sqrt(np.maximum(d2, 0.0))
    valid = (cand >= 0) & (cand // nw < n_rows)
    return np.where(valid, out, np.float32(np.inf)).astype(np.float32)


# ---------------------------------------------------------------------------
# Device-ordered candidate stream
# ---------------------------------------------------------------------------

class DeviceOrderedStream:
    """Candidate frontier sorted by (bound, id) ONCE on device; the full
    (Q, N) bound matrix never reaches the host.

    ``core.engine.topk_verify`` drives it through two calls per round:
    ``peek()`` returns the next unverified bound per query ((Q,) f32 —
    the only per-round host transfer besides the ids themselves) and
    ``take(aq, batch)`` pops the next ``batch`` GLOBAL ids for the
    active queries, -1-padded past each query's finite frontier.  The
    (bound, id) sort equals the host matrix path's stable argsort
    (ties break toward the smaller id), so the verification schedule is
    identical — and the verified top-k is exact for ANY valid-bound
    order regardless."""

    def __init__(self, sorted_bounds, sorted_ids, n_fin, width: int):
        self._b = sorted_bounds          # (Q, C) device, ascending
        self._i = sorted_ids             # (Q, C) device int32 global ids
        self._n_fin = np.asarray(n_fin, np.int64)
        self._pos = np.zeros(self._n_fin.shape[0], np.int64)
        self._C = 0 if sorted_bounds is None else int(sorted_bounds.shape[1])
        self.width = int(width)

    @classmethod
    def empty(cls, q_n: int) -> "DeviceOrderedStream":
        return cls(None, None, np.zeros(q_n, np.int64), 0)

    @property
    def n_finite(self) -> np.ndarray:
        """(Q,) finite-bound candidate count per query — what the
        observability layer reports as 'candidates generated' when the
        (Q, N) matrix never reaches the host."""
        return self._n_fin.copy()

    def peek(self) -> np.ndarray:
        """(Q,) next unverified bound per query; +inf when exhausted."""
        if self._C == 0:
            return np.full(self._pos.shape[0], np.inf)
        idx = jnp.asarray(np.minimum(self._pos, self._C - 1)[:, None])
        nxt = np.asarray(jnp.take_along_axis(self._b, idx, axis=1),
                         np.float64)[:, 0]
        # a fully-finite row clipped at pos == C would leak a finite
        # bound: the exhaustion guard is load-bearing
        return np.where(self._pos < self._n_fin, nxt, np.inf)

    def take(self, aq, batch: int) -> np.ndarray:
        """Pop the next ``batch`` global ids for the active queries
        ``aq`` ((len(aq), batch) int64, -1-padded); advances the
        cursors by the number of real ids returned."""
        aq = np.asarray(aq, np.int64)
        if self._C == 0 or len(aq) == 0:
            return np.full((len(aq), batch), -1, np.int64)
        cols = (self._pos[aq][:, None]
                + np.arange(batch, dtype=np.int64)[None, :])
        valid = cols < self._n_fin[aq][:, None]
        gat = jnp.asarray(np.minimum(cols, self._C - 1))
        ids = np.asarray(jnp.take_along_axis(self._i[jnp.asarray(aq)],
                                             gat, axis=1), np.int64)
        self._pos[aq] += valid.sum(axis=1)
        return np.where(valid, ids, -1)


def _order_stream(bounds_dev, ids, width: int) -> DeviceOrderedStream:
    """One device lexsort of (bounds, broadcast ids) -> stream."""
    b = jnp.asarray(bounds_dev, jnp.float32)
    ib = jnp.broadcast_to(
        jnp.asarray(np.asarray(ids, np.int32))[None, :], b.shape)
    order = jnp.lexsort((ib, b), axis=-1)
    sb = jnp.take_along_axis(b, order, axis=1)
    si = jnp.take_along_axis(ib, order, axis=1)
    n_fin = np.asarray(jnp.sum(jnp.isfinite(b), axis=1))
    return DeviceOrderedStream(sb, si, n_fin, width)


def host_order_stream(bounds, ids) -> DeviceOrderedStream:
    """Order a host bound matrix on device (the ``TreeCandidates``
    device-ordering path: columns are the union candidate ids).  f64
    bounds are rounded DOWNWARD to f32 so every sorted bound is still a
    valid d_ED lower bound — the engine's exactness argument needs
    nothing more from the order."""
    b = np.asarray(bounds)
    if b.dtype != np.float32:
        b32 = b.astype(np.float32)
        over = np.isfinite(b32) & (b32.astype(np.float64) > b)
        b32[over] = np.nextafter(b32[over], np.float32(-np.inf))
        b = b32
    return _order_stream(jnp.asarray(b), np.asarray(ids, np.int64),
                         width=b.shape[1])


def make_matching_service(encoder, dataset, mesh: Mesh, *, k: int = 64,
                          pairwise: Callable | None = None):
    """Returns (rep_data, query_fn) — query_fn jitted end-to-end."""
    rep_data = encode_sharded(encoder, dataset, mesh)

    @jax.jit
    def query_fn(queries):
        rep_q = encoder.encode(queries)
        return repr_topk_sharded(encoder, rep_q, rep_data, mesh, k=k,
                                 pairwise=pairwise)

    return rep_data, query_fn


class ShardedRepSweep:
    """Device-resident sharded representation sweep over a
    ``repro.store.SymbolicStore`` that supports streaming ingestion.

    The store owns raw rows + host representation; this class maintains
    round-robin device mirrors (:class:`RoundRobinMirror` — global row
    ``i`` on shard ``i % n_shards``) and keeps them fresh under
    ``ingest``:

    * ``ingest(rows)`` encodes ONLY the new chunk — one sharded
      ``encode_sharded`` pass (padded up to a shard multiple, then
      trimmed) — and appends rows + representation to the store.  Nothing
      already ingested is re-encoded, ever.
    * On the next query the mirrors are refreshed incrementally: only
      the newly appended rows (and the < n_shards rows sharing their
      first slot) are uploaded, landing in the next slots of every
      shard — host->device traffic AND device work per ingest are
      O(chunk), not O(corpus) (the contiguous-range layout this replaced
      re-laid-out the entire resident corpus on every shard-boundary
      shift).  Every row is on the device, at any row count.
    * ``candidate_stream`` orders the device-resident bounds by
      (bound, id) on device and hands ``topk_verify`` a
      :class:`DeviceOrderedStream` — the exact path never materializes
      the (Q, N) matrix on the host (``host_order_bytes`` stays 0; the
      legacy ``repr_distances`` matrix path counts what it moves).
    * With ``mirror_raw=True`` the RAW rows are mirrored round-robin
      next to the representation and kept in sync by the same O(chunk)
      append — ``make_dist_fn`` then verifies candidate rows entirely
      on device (``verify="device"``); old rows are never re-encoded
      and never re-uploaded.
    """

    mirror_layout = "round_robin"

    def __init__(self, encoder, mesh: Mesh, store, *,
                 pairwise: Callable | None = None,
                 mirror_raw: bool = False):
        self.encoder = encoder
        self.mesh = mesh
        self.store = store
        self._pw = pairwise or encoder.pairwise_distance
        self.axes = _data_axes(mesh)
        self.n_shards = _n_shards(mesh)
        self.mirror_raw = bool(mirror_raw)
        if self.mirror_raw and not getattr(store, "store_raw", True):
            raise ValueError("device-resident verification needs raw rows "
                             "in the store (store_raw=True)")
        self._synced_version = -1
        self._synced_n = 0               # row frontier the mirrors cover
        self._sync_lock = threading.Lock()
        self._mirrors = None             # per-rep-leaf RoundRobinMirror
        self._raw_mirror = (RoundRobinMirror(mesh, self.n_shards,
                                             lanes=_LANES)
                            if self.mirror_raw else None)
        self.host_order_bytes = 0        # bytes of host bound matrices

    # -- ingest -----------------------------------------------------------
    def _encode_chunk(self, rows: np.ndarray):
        """Sharded one-pass encode of a chunk (pad to shard multiple,
        trim) — bit-identical to the unsharded row-wise encode.  The
        host rows go straight to their shards, ``_ENCODE_ROWS`` at a
        time, so neither one device nor the encoder's intermediates ever
        hold the whole chunk."""
        from repro.store.symbolic import rep_leaves
        sh = NamedSharding(self.mesh, P(self.axes, None))
        parts = []
        for c0 in range(0, rows.shape[0], _ENCODE_ROWS):
            blk = rows[c0:c0 + _ENCODE_ROWS]
            m = blk.shape[0]
            pad = (-m) % self.n_shards
            if pad:
                blk = np.concatenate([blk, blk[-1:].repeat(pad, axis=0)])
            rep = encode_sharded(self.encoder, jax.device_put(blk, sh),
                                 self.mesh)
            parts.append(tuple(np.asarray(l)[:m] for l in rep_leaves(rep)))
        leaves = tuple(np.concatenate(ls) for ls in zip(*parts))
        return leaves if isinstance(rep, tuple) else leaves[0]

    def ingest(self, rows) -> np.ndarray:
        """Append rows to the store; only the new chunk is encoded."""
        rows = np.asarray(rows, np.float32)
        if rows.ndim == 1:
            rows = rows[None]
        if rows.shape[0] == 0:
            return np.empty(0, np.int64)
        return self.store.append(rows, rep=self._encode_chunk(rows))

    # -- device mirror ----------------------------------------------------
    def _sync(self):
        if self._synced_version == self.store.version:
            return
        with self._sync_lock:
            if self._synced_version == self.store.version:
                return
            from repro.store.symbolic import rep_leaves
            # Capture the frontier FIRST: a writer may append while we
            # sync, so everything below (leaves, version stamp) is
            # sliced to this (version, n) pair — never the live
            # attributes, which could already be past it.
            version = self.store.version
            n = self.store.n
            if n > self._synced_n:
                leaves = rep_leaves(self.store.rep_view())
                if self._mirrors is None:
                    self._mirrors = tuple(
                        RoundRobinMirror(self.mesh, self.n_shards)
                        for _ in leaves)
                # O(chunk): only the unmirrored slots are uploaded
                for mir, l in zip(self._mirrors, leaves):
                    mir.sync(l[:n])
                if self.mirror_raw:
                    self._raw_mirror.sync(self.store.data[:n])
            self._synced_n = n
            self._synced_version = version

    @property
    def h2d_bytes(self) -> int:
        """Total host->device mirror upload traffic (bytes)."""
        total = sum(m.h2d_bytes for m in (self._mirrors or ()))
        if self._raw_mirror is not None:
            total += self._raw_mirror.h2d_bytes
        return total

    @property
    def mirror_bytes(self) -> dict:
        """Device bytes held by the mirrors, summed over shards (lane
        padding and spare capacity included): ``{"rep": ..., "raw": ...}``."""
        def held(m):
            return 0 if m is None or m.buf is None else int(m.buf.nbytes)
        return {"rep": sum(held(m) for m in (self._mirrors or ())),
                "raw": held(self._raw_mirror)}

    def transfer_stats(self) -> dict:
        """Device<->host transfer counters for the observability layer:
        ``host_order_bytes`` (host-assembled candidate-order matrices —
        0 on the streaming exact path) and ``h2d_bytes`` (mirror
        uploads)."""
        return {"host_order_bytes": int(self.host_order_bytes),
                "h2d_bytes": int(self.h2d_bytes)}

    def _mirror_tree(self):
        bufs = tuple(m.buf for m in self._mirrors)
        single = not isinstance(self.store.rep_view(), tuple)
        return bufs[0] if single else bufs

    def _rr_bounds(self, rep_q):
        """(Q, S*cap) blocked device bound matrix over the mirrors."""
        mt = self._mirror_tree()
        fn = _rr_bounds_fn(self.mesh, self._pw, *_rep_specs(rep_q, mt))
        return fn(rep_q, mt, jnp.int32(self._mirrors[0].live))

    # -- sweeps -----------------------------------------------------------
    def repr_distances(self, queries_raw) -> np.ndarray:
        """(Q, N) lower-bound matrix on the HOST (legacy matrix path:
        the blocked device matrix is pulled over and unpermuted to
        natural row order; the traffic is counted in
        ``host_order_bytes``).  The exact top-k path uses
        ``candidate_stream`` instead and never pays this."""
        self._sync()
        if self._mirrors is None:
            q_n = np.asarray(queries_raw).shape[0]
            return np.empty((q_n, 0), np.float32)
        rep_q = self.encoder.encode(jnp.asarray(queries_raw, jnp.float32))
        blk = np.asarray(self._rr_bounds(rep_q))       # (Q, S*cap)
        S, cap = self.n_shards, self._mirrors[0].cap
        # block column s*cap + j  ->  global row j*S + s; dead slots land
        # at ids >= n and are trimmed
        arr = np.ascontiguousarray(
            blk.reshape(-1, S, cap).transpose(0, 2, 1)
            .reshape(-1, S * cap)[:, :self._synced_n])
        self.host_order_bytes += arr.nbytes
        return arr

    def candidates(self, queries_raw, k: int) -> np.ndarray:
        """(Q, k) global candidate frontier: sharded local top-k over the
        mirrors, all-gathered and merged on device."""
        self._sync()
        if self._mirrors is None:        # empty corpus: no candidates yet
            q_n = np.asarray(queries_raw).shape[0]
            return np.empty((q_n, 0), np.int64)
        rep_q = self.encoder.encode(jnp.asarray(queries_raw, jnp.float32))
        mt = self._mirror_tree()
        fn = _rr_topk_fn(self.mesh, self._pw, int(k), self.n_shards,
                         *_rep_specs(rep_q, mt))
        _, i = fn(rep_q, mt, jnp.int32(self._mirrors[0].live))
        return np.asarray(i, np.int64)

    def candidate_stream(self, queries_raw,
                         mask_fn=None) -> DeviceOrderedStream:
        """Device-ordered exact candidate frontier: the blocked mirror
        bounds are lexsorted by (bound, global id) ON DEVICE — no (Q, N)
        host matrix, no host argsort.  The stream yields global ids
        directly.

        ``mask_fn``, if given, maps the (C,) int64 global-id vector to
        a (Q, C) or (C,) boolean mask of candidates to SUPPRESS (their
        bounds become +inf on device, so they fall past the finite
        frontier and never reach verification) — e.g. the self-join
        trivial-match zone.  The mask is computed and applied on
        device; candidate order still never touches the host."""
        self._sync()
        qs = np.asarray(queries_raw, np.float32)
        if qs.ndim == 1:
            qs = qs[None]
        if self._mirrors is None:
            return DeviceOrderedStream.empty(qs.shape[0])
        b = self._rr_bounds(self.encoder.encode(jnp.asarray(qs)))
        S, cap = self.n_shards, self._mirrors[0].cap
        # block column s*cap + j holds global row j*S + s (dead slots get
        # ids >= n but their bounds are +inf, so the finite-frontier
        # cursor never reaches them)
        ids = (np.arange(cap, dtype=np.int64)[None, :] * S
               + np.arange(S, dtype=np.int64)[:, None]).reshape(-1)
        if mask_fn is not None:
            mask = jnp.asarray(mask_fn(jnp.asarray(ids)))
            b = jnp.where(mask, jnp.float32(np.inf), b)
        return _order_stream(b, ids, width=self._synced_n)

    # -- device-resident verification -------------------------------------
    def shard_ranges(self):
        """Contiguous row ranges of the corpus over the shards — the
        SNAPSHOT raw manifest's per-host unit
        (``store.snapshot._shard_ranges``).  This is deliberately NOT
        the device mirror layout (see ``mirror_layout`` /
        ``owned_rows``): on-disk shards stay contiguous, device
        placement is round-robin, and results are identical either
        way."""
        from repro.store.snapshot import _shard_ranges
        return _shard_ranges(self._synced_n, self.n_shards)

    def owned_rows(self, shard: int) -> np.ndarray:
        """Global row ids resident on ``shard`` under the round-robin
        mirror layout (row ``i`` -> shard ``i % n_shards``)."""
        return np.arange(shard, self._synced_n, self.n_shards,
                         dtype=np.int64)

    def make_dist_fn(self, queries_raw):
        """Device-resident verification closure for one query batch:
        ``dist(q_idx, cand) -> (Qa, B)`` true d_ED of candidate row ids,
        computed per shard through the multi-query euclid kernel over
        the round-robin raw mirror — raw rows never move device->host.
        The contract matches ``core.engine.topk_verify``'s
        ``dist_fn``.

        The closure also carries ``verify_loop(stream, front_d, front_i,
        batch, rounds=False)`` (:func:`verify_stream_rr`): the whole
        round loop over a device-ordered stream as one device program,
        which ``topk_verify`` runs instead of its host loop."""
        if not self.mirror_raw:
            raise ValueError("ShardedRepSweep was built without "
                             "mirror_raw=True; no raw device mirror to "
                             "verify against")
        self._sync()
        qs = np.asarray(queries_raw, np.float32)
        if qs.ndim == 1:
            qs = qs[None]
        q_n = qs.shape[0]
        q_dev = jnp.asarray(qs)
        mir = self._raw_mirror

        def dist(aq, cand):
            # pad the active-query batch back to the full query set so
            # the jitted shard_map sees ONE (Q, B) shape per batch size
            # — rounds with fewer active queries reuse the compile cache
            aq = np.asarray(aq)
            cand = np.asarray(cand, np.int64)
            full = np.full((q_n, cand.shape[1]), -1, np.int64)
            full[aq] = cand
            if not (full >= 0).any():
                return np.full(cand.shape, np.inf, np.float32)
            return cand_dists_rows_rr(mir.buf, q_dev, full, self.mesh,
                                      self.n_shards, mir.live)[aq]

        def verify_loop(stream, front_d, front_i, batch, rounds=False):
            return verify_stream_rr(
                mir.buf, q_dev, stream, front_d, front_i, self.mesh,
                self.n_shards, mir.live, batch, rounds=rounds)

        dist.verify_loop = verify_loop
        return dist


def make_engine_service(encoder, dataset, mesh: Mesh, store=None, *,
                        batch_size: int = 64, verify: str = "auto",
                        pairwise: Callable | None = None,
                        media: str = "ssd", metrics=None):
    """Sharded representation sweep feeding the batched k-NN engine.

    Builds (or adopts) a ``repro.store.SymbolicStore``, runs one sharded
    encode pass over ``dataset``, and returns a ``core.engine.MatchEngine``
    whose exact top-k orders candidates ON DEVICE
    (``ShardedRepSweep.candidate_stream`` — the (Q, N) bound matrix
    never reaches the host) and whose approximate top-k uses the sharded
    candidate frontier (collective volume O(Q*k*shards)) before raw
    verification against the store.

    The engine supports ingest-while-serving: ``engine.ingest(rows)``
    encodes only the new chunk (sharded) and appends it to the
    round-robin device mirrors without touching resident rows —
    per-append cost is O(chunk) regardless of corpus size; the next
    query serves the new rows.  With ``verify="device"`` the raw mirror
    is kept in sync by the same O(chunk) append, so ingest never
    re-uploads old rows.

    ``store``: a ``SymbolicStore`` (adopted as-is; ``dataset`` may be None
    to serve its existing rows), a legacy ``RawStore`` (its cost model AND
    its rows are adopted — verification accounting moves to the returned
    ``engine.store``), or None (a fresh store with the ``media`` preset).

    ``verify``: "device" shards the raw rows across devices alongside the
    representation and verifies per shard through the euclid kernel —
    zero raw rows moved to the host; "host" is the bit-identical
    host-side fallback (store fetch + the same kernel math, modeled-I/O
    oracle); "auto" / "numpy" / "kernel" as in ``core.engine``.
    """
    from repro.core.engine import MatchEngine
    from repro.store import SymbolicStore

    if isinstance(store, SymbolicStore):
        sym = store
        if dataset is not None and sym.n:
            raise ValueError(
                "both a non-empty SymbolicStore and a dataset were given; "
                "pass dataset=None to serve the store's rows, or "
                "engine.ingest(dataset) explicitly to append them")
    elif store is not None:              # legacy RawStore: adopt cost model
        sym = SymbolicStore(encoder, seek_s=store.seek_s,
                            read_bps=store.read_bps)
        if dataset is None and store.data.shape[0]:
            dataset = store.data         # ...and its rows
    else:
        sym = SymbolicStore(encoder, media=media)

    device_verify = verify == "device"
    sweep = ShardedRepSweep(encoder, mesh, sym, pairwise=pairwise,
                            mirror_raw=device_verify)
    if dataset is not None and sym.n == 0:
        sweep.ingest(np.asarray(dataset, np.float32))

    engine = MatchEngine(encoder, sym, batch_size=batch_size,
                         verify=verify, pairwise=pairwise,
                         repr_fn=sweep.repr_distances,
                         cand_fn=sweep.candidates,
                         stream_factory=sweep.candidate_stream,
                         dist_factory=(sweep.make_dist_fn
                                       if device_verify else None),
                         metrics=metrics)
    engine.sweep = sweep
    engine.ingest = sweep.ingest
    return engine


class ShardedWindowSweep:
    """Sharded window sweep + device-resident window verification for
    ``repro.subseq.SubseqEngine``.

    * The (Q, n_windows) representation sweep shards the view's live
      window representation exactly like whole-series matching — an
      inner :class:`ShardedRepSweep` over the view's representation
      store, so stride > 1 and ragged T (already folded into the window
      geometry by ``WindowView``) and any window count are served from
      the round-robin mirrors, which window appends refresh in
      O(chunk).
    * ``candidate_stream`` is the inner sweep's device-ordered stream:
      window-representation rows ARE window ids, so the exact subsequence
      path feeds ``topk_verify`` without a host (Q, n_windows) matrix.
    * ``make_dist_fn`` verifies candidate WINDOWS device-side: the
      SOURCE long rows are mirrored round-robin on device (row ``i`` on
      shard ``i % n_shards``); each shard slices and z-normalizes its
      own rows' windows (the same ``core.normalize.znormalize`` the host
      fetch path applies) and distances them through the multi-query
      euclid kernel (:func:`cand_dists_windows_rr`).  Every source row
      is on the device; window values never materialize on the host.
    """

    mirror_layout = "round_robin"

    def __init__(self, view, mesh: Mesh, *, mirror_raw: bool = True):
        self.view = view
        self.mesh = mesh
        self.rep_sweep = ShardedRepSweep(view.encoder, mesh, view.rep_store)
        self.axes = self.rep_sweep.axes
        self.n_shards = self.rep_sweep.n_shards
        self.mirror_raw = bool(mirror_raw)
        self._raw_mirror = (RoundRobinMirror(mesh, self.n_shards,
                                             lanes=_LANES)
                            if self.mirror_raw else None)  # SOURCE rows

    def repr_distances(self, queries_z) -> np.ndarray:
        """(Q, n_windows) lower-bound matrix for already z-normalized
        queries — host matrix path (exclusion re-sweeps mutate it); the
        exact non-exclusion path uses ``candidate_stream``."""
        return self.rep_sweep.repr_distances(queries_z)

    def candidate_stream(self, queries_z,
                         mask_fn=None) -> DeviceOrderedStream:
        """Device-ordered window candidate stream (global window ids).
        ``mask_fn`` suppresses window ids on device (bounds -> +inf)
        before ordering — see ``ShardedRepSweep.candidate_stream``; the
        self-join engine uses it for the trivial-match zone."""
        return self.rep_sweep.candidate_stream(queries_z, mask_fn=mask_fn)

    @property
    def h2d_bytes(self) -> int:
        total = self.rep_sweep.h2d_bytes
        if self._raw_mirror is not None:
            total += self._raw_mirror.h2d_bytes
        return total

    @property
    def host_order_bytes(self) -> int:
        return self.rep_sweep.host_order_bytes

    def transfer_stats(self) -> dict:
        """Same contract as ``ShardedRepSweep.transfer_stats`` with the
        source-row mirror traffic folded in."""
        return {"host_order_bytes": int(self.host_order_bytes),
                "h2d_bytes": int(self.h2d_bytes)}

    def _sync_raw(self):
        """Incremental round-robin mirror of the source rows
        (append-only corpus: only rows past the mirror's ``live`` are
        read and uploaded)."""
        self._raw_mirror.sync(self.view.source.data, np.float32)

    def make_dist_fn(self, queries_z):
        """Device-resident window verification closure (the
        ``core.engine.topk_verify`` ``dist_fn`` contract over window
        ids) for one z-normalized query batch."""
        if not self.mirror_raw:
            raise ValueError("ShardedWindowSweep was built without "
                             "mirror_raw=True")
        self._sync_raw()
        qs = np.asarray(queries_z, np.float32)
        if qs.ndim == 1:
            qs = qs[None]
        q_n = qs.shape[0]
        q_dev = jnp.asarray(qs)
        view = self.view
        mir = self._raw_mirror

        def dist(aq, cand):
            # full-Q padding: one (Q, B) shard_map shape per batch size
            aq = np.asarray(aq)
            cand = np.asarray(cand, np.int64)
            full = np.full((q_n, cand.shape[1]), -1, np.int64)
            full[aq] = cand
            if not (full >= 0).any():
                return np.full(cand.shape, np.inf, np.float32)
            return cand_dists_windows_rr(
                mir.buf, q_dev, full, self.mesh, n_shards=self.n_shards,
                n_rows=mir.live, nw=view.windows_per_row,
                stride=view.stride, m=view.m)[aq]

        return dist

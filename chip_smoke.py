"""Bring-up smoke: exact season-aware matching served on the TPU.

Drives the served exact-matching path once, through the entry points a
user calls, at a corpus size a deployment holds on one chip:

    SymbolicStore -> SeriesIndex -> make_engine_service(verify="device")
    -> MatchSession (coalescing queue + planner)

and checks every answer against a float64 numpy brute force over the same
corpus.  Run from the repository root:

    python chip_smoke.py                  # one chip: 1,048,576 x 960 rows
    python chip_smoke.py --chips 4        # four chips: 4 x 1,048,576 rows,
                                          # plus device-vs-host verification
    JAX_PLATFORMS=cpu python chip_smoke.py --n 2048    # CPU rehearsal

The corpus is the paper's Season (Large) shape (T = 960, season length
10, per-series strength around 0.5) made from ``--seed``; ``--n`` is rows
per chip.  One process drives every chip and starts no child process.

The one-chip load is 64 queries served at k = 32 and then at k = 1 from
8 client threads: the first ``INDEX_PER_WAVE`` requests of a wave are
forced onto the index tier, the rest are routed by the calibrated
planner (to the linear tier).  Answers must also be bit-identical to
``engine.topk``.  ``--chips 4`` runs only what exists across chips: the
same waves over the sharded linear tier (no index, whose tree walk runs
on the host), checked against the f64 reference and against
``verify="host"``.

Everything worth knowing goes on lines before the last.  The last line
is ``{"ok": true, "device": {...}}`` only when every phase passed on a
TPU; any failed phase, reference mismatch or shed request makes the
script exit non-zero without it.  Off the TPU the phases still run (at a
rehearsal size) and the script then fails, naming the platform it found.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

T, L, W = 960, 10, 48            # configs/paper.py season_large; sSAX W
STRENGTH = 0.5
QUERIES = 64                     # distinct queries, served once per k
KS = (32, 1)
CLIENTS = 8
BATCH = 256                      # verification candidates per round
LEAF_FILL = 64
#: index-tier requests per wave: the tree walk costs seconds per query
#: on the host at 1 M rows, so a few prove the tier
INDEX_PER_WAVE = 4
REL_TOL = 1e-5                   # f32 answer vs f64 reference
#: rows per generated corpus chunk, and chunks generated at once: one
#: chunk peaks near 7x its output (about 210 MB at 8,192 rows), so the
#: pool is bounded by memory, not by the host's core count
GEN_ROWS = 1 << 13
GEN_WORKERS = 32
REF_ROWS = 1 << 15               # rows per f64 reference block
#: largest corpus a run off the TPU builds: the full size is for the chip
REHEARSAL_MAX_ROWS = 1 << 16


_START = time.perf_counter()


def log(msg: str) -> None:
    print(f"[smoke +{time.perf_counter() - _START:.0f}s] {msg}", flush=True)


def make_corpus(n: int, seed: int) -> tuple:
    """(queries (QUERIES, T), corpus (n, T)) f32 host arrays from
    ``season_dataset``, generated in independently seeded chunks on a
    thread pool (numpy's generators and ufuncs release the GIL)."""
    from repro.data.synthetic import season_dataset

    def chunk(rows, *key):
        return season_dataset(rows, T, L, STRENGTH, seed=[seed, *key],
                              per_series_strength=True)

    queries = chunk(QUERIES, 1)
    corpus = np.empty((n, T), np.float32)

    def fill(lo):
        corpus[lo:lo + GEN_ROWS] = chunk(min(GEN_ROWS, n - lo), 0, lo)

    with ThreadPoolExecutor(max_workers=GEN_WORKERS) as ex:
        list(ex.map(fill, range(0, n, GEN_ROWS)))
    return queries, corpus


def f64_distances(queries, corpus) -> np.ndarray:
    """(Q, N) float64 squared distances, in row blocks (BLAS matmul on
    the host; no device involved)."""
    q = queries.astype(np.float64)
    qq = np.einsum("ij,ij->i", q, q)
    out = np.empty((q.shape[0], corpus.shape[0]), np.float64)
    for lo in range(0, corpus.shape[0], REF_ROWS):
        x = corpus[lo:lo + REF_ROWS].astype(np.float64)
        xx = np.einsum("ij,ij->i", x, x)
        out[:, lo:lo + x.shape[0]] = (qq[:, None] + xx[None, :]
                                      - 2.0 * (q @ x.T))
    return out


def exact_f64(query, corpus, ids) -> np.ndarray:
    """Direct float64 distances of ``ids`` to ``query``."""
    diff = corpus[ids].astype(np.float64) - query.astype(np.float64)
    return np.sqrt(np.sum(diff * diff, axis=-1))


def reference_mismatch(query, corpus, d2_row, k, ids, dists):
    """None when (ids, dists) is the exact top-k under the f64 reference
    (distances within REL_TOL of the f64 distance of the same id; the id
    set equal to the f64 top-k except for ids whose f64 distance lies
    within REL_TOL of the k-th); else a message."""
    d_ret = exact_f64(query, corpus, ids)
    bad = np.abs(np.asarray(dists, np.float64) - d_ret) > REL_TOL * d_ret
    if bad.any():
        return (f"distance off for ids {ids[bad][:4].tolist()}: "
                f"{np.asarray(dists)[bad][:4].tolist()} vs f64 "
                f"{d_ret[bad][:4].tolist()}")
    top = np.argpartition(d2_row, k - 1)[:k]
    kth = float(np.sqrt(max(np.partition(d2_row, k - 1)[k - 1], 0.0)))
    diff = np.setxor1d(top, ids)
    if diff.size:
        d_diff = exact_f64(query, corpus, diff)
        outside = np.abs(d_diff - kth) > REL_TOL * kth
        if outside.any():
            return (f"id set differs from the f64 top-{k} beyond the "
                    f"k-th tie band: {diff[outside][:4].tolist()}")
    return None


def serve_wave(session, queries, k: int, n_index: int) -> list:
    """CLIENTS threads submit one single-query request each at a time;
    requests ``i < n_index`` (the first of clients 0..n_index-1, sent
    together) are forced onto the index tier, the rest are routed by the
    planner."""
    reqs = [None] * len(queries)

    def client(c):
        for i in range(c, len(queries), CLIENTS):
            tier = "index" if i < n_index else None
            r = session.submit(queries[i], k=k, tier=tier)
            r.wait(600)
            reqs[i] = r

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return reqs


def verify_program(engine, q_n: int):
    """The compiled sharded row-verification program for a (q_n, BATCH)
    round over the engine's raw device mirror."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.distributed import _rr_rows_verify_fn
    sweep = engine.sweep
    rep = NamedSharding(sweep.mesh, P())
    return _rr_rows_verify_fn(sweep.mesh, sweep.n_shards).lower(
        sweep._raw_mirror.buf,              # the device raw mirror
        jax.ShapeDtypeStruct((q_n, T), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((q_n, BATCH), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)).compile()


def ulp_gap(a, b) -> int:
    a = np.asarray(a, np.float32).view(np.int32).astype(np.int64)
    b = np.asarray(b, np.float32).view(np.int32).astype(np.int64)
    return int(np.max(np.abs(a - b))) if a.size else 0


def compare_with(oracle, reqs):
    """(requests whose answer differs bitwise from ``oracle.topk`` with
    the same tier source, k and epoch; largest ulp gap between their
    distances).  Oracle calls batch CLIENTS requests, a size the session
    itself dispatches; answers are independent of the batch."""
    groups: dict = {}
    for r in reqs:
        groups.setdefault((r.tier_served, r.k), []).append(r)
    bad, gap = 0, 0
    for (tier, k), rs in groups.items():
        for lo in range(0, len(rs), CLIENTS):
            part = rs[lo:lo + CLIENTS]
            res = oracle.topk(np.stack([r.query for r in part]), k=k,
                              source="index" if tier == "index" else None,
                              epoch=part[0].epoch)
            for i, r in enumerate(part):
                bad += not (np.array_equal(r.indices, res.indices[i])
                            and np.array_equal(r.distances,
                                               res.distances[i]))
                gap = max(gap, ulp_gap(r.distances, res.distances[i]))
    return bad, gap


def run(args) -> list:
    """Every phase; returns the failed checks (empty when all passed)."""
    import jax
    from jax.sharding import Mesh

    from repro.core import make_technique
    from repro.core.distributed import make_engine_service
    from repro.obs import REGISTRY
    from repro.service import MatchSession

    devs = jax.devices()
    log(f"devices: {len(devs)} x {devs[0].device_kind} "
        f"(platform {devs[0].platform}); mesh over {args.chips}")
    if len(devs) < args.chips:
        return [f"{args.chips} chips asked for, {len(devs)} present"]
    n = args.n * args.chips
    if devs[0].platform != "tpu" and n > REHEARSAL_MAX_ROWS:
        return [f"platform is {devs[0].platform}, not tpu: the "
                f"{n}-row corpus is for the chip (rehearse with --n)"]
    mesh = Mesh(np.asarray(devs[:args.chips]), ("data",))
    fails = []

    t0 = time.perf_counter()
    queries, corpus = make_corpus(n, args.seed)
    log(f"setup data: {n} x {T} f32 host corpus "
        f"({corpus.nbytes} bytes) in {time.perf_counter() - t0:.1f}s")

    t0 = time.perf_counter()
    tech = make_technique("ssax", T=T, W=W, L=L)
    engine = make_engine_service(tech, corpus, mesh, batch_size=BATCH,
                                 verify="device", metrics=REGISTRY)
    log(f"setup encode: store append + sharded encode in "
        f"{time.perf_counter() - t0:.1f}s")

    sharded = args.chips > 1
    if not sharded:
        t0 = time.perf_counter()
        engine.store.build_index(leaf_fill=LEAF_FILL)
        log(f"setup index: {engine.store.index.n_nodes} nodes "
            f"over {engine.store.index.n} rows in "
            f"{time.perf_counter() - t0:.1f}s")

    t0 = time.perf_counter()
    engine.topk(queries[:1], k=KS[0])
    log(f"setup first query (mirror upload + first compile): "
        f"{time.perf_counter() - t0:.1f}s")
    mb = engine.sweep.mirror_bytes
    log(f"mirror bytes on device: raw {mb['raw']}, rep {mb['rep']}"
        f" (corpus {corpus.nbytes})")
    for d in devs[:args.chips]:
        st = d.memory_stats() or {}
        log(f"{d}: bytes_in_use {st.get('bytes_in_use')}")

    prog = verify_program(engine, CLIENTS)
    has_kernel = "tpu_custom_call" in prog.as_text()
    ma = prog.memory_analysis()
    log(f"verify program ({CLIENTS} x {BATCH} round): "
        f"tpu_custom_call {'present' if has_kernel else 'ABSENT'}; temp "
        f"{getattr(ma, 'temp_size_in_bytes', None)} bytes")
    if devs[0].platform == "tpu" and not has_kernel:
        fails.append("verify program holds no tpu_custom_call")

    session = MatchSession(engine, metrics=REGISTRY, max_batch=CLIENTS,
                           max_queue=4 * QUERIES).start()
    try:
        if not sharded:
            t0 = time.perf_counter()
            session.calibrate(queries[:1], k=KS[0])
            log(f"setup planner calibration: "
                f"{time.perf_counter() - t0:.1f}s")
        reqs = []
        for k in KS:
            reqs += serve_wave(session, queries, k,
                               0 if sharded else INDEX_PER_WAVE)
    finally:
        session.close(drain=False)

    served = [r for r in reqs if r is not None and r.ok]
    shed: dict = {}
    for r in reqs:
        if r is None or not r.ok:
            why = "no answer" if r is None else (r.shed_reason or "error")
            shed[why] = shed.get(why, 0) + 1
    tiers: dict = {}
    for r in served:
        tiers[r.tier_served] = tiers.get(r.tier_served, 0) + 1
    log(f"requests: {len(served)}/{len(reqs)} served from "
        f"{CLIENTS} clients (k in {KS}); tiers {tiers}; shed {shed}")
    for r in reqs:
        if r is not None and not r.ok:
            log(f"first shed: {r.shed_reason}: {r.error}")
            break
    if len(served) != len(reqs) or shed:
        fails.append(f"{len(reqs) - len(served)} requests not served")
    want = {"linear"} if sharded else {"index", "linear"}
    if set(tiers) != want:
        fails.append(f"tiers served {sorted(tiers)}, want {sorted(want)}")

    t0 = time.perf_counter()
    d2 = f64_distances(queries, corpus)
    row_of = {q.tobytes(): i for i, q in enumerate(queries)}
    ref_bad = []
    for r in served:
        i = row_of[r.query.tobytes()]
        msg = reference_mismatch(queries[i], corpus, d2[i], r.k,
                                 r.indices, r.distances)
        if msg:
            ref_bad.append(f"query {i} k={r.k}: {msg}")
    log(f"f64 reference mismatches: {len(ref_bad)}/{len(served)} "
        f"(checked in {time.perf_counter() - t0:.1f}s)")
    for m in ref_bad[:4]:
        log(f"  {m}")
    if ref_bad:
        fails.append(f"{len(ref_bad)} answers differ from the f64 reference")

    if sharded:
        host = make_engine_service(tech, None, mesh, store=engine.store,
                                   batch_size=BATCH, verify="host")
        bad, gap = compare_with(host, served)
        log(f"device vs host verification: {bad} mismatching "
            f"requests of {len(served)}; largest ulp gap {gap}")
        if bad:
            fails.append(f"{bad} device answers differ from verify=host")
    else:
        bad, _ = compare_with(engine, served)
        log(f"session == engine.topk bitwise: "
            f"{len(served) - bad}/{len(served)}")
        if bad:
            fails.append(f"{bad} session answers differ from engine.topk")

    for d in devs[:args.chips]:
        st = d.memory_stats() or {}
        log(f"{d}: peak_bytes_in_use {st.get('peak_bytes_in_use')}")
    return fails


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--n", type=int, default=1 << 20,
                    help="corpus rows per chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    import jax

    fails = run(args)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fails.append(f"platform is {dev.platform}, not tpu")
    if fails:
        for f in fails:
            log(f"FAIL: {f}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

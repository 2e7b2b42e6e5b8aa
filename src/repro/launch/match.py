"""Matching-service launcher: build a sharded sSAX (or SAX/tSAX/stSAX)
representation of a dataset and serve batched exact / approximate top-k
matches through the unified k-NN engine.

    PYTHONPATH=src python -m repro.launch.match \
        --n 40000 --strength 0.7 --technique ssax --queries 8 --k 32 \
        --ingest 4 --snapshot-dir /tmp/match-snaps --index

``--index`` builds the split-tree index (``repro.index``) over the
store and serves exact top-k from its sublinear candidate generation
(bit-identical to the linear sweep, fewer candidates examined); the
index is maintained incrementally through ``--ingest`` appends and
persisted by ``--snapshot-dir``.  ``--leaf-fill`` tunes the leaf split
threshold.  Both flags apply to the ``--subseq`` windowed path too.

``--subseq`` switches to subsequence matching: the corpus rows become
long series, every z-normalized window of length ``--window`` at
``--stride`` is symbolically indexed (``repro.subseq.WindowView``), and
queries are snippets localized anywhere in the corpus through the pruned
windowed scan (``repro.subseq.SubseqEngine``), compared against the
MASS-style brute-force kernel:

    PYTHONPATH=src python -m repro.launch.match \
        --subseq --n 64 --T 3600 --window 240 --stride 4 --k 8

Device count is taken from the environment (set XLA_FLAGS
--xla_force_host_platform_device_count=8 for a local fleet simulation);
the same code drives the production ("pod","data") mesh axes.  The
sharded sweep produces lower bounds / candidate frontiers; raw
verification goes through ``core.engine.MatchEngine`` (Pallas euclid
kernel on TPU, one batched store fetch per round) — or, with
``--verify device``, stays device-resident end to end: the raw rows are
sharded across the mesh next to the representation and candidates are
verified per shard through the euclid kernel, moving zero raw rows to
the host (``--verify host`` is the bit-identical host fallback; both
apply to ``--subseq`` too).  The engine is backed
by a ``repro.store.SymbolicStore``: ``--ingest N`` appends N chunks while
serving queries between them (only new rows are encoded), and
``--snapshot-dir`` persists the store + representation after the run.
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def _explain(trace, *, device: bool):
    """Render the per-query plan and hard-fail on a broken trace.

    ``device=True`` additionally enforces the device-path invariants as
    a gate: ``host_order_bytes == 0`` (ordering stayed device-resident)
    and ``rows_to_host == 0`` (no raw row crossed to the host during
    verification).  CI runs this through ``--explain --dryrun``."""
    from repro.obs import check_trace, render_trace
    print(render_trace(trace))
    problems = check_trace(trace, device=device)
    if problems:
        raise SystemExit("[explain] trace check FAILED: "
                         + "; ".join(problems))


def _print_metrics(registry):
    """One-screen registry summary (counters + latency quantiles)."""
    snap = registry.snapshot()
    if snap["counters"]:
        kv = ", ".join(f"{k}={v:g}" for k, v in
                       sorted(snap["counters"].items()))
        print(f"[metrics] {kv}")
    for name, h in sorted(snap["histograms"].items()):
        hist = registry.histogram(name)
        if hist.count:
            print(f"[metrics] {name}: n={hist.count} "
                  f"p50<={hist.quantile(0.5):.3g}s "
                  f"p99<={hist.quantile(0.99):.3g}s")


def run_subseq(args):
    """Subsequence mode: index every window of an (n, T) long-series
    corpus, localize snippet queries exactly, compare against the
    brute-force windowed kernel scan."""
    import numpy as np

    from repro.core import make_technique
    from repro.data.synthetic import season_dataset
    from repro.obs import REGISTRY
    from repro.subseq import SubseqEngine, WindowView

    m, s = args.window, args.stride
    if m % args.L:
        raise SystemExit(f"--window {m} must be a multiple of --L {args.L}")
    if m > args.T:
        raise SystemExit(f"--window {m} longer than --T {args.T}")
    tech = make_technique(args.technique, T=m, W=m // args.L, L=args.L,
                          r2_season=args.strength)

    mesh = None
    if args.verify == "device":
        import jax
        from repro.launch.mesh import make_mesh_compat
        mesh = make_mesh_compat((len(jax.devices()),), ("data",))
        print(f"[subseq] device-resident verification over "
              f"{len(jax.devices())} devices")

    rng = np.random.default_rng(7)
    D = season_dataset(args.n, args.T, args.L, args.strength,
                       per_series_strength=True, seed=7)
    q_rows = rng.integers(0, args.n, size=args.queries)
    offs = rng.integers(0, args.T - m + 1, size=args.queries)
    Q = np.stack([D[r, o:o + m] for r, o in zip(q_rows, offs)])
    Q = Q + 0.05 * rng.normal(size=Q.shape).astype(np.float32)

    t0 = time.perf_counter()
    view = WindowView(tech, D, stride=s, media=args.store)
    print(f"[subseq] {args.technique} over {args.n} x {args.T} "
          f"-> {view.n} windows (m={m}, stride={s}); "
          f"encode {time.perf_counter() - t0:.2f}s")
    engine = SubseqEngine(view, batch_size=args.batch, verify=args.verify,
                          mesh=mesh, metrics=REGISTRY)

    if args.index:
        t0 = time.perf_counter()
        view.build_index(leaf_fill=args.leaf_fill)
        print(f"[subseq] window index: {view.index.n_nodes} nodes over "
              f"{view.index.n} windows (leaf_fill {args.leaf_fill}) in "
              f"{time.perf_counter() - t0:.2f}s")

    view.reset()
    t0 = time.perf_counter()
    res = engine.topk(Q, k=args.k, exclusion=args.exclusion,
                      explain=args.explain)
    dt = time.perf_counter() - t0
    if args.explain:
        _explain(res.trace, device=args.verify == "device")
    t0 = time.perf_counter()
    scan = engine.scan_topk(Q, k=args.k, use_kernel=False)
    dt_scan = time.perf_counter() - t0
    hits = sum(int(res.window_ids[qi, 0] == scan.window_ids[qi, 0])
               for qi in range(args.queries))
    loc = sum(int(res.rows[qi, 0] == q_rows[qi]
                  and abs(res.starts[qi, 0] - offs[qi]) < m)
              for qi in range(args.queries))
    print(f"[subseq] exact k={args.k}"
          + (f" excl={args.exclusion}" if args.exclusion else "")
          + f": top-1 == scan {hits}/{args.queries}, snippet localized "
          f"{loc}/{args.queries}; windows/query "
          f"{res.raw_accesses.mean():.0f} "
          f"({1 - res.pruned_fraction.mean():.2%} of {view.n}); "
          f"rows read {res.store_accesses}/{view.n_rows}; modeled "
          f"{args.store} I/O {res.io_seconds * 1e3:.2f}ms vs scan "
          f"{scan.io_seconds * 1e3:.2f}ms "
          f"({scan.io_seconds / max(res.io_seconds, 1e-12):.1f}x); "
          f"wall {dt:.2f}s (scan {dt_scan:.2f}s)")

    if args.index:
        # cold-cache boundary: the indexed run above left its I/O counts
        # and a warm row buffer behind, which used to bleed into (and
        # under-report) the linear comparison below
        view.reset()
        lin = engine.topk(Q, k=args.k, exclusion=args.exclusion,
                          use_index=False, explain=args.explain)
        if args.explain:
            _explain(lin.trace, device=args.verify == "device")
        agree = int(np.array_equal(res.window_ids, lin.window_ids))
        print(f"[subseq] index vs linear sweep: bitwise identical "
              f"{'yes' if agree else 'NO'}; windows examined/query "
              f"{res.raw_accesses.mean():.0f} (indexed) vs "
              f"{lin.raw_accesses.mean():.0f} (linear) of {view.n}")

    # streaming: new long series are searchable immediately
    extra = season_dataset(2, args.T, args.L, args.strength, seed=8)
    t0 = time.perf_counter()
    view.append(extra)
    print(f"[subseq] append 2 rows (+{2 * view.windows_per_row} windows) "
          f"in {(time.perf_counter() - t0) * 1e3:.0f}ms; corpus "
          f"{view.n_rows} rows / {view.n} windows")
    o2 = min(100, args.T - m)
    res2 = engine.topk(extra[:1, o2:o2 + m], k=1)
    print(f"[subseq] query of appended row -> row {res2.rows[0, 0]} "
          f"start {res2.starts[0, 0]} d={res2.distances[0, 0]:.4f}")
    if args.explain:
        _print_metrics(REGISTRY)


def run_selfjoin(args):
    """Self-join mode: compute the corpus matrix profile exactly
    (``repro.profile.SelfJoinEngine``), report top-k motifs and
    discords, and check them bit-identical against the brute-force
    profile oracle.  A motif pair and a discord are planted into the
    synthetic corpus so the answer is visibly right."""
    import jax
    import numpy as np

    from repro.core import make_technique
    from repro.data.synthetic import season_dataset
    from repro.obs import REGISTRY
    from repro.profile import SelfJoinEngine, topk_discords, topk_motifs
    from repro.subseq import WindowView

    m, s = args.window, args.stride
    if m % args.L:
        raise SystemExit(f"--window {m} must be a multiple of --L {args.L}")
    if m > args.T:
        raise SystemExit(f"--window {m} longer than --T {args.T}")
    tech = make_technique(args.technique, T=m, W=m // args.L, L=args.L,
                          r2_season=args.strength)

    mesh = None
    if args.verify == "device":
        from repro.launch.mesh import make_mesh_compat
        mesh = make_mesh_compat((len(jax.devices()),), ("data",))
        print(f"[selfjoin] device-resident verification over "
              f"{len(jax.devices())} devices")

    rng = np.random.default_rng(17)
    D = np.array(season_dataset(args.n, args.T, args.L, args.strength,
                                per_series_strength=True, seed=17))
    # plant a motif (one snippet duplicated across two rows) and a
    # discord (one burst unlike anything else) to make the self-join's
    # answer checkable by eye
    snippet = np.sin(np.linspace(0, 6 * np.pi, m)).astype(np.float32)
    o = (args.T - m) // 2
    D[0, o:o + m] = snippet + 0.01 * rng.normal(size=m)
    D[1, o:o + m] = snippet + 0.01 * rng.normal(size=m)
    D[2, o:o + m] += 6.0 * np.hanning(m).astype(np.float32)

    t0 = time.perf_counter()
    view = WindowView(tech, D, stride=s, media=args.store)
    print(f"[selfjoin] {args.technique} over {args.n} x {args.T} "
          f"-> {view.n} windows (m={m}, stride={s}); "
          f"encode {time.perf_counter() - t0:.2f}s")
    if args.index:
        view.build_index(leaf_fill=args.leaf_fill)
        print(f"[selfjoin] window index: {view.index.n_nodes} nodes")
    excl = args.exclusion if args.exclusion > 0 else None
    engine = SelfJoinEngine(view, batch_size=args.batch,
                            verify=args.verify, mesh=mesh,
                            exclusion=excl, metrics=REGISTRY)

    view.reset()
    t0 = time.perf_counter()
    prof = engine.profile(explain=args.explain)
    dt = time.perf_counter() - t0
    if args.explain:
        _explain(prof.trace, device=args.verify == "device")
    motifs = topk_motifs(prof, view.locate, args.k)
    discords = topk_discords(prof, view.locate, args.k)

    t0 = time.perf_counter()
    oracle = engine.scan_profile()
    dt_scan = time.perf_counter() - t0
    same = (np.array_equal(prof.distances, oracle.distances)
            and np.array_equal(prof.neighbors, oracle.neighbors))
    print(f"[selfjoin] profile over {prof.n} windows "
          f"(exclusion {prof.exclusion} samples, source {prof.source}): "
          f"bitwise == oracle {'yes' if same else 'NO'}; "
          f"windows verified/query {prof.raw_accesses.mean():.0f} "
          f"({1 - prof.pruned_fraction.mean():.2%} of {prof.n}); modeled "
          f"{args.store} I/O {prof.io_seconds * 1e3:.2f}ms vs scan "
          f"{oracle.io_seconds * 1e3:.2f}ms; wall {dt:.2f}s "
          f"(scan {dt_scan:.2f}s)")
    if not same:
        raise SystemExit("[selfjoin] profile diverged from the "
                         "brute-force oracle")
    rows, starts = view.locate(np.asarray([p[0] for p in motifs]))
    for i, (a, b, d) in enumerate(motifs):
        ra, sa = view.locate(np.asarray([a]))
        rb, sb = view.locate(np.asarray([b]))
        print(f"[selfjoin] motif {i + 1}: row {ra[0]}@{sa[0]} ~ "
              f"row {rb[0]}@{sb[0]} d={d:.4f}")
    for i, (w, d) in enumerate(discords):
        r, st = view.locate(np.asarray([w]))
        print(f"[selfjoin] discord {i + 1}: row {r[0]}@{st[0]} d={d:.4f}")
    if motifs:
        ra, _ = view.locate(np.asarray([motifs[0][0]]))
        rb, _ = view.locate(np.asarray([motifs[0][1]]))
        planted = {int(ra[0]), int(rb[0])} == {0, 1}
        print(f"[selfjoin] planted motif recovered: "
              f"{'yes' if planted else 'NO'}")
    if discords:
        r, _ = view.locate(np.asarray([discords[0][0]]))
        print(f"[selfjoin] planted discord recovered: "
              f"{'yes' if int(r[0]) == 2 else 'NO'}")
    if args.explain:
        _print_metrics(REGISTRY)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=20_000)
    ap.add_argument("--T", type=int, default=960)
    ap.add_argument("--L", type=int, default=10)
    ap.add_argument("--strength", type=float, default=0.7)
    ap.add_argument("--technique", default="ssax",
                    choices=["sax", "ssax", "tsax", "stsax"])
    ap.add_argument("--queries", type=int, default=8)
    ap.add_argument("--k", type=int, default=32)
    ap.add_argument("--batch", type=int, default=256,
                    help="verification batch per query per round")
    ap.add_argument("--store", default="ssd", choices=["hdd", "ssd", "hbm"])
    ap.add_argument("--verify", default="auto",
                    choices=["auto", "numpy", "kernel", "host", "device"],
                    help="raw verification path: 'device' shards the raw "
                    "rows across devices and verifies through the euclid "
                    "kernel without moving a row to the host; 'host' is "
                    "the bit-identical host fallback (store fetch + the "
                    "same kernel math, modeled-I/O oracle)")
    ap.add_argument("--ingest", type=int, default=0,
                    help="chunks to append while serving (ingest demo)")
    ap.add_argument("--ingest-rows", type=int, default=1024,
                    help="rows per ingest chunk")
    ap.add_argument("--snapshot-dir", default="",
                    help="persist the store (raw + rep) after the run")
    ap.add_argument("--index", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="build the split-tree index and serve "
                    "index-accelerated exact queries (--no-index: linear "
                    "sweep only)")
    ap.add_argument("--leaf-fill", type=int, default=64,
                    help="index leaf fill factor (split threshold)")
    ap.add_argument("--subseq", action="store_true",
                    help="subsequence matching over long series")
    ap.add_argument("--selfjoin", action="store_true",
                    help="matrix-profile self-join: exact per-window "
                    "nearest non-trivial neighbors, top-k motifs and "
                    "discords, checked bitwise against the brute-force "
                    "profile oracle")
    ap.add_argument("--window", type=int, default=240,
                    help="subsequence window length m (encoder T)")
    ap.add_argument("--stride", type=int, default=4,
                    help="window hop in samples")
    ap.add_argument("--exclusion", type=int, default=0,
                    help="non-overlap suppression distance (0: off)")
    ap.add_argument("--explain", action="store_true",
                    help="print a per-query plan (spans, candidates, "
                    "pruning, I/O, rounds) for every served path and "
                    "hard-fail if required spans are missing or a "
                    "device-path transfer invariant is violated")
    ap.add_argument("--dryrun", action="store_true",
                    help="shrink every dimension to a seconds-scale "
                    "smoke (the CI explain gate)")
    args = ap.parse_args()

    if args.dryrun:
        windowed = args.subseq or args.selfjoin
        args.n = min(args.n, 12 if windowed else 256)
        args.T = min(args.T, 480)
        args.queries = min(args.queries, 4)
        args.k = min(args.k, 8)
        args.batch = min(args.batch, 64)
        args.ingest = min(args.ingest, 1)
        if windowed:
            args.window = min(args.window, 240)
            args.stride = max(args.stride, 8)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.selfjoin:
        args.k = min(args.k, 4)       # motif/discord count, not top-k
        return run_selfjoin(args)
    if args.subseq:
        return run_subseq(args)

    import jax
    import jax.numpy as jnp

    from repro.core.distributed import make_engine_service
    from repro.core.matching import pairwise_euclidean
    from repro.data.synthetic import season_dataset
    from repro.launch.mesh import make_mesh_compat
    from repro.obs import REGISTRY

    n_dev = len(jax.devices())
    mesh = make_mesh_compat((n_dev,), ("data",))
    n = (args.n // n_dev) * n_dev
    n_ingest = args.ingest * args.ingest_rows
    X = season_dataset(n + args.queries + n_ingest, args.T, args.L,
                       args.strength, per_series_strength=True, seed=1)
    Q, D = X[:args.queries], X[args.queries:args.queries + n]
    ingest_pool = X[args.queries + n:]

    from repro.core import make_technique
    tech = make_technique(args.technique, T=args.T, W=48, L=args.L,
                          r2_season=args.strength)

    print(f"[match] {args.technique} over {n} x {args.T} "
          f"on {n_dev} devices (verify={args.verify})")
    t0 = time.perf_counter()
    # the host array goes in as is: the service shards it row-wise onto
    # the mesh, never through one device
    engine = make_engine_service(tech, D, mesh,
                                 batch_size=args.batch, media=args.store,
                                 verify=args.verify, metrics=REGISTRY)
    store = engine.store                 # SymbolicStore: raw + live rep
    print(f"[match] encode: {time.perf_counter() - t0:.2f}s")

    ed = np.asarray(pairwise_euclidean(jnp.asarray(Q), jnp.asarray(D)))
    true_nn = np.argsort(ed, axis=1, kind="stable")

    # exact top-k through the pruned batched scan
    for k in (1, args.k):
        store.reset()
        t0 = time.perf_counter()
        res = engine.topk(Q, k=k, explain=args.explain)
        dt = time.perf_counter() - t0
        if args.explain:
            _explain(res.trace, device=args.verify == "device")
        hits = sum(int(np.array_equal(res.indices[qi],
                                      true_nn[qi, :k]))
                   for qi in range(args.queries))
        acc = res.raw_accesses.mean()
        print(f"[match] exact k={k}: {hits}/{args.queries} query frontiers "
              f"== brute force; raw rows/query {acc:.0f} "
              f"({acc / n:.2%} of dataset), {res.store_fetches} batched "
              f"fetches; modeled {args.store} I/O {res.io_seconds:.3f}s; "
              f"wall {dt:.2f}s")

    # index-accelerated exact top-k: the split tree generates a compact
    # candidate set instead of the linear sweep — bit-identical results
    if args.index:
        t0 = time.perf_counter()
        store.build_index(leaf_fill=args.leaf_fill)
        t_build = time.perf_counter() - t0
        store.reset()
        res_lin = engine.topk(Q, k=args.k)
        lin_acc = res_lin.raw_accesses.mean()
        store.reset()
        t0 = time.perf_counter()
        res_idx = engine.topk(Q, k=args.k, source="index",
                              explain=args.explain)
        dt = time.perf_counter() - t0
        if args.explain:
            _explain(res_idx.trace, device=args.verify == "device")
        agree = np.array_equal(res_idx.indices, res_lin.indices)
        print(f"[match] index: {store.index.n_nodes} nodes over "
              f"{store.index.n} rows (leaf_fill {args.leaf_fill}) in "
              f"{t_build:.2f}s; indexed k={args.k} bitwise==linear "
              f"{'yes' if agree else 'NO'}; candidates/query "
              f"{res_idx.raw_accesses.mean():.0f} (indexed) vs "
              f"{lin_acc:.0f} (linear) of {n}; wall {dt:.2f}s")

    # approximate top-k from the sharded candidate frontier
    store.reset()
    t0 = time.perf_counter()
    res = engine.topk(Q, k=args.k, exact=False, explain=args.explain)
    dt = time.perf_counter() - t0
    if args.explain:
        _explain(res.trace, device=args.verify == "device")
    hit1 = sum(int(res.indices[qi, 0] == true_nn[qi, 0])
               for qi in range(args.queries))
    print(f"[match] approx k={args.k}: 1-NN hit {hit1}/{args.queries}; "
          f"raw rows/query {res.raw_accesses.mean():.0f}; modeled "
          f"{args.store} I/O {res.io_seconds:.3f}s; wall {dt:.2f}s")

    # ingest-while-serving: append chunks, answer queries between them —
    # only the new chunk is encoded each round
    for c in range(args.ingest):
        chunk = ingest_pool[c * args.ingest_rows:(c + 1) * args.ingest_rows]
        t0 = time.perf_counter()
        engine.ingest(chunk)
        t_ing = time.perf_counter() - t0
        t0 = time.perf_counter()
        res = engine.topk(Q, k=args.k, exact=False)
        t_q = time.perf_counter() - t0
        print(f"[match] ingest {c + 1}/{args.ingest}: +{chunk.shape[0]} "
              f"rows in {t_ing * 1e3:.0f}ms "
              f"({chunk.shape[0] / max(t_ing, 1e-9):.0f} rows/s), corpus "
              f"{store.n}; query k={args.k} under ingest {t_q * 1e3:.0f}ms")

    # the index was maintained incrementally through every ingest —
    # indexed queries stay exact with no rebuild
    if args.index and args.ingest:
        assert store.index is not None and store.index.n == store.n
        res_idx = engine.topk(Q, k=args.k, source="index")
        res_lin = engine.topk(Q, k=args.k)
        agree = np.array_equal(res_idx.indices, res_lin.indices)
        print(f"[match] index after {args.ingest} ingests: covers "
              f"{store.index.n} rows without rebuild; bitwise==linear "
              f"{'yes' if agree else 'NO'}")

    if args.snapshot_dir:
        t0 = time.perf_counter()
        path = store.save(args.snapshot_dir)
        print(f"[match] snapshot: {store.n} rows + rep -> {path} "
              f"({time.perf_counter() - t0:.2f}s)")

    if args.explain:
        _print_metrics(REGISTRY)


if __name__ == "__main__":
    main()

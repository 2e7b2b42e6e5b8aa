"""Always-on matching service launcher.

    PYTHONPATH=src python -m repro.launch.serve_match \
        --n 40000 --technique ssax --clients 32 --k 8 --window-ms 2

Builds the sharded device-resident matching engine
(``core.distributed.make_engine_service``) with its split-tree index,
wraps it in a :class:`repro.service.MatchSession` — the coalescing
queue front-end plus the telemetry-driven query planner — and drives
it with ``--clients`` concurrent threads submitting single-query
requests.  The run demonstrates the service contract end to end:

* coalescing: waiting requests batch into one (Q, T) engine dispatch;
  the run reports requests-per-dispatch and the latency/QPS effect.
* exactness: planner-routed exact answers are checked bit-identical
  to a direct ``engine.topk`` oracle for every request.
* deadlines: a second wave runs under a tight per-request budget —
  deadline-threatened requests downgrade to the anytime tier and come
  back with an error bar instead of being shed.
* ``--explain`` renders the per-dispatch plan trace
  (``repro.obs.render_trace``) for the first request of each tier and
  validates it (device invariants included under ``--verify device``).
* ``--replicas N`` serves through N engine replicas over the ONE
  shared store (per-replica dispatch workers, planner-EWMA placement);
  ``--ingest-while-serving`` runs a writer thread appending rows
  throughout wave 1 — every request is pinned to its admission-time
  corpus epoch and the oracle check compares against a store truncated
  there, so exactness holds mid-ingest.

``--dryrun`` shrinks everything to a seconds-scale smoke (the CI
path).
"""

from __future__ import annotations

import argparse
import threading
import time

import numpy as np


def _percentile(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if len(xs) \
        else float("nan")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=20_000)
    ap.add_argument("--T", type=int, default=960)
    ap.add_argument("--L", type=int, default=10)
    ap.add_argument("--strength", type=float, default=0.7)
    ap.add_argument("--technique", default="ssax",
                    choices=["sax", "ssax", "tsax", "stsax"])
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--clients", type=int, default=32,
                    help="concurrent client threads")
    ap.add_argument("--requests", type=int, default=4,
                    help="requests per client per wave")
    ap.add_argument("--window-ms", type=float, default=2.0,
                    help="coalescing window")
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--deadline-ms", type=float, default=5.0,
                    help="per-request budget for the deadline wave")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--store", default="ssd",
                    choices=["hdd", "ssd", "hbm"])
    ap.add_argument("--verify", default="auto",
                    choices=["auto", "numpy", "kernel", "host", "device"])
    ap.add_argument("--leaf-fill", type=int, default=64)
    ap.add_argument("--explain", action="store_true",
                    help="render + validate one dispatch trace per tier")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas over the shared store")
    ap.add_argument("--ingest-while-serving", action="store_true",
                    help="append rows concurrently with wave 1; "
                         "answers stay exact at their pinned epochs")
    ap.add_argument("--dryrun", action="store_true",
                    help="seconds-scale smoke (the CI path)")
    args = ap.parse_args()

    if args.dryrun:
        args.n = min(args.n, 256)
        args.T = min(args.T, 480)
        args.clients = min(args.clients, 8)
        args.requests = min(args.requests, 2)
        args.k = min(args.k, 4)
        args.batch = min(args.batch, 64)
        args.leaf_fill = min(args.leaf_fill, 16)

    import jax

    from repro.core import make_technique
    from repro.core.distributed import make_engine_service
    from repro.data.synthetic import season_dataset
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh_compat
    from repro.obs import REGISTRY
    from repro.service import MatchSession
    from repro.service.queue import SHED_ENGINE_ERROR

    enable_compile_cache()

    n_dev = len(jax.devices())
    mesh = make_mesh_compat((n_dev,), ("data",))
    n = max((args.n // n_dev) * n_dev, n_dev)
    n_q = args.clients * args.requests
    n_ingest = (max(n // 8, n_dev) // n_dev) * n_dev \
        if args.ingest_while_serving else 0
    X = season_dataset(n + n_q + n_ingest, args.T, args.L,
                       args.strength, per_series_strength=True, seed=11)
    Q, D = X[:n_q], X[n_q:n_q + n]
    D_ingest = X[n_q + n:]
    tech = make_technique(args.technique, T=args.T, W=48, L=args.L,
                          r2_season=args.strength)

    print(f"[serve] {args.technique} over {n} x {args.T} on {n_dev} "
          f"devices (verify={args.verify})")
    t0 = time.perf_counter()
    # the host array goes in as is: the service shards it row-wise onto
    # the mesh, never through one device
    engine = make_engine_service(tech, D, mesh,
                                 batch_size=args.batch, media=args.store,
                                 verify=args.verify, metrics=REGISTRY)
    engine.store.build_index(leaf_fill=args.leaf_fill)
    # replicas share the ONE store (dataset=None adopts it); each keeps
    # its own device mirrors, synced independently by store version
    replicas = [make_engine_service(tech, None, mesh,
                                    store=engine.store,
                                    batch_size=args.batch,
                                    media=args.store,
                                    verify=args.verify)
                for _ in range(max(args.replicas, 1) - 1)]
    print(f"[serve] engine + index ready in "
          f"{time.perf_counter() - t0:.2f}s"
          + (f" ({args.replicas} replicas)" if replicas else ""))

    session = MatchSession(engine, replicas=replicas, metrics=REGISTRY,
                           window_s=args.window_ms * 1e-3,
                           max_batch=args.max_batch,
                           max_queue=max(4 * n_q, 256)).start()
    cal = session.calibrate(Q[:1], k=args.k)
    print("[serve] planner calibration: "
          + ", ".join(f"{t} {e['wall_s'] * 1e3:.1f}ms" for t, e in
                      cal.items()))

    # -- wave 1: concurrent exact serving + bit-identity oracle ----------
    # (with --ingest-while-serving a writer appends rows throughout;
    # requests stay exact at their admission-pinned corpus epochs)
    results = [None] * n_q
    writer_stop = threading.Event()

    def client(cid):
        for j in range(args.requests):
            i = cid * args.requests + j
            req = session.submit(Q[i], k=args.k,
                                 explain=args.explain and i == 0)
            req.wait(120)
            results[i] = req

    def writer():
        chunk = max(n_dev, len(D_ingest) // 16)
        for lo in range(0, len(D_ingest), chunk):
            if writer_stop.is_set():
                break
            engine.ingest(D_ingest[lo:lo + chunk])
            time.sleep(0.001)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(c,))
               for c in range(args.clients)]
    wt = None
    if args.ingest_while_serving:
        wt = threading.Thread(target=writer)
        wt.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if wt is not None:
        writer_stop.set()
        wt.join()
    wall = time.perf_counter() - t0

    ok = [r for r in results if r is not None and r.ok]
    lat = [r.latency_s for r in ok]
    snap = REGISTRY.snapshot()["counters"]
    batches = snap.get("serve.batches", 0)
    batched = snap.get("serve.batched_requests", 0)
    tiers = {}
    for r in ok:
        tiers[r.tier_served] = tiers.get(r.tier_served, 0) + 1
    print(f"[serve] wave 1: {len(ok)}/{n_q} served in {wall:.2f}s "
          f"({len(ok) / max(wall, 1e-9):.0f} QPS); p50 "
          f"{_percentile(lat, 50) * 1e3:.1f}ms p99 "
          f"{_percentile(lat, 99) * 1e3:.1f}ms; "
          f"{batched / max(batches, 1):.1f} requests/dispatch; "
          f"tiers {tiers}")
    if args.ingest_while_serving:
        epochs = sorted({r.epoch.n_rows for r in ok
                         if r.epoch is not None})
        print(f"[serve] ingested to {engine.store.n} rows during "
              f"wave 1; answers pinned across {len(epochs)} epochs "
              f"({epochs[0] if epochs else 0}.."
              f"{epochs[-1] if epochs else 0} rows)")
    if args.replicas > 1:
        by_rep = {}
        for r in ok:
            by_rep[r.replica] = by_rep.get(r.replica, 0) + 1
        print(f"[serve] replica placement: {by_rep}")

    unserved = [r for r in results if r is None or not r.ok]
    for r in unserved[:4]:
        print(f"[serve] unserved: "
              f"{'no answer' if r is None else (r.shed_reason, r.error)}")
    if unserved:
        raise SystemExit(f"[serve] {len(unserved)}/{n_q} wave-1 requests "
                         "were not served")

    mism = 0
    for r in ok:
        if r.tier_served == "approx":
            continue
        # the oracle answers at the request's PINNED epoch — under
        # --ingest-while-serving the live corpus has moved on, and
        # bit-identity is defined against the admission frontier
        oracle = engine.topk(
            r.query[None], k=r.k,
            source="index" if r.tier_served == "index" else None,
            epoch=r.epoch)
        if not (np.array_equal(r.indices, oracle.indices[0])
                and np.array_equal(r.distances, oracle.distances[0])):
            mism += 1
    exact_n = sum(1 for r in ok if r.tier_served != "approx")
    print(f"[serve] exact-tier bit-identity vs direct topk: "
          f"{exact_n - mism}/{exact_n}")
    if mism:
        raise SystemExit("[serve] exact-tier answers diverged from the "
                         "direct engine oracle")

    if args.explain and results[0] is not None \
            and results[0].trace is not None:
        from repro.launch.match import _explain
        _explain(results[0].trace, device=args.verify == "device")

    # -- wave 2: tight deadlines -> anytime downgrade + error bars -------
    reqs = session.serve(Q[:args.clients], k=args.k,
                         deadline_s=args.deadline_ms * 1e-3,
                         timeout=120.0)
    served = [r for r in reqs if r.ok]
    down = [r for r in served if r.plan is not None and r.plan.downgraded]
    bars = [r.error_bar for r in served if r.error_bar is not None]
    shed = [r for r in reqs if not r.ok]
    print(f"[serve] wave 2 (deadline {args.deadline_ms:.1f}ms): "
          f"{len(served)}/{len(reqs)} served, {len(down)} downgraded to "
          f"approx, {len(shed)} shed; error bar mean "
          f"{np.mean(bars) if bars else 0.0:.4f} "
          f"({sum(1 for b in bars if b == 0)}/{len(bars)} provably exact)")

    session.close()
    failed = [r for r in reqs if r.shed_reason == SHED_ENGINE_ERROR]
    if failed:
        raise SystemExit(f"[serve] {len(failed)} wave-2 requests shed as "
                         f"{SHED_ENGINE_ERROR}: {failed[0].error}")
    from repro.launch.match import _print_metrics
    _print_metrics(REGISTRY)
    print("[serve] planner estimates: "
          + ", ".join(f"{t} {e['wall_s'] * 1e3:.1f}ms (n={e['n_obs']})"
                      for t, e in session.planner.snapshot().items()))


if __name__ == "__main__":
    main()

"""Observability neutrality: tracing MUST be a pure observer.

For every encoder (SAX / sSAX / tSAX / stSAX), candidate source (linear
sweep / split-tree index) and verification path (host / device), running
the same query batch with ``explain=True`` must be bit-identical to the
untraced run — identical result ids AND distances, identical per-query
raw-access counts, identical store accounting (accesses / fetches /
modeled I/O).  Whole-series (``MatchEngine``) and subsequence
(``SubseqEngine``) stacks are both covered.

This is the property the zero-overhead-when-off design rests on: every
instrumentation site only *reads* engine state after the computation,
so turning tracing on cannot change what the engine does — only what it
reports.  The traced run must additionally produce a well-formed trace
(required spans present, rounds recorded, device transfer invariants
zero) and a JSON-serializable export.
"""

import json

import numpy as np
import pytest

from repro.core import MatchEngine, make_technique
from repro.data.synthetic import season_dataset
from repro.obs import check_trace
from repro.store import SymbolicStore

L = 10
TECHS = ["sax", "ssax", "tsax", "stsax"]


def _enc(name, T):
    kw = {"sax": {}, "ssax": {"r2_season": 0.7},
          "tsax": {"r2_trend": 0.3}, "stsax": {"r2_season": 0.5}}[name]
    return make_technique(name, T=T, W=T // (2 * L), L=L, **kw)


def _mesh1():
    from repro.launch.mesh import make_mesh_compat
    return make_mesh_compat((1,), ("data",))


def _fingerprint(res, store):
    ids = res.indices if hasattr(res, "indices") else res.window_ids
    return {
        "ids": np.asarray(ids).copy(),
        "distances": np.asarray(res.distances).copy(),
        "raw_accesses": np.asarray(res.raw_accesses).copy(),
        "store_accesses": int(res.store_accesses),
        "store_fetches": int(res.store_fetches),
        "io_seconds": float(res.io_seconds),
        "accesses": int(store.accesses),
        "fetches": int(store.fetches),
    }


def _assert_identical(base, traced, label):
    for key in base:
        a, b = base[key], traced[key]
        assert np.array_equal(a, b), (
            f"{label}: tracing changed {key}: {a!r} != {b!r}")


def _check(trace, *, device):
    problems = check_trace(trace, device=device)
    assert problems == [], problems
    json.dumps(trace.to_dict())


@pytest.mark.parametrize("tech", TECHS)
def test_match_engine_neutral_all_paths(tech):
    T, n, n_q, k = 240, 64, 3, 4
    X = season_dataset(n + n_q, T, L, 0.7, per_series_strength=True,
                       seed=5)
    Q, D = X[:n_q], X[n_q:]
    enc = _enc(tech, T)

    store = SymbolicStore.from_rows(enc, D, media="ssd")
    store.build_index(leaf_fill=16)
    host = MatchEngine(enc, store, verify="host", batch_size=32)

    import jax.numpy as jnp
    from repro.core.distributed import make_engine_service
    dev = make_engine_service(_enc(tech, T), jnp.asarray(D), _mesh1(),
                              batch_size=32, verify="device")
    dev.store.build_index(leaf_fill=16)

    for engine, verify in ((host, "host"), (dev, "device")):
        for source in (None, "index"):
            label = f"{tech}/{verify}/{source or 'linear'}"
            engine.store.reset()
            base = _fingerprint(engine.topk(Q, k=k, source=source),
                                engine.store)
            engine.store.reset()
            res = engine.topk(Q, k=k, source=source, explain=True)
            _assert_identical(base, _fingerprint(res, engine.store),
                              label)
            # the device engine's linear sweep runs its rounds as one
            # device program: neutrality holds on that path too
            if source is None:
                assert res.device_loop == (verify == "device"), label
                assert res.trace.get("device_loop") == int(res.device_loop)
            _check(res.trace, device=(verify == "device"))
            # replaying untraced after the traced run is unchanged too
            engine.store.reset()
            again = _fingerprint(engine.topk(Q, k=k, source=source),
                                 engine.store)
            _assert_identical(base, again, label + "/replay")


@pytest.mark.parametrize("tech", TECHS)
def test_subseq_engine_neutral_all_paths(tech):
    from repro.subseq import SubseqEngine, WindowView
    n, T, m, stride, k, n_q = 6, 360, 120, 6, 3, 2
    rng = np.random.default_rng(9)
    D = season_dataset(n, T, L, 0.7, per_series_strength=True, seed=9)
    q_rows = rng.integers(0, n, size=n_q)
    offs = rng.integers(0, T - m, size=n_q)
    Q = np.stack([D[r, o:o + m] for r, o in zip(q_rows, offs)])
    Q = Q + 0.05 * rng.normal(size=Q.shape).astype(np.float32)
    enc = _enc(tech, m)

    view = WindowView(enc, D, stride=stride, media="ssd")
    view.build_index(leaf_fill=16)
    engines = {"host": SubseqEngine(view, verify="host", batch_size=64),
               "device": SubseqEngine(view, mesh=_mesh1(),
                                      verify="device", batch_size=64)}

    for verify, eng in engines.items():
        for use_index in (False, True):
            label = f"{tech}/{verify}/{'index' if use_index else 'linear'}"
            view.reset()
            base = _fingerprint(eng.topk(Q, k=k, use_index=use_index),
                                view)
            view.reset()
            res = eng.topk(Q, k=k, use_index=use_index, explain=True)
            _assert_identical(base, _fingerprint(res, view), label)
            _check(res.trace, device=(verify == "device"))


def test_metrics_registry_is_neutral_too():
    """Attaching a MetricsRegistry (without tracing) must not change
    results or store accounting either — metrics recording reads the
    same post-hoc state traces do."""
    from repro.obs import MetricsRegistry
    T, n, n_q, k = 240, 48, 2, 3
    X = season_dataset(n + n_q, T, L, 0.7, seed=11)
    Q, D = X[:n_q], X[n_q:]
    enc = _enc("ssax", T)
    store = SymbolicStore.from_rows(enc, D, media="ssd")
    plain = MatchEngine(enc, store, verify="host", batch_size=32)
    store.reset()
    base = _fingerprint(plain.topk(Q, k=k), store)

    reg = MetricsRegistry()
    observed = MatchEngine(enc, store, verify="host", batch_size=32,
                           metrics=reg)
    store.reset()
    _assert_identical(base, _fingerprint(observed.topk(Q, k=k), store),
                      "metrics-attached")
    snap = reg.snapshot()
    assert snap["counters"]["match.queries"] == n_q
    assert snap["counters"]["match.rows_fetched"] == base["accesses"]
    assert snap["histograms"]["match.topk_latency_s"]["count"] == 1


@pytest.mark.parametrize("verify", ["host", "device"])
def test_session_answers_neutral_to_explain(verify):
    """Requests served through ``MatchSession`` get bit-identical answers
    and store accounting with ``explain`` on and off: the dispatch span
    tree, its per-round spans and their profiler annotations only
    observe."""
    from repro.service import MatchSession
    T, n, n_q, k = 240, 64, 5, 4
    X = season_dataset(n + n_q, T, L, 0.7, per_series_strength=True,
                       seed=13)
    Q, D = X[:n_q], X[n_q:]
    if verify == "host":
        store = SymbolicStore.from_rows(_enc("ssax", T), D, media="ssd")
        store.build_index(leaf_fill=16)
        engine = MatchEngine(_enc("ssax", T), store, verify="host",
                             batch_size=32)
    else:
        import jax.numpy as jnp
        from repro.core.distributed import make_engine_service
        engine = make_engine_service(_enc("ssax", T), jnp.asarray(D),
                                     _mesh1(), batch_size=32,
                                     verify="device")
        engine.store.build_index(leaf_fill=16)

    def serve(tier, explain):
        engine.store.reset()
        sess = MatchSession(engine, window_s=0.05, max_batch=8)
        reqs = [sess.submit(q, k=k, tier=tier, explain=explain)
                for q in Q]
        sess.start()
        for r in reqs:
            assert r.wait(120) and r.ok, r.error
        sess.close()
        return reqs, (engine.store.accesses, engine.store.fetches)

    for tier in ("linear", "index"):
        base, acc = serve(tier, False)
        traced, acc_t = serve(tier, True)
        assert acc == acc_t, tier
        for a, b in zip(base, traced):
            assert a.trace is None and b.trace is not None
            assert np.array_equal(a.indices, b.indices), tier
            assert np.array_equal(a.distances, b.distances), tier
            assert a.tier_served == b.tier_served == tier
        _check(traced[0].trace, device=(verify == "device"))


@pytest.mark.parametrize("tech", TECHS)
def test_device_loop_makes_no_round_trips(tech, monkeypatch):
    """On the device loop, tracing on or off, no round calls the
    stream's ``peek``/``take`` or the per-round ``dist_fn``: the host
    launches one program and fetches once, and the answers are those of
    the host round loop over the same stream."""
    import jax.numpy as jnp
    from repro.core import distributed as dist
    T, n, n_q, k = 240, 64, 3, 4
    X = season_dataset(n + n_q, T, L, 0.7, per_series_strength=True,
                       seed=17)
    Q, D = X[:n_q], X[n_q:]
    dev = dist.make_engine_service(_enc(tech, T), jnp.asarray(D), _mesh1(),
                                   batch_size=16, verify="device")
    host = dist.make_engine_service(_enc(tech, T), jnp.asarray(D), _mesh1(),
                                    batch_size=16, verify="host")
    want = host.topk(Q, k=k)

    def refuse(*a, **kw):
        raise AssertionError("a per-round host call on the device loop")

    monkeypatch.setattr(dist.DeviceOrderedStream, "peek", refuse)
    monkeypatch.setattr(dist.DeviceOrderedStream, "take", refuse)
    monkeypatch.setattr(dist, "cand_dists_rows_rr", refuse)
    for explain in (False, True):
        res = dev.topk(Q, k=k, explain=explain)
        assert res.device_loop
        assert np.array_equal(res.indices, want.indices)
        assert np.array_equal(res.distances, want.distances)
        assert np.array_equal(res.raw_accesses, want.raw_accesses)

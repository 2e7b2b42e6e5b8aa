"""The device round loop of exact verification (``verify="device"``,
``core.distributed.verify_stream_rr``) against the host round loop of
``core.engine.topk_verify`` over the same device-ordered stream
(``verify="host"``: store fetch + the same kernel math).

For every encoder, linear and index candidate sources, and k from one to
more than there are candidates, at 1 and 4 (virtual) devices, both must
give identical indices, distances, per-query raw accesses, round counts
and per round the same active queries, rows examined and k-th bests.
Planted ties at the k-th distance, an epoch mask and index seed
frontiers are covered; an ``on_verified`` callback keeps the host loop,
and the engagement counters then read zero.  A row count that is not a
multiple of the shard count runs the device loop too.  The square root
inside the loop rounds as numpy's does, whatever the backend's ``sqrt``
rounds to.

Each device count runs in one subprocess (XLA's device count is
process-global); its results are checked case by case.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
TECHS = ("sax", "ssax", "tsax", "stsax")
SOURCES = ("linear", "index")
KS = (1, 4, "all")
FIELDS = ("indices", "distances", "raw_accesses", "rounds", "active",
          "examined", "kth")

_PRELUDE = """
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core import make_technique
    from repro.core.distributed import make_engine_service
    from repro.data.synthetic import season_dataset
    from repro.index.candidates import LinearSweep, topk_from_source
    from repro.launch.mesh import make_mesh_compat
    from repro.obs import MetricsRegistry
    from repro.service import MatchSession
    from repro.store import SymbolicStore

    S = len(jax.devices())
    mesh = make_mesh_compat((S,), ("data",))
    T, L = 120, 10
    KW = {"sax": {}, "ssax": {"r2_season": 0.7}, "tsax": {"r2_trend": 0.3},
          "stsax": {"r2_season": 0.5}}

    def enc(name):
        return make_technique(name, T=T, W=T // 20, L=L, **KW[name])

    def engines(name, D, batch=8):
        store = SymbolicStore.from_rows(enc(name), D)
        store.build_index(leaf_fill=16)
        return [make_engine_service(enc(name), None, mesh, store=store,
                                    verify=v, batch_size=batch)
                for v in ("device", "host")]

    def rounds(res):
        return [(r["active"], r["examined"], r["kth"].tolist())
                for r in res.trace.rounds]

    def compare(dev, host, Q, k, **kw):
        a = dev.topk(Q, k=k, explain=True, **kw)
        b = host.topk(Q, k=k, explain=True, **kw)
        ra, rb = rounds(a), rounds(b)
        return {
            "loop": [bool(a.device_loop), bool(b.device_loop)],
            "counter": [a.trace.get("device_loop"),
                        b.trace.get("device_loop")],
            "spans": sorted({s.name for s in a.trace.spans}),
            "same": {
                "indices": np.array_equal(a.indices, b.indices),
                "distances": np.array_equal(a.distances, b.distances),
                "raw_accesses": np.array_equal(a.raw_accesses,
                                               b.raw_accesses),
                "rounds": len(ra) == len(rb) and len(ra) > 0,
                "active": [r[0] for r in ra] == [r[0] for r in rb],
                "examined": [r[1] for r in ra] == [r[1] for r in rb],
                "kth": [r[2] for r in ra] == [r[2] for r in rb],
            }}

    # the session's counter: one a dispatch whose rounds ran on device
    def served(engine, Q):
        reg = MetricsRegistry()
        sess = MatchSession(engine, metrics=reg, window_s=0.05,
                            max_batch=8)
        reqs = [sess.submit(q, k=4) for q in Q]
        sess.start()
        assert all(r.wait(120) and r.ok for r in reqs)
        sess.close()
        c = reg.snapshot()["counters"]
        return c.get("serve.device_loop", 0), c.get("serve.batches", 0)
"""

_SCRIPT = """
    out = {}
    for i, name in enumerate(%(techs)r):
        n = 96 + 4 * i                       # a multiple of 1 and 4
        X = season_dataset(n + 5, T, L, 0.7, per_series_strength=True,
                           seed=21 + i)
        Q, D = X[:5], X[5:]
        dev, host = engines(name, D)
        for src in ("linear", "index"):
            for k in (1, 4, "all"):
                kk = n + 10 if k == "all" else k
                out[f"{name}/{src}/{k}"] = compare(
                    dev, host, Q, kk,
                    source=None if src == "linear" else "index")

    # a tie at every distance: each row twice; k odd puts the k-th
    # member's twin just past the cut, decided by the smaller id
    X = season_dataset(53, T, L, 0.7, per_series_strength=True, seed=3)
    D = np.concatenate([X[5:], X[5:]])
    dev, host = engines("ssax", D)
    for k in (1, 3, 7):
        out[f"tie/{k}"] = compare(dev, host, X[:5], k)
        out[f"tie/{k}"]["twins"] = bool(np.any(
            np.abs(np.diff(dev.topk(X[:5], k=k + 1).distances,
                           axis=1)) == 0))

    # an epoch mask: rows past the frontier never reach verification
    X = season_dataset(101, T, L, 0.7, per_series_strength=True, seed=4)
    dev, host = engines("ssax", X[5:])
    out["epoch"] = compare(dev, host, X[:5], 8, epoch=61)
    out["epoch"]["inside"] = bool(
        (dev.topk(X[:5], k=8, epoch=61).indices < 61).all())

    # an on_verified callback keeps the host loop
    seen = []
    res = topk_from_source(
        X[:5], LinearSweep(None, stream_fn=dev.sweep.candidate_stream),
        dev.store, k=8, batch_size=8, merge=dev.merge,
        dist_fn=dev._make_dist_fn(X[:5]),
        on_verified=lambda qi, ids, d: seen.append(len(ids)))
    base = dev.topk(X[:5], k=8)
    out["on_verified"] = {
        "loop": bool(res.device_loop), "calls": len(seen) > 0,
        "same": bool(np.array_equal(res.indices, base.indices)
                     and np.array_equal(res.distances, base.distances))}

    out["session"] = served(dev, X[:5])
    print(json.dumps(out))
"""

# 93 rows: ragged over 2 and 4 shards (the last slot holds 1 row)
_RAGGED = """
    X = season_dataset(98, T, L, 0.7, per_series_strength=True, seed=41)
    out = {}
    for name in %(techs)r:
        dev, host = engines(name, X[5:])
        out[name] = compare(dev, host, X[:5], 8)
        out[name]["session"] = served(dev, X[:5])
    print(json.dumps(out))
"""


def _run(devices: int, body: str = _SCRIPT) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    code = textwrap.dedent(_PRELUDE) + textwrap.dedent(body) % {
        "techs": TECHS}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module", params=[1, 4], ids=["1dev", "4dev"])
def results(request):
    return request.param, _run(request.param)


@pytest.fixture(scope="module", params=[1, 2, 4],
                ids=["1dev", "2dev", "4dev"])
def ragged(request):
    return request.param, _run(request.param, _RAGGED)


def _same(case):
    bad = [f for f in FIELDS if not case["same"][f]]
    assert not bad, f"loop and host loop differ in {bad}"


@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("source", SOURCES)
@pytest.mark.parametrize("tech", TECHS)
def test_loop_equals_host_loop(results, tech, source, k):
    _, out = results
    case = out[f"{tech}/{source}/{k}"]
    # past the corpus size the index's seeds hold every row: no scan
    scan = not (source == "index" and k == "all")
    assert case["loop"] == [scan, False]
    assert case["counter"] == ([1, 0] if scan else [None, None])
    assert ("verify/loop" in case["spans"]) == scan
    assert not {"verify/peek", "verify/take", "verify/dist",
                "verify/merge"} & set(case["spans"])
    _same(case)


@pytest.mark.parametrize("k", [1, 3, 7])
def test_loop_breaks_ties_as_the_host_loop(results, k):
    _, out = results
    case = out[f"tie/{k}"]
    assert case["twins"] and case["loop"] == [True, False]
    _same(case)


def test_loop_honours_the_epoch_mask(results):
    _, out = results
    assert out["epoch"]["inside"] and out["epoch"]["loop"] == [True, False]
    _same(out["epoch"])


def test_on_verified_keeps_the_host_loop(results):
    _, out = results
    case = out["on_verified"]
    assert not case["loop"] and case["calls"] and case["same"]


def test_session_counts_the_loop_dispatches(results):
    _, out = results
    loops, batches = out["session"]
    assert batches >= 1 and loops == batches


def test_host_tail_keeps_the_host_loop(ragged):
    """Rows past the last multiple of the shard count (once a host tail
    that kept the host loop) are on the device: 93 rows over 1, 2 and 4
    shards run the device loop in every dispatch, for every encoder,
    with the host engine's answers and rounds."""
    _, out = ragged
    for tech in TECHS:
        case = out[tech]
        assert case["loop"] == [True, False], tech
        assert case["counter"] == [1, 0], tech
        _same(case)
        loops, batches = case["session"]
        assert batches >= 1 and loops == batches, tech


def test_loop_square_root_rounds_as_numpy():
    """The loop's square root is numpy's (IEEE round-to-nearest) for
    every positive normal float, even from a guess eight ulps off (a
    v5e's own square root is up to three off)."""
    import jax
    import jax.numpy as jnp
    from repro.core.distributed import _sqrt_rn
    rng = np.random.default_rng(7)
    x = np.concatenate([
        rng.integers(0x00800000, 0x7F800000, 200_000,
                     dtype=np.int64).astype(np.uint32).view(np.float32),
        (rng.random(50_000) * 3000).astype(np.float32) ** 2,
        np.float32([1, 2, 3, 4, np.finfo(np.float32).tiny,
                    np.finfo(np.float32).max, 0, np.inf])])
    want = np.sqrt(x).view(np.int32)
    fn = jax.jit(_sqrt_rn)
    assert np.array_equal(np.asarray(fn(jnp.asarray(x))).view(np.int32),
                          want)
    for off in (-8, -3, -1, 1, 3, 8):
        guess = jnp.asarray((want + off).view(np.float32))
        got = np.asarray(fn(jnp.asarray(x), guess)).view(np.int32)
        fin = np.isfinite(x) & (x > 0)
        assert np.array_equal(got[fin], want[fin]), off

"""Device-resident sharded verification (core.distributed): the device
path (``verify="device"``) must be bit-identical to the host fallback
(``verify="host"`` — store fetch + the same kernel distance math) for
every encoder at 1, 2 and 4 mocked hosts, whole-series and windowed,
while moving ZERO raw rows to the host; ingest must keep the raw and
representation mirrors in sync without re-encoding; the device shard
unit must equal the snapshot raw manifest's row ranges.

Runs in a subprocess with 4 placeholder host devices (XLA device count
is process-global) — meshes over 1, 2 and 4 of them mock 1/2/4 hosts.
"""

import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_PRELUDE = """
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core import make_technique
    from repro.data.synthetic import season_dataset
    from repro.launch.mesh import make_mesh_compat

    def encoders(T):
        w = T // 20
        return {
            "sax": make_technique("sax", T=T, W=w, L=10),
            "ssax": make_technique("ssax", T=T, W=w, L=10, r2_season=0.7),
            "tsax": make_technique("tsax", T=T, W=w, L=10, r2_trend=0.3),
            "stsax": make_technique("stsax", T=T, W=w, L=10,
                                    r2_season=0.5),
        }
"""


def _run(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    src = textwrap.dedent(_PRELUDE) + textwrap.dedent(code)
    r = subprocess.run([sys.executable, "-c", src],
                       capture_output=True, text=True, timeout=1800,
                       env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_device_verification_bitwise_equals_host_all_encoders_shards():
    """Whole-series: every encoder x 1/2/4 shards, ragged row counts;
    the device path returns bit-identical (indices AND distances) top-k
    while touching zero host rows, and the device shard unit matches the
    snapshot raw manifest."""
    out = _run("""
        from repro.core import MatchEngine
        from repro.core.distributed import make_engine_service
        from repro.store import SymbolicStore
        from repro.store.snapshot import _shard_ranges

        X = season_dataset(n=53, T=120, L=10, strength=0.7, seed=11)
        Q, D = X[:2], X[2:]                    # 51 rows: ragged at 2 and 4
        for name, enc in encoders(120).items():
            # encode once per encoder (the ingest test covers the
            # sharded-encode path); the host comparison target is the
            # plain SymbolicStore engine (store fetch + same kernel math)
            store = SymbolicStore.from_rows(enc, D)
            host = MatchEngine(enc, store, verify="host", batch_size=64)
            r_h = host.topk(Q, k=5)
            assert r_h.store_accesses > 0
            for shards in (1, 2, 4):
                mesh = make_mesh_compat((shards,), ("data",))
                dev = make_engine_service(enc, None, mesh, store=store,
                                          verify="device", batch_size=64)
                r_d = dev.topk(Q, k=5)
                np.testing.assert_array_equal(r_d.indices, r_h.indices)
                np.testing.assert_array_equal(r_d.distances,
                                              r_h.distances)
                np.testing.assert_array_equal(r_d.raw_accesses,
                                              r_h.raw_accesses)
                assert r_d.store_accesses == 0, (shards, name)
                assert r_d.store_fetches == 0 and r_d.io_seconds == 0.0
                assert r_d.device_loop, (shards, name)
                # every row is on the device, ragged or not;
                # shard_ranges() keeps the snapshot MANIFEST semantics
                # (contiguous on disk) while the device mirror lays
                # rows out round-robin — two independent contracts
                assert dev.sweep.shard_ranges() == \\
                    _shard_ranges(51, shards), (shards, name)
                assert dev.sweep.mirror_layout == "round_robin"
                for s in range(shards):
                    np.testing.assert_array_equal(
                        dev.sweep.owned_rows(s),
                        np.arange(s, 51, shards))
        print("whole-series device==host OK")
    """)
    assert "whole-series device==host OK" in out


def test_device_verification_ingest_and_approx():
    """Ingest keeps BOTH device mirrors (raw + representation) fresh
    without re-encoding: after a ragged append the device path still
    matches the host path bitwise, exact and approximate."""
    out = _run("""
        from repro.core import MatchEngine
        from repro.core.distributed import make_engine_service

        X = season_dataset(n=60, T=240, L=10, strength=0.7, seed=13)
        Q, D, extra = X[:2], X[2:41], X[41:]   # append 19 rows (ragged)
        mesh = make_mesh_compat((4,), ("data",))
        enc = encoders(240)["ssax"]
        dev = make_engine_service(enc, jnp.asarray(D), mesh,
                                  verify="device", batch_size=64)
        host = MatchEngine(enc, dev.store, verify="host", batch_size=64)
        dev.topk(Q, k=3)                       # warm mirrors pre-ingest
        dev.ingest(extra)
        r_d = dev.topk(Q, k=5)
        r_h = host.topk(Q, k=5)
        np.testing.assert_array_equal(r_d.indices, r_h.indices)
        np.testing.assert_array_equal(r_d.distances, r_h.distances)
        assert r_d.store_accesses == 0
        r_da = dev.topk(Q, k=5, exact=False)
        r_ha = host.topk(Q, k=5, exact=False)
        np.testing.assert_array_equal(r_da.indices, r_ha.indices)
        np.testing.assert_array_equal(r_da.distances, r_ha.distances)
        assert r_da.store_accesses == 0
        # indexed exact path, device-resident
        dev.store.build_index(leaf_fill=16)
        r_di = dev.topk(Q, k=5, source="index")
        np.testing.assert_array_equal(r_di.indices, r_d.indices)
        np.testing.assert_array_equal(r_di.distances, r_d.distances)
        assert r_di.store_accesses == 0
        print("ingest + approx + indexed OK")
    """)
    assert "ingest + approx + indexed OK" in out


def test_ingest_tail_rows_encoded_exactly_once():
    """Regression for the remainder-path duplication: ragged ingests
    must run the sharded chunk encode exactly once per ingest (the tail
    is never re-encoded by the sweep), and the stored representation
    stays bitwise-equal to a one-shot host encode."""
    out = _run("""
        from repro.core.distributed import make_engine_service
        from repro.store.symbolic import rep_leaves

        X = season_dataset(n=46, T=240, L=10, strength=0.7, seed=19)
        Q, D1, D2 = X[:2], X[2:25], X[25:]     # 23 + 21 rows, both ragged
        mesh = make_mesh_compat((4,), ("data",))
        enc = encoders(240)["stsax"]
        dev = make_engine_service(enc, None, mesh, batch_size=64)
        calls = []
        orig = dev.sweep._encode_chunk
        dev.sweep._encode_chunk = \\
            lambda rows: (calls.append(rows.shape[0]), orig(rows))[1]
        dev.ingest(D1)
        dev.topk(Q, k=3)                       # sweeps must not re-encode
        dev.ingest(D2)
        dev.topk(Q, k=3)
        dev.topk(Q, k=3, exact=False)
        assert calls == [23, 21], calls
        ref = tuple(np.asarray(l) for l in rep_leaves(
            enc.encode(jnp.asarray(np.concatenate([D1, D2])))))
        for got, want in zip(rep_leaves(dev.store.rep_view()), ref):
            np.testing.assert_array_equal(np.asarray(got), want)
        print("tail encoded once OK")
    """)
    assert "tail encoded once OK" in out


def test_snapshot_contiguous_save_opens_into_round_robin_mirrors():
    """Snapshot layout independence: a store saved with contiguous
    n_hosts=2 shards must open and answer BIT-identically when served
    through the round-robin device mirrors (the on-disk ranges are a
    manifest concept, not a device layout)."""
    out = _run("""
        import tempfile
        from repro.core import MatchEngine
        from repro.core.distributed import make_engine_service
        from repro.store import SymbolicStore

        X = season_dataset(n=41, T=240, L=10, strength=0.7, seed=29)
        Q, D = X[:2], X[2:]                    # 39 rows: ragged at 2/4
        enc = encoders(240)["ssax"]
        with tempfile.TemporaryDirectory() as d:
            SymbolicStore.from_rows(enc, D).save(d, n_hosts=2)
            store = SymbolicStore.open(d)
        host = MatchEngine(enc, store, verify="host", batch_size=64)
        r_h = host.topk(Q, k=5)
        for shards in (2, 4):
            mesh = make_mesh_compat((shards,), ("data",))
            dev = make_engine_service(enc, None, mesh, store=store,
                                      verify="device", batch_size=64)
            assert dev.sweep.mirror_layout == "round_robin"
            r_d = dev.topk(Q, k=5)
            np.testing.assert_array_equal(r_d.indices, r_h.indices)
            np.testing.assert_array_equal(r_d.distances, r_h.distances)
            assert r_d.store_accesses == 0
        print("snapshot layout independence OK")
    """)
    assert "snapshot layout independence OK" in out


def test_sharded_index_build_bitwise_equals_host_build():
    """Sharded bulk index build (device feature extraction + root-subtree
    grouped routing) must produce the identical tree — leaf membership,
    node count — and identical indexed top-k for every encoder, with the
    candidate order generated on device (zero host-ordered bytes)."""
    out = _run("""
        from repro.core import MatchEngine
        from repro.core.distributed import make_engine_service
        from repro.index import SeriesIndex
        from repro.store import SymbolicStore

        X = season_dataset(n=93, T=120, L=10, strength=0.7, seed=31)
        Q, D = X[:2], X[2:]                    # 91 rows, ragged at 4
        mesh = make_mesh_compat((4,), ("data",))
        for name, enc in encoders(120).items():
            store = SymbolicStore.from_rows(enc, D)
            ref = SeriesIndex.from_store(store, leaf_fill=12, max_bits=4)
            host = MatchEngine(enc, store, verify="host", batch_size=64)
            host.store.build_index(leaf_fill=12, max_bits=4)
            r_h = host.topk(Q, k=5, source="index")
            dev = make_engine_service(enc, None, mesh, store=store,
                                      verify="device", batch_size=64)
            idx = dev.store.build_index(leaf_fill=12, max_bits=4,
                                        mesh=mesh, n_shards=4)
            assert idx.n_nodes == ref.n_nodes, name
            assert idx.tree.leaf_membership() == \\
                ref.tree.leaf_membership(), name
            np.testing.assert_array_equal(
                idx.tree.feats, ref.tree.feats)
            r_d = dev.topk(Q, k=5, source="index")
            np.testing.assert_array_equal(r_d.indices, r_h.indices)
            np.testing.assert_array_equal(r_d.distances, r_h.distances)
            assert r_d.store_accesses == 0, name
            assert dev.sweep.host_order_bytes == 0, name
        print("sharded index build OK")
    """)
    assert "sharded index build OK" in out


def test_device_window_verification_bitwise_equals_host():
    """Windowed (--subseq): every encoder x 1/2/4 shards over a ragged
    (stride-indivisible) corpus — sharded window sweep + device window
    verification vs the host fetch path, bit-identical, zero rows moved;
    suppression and the window index ride the same contract."""
    out = _run("""
        from repro.subseq import SubseqEngine, WindowView

        X = season_dataset(n=7, T=610, L=10, strength=0.7, seed=7)
        rng = np.random.default_rng(0)
        Q = np.stack([X[0, 37:157],
                      X[3, 250:370]
                      + 0.1 * rng.normal(size=120).astype(np.float32)])
        for name, enc in encoders(120).items():
            view = WindowView(enc, X, stride=7)   # encoded once per enc
            e_h = SubseqEngine(view, verify="host", batch_size=128)
            view.reset()
            r_h = e_h.topk(Q, k=4)
            assert r_h.store_accesses > 0
            for shards in (1, 2, 4):
                mesh = make_mesh_compat((shards,), ("data",))
                e_d = SubseqEngine(view, verify="device", mesh=mesh,
                                   batch_size=128)
                r_d = e_d.topk(Q, k=4)
                np.testing.assert_array_equal(r_d.window_ids,
                                              r_h.window_ids)
                np.testing.assert_array_equal(r_d.distances,
                                              r_h.distances)
                assert r_d.store_accesses == 0, (shards, name)
        # suppression + index at 2 shards (ssax): same contract
        mesh = make_mesh_compat((2,), ("data",))
        enc = encoders(120)["ssax"]
        view = WindowView(enc, X, stride=7)
        view.build_index(leaf_fill=16)
        e_h = SubseqEngine(view, verify="host", batch_size=128)
        e_d = SubseqEngine(view, verify="device", mesh=mesh,
                           batch_size=128)
        r_h = e_h.topk(Q, k=3, exclusion=60)
        r_d = e_d.topk(Q, k=3, exclusion=60)
        np.testing.assert_array_equal(r_d.window_ids, r_h.window_ids)
        np.testing.assert_array_equal(r_d.distances, r_h.distances)
        assert r_d.store_accesses == 0
        print("windowed device==host OK")
    """)
    assert "windowed device==host OK" in out


def test_round_robin_mirror_ragged_appends_equal_one_shot():
    """Ragged appends of 1, S-1, S+1 and 2S+3 rows in sequence, at 2 and
    4 shards, with and without lane padding: after each one the mirror
    holds every row at shard ``i % S``, slot ``i // S`` (dead slots and
    lane padding zero), the end state is bit-identical to one sync of
    all the rows, and ``h2d_bytes`` counts exactly the rows uploaded:
    the new ones plus those already in the first slot not yet full."""
    out = _run("""
        from repro.core.distributed import RoundRobinMirror

        rng = np.random.default_rng(5)

        def blocks(mir):
            return np.asarray(mir.buf).reshape(
                (mir.n_shards, mir.cap) + mir.buf.shape[1:])

        for shards in (2, 4):
            mesh = make_mesh_compat((shards,), ("data",))
            steps = (1, shards - 1, shards + 1, 2 * shards + 3)
            n = sum(steps)
            cases = {
                "raw": (rng.normal(size=(n, 200)).astype(np.float32), 128),
                "rep": (rng.integers(0, 32, (n, 6)).astype(np.int32), 0),
                "vec": (rng.normal(size=n).astype(np.float32), 0)}
            for name, (X, lanes) in cases.items():
                one = RoundRobinMirror(mesh, shards, lanes=lanes)
                one.sync(X)
                assert one.live == n and one.h2d_bytes == X.nbytes
                assert one.cap == -(-n // shards)
                mir = RoundRobinMirror(mesh, shards, lanes=lanes)
                live = want = 0
                for step in steps:
                    lo = (live // shards) * shards
                    live += step
                    mir.sync(X[:live])
                    mir.sync(X[:live])            # nothing new: no upload
                    want += X[lo:live].nbytes
                    assert mir.live == live, (shards, name, step)
                    assert mir.h2d_bytes == want, (shards, name, step)
                    b = blocks(mir)
                    held = np.zeros_like(b)
                    cols = (slice(0, X.shape[1]),) if X.ndim == 2 else ()
                    for i in range(live):
                        held[(i % shards, i // shards) + cols] = X[i]
                    np.testing.assert_array_equal(b, held)
                b, ref = blocks(mir), blocks(one)
                np.testing.assert_array_equal(b[:, :one.cap], ref)
                assert not b[:, one.cap:].any(), (shards, name)
        print("ragged mirror OK")
    """)
    assert "ragged mirror OK" in out


def test_ragged_ingest_keeps_the_device_loop():
    """After an append that starts mid-slot, the served path keeps the
    device loop in every dispatch (``serve.device_loop`` equals
    ``serve.batches``) and answers bit-identically to the host path, at
    2 and 4 shards; windows over a ragged row count stay bit-identical
    to the host fetch path across such an append."""
    out = _run("""
        from repro.core import MatchEngine
        from repro.core.distributed import make_engine_service
        from repro.obs import MetricsRegistry
        from repro.service import MatchSession
        from repro.subseq import SubseqEngine, WindowView

        X = season_dataset(n=60, T=240, L=10, strength=0.7, seed=17)
        Q, D, extra = X[:4], X[4:41], X[41:59]  # 37 rows, then 18 more
        enc = encoders(240)["ssax"]
        W = season_dataset(n=9, T=610, L=10, strength=0.7, seed=23)
        Qw = np.stack([W[0, 37:157], W[8, 250:370]])
        for shards in (2, 4):
            mesh = make_mesh_compat((shards,), ("data",))
            dev = make_engine_service(enc, jnp.asarray(D), mesh,
                                      verify="device", batch_size=8)
            host = MatchEngine(enc, dev.store, verify="host", batch_size=8)
            assert dev.topk(Q, k=3).device_loop   # mirrors synced at 37
            dev.ingest(extra)
            reg = MetricsRegistry()
            sess = MatchSession(dev, metrics=reg, window_s=0.05,
                                max_batch=4)
            reqs = [sess.submit(q, k=5) for q in Q]
            sess.start()
            assert all(r.wait(120) and r.ok for r in reqs)
            sess.close()
            c = reg.snapshot()["counters"]
            assert c["serve.batches"] >= 1
            assert c.get("serve.device_loop", 0) == c["serve.batches"], c
            r_h = host.topk(Q, k=5)
            for i, r in enumerate(reqs):
                assert r.tier_served == "linear", r.tier_served
                np.testing.assert_array_equal(r.indices, r_h.indices[i])
                np.testing.assert_array_equal(r.distances,
                                              r_h.distances[i])
            assert dev.sweep._raw_mirror.live == 55
            # dead slots stay +inf: exactly the 55 rows are candidates
            assert (dev.sweep.candidate_stream(Q).n_finite == 55).all()
            assert dev.sweep.repr_distances(Q).shape == (4, 55)

            view = WindowView(encoders(120)["ssax"], W[:7], stride=7)
            e_h = SubseqEngine(view, verify="host", batch_size=128)
            e_d = SubseqEngine(view, verify="device", mesh=mesh,
                               batch_size=128)
            for rows in (None, W[7:9]):            # 7 rows, then 9
                if rows is not None:
                    view.append(rows)
                r_h = e_h.topk(Qw, k=4)
                r_d = e_d.topk(Qw, k=4)
                np.testing.assert_array_equal(r_d.window_ids,
                                              r_h.window_ids)
                np.testing.assert_array_equal(r_d.distances,
                                              r_h.distances)
                assert r_d.store_accesses == 0, shards
        print("ragged ingest OK")
    """)
    assert "ragged ingest OK" in out

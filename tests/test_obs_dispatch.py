"""A served dispatch's span tree, its timestamps, its profiler
annotations and the names of the programs it runs.

A traced ``MatchSession`` dispatch records the root span ``dispatch``
(meta ``rids``: the requests it answers), the engine's ``order`` and
``verify`` under it, and under ``verify`` either each host round's
``peek``, ``take``, ``dist`` and ``merge``, or, where the rounds run as
one device program, the one span ``loop``.  Each span is also a
``jax.profiler.TraceAnnotation`` named ``repro/<path>``, which a device
trace keeps on the dispatching thread's host line.  Every request
carries the time its dispatch took it off the queue (``t_dispatch``).
The sharded programs are named for their role, so their compiled
modules are ``jit_<role>``.
"""

import json
import os
import subprocess
import sys
import textwrap
from collections import Counter

import numpy as np
import pytest

from repro.core import MatchEngine, make_technique
from repro.data.synthetic import season_dataset
from repro.service import MatchSession
from repro.store import SymbolicStore

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
L, T = 10, 240
ROUND_STEPS = ("peek", "take", "dist", "merge")


def _enc():
    return make_technique("ssax", T=T, W=T // (2 * L), L=L, r2_season=0.7)


def _data(n=96, n_q=5, seed=5):
    X = season_dataset(n + n_q, T, L, 0.7, per_series_strength=True,
                       seed=seed)
    return X[:n_q], X[n_q:]


def _engine(verify, D):
    if verify == "host":
        store = SymbolicStore.from_rows(_enc(), D, media="ssd")
        return MatchEngine(_enc(), store, verify="host", batch_size=16)
    import jax.numpy as jnp
    from repro.core.distributed import make_engine_service
    from repro.launch.mesh import make_mesh_compat
    return make_engine_service(_enc(), jnp.asarray(D),
                               make_mesh_compat((1,), ("data",)),
                               batch_size=16, verify="device")


def _serve_one_batch(engine, Q, *, k=4, explain=True):
    """Submit every query before the dispatcher starts: one coalesced
    dispatch answers them all."""
    sess = MatchSession(engine, window_s=0.05, max_batch=8)
    reqs = [sess.submit(q, k=k, explain=explain) for q in Q]
    sess.start()
    for r in reqs:
        assert r.wait(120) and r.ok, r.error
    sess.close()
    return reqs


@pytest.mark.parametrize("verify", ["host", "device"])
def test_dispatch_span_tree(verify):
    Q, D = _data()
    reqs = _serve_one_batch(_engine(verify, D), Q)
    tr = reqs[0].trace
    assert all(r.trace is tr for r in reqs)
    names = Counter(s.name for s in tr.spans)
    rounds = len(tr.rounds)
    assert rounds >= 1
    assert names["dispatch"] == names["dispatch/order"] \
        == names["dispatch/verify"] == 1
    if verify == "host":
        for step in ("take", "dist", "merge"):
            assert names[f"dispatch/verify/{step}"] == rounds, step
        # one peek a round, and the closing one that finds none active
        assert names["dispatch/verify/peek"] == rounds + 1
        steps = {f"dispatch/verify/{s}" for s in ROUND_STEPS}
    else:
        # every round in one device program: one span, fenced on the
        # fetched result, and no step spans
        assert names["dispatch/verify/loop"] == 1
        assert tr.get("device_loop") == 1
        steps = {"dispatch/verify/loop"}
    assert set(names) == {"dispatch", "dispatch/order",
                          "dispatch/verify"} | steps
    root = tr.spans[0]
    assert root.name == "dispatch"
    assert root.meta["rids"] == [r.rid for r in reqs]
    # children open and close inside their parent
    by = {s.name: s for s in tr.spans}
    for s in tr.spans[1:]:
        parent = by[s.name.rsplit("/", 1)[0]]
        assert parent.t0 <= s.t0 <= s.t1 <= parent.t1, s.name
    # the existing readers' suffix matches see the nested spans
    assert tr.has_span("order") and tr.has_span("verify")
    assert tr.span_seconds("verify") == by["dispatch/verify"].seconds
    step = "take" if verify == "host" else "loop"
    assert tr.span_seconds(step) == pytest.approx(
        sum(s.seconds for s in tr.spans if s.name.endswith("/" + step)))


def test_every_request_carries_its_dispatch_time():
    """``t_submit <= t_dispatch <= t_done`` on every answered request,
    traced or not, over several dispatches."""
    Q, D = _data(n_q=8)
    sess = MatchSession(_engine("device", D), window_s=0.0, max_batch=2)
    sess.start()
    reqs = [sess.submit(q, k=2, explain=bool(i % 2))
            for i, q in enumerate(Q)]
    for r in reqs:
        assert r.wait(120) and r.ok, r.error
    sess.close()
    for r in reqs:
        assert r.t_dispatch > 0.0
        assert r.t_submit <= r.t_dispatch <= r.t_done, (
            r.t_submit, r.t_dispatch, r.t_done)


@pytest.mark.parametrize("verify", ["host", "device"])
def test_explain_renders_rounds_summed_by_path(verify):
    """EXPLAIN prints each nested path once, with its count and total,
    takes its phases from the children of the dispatch root, and prints
    the round table whichever loop ran the rounds."""
    from repro.obs import check_trace, render_trace
    Q, D = _data()
    tr = _serve_one_batch(_engine(verify, D), Q, k=8)[0].trace
    out = render_trace(tr)
    rounds = len(tr.rounds)
    assert check_trace(tr, device=verify == "device") == []
    phases = next(ln for ln in out.splitlines()
                  if ln.startswith("phases:"))
    assert phases.startswith("phases: order ")
    assert "| verify " in phases and "dispatch " in phases
    nested = [ln for ln in out.splitlines() if ln.startswith("  .. ")]
    if verify == "host":
        assert [ln.split()[1] for ln in nested] == [
            f"verify/{s}" for s in ROUND_STEPS]
        assert nested[0].endswith(f" x{rounds + 1}")
    else:
        assert [ln.split()[1] for ln in nested] == ["verify/loop"]
        assert " x" not in nested[0]
    table = [ln for ln in out.splitlines() if ln.split()[1:2] == ["scan"]]
    assert len(table) == rounds


def _subprocess(code: str, devices: int = 1) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(REPO, "src"), os.path.join(REPO, "bench"),
         env.get("PYTHONPATH", "")])
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout.strip().splitlines()[-1]


#: program -> the module name it lowers to on four devices; the device
#: round loop is the row-verify program, named as the one-round one
ROLES = {r: r for r in ("encode_rows", "repr_dists", "repr_topk",
                        "rr_place", "rr_grow", "rr_bounds", "rr_topk",
                        "rr_rows_verify", "rr_windows_gather")}
ROLES["rr_verify_loop"] = "rr_rows_verify"


@pytest.fixture(scope="module")
def module_names():
    """Lower every sharded program on four virtual CPU devices; the
    first line of each lowered text names its module."""
    out = _subprocess("""
        import json
        import jax, jax.numpy as jnp
        from repro.core import make_technique
        from repro.core import distributed as D
        from repro.launch.mesh import make_mesh_compat

        mesh = make_mesh_compat((4,), ("data",))
        enc = make_technique("ssax", T=240, W=12, L=10, r2_season=0.7)
        pw = enc.pairwise_distance
        x = jnp.zeros((32, 240), jnp.float32)
        rx, rq = enc.encode(x), enc.encode(x[:2])
        specs = D._rep_specs(rq, rx)
        leaves, out_def = jax.tree.flatten(rx)
        buf = jnp.zeros((32, 256), jnp.float32)
        cand = jnp.zeros((2, 8), jnp.int32)
        per = jnp.int32(8)
        low = {
            "encode_rows": D._encode_fn(mesh, enc, out_def, tuple(
                l.ndim for l in leaves)).lower(x),
            "repr_dists": D._repr_dists_fn(mesh, pw, *specs).lower(rq, rx),
            "repr_topk": D._repr_topk_fn(mesh, pw, 4, *specs).lower(rq, rx),
            "rr_place": D._rr_place_fn(mesh, 2).lower(buf, buf, per),
            "rr_grow": D._rr_grow_fn(mesh, 2, 16).lower(buf),
            "rr_bounds": D._rr_bounds_fn(mesh, pw, *specs).lower(rq, rx,
                                                                per),
            "rr_topk": D._rr_topk_fn(mesh, pw, 4, 4, *specs).lower(rq, rx,
                                                                   per),
            "rr_rows_verify": D._rr_rows_verify_fn(mesh, 4).lower(
                buf, x[:2], cand, per),
            "rr_windows_gather": D._rr_windows_gather_fn(
                mesh, 4, 3, 60, 120).lower(buf, cand, per),
            "rr_verify_loop": D._rr_verify_loop_fn(mesh, 4, 4, 8, 5).lower(
                buf, x[:2], jnp.zeros((2, 32), jnp.float32),
                jnp.zeros((2, 32), jnp.int32), jnp.zeros(2, jnp.int32),
                jnp.zeros(2, jnp.int32), jnp.zeros((2, 4), jnp.float32),
                jnp.zeros((2, 4), jnp.int32), per),
        }
        print(json.dumps({r: l.as_text().split(None, 2)[1]
                          for r, l in low.items()}))
    """, devices=4)
    return json.loads(out)


@pytest.mark.parametrize("role", sorted(ROLES))
def test_sharded_program_is_named_for_its_role(module_names, role):
    assert module_names[role] == f"@jit_{ROLES[role]}"


def test_profiler_annotations_reach_the_device_trace_reader():
    """Under the profiler, a traced dispatch's spans are annotations
    ``repro/<path>`` on the dispatching thread's host line, the line
    ``tsbench.devtrace.load`` keeps, one annotation per span."""
    out = _subprocess("""
        import json, tempfile
        from collections import Counter
        import jax, jax.numpy as jnp
        from repro.core import make_technique
        from repro.core.distributed import make_engine_service
        from repro.data.synthetic import season_dataset
        from repro.launch.mesh import make_mesh_compat
        from repro.service import MatchSession
        from tsbench import devtrace

        X = season_dataset(100, 240, 10, 0.7, per_series_strength=True,
                           seed=5)
        enc = make_technique("ssax", T=240, W=12, L=10, r2_season=0.7)
        eng = make_engine_service(enc, jnp.asarray(X[4:]),
                                  make_mesh_compat((1,), ("data",)),
                                  batch_size=16, verify="device")
        sess = MatchSession(eng, window_s=0.05, max_batch=4)
        sess.start()
        sess.serve(X[:4], k=4, timeout=120)      # compile outside
        d = tempfile.mkdtemp()
        jax.profiler.start_trace(d)
        reqs = [sess.submit(q, k=4, explain=True) for q in X[:4]]
        assert all(r.wait(120) and r.ok for r in reqs)
        jax.profiler.stop_trace()
        sess.close()
        trace = devtrace.load(devtrace.find_xplane(d), 1.0)
        lines = {line: dict(Counter(n for n, _, _ in evs
                                    if n.startswith("repro/")))
                 for line, evs in trace.python.items()}
        spans = dict(Counter("repro/" + s.name
                             for s in reqs[0].trace.spans))
        print(json.dumps({"lines": lines, "spans": spans}))
    """)
    got = json.loads(out)
    marked = {line: c for line, c in got["lines"].items() if c}
    assert len(marked) == 1, marked
    assert next(iter(marked.values())) == got["spans"]
    assert got["spans"]["repro/dispatch/verify/loop"] == 1

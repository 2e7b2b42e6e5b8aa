"""The readers of the program's span tree and annotations, pinned on a
trace recorded on a TPU v5e: a traced run of ``season_large_q`` under
``closed8.k32``, cut to 4,096 rows (``data/v5e_season_tiny.*``: the
window's trace, copied from the run's trace directory before it was
removed and gzipped, and the fields of ``harness.Run`` its readers saw,
with each request's timestamps and its dispatch's spans)."""

import json
import os
from types import SimpleNamespace

import pytest

import benchtest_util as U
from repro.obs.trace import Span, Trace
from tsbench import devtrace, harness, spec, spans

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = ("service.dispatch_wait_ms", "engine.peek_ms", "engine.take_ms",
       "engine.dist_ms", "engine.merge_ms", "device.idle_order_share",
       "device.idle_verify_share", "kernels.bounds_roofline",
       "kernels.verify_roofline")


@pytest.fixture(scope="module")
def rec():
    with open(os.path.join(DATA, "v5e_season_tiny.run.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def window(rec):
    return devtrace.load(
        os.path.join(DATA, "v5e_season_tiny.window.xplane.pb.gz"),
        rec["window_s"])


def _trace(t):
    tr = Trace("serve.dispatch")
    for name, t0, t1, meta in t["spans"]:
        sp = Span(name, t0, meta)
        sp.t1 = t1
        tr.spans.append(sp)
    tr.rounds = [{}] * t["rounds"]
    return tr


@pytest.fixture(scope="module")
def run(rec, window):
    traces = [_trace(t) for t in rec["traces"]]
    reqs = [SimpleNamespace(
        t_submit=r["t_submit"], t_dispatch=r["t_dispatch"],
        t_done=r["t_done"], ok=r["ok"],
        trace=None if r["trace"] is None else traces[r["trace"]])
        for r in rec["requests"]]
    return harness.Run(
        root=U.REPO, seconds=rec["seconds"], setup_s=0.0,
        rows=rec["rows"], length=rec["length"], requests=reqs,
        t_start=rec["t_start"], t_end=rec["t_end"],
        t_drained=rec["t_drained"], counters=rec["counters"],
        memory_peak_bytes=0, mirror_bytes=rec["mirror_bytes"],
        peaks=rec["peaks"], dispatches=rec["dispatches"], device=window)


def read(name, run):
    return spec.plugin(U.REPO, "metrics", name).read(run)


@pytest.mark.parametrize("name,value", [
    ("service.dispatch_wait_ms", 0.728255),
    ("engine.peek_ms", 8.452229),
    ("engine.take_ms", 20.08847),
    ("engine.dist_ms", 18.801781),
    ("engine.merge_ms", 21.25878),
    ("device.idle_order_share", 36.937946),
    ("device.idle_verify_share", 49.761840),
    ("kernels.bounds_roofline", 1.7964235),
    ("kernels.verify_roofline", 0.2057638),
])
def test_readers_on_the_recorded_run(run, name, value):
    assert read(name, run) == pytest.approx(value, rel=1e-6)


def _annotations(window, name):
    return [(s, e) for evs in window.python.values()
            for n, s, e in evs if n == name]


def _runs(window, role):
    return [(s, e) for evs in window.modules.values()
            for n, s, e in evs if n.startswith(f"jit_{role}(")]


@pytest.mark.parametrize("role,span", [
    ("rr_bounds", "dispatch/order"),
    ("rr_rows_verify", "dispatch/verify/dist"),
])
def test_programs_run_inside_their_spans(window, rec, role, span):
    """Every run of the bound sweep lies inside an ``order`` annotation
    and every run of the row-verify program inside a round's ``dist``:
    the program's spans and the device's programs share one clock."""
    ann = _annotations(window, f"repro/{span}")
    runs = _runs(window, role)
    assert runs and ann
    for s, e in runs:
        assert any(a <= s and e <= b for a, b in ann), (role, s, e)
    # one sweep a dispatch, one verification a round
    want = (len(rec["dispatches"]) if role == "rr_bounds"
            else sum(d["rounds"] for d in rec["dispatches"]))
    assert len(runs) == want == len(ann)


def test_annotations_are_the_spans_of_the_traced_dispatches(window, run):
    """One annotation ``repro/<path>`` per recorded span, all on the one
    host line of the dispatching thread."""
    want = {}
    for tr in spans.traces(run):
        for sp in tr.spans:
            want[f"repro/{sp.name}"] = want.get(f"repro/{sp.name}", 0) + 1
    lines = {line: [n for n, _, _ in evs if n.startswith("repro/")]
             for line, evs in window.python.items()}
    marked = [ns for ns in lines.values() if ns]
    assert len(marked) == 1
    got = {}
    for n in marked[0]:
        got[n] = got.get(n, 0) + 1
    assert got == want
    assert len(want) == 7


def test_round_steps_cover_the_verify_span(run):
    """The four steps of each round take nearly all of a dispatch's
    ``verify`` span; the rest is the loop's own host work."""
    for tr in spans.traces(run):
        verify = tr.span_seconds("verify")
        steps = sum(s.seconds for s in tr.spans
                    if s.name.startswith("dispatch/verify/"))
        assert 0.9 * verify < steps <= verify


def test_idle_split_lies_within_the_idle_share(run):
    order = read("device.idle_order_share", run)
    verify = read("device.idle_verify_share", run)
    assert 0.0 < order and 0.0 < verify
    assert order + verify <= read("device.idle_share", run)


def test_rooflines_lie_under_the_peak(run):
    for name in ("kernels.bounds_roofline", "kernels.verify_roofline"):
        assert 0.0 < read(name, run) < 100.0, name


def test_nested_spans_keep_the_existing_readers(run, rec):
    """``sweep.order_ms`` and ``engine.verify_ms`` read the nested
    ``dispatch/order`` and ``dispatch/verify`` spans through the
    harness's suffix match."""
    recs = harness.dispatch_records(run.requests)
    assert [d["rounds"] for d in recs] == [d["rounds"]
                                           for d in rec["dispatches"]]
    assert read("engine.verify_ms", run) == pytest.approx(73.294309,
                                                          rel=1e-6)
    assert read("sweep.order_ms", run) == pytest.approx(42.970629,
                                                        rel=1e-6)


@pytest.mark.parametrize("name", NEW)
def test_readers_give_nothing_on_a_program_without_the_spans(name):
    """On the hydra recording, made by a program with neither the span
    tree, nor ``t_dispatch``, nor named programs, every new reader
    returns None and raises nothing."""
    with open(os.path.join(DATA, "v5e_hydra_tiny.run.json")) as f:
        old = json.load(f)
    window = devtrace.load(
        os.path.join(DATA, "v5e_hydra_tiny.window.xplane.pb.gz"),
        old["window_s"])
    tr = Trace("serve.dispatch")
    for span in ("order", "verify"):
        with tr.span(span):
            pass
    reqs = [SimpleNamespace(t_submit=0.0, t_done=1.0, ok=True, trace=tr)]
    run = harness.Run(
        root=U.REPO, seconds=old["seconds"], setup_s=0.0,
        rows=old["rows"], length=old["length"], requests=reqs,
        t_start=0.0, t_end=old["seconds"], t_drained=old["window_s"],
        counters=old["counters"], memory_peak_bytes=0,
        mirror_bytes=old["mirror_bytes"], peaks=old["peaks"],
        dispatches=old["dispatches"], device=window)
    assert read(name, run) is None

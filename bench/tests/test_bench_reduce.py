"""The reduction from a trace to metrics, pinned on a trace recorded on a
TPU v5e: a traced run of the ``hydra_rw256_q`` configuration under the
``closed8.k32`` mix, cut to 4,096 rows (``data/v5e_hydra_tiny.*``: the
window's trace, the trace of one more dispatch with the Python tracer
on, both copied from the run's trace directory before it was removed
and gzipped, and the fields of ``harness.Run`` its readers saw)."""

import json
import os

import pytest

import benchtest_util as U
from tsbench import devtrace, harness, spec

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
#: the recorded run's row-verification and bound-sweep programs: both are
#: "jit_local"; here they are told apart by their fingerprints, which
#: only hold for this recording
VERIFY = "jit_local(535223716160156688)"
BOUNDS = "jit_local(6776845838671640312)"


@pytest.fixture(scope="module")
def rec():
    with open(os.path.join(DATA, "v5e_hydra_tiny.run.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def window(rec):
    return devtrace.load(
        os.path.join(DATA, "v5e_hydra_tiny.window.xplane.pb.gz"),
        rec["window_s"])


@pytest.fixture(scope="module")
def run(rec, window):
    return harness.Run(
        root=U.REPO, seconds=rec["seconds"], setup_s=0.0,
        rows=rec["rows"], length=rec["length"], requests=[], t_start=0.0,
        t_end=rec["seconds"], t_drained=rec["window_s"],
        counters=rec["counters"], memory_peak_bytes=0,
        mirror_bytes=rec["mirror_bytes"], peaks=rec["peaks"],
        dispatches=rec["dispatches"], device=window)


def test_device_events(window):
    assert window.devices == ["/device:TPU:0"]
    assert len(window.ops["/device:TPU:0"]) == 4611
    assert len(window.modules["/device:TPU:0"]) == 1209
    assert window.busy_s() == pytest.approx(0.019890745, rel=1e-9)
    top = window.top_ops(3)
    assert top[0][0].startswith("%fusion = f32[131072]")
    assert top[0][1] == pytest.approx(0.016042393, rel=1e-9)
    assert len(window.top_ops()) == 10


def test_programs_match_the_dispatch_records(window, rec):
    """One bound sweep per dispatch, one verification per round."""
    mods = window.module_names()
    assert mods[BOUNDS][0] == len(rec["dispatches"]) == 12
    assert mods[VERIFY][0] == sum(d["rounds"] for d in rec["dispatches"])


def test_work_counts_give_rooflines_under_the_peak(window, run):
    """The work counts over the programs' device time at the v5e's peak
    bandwidth: shares of the roofline, which cannot pass 100%."""
    peak = spec.peaks(U.REPO, "TPU v5 lite")["hbm_bytes_per_s"]
    assert run.peaks["hbm_bytes_per_s"] == peak
    rows = sum(d["examined"] for d in run.dispatches)
    verify = run.work("row_verify").bytes_moved(rows, run.length)
    assert verify == 4.0 * 8448 * 256
    t_v = window.module_seconds(lambda n: n == VERIFY)
    share_v = 100.0 * verify / peak / t_v
    assert share_v == pytest.approx(5.9410755, rel=1e-6)
    bounds = sum(run.work("bound_sweep").bytes_moved(
        run.mirror_bytes["rep"], len(d["waits_ms"]), run.rows)
        for d in run.dispatches)
    t_b = window.module_seconds(lambda n: n == BOUNDS)
    share_b = 100.0 * bounds / peak / t_b
    assert share_b == pytest.approx(0.02671644, rel=1e-6)
    assert 0.0 < share_b < share_v < 100.0


@pytest.mark.parametrize("name,value", [
    ("device.idle_share", 97.104966),
    ("service.requests_per_dispatch", 2.0),
    ("engine.rounds_per_dispatch", 1.75),
    ("sweep.order_ms", 33.288144),
    ("engine.verify_ms", 26.11299),
    ("service.queue_wait_ms", 167.596006),
])
def test_readers_on_the_recorded_run(run, name, value):
    got = spec.plugin(U.REPO, "metrics", name).read(run)
    assert got == pytest.approx(value, rel=1e-6)


def test_idle_gaps_named_by_the_host(rec):
    """The dispatch traced with the Python tracer on: the device's idle
    gaps, named by what the dispatching thread was doing."""
    trace = devtrace.load(
        os.path.join(DATA, "v5e_hydra_tiny.python.xplane.pb.gz"), 0.0)
    gaps = trace.idle_gaps()
    assert gaps[0][0] == "DevicePut"
    assert gaps[0][1] == pytest.approx(0.014579685, rel=1e-9)
    assert len(gaps) == 10
    assert any(name.startswith("$") for name, _ in gaps)

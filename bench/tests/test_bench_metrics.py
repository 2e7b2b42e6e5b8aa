"""Metric arithmetic: what each end-to-end reader computes from a run,
and the device-trace reductions."""

from types import SimpleNamespace

import numpy as np
import pytest

import benchtest_util as U
from tsbench import devtrace, harness, spec


def _req(t_submit, t_done, ok=True):
    return SimpleNamespace(t_submit=t_submit, t_done=t_done, ok=ok,
                           trace=None)


def _run(reqs, seconds=10.0, t_start=0.0, **kw):
    fields = dict(root=U.REPO, seconds=seconds, setup_s=12.5,
                  rows=1000, length=960, requests=reqs, t_start=t_start,
                  t_end=t_start + seconds,
                  t_drained=max([t_start + seconds]
                                + [r.t_done for r in reqs]),
                  counters={},
                  memory_peak_bytes=4_000_000, mirror_bytes={"rep": 0},
                  peaks={})
    fields.update(kw)
    return harness.Run(**fields)


def read(name, run):
    return spec.plugin(U.REPO, "metrics", name).read(run)


def test_tail_is_over_all_requests_not_chunks():
    """95 fast requests and 5 slow ones in one half of the window: a
    percentile over all 200 sees the slow ones where a median of per-half
    percentiles would not."""
    fast = [_req(i * 0.01, i * 0.01 + 0.010) for i in range(190)]
    slow = [_req(5.0 + i, 5.0 + i + 1.0) for i in range(10)]
    run = _run(fast + slow)
    lat = np.asarray([10.0] * 190 + [1000.0] * 10)
    assert read("latency_p95_ms", run) == pytest.approx(
        np.percentile(lat, 95))
    assert read("latency_p95_ms", run) > 10.0 + 1e-6
    assert read("latency_p50_ms", run) == pytest.approx(10.0)


def test_qps_counts_every_answer_of_the_window_over_its_time():
    """Answers that land after the close count, and so does the time
    until the last of them; failed requests count for neither."""
    reqs = [_req(1.0, 2.0), _req(2.0, 9.9), _req(9.5, 10.5),
            _req(3.0, 4.0, ok=False)]
    run = _run(reqs, seconds=10.0)
    assert read("qps", run) == pytest.approx(3 / 10.5)
    assert len(run.latencies_ms) == 3


def test_memory_and_setup_readers():
    run = _run([_req(0, 1)])
    assert read("hbm_bytes_per_series", run) == 4000.0
    assert read("setup_s", run) == 12.5


@pytest.mark.parametrize("stats,peak", [
    ({"peak_bytes_in_use": 7_000, "peak_bytes_reserved": 6_000}, 13_000),
    ({"peak_bytes_in_use": 7_000}, 7_000),
    (None, 0),
], ids=["with_program_temps", "no_reservation", "no_stats"])
def test_device_peak_counts_program_temporaries(stats, peak):
    """A chip's peak is what its allocator held in use plus what it
    reserved for programs' temporaries, which the in-use peak leaves
    out; a device without memory statistics reads 0 (no metric)."""
    dev = SimpleNamespace(memory_stats=lambda: stats)
    assert harness.device_peak_bytes(dev) == peak


def test_per_layer_readers_from_dispatch_records():
    disp = [{"order_s": 0.010, "verify_s": 0.020, "rounds": 4,
             "examined": 1000, "waits_ms": [5.0, 7.0]},
            {"order_s": 0.030, "verify_s": 0.040, "rounds": 6,
             "examined": 3000, "waits_ms": [1.0]}]
    run = _run([], dispatches=disp,
               counters={"serve.batches": 4, "serve.batched_requests": 30})
    assert read("sweep.order_ms", run) == pytest.approx(20.0)
    assert read("engine.verify_ms", run) == pytest.approx(30.0)
    assert read("engine.rounds_per_dispatch", run) == pytest.approx(5.0)
    assert read("service.queue_wait_ms", run) == pytest.approx(5.0)
    assert read("service.requests_per_dispatch", run) == pytest.approx(7.5)


def test_readers_find_nothing_without_a_trace():
    run = _run([_req(0, 1)])
    for name in ("sweep.order_ms", "engine.verify_ms",
                 "engine.rounds_per_dispatch", "service.queue_wait_ms",
                 "service.requests_per_dispatch", "device.idle_share"):
        assert read(name, run) is None, name


def test_union_and_gaps():
    evs = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40), ("d", 35, 38)]
    assert devtrace.union_ns(evs) == 30
    assert devtrace.gaps_ns(evs) == [(20, 30)]
    host = sorted([("outer", 0, 100), ("inner", 15, 35), ("x", 50, 60)],
                  key=lambda e: e[1])
    assert devtrace.innermost(host, 25) == "inner"
    assert devtrace.innermost(host, 45) == "outer"
    assert devtrace.innermost(host, 200) is None


def test_idle_share_from_device_events():
    dev = devtrace.DeviceTrace(window_s=1e-6,
                               ops={"/device:TPU:0": [("f", 0, 250),
                                                      ("g", 500, 750)]})
    run = _run([], device=dev)
    assert read("device.idle_share", run) == pytest.approx(50.0)

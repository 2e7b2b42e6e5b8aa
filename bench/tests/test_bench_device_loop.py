"""``engine.device_loop_share``: the share of traced dispatches whose
verification rounds ran as one device program, read from each dispatch
trace's ``device_loop`` counter; nothing on a program without it."""

from types import SimpleNamespace

import pytest

import benchtest_util as U
from repro.obs.trace import Trace
from tsbench import harness, spec


def _run(marks, ok=True):
    """A run whose traced dispatches carry ``marks`` as their counter
    (None: not recorded); two requests answered by each."""
    reqs = []
    for m in marks:
        tr = Trace("serve.dispatch")
        if m is not None:
            tr.add("device_loop", m)
        reqs += [SimpleNamespace(t_submit=0.0, t_done=1.0, ok=ok,
                                 trace=tr)] * 2
    return harness.Run(root=U.REPO, seconds=1.0, setup_s=0.0, rows=1,
                       length=1, requests=reqs, t_start=0.0, t_end=1.0,
                       t_drained=1.0, counters={}, memory_peak_bytes=0,
                       mirror_bytes={}, peaks={})


def read(run):
    return spec.plugin(U.REPO, "metrics", "engine.device_loop_share").read(
        run)


@pytest.mark.parametrize("marks,share", [
    ([1, 1, 1], 100.0),
    ([1, 0, 1, 0], 50.0),
    ([0, 0], 0.0),
    ([1, None], 50.0),
])
def test_share_of_traced_dispatches(marks, share):
    assert read(_run(marks)) == pytest.approx(share)


@pytest.mark.parametrize("marks", [[None, None], []])
def test_nothing_without_the_counter(marks):
    """The parent's traces carry no counter, and an untraced run has no
    traces: the reader returns None, not 0."""
    assert read(_run(marks)) is None


def test_unanswered_requests_do_not_count():
    assert read(_run([1, 1], ok=False)) is None

"""A run whose timed path is broken underneath comes out not correct.

Each fault is planted in the program's dispatch (``MatchSession.
_run_tier``, the call that answers a coalesced batch) and the rest of a
run is driven as on the chip, on the CPU at a tiny size."""

import numpy as np
import pytest

import benchtest_util as U
from repro.service.session import MatchSession

CELL = "season_large_q.closed8.k32"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return U.tiny_root(tmp_path_factory.mktemp("bench"))


def _altered(orig):
    """One returned row id replaced where the answer is produced."""
    def run_tier(self, qs, k, tier, trace, **kw):
        res = orig(self, qs, k, tier, trace, **kw)
        n = self.engine.store.n
        res.indices[0, -1] = (res.indices[0, -1] + n // 2) % n
        return res
    return run_tier


def _half_batch(orig):
    """Only the first half of the batch computed; the rest given the
    first half's answers."""
    def run_tier(self, qs, k, tier, trace, **kw):
        h = max(1, qs.shape[0] // 2)
        res = orig(self, qs[:h], k, tier, trace, **kw)
        rows = np.arange(qs.shape[0]) % h
        res.indices = res.indices[rows]
        res.distances = res.distances[rows]
        return res
    return run_tier


def _unchanged(orig):
    """Every dispatch after the first returns the first one's answers."""
    first = {}

    def run_tier(self, qs, k, tier, trace, **kw):
        if "res" not in first:
            first["res"] = orig(self, qs, k, tier, trace, **kw)
        res = first["res"]
        rows = np.arange(qs.shape[0]) % res.indices.shape[0]
        res.indices = res.indices[rows]
        res.distances = res.distances[rows]
        return res
    return run_tier


def test_sound_run_is_correct(root):
    out = U.run(root, CELL, seed=11)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 8


@pytest.mark.parametrize("fault", [_altered, _half_batch, _unchanged],
                         ids=["answer_altered", "half_batch",
                              "state_unchanged"])
def test_fault_is_not_correct(root, monkeypatch, fault):
    monkeypatch.setattr(MatchSession, "_run_tier",
                        fault(MatchSession._run_tier))
    out = U.run(root, CELL, seed=12)
    assert not out["correct"], out["checks"]
    assert out["checks"]["kth_excess"]["value"] > \
        out["checks"]["kth_excess"]["limit"]

"""The plain reference against a direct float64 scan, and the control."""

import numpy as np
import pytest

import benchtest_util as U
from tsbench import check, gen, spec


def _direct(corpus, queries, k):
    d = np.sqrt(((corpus[None].astype(np.float64)
                  - queries[:, None].astype(np.float64)) ** 2).sum(-1))
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return ids, np.take_along_axis(d, ids, axis=1)


def _data(n=3000, q=20, T=64, seed=1):
    mod = spec.plugin(U.REPO, "data", "random_walk")
    return (gen.series(mod, {}, seed, gen.CORPUS, n, T),
            gen.series(mod, {}, seed, gen.POOL, q, T))


@pytest.fixture(scope="module")
def ref():
    return spec.plugin(U.REPO, "reference", "exact_knn")


@pytest.mark.parametrize("k", [1, 32])
def test_exact_topk_is_the_direct_scan(ref, k):
    corpus, queries = _data()
    ids, d, scanned = ref.exact_topk(corpus, queries, k)
    want_i, want_d = _direct(corpus, queries, k)
    np.testing.assert_array_equal(ids, want_i)
    np.testing.assert_allclose(d, want_d, rtol=1e-12)
    assert scanned == 0


def test_rows_the_filter_cannot_tell_apart_fall_back_to_the_scan(ref):
    """Duplicate rows tie beyond the filter's margin: the host scan
    answers, ties toward the smaller id."""
    corpus, queries = _data(n=600, q=3)
    corpus[100:400] = corpus[50]
    ids, d, scanned = ref.exact_topk(corpus, queries[:1] * 0 + corpus[50],
                                     5)
    assert scanned == 1
    np.testing.assert_array_equal(ids[0], [50, 100, 101, 102, 103])
    assert np.all(d[0] == 0.0)


def test_control_is_not_correct(ref):
    """The reference in bfloat16, in the program's place, fails the
    comparison (the control of the correctness check)."""
    corpus, queries = _data(n=4000, q=40, T=256)
    ids, dists = ref.answers(corpus, queries, 32, dtype="bfloat16")
    answers = [(ids[r], dists[r], "linear") for r in range(len(queries))]
    limits = spec.load_json(
        f"{U.BENCH}/configs/hydra_rw256_q.json")["limits"]
    checks = check.compare(corpus, queries, answers, 32, ref, limits)
    assert not check.passed(checks)
    assert checks["dist_rel_err"]["value"] > limits["dist_rel_err"]


def test_float32_answers_pass(ref):
    """The same scan at float32 (the program's precision) passes."""
    corpus, queries = _data(n=4000, q=40, T=256)
    ids, dists = ref.answers(corpus, queries, 32, dtype="float32")
    answers = [(ids[r], dists[r], "linear") for r in range(len(queries))]
    limits = spec.load_json(
        f"{U.BENCH}/configs/hydra_rw256_q.json")["limits"]
    assert check.passed(check.compare(corpus, queries, answers, 32, ref,
                                      limits))


def test_check_counts_each_kind_of_wrong_answer(ref):
    corpus, queries = _data(n=500, q=4)
    ids, d, _ = ref.exact_topk(corpus, queries, 4)
    limits = {"kth_excess": 1e-5, "dist_rel_err": 1e-5}
    swapped = ids[2].copy()
    swapped[-1] = _direct(corpus, queries[2:3], 10)[0][0, 9]
    answers = [None, (ids[1], d[1], "approx"),
               (swapped, d[2], "linear"), (ids[3], d[3][::-1], "linear")]
    checks = check.compare(corpus, queries, answers, 4, ref, limits)
    assert checks["unanswered"]["value"] == 1
    assert checks["inexact_tier"]["value"] == 1
    assert checks["malformed"]["value"] == 1       # distances descending
    assert checks["kth_excess"]["value"] > 1e-3

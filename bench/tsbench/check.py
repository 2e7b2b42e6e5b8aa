"""The comparison that decides ``correct``.

Every answered request of the window is held to the exact guarantee
against the plain reference named by the configuration.  The numbers
compared, each with its limit:

- ``unanswered``: requests of the window that were shed, failed or never
  resolved.  Limit 0.
- ``inexact_tier``: requests served by any tier but an exact one
  ("linear" or "index").  Limit 0.
- ``malformed``: answers that do not hold k distinct row ids in range
  with finite distances in ascending order.  Limit 0.
- ``kth_excess``: over all answers, the largest amount by which the
  float64 distance of a returned row exceeds the true k-th distance, as
  a share of that distance.  0 when every returned set is the true top
  k; a row that only ties the k-th within rounding reads near 1e-7.
- ``dist_rel_err``: over all answers, the largest gap between a
  returned distance and the float64 distance of the same row, as a
  share of the latter.
"""

from __future__ import annotations

import numpy as np

EXACT_TIERS = ("linear", "index")
COUNTS = ("unanswered", "inexact_tier", "malformed")


def compare(corpus: np.ndarray, queries: np.ndarray, answers: list,
            k: int, reference, limits: dict) -> dict:
    """``answers``: one ``(ids, distances, tier)`` per query, or None for
    a request that got no answer.  Returns ``{name: {"value", "limit"}}``
    in the order of the module docstring."""
    n = corpus.shape[0]
    kk = min(k, n)
    counts = dict.fromkeys(COUNTS, 0)
    rows = []
    for r, ans in enumerate(answers):
        if ans is None:
            counts["unanswered"] += 1
            continue
        ids, dists, tier = ans
        if tier not in EXACT_TIERS:
            counts["inexact_tier"] += 1
        ids = np.asarray(ids, np.int64)
        dists = np.asarray(dists, np.float64)
        if (ids.shape != (kk,) or dists.shape != (kk,)
                or np.unique(ids).size != kk or ids.min() < 0
                or ids.max() >= n or not np.all(np.isfinite(dists))
                or np.any(np.diff(dists) < 0)):
            counts["malformed"] += 1
            continue
        rows.append(r)
    kth_excess = dist_err = 0.0
    if rows:
        qs = queries[rows]
        _, ref_d, _ = reference.exact_topk(corpus, qs, kk)
        for j, r in enumerate(rows):
            ids, dists, _ = answers[r]
            diff = (corpus[np.asarray(ids, np.int64)].astype(np.float64)
                    - queries[r].astype(np.float64))
            d64 = np.sqrt(np.sum(diff * diff, axis=-1))
            kth = ref_d[j, -1]
            kth_excess = max(kth_excess,
                             float((d64.max() - kth) / max(kth, 1e-30)))
            dist_err = max(dist_err, float(np.max(
                np.abs(np.asarray(dists, np.float64) - d64)
                / np.maximum(d64, 1e-30))))
    out = {name: {"value": counts[name], "limit": 0} for name in COUNTS}
    out["kth_excess"] = {"value": kth_excess,
                         "limit": float(limits["kth_excess"])}
    out["dist_rel_err"] = {"value": dist_err,
                           "limit": float(limits["dist_rel_err"])}
    return out


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())

"""Median over the window's dispatches of the ``verify`` span: the
verification rounds of ``topk_verify`` until every query's next bound
exceeds its k-th best distance."""

import numpy as np


def read(run):
    v = [d["verify_s"] * 1e3 for d in run.dispatches]
    return float(np.median(v)) if v else None

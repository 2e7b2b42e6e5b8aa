"""Median over the window's dispatches of the ``order`` span: encode
the queries, sweep the lower bounds over the mirror, lexsort them on the
device (the span is fenced on the sort)."""

import numpy as np


def read(run):
    v = [d["order_s"] * 1e3 for d in run.dispatches]
    return float(np.median(v)) if v else None

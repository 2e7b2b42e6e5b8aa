"""Median over the window's answered requests of the time from
submission to the dispatch that took them off the queue
(``MatchRequest.t_dispatch - t_submit``): the wait for the coalescing
window and for the dispatch before."""

import numpy as np


def read(run):
    w = [(r.t_dispatch - r.t_submit) * 1e3 for r in run.requests
         if r.ok and getattr(r, "t_dispatch", None)]
    return float(np.median(w)) if w else None

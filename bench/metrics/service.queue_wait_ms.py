"""Median over the window's answered requests of the time not spent in
the dispatch that answered them: submit-to-answer time less that
dispatch's ``order`` and ``verify`` spans (program spans, recorded with
``explain=True``).  It is the wait for the coalescing window and for
the dispatch before."""

import numpy as np


def read(run):
    waits = [w for d in run.dispatches for w in d["waits_ms"]]
    return float(np.median(waits)) if waits else None

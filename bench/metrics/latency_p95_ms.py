"""95th percentile of the submit-to-answer times of every answered
request of the window (one percentile over all of them, not a median of
chunks)."""

import numpy as np


def read(run):
    lat = run.latencies_ms
    return float(np.percentile(lat, 95)) if lat.size else None

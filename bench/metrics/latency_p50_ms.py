"""Median submit-to-answer time of every answered request of the window."""

import numpy as np


def read(run):
    lat = run.latencies_ms
    return float(np.percentile(lat, 50)) if lat.size else None

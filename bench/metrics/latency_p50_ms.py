"""Median request latency, from when each request was due to its answer,
over every request due in the window (one never answered reads
infinite)."""
import numpy as np


def read(run):
    lat = run.latencies_ms()
    if not len(lat):
        return None
    q = float(np.percentile(lat, 50))
    return q if np.isfinite(q) else None

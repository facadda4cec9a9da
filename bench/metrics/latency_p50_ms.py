"""Median host-clock latency over every request of the window (ms)."""
import numpy as np


def read(run):
    if not run.latencies:
        return None
    return float(np.percentile(np.asarray(run.latencies) * 1e3, 50))

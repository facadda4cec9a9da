"""Median queue wait of the window's served requests, backlog (ms): from
arrival to the admission that gives the request a lane, on the runtime's
clock (``RequestRecord.queue_delay_s``)."""
import numpy as np


def read(run):
    if not run.served:
        return None
    return float(np.median([rec.queue_delay_s for _, rec in run.served]) * 1e3)

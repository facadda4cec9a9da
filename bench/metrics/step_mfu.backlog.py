"""The whole step's share of the chip's peak, backlog (%): the least time
of the work the served requests needed (``work.request_work``), over the
window on every chip the cell holds."""
from bench import work


def read(run):
    if not run.served or run.peak is None:
        return None
    ops = nbytes = 0.0
    for _req, rec in run.served:
        o, b = work.request_work(run.shape, rec.z, rec.iters)
        ops += o
        nbytes += b
    least = work.least_seconds(ops, nbytes, run.peak)
    return 100.0 * least / (run.window_s * run.chips)

"""prefix_power_sums' least time over its summed device time, backlog (%).

The least time counts the kernel's logical work (``work.prefix_power_sums_work``),
never a padded layout.  The Pallas call takes the kernel's name in the
trace: ``prefix_power_sums.1``.
"""
from bench import trace, work


def is_kernel(op_name: str) -> bool:
    return op_name.startswith("prefix_power_sums")


def read(run):
    if run.trace is None or run.peak is None:
        return None
    seconds, calls = trace.op_seconds(run.trace, is_kernel)
    if not calls:
        return None
    ops, nbytes = work.prefix_power_sums_work(run.shape["k"], run.cap)
    least = calls * work.least_seconds(ops, nbytes, run.peak)
    return 100.0 * least / seconds

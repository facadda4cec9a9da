"""Planner iterations a chunk dispatch advanced, summed over its lanes,
backlog (mean over dispatches): the ``lane_iters`` counter of the program's
``biathlon.screen`` spans.  A dispatch could hold lanes × chunk_iters."""
from bench import program


def read(run):
    prog = program.of(run)
    screens = program.spans(prog, "screen") if prog is not None else []
    if not screens:
        return None
    return program.counter_sum(screens, "lane_iters") / len(screens)

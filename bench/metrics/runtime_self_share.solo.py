"""Share of the window outside the server's calls, solo (%): the
runtime's own Python (health screen, bookkeeping, ``new_table``)."""
from bench import spans


def read(run):
    inside = sum(run.total(name)[0] for name in spans.SERVER_CALLS)
    return 100.0 * (1.0 - inside / run.window_s)

"""Requests served in the window's rounds over the rounds' wall time (req/s)."""


def read(run):
    return len(run.served) / run.window_s if run.window_s > 0 else None

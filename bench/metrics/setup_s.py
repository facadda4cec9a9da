"""Process start to the start of the window (s)."""


def read(run):
    return run.setup_s

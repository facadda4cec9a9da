"""Wall time per readback with the snapshot that goes with it, backlog (ms)."""


def read(run):
    s, calls, _ = run.total("readback")
    return 1e3 * (s + run.total("snapshot")[0]) / calls if calls else None

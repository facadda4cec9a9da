"""Wall time per chunk dispatch through its block_until_ready, solo (ms)."""


def read(run):
    s, calls, _ = run.total("run_chunk")
    return 1e3 * s / calls if calls else None

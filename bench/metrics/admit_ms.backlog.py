"""Wall time per admitted request through its block_until_ready, backlog (ms)."""


def read(run):
    s, _calls, admitted = run.total("admit")
    return 1e3 * s / admitted if admitted else None

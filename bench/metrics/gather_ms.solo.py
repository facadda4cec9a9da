"""Host gather per admitted request, solo (ms): the self time of the
program's ``biathlon.gather`` spans, without the put and fetch inside them."""
from bench import program


def read(run):
    prog = program.of(run)
    if prog is None or not program.admitted(prog):
        return None
    return 1e3 * program.self_seconds(prog, "gather", ("put", "fetch")) / program.admitted(prog)

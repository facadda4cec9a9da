"""Device idle under the admission's host inputs, backlog (% of the window):
idle device time under the program's ``biathlon.gather``, ``.put`` and
``.fetch`` spans."""
from bench import program, trace


def read(run):
    prog = program.of(run)
    if prog is None or not run.trace["devices"]:
        return None
    _busy, idle = program.device_under(run.trace,
                                       program.spans(prog, "gather", "put", "fetch"))
    return 100.0 * idle / trace.window_s(run.trace)

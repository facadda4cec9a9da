"""Device time under the program's admissions per admitted request, solo
(ms): the union of device ops inside the ``biathlon.admission`` spans."""
from bench import program


def read(run):
    prog = program.of(run)
    if prog is None or not program.admitted(prog) or not run.trace["devices"]:
        return None
    busy, _idle = program.device_under(run.trace, program.spans(prog, "admission"))
    return 1e3 * busy / program.admitted(prog)

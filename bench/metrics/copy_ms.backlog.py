"""Host-device copies per admitted request, backlog (ms): the program's
``biathlon.put`` and ``biathlon.fetch`` spans inside its admissions."""
from bench import program


def read(run):
    prog = program.of(run)
    if prog is None or not program.admitted(prog):
        return None
    copies = program.inside(program.spans(prog, "put", "fetch"),
                            program.spans(prog, "admission"))
    return 1e3 * program.seconds(copies) / program.admitted(prog)

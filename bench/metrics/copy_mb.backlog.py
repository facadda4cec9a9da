"""Bytes crossing host and device per admitted request, backlog (MB): the
``h2d_bytes`` and ``d2h_bytes`` counters of the program's ``biathlon.put``
and ``biathlon.fetch`` spans inside its admissions."""
from bench import program


def read(run):
    prog = program.of(run)
    if prog is None or not program.admitted(prog):
        return None
    adm = program.spans(prog, "admission")
    nbytes = (program.counter_sum(program.inside(program.spans(prog, "put"), adm), "h2d_bytes")
              + program.counter_sum(program.inside(program.spans(prog, "fetch"), adm),
                                    "d2h_bytes"))
    return nbytes * 1e-6 / program.admitted(prog)

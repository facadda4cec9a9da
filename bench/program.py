"""The program's own spans in a window's trace, and arithmetic on them.

The served path writes ``biathlon.*`` spans with integer counters into the
profiler's trace, on the host's clock beside the device ops.  :func:`load`
reads them from a trace directory, and :func:`events` from a loaded
profile, as

``[[name, start_ns, dur_ns, {counter: value}], ...]``

the list the readers look for under ``"program"`` in a reduced trace,
beside ``window``, ``devices`` and ``host`` (``bench/trace.py``).  The rest
is arithmetic on that list and on the device ops: spans by name,
containment, self time, counter sums, and device time busy or idle under a
set of spans.  A trace without ``"program"`` — a program that writes no
spans, or a ``load`` that does not keep them — reads as nothing.
"""
from __future__ import annotations

import bisect
import pathlib
import warnings

from bench import trace

PREFIX = "biathlon."


def events(data) -> list[list]:
    """The ``biathlon.*`` host events of a ``jax.profiler.ProfileData``."""
    out = []
    with warnings.catch_warnings():
        # the profiler's stats type warns once, when first built
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in data.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                out.extend(
                    [e.name, e.start_ns, e.duration_ns, dict(e.stats)]
                    for e in line.events if e.name.startswith(PREFIX)
                )
    return sorted(out, key=lambda ev: ev[1])


def load(log_dir: pathlib.Path) -> list[list]:
    """:func:`events` of the trace written under ``log_dir``."""
    import jax

    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return events(jax.profiler.ProfileData.from_file(str(files[-1])))


def of(run) -> list[list] | None:
    """The program's spans of a run's reduced trace; None when there are none."""
    return (run.trace or {}).get("program") or None


def spans(program: list[list], *names: str) -> list[tuple[float, float, dict]]:
    """``(start_ns, end_ns, counters)`` of the spans ``biathlon.<name>``."""
    full = {PREFIX + n for n in names}
    return [(st, st + d, c) for name, st, d, c in program if name in full]


def inside(inner, outer) -> list[tuple[float, float, dict]]:
    """The spans of ``inner`` that lie within a span of ``outer``.

    The spans of ``outer`` must not overlap one another, as spans of one
    name on the host's one serving thread do not.
    """
    outer = sorted(outer, key=lambda sp: sp[0])
    starts = [s for s, _, _ in outer]
    out = []
    for sp in inner:
        k = bisect.bisect_right(starts, sp[0]) - 1
        if k >= 0 and sp[1] <= outer[k][1]:
            out.append(sp)
    return out


def seconds(spans_) -> float:
    return sum(e - s for s, e, _ in spans_) * 1e-9


def self_seconds(program: list[list], name: str, children: tuple[str, ...]) -> float:
    """Time in ``biathlon.<name>`` less the time of its ``children`` in it."""
    outer = spans(program, name)
    return seconds(outer) - seconds(inside(spans(program, *children), outer))


def counter_sum(spans_, key: str) -> int:
    return sum(c.get(key, 0) for _, _, c in spans_)


def _overlap(a, b) -> float:
    """Length of the intersection of two unions of sorted disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(hi - lo, 0.0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def device_under(tr: dict, spans_) -> tuple[float, float]:
    """(busy, idle) device seconds under the union of ``spans_``, inside
    the window, averaged over the chips."""
    lo, hi = tr["window"]
    cover = trace._merged(((s, e) for s, e, _ in spans_), lo, hi)
    length = sum(e - s for s, e in cover)
    chips = tr["devices"].values()
    busy = [
        _overlap(trace._merged(((st, st + d) for _, st, d in ops), lo, hi), cover)
        for ops in chips
    ]
    if not busy:
        return 0.0, 0.0
    mean = sum(busy) / len(busy)
    return mean * 1e-9, (length - mean) * 1e-9


def admitted(program: list[list]) -> int:
    """Requests admitted in the trace: one refill span each."""
    return len(spans(program, "refill"))

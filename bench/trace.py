"""The profiler trace of a window, and its reduction to numbers.

:func:`capture` records a window with JAX's profiler; :func:`load` reads the
``.xplane.pb`` it wrote into a small plain structure:

``{"window": [start_ns, end_ns],
   "devices": {plane: [[op, start_ns, dur_ns], ...]},
   "host": [[annotation, start_ns, dur_ns], ...]}``

``devices`` holds each chip's "XLA Ops" line; ``host`` the benchmark's own
``bench.*`` annotations, on the same clock.  Everything after :func:`load`
is arithmetic on that structure, so a recorded trace checks it.
"""
from __future__ import annotations

import contextlib
import pathlib

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"


@contextlib.contextmanager
def capture(log_dir: pathlib.Path):
    """Profile the enclosed block into ``log_dir``; host Python calls are
    not traced, the benchmark's annotations are."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()


def load(log_dir: pathlib.Path) -> dict:
    """The window, device ops and annotations of the trace in ``log_dir``."""
    import jax

    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(str(files[-1]))
    devices: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [op_name(e.name), e.start_ns, e.duration_ns]
                        for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    [e.name, e.start_ns, e.duration_ns]
                    for e in line.events if e.name.startswith("bench.")
                )
    windows = [h for h in host if h[0] == WINDOW]
    if not windows:
        raise ValueError("the trace holds no bench.window annotation")
    _, start, dur = windows[0]
    return {"window": [start, start + dur], "devices": devices,
            "host": [h for h in host if h[0] != WINDOW]}


def op_name(text: str) -> str:
    """``%fusion.46 = f32[...] fusion(...)`` -> ``fusion.46``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _merged(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of ``[start, end)`` intervals clipped to ``[lo, hi)``."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def window_s(tr: dict) -> float:
    lo, hi = tr["window"]
    return (hi - lo) * 1e-9


def busy_s(tr: dict) -> float:
    """Seconds in which an op ran on the device, averaged over the chips."""
    lo, hi = tr["window"]
    per_chip = [
        sum(e - s for s, e in _merged(((st, st + d) for _, st, d in ops), lo, hi))
        for ops in tr["devices"].values()
    ]
    return sum(per_chip) / len(per_chip) * 1e-9 if per_chip else 0.0


def op_seconds(tr: dict, match) -> tuple[float, int]:
    """(summed device seconds, events) of the ops whose name ``match``es,
    inside the window, over every chip."""
    lo, hi = tr["window"]
    total, count = 0.0, 0
    for ops in tr["devices"].values():
        for name, st, d in ops:
            if match(name) and st >= lo and st + d <= hi:
                total += d
                count += 1
    return total * 1e-9, count


def top_ops(tr: dict, n: int = 10) -> list[list]:
    """The ``n`` ops that took the most device time, in seconds per chip."""
    lo, hi = tr["window"]
    by_name: dict[str, float] = {}
    for ops in tr["devices"].values():
        for name, st, d in ops:
            if st >= lo and st + d <= hi:
                by_name[name] = by_name.get(name, 0.0) + d
    chips = max(len(tr["devices"]), 1)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9 / chips] for name, ns in ranked]


def idle_by_host(tr: dict, n: int = 10) -> list[list]:
    """Idle device time, by what the host was doing, in seconds per chip.

    Each idle gap of each chip is split over the host annotations that
    overlap it (``bench.admit`` -> ``admit``, ...); idle time under no
    annotation is the runtime's own Python, named ``runtime``.
    """
    lo, hi = tr["window"]
    # the annotated server calls run one after another on the host thread
    host = sorted((st, st + d, name.removeprefix("bench."))
                  for name, st, d in tr["host"])
    idle: dict[str, float] = {}
    for ops in tr["devices"].values():
        busy = _merged(((st, st + d) for _, st, d in ops), lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        first = 0
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            while first < len(host) and host[first][1] <= g0:
                first += 1
            covered = 0.0
            for s, e, name in host[first:]:
                if s >= g1:
                    break
                part = min(e, g1) - max(s, g0)
                if part > 0:
                    idle[name] = idle.get(name, 0.0) + part
                    covered += part
            idle["runtime"] = idle.get("runtime", 0.0) + max(g1 - g0 - covered, 0)
    chips = max(len(tr["devices"]), 1)
    ranked = sorted(idle.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9 / chips] for name, ns in ranked]

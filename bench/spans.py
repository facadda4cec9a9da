"""Spans around the server's calls, recorded from outside the program.

:class:`SpanServer` stands in for a ``ContinuousBatchedServer``: the runtime
is handed it in place of the server.  It delegates every attribute and times
``admit``, ``run_chunk``, ``readback`` and ``snapshot`` on the host clock,
each through its ``block_until_ready``.  The runtime blocks on the table
right after ``admit`` and ``run_chunk`` itself, so blocking inside the span
moves no work.  From the same calls it times each request from the start of
its admission to the end of the readback that first shows its lane done.

With ``annotate`` each call also runs inside a ``TraceAnnotation`` of its
name, which puts the host's activity on the profiler's clock.
"""
from __future__ import annotations

import contextlib
import time

import jax

SERVER_CALLS = ("admit", "run_chunk", "readback", "snapshot")


class RunAborted(RuntimeError):
    """The window ran more chunk dispatches than its requests can need."""


class SpanServer:
    def __init__(self, server, *, annotate: bool = False):
        self._server = server
        self.annotate = annotate
        self.spans: list[tuple[str, float, float, int]] = []
        self.latencies: list[float] = []
        self._pending: dict[int, float] = {}
        #: chunk dispatches left before the run is aborted (None = no limit)
        self.chunk_budget: int | None = None

    def __getattr__(self, name):
        return getattr(self._server, name)

    def reset(self) -> None:
        """Forget what warm-up recorded."""
        self.spans.clear()
        self.latencies.clear()
        self._pending.clear()

    def _span(self, name: str):
        if self.annotate:
            return jax.profiler.TraceAnnotation(f"bench.{name}")
        return contextlib.nullcontext()

    def admit(self, table, cap, assignments):
        with self._span("admit"):
            t0 = time.perf_counter()
            table, rows = self._server.admit(table, cap, assignments)
            jax.block_until_ready(table)
            t1 = time.perf_counter()
        self.spans.append(("admit", t0, t1, len(assignments)))
        for lane, _req, _kn in assignments:
            self._pending[lane] = t0
        return table, rows

    def run_chunk(self, table):
        if self.chunk_budget is not None:
            if self.chunk_budget <= 0:
                raise RunAborted("more chunk dispatches than the requests need")
            self.chunk_budget -= 1
        with self._span("run_chunk"):
            t0 = time.perf_counter()
            table = self._server.run_chunk(table)
            jax.block_until_ready(table)
            t1 = time.perf_counter()
        self.spans.append(("run_chunk", t0, t1, 1))
        return table

    def readback(self, table):
        with self._span("readback"):
            t0 = time.perf_counter()
            out = self._server.readback(table)
            t1 = time.perf_counter()
        self.spans.append(("readback", t0, t1, 1))
        for lane in [lane for lane in self._pending if out["done"][lane]]:
            self.latencies.append(t1 - self._pending.pop(lane))
        return out

    def snapshot(self, table):
        with self._span("snapshot"):
            t0 = time.perf_counter()
            ckpt = self._server.snapshot(table)
            t1 = time.perf_counter()
        self.spans.append(("snapshot", t0, t1, 1))
        return ckpt


def totals(spans) -> dict[str, tuple[float, int, int]]:
    """Per call name: (seconds, calls, items) over ``spans``."""
    out: dict[str, tuple[float, int, int]] = {}
    for name, t0, t1, n in spans:
        s, c, k = out.get(name, (0.0, 0, 0))
        out[name] = (s + (t1 - t0), c + 1, k + n)
    return out

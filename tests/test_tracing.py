"""Spans of the continuous served path, and the run clock that charges
every host step.

A tiny run under ``jax.profiler`` must write every ``biathlon.*`` span,
nested as the runtime and server open them, with counters that match the
shapes they count and the records the run returns; a slow readback must
show up in each request's latency and in the run's makespan.
"""
import pathlib
import time
import warnings

import jax
import numpy as np
import pytest
from serving_fixtures import SMALL_CFG, make_small_bundle

from repro.serving import (
    ContinuousBatchedServer,
    ContinuousServingRuntime,
    FaultProfile,
    FaultyContinuousServer,
    RunClock,
)
from repro.tracing import span

ARRIVALS = [(0.0, {"g": g}) for g in range(6)]

#: span -> the spans it may sit directly inside
PARENTS = {
    "biathlon.run": {None},
    "biathlon.admission": {"biathlon.run"},
    "biathlon.refill": {"biathlon.admission"},
    "biathlon.gather": {"biathlon.refill"},
    "biathlon.put": {"biathlon.gather", "biathlon.refill"},
    "biathlon.fetch": {"biathlon.gather"},
    "biathlon.chunk": {"biathlon.run"},
    "biathlon.snapshot": {"biathlon.chunk"},
    "biathlon.screen": {"biathlon.run"},
    "biathlon.readback": {"biathlon.admission", "biathlon.screen"},
}
COUNTERS = {
    "biathlon.run": {"arrivals"},
    "biathlon.admission": {"admission", "lanes", "queue"},
    "biathlon.refill": {"lane"},
    "biathlon.gather": {"rows"},
    "biathlon.put": {"h2d_bytes"},
    "biathlon.fetch": {"d2h_bytes"},
    "biathlon.chunk": {"chunk", "occupied"},
    "biathlon.snapshot": {"d2h_bytes"},
    "biathlon.screen": {"occupied", "lane_iters", "poisoned"},
    "biathlon.readback": {"d2h_bytes"},
}


def _spans(log_dir):
    """``[(name, start_ns, end_ns, counters, parent index)]`` of the
    ``biathlon.*`` host events in the trace under ``log_dir``, in start
    order; a span's parent is the innermost span that holds it."""
    path = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))[-1]
    data = jax.profiler.ProfileData.from_file(str(path))
    found = []
    with warnings.catch_warnings():
        # the profiler's stats type warns once about its module name
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in data.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("biathlon."):
                        found.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      dict(e.stats)))
    found.sort(key=lambda s: (s[1], -s[2]))
    out, open_ = [], []
    for name, start, end, counters in found:
        while open_ and out[open_[-1]][2] < end:
            open_.pop()
        out.append((name, start, end, counters, open_[-1] if open_ else None))
        open_.append(len(out) - 1)
    return out


@pytest.fixture(scope="module")
def server():
    srv = ContinuousBatchedServer(make_small_bundle(), SMALL_CFG, batch_size=4,
                                  chunk_iters=2)
    ContinuousServingRuntime(srv).warmup([a[1] for a in ARRIVALS])
    return srv


@pytest.fixture(scope="module")
def traced(server, tmp_path_factory):
    log_dir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(log_dir)):
        stats = ContinuousServingRuntime(server).run(ARRIVALS, warmup=False)
    return stats, _spans(log_dir)


def test_every_span_appears_nested_as_specified(traced):
    _stats, spans = traced
    assert {s[0] for s in spans} == set(PARENTS)
    for name, _start, _end, counters, parent in spans:
        got = None if parent is None else spans[parent][0]
        assert got in PARENTS[name], (name, got)
        assert set(counters) == COUNTERS[name], name
    # the store's put and the fetch sit inside every gather, the refill's
    # own put beside it
    for i, s in enumerate(spans):
        if s[0] == "biathlon.gather":
            kids = [c[0] for c in spans if c[4] == i]
            assert kids == ["biathlon.put", "biathlon.fetch"]


def test_counters_count_bytes_and_iterations(traced, server):
    stats, spans = traced
    p = server.bundle.pipeline
    k, e = p.k, len(p.exact_features)
    cap = server.trace_cap([a[1] for a in ARRIVALS])
    buf = k * cap * 4
    for name, _s, _e, c, parent in spans:
        if name == "biathlon.put" and spans[parent][0] == "biathlon.refill":
            # vals, n and exact, then delta, tau, iter_cap and lane
            assert c["h2d_bytes"] == buf + 4 * k + 4 * e + 4 * 4
        elif name == "biathlon.put":
            assert c["h2d_bytes"] == buf + 4 * k      # the store's buffer, sizes
        elif name == "biathlon.fetch":
            assert c["d2h_bytes"] == buf
    run = [s for s in spans if s[0] == "biathlon.run"]
    assert len(run) == 1 and run[0][3]["arrivals"] == len(ARRIVALS)
    screens = [s[3] for s in spans if s[0] == "biathlon.screen"]
    chunks = [s[3] for s in spans if s[0] == "biathlon.chunk"]
    assert [c["chunk"] for c in chunks] == list(range(stats.n_chunks))
    assert len(screens) == stats.n_chunks
    assert sum(c["lane_iters"] for c in screens) == sum(r.iters for r in stats.records)
    assert all(c["poisoned"] == 0 for c in screens)
    assert [c["occupied"] for c in chunks] == [c["occupied"] for c in screens]


def test_admission_and_lane_name_each_record(traced):
    stats, spans = traced
    admissions = [s for s in spans if s[0] == "biathlon.admission"]
    assert [s[3]["admission"] for s in admissions] == list(range(stats.n_batches))
    pairs = sorted(
        (spans[s[4]][3]["admission"], s[3]["lane"])
        for s in spans if s[0] == "biathlon.refill"
    )
    assert pairs == sorted((r.batch_id, r.lane) for r in stats.records)
    for s in admissions:
        lanes = [c for c in spans if c[4] == spans.index(s)
                 and c[0] == "biathlon.refill"]
        assert len(lanes) == s[3]["lanes"]


def test_programs_have_stable_names(server):
    table = server.new_table(128)
    assert server._chunk.lower(table).as_text().startswith(
        "module @jit_biathlon_chunk")
    assert server._refill.__name__ == "biathlon_refill"


def test_a_span_without_a_profiler_costs_microseconds():
    n = 20000
    t0 = time.perf_counter()
    for i in range(n):
        with span("x", lane=i):
            pass
    assert (time.perf_counter() - t0) / n < 50e-6


class _SlowReadback:
    """A server whose readback sleeps first."""

    def __init__(self, server, pause_s):
        self._server = server
        self.pause_s = pause_s
        self.readbacks = 0

    def __getattr__(self, name):
        return getattr(self._server, name)

    def readback(self, table):
        self.readbacks += 1
        time.sleep(self.pause_s)
        return self._server.readback(table)


def test_the_run_clock_charges_each_readback_to_its_request(server):
    # one request at a time: each pays the readback after its admission and
    # one after each chunk it sat through, and nothing else changes
    arrivals = [(100.0 * g, {"g": g}) for g in range(6)]

    def records(pause):
        stats = ContinuousServingRuntime(_SlowReadback(server, pause)).run(
            arrivals, warmup=False)
        return {r.req_id: r for r in stats.records}

    pause = 0.05
    fast, slow = records(0.0), records(pause)
    assert any(r.n_chunks for r in slow.values())
    for j, r in slow.items():
        assert r.n_chunks == fast[j].n_chunks
        assert r.exec_s - fast[j].exec_s >= pause * (1 + r.n_chunks) - 0.02
        assert r.admit_t >= arrivals[j][0]
        if j:   # the clock jumped to it; the first waited for the new table
            assert r.queue_delay_s < 5e-3
        assert r.latency_s == pytest.approx(r.queue_delay_s + r.exec_s)


def test_the_makespan_is_the_wall_time_less_backoff(server):
    pause, backoff = 0.03, 0.5
    slow = _SlowReadback(
        FaultyContinuousServer(server, FaultProfile(refill_fail_calls=(0,))),
        pause,
    )
    rt = ContinuousServingRuntime(slow, backoff_s=backoff)
    t0 = time.perf_counter()
    stats = rt.run(ARRIVALS, warmup=False)
    wall = time.perf_counter() - t0
    assert stats.n_retries == 1
    assert all(r.disposition == "ok" for r in stats.records)
    assert abs(stats.makespan_s - backoff - wall) <= 5e-3
    assert stats.makespan_s >= pause * slow.readbacks + backoff
    # the backoff delayed every request of the first admission
    assert min(r.latency_s for r in stats.records) >= backoff


def test_the_run_clock_jumps_idle_time_and_adds_skips():
    clock = RunClock(10.0)
    assert 10.0 <= clock.now() < 10.1
    clock.jump_to(1000.0)
    assert 1000.0 <= clock.now() < 1000.1
    clock.jump_to(5.0)                 # never backwards
    assert clock.now() >= 1000.0
    clock.skip(2.0)
    assert 1002.0 <= clock.now() < 1002.1
    time.sleep(0.02)
    assert clock.now() >= 1002.02

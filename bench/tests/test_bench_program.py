"""The program's spans: read from a profile, the arithmetic on them, and
each reader of them — on a made-up trace, on a short window recorded on a
TPU v5e, and end to end at a tiny size on the CPU."""
from __future__ import annotations

import json
import pathlib
import time

import jax
import numpy as np
import pytest

from bench import harness, manifest, program, trace
from bench.harness import Run
from bench.tests import tiny

RECORDED = pathlib.Path(__file__).parent / "data" / "trace_v5e_battery_median_program.json"

#: readers of the program's spans in a traced run
READERS = ("gather_ms.backlog", "gather_ms.solo", "copy_ms.backlog", "copy_ms.solo",
           "copy_mb.backlog", "refill_device_ms.backlog", "refill_device_ms.solo",
           "input_idle_share.backlog", "input_idle_share.solo",
           "iters_per_chunk.backlog")


def read(name, run):
    return manifest.reader(name)(run)


def _run(tr, served=()):
    return Run(trace=tr, served=list(served), window_s=1.0)


def _made():
    # window 0..1000 ns, one chip.  Two admissions of one lane each: gather
    # 100-160 with the store's put 110-120 and fetch 130-150 inside, the
    # refill's put 160-170; the second admission the same, 500 ns later.
    # A screen after each admission.  Device busy 150-250 and 600-700.
    def admission(t0, adm):
        return [
            ["biathlon.admission", t0 + 90, 200, {"admission": adm, "lanes": 1, "queue": 0}],
            ["biathlon.refill", t0 + 95, 80, {"lane": adm}],
            ["biathlon.gather", t0 + 100, 60, {"rows": 7}],
            ["biathlon.put", t0 + 110, 10, {"h2d_bytes": 1000}],
            ["biathlon.fetch", t0 + 130, 20, {"d2h_bytes": 800}],
            ["biathlon.put", t0 + 160, 10, {"h2d_bytes": 820}],
            ["biathlon.readback", t0 + 250, 30, {"d2h_bytes": 64}],
            ["biathlon.screen", t0 + 300, 40, {"occupied": 1, "lane_iters": 3 + adm,
                                                "poisoned": 0}],
        ]
    return {
        "window": [0, 1000],
        "devices": {"/device:TPU:0": [["fusion", 150, 100], ["while", 600, 100]]},
        "host": [],
        "program": [["biathlon.run", 50, 900, {"arrivals": 2}]]
        + admission(0, 0) + admission(500, 1),
    }


def test_span_arithmetic_on_a_made_up_trace():
    tr = _made()
    prog = tr["program"]
    assert program.admitted(prog) == 2
    assert program.seconds(program.spans(prog, "gather")) == pytest.approx(120e-9)
    puts = program.spans(prog, "put")
    assert len(program.inside(puts, program.spans(prog, "gather"))) == 2
    assert len(program.inside(puts, program.spans(prog, "admission"))) == 4
    assert program.self_seconds(prog, "gather", ("put", "fetch")) == pytest.approx(60e-9)
    assert program.counter_sum(program.spans(prog, "screen"), "lane_iters") == 7
    # admissions cover 90-290 and 590-790; the device is busy 150-250 and
    # 600-700 under them
    busy, idle = program.device_under(tr, program.spans(prog, "admission"))
    assert busy == pytest.approx(200e-9) and idle == pytest.approx(200e-9)


def test_readers_on_a_made_up_trace():
    run = _run(_made())
    # per admitted request, of two
    assert read("gather_ms.backlog", run) == pytest.approx(1e-6 * 60 / 2)
    assert read("copy_ms.solo", run) == pytest.approx(1e-6 * 80 / 2)
    assert read("copy_mb.backlog", run) == pytest.approx(1e-6 * 2 * 2620 / 2)
    assert read("refill_device_ms.backlog", run) == pytest.approx(1e-6 * 200 / 2)
    # gather and the refill's put cover 100-170 and 600-670: the device is
    # busy 150-170 and 600-670 under them, idle 50 ns of the 1000
    assert read("input_idle_share.solo", run) == pytest.approx(100 * 50 / 1000)
    assert read("iters_per_chunk.backlog", run) == pytest.approx(3.5)


def test_readers_read_nothing_where_the_program_writes_no_spans():
    tr = _made()
    del tr["program"]
    for name in READERS:
        assert read(name, _run(tr)) is None, name
        assert read(name, _run(None)) is None, name
        assert read(name, _run(dict(tr, program=[]))) is None, name


def test_queue_wait_is_the_median_of_the_served_records():
    class Rec:
        def __init__(self, q):
            self.queue_delay_s = q
    run = _run(None, [(None, Rec(q)) for q in (0.0, 0.5, 1.0, 2.0, 9.0)])
    assert read("queue_wait_ms.backlog", run) == pytest.approx(1000.0)
    assert read("queue_wait_ms.backlog", _run(None)) is None


def test_events_are_read_from_a_capture(tmp_path):
    from repro.tracing import span

    with jax.profiler.trace(str(tmp_path)):
        with span("admission", admission=4, lanes=2, queue=9):
            with span("put", h2d_bytes=5242880):
                jax.numpy.ones(8).block_until_ready()
        with span("screen", occupied=2) as sp:
            sp.set_metadata(lane_iters=3, poisoned=0)
    got = program.load(tmp_path)
    assert [e[0] for e in got] == ["biathlon.admission", "biathlon.put", "biathlon.screen"]
    assert got[0][3] == {"admission": 4, "lanes": 2, "queue": 9}
    assert got[1][3] == {"h2d_bytes": 5242880}
    assert got[2][3] == {"occupied": 2, "lane_iters": 3, "poisoned": 0}
    assert got[0][1] <= got[1][1] and got[1][1] + got[1][2] <= got[0][1] + got[0][2]


@pytest.fixture(scope="module")
def recorded():
    return json.loads(RECORDED.read_text())


def test_recorded_trace_counts_the_copies_of_each_admission(recorded):
    # battery_median: k = 10 features at cap 131,072.  An admission puts
    # the (k, cap) buffer on the device in the store, fetches it back, and
    # puts it there again with the lane's scalars
    k, cap, e = 10, 131072, 1
    buf = 4 * k * cap
    prog = recorded["program"]
    n = program.admitted(prog)
    assert n > 0
    per_request = buf + 4 * k + buf + buf + 4 * k + 4 * e + 4 * 4
    assert read("copy_mb.backlog", _run(recorded)) == pytest.approx(per_request * 1e-6)
    assert read("copy_mb.backlog", _run(recorded)) == pytest.approx(15.73, abs=0.01)
    for name in READERS:
        value = read(name, _run(recorded))
        assert value is not None and value >= 0, name


def test_recorded_trace_device_time_under_admissions_matches_brute_force(recorded):
    lo, hi = recorded["window"]
    adm = program.spans(recorded["program"], "admission")
    (plane, ops), = recorded["devices"].items()
    grid = np.zeros(int(hi - lo) // 100 + 1, bool)    # 100 ns cells
    for _, st, d in ops:
        a, b = max(st, lo), min(st + d, hi)
        if b > a:
            grid[int((a - lo) // 100):int(np.ceil((b - lo) / 100))] = True
    under = np.zeros_like(grid)
    for s, e_, _ in adm:
        a, b = max(s, lo), min(e_, hi)
        if b > a:
            under[int((a - lo) // 100):int(np.ceil((b - lo) / 100))] = True
    brute = (grid & under).sum() * 100e-9
    busy, idle = program.device_under(recorded, adm)
    slack = 2 * 100e-9 * (len(ops) + len(adm))
    assert busy == pytest.approx(brute, abs=slack)
    assert busy + idle == pytest.approx(under.sum() * 100e-9, abs=slack)
    # the admissions' device time is most of what the device does under them
    assert busy > idle


def test_recorded_trace_names_the_programs_and_nests_the_spans(recorded):
    prog = recorded["program"]
    refills = program.spans(prog, "refill")
    assert len(program.inside(refills, program.spans(prog, "admission"))) == len(refills)
    assert len(program.inside(program.spans(prog, "fetch"),
                              program.spans(prog, "gather"))) == len(refills)
    screens = program.spans(prog, "screen")
    assert screens and all(0 <= c["lane_iters"] <= 8 * 4 for _, _, c in screens)


def test_a_tiny_traced_run_reads_every_program_metric(tmp_path, monkeypatch):
    # the reduced trace with the program's spans beside the device ops
    whole = trace.load
    monkeypatch.setattr(trace, "load",
                        lambda log_dir: dict(whole(log_dir), program=program.load(log_dir)))
    root = tiny.make_root(tmp_path)
    man = manifest.load(root / "BENCHMARK.json")
    runs = []
    finish = harness.finish

    def keep(run, *a, **kw):
        runs.append(run)
        return finish(run, *a, **kw)

    monkeypatch.setattr(harness, "finish", keep)
    res = harness.run_cell(tiny.CELL, 2**31 + 11, 1.0, True, t_start=time.perf_counter(),
                           require_chip=False, man=man, root=root)
    assert res["correct"]
    assert "queue_wait_ms.backlog" in res["metrics"]
    run, = runs
    prog = run.trace["program"]
    admitted = program.admitted(prog)
    assert admitted == res["attempted"]
    # tiny: k = 10 at cap 2,048, one exact feature
    buf = 4 * 10 * 2048
    assert read("copy_mb.backlog", run) * 1e6 == pytest.approx(3 * buf + 4 * 10 * 2 + 4 + 16)
    # the CPU's trace holds no device ops: the device readers read nothing
    on_device = {"refill_device_ms.backlog", "refill_device_ms.solo",
                 "input_idle_share.backlog", "input_idle_share.solo"}
    assert not run.trace["devices"]
    for name in READERS:
        assert (read(name, run) is None) == (name in on_device), name
    # each request's queue wait on the run clock: the median lies inside a round
    assert 0 < read("queue_wait_ms.backlog", run) < 1e3 * run.window_s

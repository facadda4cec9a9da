"""Metric arithmetic: percentiles over all requests, rates over the window,
span shares, work counted from shapes, and the traffic generator."""
from __future__ import annotations

import numpy as np
import pytest

from bench import manifest, spans, traffic, work
from bench.harness import Run


def read(name, run):
    return manifest.reader(name)(run)


def _run(**kw):
    base = dict(served=[], latencies=[], spans=[], window_s=10.0, setup_s=3.0,
                trace=None, peak=None, chips=1)
    base.update(kw)
    return Run(**base)


def test_percentiles_are_over_every_request():
    lat = [0.01] * 90 + [0.5] * 10       # 10% slow requests
    run = _run(latencies=lat)
    assert read("latency_p50_ms", run) == pytest.approx(10.0)
    # the 95th percentile of 100 requests with 10 slow ones is a slow one
    assert read("latency_p95_ms", run) == pytest.approx(500.0)
    assert read("latency_p95_ms", _run()) is None


def test_rate_is_over_the_whole_window():
    run = _run(served=[None] * 250, window_s=12.5)
    assert read("served_rps", run) == pytest.approx(20.0)


def test_span_metrics():
    sp = [("admit", 0.0, 0.2, 8), ("readback", 0.2, 0.21, 1),
          ("snapshot", 0.21, 0.22, 1), ("run_chunk", 0.22, 0.32, 1),
          ("readback", 0.32, 0.33, 1), ("admit", 0.4, 0.45, 2)]
    run = _run(spans=sp, window_s=1.0)
    for mix in ("backlog", "solo"):
        assert read(f"admit_ms.{mix}", run) == pytest.approx(1e3 * 0.25 / 10)
        assert read(f"chunk_ms.{mix}", run) == pytest.approx(100.0)
        assert read(f"chunk_ms.{mix}", _run(spans=sp[:3])) is None
        assert read(f"runtime_self_share.{mix}", run) == pytest.approx(100 * (1 - 0.38))
    assert read("readback_ms.backlog", run) == pytest.approx(1e3 * 0.03 / 2)
    assert spans.totals(sp)["admit"] == pytest.approx((0.25, 2, 10))


def test_request_rows_match_the_paper_counts():
    # turbofan: k=9, m=1000, m_sobol=256 -> 1,001 AMI rows, 2,816 Saltelli
    assert work.request_rows(9, 1000, 256, 0) == 1001
    assert work.request_rows(9, 1000, 256, 1) == 1001 + 2816 + 1001 + 2816
    # battery_median: k=10 -> 4,073 megabatch rows per planner iteration
    assert work.megabatch_rows(10, 1000, 256) == 4073


def test_request_work_from_shapes():
    shape = {"k": 5, "e": 1, "m": 1000, "m_sobol": 256, "ops_per_row": 60 * 5}
    ops, nbytes = work.request_work(shape, [3000] * 5, 2)
    rows = 1001 + 1792 + 2 * 2793
    assert ops == rows * 60 * 5
    assert nbytes == 4 * 15000 + 4 * rows * 6
    ops, nbytes = work.prefix_power_sums_work(9, 131072)
    assert nbytes == 4 * 9 * 131072 * 5 and ops == 8 * 9 * 131072


def test_peaks_are_keyed_by_device_kind():
    peak = work.peaks("TPU v5 lite")
    assert peak["flops"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")
    # memory bound: bytes over bandwidth is the larger time
    assert work.least_seconds(1e6, 819e9, peak) == pytest.approx(1.0)


def test_step_mfu_counts_served_work_over_window_and_chips():
    class Rec:
        z, iters = (100, 100), 0
    shape = {"k": 2, "e": 0, "m": 10, "m_sobol": 4, "ops_per_row": 2 * 3}
    peak = {"flops": 1e3, "hbm_bytes_per_s": 1e12}
    run = _run(served=[(None, Rec())] * 4, shape=shape, peak=peak,
               window_s=2.0, chips=4)
    ops = 4 * 11 * 2 * 3
    assert read("step_mfu.backlog", run) == pytest.approx(100 * ops / 1e3 / 8.0)


def test_traffic_rounds_are_balanced_and_fixed_by_the_seed():
    reqs = [{"gid": g} for g in range(6)]
    mix = {"round_size": 9, "spacing_s": 2.0, "rounds": 4}
    a = traffic.rounds(mix, reqs, 2**33 + 5)
    b = traffic.rounds(mix, reqs, 2**33 + 5)
    assert a == b
    assert [len(r) for r in a] == [9] * 4
    assert [t for t, _ in a[0]] == [2.0 * i for i in range(9)]
    gids = [req["gid"] for rnd in a for _, req in rnd]
    assert np.bincount(gids, minlength=6).tolist() == [6] * 6
    # every seed sends the same rounds, each seed in its own order
    others = [traffic.rounds(mix, reqs, s) for s in (7, 8, 9)]
    assert all(sorted(map(str, o)) == sorted(map(str, a)) for o in others)
    assert any(o != a for o in others)
    with pytest.raises(ValueError):
        traffic.check(dict(mix, keys="zipf"))
    with pytest.raises(ValueError):
        traffic.check({"round_size": 9, "rounds": 4})

"""Trace reduction: busy time, idle gaps by host activity, op totals — on a
hand-made trace and on a short window recorded on a TPU v5e."""
from __future__ import annotations

import json
import pathlib

import numpy as np
import pytest

from bench import manifest, trace

RECORDED = pathlib.Path(__file__).parent / "data" / "trace_v5e_sensor_health.json"


def is_kernel(name):
    return manifest.reader_module("prefix_power_sums_roofline.backlog").is_kernel(name)


def _made():
    # window 0..100 ns; chip A busy 10-30 and 20-40 (overlap) and 90-110
    # (clipped); chip B busy 0-50
    return {
        "window": [0, 100],
        "devices": {
            "/device:TPU:0": [["fusion", 10, 20], ["prefix_power_sums.1", 20, 20],
                              ["fusion", 90, 20]],
            "/device:TPU:1": [["sort", 0, 50]],
        },
        "host": [["bench.admit", 0, 45], ["bench.readback", 60, 10]],
    }


def test_busy_is_the_union_of_op_intervals_averaged_over_chips():
    tr = _made()
    assert trace.window_s(tr) == pytest.approx(100e-9)
    assert trace.busy_s(tr) == pytest.approx((40 + 50) / 2 * 1e-9)


def test_idle_gaps_are_named_by_the_host_activity_over_them():
    got = dict(trace.idle_by_host(_made()))
    # chip A idle 0-10 (admit), 40-90 (admit 40-45, readback 60-70, rest
    # runtime); chip B idle 50-100 (readback 60-70, runtime 40)
    assert got["admit"] == pytest.approx((10 + 5) / 2 * 1e-9)
    assert got["readback"] == pytest.approx((10 + 10) / 2 * 1e-9)
    assert got["runtime"] == pytest.approx((35 + 40) / 2 * 1e-9)
    assert sum(got.values()) == pytest.approx(100e-9 - trace.busy_s(_made()))


def test_op_totals_keep_only_ops_inside_the_window():
    tr = _made()
    assert trace.op_seconds(tr, is_kernel) == (pytest.approx(20e-9), 1)
    top = trace.top_ops(tr)
    assert top[0][0] == "sort" and top[0][1] == pytest.approx(25e-9)
    assert [name for name, _ in top] == ["sort", "fusion", "prefix_power_sums.1"]


@pytest.fixture(scope="module")
def recorded():
    return json.loads(RECORDED.read_text())


def test_recorded_trace_busy_matches_a_brute_force_union(recorded):
    lo, hi = recorded["window"]
    assert recorded["devices"]
    for plane, ops in recorded["devices"].items():
        one = dict(recorded, devices={plane: ops})
        grid = np.zeros(int(hi - lo) // 100 + 1, bool)  # 100 ns cells
        for _, st, d in ops:
            a, b = max(st, lo), min(st + d, hi)
            if b > a:
                grid[int((a - lo) // 100):int(np.ceil((b - lo) / 100))] = True
        brute = grid.sum() * 100e-9
        assert trace.busy_s(one) == pytest.approx(brute, abs=2 * 100e-9 * len(ops))
    busy = trace.busy_s(recorded)
    assert 0 < busy <= trace.window_s(recorded)
    idle = sum(s for _, s in trace.idle_by_host(recorded, n=100))
    assert idle == pytest.approx(trace.window_s(recorded) - busy, rel=1e-9)


def test_recorded_trace_names_the_kernel_and_the_host_calls(recorded):
    seconds, calls = trace.op_seconds(recorded, is_kernel)
    assert calls > 0 and seconds > 0
    names = {name for name, _, _ in recorded["host"]}
    assert {"bench.admit", "bench.readback"} <= names
    top = trace.top_ops(recorded)
    assert len(top) <= 10
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)

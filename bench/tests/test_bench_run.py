"""The harness end to end on the CPU at a tiny size: a sound run is correct,
the control and each fault the cell can have are not, and the command
refuses to run without a TPU."""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

from bench import harness, manifest
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench_root"))


@pytest.fixture(scope="module")
def man(root):
    return manifest.load(root / "BENCHMARK.json")


def _run(man, root, wrap=None, traced=False, control=False):
    return harness.run_cell(tiny.CELL, 2**31 + 11, 1.0, traced,
                            t_start=time.perf_counter(), require_chip=False,
                            man=man, root=root, wrap=wrap, control=control)


@pytest.fixture(scope="module")
def sound(man, root):
    return _run(man, root, control=True)


def test_a_sound_run_is_correct(sound):
    assert sound["correct"], sound["checks"]
    assert sound["failed"] == 0 and sound["attempted"] > 0
    assert set(sound["metrics"]) == {"served_rps", "latency_p50_ms",
                                     "latency_p95_ms", "setup_s"}
    assert list(sound)[-1] == "checks"
    assert sound["checks"]["window_compiles"]["value"] == 0


def test_the_bfloat16_control_is_not_correct(sound):
    # the control stands in the program's place, through the same checks
    ctl = sound["control"]
    assert ctl["correct"] is False, ctl["checks"]
    assert set(ctl["checks"]) == set(sound["checks"])


def test_traced_run_reports_layer_metrics(man, root):
    res = _run(man, root, traced=True)
    assert res["correct"]
    assert {"admit_ms.backlog", "runtime_self_share.backlog",
            "readback_ms.backlog"} <= set(res["metrics"])
    assert res["device"]["window_s"] > 0
    assert "breakdown" in res


class _Stuck:
    """A chunk that returns the lane table as it came."""

    def __init__(self, server):
        self._server = server

    def __getattr__(self, name):
        return getattr(self._server, name)

    def run_chunk(self, table):
        return table


class _Altered(_Stuck):
    """Every answer moved by a tenth of δ where the executables produce it."""

    def _shift(self, table):
        delta = self._server.bundle.pipeline.delta_default
        return table._replace(y_hat=table.y_hat + 0.1 * delta)

    def admit(self, table, cap, assignments):
        table, rows = self._server.admit(table, cap, assignments)
        return self._shift(table), rows

    def run_chunk(self, table):
        return self._shift(self._server.run_chunk(table))


def test_a_step_that_returns_its_state_unchanged_is_caught(man, root):
    res = _run(man, root, wrap=_Stuck)
    assert not res["correct"]
    assert res["checks"]["unserved"]["value"] > 0


def test_an_answer_altered_where_it_is_produced_is_caught(man, root):
    res = _run(man, root, wrap=_Altered)
    assert not res["correct"]
    assert res["checks"]["yhat_gap"]["value"] > res["checks"]["yhat_gap"]["limit"]


def test_half_of_each_group_left_out_is_caught(man, root, monkeypatch):
    from repro.serving import continuous

    whole = continuous.lane_request_inputs

    def half(pipeline, store, req, cap):
        vals, n, true_n, exact = whole(pipeline, store, req, cap)
        return vals, n // 2, true_n // 2, exact

    monkeypatch.setattr(continuous, "lane_request_inputs", half)
    res = _run(man, root)
    assert not res["correct"]
    assert res["checks"]["plan_invalid"]["value"] > 0


def test_a_skipped_guarantee_check_is_caught(man, root):
    def low_tau(server):
        server.config = dataclasses.replace(server.config, tau=0.5)
        return server

    res = _run(man, root, wrap=low_tau)
    assert not res["correct"]
    assert res["checks"]["stop_unjustified"]["value"] > 0


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "turbofan.backlog",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_the_command_exits_nonzero_without_a_tpu():
    assert jax.default_backend() == "cpu"
    out = _cli(manifest.ROOT)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert not out.stdout.strip().startswith("{")


def test_the_command_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(manifest.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(manifest.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _cli(tmp_path)
    assert out.returncode != 0
    assert all(not line.startswith("{") for line in out.stdout.splitlines())
    with pytest.raises(json.JSONDecodeError):
        json.loads(out.stdout.splitlines()[-1] if out.stdout.strip() else "")

"""The harness on a classification cell (δ = 0), end to end on the CPU: a
tiny root shaped like Biathlon's Student-QA (Table 1: a random forest over
21 AGG features, binary classification).  A sound run is correct; the
bfloat16 control and each planted fault are not."""
from __future__ import annotations

import dataclasses
import json
import time

import pytest

from bench import harness, manifest
from bench.tests import tiny

#: QMC rows of the AMI stage.  At 64 the bfloat16 control moves no row's
#: class on this cell, so the cell keeps the paper's m
M = 1000


def student_qa() -> dict:
    """The Student-QA pipeline of ``data/synthetic.py`` as a configuration:
    8 AVG, 4 STD, 3 COUNT, 2 SUM and 4 VAR over eleven game-log columns."""
    base = json.loads((manifest.BENCH_DIR / "configs" / "turbofan.json").read_text())
    ops = ([("avg", f"c{i}") for i in range(1, 9)]
           + [("std", f"c{i}") for i in range(1, 5)]
           + [("count", f"f{i}") for i in range(1, 4)]
           + [("sum", "c5"), ("sum", "c6")]
           + [("var", f"c{i}") for i in range(5, 9)])
    return {
        "name": "student_qa", "source": "Biathlon (arXiv:2405.11191) Table 1, Student-QA",
        "pipeline": "student_qa", "table": "gamelog", "task": "classification",
        "model": {"kind": "random_forest", "trees": 40, "depth": 8},
        "features": [{"op": op, "column": col} for op, col in ops], "exact": [],
        "precision": "float32", "size": base["size"], "planner": base["planner"],
        "serving": base["serving"],
        # a class must match; one QMC row of another class moves prob by 1/m
        "checks": {"yhat_gap": {"limit": 0}, "prob_gap": {"limit": 0.5 / M}},
    }


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench_cls"), student_qa(), m=M)


@pytest.fixture(scope="module")
def man(root):
    return manifest.load(root / "BENCHMARK.json")


def _run(man, root, wrap=None, control=False):
    return harness.run_cell(tiny.CELL, 2**31 + 23, 1.0, False,
                            t_start=time.perf_counter(), require_chip=False,
                            man=man, root=root, wrap=wrap, control=control)


@pytest.fixture(scope="module")
def sound(man, root):
    return _run(man, root, control=True)


def test_a_sound_classification_run_is_correct(sound):
    assert sound["correct"], sound["checks"]
    assert sound["failed"] == 0 and sound["attempted"] > 0
    assert sound["checks"]["yhat_gap"]["value"] == 0.0
    assert sound["checks"]["prob_gap"]["value"] < sound["checks"]["prob_gap"]["limit"]


def test_the_bfloat16_control_is_not_correct_on_classification(sound):
    ctl = sound["control"]
    assert ctl["correct"] is False, ctl["checks"]
    assert ctl["checks"]["prob_gap"]["value"] >= 1.0 / M


class _Lane0:
    """Changes what the refill produces in lane 0, for each request admitted
    there: ``alter(table)`` returns the table with lane 0 altered."""

    def __init__(self, server, alter):
        self._server, self._alter = server, alter

    def __getattr__(self, name):
        return getattr(self._server, name)

    def admit(self, table, cap, assignments):
        table, rows = self._server.admit(table, cap, assignments)
        if any(lane == 0 for lane, _, _ in assignments):
            table = self._alter(table)
        return table, rows


def test_one_lane_with_its_class_inverted_is_caught(man, root):
    def invert(table):
        return table._replace(y_hat=table.y_hat.at[0].set(1.0 - table.y_hat[0]))

    res = _run(man, root, wrap=lambda server: _Lane0(server, invert))
    assert not res["correct"]
    assert res["checks"]["yhat_gap"]["value"] == 1.0


def test_prob_moved_by_one_row_is_caught(man, root):
    def lower(table):
        return table._replace(prob=table.prob.at[0].add(-1.0 / M))

    res = _run(man, root, wrap=lambda server: _Lane0(server, lower))
    assert not res["correct"]
    assert res["checks"]["prob_gap"]["value"] > res["checks"]["prob_gap"]["limit"]


def test_a_skipped_class_guarantee_check_is_caught(man, root):
    def low_tau(server):
        server.config = dataclasses.replace(server.config, tau=0.5)
        return server

    res = _run(man, root, wrap=low_tau)
    assert not res["correct"]
    assert res["checks"]["stop_unjustified"]["value"] > 0

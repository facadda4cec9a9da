"""The plain reference: estimators, QMC points, trees, plans, and a control
in bfloat16 that it tells apart."""
from __future__ import annotations

import math
import types

import numpy as np
import pytest

from bench import reference as ref
from bench import trees


def test_parametric_estimates_follow_the_clt_formulas():
    rng = np.random.default_rng(0)
    x = rng.normal(3.0, 2.0, 5000)
    z, n = 400, 5000
    s = x[:z]
    fpc = math.sqrt((n - z) / (n - 1))
    v, sig = ref.parametric("avg", x, z, n)
    assert v == pytest.approx(s.mean())
    assert sig == pytest.approx(s.std(ddof=1) / math.sqrt(z) * fpc)
    v, sig = ref.parametric("sum", x, z, n)
    assert v == pytest.approx(n * s.mean())
    v, _ = ref.parametric("std", x, z, n)
    assert v == pytest.approx(s.std(ddof=1))
    # the whole group: exact, no uncertainty
    v, sig = ref.parametric("avg", x, n, n)
    assert v == pytest.approx(x.mean()) and sig == 0.0


def test_nearest_rank_and_exact_quantile():
    assert ref.nearest_rank(0.5, 5) == 2
    assert ref.nearest_rank(0.9, 11) == 9
    assert ref.nearest_rank(0.5, 0) == 0


def test_ami_uniforms_are_sobol_points_at_cell_centres():
    u = ref.ami_uniforms(8, 3)
    assert u[0].tolist() == [0.5 / 2**32] * 3
    assert u[1].tolist() == pytest.approx([0.5] * 3)
    assert np.all((u > 0) & (u < 1))


def _stump():
    # one tree: x0 <= 0.5 -> 1.0 else 3.0; a second: x1 <= 0 -> -1 else 1
    feature = np.array([[0, 0, 0], [1, 0, 0]])
    threshold = np.array([[0.5, 0, 0], [0.0, 0, 0]])
    left = np.array([[1, 1, 2], [1, 1, 2]])
    right = np.array([[2, 1, 2], [2, 1, 2]])
    value = np.array([[0, 1.0, 3.0], [0, -1.0, 1.0]])
    return ref.Trees(feature, threshold, left, right, value, 1, 10.0, True,
                     np.zeros(2), np.ones(2))


def test_trees_predict_and_interval():
    t = _stump()
    ys = t.predict(np.array([[0.0, -1.0], [1.0, 1.0]]))
    assert ys.tolist() == [10.0, 12.0]
    assert t.interval(np.array([0.0, -1.0])) == (10.0, 10.0)
    # x0 sits on its threshold to within the ambiguity: either leaf
    lo, hi = t.interval(np.array([0.5 + 1e-6, -1.0]))
    assert (lo, hi) == (10.0, 11.0)


def test_guarantee_probability():
    y = np.array([9.0, 10.0, 11.0])
    sd = math.sqrt(2 / 3)
    want = math.erf(1.0 / sd / math.sqrt(2))
    assert ref.guarantee_prob(10.0, y, 1.0) == pytest.approx(want)
    assert ref.guarantee_prob(10.0, np.full(5, 10.2), 0.5) == 1.0
    assert ref.guarantee_prob(10.0, np.full(5, 10.7), 0.5) == 0.0


def test_plan_reachability():
    n = [1000, 2000]
    step = math.ceil(0.01 * 3000)       # 30 rows per iteration
    z0 = [50, 100]
    assert ref.plan_ok(z0, n, 0, 0.05, 0.01)
    assert ref.plan_ok([50 + 2 * step, 100 + step], n, 3, 0.05, 0.01)
    assert not ref.plan_ok([50 + 2 * step, 100 + step], n, 2, 0.05, 0.01)
    assert not ref.plan_ok([51, 100], n, 0, 0.05, 0.01)
    # a feature clipped at its whole group counts its steps up to N
    assert ref.plan_ok([1000, 100], n, math.ceil(950 / step), 0.05, 0.01)
    # a plan drawn from half of each group is not a plan of the whole group
    assert not ref.plan_ok([25, 50], n, 0, 0.05, 0.01)


def test_bfloat16_control_differs_from_the_reference():
    rng = np.random.default_rng(1)
    x = rng.normal(40.0, 3.0, 20000)
    v64, _ = ref.parametric("sum", x, 3000, 20000)
    vbf, _ = ref.parametric("sum", x, 3000, 20000, ref.Precision("bfloat16"))
    assert 1e-4 < abs(vbf - v64) / abs(v64) < 2e-2
    with pytest.raises(ValueError):
        ref.Precision("float16")


def test_intervals_of_rows_match_the_interval_of_each_row_to_the_bit():
    rng = np.random.default_rng(3)
    t = ref.Trees(*_forest(rng), 4, 0.25, True, np.zeros(3), np.ones(3))
    rows = rng.normal(0.0, 1.0, (400, 3))
    rows[:40, t.feature[0, 0]] = t.threshold[0, 0] + 1e-6   # on a root's threshold
    lo, hi = t.intervals(rows)
    want = np.array([t.interval(row) for row in rows])
    assert lo.tolist() == want[:, 0].tolist() and hi.tolist() == want[:, 1].tolist()
    assert np.all(lo[:40] < hi[:40])
    assert np.array_equal(lo[40:][lo[40:] == hi[40:]],
                          t.predict(rows[40:])[lo[40:] == hi[40:]])


def _forest(rng, n_trees=5, depth=4):
    nodes = 2 ** (depth + 1) - 1
    inner = 2 ** depth - 1
    idx = np.arange(nodes)
    left = np.where(idx < inner, 2 * idx + 1, idx)
    right = np.where(idx < inner, 2 * idx + 2, idx)
    feature = rng.integers(0, 3, (n_trees, nodes))
    threshold = rng.normal(0.0, 0.7, (n_trees, nodes))
    value = rng.normal(0.0, 0.3, (n_trees, nodes))
    return (feature, threshold, np.broadcast_to(left, (n_trees, nodes)),
            np.broadcast_to(right, (n_trees, nodes)), value)


def _stump_kind():
    """A model kind of one stump: score 0.75 where x0 > 0.5, else 0.25,
    class 1 above 0.5."""
    stump = ref.Trees(np.array([[0, 0, 0]]), np.array([[0.5, 0, 0]]),
                      np.array([[1, 1, 2]]), np.array([[2, 1, 2]]),
                      np.array([[0, -0.25, 0.25]]), 1, 0.5, True,
                      np.zeros(1), np.ones(1))
    return types.SimpleNamespace(of=lambda _pipeline: stump, raw=trees.raw,
                                 interval=trees.interval,
                                 ops_per_row=trees.ops_per_row, threshold=0.5)


def test_classes_allowed_at_a_clear_and_a_straddling_point():
    model = ref.Model(_stump_kind(), None, "classification")
    # x0 = 0.9: class 1 only; x0 = 0.1: class 0 only
    lo, hi = model.interval(np.array([[0.9], [0.1], [0.5 + 1e-6]]))
    low, high = model.classes(lo, hi)
    assert low.tolist() == [1.0, 0.0, 0.0] and high.tolist() == [1.0, 0.0, 1.0]
    # a score within the ambiguity of the boundary allows both classes
    low, high = model.classes(np.array([0.5 + 1e-5]), np.array([0.5 + 1e-5]))
    assert (low[0], high[0]) == (0.0, 1.0)
    assert model.ops_per_row() == 1


def _cls_answer(model, point, rows):
    lo, hi = model.interval(np.array([point]))
    ami_lo, ami_hi = model.interval(np.array(rows))
    return ref.Answer(float(model.raw(np.array([point]))[0]), lo[0], hi[0],
                      model.raw(np.array(rows)), ami_lo, ami_hi)


def test_class_gaps_and_the_share_interval():
    model = ref.Model(_stump_kind(), None, "classification")
    # four QMC rows: two surely class 1, one surely 0, one on the boundary
    ans = _cls_answer(model, [0.9], [[0.9], [0.8], [0.1], [0.5 + 1e-6]])
    # ŷ = 1 is allowed; its share lies in [2/4, 3/4]
    got = ref.class_gaps({"y_hat": 1.0, "prob": 0.75}, ans, model)
    assert got == {"yhat_gap": 0.0, "prob_gap": 0.0, "prob_ref": 0.75}
    got = ref.class_gaps({"y_hat": 1.0, "prob": 0.25}, ans, model)
    assert got["prob_gap"] == 0.25
    # ŷ = 0 is not allowed at a clear point
    got = ref.class_gaps({"y_hat": 0.0, "prob": 0.5}, ans, model)
    assert got["yhat_gap"] == 1.0 and got["prob_ref"] == 0.5
    # at a straddling point both classes are allowed
    ans = _cls_answer(model, [0.5 + 1e-6], [[0.9]] * 4)
    for y in (0.0, 1.0):
        assert ref.class_gaps({"y_hat": y, "prob": y}, ans, model)["yhat_gap"] == 0.0
    # what a program computing this answer would serve: its class and share
    assert ref.served(_cls_answer(model, [0.9], [[0.9], [0.1]]), model, 0.0) == \
        {"y_hat": 1.0, "prob": 0.5}

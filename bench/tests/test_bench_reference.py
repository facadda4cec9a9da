"""The plain reference: estimators, QMC points, trees, plans, and a control
in bfloat16 that it tells apart."""
from __future__ import annotations

import math

import numpy as np
import pytest

from bench import reference as ref


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

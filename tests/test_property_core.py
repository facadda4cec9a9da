"""Property-based coverage for the planner and the Eq. 1 guarantee.

``core/planner.py`` and ``core/guarantee.py`` previously had only
example-based tests; these properties pin down the invariants the fused
while-loop executor silently relies on:

* plans are monotone non-decreasing across iterations and never exceed n
  (the prefix-mask trick is only sound for growing prefixes);
* the step direction is one-hot over non-exhausted features (or zero when
  every feature is exhausted);
* the guarantee probability is a true probability, monotone in the error
  budget delta, and CONSERVATIVE: whenever ``satisfied`` reports ok, a
  Monte-Carlo estimate of Pr(|Y − ŷ| ≤ δ) under the same Normal model
  is at least tau (up to MC noise).

Runs under the optional-hypothesis shim: with hypothesis installed
(requirements-dev.txt / CI) each property is fuzzed; without it the tests
collect as clean skips.
"""
import jax.numpy as jnp
import numpy as np
from hypothesis_compat import example, given, settings, st

from repro.core.guarantee import regression_prob, satisfied
from repro.core.planner import direction, gamma_abs, initial_plan, next_plan
from repro.core.propagation import InferenceUncertainty

_sizes = st.lists(st.integers(min_value=0, max_value=20_000), min_size=1, max_size=6)


def _unc(y_hat, mean, std):
    return InferenceUncertainty(
        y_hat=jnp.asarray(y_hat, jnp.float32),
        mean=jnp.asarray(mean, jnp.float32),
        std=jnp.asarray(std, jnp.float32),
        probs=jnp.zeros((0,), jnp.float32),
        samples=jnp.zeros((0,), jnp.float32),
    )


# ------------------------------------------------------------------ planner
@settings(max_examples=60, deadline=None)
@given(_sizes, st.floats(min_value=1e-4, max_value=0.9))
@example([17697], 0.0340736)  # ceil(alpha·N): 603 in float32, 604 in float64
def test_initial_plan_within_bounds(sizes, alpha):
    n = jnp.asarray(sizes, jnp.int32)
    z0 = np.asarray(initial_plan(n, alpha))
    assert (z0 <= sizes).all(), "z0 may never exceed the group size"
    assert (z0 >= np.minimum(2, sizes)).all(), "need >= 2 samples for a variance"
    # the planner computes alpha·N in float32; so does the oracle (in float64
    # a product just above an integer can round below it in float32)
    ratio = np.ceil(np.float32(alpha) * np.asarray(sizes, np.float32))
    assert (z0 >= np.minimum(ratio, sizes)).all()


@settings(max_examples=40, deadline=None)
@given(
    _sizes,
    st.floats(min_value=1e-4, max_value=0.5),
    st.floats(min_value=1e-3, max_value=0.2),
    st.lists(
        st.lists(st.floats(min_value=0.0, max_value=1.0), min_size=6, max_size=6),
        min_size=1,
        max_size=8,
    ),
)
def test_plans_monotone_and_bounded_across_iterations(sizes, alpha, gamma, idx_rows):
    """Replaying the planner against arbitrary Sobol-index sequences: z is
    monotone non-decreasing, never exceeds n, and each iteration grows at
    most one feature by at most the absolute step."""
    n = jnp.asarray(sizes, jnp.int32)
    k = len(sizes)
    step = gamma_abs(n, gamma)
    assert int(step) >= 1
    z = initial_plan(n, alpha)
    for row in idx_rows:
        indices = jnp.asarray(row[:k], jnp.float32)
        d = direction(indices, z, n)
        z_next = next_plan(z, d, step, n)
        dz = np.asarray(z_next) - np.asarray(z)
        assert (dz >= 0).all(), "plans must be monotone non-decreasing"
        assert (np.asarray(z_next) <= np.asarray(n)).all(), "z may never exceed n"
        assert (dz > 0).sum() <= 1, "LFP direction grows at most one feature"
        assert dz.sum() <= int(step)
        z = z_next


@settings(max_examples=60, deadline=None)
@given(
    _sizes,
    st.lists(st.floats(min_value=0.0, max_value=1.0), min_size=6, max_size=6),
    st.data(),
)
def test_direction_one_hot_over_non_exhausted(sizes, idx_row, data):
    n = np.asarray(sizes, np.int64)
    z_list = [data.draw(st.integers(min_value=0, max_value=int(nj))) for nj in n]
    z = jnp.asarray(z_list, jnp.int32)
    indices = jnp.asarray(idx_row[: len(sizes)], jnp.float32)
    d = np.asarray(direction(indices, z, jnp.asarray(n, jnp.int32)))
    assert set(np.unique(d)) <= {0, 1}
    if (np.asarray(z_list) >= n).all():
        assert d.sum() == 0, "all-exhausted plans have no direction"
    else:
        assert d.sum() == 1, "direction is one-hot"
        assert z_list[int(np.argmax(d))] < n[int(np.argmax(d))], (
            "the selected feature must have samples remaining"
        )


# ---------------------------------------------------------------- guarantee
@settings(max_examples=60, deadline=None)
@given(
    st.floats(min_value=-50, max_value=50),
    st.floats(min_value=-50, max_value=50),
    st.floats(min_value=0.0, max_value=20.0),
    st.floats(min_value=0.0, max_value=10.0),
    st.floats(min_value=0.0, max_value=10.0),
)
# a mean below float32's range, and a float32 subnormal mean
@example(y_hat=0.0, mean=3.631301926445762e-155, std=0.0, d1=0.0, d2=0.0)
@example(y_hat=0.0, mean=1.401298464324817e-45, std=0.0, d1=0.0, d2=0.0)
def test_guarantee_prob_bounded_and_monotone_in_delta(y_hat, mean, std, d1, d2):
    lo, hi = sorted((d1, d2))
    u = _unc(y_hat, mean, std)
    p_lo = float(regression_prob(u, jnp.asarray(lo, jnp.float32)))
    p_hi = float(regression_prob(u, jnp.asarray(hi, jnp.float32)))
    assert -1e-6 <= p_lo <= 1 + 1e-6 and -1e-6 <= p_hi <= 1 + 1e-6
    assert p_hi >= p_lo - 1e-6, "a wider error budget can only help"
    if std == 0.0:
        # degenerate sigma: exact indicator, not NaN
        assert p_hi in (0.0, 1.0)
        # the indicator is evaluated in XLA's float32, like the guarantee
        bias = _xla_f32(_xla_f32(mean) - _xla_f32(y_hat))
        assert p_hi == float(abs(bias) <= _xla_f32(hi))


def _xla_f32(x):
    """``x`` as XLA holds it in float32: subnormals flush to zero."""
    v = np.float32(x)
    return v if abs(v) >= np.finfo(np.float32).tiny else np.float32(0.0)


@settings(max_examples=25, deadline=None)
@given(
    st.floats(min_value=-5, max_value=5),     # bias = mean - y_hat
    st.floats(min_value=1e-3, max_value=5.0),  # std
    st.floats(min_value=1e-2, max_value=10.0),  # delta
    st.floats(min_value=0.5, max_value=0.99),  # tau
)
def test_guarantee_conservative_under_random_specs(bias, std, delta, tau):
    """Eq. 1's analytic probability must match (within MC noise) the TRUE
    Pr(|Y − ŷ| ≤ δ) of the Normal inference-uncertainty model it claims to
    bound — so ``ok`` is never granted to a spec whose real coverage is
    materially below tau."""
    u = _unc(0.0, bias, std)
    prob, ok = satisfied(u, delta, tau, "regression")
    prob = float(prob)
    rng = np.random.default_rng(12345)
    y = rng.normal(bias, std, 20_000)
    empirical = float(np.mean(np.abs(y) <= delta))
    mc_noise = 3.5 * np.sqrt(max(empirical * (1 - empirical), 1e-4) / 20_000)
    assert abs(prob - empirical) <= mc_noise + 1e-3
    if bool(ok):
        assert empirical >= tau - mc_noise - 1e-3, (
            "satisfied() granted a spec whose true coverage misses tau"
        )

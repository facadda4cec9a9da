"""Incremental AFC (prefix-stats precompute) — the PR-5 tentpole contract.

Covers, in order: compensated-accumulation precision at 60k rows (the
5-power-sum fp fix), prefix-table kernel/oracle parity at non-divisible
shapes, the O(1) query path vs the full-pass oracles at the z edges,
holistic rank-index queries vs the sort oracle over the whole plan ladder,
incremental-vs-rescan executor parity (bitwise z-plans), the while-body
HLO-cost flatness claim, and the serving buffer-donation (no-copy)
contract.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.executor import BiathlonConfig
from repro.core.executor_fused import build_fused_executor
from repro.core.pipeline import AggFeature, Pipeline
from repro.data.store import ColumnStore, build_table
from repro.data.synthetic import PipelineBundle, make_pipeline
from repro.kernels.sampled_agg.compensated import comp_cumsum, comp_sum
from repro.kernels.sampled_agg.ops import (
    beta_order_stat,
    bootstrap_rank_targets,
    finish_quantile_estimates,
    masked_estimates,
    masked_quantile_estimates,
    prefix_power_sums as prefix_power_sums_dispatch,
)
from repro.kernels.sampled_agg.prefix_stats import (
    block_c,
    build_rank_index,
    prefix_moments_at,
    prefix_power_sums,
    prefix_power_sums_ref,
    select_ranks_indexed,
)
from repro.kernels.sampled_agg.ref import (
    masked_select_ranks_ref,
    sampled_moments_ref,
)
from repro.kernels.sampled_agg.sampled_agg import sampled_moments
from repro.launch.hlo_cost import while_costs
from repro.models.tabular import LinearRegression
from repro.serving import BatchedFusedServer, BiathlonServer

SMALL = dict(rows_per_group=1200, n_train_groups=100, n_serve_groups=5, n_requests=4)


# --------------------------------------------------- fp accumulation @ 60k
def _heavy_tailed(n=60000, seed=7):
    """One dominant burst + a dense small tail: the Σv⁴ drift scenario."""
    rng = np.random.default_rng(seed)
    v = rng.normal(1.25, 0.12, n).astype(np.float32)
    v[0] = 100.0  # v⁴ = 1e8; each tail element contributes ~2.4
    return v


def test_power_sums_compensated_at_60k():
    """All four power-sum paths stay within 1e-6 of float64 at n=60k, where
    a naive sequential f32 accumulator (the streaming-AFC baseline this
    guards against) drifts by ~1e-3 on Σv⁴."""
    v = _heavy_tailed()
    n = v.size
    vals = jnp.asarray(v[None, :])
    z = jnp.asarray([n], jnp.int32)
    want = np.array(
        [n] + [float((v.astype(np.float64) ** p).sum()) for p in range(1, 5)]
    )
    for name, got in [
        ("ref", sampled_moments_ref(vals, z)),
        ("kernel", sampled_moments(vals, z, interpret=True)),
    ]:
        rel = np.abs(np.asarray(got)[0] - want) / np.abs(want)
        assert rel.max() < 1e-6, (name, rel)
    # prefix tables: every cumulative position, not just the total, on
    # battery_median's k = 10 rows of independent heavy-tailed groups (the
    # kernel's 8 grid steps and every row's Kahan carry)
    rows = np.stack([v] + [_heavy_tailed(seed=s) for s in range(1, 10)])
    f64 = np.stack(
        [(rows.astype(np.float64) ** p).cumsum(axis=1) for p in range(1, 5)],
        axis=1,
    )
    for name, tab in [
        ("prefix_ref", prefix_power_sums_ref(jnp.asarray(rows))),
        ("prefix_kernel", prefix_power_sums(jnp.asarray(rows), interpret=True)),
    ]:
        rel = np.max(np.abs(np.asarray(tab) - f64) / (np.abs(f64) + 1e-30))
        assert rel < 1e-6, (name, rel)
    # the naive baseline really does lose the tail: strictly-sequential f32
    seq = np.float32(0.0)
    for x in v:
        seq = np.float32(seq + np.float32(x) ** 4)
    assert abs(seq - want[4]) / want[4] > 1e-4, "scenario lost its teeth"


def test_comp_sum_matches_f64_where_plain_f32_cannot():
    """comp_sum/comp_cumsum recover increments far below the running sum's
    f32 ulp (carry 1e8, increments of 3 -> plain sequential f32 drops them
    all)."""
    x = np.full(60000, 3.0, np.float32)
    x[0] = 1.0e8
    want = 1.0e8 + 3.0 * (x.size - 1)
    got = float(comp_sum(jnp.asarray(x)))
    assert abs(got - want) / want < 1e-7
    cum = np.asarray(comp_cumsum(jnp.asarray(x)))
    want_cum = 1.0e8 + 3.0 * np.arange(x.size)
    assert np.max(np.abs(cum - want_cum) / want_cum) < 1e-7


def test_beta_order_stat_matches_beta_moments():
    """The fixed-round MT sampler is distributionally Beta(a, b): mean and
    variance match the analytic moments within MC error across the regimes
    the bootstrap hits (small/large/asymmetric integer params)."""
    n = 100_000
    for i, (a, b) in enumerate([(1.0, 1.0), (2.0, 5.0), (50.0, 50.0),
                                (3277.0, 29000.0), (10000.0, 10.0)]):
        s = np.asarray(
            beta_order_stat(
                jax.random.PRNGKey(i), jnp.asarray(a), jnp.asarray(b), (n,)
            ),
            np.float64,
        )
        mean = a / (a + b)
        var = a * b / ((a + b) ** 2 * (a + b + 1.0))
        assert (s > 0).all() and (s < 1).all()
        assert abs(s.mean() - mean) < 5.0 * np.sqrt(var / n) + 1e-6, (a, b)
        assert abs(s.var() - var) < 0.05 * var + 1e-9, (a, b)


# ------------------------------------------ prefix tables: kernel vs oracle
@pytest.mark.parametrize("k,cap", [
    (4, 512),
    (5, 129),             # cap off the 128-lane column grid
    (3, 1000),
    (1, 64),              # a block larger than the data
    (9, 640),             # turbofan's k: no row pad
    (10, 100),            # battery_median's k, a cap below 128
    (9, 2 * 8192 + 300),  # three blocks, the last one ragged
])
def test_prefix_power_sums_kernel_matches_ref(k, cap):
    rng = np.random.default_rng(k * cap)
    vals = jnp.asarray(rng.normal(1.0, 3.0, (k, cap)).astype(np.float32))
    shift = vals[:, 0]
    got = prefix_power_sums(vals, shift, interpret=True)
    want = prefix_power_sums_ref(vals, shift)
    assert got.shape == want.shape == (k, 4, cap)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=3e-5, atol=1e-3
    )


def test_prefix_block_rule_covers_the_served_widths():
    """Blocks come from (k, cap) alone: the served widths take 8,192-lane
    blocks (16 grid steps at cap 131,072, not 1,024), small caps one block
    of their own width rounded up to a 128-lane column."""
    for k in (9, 10):
        assert block_c(k, 131072) == 8192
    assert block_c(10, 100) == 128
    assert block_c(3, 1000) == 1024
    assert block_c(64, 131072) >= 128


@pytest.mark.parametrize("z_list", [[0, 1, 7, 300], [300, 299, 2, 1]])
def test_prefix_query_matches_masked_estimates(z_list):
    """One (k, 5) gather into the tables == the full rescan AFC, at the
    z ∈ {0, 1, n} edges and in between, for every parametric operator —
    under BOTH table backends (ops dispatch honors use_kernel)."""
    k, cap = 4, 300
    rng = np.random.default_rng(3)
    vals = jnp.asarray(rng.normal(50.0, 4.0, (k, cap)).astype(np.float32))
    z = jnp.asarray(z_list, jnp.int32)
    n = jnp.asarray([300, 300, 300, 300], jnp.int32)
    agg_ids = jnp.asarray([0, 3, 4, 1], jnp.int32)
    shift = vals[:, 0]
    from repro.data.aggregates import estimates_from_power_sums

    want_v, want_s = masked_estimates(vals, z, n, agg_ids, use_kernel=False)
    for use_kernel in (False, True):
        tab = prefix_power_sums_dispatch(vals, shift, use_kernel=use_kernel)
        got_v, got_s = estimates_from_power_sums(
            prefix_moments_at(tab, z), z, n, agg_ids, shift
        )
        np.testing.assert_allclose(
            np.asarray(got_v), np.asarray(want_v), rtol=2e-4, atol=2e-4
        )
        np.testing.assert_allclose(
            np.asarray(got_s), np.asarray(want_s), rtol=2e-2, atol=5e-3
        )
    # empty prefix: value/sigma match the oracle's empty convention exactly
    empty = np.asarray(z) == 0
    assert (np.asarray(got_v)[empty] == np.asarray(want_v)[empty]).all()


# -------------------------------------------- holistic rank-index queries
def test_rank_index_select_matches_sort_oracle_over_ladder():
    """Prefix-membership rank queries == sort+gather oracle, bit exact,
    over the entire candidate-z ladder (incl. z = 0, 1, n), with ties and a
    block-non-divisible cap."""
    rng = np.random.default_rng(11)
    h, cap = 3, 777
    vals = rng.normal(0, 2, (h, cap)).astype(np.float32)
    vals[0] = np.round(vals[0])                     # ties
    n = np.array([777, 500, 64], np.int32)
    ladder = np.stack(
        [np.minimum(np.array([min(i, 1) + 13 * i for i in range(33)]), nn)
         for nn in n]
    ).astype(np.int32)                              # starts at 0, then 1, ...
    idx = build_rank_index(jnp.asarray(vals), jnp.asarray(n), jnp.asarray(ladder))
    for col in range(ladder.shape[1]):
        z = ladder[:, col]
        targets = np.stack(
            [rng.integers(0, max(int(t), 1), 17) for t in z]
        ).astype(np.int32)
        got = select_ranks_indexed(idx, jnp.asarray(z), jnp.asarray(targets))
        want = masked_select_ranks_ref(
            jnp.asarray(vals), jnp.asarray(z), jnp.asarray(targets)
        )
        finite = np.asarray(z) > 0
        np.testing.assert_array_equal(
            np.asarray(got)[finite], np.asarray(want)[finite]
        )
        # z == 0 returns +inf on both paths (callers override)
        assert np.isinf(np.asarray(got)[~finite]).all()


def test_incremental_quantile_estimates_bitwise_vs_rescan():
    """Same counter-based key -> bitwise-identical (value, replicates) from
    the rank-index path and masked_quantile_estimates — the holistic half
    of the z-plan parity contract."""
    rng = np.random.default_rng(5)
    h, cap = 2, 640
    vals = jnp.asarray(rng.normal(5.0, 2.0, (h, cap)).astype(np.float32))
    n = jnp.asarray([640, 400], jnp.int32)
    qs = jnp.asarray([0.5, 0.9], jnp.float32)
    key = jax.random.PRNGKey(3)
    ladder = jnp.minimum(
        jnp.asarray([2, 64])[:, None]
        + jnp.arange(9, dtype=jnp.int32)[None, :] * 50,
        n[:, None],
    )
    idx = build_rank_index(vals, n, ladder)
    for col in range(9):
        z = ladder[:, col]
        targets = bootstrap_rank_targets(z, qs, key, 64)
        got_v, got_r = finish_quantile_estimates(
            select_ranks_indexed(idx, z, targets), z, n
        )
        want_v, want_r = masked_quantile_estimates(
            vals, z, n, qs, key, 64, use_kernel=False
        )
        np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
        np.testing.assert_array_equal(np.asarray(got_r), np.asarray(want_r))


# ------------------------------------------------ executor z-plan parity
@pytest.mark.parametrize(
    "name,median",
    [("turbofan", False), ("sensor_health", False), ("turbofan", True)],
)
def test_incremental_vs_rescan_executor_parity(name, median):
    """Acceptance: bitwise-identical z-plans and fp-close predictions vs
    the pre-refactor rescan path, on a parametric AND holistic pipelines
    (incl. the appendix-D median-substituted variant the benchmark runs)."""
    from repro.data.synthetic import make_pipeline_median

    b = (make_pipeline_median if median else make_pipeline)(name, **SMALL)
    cfg = BiathlonConfig(m=192, m_sobol=48, n_bootstrap=128)
    inc = BiathlonServer(b, cfg, mode="fused", afc_backend="incremental")
    ref = BiathlonServer(b, cfg, mode="fused", afc_backend="ref")
    for req in b.requests[:4]:
        ri = inc.serve(req)
        rr = ref.serve(req)
        assert (ri["z"] == rr["z"]).all(), (ri["z"], rr["z"])
        assert ri["iters"] == rr["iters"]
        scale = max(abs(rr["y_hat"]), 1.0)
        assert abs(ri["y_hat"] - rr["y_hat"]) <= 1e-4 * scale
        assert abs(ri["prob"] - rr["prob"]) <= 1e-4


def test_incremental_respects_exactness_pins():
    """approximate=False features stay pinned to z = n on the incremental
    path (the candidate ladder collapses to {n})."""
    k, cap = 2, 512
    rng = np.random.default_rng(0)
    w = jnp.asarray([2.0, 1.0])
    fused = build_fused_executor(
        lambda rows, exact: rows @ w,
        k=k, task="regression", m=64, m_sobol=16, max_iters=8,
        afc_backend="incremental", approximate=(False, True),
    )
    vals = jnp.asarray(rng.normal(0, 1, (k, cap)).astype(np.float32))
    n = jnp.asarray([500, 512], jnp.int32)
    res = fused(vals, n, jnp.zeros((k,), jnp.int32),
                jnp.asarray(0.05, jnp.float32), jnp.zeros((0,), jnp.float32))
    assert int(res.z[0]) == 500


# ------------------------------------- per-cap-bucket AFC heuristic (PR 7)
def test_resolve_afc_plan_cap_heuristic(monkeypatch):
    """"auto" picks rescan at/below AFC_REF_MAX_CAP (where BENCH_fused.json
    measured the prefix tables not amortizing) and incremental above; with
    no cap (build-time validation) the incremental default stands."""
    from repro.kernels.sampled_agg.ops import AFC_REF_MAX_CAP, resolve_afc_plan

    monkeypatch.delenv("REPRO_AFC_BACKEND", raising=False)
    assert resolve_afc_plan("auto", cap=AFC_REF_MAX_CAP) == (False, None)
    assert resolve_afc_plan("auto", cap=128) == (False, None)
    assert resolve_afc_plan("auto", cap=AFC_REF_MAX_CAP * 2) == (True, None)
    assert resolve_afc_plan("auto", cap=None) == (True, None)
    with pytest.raises(ValueError, match="unknown afc_backend"):
        resolve_afc_plan("bogus")


def test_resolve_afc_plan_overrides_beat_heuristic(monkeypatch):
    """Explicit build arguments and the env pin win over the cap heuristic
    at BOTH sides of the threshold — parity legs stay pinned."""
    from repro.kernels.sampled_agg.ops import resolve_afc_plan

    monkeypatch.delenv("REPRO_AFC_BACKEND", raising=False)
    for cap in (128, 65536):
        assert resolve_afc_plan("ref", cap=cap) == (False, False)
        assert resolve_afc_plan("kernel", cap=cap) == (True, True)
        assert resolve_afc_plan("incremental", cap=cap) == (True, False)
        assert resolve_afc_plan("inc", cap=cap) == (True, False)
    # env force-overrides consulted under "auto" only
    for env, want in [("ref", (False, False)), ("kernel", (True, True)),
                      ("incremental", (True, False)), ("inc", (True, False))]:
        monkeypatch.setenv("REPRO_AFC_BACKEND", env)
        assert resolve_afc_plan("auto", cap=128) == want
        assert resolve_afc_plan("auto", cap=65536) == want
        # ...but never over an explicit build argument
        assert resolve_afc_plan("ref", cap=65536) == (False, False)


@pytest.mark.parametrize("cap_factor", [1, 2])
def test_auto_heuristic_executor_parity_at_crossover(monkeypatch, cap_factor):
    """The executor built with "auto" is bitwise-identical to the strategy
    the heuristic resolves to, at the cap bucket just below and just above
    the crossover — strategy selection must never change results."""
    from repro.kernels.sampled_agg.ops import AFC_REF_MAX_CAP

    monkeypatch.delenv("REPRO_AFC_BACKEND", raising=False)
    cap = AFC_REF_MAX_CAP * cap_factor
    forced = "ref" if cap <= AFC_REF_MAX_CAP else "incremental"
    k = 2
    w = jnp.asarray([2.0, -1.0])
    kwargs = dict(k=k, task="regression", m=32, m_sobol=8, max_iters=8,
                  n_boot=16)
    auto = build_fused_executor(
        lambda rows, exact: rows @ w, afc_backend="auto", **kwargs
    )
    pinned = build_fused_executor(
        lambda rows, exact: rows @ w, afc_backend=forced, **kwargs
    )
    rng = np.random.default_rng(cap)
    vals = jnp.asarray(rng.normal(0, 2, (k, cap)).astype(np.float32))
    n = jnp.asarray([cap, cap - 7], jnp.int32)
    args = (vals, n, jnp.zeros((k,), jnp.int32),
            jnp.asarray(0.1, jnp.float32), jnp.zeros((0,), jnp.float32))
    ra, rp = auto(*args), pinned(*args)
    np.testing.assert_array_equal(np.asarray(ra.z), np.asarray(rp.z))
    assert int(ra.iters) == int(rp.iters)
    assert float(ra.y_hat) == float(rp.y_hat)


# ------------------------------------------------- HLO-cost flatness claim
def _executor_hlo(cap: int, afc_backend: str) -> str:
    k = 3
    w = jnp.asarray([1.0, -2.0, 0.5])
    fused = build_fused_executor(
        lambda rows, exact: rows @ w,
        k=k, task="regression", m=16, m_sobol=8, max_iters=8, n_boot=16,
        holistic=(1,), quantiles=(0.5,), afc_backend=afc_backend,
    )
    args = (
        jax.ShapeDtypeStruct((k, cap), jnp.float32),
        jax.ShapeDtypeStruct((k,), jnp.int32),
        jax.ShapeDtypeStruct((k,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((0,), jnp.float32),
    )
    return fused.lower(*args).compile().as_text()


def _planner_body_cost(text: str):
    costs = while_costs(text)
    assert costs, "no while loop found in the compiled executor"
    return max(costs, key=lambda c: c["cost"].bytes)["cost"]


def test_while_body_cost_independent_of_cap():
    """The core claim of this PR: the compiled while_loop body's HLO cost
    (FLOPs and HBM bytes) is flat across cap ∈ {1k, 8k, 64k} on the
    incremental path, while the rescan oracle's body bytes scale ~linearly
    with cap.  (The once-per-request precompute outside the loop is allowed
    to scale — that is the point of the precompute/query split.)"""
    caps = (1024, 8192, 65536)
    inc = [_planner_body_cost(_executor_hlo(c, "incremental")) for c in caps]
    assert inc[0].bytes > 0
    for cost in inc[1:]:
        assert cost.bytes <= 1.3 * inc[0].bytes, [c.bytes for c in inc]
        assert cost.flops <= 1.05 * max(inc[0].flops, 1.0)
    # sensitivity check: the same probe sees the rescan body grow with cap
    ref = [_planner_body_cost(_executor_hlo(c, "ref")) for c in (1024, 8192)]
    assert ref[1].bytes >= 4.0 * ref[0].bytes, [c.bytes for c in ref]


# --------------------------------------------------- donation (no-copy)
@pytest.fixture(scope="module")
def tiny_bundle():
    rng = np.random.default_rng(0)
    sizes = [300] * 6
    gid = np.concatenate([np.full(s, g) for g, s in enumerate(sizes)])
    mu = rng.normal(0, 5, len(sizes))
    vals = mu[gid] + rng.normal(0, 2.0, len(gid))
    store = ColumnStore().add("t", build_table({"v": vals}, gid, seed=1))
    y = 3 * mu + rng.normal(0, 0.01, len(sizes))
    pipe = Pipeline(
        name="tiny",
        agg_features=[AggFeature("avg_v", "t", "v", "avg", "g")],
        exact_features=[],
        model=LinearRegression().fit(mu[:, None], y),
        task="regression",
        scaler_mean=np.zeros(1, np.float32),
        scaler_scale=np.ones(1, np.float32),
        delta_default=0.5,
    )
    return PipelineBundle(
        pipeline=pipe, store=store,
        requests=[{"g": g} for g in range(len(sizes))],
        labels=y, table_rows=len(gid), name="tiny",
    )


def test_batched_server_donates_values_buffer(tiny_bundle):
    """The (lanes, k, cap) values buffer must be donated AND aliased to the
    lane_vals output — i.e. per-batch serving does not copy it.  Asserted
    via the compiled executable's memory analysis, plus a behavioral check
    that serving still works across batches after donation."""
    srv = BatchedFusedServer(tiny_bundle, BiathlonConfig(m=64, m_sobol=16),
                             batch_size=4)
    r1 = srv.serve_batch(tiny_bundle.requests[:3])
    r2 = srv.serve_batch(tiny_bundle.requests[3:6])
    assert np.isfinite(r1.y_hat).all() and np.isfinite(r2.y_hat).all()

    lanes, k, cap = 4, 1, r1.cap
    args = (
        jnp.zeros((lanes, k, cap), jnp.float32),
        jnp.zeros((lanes, k), jnp.int32),
        jnp.zeros((lanes, k), jnp.int32),
        jnp.zeros((lanes,), jnp.float32),
        jnp.zeros((lanes, 0), jnp.float32),
        jnp.zeros((lanes,), bool),
        jnp.full((lanes,), 0.95, jnp.float32),   # traced tau (PR 6)
        jnp.full((lanes,), 64, jnp.int32),       # traced iter_cap (PR 6)
    )
    compiled = srv._batched.lower(*args).compile()
    vals_bytes = lanes * k * cap * 4
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= vals_bytes, (
        f"donated values buffer not aliased: alias={ma.alias_size_in_bytes} "
        f"< vals={vals_bytes}"
    )
    assert "input_output_alias" in compiled.as_text()

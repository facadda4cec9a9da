"""Jit'd wrappers: dispatch to the Pallas kernels (TPU) or oracles (CPU)."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.data.aggregates import estimates_from_power_sums
from repro.kernels.sampled_agg.prefix_stats import (
    prefix_power_sums as prefix_power_sums_kernel,
    prefix_power_sums_ref,
)
from repro.kernels.sampled_agg.quantile_select import masked_select_ranks
from repro.kernels.sampled_agg.ref import (
    masked_select_ranks_ref,
    sampled_moments_ref,
)
from repro.kernels.sampled_agg.sampled_agg import sampled_moments

__all__ = [
    "AFC_REF_MAX_CAP",
    "moments",
    "estimates_from_moments",
    "masked_estimates",
    "masked_quantile_estimates",
    "prefix_power_sums",
    "resolve_afc_plan",
    "bootstrap_rank_targets",
    "finish_quantile_estimates",
]

# Cap bucket at or below which the incremental prefix-table precompute does
# not amortize: BENCH_fused.json["incremental_afc"] measures the incremental
# path at 0.55-0.76x the rescan oracle for cap <= 1k groups (the per-request
# table build + argsort costs more than the few full-pass rescans it saves),
# crossing over above it.  ``resolve_afc_plan`` uses this as the "auto"
# strategy threshold when the caller supplies its cap bucket.
AFC_REF_MAX_CAP = 1024


def _on_tpu() -> bool:
    """The one backend query behind kernel routing and interpret mode: on a
    TPU the Pallas kernels compile with Mosaic, anywhere else a forced
    kernel call runs in the Pallas interpreter."""
    return jax.default_backend() == "tpu"


def _resolve_backend(use_kernel: bool | None) -> bool:
    """None = auto: the REPRO_AFC_BACKEND env override (ref | kernel), else
    Pallas on TPU and the jnp oracle elsewhere.  CI runs the tier-1 suite
    under both env values so kernel/oracle parity is exercised on CPU."""
    if use_kernel is None:
        env = os.environ.get("REPRO_AFC_BACKEND", "auto").lower()
        if env == "kernel":
            return True
        if env == "ref":
            return False
        return _on_tpu()
    return use_kernel


def resolve_afc_plan(
    afc_backend: str, cap: int | None = None, *, cached: bool = False
) -> tuple[bool, bool | None]:
    """Executor AFC strategy from the ``afc_backend`` build argument.

    Returns ``(incremental, use_kernel)``.  ``"ref"`` selects the
    pre-refactor **rescan** path (full masked_estimates / rank-count pass
    per planner iteration, jnp oracles) — the parity oracle CI pins via
    ``REPRO_AFC_BACKEND=ref``.  ``"kernel"`` forces the incremental
    prefix-stats path with the Pallas table kernel (interpret off-TPU);
    ``"incremental"`` (alias ``"inc"``) the same path with the jnp table
    oracle regardless of env (explicit strategy pinning for parity tests
    and the CPU benchmarks; also accepted as a REPRO_AFC_BACKEND value —
    unknown env values fall through to auto, matching
    ``_resolve_backend``).  ``"auto"`` consults the env at trace time like
    ``_resolve_backend``, then picks **per cap bucket**: executors resolve
    with their (k, cap) buffer width, and buckets at or below
    :data:`AFC_REF_MAX_CAP` take the rescan path — the prefix-table
    precompute does not amortize on small groups (0.55–0.76× measured in
    ``BENCH_fused.json["incremental_afc"]``) — while larger buckets run
    incremental with kernel-on-TPU.  ``cap=None`` (strategy validation, no
    shapes yet) keeps the incremental default.  Force-overrides — the env
    and every non-"auto" build argument — win over the heuristic, so
    parity legs stay pinned.

    ``cached=True`` declares that the executor is fed **prebuilt tables**
    from the feature-store precompute cache (serving/feature_cache.py): the
    :data:`AFC_REF_MAX_CAP` crossover was calibrated against a per-request
    rebuild, but a cache hit pays zero precompute, so the incremental path
    wins at every cap and "auto" picks it regardless of the bucket
    (``BENCH_fused.json["feature_store"]`` re-measures the crossover).
    Explicit backends and the env override still win — the ref-parity CI
    legs stay pinned even on cached paths.
    """
    if afc_backend == "auto":
        env = os.environ.get("REPRO_AFC_BACKEND", "auto").lower()
        if env == "ref":
            return False, False
        if env == "kernel":
            return True, True
        if env in ("incremental", "inc"):
            return True, False
        if cached:
            return True, None
        if cap is not None and cap <= AFC_REF_MAX_CAP:
            return False, None
        return True, None
    if afc_backend == "ref":
        return False, False
    if afc_backend == "kernel":
        return True, True
    if afc_backend in ("incremental", "inc"):
        return True, False
    raise ValueError(f"unknown afc_backend {afc_backend!r}")


def prefix_power_sums(
    vals: jnp.ndarray,
    shift: jnp.ndarray | None = None,
    *,
    use_kernel: bool | None = None,
):
    """(k, cap) -> (k, 4, cap) running prefix power sums of ``vals - shift``.

    The incremental-AFC precompute (one call per request, before the
    while_loop); backend-routed exactly like :func:`moments`.  The table
    column at ``z - 1`` is the ``[s1..s4]`` tail :func:`moments` would return at
    plan z (``prefix_stats.prefix_moments_at`` does the gather).
    """
    if _resolve_backend(use_kernel):
        return prefix_power_sums_kernel(
            vals, shift, interpret=not _on_tpu()
        )
    return prefix_power_sums_ref(vals, shift)


def moments(
    vals: jnp.ndarray,
    z: jnp.ndarray,
    shift: jnp.ndarray | None = None,
    *,
    use_kernel: bool | None = None,
):
    """(k, cap), (k,) -> (k, 5) [count, s1, s2, s3, s4] of ``vals - shift``.

    use_kernel=None auto-selects: Pallas on TPU, oracle elsewhere (the
    interpret-mode kernel is for correctness tests, not speed).
    """
    if _resolve_backend(use_kernel):
        return sampled_moments(
            vals, z, shift, interpret=not _on_tpu()
        )
    return sampled_moments_ref(vals, z, shift)


def masked_estimates(
    vals: jnp.ndarray,
    z: jnp.ndarray,
    n: jnp.ndarray,
    agg_ids: jnp.ndarray,
    *,
    use_kernel: bool | None = None,
):
    """AFC in one call: kernel/oracle power sums -> (value, sigma) per feature.

    This is the fused executor's per-iteration AFC stage: one pass over the
    (k, cap) prefix-masked buffers (the Pallas ``sampled_moments`` kernel on
    TPU, interpret-mode fallback for kernel testing, ref oracle on CPU), then
    the parametric estimator tail with finite-population correction from
    ``aggregates.estimates_from_power_sums``.  Holistic ids fall through the
    parametric select to (0, 0) and are overwritten by
    :func:`masked_quantile_estimates`.

    Sums are accumulated about each feature's first buffered sample so the
    4th-moment cancellation stays at O(std⁴) even when |mean| >> std (the
    VAR/STD σ's would otherwise collapse to zero in float32).
    """
    shift = vals[:, 0]
    return estimates_from_power_sums(
        moments(vals, z, shift, use_kernel=use_kernel), z, n, agg_ids, shift
    )


def masked_quantile_estimates(
    vals: jnp.ndarray,        # (h, cap) holistic-feature prefix buffers
    z: jnp.ndarray,           # (h,) int32 live prefix lengths
    n: jnp.ndarray,           # (h,) int32 group sizes (exactness check)
    qs: jnp.ndarray,          # (h,) f32 per-feature quantile (0.5 = median)
    key: jax.Array,           # counter-based: fold_in(base, iteration)
    n_boot: int,
    *,
    use_kernel: bool | None = None,
):
    """Holistic AFC: (value, sorted bootstrap replicates) per feature.

    Point estimate = nearest-rank quantile of the z-prefix.  Each bootstrap
    replicate is the rank-r quantile of a size-z resample-with-replacement
    (paper appendix D); instead of materializing B resamples, the replicate
    is drawn as an order statistic of the ORIGINAL sorted prefix at a random
    rank: the (r+1)-th smallest of z iid Uniform{0..z-1} index draws is
    ``floor(z·V)`` with ``V ~ Beta(r+1, z-r)`` — one Beta draw per replicate,
    distributionally identical to ``aggregates._bootstrap_replicates``'s
    explicit resample, with every shape static (lax.while_loop safe).

    All ranks are then selected in ONE kernel/oracle pass
    (``masked_select_ranks``; afc_backend-routed like ``sampled_moments``).
    Conventions match :func:`aggregates.estimate`: empty prefix (z == 0)
    yields value 0 with all-zero replicates; exact (z >= n) yields the exact
    quantile with a degenerate replicate table.  Returns
    ``(value (h,), replicates (h, n_boot) sorted ascending)``.
    """
    targets = bootstrap_rank_targets(z, qs, key, n_boot)
    if _resolve_backend(use_kernel):
        sel = masked_select_ranks(
            vals, z, targets, interpret=not _on_tpu()
        )
    else:
        sel = masked_select_ranks_ref(vals, z, targets)
    return finish_quantile_estimates(sel, z, n)


def _gamma_mt(key: jax.Array, d: jnp.ndarray, rounds: int) -> jnp.ndarray:
    """Marsaglia-Tsang (2000) Gamma(a ≥ 1) with ``d = a - 1/3``, sampled in
    a FIXED number of unrolled proposal rounds (no data-dependent loop).

    ``jax.random.gamma``'s exact rejection ``while_loop`` costs tens of ms
    per (h, B) draw on CPU and sits in the serving loop body; the squeeze
    accepts ≥ 96% per round for a ≥ 1, so after ``rounds`` independent
    proposals the miss probability is < (0.04)^rounds (≈ 2.6e-6 at 4) and
    the fallback — the distribution mean ``d + 1/3 ≈ a`` — is statistically
    invisible next to the B-replicate bootstrap's own MC error.
    """
    c = 1.0 / jnp.sqrt(9.0 * d)
    out = d + 1.0 / 3.0
    done = jnp.zeros(d.shape, bool)
    for kk in jax.random.split(key, rounds):
        kn, ku = jax.random.split(kk)
        x = jax.random.normal(kn, d.shape)
        v = (1.0 + c * x) ** 3
        u = jax.random.uniform(ku, d.shape, minval=1e-38)
        safe_v = jnp.where(v > 0.0, v, 1.0)
        ok = (v > 0.0) & (
            jnp.log(u) < 0.5 * x * x + d - d * safe_v + d * jnp.log(safe_v)
        )
        take = ok & ~done
        out = jnp.where(take, d * safe_v, out)
        done = done | ok
    return out


def beta_order_stat(
    key: jax.Array, a: jnp.ndarray, b: jnp.ndarray, shape, rounds: int = 4
) -> jnp.ndarray:
    """Beta(a, b) draws for a, b ≥ 1 via two fixed-round MT gammas.

    Drop-in for ``jax.random.beta`` on the bootstrap hot path (the Beta
    order-statistic trick, appendix D): same distribution up to the
    < 3e-6 proposal-truncation described in :func:`_gamma_mt`, ~500×
    cheaper on CPU because nothing in it is a rejection ``while_loop``.
    """
    ka, kb = jax.random.split(key)
    f32 = jnp.float32
    da = jnp.broadcast_to(a.astype(f32), shape) - 1.0 / 3.0
    db = jnp.broadcast_to(b.astype(f32), shape) - 1.0 / 3.0
    ga = _gamma_mt(ka, da, rounds)
    gb = _gamma_mt(kb, db, rounds)
    return ga / (ga + gb)


def bootstrap_rank_targets(
    z: jnp.ndarray, qs: jnp.ndarray, key: jax.Array, n_boot: int
) -> jnp.ndarray:
    """(h, 1+B) rank targets: [point-estimate rank | bootstrap ranks].

    Shared by the rescan path above and the incremental
    ``select_ranks_indexed`` path so both draw BITWISE-identical Beta
    replicate ranks from the same counter-based key — the z-plan parity
    contract between the two executors rests on this.
    """
    f32 = jnp.float32
    h = z.shape[0]
    zf = z.astype(f32)
    zm1 = jnp.maximum(z - 1, 0)
    rank = jnp.clip(
        jnp.floor(qs * (zf - 1.0) + 0.5).astype(jnp.int32), 0, zm1
    )
    a = (rank + 1).astype(f32)
    b = jnp.maximum(z - rank, 1).astype(f32)
    v = beta_order_stat(key, a[:, None], b[:, None], (h, n_boot))
    boot = jnp.clip(
        jnp.floor(zf[:, None] * v).astype(jnp.int32), 0, zm1[:, None]
    )
    return jnp.concatenate([rank[:, None], boot], axis=1)


def finish_quantile_estimates(
    sel: jnp.ndarray, z: jnp.ndarray, n: jnp.ndarray
):
    """Apply the estimate() conventions to selected (h, 1+B) order stats.

    Empty prefix -> (0, zeros); exact (z >= n) -> degenerate replicates at
    the exact quantile; otherwise (point value, sorted replicates).
    """
    f32 = jnp.float32
    empty = z <= 0
    value = jnp.where(empty, 0.0, sel[:, 0]).astype(f32)
    reps = jnp.sort(sel[:, 1:], axis=1)
    reps = jnp.where(
        empty[:, None],
        0.0,
        jnp.where((z >= n)[:, None], value[:, None], reps),
    ).astype(f32)
    return value, reps


def estimates_from_moments(m: jnp.ndarray, n: jnp.ndarray):
    """Turn raw power sums into (mean, unbiased var, se_mean) per feature.

    n: (k,) total group sizes (finite-population correction).
    """
    count = jnp.maximum(m[:, 0], 1.0)
    mean = m[:, 1] / count
    var = jnp.maximum(m[:, 2] / count - mean**2, 0.0) * count / jnp.maximum(
        count - 1.0, 1.0
    )
    nf = n.astype(jnp.float32)
    fpc = jnp.sqrt(jnp.clip((nf - count) / jnp.maximum(nf - 1.0, 1.0), 0.0, 1.0))
    se = jnp.sqrt(var / count) * fpc
    return mean, var, se

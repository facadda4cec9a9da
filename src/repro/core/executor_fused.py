"""FusedExecutor: the whole Biathlon feedback loop as ONE XLA program.

Beyond-paper TPU adaptation (DESIGN.md §2): the HostLoopExecutor mirrors the
paper — a Python loop dispatching AFC / AMI / Planner stages per iteration,
paying a host<->device round trip + dispatch latency every cycle.  Once the
datastore I/O is approximated away, those round trips dominate single-digit-
millisecond serving budgets.

The fused variant expresses the iterate-until-guaranteed loop as a
``jax.lax.while_loop`` over fixed-shape state:

* sample growth is a *monotone prefix mask* over pre-gathered, pre-permuted
  (k, cap) buffers — the plan z is data, not shape;
* AFC covers the FULL operator set and is **incremental** (PR 5, DESIGN.md
  § Incremental AFC): a once-per-request precompute before the while_loop
  builds running prefix power-sum tables (``prefix_stats`` Pallas kernel /
  jnp oracle, compensated f32 accumulation) for the parametric aggregates
  (SUM/COUNT/AVG/VAR/STD) and an argsort-with-original-index rank
  structure for the holistic columns; the loop body then reads
  (value, sigma) for ANY plan z with O(1) gathers through the unchanged
  ``estimates_from_power_sums`` finite-population tail, and answers
  holistic order statistics by prefix-membership rank queries — the body's
  cost is independent of the group size.  Holistic aggregates
  (MEDIAN/QUANTILE, paper appendix D) keep their fixed-shape ``(h, B)``
  sorted bootstrap-replicate table recomputed each iteration: replicate
  ranks come from counter-based RNG (``jax.random.fold_in`` on the
  iteration index, so shapes and keys are static inside the while_loop).
  ``afc_backend="ref"`` retains the pre-refactor full-pass rescan
  (``masked_estimates`` / ``masked_select_ranks`` per iteration) as the
  parity oracle; under plain "auto" (no env override) the strategy is now
  picked **per cap bucket** — rescan at or below ``ops.AFC_REF_MAX_CAP``
  where the precompute does not amortize, incremental above it;
* the megabatch row sampler ports ``uncertainty.sample_features``:
  parametric features draw ``value + sigma·Φ⁻¹(u)``, holistic features draw
  the empirical inverse CDF of their replicate table at the same QMC
  uniform — so a MEDIAN feature's uncertainty is propagated exactly as the
  host loop propagates it;
* AMI + Sobol indices share ONE fused QMC evaluation megabatch: the m AMI
  rows, the single point-estimate row, and the (k+2)·m_sobol Saltelli
  A/B/AB rows are concatenated and evaluated with a single ``model_fn``
  call per planner iteration — ``m + 1 + (k+2)·m_sobol`` model rows,
  sliced afterwards for the Eq. 1 guarantee check and the main-effect
  indices (the Saltelli-style model-call amortization);
* the loop state carries ``(z, iter, y_hat, prob, indices, replicates)`` so
  each iteration steps the plan with the *previous* evaluation's indices
  and then evaluates the new plan exactly once — no duplicate pre-step
  call;
* features declared ``approximate=False`` (the paper's Fig. 10 exactness
  ablation) are pinned to ``z_j = n_j`` from z⁰ onward, exactly as the host
  loop pins them — the planner never grows them (they are exhausted) and
  their sigma/replicates are degenerate, so they contribute zero
  uncertainty;
* the initial plan gets a cheap AMI-only dispatch (m+1 rows); its Sobol
  block runs under ``lax.cond`` only when the guarantee fails at z⁰, so
  immediately-satisfied requests (the common case at the paper's α) never
  pay Saltelli rows — in the single-request path.  Under ``vmap`` (batched
  serving) a batched predicate executes both cond branches, so admission
  batches always pay the init Sobol block;
* the loop condition is the Eq. 1 guarantee check.

**Chunked execution** (continuous batching, DESIGN.md § Continuous
batching): :func:`build_chunked_executor` factors the same loop into an
``init`` (per-request precompute + z⁰ evaluation) and a ``chunk`` that runs
at most ``chunk_iters`` planner iterations per dispatch, both over a
first-class :class:`LaneState` pytree that carries the FULL per-lane state
— request buffers, prefix-table handles, the planner carry (z, iteration
counter = the counter-based bootstrap-RNG fold-in index, Sobol main-effect
state, replicates), the traced degradation knobs, and a ``done`` flag.
Because the state is data, a caller can swap a finished lane's state for a
fresh request *between* chunks (iteration-level lane recycling) without
touching the executable: the chunk program's shapes depend only on
(cap, lanes, chunk_iters).  Both executors share one per-iteration core
(``_executor_core``), so a chunked run with ``chunk_iters = max_iters`` is
bitwise-identical to the monolithic while_loop — the monolithic path stays
as the parity oracle.

Cost model (EXPERIMENTS.md §Perf): one model dispatch of
``m + 1 + (k+2)·m_sobol`` rows per iteration, zero host round trips, and a
loop body whose AFC work is cap-independent — one (k, 5) prefix-table
gather for the parametric features plus, per holistic feature, ``(1+B)``
rank queries of O(log(cap/S)) gathers + one S-element block scan each
(B = ``n_boot`` replicates, default 256; ``h·B`` Beta draws for the
replicate ranks).  All O(cap) work happens once per request in the
precompute (prefix tables + argsort); pipelines with ``h = 0`` compile to
exactly the parametric-only program.  The remaining restriction vs the
host loop is the ``cap``-row buffer bound (the guarantee's worst case
degrades to exact-over-cap).  Batched serving vmaps this executor over
concurrent requests with power-of-two bucketed caps, donating the values
buffer to the compiled program (serving/batched.py); continuous serving
vmaps the chunked executor and donates the whole lane table
(serving/continuous.py).
"""
from __future__ import annotations

from types import SimpleNamespace
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.analysis.contracts import ExecutableContract, register_contract
from repro.core.planner import direction, gamma_abs, initial_plan, next_plan
from repro.core.propagation import qmc_uniforms
from repro.core.uncertainty import sample_features_fused
from repro.data.aggregates import AGG_IDS_FULL, HOLISTIC_AGGS, estimates_from_power_sums
from repro.kernels.sampled_agg.ops import (
    bootstrap_rank_targets,
    finish_quantile_estimates,
    masked_estimates,
    masked_quantile_estimates,
    prefix_power_sums,
    resolve_afc_plan,
)
from repro.kernels.sampled_agg.prefix_stats import (
    HolisticRankIndex,
    append_power_sums,
    build_rank_index,
    merge_sorted_prefix,
    prefix_moments_at,
    rank_index_from_sorted,
    select_ranks_indexed,
)

f32 = jnp.float32

__all__ = [
    "CHUNK_CARRY_LEAVES",
    "FusedResult",
    "LaneState",
    "PrebuiltTables",
    "build_afc_precompute",
    "build_chunked_executor",
    "build_fused_executor",
    "empty_rank_index",
    "fused_rows_per_iteration",
    "pipeline_executor_kwargs",
    "shard_lanes_executor",
    "shard_lanes_state_executor",
]


class PrebuiltTables(NamedTuple):
    """Device-resident incremental-AFC precompute for one request.

    The handle the feature-store cache (serving/feature_cache.py) passes to
    a ``prebuilt=True`` executor instead of letting it run its internal
    ``core.precompute``: ``ptab (k, cap, 4)`` prefix power-sum tables,
    ``shift (k,)`` their accumulation origin (= ``vals[:, 0]``), and the
    holistic :class:`HolisticRankIndex` (zero-size when the pipeline has no
    holistic features).  Built by :func:`build_afc_precompute`, which also
    owns the append-event delta refresh — the executor only ever reads it.
    """

    ptab: jnp.ndarray
    shift: jnp.ndarray
    rindex: HolisticRankIndex


class FusedResult(NamedTuple):
    y_hat: jnp.ndarray
    prob: jnp.ndarray
    iters: jnp.ndarray
    z: jnp.ndarray          # (k,) final plan
    samples_used: jnp.ndarray
    # Batched serving threads the donated (lanes, k, cap) values buffer back
    # out as lane state: the identity passthrough gives XLA an input-output
    # alias for the donated argument, so per-batch serving provably does NOT
    # copy the big buffer (asserted via memory_analysis in tests).  None on
    # the single-request path (returning an undonated input would force the
    # copy this field exists to avoid).
    lane_vals: jnp.ndarray | None = None


class LaneState(NamedTuple):
    """One lane's complete carry between chunked-executor dispatches.

    A first-class pytree (vmapped over a leading ``lanes`` dimension by the
    continuous server) holding everything a request's planner loop needs to
    resume — so swapping a lane = overwriting its slice of every leaf, and
    the chunk executable's shapes depend only on (cap, lanes, chunk_iters):

    request inputs
      ``vals (k, cap)``  pre-gathered, pre-permuted sample buffers
      ``n (k,)``         group sizes clamped to cap
      ``agg_ids (k,)``   operator ids
      ``delta ()``       error bound (traced knob)
      ``exact (e,)``     exactly-computed feature values
      ``active ()``      pad-lane flag (False = never iterates)
      ``tau ()``         confidence target (traced knob)
      ``iter_cap ()``    planner-iteration ceiling (traced knob)
    planner carry
      ``z (k,)``         current plan
      ``it ()``          iteration counter — also the counter-based
                         bootstrap-RNG fold-in index, so replicate draws
                         are per-request-deterministic wherever the lane
                         lives (the recycling-parity property)
      ``y_hat / prob ()`` last evaluation + Eq. 1 guarantee probability
      ``idx (k,)``       Sobol main-effect indices steering the next step
      ``reps (h, B)``    holistic bootstrap replicate table
      ``done ()``        guarantee met / exhausted / capped — the lane is
                         recyclable
    incremental-AFC handles (PR 5)
      ``ptab (k, cap, 4)``  prefix power-sum tables ((k, 0, 4) under rescan)
      ``shift (k,)``        the tables' numerical shift
      ``rindex``            :class:`HolisticRankIndex` (zero-size leaves
                            when rescan or no holistic features)

    The zero-size placeholders keep the pytree structure identical across
    AFC strategies *for a given cap bucket* (the strategy is resolved from
    the cap at trace time, so one bucket always yields one structure).
    """

    vals: jnp.ndarray
    n: jnp.ndarray
    agg_ids: jnp.ndarray
    delta: jnp.ndarray
    exact: jnp.ndarray
    active: jnp.ndarray
    tau: jnp.ndarray
    iter_cap: jnp.ndarray
    z: jnp.ndarray
    it: jnp.ndarray
    y_hat: jnp.ndarray
    prob: jnp.ndarray
    idx: jnp.ndarray
    reps: jnp.ndarray
    done: jnp.ndarray
    ptab: jnp.ndarray
    shift: jnp.ndarray
    rindex: HolisticRankIndex


#: The LaneState leaves the chunk executable actually mutates (its
#: ``state._replace`` set).  Every other leaf — request inputs, knobs, AFC
#: handles — is content-invariant across a chunk dispatch (donated and
#: aliased through, values unchanged), so a chunk-boundary checkpoint is
#: host copies of exactly these small per-lane leaves: the recovery layer
#: (serving/runtime.py) snapshots them before each dispatch and restores
#: them with plain ``device_put`` — zero new executables.
CHUNK_CARRY_LEAVES = ("z", "it", "y_hat", "prob", "idx", "reps", "done")


def empty_rank_index() -> HolisticRankIndex:
    """Zero-size :class:`HolisticRankIndex` placeholder (rescan / h == 0)."""
    zi = jnp.zeros((0, 0), jnp.int32)
    return HolisticRankIndex(
        sorted_vals=jnp.zeros((0, 0), f32),
        sorted_idx=zi,
        blk_cnt=jnp.zeros((0, 0, 0), jnp.int32),
        zcand=zi,
    )


def fused_rows_per_iteration(k: int, m: int, m_sobol: int) -> int:
    """Model rows evaluated per planner iteration (the single megabatch)."""
    return m + 1 + (k + 2) * m_sobol


def shard_lanes_executor(lane_fn, mesh, *, axis: str = "lanes", donate_vals: bool = False):
    """Data-parallel lane sharding of a per-lane fused executor.

    ``lane_fn`` is a single-lane ``run(vals, n, agg_ids, delta, exact,
    active, tau, iter_cap)`` (the :func:`build_fused_executor` signature
    with the trailing optionals made mandatory so the arity is static); the
    result maps it over a leading ``lanes`` dimension — ``jax.vmap`` within
    each device, ``shard_map`` across the ``mesh``'s 1-D ``axis`` — and
    jits the whole thing.

    Because every lane is an independent while-loop over its own buffers,
    ALL eight inputs and every :class:`FusedResult` leaf partition along the
    leading dimension and the compiled program contains **zero cross-device
    collectives**: model params and the QMC/bootstrap constants are
    closure-captured and replicated, per-lane reductions stay local to the
    device that owns the lane.  A device whose lane block finishes (or is
    all pad lanes) exits its while-loop independently — stragglers only
    stall the lanes that share their device, which is the scaling win over
    the single-device megabatch.

    The leading dimension of every argument must be divisible by the mesh
    size (callers pad to a fixed lane count anyway).  ``check_vma=False``
    because the executor closes over large replicated constants and runs a
    ``while_loop`` — the conservative replication checker rejects that
    combination without adding safety for a collective-free program.

    ``donate_vals=True`` donates argument 0 (the (lanes, k, cap) values
    buffer, by far the largest per-batch transfer): when ``lane_fn``
    threads it back out (``FusedResult.lane_vals``) XLA aliases the donated
    input to that output and per-batch serving stops copying the buffer —
    the donation contract asserted via ``memory_analysis`` in tests.
    """
    from jax.sharding import PartitionSpec

    spec = PartitionSpec(axis)
    return jax.jit(
        jax.shard_map(
            jax.vmap(lane_fn),
            mesh=mesh,
            in_specs=(spec,) * 8,
            out_specs=spec,
            check_vma=False,
        ),
        donate_argnums=(0,) if donate_vals else (),
    )


def shard_lanes_state_executor(chunk_fn, mesh, *, axis: str = "lanes",
                               donate_state: bool = True):
    """Lane sharding of a chunked per-lane ``chunk(LaneState) -> LaneState``.

    The pytree twin of :func:`shard_lanes_executor`: every
    :class:`LaneState` leaf carries a leading ``lanes`` dimension, so ONE
    ``PartitionSpec("lanes")`` applied as a pytree prefix partitions the
    whole table and the compiled chunk program stays **collective-free** —
    a per-device lane swap is just the host overwriting that device's
    slice of the table between dispatches, no cross-device traffic.  The
    table (argument 0) is donated by default so XLA updates it in place
    across chunks instead of copying every leaf each dispatch.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    spec = PartitionSpec(axis)
    return jax.jit(
        jax.shard_map(
            jax.vmap(chunk_fn),
            mesh=mesh,
            in_specs=(spec,),
            out_specs=spec,
            check_vma=False,
        ),
        donate_argnums=(0,) if donate_state else (),
        # pinned so zero-size leaves keep the lanes sharding they came in
        # with (XLA reports them replicated), one cache key per bucket
        out_shardings=NamedSharding(mesh, spec),
    )


#: Sharded-lane contract: the shard_map wrappers above promise a compiled
#: module with ZERO cross-device collectives (params replicated as closure
#: constants, per-lane reductions local to the owning device) and the same
#: one-executable-per-cap-bucket cache behavior as the unsharded path.
SHARDED_LANES_CONTRACT = register_contract(ExecutableContract(
    name="sharded_lanes",
    builder="repro.core.executor_fused.shard_lanes_executor",
    executables_per_bucket=1,
    collectives=0,
    donated=("vals (lanes, k, cap) values buffer",),
    while_body_flat=True,
    description=(
        "shard_map over the 1-D ('lanes',) mesh: fixed-lane batch program "
        "partitioned device-parallel, collective-free by construction"
    ),
))


def pipeline_executor_kwargs(agg_features) -> dict:
    """Per-feature executor kwargs from a pipeline's ``agg_features``.

    Returns the ``holistic`` / ``quantiles`` / ``approximate`` build
    arguments plus the runtime ``agg_ids`` row — the one place the
    feature-spec -> executor translation lives, shared by both fused
    serving paths.  Raises on operators outside AGG_IDS_FULL.
    """
    unsupported = sorted(
        {f.agg for f in agg_features if f.agg not in AGG_IDS_FULL}
    )
    if unsupported:
        raise ValueError(f"unsupported aggregates {unsupported}")
    holistic = tuple(
        j for j, f in enumerate(agg_features) if f.agg in HOLISTIC_AGGS
    )
    return dict(
        holistic=holistic,
        quantiles=tuple(
            0.5 if agg_features[j].agg == "median" else agg_features[j].quantile
            for j in holistic
        ),
        approximate=tuple(f.approximate for f in agg_features),
        agg_ids=jnp.asarray(
            [AGG_IDS_FULL[f.agg] for f in agg_features], jnp.int32
        ),
    )


def _executor_core(
    model_fn,
    *,
    k: int,
    task: str,
    n_classes: int,
    m: int,
    m_sobol: int,
    alpha: float,
    gamma: float,
    max_iters: int,
    afc_backend: str,
    hol_idx,
    n_hol: int,
    qs,
    approx,
    n_boot: int,
    base_key,
    cached: bool = False,
):
    """The per-iteration machinery BOTH executors trace through.

    Everything here is a pure function of explicit arguments (no per-call
    closures), so the monolithic while_loop and the chunked executor build
    bitwise-identical iteration bodies — the parity contract the chunked
    tests assert.  The AFC strategy is resolved per trace from the buffer
    cap (``resolve_afc_plan(afc_backend, cap)``), so a cap bucket always
    gets one consistent strategy across init/loop/chunk programs.
    ``cached=True`` (the prebuilt-tables executors) tells the resolver the
    precompute is amortized by the feature-store cache, flipping "auto" to
    the incremental path at every cap.
    """
    u_ami = qmc_uniforms(m, k)                       # (m, k) static
    u_sob = qmc_uniforms(m_sobol, 2 * k, None)       # (m_sobol, 2k)

    def sample_rows(value, sigma, reps, u):
        """uncertainty.sample_features, fused-state edition (shared impl).

        Parametric: x̂ + σ·Φ⁻¹(u).  Holistic: empirical inverse CDF of the
        sorted (h, B) replicate table at the feature's own uniform column.
        """
        return sample_features_fused(value, sigma, reps, hol_idx, u)

    def guarantee_prob(y_hat, mean, sd, delta):
        if task == "classification":
            return mean
        bias = mean - y_hat
        safe = jnp.maximum(sd, 1e-12)
        phi = jax.scipy.stats.norm.cdf
        prob = phi((delta - bias) / safe) - phi((-delta - bias) / safe)
        return jnp.where(sd <= 1e-12, (jnp.abs(bias) <= delta).astype(f32), prob)

    def ami_prob(y, y_hat, delta):
        """Eq. 1 guarantee probability from the AMI output slice."""
        if task == "regression":
            y_bar = jnp.mean(y)
            sd = jnp.sqrt(jnp.mean((y - y_bar) ** 2))
            return guarantee_prob(y_hat, y_bar, sd, delta)
        probs = (
            jnp.bincount(y.astype(jnp.int32), length=n_classes).astype(f32) / m
        )
        return probs[y_hat.astype(jnp.int32)]

    def sobol_from_outputs(f_all, y_hat):
        """Main-effect indices from the pre-evaluated Saltelli block."""
        if task == "classification":
            f_all = (f_all.astype(jnp.int32) == y_hat.astype(jnp.int32)).astype(f32)
        f_all = f_all - jnp.mean(f_all)  # center (see sobol_indices.py)
        fa, fb = f_all[:m_sobol], f_all[m_sobol : 2 * m_sobol]
        fab = f_all[2 * m_sobol :].reshape(k, m_sobol)
        var_y = jnp.var(f_all)
        v_j = jnp.mean(fb[None] * (fab - fa[None]), axis=1)
        return jnp.where(
            var_y > 1e-12, jnp.clip(v_j / jnp.maximum(var_y, 1e-12), 0, 1), 0.0
        )

    def sobol_rows(value, sigma, reps):
        """Saltelli A/B/AB block: ((k+2)*m_sobol, k)."""
        ua, ub = u_sob[:, :k], u_sob[:, k:]
        xa = sample_rows(value, sigma, reps, ua)
        xb = sample_rows(value, sigma, reps, ub)
        eye = jnp.eye(k, dtype=bool)
        xab = jnp.where(eye[:, None, :], xb[None], xa[None]).reshape(
            k * m_sobol, k
        )
        return jnp.concatenate([xa, xb, xab], 0)

    def precompute(vals, n, z0, step):
        """Incremental-AFC precompute: every data-proportional pass runs
        HERE, once per request, before the loop (DESIGN.md § Incremental
        AFC).  The plan ladder min(z⁰ + i·γ, n) enumerates every z the
        planner can reach (γ and max_iters are loop constants), which is
        what lets the holistic membership counts be precomputed per
        candidate plan.  Returns ``(None, None, None)`` under rescan.
        """
        incremental, use_kernel = resolve_afc_plan(
            afc_backend, cap=vals.shape[1], cached=cached
        )
        if not incremental:
            return None, None, None
        shift = vals[:, 0]
        ptab = prefix_power_sums(vals, shift, use_kernel=use_kernel)
        rindex = None
        if n_hol:
            zcand = jnp.minimum(
                z0[:, None]
                + jnp.arange(max_iters + 1, dtype=jnp.int32)[None, :] * step,
                n[:, None],
            )
            rindex = build_rank_index(vals[hol_idx], n[hol_idx], zcand[hol_idx])
        return ptab, shift, rindex

    def afc(vals, n, agg_ids, ptab, shift, rindex, z, it):
        """(value, sigma, replicates) at plan z — strategy-routed.

        Incremental: one (k, 5) gather into the prefix tables feeds the
        unchanged estimator tail, and holistic order statistics come
        from rank queries against the presorted column — nothing in
        here scales with cap.  Rescan ("ref"): the pre-refactor full
        pass per iteration.  Replicate ranks use counter-based RNG on
        the iteration index (identical draws on both strategies) so the
        while_loop body stays shape- and key-static and the two
        strategies stay z-plan-parity comparable.
        """
        incremental, use_kernel = resolve_afc_plan(
            afc_backend, cap=vals.shape[1], cached=cached
        )
        if incremental:
            value, sigma = estimates_from_power_sums(
                prefix_moments_at(ptab, z), z, n, agg_ids, shift
            )
        else:
            value, sigma = masked_estimates(
                vals, z, n, agg_ids, use_kernel=use_kernel
            )
        if not n_hol:
            return value, sigma, jnp.zeros((0, n_boot), f32)
        key = jax.random.fold_in(base_key, it)
        if incremental:
            targets = bootstrap_rank_targets(z[hol_idx], qs, key, n_boot)
            sel = select_ranks_indexed(rindex, z[hol_idx], targets)
            q_val, reps = finish_quantile_estimates(
                sel, z[hol_idx], n[hol_idx]
            )
        else:
            q_val, reps = masked_quantile_estimates(
                vals[hol_idx],
                z[hol_idx],
                n[hol_idx],
                qs,
                key,
                n_boot,
                use_kernel=use_kernel,
            )
        value = value.at[hol_idx].set(q_val)
        sigma = sigma.at[hol_idx].set(0.0)
        return value, sigma, reps

    def evaluate(vals, n, agg_ids, exact, delta, ptab, shift, rindex, z, it):
        """AFC + AMI + Sobol via ONE model dispatch at plan z.

        Rows: [AMI (m,k) | point estimate (1,k) | Saltelli A/B/AB
        ((k+2)*m_sobol, k)] -> slice outputs for the guarantee check and
        the main-effect indices.
        """
        value, sigma, reps = afc(vals, n, agg_ids, ptab, shift, rindex, z, it)
        x_ami = sample_rows(value, sigma, reps, u_ami)
        batch = jnp.concatenate(
            [x_ami, value[None, :], sobol_rows(value, sigma, reps)], 0
        )
        y_all = model_fn(batch, exact).astype(f32)

        y_hat = y_all[m]
        prob = ami_prob(y_all[:m], y_hat, delta)
        idx = sobol_from_outputs(y_all[m + 1 :], y_hat)
        return y_hat, prob, idx, reps

    def init_eval(vals, n, agg_ids, exact, delta, act, tau, cap_eff,
                  z0, ptab, shift, rindex):
        """z⁰ evaluation: AMI-only dispatch (m+1 rows), cond-gated Sobol.

        The Saltelli block is only evaluated — via ``lax.cond``, so
        immediately-guaranteed requests skip its cost entirely — when the
        loop will actually be entered.  (Under vmap the cond becomes a
        select and both branches run.)  Returns the initial loop carry.
        """
        value0, sigma0, reps0 = afc(
            vals, n, agg_ids, ptab, shift, rindex, z0, jnp.zeros((), jnp.int32)
        )
        y0_all = model_fn(
            jnp.concatenate(
                [sample_rows(value0, sigma0, reps0, u_ami), value0[None, :]], 0
            ),
            exact,
        ).astype(f32)
        y_hat0 = y0_all[m]
        prob0 = ami_prob(y0_all[:m], y_hat0, delta)
        idx0 = jax.lax.cond(
            act & (prob0 < tau) & jnp.any(z0 < n) & (cap_eff > 0),
            lambda: sobol_from_outputs(
                model_fn(sobol_rows(value0, sigma0, reps0), exact).astype(f32),
                y_hat0,
            ),
            lambda: jnp.zeros((k,), f32),
        )
        return (z0, jnp.zeros((), jnp.int32), y_hat0, prob0, idx0, reps0)

    def want_more(carry, act, tau, cap_eff, n):
        """The Eq. 1 while-condition: another planner iteration needed?"""
        z, it, _, prob, _, _ = carry
        return act & (prob < tau) & (it < cap_eff) & jnp.any(z < n)

    def step_plan(carry, vals, n, agg_ids, exact, delta, step,
                  ptab, shift, rindex):
        """One planner iteration: step z along the Sobol direction, evaluate."""
        z, it, _, _, idx, _ = carry
        d = direction(idx, z, n)
        z = next_plan(z, d, step, n)
        y_hat, prob, idx, reps = evaluate(
            vals, n, agg_ids, exact, delta, ptab, shift, rindex, z, it + 1
        )
        return (z, it + 1, y_hat, prob, idx, reps)

    return SimpleNamespace(
        precompute=precompute,
        init_eval=init_eval,
        want_more=want_more,
        step_plan=step_plan,
    )


def _parse_feature_spec(k, holistic, quantiles, approximate):
    hol = tuple(int(j) for j in holistic)
    n_hol = len(hol)
    hol_idx = jnp.asarray(hol, jnp.int32) if n_hol else None
    qs = jnp.asarray([0.5] * n_hol if quantiles is None else list(quantiles), f32)
    if qs.shape[0] != n_hol:
        raise ValueError("quantiles must align with holistic indices")
    approx = jnp.asarray(
        [True] * k if approximate is None else list(approximate), bool
    )
    return hol_idx, n_hol, qs, approx


def build_fused_executor(
    model_fn,
    *,
    k: int,
    task: str,
    n_classes: int = 2,
    m: int = 512,
    m_sobol: int = 128,
    alpha: float = 0.05,
    gamma: float = 0.01,
    tau: float = 0.95,
    max_iters: int = 32,
    afc_backend: str = "auto",
    holistic: Sequence[int] = (),
    quantiles: Sequence[float] | None = None,
    n_boot: int = 256,
    approximate: Sequence[bool] | None = None,
    boot_seed: int = 0,
    prebuilt: bool = False,
):
    """Returns jit-able ``run(vals (k,cap), n (k,), agg_ids (k,), delta) -> FusedResult``.

    ``prebuilt=True`` builds the cache-fed twin: ``run(vals, n, agg_ids,
    delta, exact, tables, active=None, tau=None, iter_cap=None)`` takes a
    :class:`PrebuiltTables` (built once by :func:`build_afc_precompute` and
    kept device-resident by the feature-store cache) instead of running the
    internal per-request precompute — a cache hit pays zero precompute and
    zero H2D re-transfer.  The AFC strategy resolves with ``cached=True``
    (incremental at every cap under plain "auto"; the env override still
    wins, in which case the tables ride along unused on the rescan path).
    Everything after the precompute is the same ``_executor_core`` body, so
    cache-hit and cache-miss dispatches of the same executable are
    bitwise-identical and a prebuilt run matches the plain executor
    wherever both resolve to the same strategy.

    ``model_fn``: (rows (n,k), exact (e,)) -> (n,) predictions (regression
    values or class ids); must be jittable — tabular models and LM heads both
    qualify.  ``exact`` carries the request's exactly-computed features so a
    single compiled executor serves every request of the pipeline.

    ``run`` also accepts an optional trailing ``active`` flag (scalar bool)
    used by fixed-lane admission batching (serving/runtime.py): a vmapped
    batch pads to a constant lane count and marks pad lanes inactive, so the
    jit cache sees ONE shape per cap bucket regardless of batch fill.  An
    inactive lane never enters the while_loop (its guarantee predicate is
    forced false), reports ``iters == 0`` and ``samples_used == 0``, and its
    y_hat/prob are the init-dispatch values over its zero-padded buffers —
    callers slice inactive lanes off before interpreting results.

    Two further optional trailing inputs promote degradation knobs from
    compile-time constants to **traced loop state** (SLO-aware serving,
    DESIGN.md § Graceful degradation): ``tau`` overrides the build-time
    confidence target and ``iter_cap`` the planner-iteration ceiling, per
    call (per lane under vmap).  Both are data, not shape — an admission
    controller can vary them every batch without ever minting a new
    executable per cap bucket.  ``iter_cap`` is clamped to the static
    ``max_iters``, which still bounds the while_loop and sizes the
    incremental-AFC candidate ladder (a smaller traced cap only uses a
    prefix of that ladder); ``m_sobol``/``m`` stay static because they set
    the megabatch SHAPE.  ``None`` (the single-request default) compiles
    the constants in exactly as before.

    ``model_fn`` is invoked exactly ONCE per planner iteration, on a
    ``(m + 1 + (k+2)*m_sobol, k)`` megabatch (see module docstring).

    ``afc_backend`` selects the AFC strategy (``ops.resolve_afc_plan``,
    resolved at trace time with the buffer cap): "auto" picks per cap
    bucket — the **incremental** path (a once-per-request precompute:
    ``prefix_power_sums`` tables for the parametric features, a
    ``build_rank_index`` argsort structure for the holistic columns —
    hoisting every data-proportional pass out of the while_loop, whose
    body then reads (value, sigma) by O(1) gathers and answers holistic
    order statistics by prefix-membership rank queries) above
    ``ops.AFC_REF_MAX_CAP``, the rescan path at or below it, where the
    precompute does not amortize — honoring the REPRO_AFC_BACKEND env as a
    force-override.  "kernel" forces incremental with the Pallas table
    kernel (interpret off-TPU); "incremental" (alias "inc") forces
    incremental with the jnp table oracle regardless of env (explicit
    strategy pinning for parity tests and CPU benchmarks).  "ref" keeps
    the pre-refactor **rescan** oracle — a full ``masked_estimates`` /
    ``masked_select_ranks_ref`` pass per iteration — as the parity
    baseline (CI pins it via the env).

    Holistic support (static, per-pipeline): ``holistic`` lists the feature
    indices whose ``agg_ids`` are MEDIAN/QUANTILE, ``quantiles`` their q's
    (aligned with ``holistic``; median = 0.5), ``n_boot`` the bootstrap
    replicate count B, ``boot_seed`` the base of the counter-based replicate
    RNG (folded with the iteration index; shared across vmapped lanes, like
    the QMC uniforms).  ``approximate`` flags per feature whether Biathlon
    may sample it (False = Fig. 10 exact-only: pinned to z = n).
    """
    resolve_afc_plan(afc_backend)  # validate the string at build time

    hol_idx, n_hol, qs, approx = _parse_feature_spec(
        k, holistic, quantiles, approximate
    )
    core = _executor_core(
        model_fn, k=k, task=task, n_classes=n_classes, m=m, m_sobol=m_sobol,
        alpha=alpha, gamma=gamma, max_iters=max_iters, afc_backend=afc_backend,
        hol_idx=hol_idx, n_hol=n_hol, qs=qs, approx=approx,
        n_boot=int(n_boot), base_key=jax.random.PRNGKey(boot_seed),
        cached=prebuilt,
    )
    static_tau, static_max_iters = tau, max_iters

    def _knobs(active, tau, iter_cap):
        act = jnp.asarray(True) if active is None else active
        # degradation knobs: traced when supplied, compile-time otherwise
        tau = static_tau if tau is None else tau
        cap_eff = (
            static_max_iters
            if iter_cap is None
            else jnp.minimum(jnp.asarray(iter_cap, jnp.int32), static_max_iters)
        )
        return act, tau, cap_eff

    def _finish(vals, n, agg_ids, delta, exact, act, tau, cap_eff,
                z0, step, ptab, shift, rindex) -> FusedResult:
        carry0 = core.init_eval(
            vals, n, agg_ids, exact, delta, act, tau, cap_eff,
            z0, ptab, shift, rindex,
        )
        z, iters, y_hat, prob, _, _ = jax.lax.while_loop(
            lambda c: core.want_more(c, act, tau, cap_eff, n),
            lambda c: core.step_plan(
                c, vals, n, agg_ids, exact, delta, step, ptab, shift, rindex
            ),
            carry0,
        )
        return FusedResult(
            y_hat=y_hat,
            prob=prob,
            iters=iters,
            z=z,
            samples_used=jnp.where(act, jnp.sum(jnp.minimum(z, n)), 0),
        )

    if prebuilt:

        @jax.jit
        def run_prebuilt(vals, n, agg_ids, delta, exact, tables,
                         active=None, tau=None, iter_cap=None) -> FusedResult:
            act, tau, cap_eff = _knobs(active, tau, iter_cap)
            cap = vals.shape[1]
            n = jnp.minimum(n.astype(jnp.int32), cap)
            z0 = jnp.where(approx, initial_plan(n, alpha), n)
            step = gamma_abs(n, gamma)
            incremental, _ = resolve_afc_plan(afc_backend, cap=cap, cached=True)
            ptab = tables.ptab if incremental else None
            shift = tables.shift if incremental else None
            rindex = tables.rindex if (incremental and n_hol) else None
            return _finish(vals, n, agg_ids, delta, exact, act, tau, cap_eff,
                           z0, step, ptab, shift, rindex)

        return run_prebuilt

    @jax.jit
    def run(vals, n, agg_ids, delta, exact, active=None, tau=None,
            iter_cap=None) -> FusedResult:
        act, tau, cap_eff = _knobs(active, tau, iter_cap)
        cap = vals.shape[1]
        n = jnp.minimum(n.astype(jnp.int32), cap)
        # exact-only operators (Fig. 10 ablation) consume their full groups
        # from z⁰ on — the planner then never selects them (exhausted).
        z0 = jnp.where(approx, initial_plan(n, alpha), n)
        step = gamma_abs(n, gamma)
        ptab, shift, rindex = core.precompute(vals, n, z0, step)
        return _finish(vals, n, agg_ids, delta, exact, act, tau, cap_eff,
                       z0, step, ptab, shift, rindex)

    return run


#: Fixed-lane fused contract: the vmapped ``run`` above is the whole batch
#: program, so the jit cache is keyed by (lanes, k, cap) only — one
#: executable per power-of-two cap bucket; delta/tau/iter_cap are traced
#: (lanes,) inputs, never cache keys.  Bootstrap draws are counter-based
#: (``fold_in`` of the per-request iteration index on a closure key), the
#: lane-recycling bitwise-parity property.  The planner while body must
#: price independent of cap on the incremental-AFC path (all O(cap) work in
#: the once-per-request precompute).
FUSED_CONTRACT = register_contract(ExecutableContract(
    name="fused",
    builder="repro.core.executor_fused.build_fused_executor",
    executables_per_bucket=1,
    collectives=0,
    donated=("vals (lanes, k, cap) values buffer",),
    while_body_flat=True,
    description=(
        "fixed-lane batch program (BatchedFusedServer): one executable per "
        "cap bucket, donated values buffer threaded out as lane_vals, "
        "counter-based bootstrap RNG in the planner loop"
    ),
))

#: Prebuilt-tables twin of the fused contract: identical loop body, but the
#: per-request precompute is hoisted out of the executable entirely (fed as
#: the PrebuiltTables input), so the cap bucket still mints exactly one
#: executable and cache hits re-dispatch it with zero new compiles.
FUSED_PREBUILT_CONTRACT = register_contract(ExecutableContract(
    name="fused_prebuilt",
    builder="repro.core.executor_fused.build_fused_executor (prebuilt=True)",
    executables_per_bucket=1,
    collectives=0,
    donated=("vals (lanes, k, cap) values buffer",),
    while_body_flat=True,
    description=(
        "cache-fed fused program: PrebuiltTables replace the internal "
        "precompute; one executable per cap bucket shared by cache hits "
        "and misses"
    ),
))


def build_afc_precompute(
    *,
    k: int,
    alpha: float = 0.05,
    gamma: float = 0.01,
    max_iters: int = 32,
    holistic: Sequence[int] = (),
    quantiles: Sequence[float] | None = None,
    approximate: Sequence[bool] | None = None,
):
    """The standalone incremental-AFC precompute + its append-delta refresh.

    Returns ``SimpleNamespace(cold, refresh)``:

    ``cold(vals (k, cap), n (k,)) -> PrebuiltTables``
        exactly the tables ``_executor_core.precompute`` would build inside
        a run — same shift basis (``vals[:, 0]``), same candidate ladder
        ``min(z⁰ + i·γ, n)`` — hoisted into its own jit executable so the
        feature-store cache can build once and re-dispatch many times.

    ``refresh(vals, n, tables, j, x, aff) -> (vals', n', tables')``
        applies ONE logged append event — value ``x (k,)`` (the appended
        row read through each feature's column) inserted at prefix position
        ``j`` of the groups flagged by ``aff (k,)`` — as delta updates:
        the values buffer shifts right from j, the power-sum tables get the
        :func:`append_power_sums` two-sum row update, and the holistic
        index merges the event into its maintained sorted runs
        (:func:`merge_sorted_prefix`) then recounts ``blk_cnt`` against the
        new candidate ladder (n changed, so z⁰ and the ladder move) without
        re-sorting.  Callers must route ``j == 0`` events to ``cold``
        instead — they replace the shift basis.  All of j/x/aff are traced,
        so replaying a whole append log is N dispatches of one executable.

    The ladder math is deliberately duplicated from the executor core in
    one place only (here), and the parity tests pin ``cold`` against the
    in-executor precompute via served-result equality.
    """
    hol_idx, n_hol, _qs, approx = _parse_feature_spec(
        k, holistic, quantiles, approximate
    )
    _, use_kernel = resolve_afc_plan("auto", cached=True)
    n_z = max_iters + 1

    def zcand_of(n):
        z0 = jnp.where(approx, initial_plan(n, alpha), n)
        step = gamma_abs(n, gamma)
        return jnp.minimum(
            z0[:, None] + jnp.arange(n_z, dtype=jnp.int32)[None, :] * step,
            n[:, None],
        )

    @jax.jit
    def cold(vals, n) -> PrebuiltTables:
        cap = vals.shape[1]
        n = jnp.minimum(n.astype(jnp.int32), cap)
        shift = vals[:, 0]
        ptab = prefix_power_sums(vals, shift, use_kernel=use_kernel)
        if n_hol:
            zc = zcand_of(n)
            rindex = build_rank_index(vals[hol_idx], n[hol_idx], zc[hol_idx])
        else:
            rindex = empty_rank_index()
        return PrebuiltTables(ptab=ptab, shift=shift, rindex=rindex)

    @jax.jit
    def refresh(vals, n, tables: PrebuiltTables, j, x, aff):
        cap = vals.shape[1]
        n = jnp.minimum(n.astype(jnp.int32), cap)
        j = jnp.asarray(j, jnp.int32)
        x = jnp.asarray(x, f32)
        aff = jnp.asarray(aff, bool)
        c = jnp.arange(cap, dtype=jnp.int32)
        prev = jnp.concatenate([vals[:, :1], vals[:, :-1]], axis=1)
        inserted = jnp.where(
            c[None, :] < j, vals, jnp.where(c[None, :] == j, x[:, None], prev)
        )
        vals2 = jnp.where(aff[:, None] & (j < cap), inserted, vals)
        ptab2 = append_power_sums(tables.ptab, tables.shift, j, x, aff)
        n2 = jnp.minimum(n + aff.astype(jnp.int32), cap)
        if n_hol:
            ri = tables.rindex
            msv, msi, _ = merge_sorted_prefix(
                ri.sorted_vals, ri.sorted_idx, n[hol_idx], cap,
                j, x[hol_idx], aff[hol_idx],
            )
            block = ri.sorted_vals.shape[1] // (ri.blk_cnt.shape[-1] - 1)
            rindex = rank_index_from_sorted(
                msv, msi, zcand_of(n2)[hol_idx], block=block
            )
        else:
            rindex = tables.rindex
        return vals2, n2, PrebuiltTables(
            ptab=ptab2, shift=tables.shift, rindex=rindex
        )

    return SimpleNamespace(cold=cold, refresh=refresh, n_hol=n_hol)


#: The standalone precompute is one more executable per cap bucket on the
#: cached serving paths (cold builds on cache misses; the delta refresh
#: shares its jit cache entry count — one executable each, but refresh only
#: traces when appends actually happen, so the steady-state budget is 1).
AFC_PRECOMPUTE_CONTRACT = register_contract(ExecutableContract(
    name="afc_precompute",
    builder="repro.core.executor_fused.build_afc_precompute",
    executables_per_bucket=1,
    collectives=0,
    description=(
        "once-per-cache-miss precompute: prefix power-sum tables + holistic "
        "rank index as a standalone executable whose output (PrebuiltTables) "
        "stays device-resident in the feature-store cache"
    ),
))


def build_chunked_executor(
    model_fn,
    *,
    chunk_iters: int,
    k: int,
    task: str,
    n_classes: int = 2,
    m: int = 512,
    m_sobol: int = 128,
    alpha: float = 0.05,
    gamma: float = 0.01,
    tau: float = 0.95,
    max_iters: int = 32,
    afc_backend: str = "auto",
    holistic: Sequence[int] = (),
    quantiles: Sequence[float] | None = None,
    n_boot: int = 256,
    approximate: Sequence[bool] | None = None,
    boot_seed: int = 0,
    prebuilt: bool = False,
):
    """Chunked twin of :func:`build_fused_executor` for continuous batching.

    ``prebuilt=True`` is the cache-fed admission path: ``init`` grows a
    trailing ``tables`` argument (:class:`PrebuiltTables` from the
    feature-store cache) and packs those leaves into the LaneState instead
    of running the per-request precompute; the AFC strategy resolves with
    ``cached=True`` in both init and chunk, so every cap bucket keeps one
    consistent LaneState structure (full-size ptab/rindex leaves).

    Returns ``(init, chunk)``, both jit-able per-lane functions over
    :class:`LaneState` (callers vmap/shard them; serving/continuous.py):

    ``init(vals, n, agg_ids, delta, exact, active, tau, iter_cap)``
        runs the once-per-request work — buffer clamp, z⁰ seeding, the
        incremental-AFC precompute, and the z⁰ evaluation with its
        cond-gated Sobol block — and packs EVERYTHING into a
        :class:`LaneState`.  All eight arguments are mandatory (they are
        per-lane data under vmap; ``tau``/``iter_cap``/``delta`` are the
        PR-6 traced knobs, re-assigned per admission).

    ``chunk(state) -> state``
        advances the planner at most ``chunk_iters`` iterations — the same
        ``while_loop`` as the monolithic executor with one extra conjunct
        ``j < chunk_iters`` on a per-dispatch trip counter.  Because the
        planner's own predicate is evaluated first each trip, running
        chunks back-to-back replays EXACTLY the monolithic iteration
        sequence: with ``chunk_iters >= max_iters`` one chunk IS the
        monolithic loop (bitwise-identical z/iters — the parity oracle
        relation), and a done/inactive lane costs zero trips (its
        predicate is false on entry).  ``done`` is refreshed after the
        loop so the scheduler reads recyclability without re-deriving the
        predicate.

    The per-iteration computation is shared with the monolithic executor
    (``_executor_core``), including the counter-based bootstrap RNG — a
    request's trajectory depends only on its own buffers and ``it``
    (folded from 0 per request), never on which lane or chunk boundary it
    landed on, which is what makes recycling bitwise-reproducible against
    a serial replay of the same trace.
    """
    resolve_afc_plan(afc_backend)  # validate the string at build time
    chunk_iters = int(chunk_iters)
    if chunk_iters < 1:
        raise ValueError(f"chunk_iters must be >= 1, got {chunk_iters}")

    hol_idx, n_hol, qs, approx = _parse_feature_spec(
        k, holistic, quantiles, approximate
    )
    core = _executor_core(
        model_fn, k=k, task=task, n_classes=n_classes, m=m, m_sobol=m_sobol,
        alpha=alpha, gamma=gamma, max_iters=max_iters, afc_backend=afc_backend,
        hol_idx=hol_idx, n_hol=n_hol, qs=qs, approx=approx,
        n_boot=int(n_boot), base_key=jax.random.PRNGKey(boot_seed),
        cached=prebuilt,
    )
    static_max_iters = max_iters

    def _pack(vals, n, agg_ids, delta, exact, active, tau, iter_cap,
              ptab, shift, rindex) -> LaneState:
        act = jnp.asarray(active, bool)
        tau = jnp.asarray(tau, f32)
        iter_cap = jnp.asarray(iter_cap, jnp.int32)
        delta = jnp.asarray(delta, f32)
        cap_eff = jnp.minimum(iter_cap, static_max_iters)
        z0 = jnp.where(approx, initial_plan(n, alpha), n)
        carry = core.init_eval(
            vals, n, agg_ids, exact, delta, act, tau, cap_eff,
            z0, ptab, shift, rindex,
        )
        z, it, y_hat, prob, idx, reps = carry
        return LaneState(
            vals=vals, n=n, agg_ids=agg_ids, delta=delta, exact=exact,
            active=act, tau=tau, iter_cap=iter_cap,
            z=z, it=it, y_hat=y_hat, prob=prob, idx=idx, reps=reps,
            done=~core.want_more(carry, act, tau, cap_eff, n),
            ptab=ptab if ptab is not None else jnp.zeros((k, 0, 4), f32),
            shift=shift if shift is not None else jnp.zeros((k,), f32),
            rindex=rindex if rindex is not None else empty_rank_index(),
        )

    def init(vals, n, agg_ids, delta, exact, active, tau, iter_cap) -> LaneState:
        cap = vals.shape[1]
        n = jnp.minimum(n.astype(jnp.int32), cap)
        z0 = jnp.where(approx, initial_plan(n, alpha), n)
        step = gamma_abs(n, gamma)
        ptab, shift, rindex = core.precompute(vals, n, z0, step)
        return _pack(vals, n, agg_ids, delta, exact, active, tau, iter_cap,
                     ptab, shift, rindex)

    def init_prebuilt(vals, n, agg_ids, delta, exact, active, tau, iter_cap,
                      tables: PrebuiltTables) -> LaneState:
        cap = vals.shape[1]
        n = jnp.minimum(n.astype(jnp.int32), cap)
        incremental, _ = resolve_afc_plan(afc_backend, cap=cap, cached=True)
        state = _pack(
            vals, n, agg_ids, delta, exact, active, tau, iter_cap,
            tables.ptab if incremental else None,
            tables.shift if incremental else None,
            tables.rindex if (incremental and n_hol) else None,
        )
        # keep the full-size leaves in the table even when the env override
        # forces rescan — one LaneState structure per cap bucket either way
        return state._replace(
            ptab=tables.ptab, shift=tables.shift, rindex=tables.rindex
        )

    def chunk(state: LaneState) -> LaneState:
        incremental, _ = resolve_afc_plan(
            afc_backend, cap=state.vals.shape[1], cached=prebuilt
        )
        ptab = state.ptab if incremental else None
        shift = state.shift if incremental else None
        rindex = state.rindex if (incremental and n_hol) else None
        n = state.n
        cap_eff = jnp.minimum(state.iter_cap, static_max_iters)
        step = gamma_abs(n, gamma)
        carry0 = (state.z, state.it, state.y_hat, state.prob,
                  state.idx, state.reps)

        def cond(carry_j):
            carry, j = carry_j
            return (
                core.want_more(carry, state.active, state.tau, cap_eff, n)
                & (j < chunk_iters)
            )

        def body(carry_j):
            carry, j = carry_j
            carry = core.step_plan(
                carry, state.vals, n, state.agg_ids, state.exact,
                state.delta, step, ptab, shift, rindex,
            )
            return carry, j + 1

        carry, _ = jax.lax.while_loop(
            cond, body, (carry0, jnp.zeros((), jnp.int32))
        )
        z, it, y_hat, prob, idx, reps = carry
        return state._replace(
            z=z, it=it, y_hat=y_hat, prob=prob, idx=idx, reps=reps,
            done=~core.want_more(carry, state.active, state.tau, cap_eff, n),
        )

    return (init_prebuilt if prebuilt else init), chunk


#: Continuous-table contracts: ``build_chunked_executor`` returns the
#: (refill, chunk) pair, each its own jit executable — together the
#: 2-per-cap-bucket budget of ContinuousBatchedServer.  Both donate the
#: LaneState table so iteration-level recycling updates it in place, and
#: both inherit the counter-based RNG discipline (a recycled lane replays
#: the exact bootstrap stream of a fresh one).
REFILL_CONTRACT = register_contract(ExecutableContract(
    name="refill",
    builder="repro.core.executor_fused.build_chunked_executor (init)",
    executables_per_bucket=1,
    collectives=0,
    donated=("table (LaneState pytree, lanes-leading)",),
    description=(
        "single-lane init written into the donated table at one lane row; "
        "per-request degradation knobs are traced inputs, so admitting a "
        "request never mints an executable"
    ),
))

CHUNK_CONTRACT = register_contract(ExecutableContract(
    name="chunk",
    builder="repro.core.executor_fused.build_chunked_executor (chunk)",
    executables_per_bucket=1,
    collectives=0,
    donated=("table (LaneState pytree, lanes-leading)",),
    while_body_flat=True,
    description=(
        "bounded planner burst (<= chunk_iters trips) over every occupied "
        "lane of the donated table; cost-flat while body on the "
        "incremental-AFC path"
    ),
))

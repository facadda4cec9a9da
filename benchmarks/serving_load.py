"""Arrival-driven serving load curves: throughput / latency vs Poisson rate.

Drives the fixed-lane serving runtime (serving/runtime.py) with open-loop
Poisson arrival traces at several rates around the server's measured
saturation point, and records the provisioning curve InferLine-style
pipeline serving needs: per-rate throughput, p50/p99 latency, queueing
delay vs execution time, batch fill, and the compile count (which must stay
at one executable per power-of-two cap bucket regardless of batch fill —
the fixed-lane property).

Rates are chosen RELATIVE to measured FULL-BATCH capacity (``batch_size /
full_batch_service_time``, the per-lane-amortized best case) so the curve
shape is machine-independent; absolute rates are recorded in the payload.
Note the batch cost is nearly fill-invariant (a 2-lane batch costs almost
as much as a full one), so effective capacity at low arrival rates — where
admission fills are small — is WELL below the full-batch number: expect
high utilization even at the lowest load factor.  The saturation signal to
read is queueing delay and throughput plateau, not utilization.

The sharded sweep (``run_sharded_sweep``) varies SERVING-MESH device
counts at fixed batch size (the PR-4 lane-sharding backend): each admission
batch's lanes are partitioned over a 1-D mesh, so a device only runs its
own lane block's while-loop — stragglers stall 1/D of the batch instead of
all of it, and the per-device programs execute concurrently.  With an
accelerator present it runs in-process over the real devices (device
counts capped at what is visible; the process that holds the chips is the
only one that may use them).  On a CPU host it is a rehearsal: the
``--sharded-worker`` child is re-exec'd with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set before jax
initializes.  A tighter-than-default delta makes iteration counts
heterogeneous across lanes — the regime where straggler localization
pays.
Writes ``BENCH_serving.json`` at the repo root.
"""
from __future__ import annotations

import pathlib
import subprocess
import sys
import time

from benchmarks.common import DEFAULT_CFG, bundle, csv_row, write_bench_json
from repro.core.executor import BiathlonConfig
from repro.data.synthetic import poisson_arrivals
from repro.serving import BatchedFusedServer, ServingRuntime

BENCH_SERVING_JSON = pathlib.Path(__file__).resolve().parent.parent / "BENCH_serving.json"

PIPE = "turbofan"
BATCH_SIZE = 8
MAX_WAIT_MS = 20.0
# offered load as a fraction of full-batch (per-lane-amortized) capacity;
# see the module docstring for why 0.3x is not "30% utilization"
LOAD_FACTORS = (0.3, 1.0, 3.0)
N_REQUESTS = 48

# ---- sharded lane-parallel sweep (one TPU v5e host holds 4 chips) ----
DEVICE_COUNTS = (1, 2, 4)
# fraction of the pipeline's default delta: tight enough that requests
# iterate a heterogeneous number of times (the straggler regime)
SHARDED_DELTA_FRAC = 0.35
SHARDED_RATE_FACTOR = 3.0  # offered load vs 1-device capacity (saturating)


def _measure_capacity(
    srv: BatchedFusedServer, requests: list[dict], reps: int = 3,
    best_of: bool = False,
) -> float:
    """Steady-state full-batch service rate (req/s), post-warmup.

    ``best_of=False`` keeps the mean-of-reps methodology the tracked
    ``serving_load`` section of BENCH_serving.json was measured with (so
    re-runs stay comparable across PRs); the sharded sweep uses best-of to
    suppress 2-core scheduling noise and records that choice in its
    payload.
    """
    batch = [requests[i % len(requests)] for i in range(srv.batch_size)]
    srv.serve_batch(batch)  # warm every shape this batch hits
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        srv.serve_batch(batch)
        times.append(time.perf_counter() - t0)
    dt = min(times) if best_of else sum(times) / len(times)
    return srv.batch_size / max(dt, 1e-9)


def run(pipeline: str = PIPE) -> list[str]:
    out = []
    cfg = BiathlonConfig(**DEFAULT_CFG)
    b = bundle(pipeline)
    srv = BatchedFusedServer(b, cfg, batch_size=BATCH_SIZE)
    runtime = ServingRuntime(srv, max_wait_s=MAX_WAIT_MS / 1e3)
    runtime.warmup(b.requests)

    capacity_rps = _measure_capacity(srv, b.requests)
    payload = {
        "pipeline": pipeline,
        "batch_size": BATCH_SIZE,
        "max_wait_ms": MAX_WAIT_MS,
        "n_requests_per_rate": N_REQUESTS,
        "capacity_rps": capacity_rps,
        "config": {"m": cfg.m, "m_sobol": cfg.m_sobol, "tau": cfg.tau},
        "rates": [],
    }
    for j, lf in enumerate(LOAD_FACTORS):
        rate = lf * capacity_rps
        arrivals = poisson_arrivals(b.requests, rate, n=N_REQUESTS, seed=100 + j)
        stats = runtime.run(arrivals, warmup=False)
        s = stats.summary()
        s["load_factor"] = lf
        s["rate_rps"] = rate
        payload["rates"].append(s)
        out.append(
            csv_row(
                f"serving_load/{pipeline}/x{lf:g}",
                1e3 * s["p50_latency_ms"],
                f"rate={rate:.1f}rps;thru={s['throughput_rps']:.1f}rps;"
                f"p99_ms={s['p99_latency_ms']:.1f};"
                f"qdelay_ms={s['mean_queue_delay_ms']:.1f};"
                f"fill={s['mean_batch_fill']:.1f};"
                f"compiles={s['compile_count']}",
            )
        )
    # fixed lanes: the whole sweep (fills 1..batch_size across all rates)
    # may only ever compile one executable per cap bucket
    payload["total_compile_count"] = srv.compile_count
    payload["compiled_buckets"] = srv.compiled_buckets
    write_bench_json("serving_load", payload, path=str(BENCH_SERVING_JSON))
    return out


# ------------------------------------------------------------------------
# SLO-aware graceful degradation: latency/guarantee Pareto + 3x overload
# ------------------------------------------------------------------------
# per-request latency budgets as multiples of the full-batch service time;
# tighter budgets buy latency with accuracy (looser knobs) and shed rate
SLO_FACTORS = (4.0, 8.0, 16.0, 32.0)
OVERLOAD_FACTOR = 3.0
N_REQUESTS_SLO = 64


def run_adaptive_slo(pipeline: str = PIPE) -> list[str]:
    """Deadline-driven degradation under 3x overload (BENCH adaptive_slo).

    The same saturating Poisson trace (3x measured full-batch capacity) is
    replayed once WITHOUT degradation — the PR-2 behavior, queue delay
    absorbing the whole overload, p99 growing with trace length — and then
    across a sweep of per-request SLO budgets with the knob-tier admission
    controller installed.  Each sweep point reports the latency/guarantee
    trade InferLine/Loki frame: p99 over served requests, achieved
    guarantee rate (each request judged against the tau it was actually
    served under), shed rate, and mean knob tier.  The fixed-lane compile
    contract must hold throughout: knob changes and fill variation are
    traced data, so every sweep point asserts ZERO new executables.
    """
    from repro.serving import DegradationController, default_tiers

    cfg = BiathlonConfig(**DEFAULT_CFG)
    b = bundle(pipeline)
    srv = BatchedFusedServer(b, cfg, batch_size=BATCH_SIZE)
    runtime = ServingRuntime(srv, max_wait_s=MAX_WAIT_MS / 1e3)
    runtime.warmup(b.requests)
    capacity_rps = _measure_capacity(srv, b.requests)
    service_s = BATCH_SIZE / capacity_rps
    rate = OVERLOAD_FACTOR * capacity_rps
    arrivals = poisson_arrivals(b.requests, rate, n=N_REQUESTS_SLO, seed=424)

    out = []
    payload = {
        "pipeline": pipeline,
        "batch_size": BATCH_SIZE,
        "max_wait_ms": MAX_WAIT_MS,
        "n_requests": N_REQUESTS_SLO,
        "capacity_rps": capacity_rps,
        "full_batch_service_ms": 1e3 * service_s,
        "overload_factor": OVERLOAD_FACTOR,
        "rate_rps": rate,
        "config": {"m": cfg.m, "m_sobol": cfg.m_sobol, "tau": cfg.tau},
        "pareto": [],
    }
    # -- baseline: no degradation, queue absorbs the 3x overload unboundedly
    base = runtime.run(arrivals, warmup=False).summary()
    payload["overload_baseline"] = {
        k: base[k]
        for k in (
            "n", "p50_latency_ms", "p99_latency_ms", "mean_queue_delay_ms",
            "guarantee_rate", "shed_rate", "compile_count",
        )
    }
    out.append(
        csv_row(
            f"adaptive_slo/{pipeline}/baseline",
            1e3 * base["p50_latency_ms"],
            f"p99_ms={base['p99_latency_ms']:.1f};shed=0.00;"
            f"guar={base['guarantee_rate']:.3f};compiles={base['compile_count']}",
        )
    )
    # -- Pareto sweep: degradation on, SLO budget varied
    for slo_f in SLO_FACTORS:
        slo_s = slo_f * service_s
        ctl = DegradationController(
            default_tiers(cfg.tau, cfg.max_iters),
            service_est_s=service_s,
            lanes=BATCH_SIZE,
        )
        rt = ServingRuntime(
            srv, max_wait_s=MAX_WAIT_MS / 1e3, slo_s=slo_s, controller=ctl,
        )
        stats = rt.run(arrivals, warmup=False)
        s = stats.summary()
        entry = {
            "slo_factor": slo_f,
            "slo_ms": 1e3 * slo_s,
            **{
                k: s[k]
                for k in (
                    "n", "n_offered", "n_shed", "shed_rate",
                    "deadline_met_rate", "p50_latency_ms", "p99_latency_ms",
                    "mean_queue_delay_ms", "guarantee_rate", "mean_tier",
                    "max_tier", "mean_sample_frac", "compile_count",
                )
            },
        }
        payload["pareto"].append(entry)
        out.append(
            csv_row(
                f"adaptive_slo/{pipeline}/slo{slo_f:g}x",
                1e3 * s["p50_latency_ms"],
                f"slo_ms={1e3 * slo_s:.0f};p99_ms={s['p99_latency_ms']:.1f};"
                f"shed={s['shed_rate']:.2f};guar={s['guarantee_rate']:.3f};"
                f"tier={s['mean_tier']:.2f};compiles={s['compile_count']}",
            )
        )
    # knob changes + fill variation are traced data: the whole sweep may
    # never mint an executable beyond the warmed cap buckets
    payload["zero_compiles_during_measurement"] = bool(
        base["compile_count"] == 0
        and all(e["compile_count"] == 0 for e in payload["pareto"])
    )
    payload["p99_bounded_vs_baseline"] = bool(
        payload["pareto"]
        and min(e["p99_latency_ms"] for e in payload["pareto"])
        < payload["overload_baseline"]["p99_latency_ms"]
    )
    write_bench_json("adaptive_slo", payload, path=str(BENCH_SERVING_JSON))
    return out


# ------------------------------------------------------------------------
# Continuous batching: chunked lane recycling vs fixed-lane admission
# ------------------------------------------------------------------------
CONTINUOUS_CHUNK_ITERS = 4
CONTINUOUS_RATE_FACTOR = 3.0  # saturating, like the sharded sweep
# Tight enough for CAP-BOUND stragglers next to converge-at-init requests
# (measured turbofan full-batch iters at 0.08: [0, 0, 64, 6, 2, 0, 0, 64]).
# The sharded sweep's 0.35 is NOT that regime — there every request
# converges at init (iters <= 5, mean_sample_frac ~ 0.06), so a fixed
# batch never waits on a straggler and recycling has nothing to reclaim.
CONTINUOUS_DELTA_FRAC = 0.08


def run_continuous(pipeline: str = PIPE) -> list[str]:
    """Fixed-lane vs continuous batching on the SAME saturating trace.

    One Poisson trace at 3x the fixed-lane full-batch capacity, with a
    tight delta so per-request iteration counts are heterogeneous, replayed
    through (a) the PR-3 fixed-lane runtime — every admission batch held
    open until its slowest lane converges — and (b) the chunked lane-table
    runtime, which refills a converged lane from the queue at the next
    chunk boundary.  Same pipeline, same batch_size (= lanes), same
    requests: ``throughput_gain`` isolates the scheduling policy.

    Tracked invariants (BENCH_serving.json["continuous_batching"]):
    ``zero_compiles_during_measurement`` (2 executables per cap bucket,
    all minted during warmup) and ``occupancy_gain`` — chunk-boundary lane
    occupancy above the fixed path's ``mean_batch_fill / lanes``.
    """
    from repro.serving import ContinuousBatchedServer, ContinuousServingRuntime

    b = bundle(pipeline)
    cfg = BiathlonConfig(
        **DEFAULT_CFG, delta=b.pipeline.delta_default * CONTINUOUS_DELTA_FRAC
    )
    # -- fixed-lane baseline on the shared trace
    srv_f = BatchedFusedServer(b, cfg, batch_size=BATCH_SIZE)
    rt_f = ServingRuntime(srv_f, max_wait_s=MAX_WAIT_MS / 1e3)
    rt_f.warmup(b.requests)
    capacity_rps = _measure_capacity(srv_f, b.requests, reps=5, best_of=True)
    rate = CONTINUOUS_RATE_FACTOR * capacity_rps
    arrivals = poisson_arrivals(b.requests, rate, n=N_REQUESTS, seed=321)
    fixed_stats = rt_f.run(arrivals, warmup=False)
    fixed = fixed_stats.summary()
    # iteration-level lane occupancy of the fixed path: useful iterations /
    # lane-iterations held open.  This is the number straggler waste eats,
    # and the like-for-like twin of the continuous path's chunk-slot
    # ``lane_occupancy`` — admission-time ``mean_batch_fill`` is NOT (at
    # overload every fixed batch admits full, yet its lanes then idle
    # behind the straggler; converge-at-init requests hold no loop
    # residency on either path).
    by_batch: dict[int, list[int]] = {}
    for r in fixed_stats.records:
        by_batch.setdefault(r.batch_id, []).append(r.iters)
    held = sum(BATCH_SIZE * max(its) for its in by_batch.values())
    fixed_iter_occ = (
        sum(sum(its) for its in by_batch.values()) / held if held else 0.0
    )

    # -- continuous: persistent lane table, chunked dispatch, recycling
    srv_c = ContinuousBatchedServer(
        b, cfg, batch_size=BATCH_SIZE, chunk_iters=CONTINUOUS_CHUNK_ITERS
    )
    rt_c = ContinuousServingRuntime(srv_c)
    rt_c.warmup([a[1] for a in arrivals])
    cont = rt_c.run(arrivals, warmup=False).summary()

    gain = cont["throughput_rps"] / max(fixed["throughput_rps"], 1e-9)
    payload = {
        "pipeline": pipeline,
        "batch_size": BATCH_SIZE,
        "chunk_iters": CONTINUOUS_CHUNK_ITERS,
        "n_requests": N_REQUESTS,
        "delta_frac": CONTINUOUS_DELTA_FRAC,
        "rate_factor": CONTINUOUS_RATE_FACTOR,
        "capacity_rps": capacity_rps,
        "rate_rps": rate,
        "config": {"m": cfg.m, "m_sobol": cfg.m_sobol, "tau": cfg.tau},
        "fixed": fixed,
        "continuous": cont,
        "throughput_gain": gain,
        "lane_occupancy": cont["lane_occupancy"],
        "fixed_mean_fill_frac": fixed["mean_batch_fill"] / BATCH_SIZE,
        "fixed_iter_occupancy": fixed_iter_occ,
        "occupancy_above_fixed": bool(
            cont["lane_occupancy"] > fixed_iter_occ
        ),
        "occupancy_gain": cont["lane_occupancy"] / max(fixed_iter_occ, 1e-9),
        "zero_compiles_during_measurement": bool(
            fixed["compile_count"] == 0 and cont["compile_count"] == 0
        ),
    }
    write_bench_json("continuous_batching", payload, path=str(BENCH_SERVING_JSON))
    return [
        csv_row(
            f"continuous/{pipeline}/fixed",
            1e3 * fixed["p50_latency_ms"],
            f"thru={fixed['throughput_rps']:.1f}rps;"
            f"p99_ms={fixed['p99_latency_ms']:.1f};"
            f"fill={fixed['mean_batch_fill']:.1f};"
            f"compiles={fixed['compile_count']}",
        ),
        csv_row(
            f"continuous/{pipeline}/chunk{CONTINUOUS_CHUNK_ITERS}",
            1e3 * cont["p50_latency_ms"],
            f"thru={cont['throughput_rps']:.1f}rps;"
            f"p99_ms={cont['p99_latency_ms']:.1f};"
            f"occ={cont['lane_occupancy']:.2f};"
            f"recycles={cont['n_recycles']};gain={gain:.2f}x;"
            f"compiles={cont['compile_count']}",
        ),
    ]


# ------------------------------------------------------------------------
# Device-scaling sweep: sharded lanes over a 1-D serving mesh
# ------------------------------------------------------------------------
def run_sharded(pipeline: str = PIPE) -> list[str]:
    """Sweep serving-mesh sizes at fixed batch size, in this process.

    Covers the ``DEVICE_COUNTS`` that fit the visible devices.  The same
    saturating Poisson trace (rate pinned to 3x the 1-device capacity) is
    replayed at every device count, so ``throughput_rps`` isolates the
    sharding effect: lane blocks run concurrently and each device's
    while-loop exits at ITS stragglers, not the batch's.
    """
    import jax

    from repro.launch.mesh import make_serving_mesh

    n_visible = len(jax.devices())
    counts = [d for d in DEVICE_COUNTS if d <= n_visible]
    b = bundle(pipeline)
    cfg = BiathlonConfig(
        **DEFAULT_CFG, delta=b.pipeline.delta_default * SHARDED_DELTA_FRAC
    )
    out = []
    payload = {
        "pipeline": pipeline,
        "batch_size": BATCH_SIZE,
        "max_wait_ms": MAX_WAIT_MS,
        "n_requests": N_REQUESTS,
        "delta_frac": SHARDED_DELTA_FRAC,
        "rate_factor": SHARDED_RATE_FACTOR,
        "capacity_method": "best_of_5",
        "config": {"m": cfg.m, "m_sobol": cfg.m_sobol, "tau": cfg.tau},
        "platform": jax.devices()[0].platform,
        "devices": [],
    }
    rate = None
    for d in counts:
        srv = BatchedFusedServer(
            b, cfg, batch_size=BATCH_SIZE, mesh=make_serving_mesh(d)
        )
        runtime = ServingRuntime(srv, max_wait_s=MAX_WAIT_MS / 1e3)
        runtime.warmup(b.requests)
        capacity_rps = _measure_capacity(srv, b.requests, reps=5, best_of=True)
        if rate is None:  # pin the trace to the 1-device saturation point
            rate = SHARDED_RATE_FACTOR * capacity_rps
        arrivals = poisson_arrivals(b.requests, rate, n=N_REQUESTS, seed=777)
        stats = runtime.run(arrivals, warmup=False)
        s = stats.summary()
        entry = {
            "n_devices": d,
            "capacity_rps": capacity_rps,
            "rate_rps": rate,
            **s,
        }
        payload["devices"].append(entry)
        out.append(
            csv_row(
                f"serving_sharded/{pipeline}/dev{d}",
                1e3 * s["p50_latency_ms"],
                f"cap={capacity_rps:.1f}rps;thru={s['throughput_rps']:.1f}rps;"
                f"p99_ms={s['p99_latency_ms']:.1f};"
                f"imb={s.get('mean_lane_imbalance', 0.0):.2f};"
                f"compiles={s['compile_count']}",
            )
        )
    d1 = payload["devices"][0]["throughput_rps"]
    payload["speedup_vs_1dev"] = [
        e["throughput_rps"] / max(d1, 1e-9) for e in payload["devices"]
    ]
    write_bench_json("sharded_scaling", payload, path=str(BENCH_SERVING_JSON))
    return out


def run_sharded_sweep(pipeline: str = PIPE) -> list[str]:
    """The device-scaling sweep over the devices this host has.

    With an accelerator the sweep runs here, over the real chips: a child
    process could not reach a chip this process already holds.  On a CPU
    host it re-execs a worker with forced host devices
    (:func:`run_sharded_subprocess`), the multi-device rehearsal.
    """
    import jax

    if jax.default_backend() != "cpu":
        return run_sharded(pipeline)
    return run_sharded_subprocess(pipeline)


def run_sharded_subprocess(pipeline: str = PIPE) -> list[str]:
    """Re-exec this module as a worker with forced host devices (CPU only).

    jax fixes its device list at first initialization, so the CPU
    rehearsal cannot run in a process that already touched jax with the
    default flags.
    """
    from repro.launch.mesh import forced_host_devices_env

    repo = pathlib.Path(__file__).resolve().parent.parent
    env = forced_host_devices_env(max(DEVICE_COUNTS))
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.serving_load",
         "--sharded-worker", pipeline],
        env=env, cwd=str(repo), text=True, capture_output=True,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded worker failed:\n{proc.stdout}\n{proc.stderr}"
        )
    return [l for l in proc.stdout.splitlines() if l.startswith("serving_sharded/")]


# ------------------------------------------------------------------------
# Availability under faults: storm replay vs fault-free ground truth
# ------------------------------------------------------------------------
FAULT_SEED = 11
FAULT_CHUNK_FAIL_PROB = 0.15
FAULT_POISON_PROB = 0.10
FAULT_REFILL_FAIL_PROB = 0.05
FAULT_CACHE_CORRUPT_CALLS = (1,)
# p99 under the storm must stay within this factor of the fault-free p99 —
# retries are bounded (max_retries) and backoff is virtual, so blowups here
# mean unbounded retry queueing, the failure mode this section guards
FAULT_P99_BOUND = 20.0


def run_fault_recovery(pipeline: str = PIPE) -> list[str]:
    """Continuous serving through a seeded fault storm at ~capacity load.

    One warmed :class:`ContinuousBatchedServer` serves the same Poisson
    trace twice: bare (ground truth), then wrapped in a
    :class:`FaultyContinuousServer` injecting chunk-dispatch failures
    (rolled back to the chunk-boundary checkpoint and replayed), lane
    poisoning (quarantined, re-admitted once), admission failures
    (retried whole — admission is idempotent), and one feature-cache
    corruption (detected by the power-sum checksum, rebuilt cold).

    Tracked invariants (BENCH_serving.json["fault_recovery"]): every
    surviving request's z-plan bitwise-matches the fault-free replay
    (checkpoint restore + counter-based RNG make recovery exact, not
    approximate), p99 stays within ``FAULT_P99_BOUND`` of fault-free
    (bounded retries, no unbounded queueing), zero executables are minted
    during either measured run, and the two recovery mutants introduced
    with this section are caught by the checker (9/9 overall).
    """
    from repro.analysis.mutations import MUTATIONS
    from repro.serving import (
        ContinuousBatchedServer,
        ContinuousServingRuntime,
        FaultProfile,
        FaultyContinuousServer,
    )

    b = bundle(pipeline)
    cfg = BiathlonConfig(
        **DEFAULT_CFG, delta=b.pipeline.delta_default * CONTINUOUS_DELTA_FRAC
    )
    # capacity is priced on the fixed-lane twin (serve_batch amortization),
    # as every other section does — the trace runs at 1x that rate
    srv_cap = BatchedFusedServer(b, cfg, batch_size=BATCH_SIZE)
    srv_cap.serve_batch(b.requests[:BATCH_SIZE])
    capacity_rps = _measure_capacity(srv_cap, b.requests, reps=5, best_of=True)
    arrivals = poisson_arrivals(
        b.requests, capacity_rps, n=N_REQUESTS, seed=555
    )

    srv = ContinuousBatchedServer(
        b, cfg, batch_size=BATCH_SIZE, chunk_iters=CONTINUOUS_CHUNK_ITERS,
        cache_size=8,
    )
    ContinuousServingRuntime(srv).warmup([a[1] for a in arrivals])
    compiles_before = srv.compile_count

    free = ContinuousServingRuntime(srv).run(arrivals, warmup=False)
    want = {r.req_id: r.z for r in free.records if r.disposition == "ok"}

    srv.cache.verify_hits = True  # the storm corrupts an entry; detect it
    prof = FaultProfile(
        seed=FAULT_SEED,
        chunk_fail_prob=FAULT_CHUNK_FAIL_PROB,
        poison_prob=FAULT_POISON_PROB,
        refill_fail_prob=FAULT_REFILL_FAIL_PROB,
        cache_corrupt_calls=FAULT_CACHE_CORRUPT_CALLS,
    )
    fsrv = FaultyContinuousServer(srv, prof)
    storm = ContinuousServingRuntime(fsrv).run(arrivals, warmup=False)
    srv.cache.verify_hits = False

    ok = [r for r in storm.records if r.disposition == "ok"]
    survivors_match = bool(ok) and all(r.z == want[r.req_id] for r in ok)
    s_free, s_storm = free.summary(), storm.summary()
    p99_ratio = s_storm["p99_latency_ms"] / max(s_free["p99_latency_ms"], 1e-9)
    mutations = {name: bool(fn()) for name, fn in MUTATIONS.items()}
    new_muts = ("rollback_skips_bootstrap_carry",
                "quarantine_readmit_without_reset")

    payload = {
        "pipeline": pipeline,
        "batch_size": BATCH_SIZE,
        "chunk_iters": CONTINUOUS_CHUNK_ITERS,
        "n_requests": N_REQUESTS,
        "delta_frac": CONTINUOUS_DELTA_FRAC,
        "rate_rps": capacity_rps,
        "config": {"m": cfg.m, "m_sobol": cfg.m_sobol, "tau": cfg.tau},
        "fault_profile": {
            "seed": FAULT_SEED,
            "chunk_fail_prob": FAULT_CHUNK_FAIL_PROB,
            "poison_prob": FAULT_POISON_PROB,
            "refill_fail_prob": FAULT_REFILL_FAIL_PROB,
            "cache_corrupt_calls": list(FAULT_CACHE_CORRUPT_CALLS),
        },
        "fault_events": len(fsrv.events),
        "fault_free": s_free,
        "storm": s_storm,
        "n_ok": len(ok),
        "n_rollbacks": storm.n_rollbacks,
        "n_retries": storm.n_retries,
        "n_poisoned": storm.n_poisoned,
        "n_failed": storm.n_failed,
        "cache_corruptions_detected": srv.cache.corruptions,
        "survivors_bitwise_match": survivors_match,
        "p99_ratio_vs_fault_free": p99_ratio,
        "p99_bounded": bool(p99_ratio < FAULT_P99_BOUND),
        "zero_compiles_during_measurement": bool(
            srv.compile_count == compiles_before
        ),
        "mutations_caught": sum(mutations.values()),
        "mutations_total": len(mutations),
        "new_mutations_caught": bool(all(mutations[n] for n in new_muts)),
    }
    write_bench_json("fault_recovery", payload, path=str(BENCH_SERVING_JSON))
    return [
        csv_row(
            f"fault_recovery/{pipeline}/storm",
            1e3 * s_storm["p50_latency_ms"],
            f"events={len(fsrv.events)};ok={len(ok)}/{N_REQUESTS};"
            f"rollbacks={storm.n_rollbacks};poisoned={storm.n_poisoned};"
            f"bitwise={'Y' if survivors_match else 'N'};"
            f"p99x={p99_ratio:.1f};"
            f"muts={sum(mutations.values())}/{len(mutations)};"
            f"compiles={srv.compile_count - compiles_before}",
        )
    ]


if __name__ == "__main__":
    if "--sharded-worker" in sys.argv:
        pipe = sys.argv[sys.argv.index("--sharded-worker") + 1]
        for row in run_sharded(pipe):
            print(row)
    else:
        print("name,us_per_call,derived")
        for row in run():
            print(row)
        for row in run_adaptive_slo():
            print(row)
        for row in run_continuous():
            print(row)
        for row in run_fault_recovery():
            print(row)
        for row in run_sharded_sweep():
            print(row)

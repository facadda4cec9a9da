"""Serving launcher: ``python -m repro.launch.serve --pipeline <name>``.

Builds one of the seven paper pipelines and serves it through the chosen
executor, printing the paper's §4 metrics.

Modes:
  host           paper-faithful host-loop executor, one request at a time
  fused          single-XLA-program executor, one request at a time
  fused-batched  arrival-driven runtime: Poisson arrivals -> request queue
                 -> max-wait/max-size admission -> fixed-lane batched
                 dispatch (serving/runtime.py)
  fused-sharded  fused-batched with the fixed lanes sharded data-parallel
                 over a 1-D device mesh (--devices N; launch/mesh.py
                 make_serving_mesh).  On CPU, simulate devices with
                 XLA_FLAGS=--xla_force_host_platform_device_count=N.
  fused-continuous  continuous batching: a persistent lane table advanced
                 ``--chunk-iters`` planner iterations per dispatch, with
                 completed lanes recycled to queued requests at chunk
                 boundaries (serving/continuous.py + the lane-table
                 scheduler in serving/runtime.py).  Accepts --devices for
                 a sharded table; --max-wait-ms does not apply (admission
                 happens at every chunk boundary).

Holistic (MEDIAN/QUANTILE) pipelines are served by every mode: pick the
``sensor_health`` pipeline (median + tail-quantile features) or pass
``--median`` for the appendix-D AVG→MEDIAN substitution of any Table 1
pipeline.

SLO-aware graceful degradation (fused-batched / fused-sharded /
fused-continuous):
``--slo-ms`` attaches a latency budget to every arrival, ``--degrade``
installs the knob-tier admission controller (deadline-driven (delta, tau,
iter_cap) scaling + load shedding; serving/degrade.py), and
``--fault-profile`` injects a seeded fault schedule (service-time spikes,
transient executor failures, or an arrival burst; serving/faults.py) to
exercise degradation and recovery.  On fused-continuous the profiles map
to chunk-granular fault points (chunk-dispatch failures roll back to the
checkpointed chunk boundary and replay; refill failures retry the
admission) and a continuous-only ``poison`` profile NaN-scrambles a
lane's carry to exercise per-lane quarantine.  All of it composes with
``--degrade``, ``--devices``, and ``--cache-size``.

Examples:
  PYTHONPATH=src python -m repro.launch.serve --pipeline trip_fare
  PYTHONPATH=src python -m repro.launch.serve --pipeline turbofan --mode fused
  PYTHONPATH=src python -m repro.launch.serve --pipeline sensor_health --mode fused
  PYTHONPATH=src python -m repro.launch.serve --pipeline turbofan --median \
      --mode fused-batched --arrival-rate 50 --batch-size 8 --max-wait-ms 20
  PYTHONPATH=src python -m repro.launch.serve --pipeline turbofan \
      --mode fused-batched --arrival-rate 80 --slo-ms 250 --degrade \
      --fault-profile spikes
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.serve --pipeline turbofan --mode fused-sharded \
      --devices 4 --batch-size 8
  PYTHONPATH=src python -m repro.launch.serve --pipeline turbofan \
      --mode fused-continuous --arrival-rate 80 --batch-size 8 \
      --chunk-iters 4
  PYTHONPATH=src python -m repro.launch.serve --pipeline turbofan \
      --mode fused --cache-size 64

``--cache-size N`` (fused modes, unsharded) turns on the hot-group feature
cache: per-(group, version) device-resident sample buffers + AFC precompute
served from an N-entry LRU (serving/feature_cache.py), so repeat hits on a
hot key pay zero precompute and zero H2D transfer.
"""
from __future__ import annotations

import argparse

from repro.core.executor import BiathlonConfig
from repro.data.synthetic import (
    EXTRA_PIPELINE_NAMES,
    PIPELINE_NAMES,
    make_pipeline,
    make_pipeline_median,
    poisson_arrivals,
)
from repro.launch.compile_cache import configure_compile_cache
from repro.serving import BatchedFusedServer, BiathlonServer, ServingRuntime


def _print_table(d: dict) -> None:
    for k, v in d.items():
        print(f"  {k:24s} {v:.4f}" if isinstance(v, float) else f"  {k:24s} {v}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--pipeline", choices=PIPELINE_NAMES + EXTRA_PIPELINE_NAMES, required=True
    )
    ap.add_argument(
        "--mode",
        choices=("host", "fused", "fused-batched", "fused-sharded",
                 "fused-continuous"),
        default="host",
    )
    ap.add_argument(
        "--devices", type=int, default=None,
        help="serving-mesh size for fused-sharded / fused-continuous "
        "(default: every visible device for fused-sharded, unsharded for "
        "fused-continuous); batch-size must be divisible by it",
    )
    ap.add_argument(
        "--chunk-iters", type=int, default=4,
        help="planner iterations per chunk dispatch (fused-continuous); "
        "lower = finer-grained lane recycling, higher = fewer dispatches",
    )
    ap.add_argument(
        "--median", action="store_true",
        help="appendix-D variant: AVG→MEDIAN substitution, retrained",
    )
    ap.add_argument("--rows-per-group", type=int, default=20000)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--tau", type=float, default=0.95)
    ap.add_argument("--delta", type=float, default=None)
    ap.add_argument("--alpha", type=float, default=0.05)
    ap.add_argument("--gamma", type=float, default=0.01)
    ap.add_argument("--m", type=int, default=500)
    # fused-batched runtime knobs
    ap.add_argument("--arrival-rate", type=float, default=20.0,
                    help="Poisson arrival rate in requests/s (fused-batched)")
    ap.add_argument("--batch-size", type=int, default=8,
                    help="fixed lane count per admission batch (fused-batched)")
    ap.add_argument("--max-wait-ms", type=float, default=20.0,
                    help="admission max-wait in milliseconds (fused-batched)")
    # SLO-aware graceful degradation + fault injection (fused-batched)
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="per-request latency budget in ms; arrivals get a "
                    "deadline of t + slo (fused-batched)")
    ap.add_argument("--degrade", action="store_true",
                    help="install the knob-tier admission controller: "
                    "deadline-driven (delta, tau, iter_cap) scaling + load "
                    "shedding (requires --slo-ms for deadline pressure)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="shed when the queue exceeds this bound (--degrade)")
    ap.add_argument("--fault-profile",
                    choices=("none", "spikes", "failures", "burst", "poison"),
                    default="none",
                    help="seeded fault schedule wrapped around the server "
                    "(serving/faults.py): serve_batch-level on fixed-lane "
                    "modes, chunk-granular on fused-continuous; 'poison' "
                    "(lane-carry NaN scramble) is fused-continuous only")
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--cache-size", type=int, default=None,
                    help="enable the hot-group feature cache with this many "
                    "LRU entries: fused modes serve version-keyed "
                    "device-resident precompute (fused / fused-batched / "
                    "fused-continuous; incompatible with --devices)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    configure_compile_cache()

    make = make_pipeline_median if args.median else make_pipeline
    bundle = make(
        args.pipeline, rows_per_group=args.rows_per_group,
        n_serve_groups=6, n_requests=args.requests,
    )
    cfg = BiathlonConfig(
        tau=args.tau, delta=args.delta, alpha=args.alpha, gamma=args.gamma,
        m=args.m, m_sobol=max(args.m // 4, 64),
    )
    delta = cfg.delta if cfg.delta is not None else bundle.pipeline.delta_default

    if args.mode == "fused-continuous":
        import time as _time

        import jax

        from repro.serving import (
            ContinuousBatchedServer,
            ContinuousServingRuntime,
            DegradationController,
            FaultProfile,
            FaultyContinuousServer,
            default_tiers,
            inject_burst,
        )

        mesh = None
        if args.devices is not None:
            from repro.launch.mesh import make_serving_mesh

            mesh = make_serving_mesh(args.devices)
        srv = ContinuousBatchedServer(
            bundle, cfg, batch_size=args.batch_size,
            chunk_iters=args.chunk_iters, mesh=mesh,
            cache_size=args.cache_size,
        )
        arrivals = poisson_arrivals(
            bundle.requests, args.arrival_rate, n=args.requests,
            seed=args.seed,
        )
        if args.fault_profile == "burst":
            mid = arrivals[len(arrivals) // 2][0]
            arrivals = inject_burst(
                arrivals, at_t=mid, n=max(args.requests, 8),
                width_s=0.05, seed=args.fault_seed,
            )
        controller = None
        if args.degrade:
            # seed the controller's per-request service estimate from one
            # measured post-warmup chunk: a request needs at most
            # ceil(max_iters / chunk_iters) chunks to converge
            cap = srv.trace_cap([a[1] for a in arrivals])
            table, _ = srv.admit(
                srv.new_table(cap), cap,
                [(l, bundle.requests[l % len(bundle.requests)], None)
                 for l in range(args.batch_size)],
            )
            table = jax.block_until_ready(srv.run_chunk(table))
            t0 = _time.perf_counter()
            jax.block_until_ready(srv.run_chunk(table))
            chunk_s = _time.perf_counter() - t0
            n_chunks_est = -(-cfg.max_iters // args.chunk_iters)
            controller = DegradationController(
                default_tiers(cfg.tau, cfg.max_iters),
                service_est_s=chunk_s * n_chunks_est,
                lanes=args.batch_size,
                max_queue=args.max_queue,
            )
        # pre-warm the INNER server before wrapping it: injected faults
        # must hit measured traffic (with call indices starting at 0),
        # never the compilation warmup
        ContinuousServingRuntime(srv).warmup([a[1] for a in arrivals])
        server = srv
        if args.fault_profile == "spikes":
            server = FaultyContinuousServer(
                srv, FaultProfile(seed=args.fault_seed, spike_prob=0.2,
                                  spike_s=0.25),
            )
        elif args.fault_profile == "failures":
            server = FaultyContinuousServer(
                srv, FaultProfile(seed=args.fault_seed, chunk_fail_prob=0.1,
                                  refill_fail_prob=0.05),
            )
        elif args.fault_profile == "poison":
            server = FaultyContinuousServer(
                srv, FaultProfile(seed=args.fault_seed, poison_prob=0.05),
            )
        runtime = ContinuousServingRuntime(
            server,
            slo_s=None if args.slo_ms is None else args.slo_ms / 1e3,
            controller=controller,
        )
        stats = runtime.run(arrivals, warmup=False)
        print(f"[serve] {args.pipeline} mode={args.mode} "
              f"rate={args.arrival_rate:.1f}rps lanes={args.batch_size} "
              f"devices={srv.n_devices} chunk_iters={args.chunk_iters} "
              f"delta={delta:.4f} slo={args.slo_ms}ms "
              f"degrade={args.degrade} faults={args.fault_profile}")
        _print_table(stats.summary())
        return

    if args.mode in ("fused-batched", "fused-sharded"):
        import time as _time

        from repro.serving import (
            DegradationController,
            FaultProfile,
            FaultyServer,
            default_tiers,
            inject_burst,
        )

        if args.fault_profile == "poison":
            ap.error("--fault-profile poison scrambles lane carry at chunk "
                     "boundaries; use --mode fused-continuous")
        mesh = None
        if args.mode == "fused-sharded":
            from repro.launch.mesh import make_serving_mesh

            mesh = make_serving_mesh(args.devices)
        srv = BatchedFusedServer(
            bundle, cfg, batch_size=args.batch_size, mesh=mesh,
            cache_size=args.cache_size,
        )
        controller = None
        if args.degrade:
            # seed the controller's service estimate with one measured
            # full-lane batch (post-warmup, so it times the steady state)
            batch = [bundle.requests[i % len(bundle.requests)]
                     for i in range(args.batch_size)]
            srv.serve_batch(batch)
            t0 = _time.perf_counter()
            srv.serve_batch(batch)
            controller = DegradationController(
                default_tiers(cfg.tau, cfg.max_iters),
                service_est_s=_time.perf_counter() - t0,
                lanes=args.batch_size,
                max_queue=args.max_queue,
            )
        arrivals = poisson_arrivals(
            bundle.requests, args.arrival_rate, n=args.requests, seed=args.seed
        )
        if args.fault_profile == "burst":
            mid = arrivals[len(arrivals) // 2][0]
            arrivals = inject_burst(
                arrivals, at_t=mid, n=max(args.requests, 8),
                width_s=0.05, seed=args.fault_seed,
            )
        # pre-warm every cap bucket on the INNER server: injected faults
        # must hit measured traffic (with call indices starting at 0),
        # never the compilation warmup
        ServingRuntime(srv).warmup([a[1] for a in arrivals])
        server = srv
        if args.fault_profile == "spikes":
            server = FaultyServer(
                srv, FaultProfile(seed=args.fault_seed, spike_prob=0.2,
                                  spike_s=0.25),
            )
        elif args.fault_profile == "failures":
            server = FaultyServer(
                srv, FaultProfile(seed=args.fault_seed, fail_prob=0.15),
            )
        runtime = ServingRuntime(
            server, max_wait_s=args.max_wait_ms / 1e3,
            slo_s=None if args.slo_ms is None else args.slo_ms / 1e3,
            controller=controller,
        )
        stats = runtime.run(arrivals)
        print(f"[serve] {args.pipeline} mode={args.mode} "
              f"rate={args.arrival_rate:.1f}rps lanes={args.batch_size} "
              f"devices={srv.n_devices} "
              f"max_wait={args.max_wait_ms:.0f}ms delta={delta:.4f} "
              f"slo={args.slo_ms}ms degrade={args.degrade} "
              f"faults={args.fault_profile}")
        _print_table(stats.summary())
        return

    if args.cache_size is not None and args.mode != "fused":
        ap.error("--cache-size requires a fused mode")
    srv = BiathlonServer(bundle, cfg, mode=args.mode,
                         cache_size=args.cache_size)
    srv.serve(bundle.requests[0])  # warm the jit caches
    stats = srv.serve_all(bundle.requests)
    s = stats.summary(bundle.pipeline.delta_default, bundle.pipeline.task)
    print(f"[serve] {args.pipeline} mode={args.mode} delta={delta:.4f}"
          + (f" cache={args.cache_size}" if args.cache_size is not None
             else ""))
    _print_table(s)
    if srv.cache is not None:
        _print_table({f"cache_{k}": v for k, v in srv.cache.stats.items()})
        srv.check_compile_contract()


if __name__ == "__main__":
    main()

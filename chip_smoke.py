"""Serve turbofan and sensor_health end to end on a TPU, and check the result.

    python chip_smoke.py                # phases A-C on one chip
    python chip_smoke.py --four-chips   # only the lanes mesh, over four chips

The quickest proof that the system still runs on the chip.  It drives the
continuous serving path the CLI builds (``ContinuousBatchedServer`` +
``ContinuousServingRuntime``, as in ``repro.launch.serve --mode
fused-continuous``) at the benchmark scale of ``benchmarks/common.py``:

* A — turbofan (k=9 parametric, random forest) at cap bucket 131,072
  (groups of 45k-75k rows): the incremental AFC path, whose per-request
  prefix tables come from the ``prefix_power_sums`` Pallas kernel;
* B — sensor_health (MEDIAN/QUANTILE + parametric, GBM) at the same scale:
  the holistic rank index beside the prefix tables;
* C — sensor_health with groups of at most 1,024 rows: the rescan route,
  where ``sampled_moments`` and ``masked_select_ranks`` run in the loop.

Each phase warms up, then serves a Poisson trace and requires every request
served (none shed, failed or poisoned), finite predictions and no compile
inside the measured window.  It also requires ``tpu_custom_call`` in the
compiled executables that hold the kernels, the Pallas results to match
the jnp oracles on the chip, and prints the share of predictions within
delta of ``run_exact`` beside tau.

``--four-chips`` runs only the ``("lanes",)`` mesh: 8 lanes over 4 chips at
turbofan scale against the unsharded one-chip table on the same trace.

There is no CPU fallback: without a TPU it exits non-zero before any work.
No exception is caught.  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

LANES = 8            # lane-table width (2 lanes per chip on the mesh)
CHUNK_ITERS = 4      # planner iterations per chunk dispatch
N_ARRIVALS = 32      # Poisson arrivals served per phase
RATE_RPS = 50.0      # offered load; correctness does not depend on it
SMALL_ROWS = 800     # rows per group in phase C (sizes vary +-25%): cap 1,024
# kernel vs jnp oracle, the tolerances of tests/test_incremental_afc.py
# and tests/test_kernels.py (rank selection is exact there)
RTOL, ATOL = 3e-5, 1e-3


def require_tpu():
    """Exit non-zero unless JAX's first device is a TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(
            f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}; "
            "there is no CPU fallback"
        )
    if os.environ.get("REPRO_AFC_BACKEND"):
        sys.exit("chip_smoke: unset REPRO_AFC_BACKEND; it overrides the "
                 "kernel routing this script checks")
    return devices


class CompileClock:
    """Counts XLA backend compiles and their seconds, from JAX's events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        self.s = 0.0

    def __call__(self, event, secs, **_):
        if event == self.EVENT:
            self.n += 1
            self.s += secs

    def mark(self):
        return self.n, self.s

    def since(self, mark):
        return self.n - mark[0], self.s - mark[1]


def check(cond, what):
    if not cond:
        raise AssertionError(f"chip_smoke: {what}")


def serve_phase(tag, bundle, cfg, clock, *, mesh=None, seed=0):
    """Warm up, then serve a Poisson trace through the continuous runtime.

    Returns the server, the run's stats and the arrivals.  Raises unless
    every request was served with a finite prediction and nothing compiled
    inside the measured window.
    """
    from repro.data.synthetic import poisson_arrivals
    from repro.serving import ContinuousBatchedServer, ContinuousServingRuntime

    srv = ContinuousBatchedServer(
        bundle, cfg, batch_size=LANES, chunk_iters=CHUNK_ITERS, mesh=mesh
    )
    arrivals = poisson_arrivals(bundle.requests, RATE_RPS, n=N_ARRIVALS,
                                seed=seed)
    runtime = ContinuousServingRuntime(srv)
    mark, t0 = clock.mark(), time.perf_counter()
    runtime.warmup([req for _, req in arrivals])
    warm_s = time.perf_counter() - t0
    warm_n, warm_c = clock.since(mark)

    mark, t0 = clock.mark(), time.perf_counter()
    stats = runtime.run(arrivals, warmup=False)
    serve_s = time.perf_counter() - t0
    window_compiles = clock.since(mark)[0]

    s = stats.summary()
    y_hat = [r.y_hat for r in stats.records]
    print(
        f"[{tag}] cap={srv.trace_cap([r for _, r in arrivals])} "
        f"devices={srv.n_devices} lanes={LANES} chunk_iters={CHUNK_ITERS} "
        f"served={s['n']}/{N_ARRIVALS} shed={stats.n_shed} "
        f"failed={stats.n_failed} poisoned={stats.n_poisoned} "
        f"warmup_wall_s={warm_s!r} warmup_compiles={warm_n} "
        f"warmup_compile_s={warm_c!r} serve_wall_s={serve_s!r} "
        f"compiles_in_window={window_compiles} "
        f"runtime_compile_count={stats.compile_count} "
        f"n_chunks={stats.n_chunks} p50_latency_ms={s['p50_latency_ms']!r} "
        f"guarantee_rate={s['guarantee_rate']!r}",
        flush=True,
    )
    check(s["n"] == N_ARRIVALS, f"{tag}: served {s['n']} of {N_ARRIVALS}")
    check(stats.n_shed == 0 and stats.n_failed == 0
          and stats.n_poisoned == 0,
          f"{tag}: shed/failed/poisoned = {stats.n_shed}/{stats.n_failed}/"
          f"{stats.n_poisoned}")
    check(all(math.isfinite(y) for y in y_hat), f"{tag}: non-finite y_hat")
    check(stats.compile_count == 0 and window_compiles == 0,
          f"{tag}: {stats.compile_count} executables / {window_compiles} "
          "XLA compiles inside the measured window")
    srv.check_compile_contract()
    return srv, stats, arrivals


def reference_share(tag, bundle, cfg, stats, arrivals):
    """Share of served predictions within delta of ``run_exact``."""
    from repro.core.executor import run_exact

    p = bundle.pipeline
    delta = cfg.delta if cfg.delta is not None else p.delta_default
    exact = {}
    hits = []
    for r in stats.records:
        req = arrivals[r.req_id][1]
        key = tuple(sorted(req.items()))
        if key not in exact:
            exact[key] = run_exact(bundle.store, p, req)[0]
        hits.append(abs(r.y_hat - exact[key]) <= delta)
    share = sum(hits) / len(hits)
    print(f"[{tag}] reference: {sum(hits)}/{len(hits)} within delta="
          f"{delta!r} of run_exact (share {share!r}, tau={cfg.tau!r}; "
          f"{len(exact)} distinct requests)", flush=True)
    return share


def lane_buffer(srv, req, cap):
    """One served request's (k, cap) buffer and group sizes, on the device."""
    import jax.numpy as jnp

    from repro.serving.batched import lane_request_inputs

    vals, n, _, _ = lane_request_inputs(
        srv.bundle.pipeline, srv.bundle.store, req, cap
    )
    return jnp.asarray(vals), jnp.asarray(n)


def refill_args(srv, req, cap):
    """The refill executable's arguments for one admission of ``req``."""
    import jax.numpy as jnp

    vals, n = lane_buffer(srv, req, cap)
    p = srv.bundle.pipeline
    return (
        srv.new_table(cap), vals, n, srv._agg_ids,
        jnp.asarray(p.delta_default, jnp.float32),
        jnp.zeros((len(p.exact_features),), jnp.float32),
        jnp.asarray(srv.config.tau, jnp.float32),
        jnp.asarray(srv.config.max_iters, jnp.int32),
        jnp.asarray(0, jnp.int32),
    )


def require_kernel(tag, what, jitted, *args):
    """Compile ``jitted`` at ``args`` and require a Pallas call in it.

    Runs after the measured window: the re-trace fires the server's
    compile hooks, which the contract check has already read.
    """
    text = jitted.lower(*args).compile().as_text()
    n = text.count("tpu_custom_call")
    print(f"[{tag}] {what}: tpu_custom_call x{n}", flush=True)
    check(n > 0, f"{tag}: no tpu_custom_call in the {what} executable")


def prefix_kernel_vs_oracle(tag, srv, req, cap):
    """``prefix_power_sums`` Pallas vs jnp oracle on one served buffer."""
    import numpy as np

    from repro.kernels.sampled_agg import ops

    vals, _ = lane_buffer(srv, req, cap)
    shift = vals[:, 0]
    got = np.asarray(ops.prefix_power_sums(vals, shift, use_kernel=True))
    want = np.asarray(ops.prefix_power_sums(vals, shift, use_kernel=False))
    _report(tag, "prefix_power_sums", got, want)
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


def rescan_kernels_vs_oracle(tag, srv, req, cap):
    """``sampled_moments`` and ``masked_select_ranks``, Pallas vs jnp
    oracle, on one served small-group buffer at its z⁰ plan."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.executor_fused import pipeline_executor_kwargs
    from repro.core.planner import initial_plan
    from repro.kernels.sampled_agg import ops
    from repro.kernels.sampled_agg.quantile_select import masked_select_ranks
    from repro.kernels.sampled_agg.ref import masked_select_ranks_ref

    vals, n = lane_buffer(srv, req, cap)
    z = initial_plan(n, srv.config.alpha)
    shift = vals[:, 0]
    got = np.asarray(ops.moments(vals, z, shift, use_kernel=True))
    want = np.asarray(ops.moments(vals, z, shift, use_kernel=False))
    _report(tag, "sampled_moments", got, want)
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)

    kw = pipeline_executor_kwargs(srv.bundle.pipeline.agg_features)
    hol = jnp.asarray(kw["holistic"], jnp.int32)
    qs = jnp.asarray(kw["quantiles"], jnp.float32)
    targets = ops.bootstrap_rank_targets(
        z[hol], qs, jax.random.PRNGKey(0), srv.config.n_bootstrap
    )
    got = np.asarray(masked_select_ranks(
        vals[hol], z[hol], targets,
        interpret=jax.default_backend() != "tpu",
    ))
    want = np.asarray(masked_select_ranks_ref(vals[hol], z[hol], targets))
    _report(tag, "masked_select_ranks", got, want)
    np.testing.assert_array_equal(got, want)


def _report(tag, name, got, want):
    import numpy as np

    diff = np.abs(got.astype(np.float64) - want.astype(np.float64))
    rel = diff / np.maximum(np.abs(want.astype(np.float64)), 1e-30)
    print(f"[{tag}] {name} kernel vs oracle on the chip: shape={got.shape} "
          f"max_abs_diff={float(diff.max())!r} max_rel_diff="
          f"{float(rel.max())!r}", flush=True)


def build_bundle(tag, name, scale):
    from repro.data.synthetic import make_pipeline

    t0 = time.perf_counter()
    bundle = make_pipeline(name, **scale)
    print(f"[{tag}] built {name} {scale} in "
          f"{time.perf_counter() - t0!r} s", flush=True)
    return bundle


def one_chip(scale, cfg, clock):
    """Phases A-C."""
    small = dict(scale, rows_per_group=SMALL_ROWS)

    # A, B: the incremental route, prefix tables built by the Pallas kernel
    # in every refill; B adds the holistic rank index
    for tag, name in (("A turbofan", "turbofan"),
                      ("B sensor_health", "sensor_health")):
        b = build_bundle(tag, name, scale)
        srv, stats, arrivals = serve_phase(tag, b, cfg, clock)
        reference_share(tag, b, cfg, stats, arrivals)
        cap = srv.trace_cap([r for _, r in arrivals])
        req = arrivals[0][1]
        require_kernel(tag, "refill (prefix tables)", srv._refill,
                       *refill_args(srv, req, cap))
        prefix_kernel_vs_oracle(tag, srv, req, cap)

    # C: small groups take the rescan route: both loop kernels on the chip
    b = build_bundle("C small groups", "sensor_health", small)
    srv, stats, arrivals = serve_phase("C small groups", b, cfg, clock)
    reference_share("C small groups", b, cfg, stats, arrivals)
    cap = srv.trace_cap([r for _, r in arrivals])
    check(cap <= 1024, f"C: cap {cap} is above the rescan crossover")
    req = arrivals[0][1]
    args = refill_args(srv, req, cap)
    require_kernel("C small groups", "rescan chunk", srv._chunk, args[0])
    rescan_kernels_vs_oracle("C small groups", srv, req, cap)


def table_trace(srv, reqs):
    """Fill every lane, run a chunk, recycle lane 0, drain; return the
    final table and its readback."""
    cap = srv.trace_cap(reqs)
    table, _ = srv.admit(srv.new_table(cap), cap,
                         [(i, r, None) for i, r in enumerate(reqs)])
    table = srv.run_chunk(table)
    table, _ = srv.admit(table, cap, [(0, reqs[-1], None)])
    out = srv.readback(table)
    for _ in range(200):
        if out["done"].all():
            return table, out
        table = srv.run_chunk(table)
        out = srv.readback(table)
    raise AssertionError("chip_smoke: the lane table did not drain")


def four_chips(scale, cfg, clock):
    """The ``("lanes",)`` mesh over four chips against one chip."""
    import jax
    import numpy as np

    from repro.launch.mesh import make_serving_mesh
    from repro.serving import ContinuousBatchedServer

    check(len(jax.devices()) >= 4,
          f"--four-chips needs 4 devices, JAX sees {len(jax.devices())}")
    tag = "mesh turbofan"
    b = build_bundle(tag, "turbofan", scale)
    mesh = make_serving_mesh(4)
    reqs = [b.requests[i % len(b.requests)] for i in range(LANES)]
    base = ContinuousBatchedServer(b, cfg, batch_size=LANES,
                                   chunk_iters=CHUNK_ITERS)
    sharded = ContinuousBatchedServer(b, cfg, batch_size=LANES,
                                      chunk_iters=CHUNK_ITERS, mesh=mesh)
    t0 = time.perf_counter()
    _, ob = table_trace(base, reqs)
    one_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    table, om = table_trace(sharded, reqs)
    four_s = time.perf_counter() - t0

    devices = {s.device for s in table.vals.addressable_shards}
    rows = {s.data.shape[0] for s in table.vals.addressable_shards}
    leaves = jax.tree_util.tree_leaves(table)
    spread = all(len(x.sharding.device_set) == 4 for x in leaves)
    z_eq = bool(np.array_equal(ob["z"], om["z"]))
    it_eq = bool(np.array_equal(ob["it"], om["it"]))
    dy = float(np.max(np.abs(ob["y_hat"] - om["y_hat"])))
    dp = float(np.max(np.abs(ob["prob"] - om["prob"])))
    print(f"[{tag}] table trace: one chip {one_s!r} s, four chips "
          f"{four_s!r} s (compiles included); vals shards on "
          f"{len(devices)} devices with {sorted(rows)} lanes each; every "
          f"leaf on 4 devices: {spread}; z equal: {z_eq}; it equal: "
          f"{it_eq}; max |dy_hat|={dy!r} max |dprob|={dp!r}", flush=True)
    check(len(devices) == 4 and rows == {LANES // 4} and spread,
          "the lane table is not spread over 4 devices")
    check(z_eq and it_eq, "sharded z/it differ from the one-chip table")
    np.testing.assert_allclose(ob["y_hat"], om["y_hat"], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(ob["prob"], om["prob"], rtol=1e-5, atol=1e-6)
    sharded.check_compile_contract()
    base.check_compile_contract()

    srv, stats, arrivals = serve_phase(tag, b, cfg, clock, mesh=mesh)
    reference_share(tag, b, cfg, stats, arrivals)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the lanes mesh over four chips")
    args = ap.parse_args()
    devices = require_tpu()

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from benchmarks.common import DEFAULT_CFG, QUICK, SCALE
    from repro.core.executor import BiathlonConfig
    from repro.launch.compile_cache import configure_compile_cache

    check(not QUICK, "QUICK=1 shrinks the benchmark scale; unset it")
    print(f"[setup] compile cache: {configure_compile_cache()}", flush=True)
    print(f"[setup] devices: {devices}", flush=True)
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    cfg = BiathlonConfig(**DEFAULT_CFG)
    scale = dict(SCALE)

    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(scale, cfg, clock)
    else:
        one_chip(scale, cfg, clock)
    print(f"[done] total_wall_s={time.perf_counter() - t0!r} "
          f"xla_compiles={clock.n} xla_compile_s={clock.s!r}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))


if __name__ == "__main__":
    main()

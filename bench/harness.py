"""Run one cell of ``BENCHMARK.json`` once, and say what it measured.

Order of a run: find a TPU (none: exit non-zero, no result); point JAX's
compile cache at its fixed directory; build the pipeline bundle and the
traffic from ``--seed``; warm every cap bucket the rounds reach, and one
short round; then drive whole rounds through
``ContinuousServingRuntime.run`` until ``--seconds`` have passed.  With
``--trace 1`` the window runs under the profiler.  After the window:
compiles inside it, the peak device memory, the comparison with the plain
reference (``bench/reference.py``), the metrics, and the result line.
"""
from __future__ import annotations

import contextlib
import gc
import json
import math
import sys
import tempfile
import time

import numpy as np

from bench import manifest, reference, spans, trace, traffic, work

#: requests compared with the reference in each run, drawn from the seed;
#: the request with the most planner iterations is always among them
SAMPLE = 48


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


class GcClock:
    """Counts the interpreter's garbage collections and their pauses."""

    def __init__(self):
        self.n = 0
        self.s = 0.0
        self.longest = 0.0
        self._t0 = None

    def __call__(self, phase, _info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            pause = time.perf_counter() - self._t0
            self.n += 1
            self.s += pause
            self.longest = max(self.longest, pause)
            self._t0 = None


class Run:
    """What one run measured; the metric readers take their numbers here."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def total(self, name: str) -> tuple[float, int, int]:
        """(seconds, calls, items) of one server call over the window."""
        return spans.totals(self.spans).get(name, (0.0, 0, 0))


def say(*parts) -> None:
    print(*parts, flush=True)


def require_chips(chips: int):
    """The devices of a TPU host with at least ``chips`` chips, or exit."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX sees {len(devices)}")
    return devices


def check_features(cfg: dict, pipeline) -> None:
    """The bundle serves the aggregates and the task the configuration states."""
    got = [[f.agg, f.column, f.quantile if f.agg == "quantile" else None]
           for f in pipeline.agg_features]
    want = [[f["op"], f["column"], f.get("q")] for f in cfg["features"]]
    if got != want or [e.name for e in pipeline.exact_features] != cfg["exact"]:
        raise ValueError(f"pipeline features {got} differ from the config {want}")
    if pipeline.task != cfg["task"]:
        raise ValueError(f"pipeline task {pipeline.task!r} differs from the "
                         f"config's {cfg['task']!r}")


def build(cfg: dict, deployment_seed: int, chips: int):
    """Bundle, server and per-group requests of a configuration.

    The deployment (rows, trained model, δ) comes from its own seed: the
    program compiles the model's trees into its executables, so one
    deployment keeps every program in the compile cache from run to run, and
    every run does the same work.
    """
    from repro.core.executor import BiathlonConfig
    from repro.data.synthetic import make_pipeline, make_pipeline_median
    from repro.launch.mesh import make_serving_mesh
    from repro.serving import ContinuousBatchedServer

    size = cfg["size"]
    make = make_pipeline_median if cfg.get("median_substitution") else make_pipeline
    bundle = make(
        cfg["pipeline"], seed=int(deployment_seed),
        rows_per_group=size["rows_per_group"],
        n_train_groups=size["n_train_groups"],
        n_serve_groups=size["n_serve_groups"],
        n_requests=size["request_log"],
    )
    check_features(cfg, bundle.pipeline)
    by_group = {}
    for req in bundle.requests:
        by_group.setdefault(req["gid"], req)
    if len(by_group) != size["n_serve_groups"]:
        raise ValueError("the request log does not name every serve group")
    planner = BiathlonConfig(**cfg["planner"])
    server = ContinuousBatchedServer(
        bundle, planner, batch_size=cfg["serving"]["lanes_per_chip"] * chips,
        chunk_iters=cfg["serving"]["chunk_iters"],
        mesh=make_serving_mesh(chips) if chips > 1 else None,
    )
    return bundle, server, [by_group[g] for g in sorted(by_group)]


def model_of(cfg: dict, pipeline, root=manifest.ROOT) -> reference.Model:
    """The served model, read by the file of the configuration's kind."""
    return reference.Model(manifest.model_module(cfg["model"]["kind"], root),
                           pipeline, cfg["task"])


def problem_of(cfg: dict, bundle, req: dict) -> dict:
    """The reference's inputs for one request, read from the host store."""
    store = bundle.store
    groups, n = [], []
    for f in cfg["features"]:
        vals = store[cfg["table"]].full_values(f["column"], req["gid"])
        groups.append(np.asarray(vals, np.float64))
        n.append(vals.shape[0])
    return {
        "features": [(f["op"], 0.5 if f["op"] == "median" else f.get("q", 0.5))
                     for f in cfg["features"]],
        "groups": groups, "n": n,
        "exact": [req[name] for name in cfg["exact"]],
        "m": cfg["planner"]["m"], "n_boot": cfg["planner"]["n_bootstrap"],
    }


def compare(cfg: dict, bundle, served, seed: int, model: reference.Model,
            precision=reference.F64) -> dict:
    """The compared numbers over a sample of served requests.

    ``served``: ``[(request, record)]`` of the window.  With a lower
    ``precision`` the reference computed in it stands in for the program.
    """
    planner = cfg["planner"]
    delta = bundle.pipeline.delta_default
    rng = traffic.rng_for(seed, 2)
    pick = set(rng.choice(len(served), size=min(SAMPLE, len(served)),
                          replace=False).tolist()) if served else set()
    if served:
        pick.add(int(np.argmax([rec.iters for _, rec in served])))
    worst = {"yhat_gap": 0.0, "prob_gap": 0.0, "stop_unjustified": 0,
             "plan_invalid": 0}
    limit_prob = cfg["checks"]["prob_gap"]["limit"]
    for i in sorted(pick):
        req, rec = served[i]
        prob_in = problem_of(cfg, bundle, req)
        z, it = list(rec.z), int(rec.iters)
        ref = reference.answer(prob_in, model, z, it)
        got = {"y_hat": rec.y_hat, "prob": rec.prob}
        if precision is not reference.F64:
            ctl = reference.answer(prob_in, model, z, it, precision)
            got = reference.served(ctl, model, delta, precision)
        gaps = reference.gaps(got, ref, model, delta)
        worst["yhat_gap"] = max(worst["yhat_gap"], gaps["yhat_gap"])
        worst["prob_gap"] = max(worst["prob_gap"], gaps["prob_gap"])
        exhausted = all(zj >= nj for zj, nj in zip(z, prob_in["n"]))
        if not (exhausted or it >= planner["max_iters"]
                or gaps["prob_ref"] >= planner["tau"] - limit_prob):
            worst["stop_unjustified"] += 1
        if not reference.plan_ok(z, prob_in["n"], it, planner["alpha"],
                                 planner["gamma"]):
            worst["plan_invalid"] += 1
    worst["compared"] = len(pick)
    return worst


def checks_of(cfg: dict, gaps: dict, unserved: int, window_compiles: int) -> dict:
    """Each compared number beside its limit: ``{name: (value, limit)}``."""
    limits = {name: c["limit"] for name, c in cfg["checks"].items()}
    return {
        "unserved": (unserved, 0),
        "window_compiles": (window_compiles, 0),
        "yhat_gap": (float(gaps["yhat_gap"]), limits["yhat_gap"]),
        "prob_gap": (float(gaps["prob_gap"]), limits["prob_gap"]),
        "stop_unjustified": (gaps["stop_unjustified"], 0),
        "plan_invalid": (gaps["plan_invalid"], 0),
    }


def is_correct(served: list, checks: dict) -> bool:
    return bool(served) and all(v <= lim for v, lim in checks.values())


def exact_share(cfg: dict, bundle, served, model: reference.Model) -> float:
    """Share of served answers within δ of the exact pipeline answer (every
    group aggregated whole), or of its class; reported beside τ, decides
    nothing."""
    delta = bundle.pipeline.delta_default
    exact = {}
    hits = 0
    for req, rec in served:
        key = tuple(sorted(req.items()))
        if key not in exact:
            p = problem_of(cfg, bundle, req)
            value, _, _ = reference.estimates(p["features"], p["groups"], p["n"],
                                              p["n"], 0, p["n_boot"])
            full = np.concatenate([value, np.asarray(p["exact"], np.float64)])
            exact[key] = model.label(model.raw(full[None, :])[0])
        hits += (rec.y_hat == exact[key] if model.classifies
                 else abs(rec.y_hat - exact[key]) <= delta)
    return hits / max(len(served), 1)


def run_cell(cell_name: str, seed: int, seconds: float, traced: bool, *,
             t_start: float, require_chip: bool = True, man: dict | None = None,
             root=manifest.ROOT, wrap=None, control: bool = False) -> dict:
    """Run the cell once and return the result line's object.

    ``wrap(server) -> server`` lets a test put a broken server under the
    timed path; ``require_chip=False`` lets it run on the CPU; ``control``
    puts the bfloat16 control in the program's place on the same requests,
    through the same checks, and adds its ``correct`` and checks under
    ``"control"`` (for ``bench/calibrate.py``).  ``seed`` draws the traffic
    and the requests compared.
    """
    import jax

    man = manifest.load() if man is None else man
    cell = manifest.cell(man, cell_name)
    cfg = manifest.config(man, cell["config"], root)
    mix = traffic.check(manifest.traffic(cell["traffic"], root))
    chips = cell["chips"]
    devices = require_chips(chips) if require_chip else jax.devices()
    peak = work.peaks(devices[0].device_kind) if require_chip else None

    from repro.serving import ContinuousServingRuntime

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)

    bundle, server, group_requests = build(cfg, cfg["size"]["deployment_seed"], chips)
    model = model_of(cfg, bundle.pipeline, root)
    if wrap is not None:
        server = wrap(server)
    timed = spans.SpanServer(server, annotate=traced)
    runtime = ContinuousServingRuntime(timed)
    rounds = traffic.rounds(mix, group_requests, seed)
    caps = {}
    for rnd in rounds:
        caps.setdefault(server.trace_cap([r for _, r in rnd]), [r for _, r in rnd])
    lanes = server.batch_size
    # no request needs more chunks than its planner iterations allow; past
    # that the timed path is stuck, and the run ends as not correct
    per_round = int(mix["round_size"]) * (
        -(-cfg["planner"]["max_iters"] // cfg["serving"]["chunk_iters"]) + 1)
    aborted = None
    timed.chunk_budget = per_round
    try:
        for reqs in caps.values():
            runtime.warmup(reqs)
        runtime.run(rounds[0][: lanes + 1], warmup=False)
    except spans.RunAborted as exc:
        aborted = f"warm-up: {exc}"
    timed.reset()
    # what set-up left behind is collected once and frozen, so that the
    # window's collections scan only what the window makes
    t_gc = time.perf_counter()
    gc.collect()
    gc_setup_s = time.perf_counter() - t_gc
    gc_objects = len(gc.get_objects())
    gc.freeze()
    gc_clock = GcClock()
    gc.callbacks.append(gc_clock)
    compiles0 = (clock.n, server.compile_count)
    setup_s = time.perf_counter() - t_start
    say(f"[setup] setup_s={setup_s!r} caps={sorted(caps)} lanes={lanes} "
        f"chips={chips} xla_compiles_in_setup={clock.n} "
        f"delta={bundle.pipeline.delta_default!r} "
        f"gc_collect_s={gc_setup_s!r} gc_objects_frozen={gc_objects}")

    log_dir = tempfile.TemporaryDirectory(prefix="bench_trace_") if traced else None
    served, attempted, round_s = [], 0, []
    t0 = time.perf_counter()
    with (trace.capture(log_dir.name) if traced else contextlib.nullcontext()):
        r = 0
        while not aborted and time.perf_counter() - t0 < seconds:
            rnd = rounds[r % len(rounds)]
            timed.chunk_budget = per_round
            attempted += len(rnd)
            try:
                t_round = time.perf_counter()
                stats = runtime.run(rnd, warmup=False)
                round_s.append(time.perf_counter() - t_round)
            except spans.RunAborted as exc:
                aborted = str(exc)
                break
            served += [(rnd[rec.req_id][1], rec) for rec in stats.records
                       if rec.disposition == "ok"]
            r += 1
        window_s = time.perf_counter() - t0
    gc.callbacks.remove(gc_clock)
    gc.unfreeze()
    window_compiles = clock.n - compiles0[0]
    executables = server.compile_count - compiles0[1]
    memory_peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in devices[:chips]
    ) if require_chip else 0
    say(f"[window] rounds={r} attempted={attempted} served={len(served)} "
        f"window_s={window_s!r} xla_compiles_in_window={window_compiles} "
        f"server_compile_count={server.compile_count} "
        f"new_executables_in_window={executables} "
        f"peak_bytes_in_use={memory_peak}")
    longest = max(timed.spans, key=lambda sp: sp[2] - sp[1], default=None)
    say(f"[window] round_s={[round(x, 4) for x in round_s]} "
        f"gc_collections={gc_clock.n} gc_pause_s={gc_clock.s!r} "
        f"gc_longest_pause_s={gc_clock.longest!r} longest_call="
        f"{(longest[0], longest[2] - longest[1]) if longest else None!r}")

    reduced = None
    if traced:
        reduced = trace.load(log_dir.name)
        log_dir.cleanup()
    p = bundle.pipeline
    run = Run(
        cell=cell, config=cfg, chips=chips, seconds=seconds, setup_s=setup_s,
        window_s=window_s, attempted=attempted, served=served,
        spans=list(timed.spans), latencies=list(timed.latencies), trace=reduced,
        peak=peak, cap=max(caps),
        shape={"k": p.k, "e": len(p.exact_features), "m": cfg["planner"]["m"],
               "m_sobol": cfg["planner"]["m_sobol"], "ops_per_row": model.ops_per_row()},
        model=model,
    )
    # the program's state goes before the reference runs
    del runtime, timed, server
    result = finish(run, man, bundle, seed, devices, aborted, window_compiles,
                    memory_peak, traced, root)
    if control:
        gaps = compare(cfg, bundle, served, seed, model, reference.Precision("bfloat16"))
        ctl = checks_of(cfg, gaps, result["failed"] + (1 if aborted else 0),
                        window_compiles)
        checks = result.pop("checks")
        result["control"] = {
            "correct": is_correct(served, ctl),
            "checks": {name: {"value": v, "limit": lim}
                       for name, (v, lim) in ctl.items()}}
        result["checks"] = checks
    return result


def finish(run: Run, man: dict, bundle, seed: int, devices, aborted,
           window_compiles: int, memory_peak: int, traced: bool, root) -> dict:
    """Compare with the reference, read the metrics, print, and return the
    result line's object."""
    import jax

    cfg = run.config
    t0 = time.perf_counter()
    gaps = compare(cfg, bundle, run.served, seed, run.model)
    share = exact_share(cfg, bundle, run.served, run.model)
    iters = [rec.iters for _, rec in run.served]
    say(f"[check] compared={gaps['compared']} reference_s="
        f"{time.perf_counter() - t0!r} within_delta_of_exact={share!r} "
        f"tau={cfg['planner']['tau']!r} latencies={len(run.latencies)} "
        f"iterating_share={np.mean([i > 0 for i in iters]) if iters else 0.0!r} "
        f"max_iters_served={max(iters, default=0)}")
    checks = checks_of(cfg, gaps,
                       run.attempted - len(run.served) + (1 if aborted else 0),
                       window_compiles)
    correct = is_correct(run.served, checks)
    if aborted:
        say(f"[check] run aborted: {aborted}")

    group = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in manifest.cell_metrics(man, run.cell["name"], group):
        value = manifest.reader(m["name"], root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {
        "correct": correct,
        "attempted": run.attempted,
        "failed": run.attempted - len(run.served),
        "metrics": metrics,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": jax.device_count(),
            "memory_peak_bytes": memory_peak,
        },
    }
    if traced:
        result["device"]["busy_s"] = trace.busy_s(run.trace)
        result["device"]["window_s"] = trace.window_s(run.trace)
        result["breakdown"] = {"device_ops": trace.top_ops(run.trace),
                               "idle_gaps": trace.idle_by_host(run.trace)}
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim) in checks.items()}
    for name, (v, lim) in checks.items():
        print(f"check {name} = {v!r} (limit {lim!r})", file=sys.stderr, flush=True)
    return result


def use_compile_cache() -> None:
    """JAX's persistent compile cache at ``.jax_cache/`` in this checkout,
    whatever the environment names: a fixed path, so that only a checkout's
    first run of a cell compiles, and no other checkout shares it.  Every
    program goes in, however quick its compile."""
    import jax

    cache = manifest.ROOT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # no size limit: a limit set in the environment for another directory
    # turns on eviction bookkeeping that loses entries of this one
    jax.config.update("jax_compilation_cache_max_size", -1)
    say(f"[setup] compile cache: {cache}")


def main(argv=None, *, t_start: float) -> None:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not math.isfinite(args.seconds) or args.seconds <= 0:
        sys.exit("bench: --seconds must be positive")
    require_chips(1)
    use_compile_cache()
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                      t_start=t_start)
    print(json.dumps(result), flush=True)

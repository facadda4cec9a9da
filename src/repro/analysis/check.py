"""The contract checker CLI / CI gate: ``python -m repro.analysis.check``.

Builds every shipped serving executable at test scale — the fixed-lane
batch program (``fused``), its shard_map twin (``sharded_lanes``), and the
continuous table's ``refill`` + ``chunk`` pair, for each pipeline in
``--pipelines`` — then enforces each one's registered
:class:`~repro.analysis.contracts.ExecutableContract` three ways:

1. **compile contract** — serve real fills through the server and assert
   the trace-hook counters via ``check_compile_contract`` (one executable
   per cap bucket; two for the continuous pair);
2. **jaxpr lint** — trace the jitted callable and run
   :mod:`repro.analysis.jaxpr_lint` (counter-based RNG in loop bodies, no
   host callbacks, no weak-typed inputs, no f64);
3. **HLO lint** — lower + compile and run :mod:`repro.analysis.hlo_lint`
   (zero collectives, donation actually aliases via ``memory_analysis``,
   no f64 buffers), plus a pipeline-independent while-body **flatness
   probe** of the incremental-AFC path at two caps.

Observed facts (collective counts, donation aliasing, finding counts) are
diffed against the checked-in ``baseline.json`` next to this module, so
drift fails loudly with a diff even when a contract was loosened to match;
``--update-baseline`` rewrites it.  ``--mutation-test`` runs the seeded
violations in :mod:`repro.analysis.mutations` and fails unless every one
is caught — the checker must be known-sensitive, not vacuously green.

Exit status: 0 clean, 1 on findings / baseline drift / missed mutations.
"""
from __future__ import annotations

import argparse
import difflib
import json
import sys
from collections.abc import Sequence
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import hlo_lint, jaxpr_lint
from repro.analysis.contracts import ExecutableContract, all_contracts, contract_for
from repro.analysis.jaxpr_lint import LintFinding
from repro.core.executor import BiathlonConfig
from repro.core.executor_fused import build_fused_executor
from repro.data.synthetic import make_pipeline
from repro.launch.hlo_stats import collect_collective_stats
from repro.launch.mesh import make_serving_mesh
from repro.serving.batched import BatchedFusedServer, lane_request_inputs
from repro.serving.continuous import ContinuousBatchedServer
from repro.serving.server import BiathlonServer

__all__ = ["main", "run_checks"]

BASELINE_PATH = Path(__file__).with_name("baseline.json")
DEFAULT_PIPELINES = ("turbofan", "sensor_health")
#: test-scale data (same knobs the serving tests use): one cap bucket,
#: millisecond dispatches, but the REAL builders and the REAL servers.
SMALL = dict(rows_per_group=300, n_train_groups=30, n_serve_groups=4,
             n_requests=6)
CFG = BiathlonConfig(m=64, m_sobol=16, n_bootstrap=32)
LANES = 4
#: caps for the incremental-AFC while-body flatness probe (4x apart — a
#: rescan body scales ~linearly, so leakage is unmistakable at this ratio).
FLATNESS_CAPS = (2048, 8192)


# ---------------------------------------------------------------- helpers
def _batch_args(
    srv: BatchedFusedServer, requests: Sequence[dict[str, Any]]
) -> tuple[Any, ...]:
    """The exact (8-tuple) device arguments ``serve_batch`` would build."""
    p = srv.bundle.pipeline
    store = srv.bundle.store
    lanes = srv.batch_size
    cap = srv.batch_cap(requests)
    r = len(requests)
    vals = np.zeros((lanes, p.k, cap), np.float32)
    ns = np.zeros((lanes, p.k), np.int32)
    exacts = np.zeros((lanes, len(p.exact_features)), np.float32)
    for i, req in enumerate(requests):
        vals[i], ns[i], _, exacts[i] = lane_request_inputs(p, store, req, cap)
    delta = srv.config.delta if srv.config.delta is not None else p.delta_default
    return (
        jnp.asarray(vals),
        jnp.asarray(ns),
        jnp.broadcast_to(srv._agg_ids, (lanes, p.k)),
        jnp.asarray(np.full((lanes,), delta, np.float32)),
        jnp.asarray(exacts),
        jnp.asarray(np.arange(lanes) < r),
        jnp.asarray(np.full((lanes,), srv.config.tau, np.float32)),
        jnp.asarray(np.full((lanes,), srv.config.max_iters, np.int32)),
    )


def _lint_static(
    fn: Any, args: tuple[Any, ...], contract: ExecutableContract, exe: str,
    *, min_alias_bytes: int, n_devices: int,
) -> tuple[list[LintFinding], dict[str, Any]]:
    """Jaxpr + HLO lint of one jitted callable against its contract.

    Returns ``(findings, facts)`` — ``facts`` are the version-stable
    observations recorded in the baseline.
    """
    findings: list[LintFinding] = []
    jaxpr, trace_findings = jaxpr_lint.trace_for_lint(fn, *args, executable=exe)
    findings += trace_findings
    if jaxpr is not None:
        findings += jaxpr_lint.lint_jaxpr(
            jaxpr, exe,
            rng=contract.rng,
            allow_weak_inputs=contract.weak_type_inputs,
            allow_f64=contract.allow_f64,
        )
    compiled = fn.lower(*args).compile()
    hlo = compiled.as_text()
    findings += hlo_lint.check_collectives(
        hlo, exe, allowed=contract.collectives, n_devices=n_devices
    )
    if not contract.allow_f64:
        findings += hlo_lint.check_f64(hlo, exe)
    if contract.donated:
        findings += hlo_lint.check_donation(
            compiled, exe,
            min_alias_bytes=min_alias_bytes, donated=contract.donated,
        )
    stats = collect_collective_stats(hlo, n_devices)
    facts = {
        "contract": contract.name,
        "collectives": int(sum(stats.per_op_count.values())),
        "donation_aliased": bool(contract.donated) and not any(
            f.contract == "donated" for f in findings
        ),
        "rng_findings": sum(1 for f in findings if f.contract == "rng"),
        "host_sync_findings": sum(
            1 for f in findings if f.contract == "host_sync"
        ),
        "weak_type_inputs": sum(
            1 for f in findings if f.contract == "weak_type_inputs"
        ),
        "f64": bool(hlo_lint.check_f64(hlo, exe)),
    }
    return findings, facts


def _compile_contract_findings(srv: Any, exe: str) -> list[LintFinding]:
    """Run the server's own compile-contract assertion as a lint check."""
    try:
        srv.check_compile_contract()
        return []
    except AssertionError as e:
        return [LintFinding(
            contract="executables_per_bucket", executable=exe,
            where="<trace hooks>", message=str(e),
        )]


def cache_coherence_findings(
    cached: Any, oracle: Any, requests: Sequence[dict[str, Any]], exe: str
) -> list[LintFinding]:
    """Serve the same log through a cache-fed server and an uncached oracle.

    Any divergence is a stale read: the z-plans are a bitwise contract
    (incremental and rescan AFC agree exactly, the PR-5 parity property),
    so a cached entry whose versions lag the store shows up as a z or
    prediction mismatch.  Also the sensitivity oracle for the
    ``stale_cache_read`` mutant (analysis/mutations.py) — a cache keyed
    without group versions must trip this probe.
    """
    findings: list[LintFinding] = []
    for i, req in enumerate(requests):
        a = cached.serve(req)
        b = oracle.serve(req)
        same_z = bool(np.array_equal(a["z"], b["z"]))
        scale = max(abs(b["y_hat"]), 1.0)
        same_y = abs(a["y_hat"] - b["y_hat"]) <= 1e-4 * scale
        if not (same_z and same_y):
            findings.append(LintFinding(
                contract="cache_version_key", executable=exe,
                where=f"request[{i}]",
                message=(
                    "cache-fed serve diverged from the uncached oracle "
                    f"(y {a['y_hat']:.6g} vs {b['y_hat']:.6g}, "
                    f"z match={same_z}): stale entry served — the cache "
                    "key must include the per-spec group versions"
                ),
            ))
    return findings


def _drain_lanes(srv: Any, table: Any, lanes: Sequence[int],
                 max_chunks: int = 128) -> tuple[Any, dict]:
    """Chunk until every named lane reports done (bounded), then read back."""
    for _ in range(max_chunks):
        out = srv.readback(table)
        if all(bool(out["done"][l]) for l in lanes):
            return table, out
        table = srv.run_chunk(table)
    return table, srv.readback(table)


def rollback_findings(
    srv: Any, requests: Sequence[dict[str, Any]], exe: str,
    *, skip_restore: Sequence[str] = (),
) -> list[LintFinding]:
    """Crash a chunk mid-flight, roll back, replay; diff bitwise vs oracle.

    The rollback invariant (DESIGN.md § Fault tolerance): restoring the
    CHUNK_CARRY_LEAVES snapshot after a mid-chunk wreck and replaying must
    be bitwise-identical to the fault-free run — the bootstrap RNG is
    counter-based on the restored iteration index, so nothing is re-drawn.
    ``skip_restore`` is the sensitivity seam for the
    ``rollback_skips_bootstrap_carry`` mutant (analysis/mutations.py): a
    rollback that forgets a carry leaf must trip this probe.
    """
    from repro.serving import faults

    lanes = list(range(min(srv.batch_size, len(requests))))
    reqs = [requests[l] for l in lanes]
    cap = srv.trace_cap(reqs)
    assignments = [(l, reqs[l], None) for l in lanes]
    table = srv.new_table(cap)
    table, _ = srv.admit(table, cap, assignments)
    _, want = _drain_lanes(srv, table, lanes)

    table = srv.new_table(cap)
    table, _ = srv.admit(table, cap, assignments)
    table = srv.run_chunk(table)
    ckpt = srv.snapshot(table)
    wreck = faults.scramble_chunk_carry(table)  # simulated mid-chunk crash
    kept = {k: v for k, v in ckpt.items() if k not in skip_restore}
    table = srv.restore(wreck, kept)
    _, got = _drain_lanes(srv, table, lanes)

    findings: list[LintFinding] = []
    for l in lanes:
        same_z = bool(np.array_equal(want["z"][l], got["z"][l]))
        # the iteration counter and the guarantee probability are carry
        # too: a request that converges on the next step reaches the same
        # z and y_hat from a wrong counter, but not the same it / prob
        same_it = bool(want["it"][l] == got["it"][l])
        same_y = (np.asarray(want["y_hat"][l]).tobytes()
                  == np.asarray(got["y_hat"][l]).tobytes())
        same_p = (np.asarray(want["prob"][l]).tobytes()
                  == np.asarray(got["prob"][l]).tobytes())
        if not (same_z and same_it and same_y and same_p):
            findings.append(LintFinding(
                contract="rollback_replay", executable=exe,
                where=f"lane[{l}]",
                message=(
                    "replay after chunk rollback diverged from the "
                    f"fault-free oracle (z match={same_z}, it match="
                    f"{same_it}, prob match={same_p}, y_hat "
                    f"{got['y_hat'][l]:.6g} vs {want['y_hat'][l]:.6g}): "
                    "the checkpoint must restore every chunk-mutable "
                    "carry leaf"
                ),
            ))
    return findings


def quarantine_findings(
    srv: Any, requests: Sequence[dict[str, Any]], exe: str,
    *, reset_on_readmit: bool = True,
) -> list[LintFinding]:
    """Poison one lane's carry, quarantine + re-admit, diff bitwise vs oracle.

    Two invariants: the poisoned lane's FULL re-admission must converge to
    the same result as a never-poisoned run (admits re-init every lane leaf
    from counter-based RNG), and the neighbor lane must be bitwise
    untouched (quarantine is per-lane, never table-wide).
    ``reset_on_readmit=False`` is the sensitivity seam for the
    ``quarantine_readmit_without_reset`` mutant: flag-flipping the lane
    back to live while keeping its poisoned carry must trip this probe.
    """
    import jax

    from repro.serving import faults

    lanes = [0, 1]
    reqs = list(requests[:2])
    cap = srv.trace_cap(reqs)
    assignments = [(l, reqs[l], None) for l in lanes]
    table = srv.new_table(cap)
    table, _ = srv.admit(table, cap, assignments)
    _, want = _drain_lanes(srv, table, lanes)

    table = srv.new_table(cap)
    table, _ = srv.admit(table, cap, assignments)
    table = srv.run_chunk(table)
    table = faults.poison_lane_carry(table, 0)
    if reset_on_readmit:
        table = srv.clear_lanes(table, [0])
        table, _ = srv.admit(table, cap, [(0, reqs[0], None)])
    else:
        # the seeded bug: "re-admit" by flipping the lane flags back to
        # live while keeping the poisoned carry
        done = np.asarray(table.done).copy()
        active = np.asarray(table.active).copy()
        done[0] = False
        active[0] = True
        table = table._replace(
            done=jax.device_put(done, table.done.sharding),
            active=jax.device_put(active, table.active.sharding),
        )
    _, got = _drain_lanes(srv, table, lanes)

    findings: list[LintFinding] = []
    for l, label in ((0, "re-admitted"), (1, "neighbor")):
        same_z = bool(np.array_equal(want["z"][l], got["z"][l]))
        same_y = bool(
            np.asarray(want["y_hat"][l]).tobytes()
            == np.asarray(got["y_hat"][l]).tobytes()
        )
        if not (same_z and same_y):
            findings.append(LintFinding(
                contract="quarantine_isolation", executable=exe,
                where=f"lane[{l}] ({label})",
                message=(
                    f"{label} lane diverged from the never-poisoned oracle "
                    f"(z match={same_z}, y_hat {got['y_hat'][l]:.6g} vs "
                    f"{want['y_hat'][l]:.6g}): quarantine must fully "
                    "re-initialize the poisoned lane and touch nothing else"
                ),
            ))
    return findings


def store_recovery_findings(bundle: Any, exe: str) -> list[LintFinding]:
    """Journal-replay recovery must rebuild the index byte-identical.

    Appends a few rows (journaled), tears the derived index state the way a
    crash mid-append would (shuffled permutation, bumped offsets, cleared
    version counters), then requires :meth:`Table.recover` to rebuild
    ``perm`` / ``group_ptr`` / ``versions`` exactly equal to the
    never-crashed state.
    """
    findings: list[LintFinding] = []
    t, _c, g = bundle.pipeline.agg_specs(bundle.requests[0])[0]
    table = bundle.store[t]
    for shift in (0.5, -1.25):
        table.append(
            {name: [float(np.asarray(col[np.isfinite(col)]).mean()) + shift]
             for name, col in table.columns.items()},
            group_key=g,
        )
    want = (table.perm.copy(), table.group_ptr.copy(),
            dict(table.group_ids), list(table.versions))
    # tear the derived state: recover() must not depend on any of it
    rng = np.random.default_rng(0)
    table.perm = rng.permutation(table.perm)
    table.group_ptr = table.group_ptr + 7
    table.versions = []
    table.recover()
    got = (table.perm, table.group_ptr, table.group_ids, table.versions)
    same = (
        np.array_equal(want[0], got[0])
        and np.array_equal(want[1], got[1])
        and want[2] == dict(got[2])
        and want[3] == list(got[3])
    )
    if not same:
        findings.append(LintFinding(
            contract="store_recovery", executable=exe,
            where=f"table[{t}]",
            message=(
                "journal replay did not rebuild the index byte-identical "
                "to the never-crashed table (perm/group_ptr/versions "
                "mismatch)"
            ),
        ))
    return findings


def cache_integrity_findings(bundle: Any, exe: str) -> list[LintFinding]:
    """A flipped byte in a resident entry must be detected, never served."""
    from repro.serving import corrupt_cache_entry

    findings: list[LintFinding] = []
    srv = BiathlonServer(bundle, CFG, mode="fused", cache_size=4)
    req = bundle.requests[0]
    want = srv.serve(req)
    srv.cache.verify_hits = True
    if not corrupt_cache_entry(srv.cache, seed=0):
        findings.append(LintFinding(
            contract="cache_integrity", executable=exe, where="<cache>",
            message="corruption probe found no resident entry to flip",
        ))
        return findings
    got = srv.serve(req)  # must detect, drop, rebuild cold
    if srv.cache.corruptions < 1:
        findings.append(LintFinding(
            contract="cache_integrity", executable=exe, where="<cache>",
            message=(
                "a flipped byte in a resident entry went undetected: the "
                "power-sum checksum must fail the entry on the hit path"
            ),
        ))
    if not (np.array_equal(want["z"], got["z"])
            and want["y_hat"] == got["y_hat"]):
        findings.append(LintFinding(
            contract="cache_integrity", executable=exe, where="<cache>",
            message=(
                "post-corruption rebuild diverged from the pre-corruption "
                f"serve (y {got['y_hat']:.6g} vs {want['y_hat']:.6g})"
            ),
        ))
    return findings


# --------------------------------------------------------- per-executable
def check_fused(
    bundle: Any, *, mesh: Any = None, n_devices: int = 1
) -> tuple[str, list[LintFinding], dict[str, Any]]:
    """Fixed-lane batch program (sharded when ``mesh`` is given)."""
    name = "sharded_lanes" if mesh is not None else "fused"
    exe = f"{bundle.name}/{name}"
    srv = BatchedFusedServer(bundle, CFG, batch_size=LANES, mesh=mesh)
    reqs = list(bundle.requests[:3])
    srv.serve_batch(reqs[:1])
    srv.serve_batch(reqs)  # fill variation: same bucket, zero new compiles
    findings = _compile_contract_findings(srv, exe)
    args = _batch_args(srv, reqs)
    # memory_analysis reports PER-DEVICE bytes; the lanes axis shards the
    # donated values buffer, so the per-shard slice is the floor.
    f2, facts = _lint_static(
        srv._batched, args, contract_for(name), exe,
        min_alias_bytes=args[0].nbytes // max(n_devices, 1),
        n_devices=n_devices,
    )
    return exe, findings + f2, facts


def check_continuous(
    bundle: Any,
) -> list[tuple[str, list[LintFinding], dict[str, Any]]]:
    """Continuous lane table: the refill + chunk executable pair."""
    srv = ContinuousBatchedServer(bundle, CFG, batch_size=LANES, chunk_iters=2)
    p = srv.bundle.pipeline
    reqs = list(bundle.requests[:3])
    cap = srv.trace_cap(reqs)
    table = srv.new_table(cap)
    table, _ = srv.admit(table, cap, [(0, reqs[0], None), (1, reqs[1], None)])
    for _ in range(2):
        table = srv.run_chunk(table)
    table, _ = srv.admit(table, cap, [(2, reqs[2], None)])  # recycling admit
    exe_r = f"{bundle.name}/refill"
    exe_c = f"{bundle.name}/chunk"
    findings = _compile_contract_findings(srv, f"{bundle.name}/refill+chunk")

    vals, n, _, exact = lane_request_inputs(p, bundle.store, reqs[0], cap)
    delta = CFG.delta if CFG.delta is not None else p.delta_default
    refill_args = (
        table,
        jnp.asarray(vals),
        jnp.asarray(n),
        srv._agg_ids,
        jnp.asarray(delta, jnp.float32),
        jnp.asarray(exact),
        jnp.asarray(CFG.tau, jnp.float32),
        jnp.asarray(CFG.max_iters, jnp.int32),
        jnp.asarray(0, jnp.int32),
    )
    table_bytes = int(table.vals.nbytes)
    fr, facts_r = _lint_static(
        srv._refill, refill_args, contract_for("refill"), exe_r,
        min_alias_bytes=table_bytes, n_devices=1,
    )
    fc, facts_c = _lint_static(
        srv._chunk, (table,), contract_for("chunk"), exe_c,
        min_alias_bytes=table_bytes, n_devices=1,
    )
    return [(exe_r, findings + fr, facts_r), (exe_c, fc, facts_c)]


def check_feature_cache(
    bundle: Any,
) -> tuple[str, list[LintFinding], dict[str, Any]]:
    """Cache-fed serving (PR 9): hits mint nothing, appends stay coherent.

    Three probes on top of the static lint of the prebuilt batch program:

    1. compile contract — the cached server's trace hooks must show exactly
       ``fused_prebuilt + afc_precompute`` executables per cap bucket;
    2. hit path — re-serving a resident key must compile ZERO new
       executables (the whole point of device-resident precompute);
    3. append coherence — after ``Table.append`` on a served group, the
       cached server must match an uncached oracle (version-keyed entries
       can never serve stale data).
    """
    exe = f"{bundle.name}/fused_prebuilt"
    srv = BiathlonServer(bundle, CFG, mode="fused", cache_size=8)
    reqs = list(bundle.requests[:3])
    for req in reqs:
        srv.serve(req)
    findings = _compile_contract_findings(srv, exe)
    before = srv.compile_count
    srv.serve(reqs[0])
    hit_clean = srv.compile_count == before
    if not hit_clean:
        findings.append(LintFinding(
            contract="executables_per_bucket", executable=exe,
            where="<cache hit>",
            message=(
                f"cache-hit serve minted {srv.compile_count - before} "
                "executable(s); hits must re-dispatch the bucket's "
                "existing prebuilt program"
            ),
        ))
    # append-coherence probe: grow a served group, then diff against an
    # uncached oracle (fresh server; the store is shared, so the oracle
    # re-gathers the post-append truth)
    oracle = BiathlonServer(bundle, CFG, mode="fused")
    t, _c, g = bundle.pipeline.agg_specs(reqs[0])[0]
    table = bundle.store[t]
    table.append(
        {name: [float(np.asarray(col).mean()) + 3.0]
         for name, col in table.columns.items()},
        group_key=g,
    )
    coherence = cache_coherence_findings(srv, oracle, reqs, exe)
    findings += coherence

    # static lint of the prebuilt batch program: the donated stacked values
    # buffer must still alias through lane_vals with tables as an input
    bsrv = BatchedFusedServer(bundle, CFG, batch_size=LANES, cache_size=8)
    bsrv.serve_batch(reqs)
    cap = bsrv.batch_cap(reqs)
    p = bundle.pipeline
    entries = [bsrv.cache.get(p.agg_specs(r), cap) for r in reqs]
    lane_entries = entries + [entries[0]] * (LANES - len(reqs))
    args = (
        jnp.stack([e.vals for e in lane_entries]),
        jnp.stack([e.n for e in lane_entries]),
        jnp.broadcast_to(bsrv._agg_ids, (LANES, p.k)),
        jnp.zeros((LANES,), jnp.float32) + jnp.float32(1.0),
        jnp.zeros((LANES, len(p.exact_features)), jnp.float32),
        jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[e.tables for e in lane_entries]
        ),
        jnp.asarray(np.arange(LANES) < len(reqs)),
        jnp.full((LANES,), CFG.tau, jnp.float32),
        jnp.full((LANES,), CFG.max_iters, jnp.int32),
    )
    f2, facts = _lint_static(
        bsrv._batched, args, contract_for("fused_prebuilt"), exe,
        min_alias_bytes=args[0].nbytes, n_devices=1,
    )
    facts["hit_zero_compiles"] = hit_clean
    facts["append_coherent"] = not coherence
    return exe, findings + f2, facts


def check_recovery(
    bundle: Any,
) -> tuple[str, list[LintFinding], dict[str, Any]]:
    """Fault-tolerance probes (PR 10): rollback, quarantine, recovery.

    Four dynamic invariants on the REAL servers (no fault profile — the
    probes crash the state directly, so they are deterministic):

    1. chunk rollback — restore + replay is bitwise-identical to fault-free;
    2. lane quarantine — a poisoned lane's full re-admission matches the
       never-poisoned oracle and its neighbor is untouched;
    3. store recovery — journal replay rebuilds the derived index
       byte-identical after a torn crash state;
    4. cache integrity — a flipped byte in a resident entry is detected by
       the power-sum checksum and rebuilt, never served.

    Mutates the store (journaled appends + recover), so it must run LAST
    for its pipeline.
    """
    exe = f"{bundle.name}/recovery"
    srv = ContinuousBatchedServer(bundle, CFG, batch_size=2, chunk_iters=2)
    reqs = list(bundle.requests[:2])
    f_roll = rollback_findings(srv, reqs, exe)
    f_quar = quarantine_findings(srv, reqs, exe)
    f_cache = cache_integrity_findings(bundle, exe)
    f_store = store_recovery_findings(bundle, exe)
    facts = {
        "contract": "recovery",
        "rollback_bitwise": not f_roll,
        "quarantine_isolated": not f_quar,
        "store_recover_exact": not f_store,
        "cache_corruption_detected": not f_cache,
    }
    return exe, f_roll + f_quar + f_cache + f_store, facts


def check_flatness() -> tuple[str, list[LintFinding], dict[str, Any]]:
    """Incremental-AFC while-body flatness probe (pipeline-independent).

    Explicitly pins ``afc_backend="incremental"`` so the probe stays
    meaningful under the CI legs that force ``REPRO_AFC_BACKEND=ref`` —
    env overrides only apply to "auto".
    """
    exe = "probe/incremental_flatness"
    k = 3
    w = jnp.asarray([1.0, -2.0, 0.5])
    texts: dict[int, str] = {}
    for cap in FLATNESS_CAPS:
        fused = build_fused_executor(
            lambda rows, exact: rows @ w,
            k=k, task="regression", m=16, m_sobol=8, max_iters=8, n_boot=16,
            holistic=(1,), quantiles=(0.5,), afc_backend="incremental",
        )
        args = (
            jax.ShapeDtypeStruct((k, cap), jnp.float32),
            jax.ShapeDtypeStruct((k,), jnp.int32),
            jax.ShapeDtypeStruct((k,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.float32),
            jax.ShapeDtypeStruct((0,), jnp.float32),
        )
        texts[cap] = jax.jit(fused).lower(*args).compile().as_text()
    findings = hlo_lint.check_while_flatness(texts, exe)
    facts = {
        "contract": "fused",
        "caps": list(FLATNESS_CAPS),
        "flat": not findings,
    }
    return exe, findings, facts


# ----------------------------------------------------------------- driver
def run_checks(
    pipelines: Sequence[str] = DEFAULT_PIPELINES, *, flatness: bool = True
) -> tuple[list[LintFinding], dict[str, dict[str, Any]]]:
    """Run every check; returns ``(findings, facts_by_executable)``."""
    n_dev = len(jax.devices())
    mesh_dev = next((d for d in (4, 2) if d <= n_dev and LANES % d == 0), 1)
    findings: list[LintFinding] = []
    facts: dict[str, dict[str, Any]] = {}
    for pname in pipelines:
        bundle = make_pipeline(pname, **SMALL)
        exe, f, fa = check_fused(bundle)
        findings += f
        facts[exe] = fa
        mesh = make_serving_mesh(mesh_dev)
        exe, f, fa = check_fused(bundle, mesh=mesh, n_devices=mesh_dev)
        findings += f
        facts[exe] = fa
        for exe, f, fa in check_continuous(bundle):
            findings += f
            facts[exe] = fa
        # LAST per pipeline: these probes mutate the store (append
        # coherence, then journaled appends + recovery)
        exe, f, fa = check_feature_cache(bundle)
        findings += f
        facts[exe] = fa
        exe, f, fa = check_recovery(bundle)
        findings += f
        facts[exe] = fa
    if flatness:
        exe, f, fa = check_flatness()
        findings += f
        facts[exe] = fa
    return findings, facts


def _baseline_diff(
    facts: dict[str, Any], baseline_path: Path
) -> list[str]:
    """Unified diff of observed facts vs the checked-in baseline."""
    got = json.dumps(facts, indent=2, sort_keys=True) + "\n"
    if not baseline_path.exists():
        return [f"baseline {baseline_path} missing — run with --update-baseline"]
    want = baseline_path.read_text()
    if want == got:
        return []
    return list(difflib.unified_diff(
        want.splitlines(), got.splitlines(),
        fromfile=str(baseline_path), tofile="<observed>", lineterm="",
    ))


def _run_mutations() -> int:
    """Run the seeded violations; returns the number NOT caught."""
    from repro.analysis import mutations

    missed = 0
    for name, fn in mutations.MUTATIONS.items():
        caught = fn()
        status = "caught" if caught else "MISSED"
        print(f"mutation {name:<24s} {status}")
        for f in caught:
            print(f"    {f}")
        if not caught:
            missed += 1
    return missed


def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.check",
        description="Static contract checker for the serving executables.",
    )
    ap.add_argument("--pipelines", default=",".join(DEFAULT_PIPELINES),
                    help="comma-separated pipeline names (data/synthetic.py)")
    ap.add_argument("--baseline", type=Path, default=BASELINE_PATH,
                    help="facts baseline to diff against")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline from this run's facts")
    ap.add_argument("--no-flatness", action="store_true",
                    help="skip the incremental-AFC flatness probe")
    ap.add_argument("--list", action="store_true",
                    help="print the registered contracts and exit")
    ap.add_argument("--mutation-test", action="store_true",
                    help="verify the checker catches every seeded violation")
    args = ap.parse_args(argv)

    if args.list:
        for name, c in sorted(all_contracts().items()):
            print(f"{name}: {json.dumps(c.as_dict(), indent=2)}")
        return 0

    rc = 0
    if args.mutation_test:
        missed = _run_mutations()
        if missed:
            print(f"FAIL: {missed} seeded mutation(s) not caught")
            return 1
        print("all seeded mutations caught")
        return 0

    pipelines = tuple(p for p in args.pipelines.split(",") if p)
    findings, facts = run_checks(pipelines, flatness=not args.no_flatness)
    for f in findings:
        print(f"VIOLATION {f}")
    if args.update_baseline:
        args.baseline.write_text(
            json.dumps(facts, indent=2, sort_keys=True) + "\n"
        )
        print(f"baseline written: {args.baseline}")
    else:
        diff = _baseline_diff(facts, args.baseline)
        if diff:
            print("baseline drift:")
            for line in diff:
                print(f"  {line}")
            rc = 1
    if findings:
        rc = 1
    n = len(facts)
    print(("FAIL" if rc else "OK") + f": {n} executables checked, "
          f"{len(findings)} violation(s)")
    return rc


if __name__ == "__main__":
    sys.exit(main())

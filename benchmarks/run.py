"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (scaffold contract).  Set
``QUICK=1`` for a fast smoke pass; ``ONLY=fig4,roofline`` filters sections.
"""
from __future__ import annotations

import os
import sys
import time
import traceback


def main() -> None:
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    from benchmarks import (
        breakdown,
        end_to_end,
        feature_store,
        fused_vs_host,
        kernel_micro,
        median_bootstrap,
        median_imbalance,
        roofline,
        serving_load,
        vary_alpha,
        vary_delta,
        vary_gamma,
        vary_num_ops,
        vary_tau,
    )

    sections = {
        "fig4_end_to_end": end_to_end.run,
        "fig5_breakdown": breakdown.run,
        "fig6_vary_tau": vary_tau.run,
        "fig7_vary_delta": vary_delta.run,
        "fig8_vary_alpha": vary_alpha.run,
        "fig9_vary_gamma": vary_gamma.run,
        "fig10_vary_num_ops": vary_num_ops.run,
        "fig11_12_median": median_bootstrap.run,
        "fig13_14_imbalance": median_imbalance.run,
        "kernel_micro": kernel_micro.run,
        "perf_fused_vs_host": fused_vs_host.run,
        "perf_fused_vs_host_holistic": fused_vs_host.run_holistic,
        # incremental-AFC cap sweep (PR 5): rescan vs prefix-stats loop body
        "perf_incremental_afc": fused_vs_host.run_large_n,
        # hot-group feature cache (PR 9): cached precompute ~0, small-cap
        # speedup >= 1 (BENCH_fused.json["feature_store"])
        "perf_feature_store": feature_store.run,
        "perf_serving_load": serving_load.run,
        # SLO-aware degradation: latency/guarantee Pareto sweep + bounded
        # 3x-overload run (BENCH_serving.json["adaptive_slo"]) — wired here
        # so the tracked section can't go stale
        "perf_adaptive_slo": serving_load.run_adaptive_slo,
        # continuous batching vs fixed lanes on one saturating trace
        # (BENCH_serving.json["continuous_batching"])
        "perf_continuous": serving_load.run_continuous,
        # availability under a seeded fault storm: rollback/quarantine
        # recovery must be bitwise-exact (BENCH_serving.json["fault_recovery"])
        "perf_fault_recovery": serving_load.run_fault_recovery,
        # device-scaling sweep: in-process over the real chips when an
        # accelerator is present; a CPU host rehearses it in a child with
        # forced host devices
        "perf_serving_sharded": serving_load.run_sharded_sweep,
        "roofline": roofline.run,
    }
    only = os.environ.get("ONLY")
    if only:
        keys = [k for k in sections if any(tok in k for tok in only.split(","))]
        sections = {k: sections[k] for k in keys}

    print("name,us_per_call,derived")
    failures = []
    for key, fn in sections.items():
        t0 = time.time()
        try:
            for row in fn():
                print(row, flush=True)
        except Exception as e:  # noqa: BLE001 — keep the suite going
            traceback.print_exc()
            failures.append((key, str(e)[:120]))
        print(f"# section {key} done in {time.time()-t0:.1f}s", flush=True)
    if failures:
        print(f"# {len(failures)} section failures: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()

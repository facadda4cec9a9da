"""A benchmark root at a size the CPU holds, beside the real one.

It carries its own ``BENCHMARK.json``, a configuration, a traffic mix, the
real metric readers and model kinds, so building it also shows that a cell
is added by files and entries alone.
"""
from __future__ import annotations

import copy
import json
import pathlib
import shutil

from bench import manifest

CELL = "tiny.backlog"


def make_root(tmp: pathlib.Path, config: str | dict = "battery_median",
              m: int = 64) -> pathlib.Path:
    """A root whose one cell, ``tiny.backlog``, runs ``config`` (a
    configuration of ``bench/configs`` by name, or one given whole) cut to
    a size the CPU holds, with ``m`` QMC rows of the AMI stage."""
    tmp = pathlib.Path(tmp)
    bench = tmp / "bench"
    shutil.copytree(manifest.BENCH_DIR / "metrics", bench / "metrics")
    shutil.copytree(manifest.BENCH_DIR / "models", bench / "models",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "configs").mkdir()
    (bench / "traffic").mkdir()
    if isinstance(config, str):
        config = json.loads((manifest.BENCH_DIR / "configs" / f"{config}.json").read_text())
    cfg = copy.deepcopy(config)
    # groups of 1,125-1,875 rows: cap 2,048, above the rescan crossover, so
    # the incremental path with its prefix tables and rank index serves them
    cfg["size"] = {"deployment_seed": 3, "rows_per_group": 1500,
                   "n_train_groups": 60, "n_serve_groups": 6, "request_log": 64}
    cfg["planner"].update(m=m, m_sobol=16, n_bootstrap=32, max_iters=8)
    cfg["serving"] = {"lanes_per_chip": 2, "chunk_iters": 2}
    (bench / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "tiny.json").write_text(json.dumps(
        {"round_size": 6, "spacing_s": 0.0, "rounds": 3}))
    man = json.loads((manifest.ROOT / "BENCHMARK.json").read_text())
    man["configs"] = [{"name": "tiny", "source": "https://arxiv.org/abs/2405.11191",
                       "file": "bench/configs/tiny.json", "reduced": [],
                       "why": "a CPU-sized copy of a real configuration"}]
    man["workloads"] = [{"name": CELL, "config": "tiny", "traffic": "tiny",
                         "chips": 1, "why": "tests"}]
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL]
    (tmp / "BENCHMARK.json").write_text(json.dumps(man))
    return tmp

"""BENCHMARK.json: the contract's checks, and files found by their names."""
from __future__ import annotations

import copy
import json
import time

import pytest

from bench import harness, manifest, traffic
from bench.tests import tiny


@pytest.fixture(scope="module")
def man():
    return manifest.load()


def test_benchmark_json_keeps_the_contract(man):
    assert man["command"] == ["python3", "bench/run.py"]
    assert man["paths"] == ["bench"]
    assert 1 <= man["run_seconds"] <= 51
    assert {c["name"] for c in man["configs"]} == {w["config"] for w in man["workloads"]}
    four = [w["name"] for w in man["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(man["workloads"]) // 2)
    assert all(name.endswith("mesh4") for name in four)
    assert len(json.dumps(man)) < 64 * 1024


def test_every_file_is_found_by_its_name(man):
    for c in man["configs"]:
        cfg = manifest.config(man, c["name"])
        assert cfg["name"] == c["name"]
        assert set(cfg["checks"]) == {"yhat_gap", "prob_gap"}
    for w in man["workloads"]:
        traffic.check(manifest.traffic(w["traffic"]))
    for m in man["end_to_end"] + man["per_layer"]:
        assert callable(manifest.reader(m["name"]))


def test_each_cell_reports_what_its_layer_metrics_move(man):
    for m in man["per_layer"]:
        moved = next(e for e in man["end_to_end"] if e["name"] == m["moves"])
        assert set(manifest.metric_cells(m, man)) <= set(manifest.metric_cells(moved, man))


def _broken(man, path, value):
    bad = copy.deepcopy(man)
    *keys, last = path
    node = bad
    for k in keys:
        node = node[k]
    node[last] = value
    return bad


@pytest.mark.parametrize("path,value", [
    (("workloads", 0, "name"), "has space"),
    (("workloads", 0, "name"), "a/b"),
    (("end_to_end", 0, "unit"), "req per s"),
    (("end_to_end", 0, "unit"), "µs"),
    (("end_to_end", 0, "bound"), 0.3),
    (("per_layer", 0, "moves"), "no_such_metric"),
    (("per_layer", 1, "workloads"), ["turbofan.backlog"]),
    (("workloads", 0, "chips"), 2),
    (("workloads", 0, "traffic"), "no_such_mix"),
    (("configs", 0, "file"), "bench/configs/missing.json"),
    (("per_layer", 0, "name"), "no_such_reader"),
])
def test_a_manifest_outside_the_contract_is_refused(man, path, value):
    with pytest.raises(manifest.ManifestError):
        manifest.validate(_broken(man, path, value), manifest.ROOT)


def test_an_unused_config_is_refused(man):
    bad = copy.deepcopy(man)
    bad["configs"].append(dict(bad["configs"][0], name="spare"))
    with pytest.raises(manifest.ManifestError):
        manifest.validate(bad, manifest.ROOT)


def test_new_config_traffic_and_metric_need_only_files_and_entries(tmp_path):
    root = tiny.make_root(tmp_path)
    (root / "bench" / "metrics" / "served_per_round.py").write_text(
        "def read(run):\n    return 42.0\n")
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["per_layer"].append({
        "name": "served_per_round", "unit": "req", "better": "higher",
        "source": "host_clock", "layer": "runtime", "moves": "served_rps",
        "workloads": [tiny.CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    man = manifest.load(root / "BENCHMARK.json")
    names = [m["name"] for m in manifest.cell_metrics(man, tiny.CELL, "per_layer")]
    assert "served_per_round" in names
    assert manifest.reader("served_per_round", root)(None) == 42.0
    assert manifest.config(man, "tiny", root)["size"]["rows_per_group"] == 1500
    assert manifest.traffic("tiny", root)["round_size"] == 6


LINEAR = '''"""Linear regression: the scaled row times the coefficients, plus the
intercept; no comparison, so a row's score range is the score."""
import numpy as np

from bench.precision import F64

threshold = 0.0


def of(pipeline):
    model = pipeline.model
    return (np.asarray(model.coef, np.float64), float(model.intercept),
            np.asarray(pipeline.scaler_mean, np.float64),
            np.asarray(pipeline.scaler_scale, np.float64))


def raw(model, full, r):
    coef, intercept, mu, scale = model
    xs = r(r(np.asarray(full, np.float64) - mu) / scale)
    return r(r(xs @ r(coef)) + intercept)


def interval(model, full):
    score = raw(model, full, F64)
    return score, score


def ops_per_row(model):
    return 2 * model[0].size
'''


def tick_price(task: str = "regression") -> dict:
    """Biathlon's Tick-Price (Table 1: linear regression over 1 AGG and 6
    request features) as a configuration."""
    cfg = json.loads((manifest.BENCH_DIR / "configs" / "turbofan.json").read_text())
    cfg.update(name="tick_price", pipeline="tick_price", table="ticks", task=task,
               model={"kind": "linear"}, features=[{"op": "avg", "column": "price"}],
               exact=["bid", "ask", "spread", "vol", "hour", "lag_price"])
    if task == "classification":
        cfg["checks"] = {"yhat_gap": {"limit": 0}, "prob_gap": {"limit": 1e-3}}
    return cfg


def test_a_model_kind_joins_by_its_file_alone(tmp_path):
    root = tiny.make_root(tmp_path, tick_price())
    (root / "bench" / "models" / "linear.py").write_text(LINEAR)
    man = manifest.load(root / "BENCHMARK.json")
    res = harness.run_cell(tiny.CELL, 2**31 + 5, 1.0, False, t_start=time.perf_counter(),
                           require_chip=False, man=man, root=root)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


def test_a_model_kind_without_its_file_is_refused(tmp_path):
    cfg = tick_price()
    cfg.update(name="bearing_imbalance", pipeline="bearing_imbalance", table="vibration",
               task="classification", model={"kind": "mlp"}, exact=[],
               checks={"yhat_gap": {"limit": 0}, "prob_gap": {"limit": 1e-3}})
    root = tiny.make_root(tmp_path, cfg)
    with pytest.raises(manifest.ManifestError, match="bench/models/mlp.py"):
        manifest.load(root / "BENCHMARK.json")


@pytest.mark.parametrize("change", [{"task": "ranking"},
                                    {"checks": {"yhat_gap": {"limit": 0},
                                                "prob_gap": {"limit": 1.0 / 64}}}])
def test_a_config_without_a_sound_task_is_refused(tmp_path, change):
    cfg = dict(tick_price("classification"), **change)
    root = tiny.make_root(tmp_path, cfg)
    (root / "bench" / "models" / "linear.py").write_text(LINEAR)
    with pytest.raises(manifest.ManifestError, match="task|1/m"):
        manifest.load(root / "BENCHMARK.json")


def test_the_harness_refuses_a_task_that_differs_from_the_pipeline(tmp_path):
    root = tiny.make_root(tmp_path, tick_price("classification"))
    (root / "bench" / "models" / "linear.py").write_text(LINEAR)
    man = manifest.load(root / "BENCHMARK.json")
    cfg = manifest.config(man, "tiny", root)
    with pytest.raises(ValueError, match="task"):
        harness.build(cfg, cfg["size"]["deployment_seed"], 1)

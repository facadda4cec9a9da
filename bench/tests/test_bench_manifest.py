"""BENCHMARK.json: the contract's checks, and files found by their names."""
from __future__ import annotations

import copy
import json

import pytest

from bench import manifest, traffic
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

"""``BENCHMARK.json``: load it, check it, and find each file by its name.

A configuration, a traffic mix, a metric or a model kind is added by adding
its file and its entry; nothing here names one.  The checks are those a
later entry can break: characters of names and units, every per-layer
metric's ``moves`` reported by each of its cells, every configuration used
by a cell, its task and the file of its model's kind, and every file
present.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import re

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
SOURCES_E2E = ("host_clock", "device_trace")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TASKS = ("regression", "classification")


class ManifestError(ValueError):
    """``BENCHMARK.json`` or a file it names is not as the contract says."""


def load(path: pathlib.Path | None = None) -> dict:
    """Read and check ``BENCHMARK.json`` (at the checkout root by default)."""
    path = ROOT / "BENCHMARK.json" if path is None else pathlib.Path(path)
    man = json.loads(path.read_text())
    validate(man, path.parent)
    return man


def _name(value, what: str) -> str:
    if not isinstance(value, str) or not NAME.fullmatch(value):
        raise ManifestError(f"{what}: {value!r} is not a valid name")
    return value


def _line(value, what: str) -> str:
    if (not isinstance(value, str) or not 1 <= len(value) <= 200
            or "\n" in value or "\t" in value):
        raise ManifestError(f"{what}: must be one line of 1-200 characters")
    return value


def metric_cells(metric: dict, man: dict) -> list[str]:
    """The cells that report ``metric``.

    An end-to-end metric without ``workloads`` is reported by every cell; a
    per-layer one by every cell that reports the metric it ``moves``.
    """
    if "workloads" in metric:
        return list(metric["workloads"])
    if "moves" in metric:
        moved = next(m for m in man["end_to_end"] if m["name"] == metric["moves"])
        return metric_cells(moved, man)
    return [w["name"] for w in man["workloads"]]


def validate(man: dict, root: pathlib.Path) -> None:
    """Raise :class:`ManifestError` unless ``man`` keeps the contract."""
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(man) != keys:
        raise ManifestError(f"top-level keys must be {sorted(keys)}")
    configs = {}
    for c in man["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            raise ManifestError(f"config {c.get('name')!r}: wrong keys")
        name = _name(c["name"], "config name")
        _line(c["source"], f"config {name} source")
        _line(c["why"], f"config {name} why")
        for k in c["reduced"]:
            _name(k, f"config {name} reduced key")
        if not (root / c["file"]).is_file():
            raise ManifestError(f"config {name}: no file {c['file']}")
        check_config(json.loads((root / c["file"]).read_text()), name, root)
        if name in configs:
            raise ManifestError(f"config {name} appears twice")
        configs[name] = c
    cells, pairs = {}, set()
    for w in man["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            raise ManifestError(f"workload {w.get('name')!r}: wrong keys")
        name = _name(w["name"], "workload name")
        _name(w["traffic"], f"workload {name} traffic")
        _line(w["why"], f"workload {name} why")
        if w["config"] not in configs:
            raise ManifestError(f"workload {name}: unknown config {w['config']}")
        if w["chips"] not in (1, 4):
            raise ManifestError(f"workload {name}: chips must be 1 or 4")
        traffic_path(w["traffic"], root)
        if name in cells or (w["config"], w["traffic"]) in pairs:
            raise ManifestError(f"workload {name} or its pair appears twice")
        cells[name] = w
        pairs.add((w["config"], w["traffic"]))
    unused = set(configs) - {w["config"] for w in cells.values()}
    if unused:
        raise ManifestError(f"configs used by no cell: {sorted(unused)}")
    e2e = {}
    seen = set()
    for group, sources in (("end_to_end", SOURCES_E2E), ("per_layer", SOURCES)):
        for m in man[group]:
            name = _name(m["name"], f"{group} metric name")
            if name in seen:
                raise ManifestError(f"metric {name} appears twice")
            seen.add(name)
            if not UNIT.fullmatch(m.get("unit", "")):
                raise ManifestError(f"metric {name}: bad unit {m.get('unit')!r}")
            if m.get("better") not in ("lower", "higher"):
                raise ManifestError(f"metric {name}: better must be lower|higher")
            if m.get("source") not in sources:
                raise ManifestError(f"metric {name}: source {m.get('source')!r}")
            for cell in m.get("workloads", []):
                if cell not in cells:
                    raise ManifestError(f"metric {name}: unknown cell {cell}")
            metric_path(name, root)
            if group == "end_to_end":
                allowed = {"name", "unit", "better", "bound", "source", "workloads"}
                if not 0 < m.get("bound", -1) <= 0.25:
                    raise ManifestError(f"metric {name}: bound must be in (0, 0.25]")
                e2e[name] = m
            else:
                allowed = {"name", "unit", "better", "source", "layer", "moves",
                           "workloads"}
                _line(m.get("layer"), f"metric {name} layer")
                if m.get("moves") not in e2e:
                    raise ManifestError(f"metric {name}: moves an unknown metric")
            if not set(m) <= allowed:
                raise ManifestError(f"metric {name}: keys {sorted(set(m) - allowed)}")
    if "setup_s" not in e2e:
        raise ManifestError("setup_s must be an end-to-end metric")
    for m in man["per_layer"]:
        moved = set(metric_cells(e2e[m["moves"]], man))
        missing = set(metric_cells(m, man)) - moved
        if missing:
            raise ManifestError(
                f"metric {m['name']}: cells {sorted(missing)} do not report "
                f"{m['moves']}"
            )
    for cell in cells:
        e2e_here = [n for n, m in e2e.items() if cell in metric_cells(m, man)]
        layer_here = [m for m in man["per_layer"] if cell in metric_cells(m, man)]
        if "setup_s" not in e2e_here or len(e2e_here) < 2 or not layer_here:
            raise ManifestError(
                f"cell {cell}: needs setup_s, another end-to-end metric and a "
                "per-layer metric"
            )


def check_config(cfg: dict, name: str, root: pathlib.Path) -> None:
    """A configuration states its task, and its model's kind has a file.

    A classification (δ = 0) judges the share of the m QMC rows of each
    class, so its ``prob_gap`` limit lies below 1/m: one row of the wrong
    class fails.
    """
    task = cfg.get("task")
    if task not in TASKS:
        raise ManifestError(f"config {name}: task {task!r} is not one of {TASKS}")
    model_path(_name(cfg.get("model", {}).get("kind"), f"config {name} model kind"),
               root)
    if task == "classification":
        limit = cfg["checks"]["prob_gap"]["limit"]
        if not 0 <= limit < 1.0 / cfg["planner"]["m"]:
            raise ManifestError(
                f"config {name}: a classification's prob_gap limit {limit!r} "
                f"must lie below 1/m = {1.0 / cfg['planner']['m']!r}")


def cell(man: dict, name: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise ManifestError(f"no workload {name!r} in BENCHMARK.json")


def config(man: dict, name: str, root: pathlib.Path = ROOT) -> dict:
    """The configuration file of ``name``, as it is run."""
    entry = next(c for c in man["configs"] if c["name"] == name)
    return json.loads((root / entry["file"]).read_text())


def traffic_path(name: str, root: pathlib.Path = ROOT) -> pathlib.Path:
    path = root / "bench" / "traffic" / f"{name}.json"
    if not path.is_file():
        raise ManifestError(f"traffic {name}: no file {path.relative_to(root)}")
    return path


def traffic(name: str, root: pathlib.Path = ROOT) -> dict:
    return json.loads(traffic_path(name, root).read_text())


def metric_path(name: str, root: pathlib.Path = ROOT) -> pathlib.Path:
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise ManifestError(f"metric {name}: no reader {path.relative_to(root)}")
    return path


def _module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reader_module(name: str, root: pathlib.Path = ROOT):
    """The module of metric ``name``'s reader, loaded from its file."""
    return _module(metric_path(name, root), f"bench_metric_{name}")


def reader(name: str, root: pathlib.Path = ROOT):
    """The ``read(run) -> float | None`` function of metric ``name``."""
    return reader_module(name, root).read


def cell_metrics(man: dict, cell_name: str, group: str) -> list[dict]:
    """The metrics of ``group`` that ``cell_name`` reports."""
    return [m for m in man[group] if cell_name in metric_cells(m, man)]


def model_path(kind: str, root: pathlib.Path = ROOT) -> pathlib.Path:
    path = root / "bench" / "models" / f"{kind}.py"
    if not path.is_file():
        raise ManifestError(f"model kind {kind}: no file {path.relative_to(root)}")
    return path


def model_module(kind: str, root: pathlib.Path = ROOT):
    """The module of model kind ``kind``, loaded from its file
    (``bench/reference.py`` ``Model`` says what it gives)."""
    return _module(model_path(kind, root), f"bench_model_{kind}")

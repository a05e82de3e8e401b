"""Find a cell's pieces by name.

``BENCHMARK.json`` names the cells; each piece of a cell sits in a file
of its own under ``bench/``:

* ``configs/<config>.json``      the model configuration as it is run;
* ``reference/<reference>.py``   the plain reference the configuration
                                 names: forward pass, loss, parameter
                                 layout (``layout(model)``);
* ``traffic/<traffic>.json``     the traffic mix, read by ``harness.traffic``;
* ``workloads/<cell>.json``      the server settings and the check limits;
* ``metrics/<metric>.py``        one reader per metric (``read(rec)``).

Adding a cell, a configuration, an architecture's reference, a traffic
mix or a metric is adding files and entries; no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    kind: str                      # "end_to_end" | "per_layer"
    workloads: Optional[List[str]]
    moves: Optional[str] = None
    layer: Optional[str] = None
    bound: Optional[float] = None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    settings: Dict[str, Any]       # workloads/<cell>.json
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_benchmark(root: str = REPO_DIR) -> Dict[str, Any]:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _metrics(bench: Dict[str, Any], kind: str) -> List[Metric]:
    out = []
    for m in bench.get(kind, []):
        out.append(Metric(name=m["name"], unit=m["unit"], better=m["better"],
                          source=m["source"], kind=kind,
                          workloads=m.get("workloads"), moves=m.get("moves"),
                          layer=m.get("layer"), bound=m.get("bound")))
    return out


def reports(metric: Metric, cell: str, bench: Dict[str, Any]) -> bool:
    """Whether ``cell`` reports ``metric``: a metric with a ``workloads``
    list is reported there; one without it where the end-to-end metric
    it moves is (per-layer) or in every cell (end-to-end)."""
    if metric.workloads is not None:
        return cell in metric.workloads
    if metric.kind == "per_layer" and metric.moves:
        moved = next(m for m in _metrics(bench, "end_to_end")
                     if m.name == metric.moves)
        return reports(moved, cell, bench)
    return True


def load_cell(name: str, root: str = REPO_DIR,
              bench: Optional[Dict[str, Any]] = None,
              bench_dir: Optional[str] = None) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    bench_dir = bench_dir or os.path.join(root, "bench")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = sorted(w["name"] for w in bench["workloads"])
        raise KeyError(f"unknown workload {name!r}; known: {known}")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    config["_reference_file"] = _find("reference", config["reference"],
                                      ".py", bench_dir)
    traffic = _load_json(os.path.join(bench_dir, "traffic",
                                      entry["traffic"] + ".json"))
    settings = _load_json(os.path.join(bench_dir, "workloads", name + ".json"))
    e2e = [m for m in _metrics(bench, "end_to_end") if reports(m, name, bench)]
    per = [m for m in _metrics(bench, "per_layer") if reports(m, name, bench)]
    return Cell(name=name, chips=int(entry["chips"]),
                config_name=entry["config"], traffic_name=entry["traffic"],
                config=config, traffic=traffic, settings=settings,
                end_to_end=e2e, per_layer=per)


_MODULES: Dict[str, Any] = {}


def _find(kind: str, name: str, ext: str,
          bench_dir: Optional[str] = None) -> str:
    """``<bench_dir>/<kind>/<name><ext>``, else the benchmark's own."""
    for d in (bench_dir, BENCH_DIR):
        path = os.path.join(d or BENCH_DIR, kind, name + ext)
        if os.path.exists(path):
            return path
    raise KeyError(f"no {kind} file {name + ext!r}")


def _module(path: str) -> Any:
    if path not in _MODULES:
        stem = os.path.basename(os.path.dirname(path)) + "_" + \
            os.path.basename(path)[:-3]
        spec = importlib.util.spec_from_file_location(
            "bench_" + "".join(c if c.isalnum() else "_" for c in stem),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def reference_file(config: Dict[str, Any]) -> str:
    """The file of the plain reference that ``config`` names."""
    return config.get("_reference_file") or _find(
        "reference", config["reference"], ".py")


def reference_at(path: str) -> Any:
    return _module(path)


def reference(config: Dict[str, Any]) -> Any:
    """The plain reference module of a configuration."""
    return _module(reference_file(config))


def metric_reader(name: str, bench_dir: Optional[str] = None) -> Callable:
    """``read(rec) -> float | None`` from ``metrics/<name>.py``."""
    return _module(_find("metrics", name, ".py", bench_dir)).read

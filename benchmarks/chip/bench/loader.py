"""Find everything a cell needs by name.

    BENCHMARK.json workload  -> config name, traffic name, chips
    configs/<config>.json    -> model sizes, precision, reference module
    traffic/<traffic>.json   -> driver name and its parameters
    drivers/<driver>.py      -> the code that runs the window
    limits/<workload>.json   -> the limit of each number compared
    metrics/<metric>.py      -> the reader of one per-layer metric

A new cell is new files plus its BENCHMARK.json entry.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Dict, List

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: Dict[str, float]
    end_to_end: List[dict]
    per_layer: List[dict]
    driver_path: str
    metric_paths: Dict[str, str] = field(default_factory=dict)

    def driver(self):
        return load_module(self.driver_path, "driver_" + self.traffic["driver"])

    def metric_reader(self, name: str):
        return load_module(self.metric_paths[name],
                           "metric_" + name.replace(".", "_"))


def benchmark(root: str = ROOT) -> dict:
    return read_json(os.path.join(root, "BENCHMARK.json"))


def resolve(workload: str, root: str = ROOT) -> Cell:
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    here = os.path.join(root, bench["paths"][0])
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = read_json(os.path.join(root, cfg_entry["file"]))
    traffic = read_json(os.path.join(here, "traffic", w["traffic"] + ".json"))
    limits = read_json(os.path.join(here, "limits", workload + ".json"))
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    reported = {m["name"] for m in e2e}

    def reads_here(m):
        if "workloads" in m:
            return workload in m["workloads"]
        return m["moves"] in reported

    per_layer = [m for m in bench["per_layer"] if reads_here(m)]
    cell = Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], config=config,
                traffic_name=w["traffic"], traffic=traffic, limits=limits,
                end_to_end=e2e, per_layer=per_layer,
                driver_path=os.path.join(here, "drivers",
                                         traffic["driver"] + ".py"))
    for m in per_layer:
        cell.metric_paths[m["name"]] = os.path.join(
            here, "metrics", m["name"] + ".py")
    return cell

"""Finds what belongs to a cell by the names in ``BENCHMARK.json``: the
configuration's file of sizes and the plain reference beside it, the
traffic mix, the cell's limits, and each per-layer metric's reader."""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Dict, List, NamedTuple

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    name = "benchmark_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, HERE))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell(NamedTuple):
    name: str
    chips: int
    config_name: str
    config: dict            # the configuration's file, as it is run
    reference: Any          # module beside it: the plain reference
    traffic: dict
    limits: Dict[str, float]
    end_to_end: List[dict]  # metric entries this cell reports
    per_layer: List[dict]
    bench_dir: str


def _for_cell(metrics: List[dict], cell: str, e2e_of_cell=None) -> List[dict]:
    out = []
    for m in metrics:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif e2e_of_cell is None or m.get("moves") in e2e_of_cell:
            out.append(m)
    return out


def load_cell(root: str, workload: str) -> Cell:
    """``root`` holds ``BENCHMARK.json``; every other file is found from
    the names in it, under the directory of the benchmark."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg_path = os.path.join(root, cfg_entry["file"])
    bench_dir = os.path.dirname(os.path.dirname(cfg_path))
    e2e = _for_cell(bench["end_to_end"], workload)
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        config=_load_json(cfg_path),
        reference=load_module(os.path.splitext(cfg_path)[0] + ".py"),
        traffic=_load_json(os.path.join(bench_dir, "traffic",
                                        w["traffic"] + ".json")),
        limits=_load_json(os.path.join(bench_dir, "limits",
                                       workload + ".json"))["limits"],
        end_to_end=e2e,
        per_layer=_for_cell(bench["per_layer"], workload,
                            {m["name"] for m in e2e}),
        bench_dir=bench_dir)


def load_reader(cell: Cell, metric_name: str):
    return load_module(os.path.join(cell.bench_dir, "metrics",
                                    metric_name + ".py")).read

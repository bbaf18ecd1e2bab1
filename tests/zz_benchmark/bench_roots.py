"""Roots for the benchmark's tests: a copy of the benchmark's directory
with a ``BENCHMARK.json`` that also holds the entries which wait under
``benchmark/pending/``, so that a cell that is ready but not yet proved on
the chip is still compiled and rehearsed."""

import glob
import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def benchmark_with_pending() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for path in sorted(glob.glob(os.path.join(ROOT, "benchmark", "pending",
                                              "*.json"))):
        with open(path) as f:
            pending = json.load(f)
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            bench[key] = bench[key] + pending.get(key, [])
    return bench


def make_root(root: str, bench: dict) -> str:
    """``root`` gets a copy of ``benchmark/`` and ``bench`` as its
    ``BENCHMARK.json``."""
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root

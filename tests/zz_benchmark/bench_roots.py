"""Roots for the benchmark's tests: a copy of the benchmark's directory
with a ``BENCHMARK.json`` that also holds the entries which wait under
``benchmark/pending/``, so that a cell that is ready but not yet proved on
the chip is still compiled and rehearsed; and one as a PR that adds a
configuration leaves it, with new files and entries appended."""

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
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[key]]
        twice = sorted({n for n in names if names.count(n) > 1})
        if twice:
            raise ValueError(f"{key} names {twice} twice: an entry that "
                             "has joined BENCHMARK.json leaves "
                             "benchmark/pending/")
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


# What a configuration that joins later brings: Mellum2's file under
# another name at another window, its cell, and one entry of its own.
ADDED_CONFIG = "mellum2_w512"
ADDED_CELL = "mellum2w512_l4_e8_s8192_resident"
ADDED_METRIC = "model.gate_device_ms"
_LIKE_CONFIG = "mellum2_12b_a2p5b_l4_e8"
_LIKE_CELL = "mellum2_l4_e8_s8192_resident"
_READER = '''"""Device time of a step in layer kind ``gate``."""

from benchmark.lib import scopes


def read(ctx):
    return scopes.kind_ms_per_step(ctx, "gate")
'''


def benchmark_with_one_appended() -> dict:
    """``BENCHMARK.json`` with a seventh configuration, its cell and one
    per-layer entry listing that cell alone appended at the ends of their
    lists, and nothing before them changed."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    like = {c["name"]: c for c in bench["configs"]}[_LIKE_CONFIG]
    cell = {w["name"]: w for w in bench["workloads"]}[_LIKE_CELL]
    entry = {m["name"]: m for m in bench["per_layer"]}[
        "model.window_attention_device_ms"]
    bench["configs"].append(dict(
        like, name=ADDED_CONFIG,
        file=f"benchmark/configs/{ADDED_CONFIG}.json",
        why="a window decoder at a window of 512"))
    bench["workloads"].append(dict(
        cell, name=ADDED_CELL, config=ADDED_CONFIG,
        why="4 x 8,192 tokens a step, K=2: window layers at 512"))
    bench["per_layer"].append(dict(entry, name=ADDED_METRIC,
                                   workloads=[ADDED_CELL]))
    return bench


def make_root_with_one_appended(root: str) -> str:
    """``make_root`` of ``benchmark_with_one_appended()``, with the files
    such a PR adds: the configuration's file (``sliding_window`` 512) and
    its reference, the cell's limits and the entry's reader."""
    make_root(root, benchmark_with_one_appended())
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", _LIKE_CONFIG + ".json")) as f:
        spec = json.load(f)
    spec["sliding_window"] = 512
    spec["flags"]["model_config_file"] = \
        f"benchmark/configs/{ADDED_CONFIG}.json"
    with open(os.path.join(bench, "configs", ADDED_CONFIG + ".json"),
              "w") as f:
        json.dump(spec, f)
    shutil.copy(os.path.join(bench, "configs", _LIKE_CONFIG + ".py"),
                os.path.join(bench, "configs", ADDED_CONFIG + ".py"))
    shutil.copy(os.path.join(bench, "limits", _LIKE_CELL + ".json"),
                os.path.join(bench, "limits", ADDED_CELL + ".json"))
    with open(os.path.join(bench, "metrics", ADDED_METRIC + ".py"),
              "w") as f:
        f.write(_READER)
    return root

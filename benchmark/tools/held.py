"""Runs one cell traced, with the per-layer entries that wait under
``benchmark/pending/`` (key ``per_layer_held``) read beside those of
``BENCHMARK.json``, over more than one boundary interval:

    python3 benchmark/tools/held.py --workload <cell> --seed <n> \\
        [--trace_boundaries 2]

The cell runs from a copy of the benchmark under ``.bench_work/`` whose
``BENCHMARK.json`` also lists the held entries and whose traffic file
traces ``--trace_boundaries`` intervals; nothing the benchmark's own
command reads is touched. Prints the result line of ``benchmark/run.py
--trace 1``, with the held metrics in it.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def held_entries(root: str = ROOT) -> list:
    out = []
    for path in sorted(glob.glob(os.path.join(root, "benchmark", "pending",
                                              "*.json"))):
        with open(path) as f:
            out += json.load(f).get("per_layer_held", [])
    return out


def make_root(root: str, workload: str, trace_boundaries: int) -> str:
    """``root`` gets a copy of ``benchmark/`` and a ``BENCHMARK.json``
    with the held entries at the end of ``per_layer``; the traffic of
    ``workload`` traces ``trace_boundaries`` intervals there."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["per_layer"] = bench["per_layer"] + held_entries()
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    cell = {w["name"]: w for w in bench["workloads"]}[workload]
    path = os.path.join(root, "benchmark", "traffic",
                        cell["traffic"] + ".json")
    with open(path) as f:
        traffic = json.load(f)
    traffic["trace_boundaries"] = trace_boundaries
    with open(path, "w") as f:
        json.dump(traffic, f)
    return root


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--trace_boundaries", type=int, default=2)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark.lib import harness, peaks
    os.makedirs(os.path.join(ROOT, ".bench_work"), exist_ok=True)
    root = tempfile.mkdtemp(prefix="held.",
                            dir=os.path.join(ROOT, ".bench_work"))
    try:
        make_root(root, args.workload, args.trace_boundaries)
        line = harness.run_cell(root, args.workload, args.seed, 0.0, True,
                                T_PROCESS_START)
    except peaks.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    finally:
        shutil.rmtree(root, ignore_errors=True)
    sys.stderr.flush()
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

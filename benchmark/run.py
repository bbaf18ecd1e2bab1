"""The benchmark's command:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine and
prints, as the last line of standard output, one JSON object: whether
what the timed path produced is correct, the cell's end-to-end metrics
(``--trace 0``) or its per-layer metrics (``--trace 1``), and the device.
Everything else goes to standard error.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "dml_cnn_cifar10_tpu")):
        print("benchmark: the system under test (dml_cnn_cifar10_tpu/) is "
              "not in this directory", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    from benchmark.lib import harness, peaks
    try:
        line = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                                bool(args.trace), T_PROCESS_START)
    except peaks.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    sys.stderr.flush()
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

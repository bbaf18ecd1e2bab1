"""The control's and the planted faults' readings of ``calibrate.py`` for a
cell whose state is gigabytes, one reading at a time and without the
program: the reference in the numerics the configuration states, then the
reference one precision down and with each fault planted, each put in the
program's place and compared by ``check.compare``.

    python3 benchmark/tools/calibrate_controls.py --workload <cell> \\
        --seeds 1,2,3 [--controls bfloat16,float8] \\
        [--faults half_batch,no_exchange] [--out file.jsonl]

``calibrate.py`` holds the program's first dispatch, the reference's
result and a control's side by side on the host while ``check.compare``
makes float64 copies of three trees; at half a billion parameters under
AdamW that passed the 40 GiB of a one-chip machine. Here the sound runs'
readings come from ``benchmark/run.py`` itself (``readings`` in its line:
the same ``check.compare``), and this tool needs no program: the shapes
of the weights are the configuration module's ``param_shapes(spec)``.

Prints one JSON line per reading, in ``calibrate.py``'s form.
"""

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--out", default=None)
    ap.add_argument("--root", default=ROOT,
                    help="directory of BENCHMARK.json (tests)")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark.lib import cells, check, driver, harness, peaks

    cell = cells.load_cell(args.root, args.workload)
    devices = peaks.require_chips(cell.chips)
    like = cell.reference.param_shapes(cell.config)
    task, hyper = harness.task_of(cell), harness.hyper_of(cell)
    stated = cell.config["reference_numerics"]
    out = open(args.out, "a") if args.out else None

    def emit(seed, what, numbers, seconds):
        line = json.dumps({"cell": cell.name, "reference": stated,
                           "seed": seed, "what": what, "numbers": numbers,
                           "seconds": round(seconds, 2)})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    variants = [("control_" + c, task, c)
                for c in args.controls.split(",") if c]
    variants += [("fault_" + f, task.fault(f), None)
                 for f in args.faults.split(",") if f]
    for seed in [int(s) for s in args.seeds.split(",")]:
        os.makedirs(os.path.join(args.root, ".bench_work"), exist_ok=True)
        work = tempfile.mkdtemp(prefix="controls.",
                                dir=os.path.join(args.root, ".bench_work"))
        try:
            flags = harness.program_flags(cell, work)
            records = harness.write_records(cell, task, seed, flags)
            t = time.perf_counter()
            p0, s0, ref = harness.reference_chunk(
                cell, task, hyper, seed, devices, like, records)
            emit(seed, "reference_losses",
                 {"first": float(ref.losses[0]),
                  "last": float(ref.losses[-1])}, time.perf_counter() - t)
            for what, variant, numerics in variants:
                t = time.perf_counter()
                _, _, c = harness.reference_chunk(
                    cell, variant, hyper, seed, devices, like, records,
                    numerics=numerics)
                numbers = check.compare(driver.in_the_programs_place(c),
                                        p0, s0, ref)
                del c
                gc.collect()
                emit(seed, what, numbers, time.perf_counter() - t)
            del p0, s0, ref
            gc.collect()
        finally:
            shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

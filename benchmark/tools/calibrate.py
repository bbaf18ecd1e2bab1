"""Reads, for one cell, the numbers that ``correct`` compares: what sound
runs of the program give over seeds (the lower reading of each limit),
what the control gives (the reference put in the program's place, one
precision below what the configuration states), and what each planted
fault gives. No measured window: a training cell's readings need none.
One process for all seeds, so everything compiles once.

    python3 benchmark/tools/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--control bfloat16|float8] [--faults half_batch,no_exchange] \\
        [--program_control compute_dtype=bfloat16] [--reference float32] \\
        [--set learning_rate=0.01] [--out file.jsonl]

``--program_control`` runs the program itself with a lower-precision
path of its own switched on and reads it against the same reference.
``--reference`` reads everything against another numerics than the one
the configuration states, and ``--set`` overrides flags of the cell:
both are for choosing what a configuration should state, never for a
limit of what it does state.

Prints one JSON line per reading: {"seed", "what", "numbers", "seconds"}.
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
    ap.add_argument("--control", default=None)
    ap.add_argument("--faults", default="",
                    help="as the configuration's task plants them")
    ap.add_argument("--program_control", default=None)
    ap.add_argument("--reference", default=None)
    ap.add_argument("--set", default="", dest="overrides")
    ap.add_argument("--out", default=None)
    ap.add_argument("--root", default=ROOT,
                    help="directory of BENCHMARK.json (tests)")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark.lib import cells, check, driver, harness, peaks

    def parse(pairs):
        return {k: json.loads(v) if v[:1] in "-0123456789tf[{\"" else v
                for k, v in (kv.split("=", 1)
                             for kv in pairs.split(",") if kv)}

    overrides = parse(args.overrides)
    cell = cells.load_cell(args.root, args.workload)
    devices = peaks.require_chips(cell.chips)
    out = open(args.out, "a") if args.out else None

    def emit(seed, what, numbers, seconds):
        line = json.dumps({"cell": cell.name, "set": overrides,
                           "reference": args.reference
                           or cell.config["reference_numerics"],
                           "seed": seed, "what": what, "numbers": numbers,
                           "seconds": round(seconds, 2)})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for seed in [int(s) for s in args.seeds.split(",")]:
        os.makedirs(os.path.join(args.root, ".bench_work"), exist_ok=True)
        work = tempfile.mkdtemp(prefix="calibrate.",
                                dir=os.path.join(args.root, ".bench_work"))
        try:
            flags = harness.program_flags(cell, work, overrides)
            task = harness.task_of(cell, overrides)
            records = harness.write_records(cell, task, seed, flags)
            hyper = harness.hyper_of(cell, overrides)

            def run_program(flags):
                t = time.perf_counter()
                program = driver.start_program(
                    flags, devices,
                    lambda a, sh: harness.make_params(cell, seed, a, sh))
                first = program.first
                del program
                gc.collect()
                return first, time.perf_counter() - t

            first, t_program = run_program(flags)
            like = first.params
            t = time.perf_counter()
            p0, s0, ref = harness.reference_chunk(
                cell, task, hyper, seed, devices, like, records,
                numerics=args.reference)
            t_ref = time.perf_counter() - t
            emit(seed, "reference_losses",
                 {"first": float(ref.losses[0]),
                  "last": float(ref.losses[-1])}, t_ref)
            emit(seed, "program", check.compare(first, p0, s0, ref),
                 t_program)
            if args.program_control:
                # a log directory of its own: the first program left a
                # checkpoint in the other, and this one must not resume
                low, t_low = run_program(
                    {**flags, **parse(args.program_control),
                     "log_dir": flags["log_dir"] + "_control"})
                emit(seed, "program_" + args.program_control,
                     check.compare(low, p0, s0, ref), t_low)
            if args.control:
                t = time.perf_counter()
                _, _, c = harness.reference_chunk(
                    cell, task, hyper, seed, devices, like, records,
                    numerics=args.control)
                emit(seed, "control_" + args.control,
                     check.compare(driver.in_the_programs_place(c), p0, s0,
                                   ref),
                     time.perf_counter() - t)
            for fault in [f for f in args.faults.split(",") if f]:
                t = time.perf_counter()
                _, _, c = harness.reference_chunk(
                    cell, task.fault(fault), hyper, seed, devices, like,
                    records)
                emit(seed, "fault_" + fault,
                     check.compare(driver.in_the_programs_place(c), p0, s0,
                                   ref),
                     time.perf_counter() - t)
        finally:
            shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""One run of one cell: set-up, window, then ``correct``, metrics, line."""

from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Optional

import numpy as np

from benchmark.lib import cells, check, datagen, driver, peaks, reference
from benchmark.lib import validate, xplane


def cell_flags(cell: cells.Cell, overrides: Optional[dict] = None) -> dict:
    """The program's flags as the cell's two files state them.
    ``overrides`` is for the reading of limits only."""
    return {**cell.config["flags"], **cell.traffic["flags"],
            **(overrides or {})}


def program_flags(cell: cells.Cell, work: str,
                  overrides: Optional[dict] = None) -> dict:
    flags = cell_flags(cell, overrides)
    k, batch = flags["steps_per_dispatch"], flags["batch_size"]
    # as many dispatches as the index stream's 32-bit positions allow, and
    # more than any window holds
    total = min(k * 5000, (2 ** 32 - 1) // batch // k * k)
    # (The program's own --seed, for shuffle order and crop draws, is the
    # cell's: it is baked into the compiled step, so a new one would compile
    # anew in every run. The run's --seed makes the records and the weights.)
    # A drained metrics boundary every ``output_every`` steps: the
    # dispatches between two boundaries are enqueued ahead, so a host that
    # is held up for less than they last does not idle the device
    flags.setdefault("output_every", k)
    if flags["output_every"] % k:
        raise ValueError("output_every has to be a multiple of "
                         "steps_per_dispatch")
    flags.update(data_dir=os.path.join(work, "data"),
                 log_dir=os.path.join(work, "logs"), total_steps=total,
                 eval_every=2 * total, checkpoint_every=2 * total)
    return flags


def hyper_of(cell: cells.Cell, overrides: Optional[dict] = None
             ) -> reference.Hyper:
    """What the reference of any task has to know of the stream and the
    dispatch, from the cell's flags."""
    flags = cell_flags(cell, overrides)
    return reference.Hyper(
        seed=flags["seed"], batch=flags["batch_size"],
        steps=flags["steps_per_dispatch"],
        records=flags["synthetic_train_records"])


def task_of(cell: cells.Cell, overrides: Optional[dict] = None
            ) -> reference.Task:
    """The records, the feed, the loss and the update as the
    configuration's module states them (``task(spec, flags)``), or those of
    an image classifier under SGD, read from the configuration's file."""
    flags = cell_flags(cell, overrides)
    stated = getattr(cell.reference, "task", None)
    if stated is not None:
        return stated(cell.config, flags)
    return reference.image_task(
        reference.image_hyper(cell.config, flags),
        cell.reference.make_forward(cell.config))


def make_params(cell: cells.Cell, seed: int, abstract, sharding=None):
    """The starting weights, with the fan-ins the configuration states."""
    return datagen.make_params(seed, abstract, sharding,
                               getattr(cell.reference, "fan_in", None))


def write_records(cell: cells.Cell, task: reference.Task, seed: int,
                  flags: dict):
    """The task's records, where the program will look for them; gives
    back the training split."""
    from dml_cnn_cifar10_tpu.data import download
    data_cfg = driver.build_train_config(flags).data
    return task.write_records(
        seed, flags["synthetic_train_records"],
        {"train": download.train_files(data_cfg),
         "test": download.test_files(data_cfg)})


def reference_chunk(cell: cells.Cell, task: reference.Task,
                    hyper: reference.Hyper, seed: int, devices, like_params,
                    records, numerics: Optional[str] = None):
    """The reference's K steps from the seed's weights, on ``devices``,
    in the numerics the configuration states (or a control's)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devices), ("batch",))
    repl = NamedSharding(mesh, P())

    def batch_sharding(ndim):
        return NamedSharding(mesh, P("batch", *[None] * (ndim - 1)))

    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), like_params)
    params = make_params(cell, seed, abstract, repl)
    state = jax.device_put(cell.reference.init_model_state(params), repl)
    result = reference.run_chunk(
        task, hyper, params, state, jax.device_put(records, repl),
        numerics=numerics or cell.config["reference_numerics"],
        batch_sharding=batch_sharding if len(devices) > 1 else None)
    return jax.device_get(params), jax.device_get(state), \
        jax.device_get(result)


def run_cell(root: str, workload: str, seed: int, seconds: float,
             traced: bool, t_process_start: float, fault=None) -> str:
    clock = {"start": t_process_start}

    def lap(name):
        clock[name] = time.perf_counter()

    cell = cells.load_cell(root, workload)
    devices = peaks.require_chips(cell.chips)
    peak = peaks.PEAKS[devices[0].device_kind]
    os.makedirs(os.path.join(root, ".bench_work"), exist_ok=True)
    work = tempfile.mkdtemp(prefix=workload + ".",
                            dir=os.path.join(root, ".bench_work"))
    try:
        flags = program_flags(cell, work)
        task, hyper = task_of(cell), hyper_of(cell)
        lap("imports")
        records = write_records(cell, task, seed, flags)
        lap("records")
        program = driver.start_program(
            flags, devices,
            lambda abstract, sharding: make_params(cell, seed, abstract,
                                                   sharding),
            telemetry=traced, fault=fault)
        lap("first_dispatch")
        w = driver.measure_window(
            program, seconds,
            trace_dir=os.path.join(work, "trace") if traced else None,
            trace_boundaries=cell.traffic["trace_boundaries"])
        lap("window_closed")
        memory_peak = driver.memory_peak_bytes(devices)
        first = program.first
        del program
        gc.collect()   # the program's state is freed before the reference
        start_params, start_state, ref = reference_chunk(
            cell, task, hyper, seed, devices, first.params, records)
        numbers = check.compare(first, start_params, start_state, ref)
        lap("reference")
        trace = xplane.load(w.trace_dir, driver.TRACE_MARKER) \
            if traced else None
        lap("trace_read")
    finally:
        shutil.rmtree(work, ignore_errors=True)

    correct, compared = check.verdict(numbers, cell.limits)
    steps = w.step1 - w.step0
    batch = hyper.batch
    losses = [b.loss for b in w.boundaries[1:]]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    out = {"correct": correct, "attempted": steps,
           "failed": hyper.steps * sum(1 for x in losses
                                       if not np.isfinite(x))}
    # one example is one row of the batch: an image, or a sequence of the
    # traffic's length; the older two names are what the readers know
    examples = steps * batch
    flops = cell.reference.train_flops_per_image(cell.config)
    # ``config`` is the cell's configuration's file as it was run: a
    # reader takes its sizes from there, never from a file it names
    ctx = {"window_s": w.t1 - w.t0, "steps": steps, "examples": examples,
           "images": examples, "chips": cell.chips, "peak": peak,
           "spans": w.spans, "trace": trace,
           "setup_s": w.t0 - t_process_start, "flops_per_example": flops,
           "flops_per_image": flops, "config": cell.config}
    wanted = cell.per_layer if traced else cell.end_to_end
    metrics, silent = {}, []
    for m in wanted:
        value = cells.load_reader(cell, m["name"])(ctx)
        if value is None:
            # only a per-layer reader may find nothing to read
            silent.append(m["name"])
        else:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    out.update(metrics=metrics, device=device)
    if traced:
        device["busy_s"] = trace.busy_s()
        device["window_s"] = w.t1 - w.t0
        out["breakdown"] = xplane.breakdown(trace, w)
    # every number read, then, last, those that are held to a limit
    out["readings"] = {k: float(v) for k, v in numbers.items()
                       if np.isfinite(v)}
    out["compared"] = compared
    line = json.dumps(out, allow_nan=False)
    faults = validate.problems(line, wanted, cell.chips, traced,
                               silent if traced else ())
    if faults:
        raise RuntimeError("the result line is not sound: "
                           + "; ".join(faults) + "\n" + line)
    # where a run's seconds went: imports and the look for the chip, the
    # records, the program's start with its first dispatch (compile or
    # cache), the second fit up to the window's opening, the window, the
    # reference with the comparison, the reading of the trace
    marks = [("imports", "start"), ("records", "imports"),
             ("first_dispatch", "records"), ("window_open", "first_dispatch"),
             ("window", "window_open"), ("reference", "window_closed"),
             ("trace_read", "reference")]
    clock["window_open"], clock["window"] = w.t0, w.t1
    print("seconds " + " ".join(
        f"{name}={clock[name] - clock[since]:.2f}" for name, since in marks),
        file=sys.stderr)
    gaps = np.diff([b.t for b in w.boundaries])
    print(f"boundaries n={len(gaps)} median_s={np.median(gaps):.4f} "
          f"max_s={gaps.max():.4f} steps_each="
          f"{w.boundaries[1].step - w.boundaries[0].step}", file=sys.stderr)
    for name, (value, limit) in compared.items():
        print(f"compared {name} {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    return line

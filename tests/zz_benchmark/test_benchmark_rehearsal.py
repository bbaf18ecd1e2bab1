"""The harness end to end on the CPU at a tiny size: traced and untraced,
on one and on four virtual devices, through ``Trainer.fit`` and the plain
reference. Only the look for a chip is stubbed (and, traced, the trace's
device planes, which a CPU does not write). Then the same run with the
timed path broken underneath, once for each fault a training cell can
have, and the reference put in the program's place one precision down:
``correct`` has to come out false."""

import json
import os
import shutil
import time

import jax
import jax.numpy as jnp
import pytest

import bench_roots

from benchmark.lib import cells, check, driver, harness, peaks, validate
from benchmark.lib import xplane
from dml_cnn_cifar10_tpu.parallel import step as step_lib

# Out of the tier-1 smoke pass: the program's multi-process simulations
# (tests/test_peerstore.py, tests/test_chaos.py) depend on timing and fail
# when the machine is busy; a minute and a half of training on eight
# virtual devices beside them is load they do not need. Run with
# ``pytest -m slow tests/zz_benchmark``.
pytestmark = pytest.mark.slow

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2 ** 31 + 11          # the driver's seeds pass 32 signed bits
LIMITS = {"dparam": 1e-3, "ddiff_mid": 1e-3, "dparam_mid": 1e-3}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A copy of the benchmark with two tiny cells of the paper's CNN."""
    root = str(tmp_path_factory.mktemp("bench_root"))
    bench = bench_roots.benchmark_with_pending()
    bench["workloads"] = [
        {"name": "tiny1", "config": "cnn_cifar10", "traffic": "tiny",
         "chips": 1, "why": "rehearsal"},
        {"name": "tiny4", "config": "cnn_cifar10", "traffic": "tiny4",
         "chips": 4, "why": "rehearsal"}]
    bench["configs"] = [c for c in bench["configs"]
                        if c["name"] == "cnn_cifar10"]
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny4"] if m["name"].startswith(
                "collective") else ["tiny1", "tiny4"]
    bench_roots.make_root(root, bench)
    # on the CPU the program's float32 products are float32: the
    # reference of the rehearsal is, too
    cfg_path = os.path.join(root, "benchmark", "configs", "cnn_cifar10.json")
    with open(cfg_path) as f:
        config = json.load(f)
    config["reference_numerics"] = "float32"
    with open(cfg_path, "w") as f:
        json.dump(config, f)
    traffic = {"kind": "training", "trace_boundaries": 2,
               "flags": {"seed": 1, "batch_size": 16,
                         "steps_per_dispatch": 2, "output_every": 4,
                         "resident_data": True,
                         "device_index_stream": True,
                         "synthetic_train_records": 320}}
    for name in ("tiny", "tiny4"):
        with open(os.path.join(root, "benchmark", "traffic",
                               name + ".json"), "w") as f:
            json.dump(traffic, f)
    for name in ("tiny1", "tiny4"):
        with open(os.path.join(root, "benchmark", "limits",
                               name + ".json"), "w") as f:
            json.dump({"limits": LIMITS}, f)
    return root


@pytest.fixture
def no_chip_needed(monkeypatch, request):
    """The chip check, stubbed here and nowhere else. So is the profiler,
    except in the one case that checks that it writes where the reducer
    looks: a real trace on the CPU loads the whole machine for its
    length."""
    real_profiler = "real_profiler" in request.node.name
    if not real_profiler:
        monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(peaks, "require_chips",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(driver, "memory_peak_bytes", lambda devices: 1 << 20)

    def recorded_planes(trace_dir, marker=None):
        if real_profiler:
            assert xplane.find_xplane(trace_dir)    # the profiler did write
        n = len(jax.devices())
        return xplane.Trace([xplane.DevicePlane(f"/device:TPU:{i}", [
            xplane.Op(0, 4e6, "while.1", "%while.1 = while()", "XLA Ops"),
            xplane.Op(1e6, 2e6, "fusion.1",
                      "%fusion.1 op_name=\"jit(chunk)/fwd_bwd/conv\"",
                      "XLA Ops"),
            xplane.Op(2e6, 3e6, "custom-call.1",
                      "%custom-call.1 op_name=\"jit(chunk)/optimizer/sgd\"",
                      "XLA Ops"),
            xplane.Op(2.5e6, 3.5e6, "all-reduce-start.1",
                      "%all-reduce-start.1", "Async XLA Ops"),
        ]) for i in range(min(n, 4))], None)
    monkeypatch.setattr(xplane, "load", recorded_planes)


def _metrics_of(root, workload, traced):
    cell = cells.load_cell(root, workload)
    return cell, (cell.per_layer if traced else cell.end_to_end)


@pytest.mark.parametrize("workload,traced", [
    ("tiny1", False), ("tiny1", True), ("tiny4", False), ("tiny4", True),
    pytest.param("tiny4", True, id="tiny4-True-real_profiler")])
def test_a_run_prints_a_sound_line_and_is_correct(
        tiny_root, no_chip_needed, workload, traced, capfd):
    line = harness.run_cell(tiny_root, workload, SEED, 0.5, traced,
                            time.perf_counter())
    cell, wanted = _metrics_of(tiny_root, workload, traced)
    assert validate.problems(line, wanted, cell.chips, traced) == []
    result = json.loads(line)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 4
    assert set(result["metrics"]) == {m["name"] for m in wanted}
    assert list(result)[-1] == "compared"
    assert set(result["compared"]) == set(LIMITS)
    if traced:
        assert ("collective.exposed_pct" in result["metrics"]) \
            == (workload == "tiny4")
        assert result["breakdown"]["device_ops"][0][0] == "while.1"
    # nothing of the program's own printing reaches standard output, and
    # the numbers compared close standard error
    out, err = capfd.readouterr()
    assert out == ""
    assert err.rstrip().splitlines()[-1].startswith("compared dparam_mid")
    assert not os.listdir(os.path.join(tiny_root, ".bench_work"))


def _state_unchanged(monkeypatch):
    real = step_lib.make_train_chunk_resident

    def make(*args, **kwargs):
        chunk = real(*args, **kwargs)

        def stuck(state):
            kept = jax.tree.map(jnp.copy, state)
            new, metrics = chunk(state)
            return kept._replace(opt={**kept.opt,
                                      "step": new.opt["step"]}), metrics
        return stuck
    monkeypatch.setattr(step_lib, "make_train_chunk_resident", make)


def _rows_left_out(monkeypatch, share):
    """The loss and its gradient see the first ``1/share`` of the batch
    only: half of the batch left out, or one chip's quarter with nothing
    exchanged."""
    real = step_lib._forward_loss

    def make(*args, **kwargs):
        loss_fn = real(*args, **kwargs)

        def partial(params, model_state, images, labels):
            n = images.shape[0] // share
            loss, (_, new_state, stats) = loss_fn(
                params, model_state, images[:n], labels[:n])
            # the step's accuracy still wants logits of every row
            _, (logits, _, _) = loss_fn(params, model_state, images, labels)
            return loss, (logits, new_state, stats)
        return partial
    monkeypatch.setattr(step_lib, "_forward_loss", make)


@pytest.mark.parametrize("fault,workload", [
    ("state_unchanged", "tiny1"), ("half_batch", "tiny1"),
    ("no_exchange", "tiny4")])
def test_a_broken_timed_path_comes_out_not_correct(
        tiny_root, no_chip_needed, monkeypatch, fault, workload):
    if fault == "state_unchanged":
        _state_unchanged(monkeypatch)
    else:
        _rows_left_out(monkeypatch, 2 if fault == "half_batch" else 4)
    result = json.loads(harness.run_cell(tiny_root, workload, SEED, 0.5,
                                         False, time.perf_counter()))
    assert result["correct"] is False
    failed = {k for k, (v, lim) in result["compared"].items()
              if v is None or v > lim}
    assert failed
    if fault == "state_unchanged":
        # nothing moved: the gap of norms is the whole norm
        assert result["compared"]["dparam"][0] == pytest.approx(1.0)


@pytest.mark.parametrize("numerics", ["bfloat16", "float8"])
def test_the_control_comes_out_not_correct(tiny_root, no_chip_needed,
                                           numerics, tmp_path):
    """The reference in the program's place, one precision (and two)
    below float32, at the test's size."""
    cell = cells.load_cell(tiny_root, "tiny1")
    flags = harness.program_flags(cell, str(tmp_path))
    task, hyper = harness.task_of(cell), harness.hyper_of(cell)
    records = harness.write_records(cell, task, SEED, flags)
    like = jax.eval_shape(
        lambda: {n: {"kernel": jnp.zeros(s), "bias": jnp.zeros(s[-1])}
                 for n, s in (("conv1", (5, 5, 3, 64)),
                              ("conv2", (5, 5, 64, 64)),
                              ("full1", (2304, 384)), ("full2", (384, 192)),
                              ("full3", (192, 10)))})
    devices = jax.devices()[:1]
    p0, s0, ref = harness.reference_chunk(cell, task, hyper, SEED, devices,
                                          like, records)
    _, _, low = harness.reference_chunk(cell, task, hyper, SEED, devices,
                                        like, records, numerics=numerics)
    as_program = driver.in_the_programs_place(low)
    same = driver.in_the_programs_place(ref)
    assert check.verdict(check.compare(same, p0, s0, ref), LIMITS)[0]
    correct, compared = check.verdict(
        check.compare(as_program, p0, s0, ref), LIMITS)
    assert correct is False, compared


def test_the_tool_that_reads_limits_runs_on_the_seam(tiny_root,
                                                     no_chip_needed, capfd):
    """``benchmark/tools/calibrate.py`` at the test's size: the program's
    sound run, the control and both faults as the task plants them."""
    from benchmark.tools import calibrate
    assert calibrate.main([
        "--workload", "tiny1", "--seeds", str(SEED), "--control", "bfloat16",
        "--faults", "half_batch,no_exchange", "--root", tiny_root]) == 0
    out, _ = capfd.readouterr()
    read = {r["what"]: r["numbers"] for r in map(json.loads,
                                                 out.strip().splitlines())}
    assert set(read) == {"reference_losses", "program", "control_bfloat16",
                         "fault_half_batch", "fault_no_exchange"}
    assert check.verdict(read["program"], LIMITS)[0]
    for what in ("control_bfloat16", "fault_half_batch",
                 "fault_no_exchange"):
        assert not check.verdict(read[what], LIMITS)[0], (what, read[what])

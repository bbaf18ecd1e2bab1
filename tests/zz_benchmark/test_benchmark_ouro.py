"""The looped decoder's configuration (``benchmark/configs/ouro_2p6b_l6``)
through the harness at a size a CPU test holds: the configuration's own
module, the program's own ``Trainer`` on resident token rows, and the
comparison that decides ``correct``. And what the module counts, at the
published widths, from shapes alone."""

import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import cells, check, driver, harness, kernel_costs

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2 ** 31 + 28          # the driver's seeds pass 32 signed bits
CONFIG = os.path.join(ROOT, "benchmark", "configs", "ouro_2p6b_l6")
SMALL = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 128,
         "num_hidden_layers": 2, "vocab_size": 96, "sequence_length": 32}


def published() -> dict:
    with open(CONFIG + ".json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def small_cell(tmp_path_factory):
    """The configuration's module beside a file of small sizes, in a root
    of its own, as the harness finds a cell; float32 on both sides, as a
    CPU computes."""
    root = str(tmp_path_factory.mktemp("ouro") / "root")
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(root, "bench", sub))
    shutil.copy(CONFIG + ".py",
                os.path.join(root, "bench", "configs", "small.py"))
    config = {**published(), **SMALL, "reference_numerics": "float32",
              "reference_loss_blocks": 2}
    config["flags"] = {**config["flags"], "compute_dtype": "float32",
                       "synthetic_train_records": 32,
                       "model_config_file": os.path.join(
                           root, "bench", "configs", "small.json")}
    traffic = {"kind": "training", "trace_boundaries": 1,
               "flags": {"batch_size": 2, "sequence_length": 32,
                         "steps_per_dispatch": 2, "resident_data": True,
                         "device_index_stream": True, "seed": 1,
                         "output_every": 2}}
    # round-off: the program sums a product's terms in another order than
    # the reference (a scan over passes, a loss in blocks); float32 on both
    # sides, Adam's normalised step at the start of a warm-up
    limits = {"limits": {"loss": 1e-5, "dparam": 1e-3, "ddiff_mid": 1e-3,
                         "mu_diff": 1e-4, "nu_diff": 1e-4}}
    for sub, name, body in (("configs", "small", config),
                            ("traffic", "tiny", traffic),
                            ("limits", "small_b2", limits)):
        with open(os.path.join(root, "bench", sub, name + ".json"),
                  "w") as f:
            json.dump(body, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump({"configs": [{"name": "small",
                                "file": "bench/configs/small.json"}],
                   "workloads": [{"name": "small_b2", "config": "small",
                                  "traffic": "tiny", "chips": 1}],
                   "end_to_end": [], "per_layer": []}, f)
    return cells.load_cell(root, "small_b2"), os.path.join(root, "work")


def test_k_steps_of_the_trainer_equal_the_references(small_cell):
    """K = 2 steps of ``Trainer.fit`` on resident token rows against the
    reference's ``run_chunk``, through the harness's own functions:
    parameters, ``mu``, ``nu`` and the loss."""
    cell, work = small_cell
    devices = jax.devices()[:1]
    flags = harness.program_flags(cell, work)
    task, hyper = harness.task_of(cell), harness.hyper_of(cell)
    records = harness.write_records(cell, task, SEED, flags)
    assert records.shape == (32, 33) and records.dtype == np.int32
    program = driver.start_program(
        flags, devices,
        lambda abstract, sharding: harness.make_params(cell, SEED, abstract,
                                                       sharding))
    first = program.first
    assert first.loss is not None and set(first.opt) == {"mu", "nu"}
    p0, s0, ref = harness.reference_chunk(cell, task, hyper, SEED, devices,
                                          first.params, records)
    numbers = check.compare(first, p0, s0, ref)
    correct, compared = check.verdict(numbers, cell.limits)
    assert correct, compared
    # and each planted fault is seen
    for fault in ("half_batch", "no_exchange"):
        _, _, broken = harness.reference_chunk(
            cell, task.fault(fault), hyper, SEED, devices, first.params,
            records)
        bad = check.compare(driver.in_the_programs_place(broken), p0, s0,
                            ref)
        assert not check.verdict(bad, cell.limits)[0], fault


def test_the_module_counts_the_published_model():
    ref = cells.load_module(CONFIG + ".py")
    spec = published()
    assert ref.param_count(spec) == spec["parameters"] == 509_661_185
    whole = {**spec, "num_hidden_layers":
             spec["published"]["num_hidden_layers"]}
    assert ref.param_count(whole) == spec["published"]["parameters"] \
        == 2_667_974_657
    # every key of the catalog's row that shapes the model, as published
    for key, value in {"hidden_size": 2048, "num_attention_heads": 16,
                       "num_key_value_heads": 16, "head_dim": 128,
                       "intermediate_size": 5632, "vocab_size": 49152,
                       "rope_theta": 1000000, "rms_norm_eps": 1e-6,
                       "total_ut_steps": 4, "early_exit_threshold": 1,
                       "max_position_embeddings": 65536,
                       "max_window_layers": 48}.items():
        assert spec[key] == value, key
    assert spec["reduced"] == ["num_hidden_layers"]
    assert spec["tie_word_embeddings"] is False
    assert spec["layer_types"] == ["full_attention"] * 48


def test_operations_a_sequence_by_hand():
    """11.0 GFLOP a token at the cell's sizes: 24 layer applications of
    51.38M multiply-adds, 4 heads of 100.66M, and the half square of
    attention, times 6."""
    ref = cells.load_module(CONFIG + ".py")
    spec = published()
    s = spec["sequence_length"]
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    attention = 2 * 2048 * (s * (s + 1) // 2)
    by_hand = 6 * 4 * (s * (6 * layer + 2048 * 49152) + 6 * attention)
    assert ref.train_flops_per_image(spec) == by_hand
    assert 10.9e9 < by_hand / s < 11.1e9


@pytest.mark.parametrize("s", [128, 4096])
def test_a_causal_kernels_operations_are_the_half_square(s):
    """What the flash kernels' roofline shares divide by: two products of
    ``head_dim`` for each pair of a query and a key it may see, ``S (S +
    1) / 2`` pairs a head, and two and a half times that backward;
    whatever a kernel multiplies on the masked side of the diagonal is
    not in it."""
    b, h, d = 2, 16, 128
    pairs = b * h * s * (s + 1) // 2
    fwd = kernel_costs.flash_fwd(b, s, h, d)
    assert fwd["flops"] == 2 * 2 * d * pairs < 4 * d * b * h * s * s
    bwd = kernel_costs.flash_bwd(b, s, h, d)
    assert bwd["flops"] == 5 * 2 * d * pairs
    # q, k, v, o in bfloat16 and a float32 row statistic
    assert fwd["bytes"] == b * h * s * (4 * d * 2 + 4)
    assert bwd["bytes"] == b * h * s * (8 * d * 2 + 8)
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert kernel_costs.least_seconds(fwd, peak) == max(
        fwd["flops"] / 197e12, fwd["bytes"] / 819e9)


def test_a_kernels_share_is_read_from_its_own_events():
    """Two launches named ``flash_fwd.<n>`` of 2 ms each, shapes in the
    event's text; a trace without such a name gives nothing."""
    from benchmark.lib import xplane
    text = "%flash_fwd.1 = (bf16[32,4096,128]{2,1,0}, f32[32,4096,128]) " \
        "custom-call(...)"
    ops = [xplane.Op(0, 2e6, "flash_fwd.1", text, "XLA Ops"),
           xplane.Op(3e6, 5e6, "flash_fwd.2", text, "XLA Ops"),
           xplane.Op(5e6, 6e6, "fusion.1", "%fusion.1", "XLA Ops")]
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"trace": xplane.Trace([xplane.DevicePlane("/device:TPU:0", ops)]),
           "peak": peak}
    least = kernel_costs.least_seconds(
        kernel_costs.flash_fwd(32, 4096, 1, 128), peak)
    assert kernel_costs.kernel_roofline_pct(
        ctx, "flash_fwd", kernel_costs.flash_fwd) \
        == pytest.approx(100 * least / 2e-3)
    assert kernel_costs.kernel_roofline_pct(
        ctx, "flash_bwd_dq|flash_bwd_dkv", kernel_costs.flash_bwd, 2) is None
    for name in ("flash_fwd_roofline", "flash_bwd_roofline"):
        read = cells.load_module(os.path.join(
            ROOT, "benchmark", "metrics", name + ".py")).read
        assert (read(ctx) is None) == (name == "flash_bwd_roofline")


def test_the_controls_tool_reads_a_control_and_a_fault(small_cell,
                                                       monkeypatch, capsys):
    """``calibrate_controls.py`` at the small size: one line a reading, the
    control one precision down and the fault both past a limit that the
    reference against itself meets."""
    from benchmark.lib import peaks
    from benchmark.tools import calibrate_controls
    cell, _ = small_cell
    root = os.path.dirname(cell.bench_dir)
    monkeypatch.setattr(peaks, "require_chips",
                        lambda chips: jax.devices()[:chips])
    assert calibrate_controls.main(
        ["--workload", "small_b2", "--seeds", str(SEED), "--root", root,
         "--controls", "bfloat16", "--faults", "half_batch"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert [x["what"] for x in lines] == ["reference_losses",
                                          "control_bfloat16",
                                          "fault_half_batch"]
    for x in lines[1:]:
        assert not check.verdict(x["numbers"], cell.limits)[0], x["what"]

"""The configuration ``mellum2_12b_a2p5b_l4_e8`` through the harness at a
size a CPU test holds: the configuration's own module, the program's own
``Trainer`` on resident token rows, and the comparison that decides
``correct``, with the faults of this model's own. And what the module and
the window's cost file count, from shapes alone."""

import json
import os
import shutil

import jax
import pytest

from benchmark.lib import (cells, check, driver, harness, kernel_costs,
                           window_costs, xplane)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2 ** 31 + 34          # the driver's seeds pass 32 signed bits
CONFIG = os.path.join(ROOT, "benchmark", "configs", "mellum2_12b_a2p5b_l4_e8")
CONFIG_NAME = "mellum2_12b_a2p5b_l4_e8"
CELL = "mellum2_l4_e8_s8192_resident"
# the entries that list this cell, in their order
OWN = ["model.window_attention_device_ms", "flash_window_fwd_roofline",
       "flash_window_bwd_roofline"]
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
SMALL = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
         "moe_intermediate_size": 32, "num_hidden_layers": 4,
         "num_experts": 4, "router_num_experts": 8, "expert_first_id": 0,
         "num_experts_per_tok": 2, "vocab_size": 96, "sliding_window": 12,
         "sequence_length": 40}


def published() -> dict:
    with open(CONFIG + ".json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def small_cell(tmp_path_factory):
    """The configuration's module beside a file of small sizes (the
    published list of layers, both rotary rules as published, a window
    shorter than the sequence), in a root of its own, as the harness finds
    a cell; float32 on both sides, as a CPU computes."""
    root = str(tmp_path_factory.mktemp("mellum2") / "root")
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
               "flags": {"batch_size": 2, "sequence_length": 40,
                         "steps_per_dispatch": 2, "resident_data": True,
                         "device_index_stream": True, "seed": 1,
                         "output_every": 2}}
    # round-off: the program sums a product's terms in another order than
    # the reference (grouped products over sorted rows, a loss in blocks);
    # float32 on both sides, Adam's normalised step at the start of a
    # warm-up
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
    parameters (the head a leaf of its own), ``mu``, ``nu`` and the loss;
    the model holds no state on either side. Then the harness's two faults
    and this model's three: full attention in the window layers, the plain
    rotary rule in the full layer, and the weights held answering to the
    wrong ids."""
    cell, work = small_cell
    devices = jax.devices()[:1]
    flags = harness.program_flags(cell, work)
    task, hyper = harness.task_of(cell), harness.hyper_of(cell)
    records = harness.write_records(cell, task, SEED, flags)
    assert records.shape == (32, 41) and records.max() < 96
    program = driver.start_program(
        flags, devices,
        lambda abstract, sharding: harness.make_params(cell, SEED, abstract,
                                                       sharding))
    first = program.first
    assert first.loss is not None and set(first.opt) == {"mu", "nu"}
    assert "head" in first.params and not jax.tree.leaves(first.model_state)
    p0, s0, ref = harness.reference_chunk(cell, task, hyper, SEED, devices,
                                          first.params, records)
    numbers = check.compare(first, p0, s0, ref)
    correct, compared = check.verdict(numbers, cell.limits)
    assert correct, compared
    for fault in ("half_batch", "no_exchange", "no_window", "one_rope",
                  "wrong_experts"):
        _, _, broken = harness.reference_chunk(
            cell, task.fault(fault), hyper, SEED, devices, first.params,
            records)
        bad = check.compare(driver.in_the_programs_place(broken), p0, s0,
                            ref)
        assert not check.verdict(bad, cell.limits)[0], fault
    with pytest.raises(ValueError, match="unknown fault"):
        task.fault("no_such_fault")


def test_the_module_counts_the_published_model():
    ref = cells.load_module(CONFIG + ".py")
    spec = published()
    assert ref.param_count(spec) == spec["parameters"] == 340_349_184
    whole = {**spec, **{k: v for k, v in spec["published"].items()
                        if k != "parameters"}}
    assert ref.param_count(whole) == spec["published"]["parameters"] \
        == 12_149_915_904
    # every key of the catalog's row, as published, but the five reduced
    for key, value in {"attention_bias": False, "head_dim": 128,
                       "hidden_act": "silu", "hidden_size": 2304,
                       "intermediate_size": 7168,
                       "max_position_embeddings": 131072,
                       "max_window_layers": 0, "model_type": "mellum",
                       "moe_intermediate_size": 896, "norm_topk_prob": True,
                       "num_attention_heads": 32, "num_experts_per_tok": 8,
                       "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
                       "sliding_window": 1024, "tie_word_embeddings": False,
                       "use_sliding_window": True}.items():
        assert spec[key] == value, key
    assert spec["rope_parameters"] == {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                           "factor": 16,
                           "original_max_position_embeddings": 8192,
                           "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
    assert spec["reduced"] == ["num_hidden_layers", "layer_types",
                               "mlp_layer_types", "num_experts", "vocab_size"]
    assert (spec["num_hidden_layers"], spec["num_experts"],
            spec["vocab_size"]) == (4, 8, 12288)
    assert (spec["router_num_experts"], spec["expert_first_id"],
            spec["num_dense_layers"], spec["use_expert_bias"],
            spec["router_score"], spec["qk_norm"]) \
        == (64, 0, 0, False, "softmax", False)
    # one whole period of the published list, every layer sparse
    assert spec["layer_types"] == spec["published"]["layer_types"][:4] \
        == ["sliding_attention"] * 3 + ["full_attention"]
    assert spec["published"]["layer_types"] == spec["layer_types"] * 7
    assert spec["mlp_layer_types"] == ["sparse"] * 4
    for key in ("deployment", "assumed", "architecture"):
        assert spec[key]
    for key in ("qk_norm", "mtp_head", "auxiliary_loss", "router",
                "sequence_length", "optimizer", "weights"):
        assert key in spec["assumed"], key
    # the cell's traffic is the sequence the count of operations assumes
    cell = cells.load_cell(ROOT, CELL)
    assert cell.traffic["flags"]["sequence_length"] \
        == spec["sequence_length"] == 8192
    assert cell.chips == 1 and harness.task_of(cell).grad_blocks == 1
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        check_the_cells_entries(json.load(f))


def check_the_cells_entries(bench: dict):
    """In ``bench`` (``BENCHMARK.json``, or one to which a later
    configuration has been appended), found by name: the configuration,
    the cell, and the three entries that list it, in their order. Entries
    that list it may be added, and a later cell may join their lists."""
    assert CONFIG_NAME in {c["name"] for c in bench["configs"]}
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["chips"]) == (CONFIG_NAME, 1)
    listing = [m["name"] for m in bench["per_layer"]
               if CELL in m.get("workloads", ())]
    assert [n for n in listing if n in OWN] == OWN


def test_the_bands_pairs_and_costs_by_hand():
    """8,192 tokens under a window of 1,024: 7,864,832 pairs a head, 23.4%
    of the half square; 4 x 128 operations a pair forward and 2.5 times
    that backward; the bytes are the causal kernel's (every row read and
    written once). One window layer's forward pass over 4 sequences of 32
    heads: 2.62 ms at the peak, against 1.32 ms of bytes."""
    assert window_costs.band_pairs(8192, 1024) == 7_864_832
    assert window_costs.band_pairs(8192, None) \
        == window_costs.band_pairs(8192, 8192) \
        == window_costs.band_pairs(8192, 10_000) \
        == kernel_costs.causal_pairs(8192) == 33_558_528
    fwd = window_costs.flash_window_fwd(128, 8192, 1, 128, 1024)
    bwd = window_costs.flash_window_bwd(128, 8192, 1, 128, 1024)
    assert fwd["flops"] == 4 * 128 * 128 * 7_864_832
    assert bwd["flops"] == 10 * 128 * 128 * 7_864_832
    whole = kernel_costs.flash_fwd(128, 8192, 1, 128)
    assert fwd["bytes"] == whole["bytes"]
    assert fwd["flops"] / whole["flops"] == pytest.approx(0.23436, abs=1e-5)
    assert fwd["flops"] / PEAK["bf16_flops"] == pytest.approx(2.616e-3,
                                                              rel=1e-3)
    assert fwd["bytes"] / PEAK["hbm_bytes_per_s"] \
        == pytest.approx(1.316e-3, rel=1e-2)
    assert window_costs.config_window(published()) == 1024


@pytest.mark.parametrize("name,names,passes", [
    ("flash_window_fwd_roofline", ("flash_window_fwd.3",), 1.0),
    ("flash_window_bwd_roofline", ("flash_window_bwd_dq.4",
                                   "flash_window_bwd_dkv.5"), 2.5)])
def test_a_windows_share_is_of_the_bands_pairs(name, names, passes):
    """Kernels named as a call with a window names them, each 4 ms, shapes
    in the event's own text: the band's operations over the peak over the
    time taken; the full layers' kernels (``flash_fwd.<n>``) are not read,
    and a trace without the window's kernels gives nothing."""
    read = cells.load_module(os.path.join(
        ROOT, "benchmark", "metrics", name + ".py")).read
    text = "%k = bf16[128,8192,128]{2,1,0} custom-call(...)"
    ops = [xplane.Op(i * 1e7, i * 1e7 + 4e6, n, text, "XLA Ops")
           for i, n in enumerate(names)]
    full = [xplane.Op(9e7, 9.9e7, "flash_fwd.1", text, "XLA Ops"),
            xplane.Op(10e7, 10.9e7, "flash_bwd_dq.2", text, "XLA Ops")]
    ctx = {"trace": xplane.Trace([xplane.DevicePlane("/device:TPU:0",
                                                     ops + full)]),
           "peak": PEAK, "config": published()}
    least = passes * 4 * 128 * 128 * 7_864_832 / 197e12
    assert read(ctx) == pytest.approx(100 * least / (4e-3 * len(names)))
    assert 0 < read(ctx) < 100
    ctx["trace"] = xplane.Trace([xplane.DevicePlane("/device:TPU:0", full)])
    assert read(ctx) is None
    assert read({"trace": None, "peak": PEAK, "config": published()}) is None


@pytest.mark.parametrize("name,names,passes", [
    ("flash_window_fwd_roofline", ("flash_window_fwd.3",), 1.0),
    ("flash_window_bwd_roofline", ("flash_window_bwd_dq.4",
                                   "flash_window_bwd_dkv.5"), 2.5)])
def test_a_windows_share_takes_the_window_of_the_cells_configuration(
        name, names, passes):
    """The same kernels read for a cell whose configuration states a
    window of 512: the band of 512 (4,063,488 pairs a head), not this
    configuration's 1,024; a configuration that states no window is
    refused, not read as the half square."""
    read = cells.load_module(os.path.join(
        ROOT, "benchmark", "metrics", name + ".py")).read
    text = "%k = bf16[128,8192,128]{2,1,0} custom-call(...)"
    ops = [xplane.Op(i * 1e7, i * 1e7 + 4e6, n, text, "XLA Ops")
           for i, n in enumerate(names)]
    ctx = {"trace": xplane.Trace([xplane.DevicePlane("/device:TPU:0", ops)]),
           "peak": PEAK, "config": {**published(), "sliding_window": 512}}
    assert window_costs.band_pairs(8192, 512) == 4_063_488
    least = passes * 4 * 128 * 128 * 4_063_488 / 197e12
    assert read(ctx) == pytest.approx(100 * least / (4e-3 * len(names)))
    assert read({**ctx, "config": published()}) > read(ctx)
    with pytest.raises(ValueError, match="sliding_window"):
        read({**ctx, "config": {**published(), "sliding_window": None}})


def test_the_window_kinds_reader_reads_its_kind_or_nothing(monkeypatch):
    """An instruction the program's map gives the kind
    ``window_attention``, 2 ms in a window of 4 steps; the full layer's
    instruction beside it is not counted; without a map, nothing."""
    from benchmark.lib import scopes
    from dml_cnn_cifar10_tpu.utils import devprof
    read = cells.load_module(os.path.join(
        ROOT, "benchmark", "metrics",
        "model.window_attention_device_ms.py")).read
    ops = [xplane.Op(0, 2e6, "fusion.7", "%fusion.7 = f32[8]", "XLA Ops"),
           xplane.Op(3e6, 4e6, "fusion.8", "%fusion.8 = f32[8]", "XLA Ops")]
    ctx = {"trace": xplane.Trace([xplane.DevicePlane("/device:TPU:0", ops)]),
           "steps": 4}
    maps = {"jit_chunk": {
        "fusion.7": devprof.ScopeEntry("layer0/attn_window/qkv",
                                       "window_attention", "forward", False,
                                       False),
        "fusion.8": devprof.ScopeEntry("layer3/attn/qkv", "attention",
                                       "forward", False, False)}}
    monkeypatch.setattr(scopes, "program_maps", lambda: maps)
    assert read(ctx) == pytest.approx(0.5)
    monkeypatch.setattr(scopes, "program_maps", lambda: None)
    ctx["trace"] = xplane.Trace([xplane.DevicePlane("/device:TPU:0", ops)])
    assert read(ctx) is None

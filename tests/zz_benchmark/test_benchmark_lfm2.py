"""The configuration ``lfm2_8b_a1b_l5_e8`` through the harness at a size a
CPU test holds: the configuration's own module, the program's own
``Trainer`` on resident token rows, and the comparison that decides
``correct``, with the faults of this model's own. And what the module and
the experts' cost file count, from shapes alone."""

import json
import os
import shutil

import jax
import numpy as np
import pytest

from benchmark.lib import cells, check, driver, expert_costs, harness, xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2 ** 31 + 32          # the driver's seeds pass 32 signed bits
CONFIG = os.path.join(ROOT, "benchmark", "configs", "lfm2_8b_a1b_l5_e8")
CELL = "lfm2_l5_e8_s8192_resident"
SMALL = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
         "moe_intermediate_size": 32, "num_hidden_layers": 3,
         "layer_types": ["conv", "full_attention", "conv"],
         "num_dense_layers": 1, "num_experts": 4, "router_num_experts": 8,
         "expert_first_id": 0, "num_experts_per_tok": 2, "vocab_size": 96,
         "sequence_length": 32, "expert_bias_update_rate": 0.001}


def published() -> dict:
    with open(CONFIG + ".json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def small_cell(tmp_path_factory):
    """The configuration's module beside a file of small sizes, in a root
    of its own, as the harness finds a cell; float32 on both sides, as a
    CPU computes."""
    root = str(tmp_path_factory.mktemp("lfm2") / "root")
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
    # the reference (grouped products over sorted rows, a loss in blocks);
    # float32 on both sides, Adam's normalised step at the start of a
    # warm-up; the experts' bias, a buffer, has to come out the same
    limits = {"limits": {"loss": 1e-5, "dparam": 1e-3, "ddiff_mid": 1e-3,
                         "mu_diff": 1e-4, "nu_diff": 1e-4, "sdiff": 1e-6}}
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
    parameters (the tied embedding among them), ``mu``, ``nu``, the loss,
    and the model's state: the experts' bias, which both sides move by the
    rate toward an even load. Then the harness's two faults and this
    model's two: the choice of experts made without the bias (it shows in
    the second step), and the weights held answering to the wrong ids."""
    cell, work = small_cell
    devices = jax.devices()[:1]
    flags = harness.program_flags(cell, work)
    task, hyper = harness.task_of(cell), harness.hyper_of(cell)
    records = harness.write_records(cell, task, SEED, flags)
    assert records.shape == (32, 33) and records.max() < 96
    program = driver.start_program(
        flags, devices,
        lambda abstract, sharding: harness.make_params(cell, SEED, abstract,
                                                       sharding))
    first = program.first
    assert first.loss is not None and set(first.opt) == {"mu", "nu"}
    p0, s0, ref = harness.reference_chunk(cell, task, hyper, SEED, devices,
                                          first.params, records)
    moved = abs(first.model_state["layers"][1]["expert_bias"])
    # two steps of the rate, each up, down or (a load at the mean) none
    assert np.isclose(moved[:, None], [0.0, 0.001, 0.002], atol=1e-7).any(
        -1).all() and moved.max() > 0
    numbers = check.compare(first, p0, s0, ref)
    correct, compared = check.verdict(numbers, cell.limits)
    assert correct, compared
    for fault in ("half_batch", "no_exchange", "no_expert_bias",
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
    assert ref.param_count(spec) == spec["parameters"] == 507_820_288
    whole = {**spec, **{k: v for k, v in spec["published"].items()
                        if k != "parameters"}}
    assert ref.param_count(whole) == spec["published"]["parameters"] \
        == 8_339_930_560
    # every key of the catalog's row, as published, but the five reduced
    for key, value in {"hidden_size": 2048, "num_attention_heads": 32,
                       "num_key_value_heads": 8, "intermediate_size": 7168,
                       "moe_intermediate_size": 1792, "conv_L_cache": 3,
                       "conv_bias": False, "norm_eps": 1e-5,
                       "norm_topk_prob": True, "num_experts_per_tok": 4,
                       "rope_theta": 1000000, "routed_scaling_factor": 1,
                       "use_expert_bias": True, "model_type": "lfm2_moe",
                       "max_position_embeddings": 128000}.items():
        assert spec[key] == value, key
    assert spec["reduced"] == ["num_hidden_layers", "num_dense_layers",
                               "layer_types", "num_experts", "vocab_size"]
    assert (spec["num_hidden_layers"], spec["num_dense_layers"],
            spec["num_experts"], spec["vocab_size"]) == (5, 1, 8, 16384)
    assert (spec["router_num_experts"], spec["expert_first_id"]) == (32, 0)
    # one of the leading dense layers, then one whole period of the list
    assert spec["layer_types"] == [spec["published"]["layer_types"][1]] \
        + spec["published"]["layer_types"][2:6]
    assert len(spec["published"]["layer_types"]) == 24
    # the cell's traffic is the sequence the count of operations assumes
    cell = cells.load_cell(ROOT, CELL)
    assert cell.traffic["flags"]["sequence_length"] \
        == spec["sequence_length"] == 8192
    assert harness.task_of(cell).grad_blocks == 1


def test_the_experts_products_by_hand():
    """Nine products a layer on the rows routed here, ``2 x rows x 2048 x
    1792`` operations each; bytes: the 8 experts' matrices and the rows,
    once a product, in bfloat16."""
    rows, d, h = 32768, 2048, 1792
    cost = expert_costs.grouped_products(rows, d, h, 8)
    assert cost["flops"] == 9 * 2 * rows * d * h
    assert cost["bytes"] == 9 * 2 * (8 * d * h + rows * (d + h))
    # bound by operations at this load: 10.99 ms against 3.41 ms of bytes
    assert cost["flops"] / 197e12 == pytest.approx(10.99e-3, rel=1e-3)
    assert cost["bytes"] / 819e9 == pytest.approx(3.41e-3, rel=1e-2)


def test_the_experts_share_is_read_from_the_programs_count(monkeypatch):
    """Instructions of kind ``expert`` took 60 ms a step, the program
    counted a quarter of the slots here: 4 layers of 10.99 ms over 60 ms.
    A program that posts no count, or maps no instruction to the kind,
    gives nothing."""
    from benchmark.lib import scopes
    spec = published()
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"trace": None, "peak": peak, "steps": 4, "examples": 16,
           "config": spec}
    read = cells.load_module(os.path.join(
        ROOT, "benchmark", "metrics", "expert_roofline.py")).read
    monkeypatch.setattr(scopes, "kind_ms_per_step",
                        lambda ctx, kind: {"expert": 60.0}.get(kind))
    monkeypatch.setattr(expert_costs, "rows_here_frac", lambda: 0.25)
    least = 4 * 9 * 2 * 32768 * 2048 * 1792 / 197e12
    assert read(ctx) == pytest.approx(100 * least / 60e-3)
    assert 100 * least / 60e-3 < 100
    monkeypatch.setattr(expert_costs, "rows_here_frac", lambda: None)
    assert read(ctx) is None
    monkeypatch.setattr(expert_costs, "rows_here_frac", lambda: 0.25)
    monkeypatch.setattr(scopes, "kind_ms_per_step", lambda ctx, kind: None)
    assert read(ctx) is None


@pytest.mark.parametrize("config,rows,d,h", [
    ("mellum2_12b_a2p5b_l4_e8", 32768 * 8, 2304, 896),
    ("deepseek_v2_lite_l5_e8", 32768 * 6, 2048, 1408)])
def test_the_experts_share_takes_the_cells_own_configuration(
        config, rows, d, h, monkeypatch):
    """The same trace and count read for a cell of another configuration:
    its own four expert layers, experts a token and widths (Mellum2's 8
    choices of 2,304 -> 896, DeepSeek-V2-Lite's 6 of 2,048 -> 1,408 after
    its dense layer), not the expert decoder's."""
    from benchmark.lib import scopes
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config + ".json")) as f:
        spec = json.load(f)
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"trace": None, "peak": peak, "steps": 4, "examples": 16,
           "config": spec}
    read = cells.load_module(os.path.join(
        ROOT, "benchmark", "metrics", "expert_roofline.py")).read
    monkeypatch.setattr(scopes, "kind_ms_per_step",
                        lambda ctx, kind: {"expert": 60.0}.get(kind))
    monkeypatch.setattr(expert_costs, "rows_here_frac", lambda: 0.25)
    least = 4 * 9 * 2 * (rows / 4) * d * h / 197e12
    assert read(ctx) == pytest.approx(100 * least / 60e-3)
    assert read(ctx) != read({**ctx, "config": published()})


EXPERTS = {   # configuration: (layers, held, a token, D, H), or none
    "cnn_cifar10": None, "resnet50_imagenet": None, "ouro_2p6b_l6": None,
    "lfm2_8b_a1b_l5_e8": (4, 8, 4, 2048, 1792),
    "mellum2_12b_a2p5b_l4_e8": (4, 8, 8, 2304, 896),
    "deepseek_v2_lite_l5_e8": (4, 8, 6, 2048, 1408)}


@pytest.mark.parametrize("config", sorted(EXPERTS))
def test_the_expert_layers_of_each_configuration(config):
    """Each configuration's expert layers by its own keys: LFM2's leading
    dense layer, Mellum2's list of MLP kinds, DeepSeek-V2's first dense
    layer and the frequency after it; none where a configuration states no
    experts."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config + ".json")) as f:
        spec = json.load(f)
    got = expert_costs.expert_layers(spec)
    assert (got if got is None else tuple(got)) == EXPERTS[config]


@pytest.mark.parametrize("change,match", [
    ({"num_dense_layers": None, "mlp_layer_types": None}, "expert layers"),
    ({"num_dense_layers": 2}, "expert layers"),
    ({"num_experts": None}, "experts held"),
    ({"n_routed_experts": 16}, "experts held"),
    ({"mlp_layer_types": ["sparse", "mixed", "sparse", "sparse"]},
     "mlp_layer_types"),
    ({"num_experts_per_tok": None}, "num_experts_per_tok")])
def test_a_configuration_whose_experts_cannot_be_counted_is_refused(
        change, match):
    """A key left out, two keys that disagree, a kind of layer it does not
    know: a ``ValueError``, never a guess."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mellum2_12b_a2p5b_l4_e8.json")) as f:
        spec = json.load(f)
    spec.update(change)
    spec = {k: v for k, v in spec.items() if v is not None}
    with pytest.raises(ValueError, match=match):
        expert_costs.expert_layers(spec)


def test_the_gauge_is_the_programs_or_nothing():
    from dml_cnn_cifar10_tpu.utils import metrics_registry
    reg = metrics_registry.default_registry()
    if reg.get("dml_moe_rows_here_frac") is None:
        assert expert_costs.rows_here_frac() is None
    metrics_registry.observe_record("train", {"step": 4, "loss": 1.0,
                                              "moe_rows_here_frac": 0.2513})
    assert expert_costs.rows_here_frac() == 0.2513


@pytest.mark.parametrize("name,kind", [
    ("model.expert_device_ms", "expert"), ("model.route_device_ms", "route"),
    ("model.short_conv_device_ms", "short_conv")])
def test_a_kinds_reader_reads_its_kind_or_nothing(name, kind, monkeypatch):
    """An instruction the program's map gives the kind, 2 ms in a window of
    4 steps; without a map, nothing."""
    from benchmark.lib import scopes
    from dml_cnn_cifar10_tpu.utils import devprof
    read = cells.load_module(os.path.join(
        ROOT, "benchmark", "metrics", name + ".py")).read
    ops = [xplane.Op(0, 2e6, "fusion.7", "%fusion.7 = f32[8]", "XLA Ops"),
           xplane.Op(3e6, 4e6, "fusion.8", "%fusion.8 = f32[8]", "XLA Ops")]
    ctx = {"trace": xplane.Trace([xplane.DevicePlane("/device:TPU:0", ops)]),
           "steps": 4}
    entry = devprof.ScopeEntry(f"layer1/moe/{kind}", kind, "forward", False,
                               False)
    monkeypatch.setattr(scopes, "program_maps",
                        lambda: {"jit_chunk": {"fusion.7": entry}})
    assert read(ctx) == pytest.approx(0.5)
    monkeypatch.setattr(scopes, "program_maps", lambda: None)
    ctx["trace"] = xplane.Trace([xplane.DevicePlane("/device:TPU:0", ops)])
    assert read(ctx) is None

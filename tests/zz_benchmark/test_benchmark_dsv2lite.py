"""The configuration ``deepseek_v2_lite_l5_e8`` through the harness at a
size a CPU test holds: the configuration's own module, the program's own
``Trainer`` on resident token rows, and the comparison that decides
``correct``, with the faults of this model's own. And what the module and
the latent kernels' cost file count, from shapes alone, the four readers
of its per-layer metrics, and what its reference holds on the chip."""

import json
import os
import shutil

import jax
import pytest

import bench_roots
from benchmark.lib import (cells, check, driver, harness, kernel_costs,
                           latent_costs, xplane)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2 ** 31 + 38          # a run's seed may pass 32 signed bits
CONFIG = os.path.join(ROOT, "benchmark", "configs", "deepseek_v2_lite_l5_e8")
CELL = "dsv2lite_l5_e8_s8192_resident"
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
HBM_BYTES = 16 * 2 ** 30
SMALL = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 4, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 12,
         "intermediate_size": 128, "moe_intermediate_size": 32,
         "num_hidden_layers": 3, "layer_types": ["latent_attention"] * 3,
         "n_routed_experts": 4, "router_num_experts": 8,
         "num_experts_per_tok": 3, "vocab_size": 96,
         "rope_scaling": {"type": "yarn", "factor": 4,
                          "original_max_position_embeddings": 64,
                          "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                          "mscale_all_dim": 0.707},
         "aux_loss_alpha": 0.01, "sequence_length": 40}
NEW = ["model.latent_attention_device_ms", "model.shared_expert_device_ms",
       "flash_latent_fwd_roofline", "flash_latent_bwd_roofline"]
# entries by layer kind that list this cell beside the cells they began with
BY_KIND = ["model.route_device_ms", "model.expert_device_ms",
           "model.mlp_device_ms"]


def published() -> dict:
    with open(CONFIG + ".json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def small_cell(tmp_path_factory):
    """The configuration's module beside a file of small sizes (latent
    attention at unequal query and value widths, YaRN as published but
    over a short original length, shared experts, a balance loss large
    enough to be seen), in a root of its own, as the harness finds a cell;
    float32 on both sides, as a CPU computes."""
    root = str(tmp_path_factory.mktemp("dsv2lite") / "root")
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
    # the reference (grouped products over sorted rows, a loss in blocks,
    # attention in one piece where the reference takes blocks of queries);
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
    parameters (the latent's norm and the shared experts among them),
    ``mu``, ``nu`` and the loss with its balance terms; the model holds no
    state on either side. Then the harness's two faults and this model's
    three: the shared experts left out, the softmax scale without YaRN's
    factor, and the weights held answering to the wrong ids."""
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
    assert "shared" in first.params["layers"][1]["moe"]
    assert not jax.tree.leaves(first.model_state)
    p0, s0, ref = harness.reference_chunk(cell, task, hyper, SEED, devices,
                                          first.params, records)
    numbers = check.compare(first, p0, s0, ref)
    correct, compared = check.verdict(numbers, cell.limits)
    assert correct, compared
    for fault in ("half_batch", "no_exchange", "no_shared", "no_mscale",
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
    assert ref.param_count(spec) == spec["parameters"] == 535_060_992
    whole = {**spec, **{k: v for k, v in spec["published"].items()
                        if k != "parameters"}}
    assert ref.param_count(whole) == spec["published"]["parameters"] \
        == 15_706_484_224
    # every key of the catalog's row, as published, but the three reduced
    for key, value in {
            "attention_bias": False, "first_k_dense_replace": 1,
            "hidden_act": "silu", "hidden_size": 2048,
            "intermediate_size": 10944, "kv_lora_rank": 512,
            "max_position_embeddings": 163840, "model_type": "deepseek_v2",
            "moe_intermediate_size": 1408, "moe_layer_freq": 1,
            "n_group": 1, "n_shared_experts": 2, "norm_topk_prob": False,
            "num_attention_heads": 16, "num_experts_per_tok": 6,
            "num_key_value_heads": 16, "q_lora_rank": None,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "rms_norm_eps": 1e-06, "rope_theta": 10000,
            "routed_scaling_factor": 1, "scoring_func": "softmax",
            "seq_aux": True, "tie_word_embeddings": False, "topk_group": 1,
            "topk_method": "greedy", "v_head_dim": 128}.items():
        assert spec[key] == value, key
    assert spec["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert spec["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert (spec["num_hidden_layers"], spec["n_routed_experts"],
            spec["vocab_size"]) == (5, 8, 12800)
    assert (spec["router_num_experts"], spec["expert_first_id"],
            spec["use_expert_bias"], spec["aux_loss_alpha"]) \
        == (64, 0, False, 0.001)
    assert spec["layer_types"] == spec["published"]["layer_types"][:5] \
        == ["latent_attention"] * 5
    for key in ("deployment", "assumed", "architecture"):
        assert spec[key]
    for key in ("aux_loss_alpha", "rope_pairing", "sequence_length",
                "optimizer", "weights", "router", "shared_experts"):
        assert key in spec["assumed"], key
    # the cell's traffic is the sequence the count of operations assumes
    cell = cells.load_cell(ROOT, CELL)
    assert cell.traffic["flags"]["sequence_length"] \
        == spec["sequence_length"] == 8192
    assert cell.traffic["flags"]["batch_size"] == 4
    assert cell.chips == 1 and harness.task_of(cell).grad_blocks == 1


def check_the_cells_entries(bench: dict):
    """In ``bench`` (``BENCHMARK.json``, or one to which a later
    configuration has been appended), found by name: the configuration,
    the cell, the cell's four entries of its own and the three by kind
    that it joined. An entry's list of cells holds this one, and may hold
    cells that join later. The causal kernels' two shares do not list it:
    they would cost its value side at the query's width."""
    configs = {c["name"]: c for c in bench["configs"]}
    workloads = {w["name"]: w for w in bench["workloads"]}
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert "deepseek_v2_lite_l5_e8" in configs
    assert workloads[CELL] == {
        "name": CELL, "config": "deepseek_v2_lite_l5_e8",
        "traffic": "b4_s8192_resident_k2", "chips": 1,
        "why": workloads[CELL]["why"]}
    assert set(NEW) | set(BY_KIND) <= set(listed)
    for name in NEW + BY_KIND:
        m = listed[name]
        assert CELL in m["workloads"], name
        assert (m["better"], m["source"], m["moves"], m["layer"]) == (
            "higher" if name.endswith("_roofline") else "lower",
            "device_trace", "img_per_s_per_chip", "models and kernels")
        assert m["unit"] == ("%" if name.endswith("_roofline") else "ms")
    for name in ("flash_fwd_roofline", "flash_bwd_roofline"):
        assert CELL not in listed[name]["workloads"], name


def test_the_cells_entries_and_those_that_wait():
    """The configuration, the cell and its entries are in
    ``BENCHMARK.json``; none of them waits under ``benchmark/pending/``
    any longer, and the tests' roots lay nothing of this cell over it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    check_the_cells_entries(bench)
    check_the_cells_entries(bench_roots.benchmark_with_pending())
    cell = cells.load_cell(ROOT, CELL)
    assert set(NEW) | set(BY_KIND) <= {m["name"] for m in cell.per_layer}


def test_the_latent_kernels_costs_by_hand():
    """8,192 tokens, 16 heads of 192 over values of 128, 4 sequences: the
    half square's pairs, 2 x (192 + 128) operations a pair forward, 2 x (3
    x 192 + 2 x 128) backward (2.6 times the forward, not 2.5); bytes at
    each array's own width. One layer's forward over the cell's step at the
    peak: 6.98 ms, against 3.28 ms of bytes."""
    pairs = kernel_costs.causal_pairs(8192)
    fwd = latent_costs.flash_latent_fwd(64, 8192, 1, 192, 128)
    bwd = latent_costs.flash_latent_bwd(64, 8192, 1, 192, 128)
    assert fwd["flops"] == 2 * 64 * pairs * 320
    assert bwd["flops"] == 2 * 64 * pairs * 832
    assert bwd["flops"] / fwd["flops"] == 2.6
    rows = 64 * 8192
    assert fwd["bytes"] == rows * (2 * 192 + 2 * 128) * 2 + rows * 4
    assert bwd["bytes"] == rows * (4 * 192 + 4 * 128) * 2 + 2 * rows * 4
    # at equal widths the costs are kernel_costs' own
    for mine, theirs in ((latent_costs.flash_latent_fwd(8, 512, 2, 64, 64),
                          kernel_costs.flash_fwd(8, 512, 2, 64)),
                         (latent_costs.flash_latent_bwd(8, 512, 2, 64, 64),
                          kernel_costs.flash_bwd(8, 512, 2, 64))):
        assert mine == theirs
    assert fwd["flops"] / PEAK["bf16_flops"] == pytest.approx(6.977e-3,
                                                              rel=1e-3)
    assert fwd["bytes"] / PEAK["hbm_bytes_per_s"] \
        == pytest.approx(0.822e-3, rel=1e-2)
    assert latent_costs.config_widths(published()) == (192, 128)


@pytest.mark.parametrize("name,names,passes", [
    ("flash_latent_fwd_roofline", ("flash_fwd.3",), 320),
    ("flash_latent_bwd_roofline", ("flash_bwd_dq.4", "flash_bwd_dkv.5"),
     832)])
def test_a_latent_share_counts_each_product_at_its_width(name, names,
                                                         passes):
    """Kernels named as the program names them, each 12 ms; the event's
    first array is the output, ``d_v`` wide, and the share still counts
    the query side at 192 (the configuration's file), the shapes ``B H``
    and ``S`` the event's own; a trace without them gives nothing."""
    read = cells.load_module(os.path.join(
        ROOT, "benchmark", "metrics", name + ".py")).read
    text = "%k = (bf16[64,8192,128]{2,1,0}, f32[64,8192,128]) custom-call(" \
        "s32[4,136] %s, bf16[64,8192,192] %q, ...)"
    ops = [xplane.Op(i * 2e7, i * 2e7 + 12e6, n, text, "XLA Ops")
           for i, n in enumerate(names)]
    ctx = {"trace": xplane.Trace([xplane.DevicePlane("/device:TPU:0", ops)]),
           "peak": PEAK, "config": published()}
    least = 2 * 64 * kernel_costs.causal_pairs(8192) * passes / 197e12
    assert read(ctx) == pytest.approx(100 * least / (12e-3 * len(names)))
    assert 0 < read(ctx) < 100
    other = [xplane.Op(0, 1e6, "flash_window_fwd.1", text, "XLA Ops")]
    ctx["trace"] = xplane.Trace([xplane.DevicePlane("/device:TPU:0", other)])
    assert read(ctx) is None
    assert read({"trace": None, "peak": PEAK, "config": published()}) is None


@pytest.mark.parametrize("name,names,passes", [
    ("flash_latent_fwd_roofline", ("flash_fwd.3",), 128 + 64),
    ("flash_latent_bwd_roofline", ("flash_bwd_dq.4", "flash_bwd_dkv.5"),
     3 * 128 + 2 * 64)])
def test_a_latent_share_takes_the_widths_of_the_cells_configuration(
        name, names, passes):
    """The same kernels read for a cell whose configuration states other
    widths (queries and keys 96 + 32, values 64): the share costs the
    products at those, not at this configuration's 192 and 128."""
    read = cells.load_module(os.path.join(
        ROOT, "benchmark", "metrics", name + ".py")).read
    text = "%k = (bf16[64,8192,64]{2,1,0}, f32[64,8192,128]) custom-call(" \
        "s32[4,136] %s, bf16[64,8192,128] %q, ...)"
    ops = [xplane.Op(i * 2e7, i * 2e7 + 12e6, n, text, "XLA Ops")
           for i, n in enumerate(names)]
    other = {**published(), "qk_nope_head_dim": 96, "qk_rope_head_dim": 32,
             "v_head_dim": 64}
    ctx = {"trace": xplane.Trace([xplane.DevicePlane("/device:TPU:0", ops)]),
           "peak": PEAK, "config": other}
    assert latent_costs.config_widths(other) == (128, 64)
    least = 2 * 64 * kernel_costs.causal_pairs(8192) * passes / 197e12
    assert read(ctx) == pytest.approx(100 * least / (12e-3 * len(names)))
    assert read({**ctx, "config": published()}) > read(ctx)


@pytest.mark.parametrize("name,kind", [
    ("model.latent_attention_device_ms", "latent_attention"),
    ("model.shared_expert_device_ms", "shared_expert")])
def test_the_kinds_readers_read_their_kind_or_nothing(name, kind,
                                                      monkeypatch):
    """An instruction the program's map gives the kind, 2 ms in a window of
    4 steps; an instruction of another kind beside it is not counted;
    without a map, nothing."""
    from benchmark.lib import scopes
    from dml_cnn_cifar10_tpu.utils import devprof
    read = cells.load_module(os.path.join(
        ROOT, "benchmark", "metrics", name + ".py")).read
    ops = [xplane.Op(0, 2e6, "fusion.7", "%fusion.7 = f32[8]", "XLA Ops"),
           xplane.Op(3e6, 4e6, "fusion.8", "%fusion.8 = f32[8]", "XLA Ops")]
    ctx = {"trace": xplane.Trace([xplane.DevicePlane("/device:TPU:0", ops)]),
           "steps": 4}
    maps = {"jit_chunk": {
        "fusion.7": devprof.ScopeEntry("layer1/x", kind, "forward", False,
                                       False, part="q"),
        "fusion.8": devprof.ScopeEntry("layer1/moe/experts", "expert",
                                       "forward", False, False)}}
    monkeypatch.setattr(scopes, "program_maps", lambda: maps)
    assert read(ctx) == pytest.approx(0.5)
    monkeypatch.setattr(scopes, "program_maps", lambda: None)
    ctx["trace"] = xplane.Trace([xplane.DevicePlane("/device:TPU:0", ops)])
    assert read(ctx) is None


def test_the_reference_fits_beside_its_state():
    """The reference holds the starting weights, the K steps' weights and
    AdamW's two moments, and a gradient as the sequences are summed: 24
    bytes a parameter, 12.84e9 of the chip's 17.18e9, before activations.
    Its plan for a described v5e (PERF.md 4: 2.21e9 of arguments, 7.71e9
    of temporaries, 6.42e9 of outputs) was read with the attention taken a
    block of queries at a time and the loss, the dense MLP and the shared
    experts 512 tokens at a time: blocks of the sequence that divide it."""
    spec = published()
    assert 24 * spec["parameters"] == 12_841_463_808 < 0.75 * HBM_BYTES
    blocks = spec["reference_loss_blocks"]
    assert spec["sequence_length"] % blocks == 0
    assert spec["sequence_length"] // blocks == 512

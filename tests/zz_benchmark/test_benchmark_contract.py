"""``BENCHMARK.json`` against the contract's shape, and against the files
the harness finds by its names."""

import json
import os
import re

import pytest

import bench_roots
from benchmark.lib import cells, harness, driver, reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")


@pytest.fixture(scope="module",
                params=["committed", "with_pending", "appended"])
def bench(request, tmp_path_factory):
    """``BENCHMARK.json`` as committed, as it will read once the entries
    under ``benchmark/pending/`` have joined it, and as a configuration
    that joins at the end leaves it. Gives the root to load cells from,
    too."""
    if request.param == "committed":
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            return dict(json.load(f), _root=ROOT)
    if request.param == "appended":
        root = bench_roots.make_root_with_one_appended(
            str(tmp_path_factory.mktemp("appended_root")))
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            return dict(json.load(f), _root=root)
    pending = bench_roots.benchmark_with_pending()
    root = bench_roots.make_root(
        str(tmp_path_factory.mktemp("pending_root")), pending)
    return dict(pending, _root=root)


def test_top_level_keys(bench):
    assert set(bench) - {"_root"} == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"][:2] == ["python3", "benchmark/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    # a full check with all 24 cells fits the driver's day
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_names_units_and_lines(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for e in bench["configs"] + bench["workloads"]:
        assert LINE.match(e["why"])
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
        assert LINE.match(m["layer"])


def test_cells_and_chips(bench):
    cfgs = {c["name"] for c in bench["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in bench["workloads"]} == cfgs
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    for c in bench["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        assert not set(c["reduced"]) & {"crop_size", "conv_width",
                                        "stage_widths", "fc1_width"}


def test_every_name_finds_its_files(bench):
    for w in bench["workloads"]:
        cell = cells.load_cell(bench["_root"], w["name"])
        assert cell.limits and all(v > 0 for v in cell.limits.values())
        assert {"setup_s", "img_per_s_per_chip"} <= {
            m["name"] for m in cell.end_to_end}
        assert cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(cells.load_reader(cell, m["name"]))
        assert cell.reference.param_count(cell.config) \
            == cell.config["parameters"]
        assert ("collective.exposed_pct" in {m["name"]
                                             for m in cell.per_layer}) \
            == (cell.chips == 4)


@pytest.mark.parametrize("workload", ["cnn_b16k_resident",
                                      "resnet50_b256_resident",
                                      "resnet50_dp4_b1024"])
def test_the_reference_reads_the_feed_as_the_programs_flags_state_it(
        workload, tmp_path):
    """The configuration's file states the decode and the update for the
    reference; the program gets flags. They have to say the same."""
    root = bench_roots.make_root(str(tmp_path / "root"),
                                 bench_roots.benchmark_with_pending())
    cell = cells.load_cell(root, workload)
    cfg = driver.build_train_config(harness.program_flags(cell,
                                                          str(tmp_path)))
    # what every task shares, and what the default task (an image
    # classifier under SGD: neither configuration states another) reads
    hyper = harness.hyper_of(cell)
    assert not hasattr(cell.reference, "task")
    image = reference.image_hyper(cell.config, harness.cell_flags(cell))
    assert harness.task_of(cell).whole_chunk == image.decode_whole_chunk
    assert (image.random_crop, image.random_flip, image.normalize) == (
        cfg.data.random_crop, cfg.data.random_flip, cfg.data.normalize)
    assert (image.crop, hyper.records) == (
        cfg.data.crop_height, cfg.data.synthetic_train_records)
    assert cfg.data.image_height == cell.config["image_size"] \
        == image.image_size
    assert (image.learning_rate, image.momentum, image.weight_decay) == (
        cfg.optim.learning_rate, cfg.optim.momentum, cfg.optim.weight_decay)
    # the default task knows a constant rate under a linear warm-up, plain SGD
    assert (cfg.optim.schedule, cfg.optim.optimizer) == ("constant", "sgd")
    assert image.warmup_steps == cfg.optim.warmup_steps
    assert not cfg.optim.label_smoothing and not cfg.optim.grad_clip_norm
    assert cfg.optim.grad_accum == 1 and not cfg.optim.ema_decay
    assert (hyper.batch, hyper.steps, hyper.seed, hyper.step0) == (
        cfg.batch_size, cfg.steps_per_dispatch, cfg.data.seed, 0)
    assert cfg.output_every % cfg.steps_per_dispatch == 0
    assert cfg.eval_every > cfg.total_steps < cfg.checkpoint_every
    assert cfg.model.compute_dtype == cell.config["compute_dtype"]
    assert not cfg.model.logit_relu and not image.decode_whole_chunk
    assert cfg.model.num_classes == cell.config["num_classes"] \
        == image.classes
    assert cfg.data.num_channels == image.channels and image.fault is None

"""The benchmark's operation counts: the published parameter counts, each
count against XLA's cost analysis of the scan-free step, and each cell's
dispatch compiled at its real size for a described v5e:2x2, with what it
holds on a chip above the driver's floor for a new cell."""

import os

import jax
import jax.numpy as jnp
import pytest

import bench_roots
from benchmark.lib import cells, driver, flops, harness
from dml_cnn_cifar10_tpu.config import ParallelConfig
from dml_cnn_cifar10_tpu.models.registry import get_model
from dml_cnn_cifar10_tpu.parallel import mesh as mesh_lib
from dml_cnn_cifar10_tpu.parallel import step as step_lib
from dml_cnn_cifar10_tpu.utils import platform as platform_lib

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = ["cnn_b16k_resident", "resnet50_b256_resident",
         "resnet50_dp4_b1024"]
HBM_BYTES = 16 * 2 ** 30
FLOOR = 0.25


def _cell_and_config(workload, tmp_path):
    # the four-chip cell's entries wait under benchmark/pending/
    root = bench_roots.make_root(str(tmp_path / "root"),
                                 bench_roots.benchmark_with_pending())
    cell = cells.load_cell(root, workload)
    return cell, driver.build_train_config(
        harness.program_flags(cell, str(tmp_path)))


@pytest.mark.parametrize("config,want", [("cnn_b16k_resident", 1_068_298),
                                         ("resnet50_b256_resident",
                                          25_557_032)])
def test_parameter_counts_are_the_published_ones(config, want, tmp_path):
    cell, cfg = _cell_and_config(config, tmp_path)
    assert cell.reference.param_count(cell.config) == want
    model_def = get_model(cfg.model.name)
    shapes = jax.eval_shape(
        lambda k: model_def.init(k, cfg.model, cfg.data), jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == want


@pytest.mark.parametrize("size,kernel,stride,want", [
    (24, 5, 1, 24 * 5 - 6), (12, 5, 1, 12 * 5 - 6), (56, 1, 1, 56),
    (224, 7, 2, 112 * 7 - 2 - 4), (56, 3, 2, 28 * 3 - 1), (4, 3, 1, 10)])
def test_taps_on_padding_are_not_counted(size, kernel, stride, want):
    assert flops.valid_taps(size, kernel, stride) == want


def test_cnn_count_is_the_one_worked_by_hand(tmp_path):
    cell, _ = _cell_and_config("cnn_b16k_resident", tmp_path)
    macs = 114 * 114 * 3 * 64 + 54 * 54 * 64 * 64 \
        + 2304 * 384 + 384 * 192 + 192 * 10
    assert cell.reference.train_flops_per_image(cell.config) \
        == 2 * (3 * macs - 114 * 114 * 3 * 64) == 87_406_848


@pytest.mark.parametrize("workload", CELLS[:2])
def test_count_is_held_to_xlas_cost_analysis(workload, tmp_path):
    """XLA's count of the scan-free step adds the elementwise work to the
    same multiply-adds: the benchmark's count lies just under it, and a
    count that ran over would put shares above 100%."""
    cell, cfg = _cell_and_config(workload, tmp_path)
    batch = 4
    model_def = get_model(cfg.model.name)
    mesh = mesh_lib.build_mesh(ParallelConfig(), devices=jax.devices()[:1])
    state = jax.eval_shape(
        lambda k: step_lib.init_train_state(k, model_def, cfg.model,
                                            cfg.data, cfg.optim),
        jax.random.key(0))
    step = step_lib.make_train_step(model_def, cfg.model, cfg.optim, mesh)
    lowered = step.lower(
        state,
        jax.ShapeDtypeStruct((batch, cfg.data.crop_height,
                              cfg.data.crop_width, cfg.data.num_channels),
                             jnp.float32),
        jax.ShapeDtypeStruct((batch,), jnp.int32))
    xla = lowered.cost_analysis()["flops"] / batch
    mine = cell.reference.train_flops_per_image(cell.config)
    assert 0.97 * xla <= mine <= xla


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


# slow: up to a minute of compiling on every core for each, beside the
# program's timing-dependent multi-process tests (see the rehearsal file)
@pytest.mark.slow
@pytest.mark.parametrize("workload", CELLS)
def test_the_cells_dispatch_compiles_at_real_size_and_fills_the_chip(
        workload, topo, tmp_path, monkeypatch):
    """The K-step resident dispatch the window drives, with the fused
    update's kernel, compiled by the chip's compiler for a chip that is
    described and not attached. A compile, not a run."""
    monkeypatch.setattr(platform_lib, "on_tpu", lambda: True)
    cell, cfg = _cell_and_config(workload, tmp_path)
    mesh = mesh_lib.build_mesh(cfg.parallel,
                               devices=topo.devices[:cell.chips])
    model_def = get_model(cfg.model.name)
    sh = step_lib.train_state_shardings(mesh, model_def, cfg.model,
                                        cfg.data, cfg.optim)
    state = jax.eval_shape(
        lambda k: step_lib.init_train_state(k, model_def, cfg.model,
                                            cfg.data, cfg.optim),
        jax.random.key(0))
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        state, sh)
    repl = mesh_lib.replicated(mesh)
    d = cfg.data
    images = jax.ShapeDtypeStruct(
        (d.synthetic_train_records, d.image_height, d.image_width,
         d.num_channels), jnp.uint8, sharding=repl)
    labels = jax.ShapeDtypeStruct((d.synthetic_train_records,), jnp.int32,
                                  sharding=repl)
    chunk = step_lib.make_train_chunk_resident(
        model_def, cfg.model, cfg.optim, mesh, images, labels,
        state_sharding=sh, data_cfg=d,
        index_stream=(d.seed, cfg.batch_size, cfg.steps_per_dispatch))
    compiled = chunk.func.lower(images, labels, state).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= len(jax.tree.leaves(
        state.params))
    assert ("all-reduce" in text) == (cell.chips > 1)
    held = compiled.memory_analysis()
    on_chip = held.temp_size_in_bytes + held.argument_size_in_bytes
    assert FLOOR * HBM_BYTES < on_chip < HBM_BYTES, on_chip

"""The training steps lower for the TPU on one device and on four.

A compiled ``pallas_call`` cannot sit bare in a program GSPMD partitions
over more than one device: jax refuses to lower it ("Mosaic kernels
cannot be automatically partitioned"). The CPU mesh never sees that —
off TPU the fused update is an XLA expression and flash attention runs
in the interpreter — so this cross-lowers the chip branch instead:
``utils.platform.on_tpu`` forced true, then
``.trace(...).lower(lowering_platforms=("tpu",))`` on the virtual mesh.
Lowering is all a CPU can check; whether Mosaic compiles the kernels is
``chip_smoke.py``'s job.
"""

import jax
import jax.numpy as jnp
import pytest

from dml_cnn_cifar10_tpu.config import (DataConfig, ModelConfig, OptimConfig,
                                        ParallelConfig)
from dml_cnn_cifar10_tpu.models.registry import get_model
from dml_cnn_cifar10_tpu.parallel import mesh as mesh_lib
from dml_cnn_cifar10_tpu.parallel import step as step_lib
from dml_cnn_cifar10_tpu.utils import platform as platform_lib
from dml_cnn_cifar10_tpu.utils.profiling import abstractify

BATCH = 8


@pytest.fixture
def chip_branch(monkeypatch):
    """Every kernel chooser reads the one switch; flip it."""
    monkeypatch.setattr(platform_lib, "on_tpu", lambda: True)


def _mesh(n):
    return mesh_lib.build_mesh(ParallelConfig(), devices=jax.devices()[:n])


def _state_abs(model_def, model_cfg, data_cfg, optim_cfg, mesh, **layout):
    sh = step_lib.train_state_shardings(mesh, model_def, model_cfg,
                                        data_cfg, optim_cfg, **layout)
    state = jax.eval_shape(
        lambda k: step_lib.init_train_state(k, model_def, model_cfg,
                                            data_cfg, optim_cfg),
        jax.random.key(0))
    return sh, jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        state, sh)


def _tpu_text(jitted, *abs_args):
    return jitted.trace(*abs_args).lower(
        lowering_platforms=("tpu",)).as_text()


def _batch_abs(data_cfg, lead=(), batch=BATCH):
    return (jax.ShapeDtypeStruct(
        (*lead, batch, data_cfg.crop_height, data_cfg.crop_width,
         data_cfg.num_channels), jnp.float32),
        jax.ShapeDtypeStruct((*lead, batch), jnp.int32))


@pytest.mark.parametrize("ndev", [1, 4])
def test_cnn_step_keeps_the_fused_update_kernel(chip_branch, ndev, capsys):
    model_def = get_model("cnn")
    model_cfg, data_cfg = ModelConfig(), DataConfig()
    optim_cfg = OptimConfig(momentum=0.9)
    mesh = _mesh(ndev)
    sh, state = _state_abs(model_def, model_cfg, data_cfg, optim_cfg, mesh)
    step = step_lib.make_train_step(model_def, model_cfg, optim_cfg, mesh,
                                    state_sharding=sh)
    text = _tpu_text(step, state, *_batch_abs(data_cfg))
    # One kernel per parameter leaf, on one device and on four.
    assert text.count("tpu_custom_call") == len(jax.tree.leaves(
        state.params))
    said = capsys.readouterr().out
    want = "update=pallas" + (f"/shard_map[{ndev} replicas]"
                              if ndev > 1 else "")
    assert f"[step] train_step on {ndev} device(s): {want} " in said


@pytest.mark.parametrize("ndev", [1, 4])
def test_resident_chunk_keeps_the_fused_update_kernel(chip_branch, ndev):
    model_def = get_model("cnn")
    model_cfg, data_cfg = ModelConfig(), DataConfig()
    optim_cfg = OptimConfig()
    mesh = _mesh(ndev)
    sh, state = _state_abs(model_def, model_cfg, data_cfg, optim_cfg, mesh)
    repl = mesh_lib.replicated(mesh)
    ds_images = jax.device_put(
        jnp.zeros((64, data_cfg.image_height, data_cfg.image_width,
                   data_cfg.num_channels), jnp.uint8), repl)
    ds_labels = jax.device_put(jnp.zeros((64,), jnp.int32), repl)
    chunk = step_lib.make_train_chunk_resident(
        model_def, model_cfg, optim_cfg, mesh, ds_images, ds_labels,
        state_sharding=sh, data_cfg=data_cfg,
        index_stream=(0, BATCH, 2))
    # The builder returns the jitted chunk with the dataset bound.
    text = _tpu_text(chunk.func, *abstractify((ds_images, ds_labels)),
                     state)
    assert text.count("tpu_custom_call") == len(jax.tree.leaves(
        state.params))


@pytest.mark.parametrize("ndev", [1, 4])
def test_cnn_step_pools_are_the_kernels(chip_branch, ndev, capsys):
    """At a batch that fills the lanes of every device, each of the CNN's
    bias + ReLU + pool pairs is two kernels (forward, backward) beside
    the update's one a leaf, on four devices under a ``shard_map`` over
    ``data``; no select-and-scatter is left in the step. The model is
    float32 and the backend (as patched) a TPU at the default precision,
    so the kernels store bfloat16 and the step's line says so."""
    model_def = get_model("cnn")
    model_cfg, data_cfg = ModelConfig(), DataConfig()
    optim_cfg = OptimConfig()
    mesh = _mesh(ndev)
    sh, state = _state_abs(model_def, model_cfg, data_cfg, optim_cfg, mesh)
    step = step_lib.make_train_step(model_def, model_cfg, optim_cfg, mesh,
                                    state_sharding=sh)
    text = _tpu_text(step, state, *_batch_abs(data_cfg, batch=128 * ndev))
    assert "select_and_scatter" not in text
    assert text.count("tpu_custom_call") == 4 + len(jax.tree.leaves(
        state.params))
    want = "pallas" + (f"/shard_map[batch/data x{ndev}]" if ndev > 1 else "")
    assert f" pool={want}, stores bfloat16\n" in capsys.readouterr().out
    # pool1's output and its input's gradient, a device's share of each
    assert "tensor<12x12x64x128xbf16>" in text
    assert "tensor<24x24x64x128xbf16>" in text
    # The same builder at a batch that leaves lanes empty keeps XLA's pool.
    step = step_lib.make_train_step(model_def, model_cfg, optim_cfg, mesh,
                                    state_sharding=sh)
    text = _tpu_text(step, state, *_batch_abs(data_cfg, batch=8 * ndev))
    assert "select_and_scatter" in text
    assert " pool=xla\n" in capsys.readouterr().out


def test_cnn_pools_carry_no_activation_to_the_backward_pass(chip_branch):
    """What the CNN's forward pass keeps for its backward pass at 24x24:
    of the pools the winning tap (int8) and the pooled output as stored
    (bfloat16: a float32 model on a TPU at the default precision), both
    at the pool's output size, and nothing of the size of a convolution's
    activation (XLA's ReLU and max-pool keep it, twice). conv2's input is
    float32 as traced; the chip's compiler reads it from the bfloat16
    array (PERF.md, Findings, PR 31)."""
    from dml_cnn_cifar10_tpu.models import cnn

    cfg, data_cfg, batch = ModelConfig(logit_relu=False), DataConfig(), 128
    params = jax.eval_shape(lambda k: cnn.init_params(k, cfg, data_cfg),
                            jax.random.key(0))
    images = _batch_abs(data_cfg, batch=batch)[0]
    _, vjp = jax.eval_shape(
        lambda p, x: jax.vjp(lambda p: cnn.apply(p, x, cfg), p),
        params, images)
    carried = sorted((str(l.dtype), l.shape) for l in jax.tree.leaves(vjp)
                     if l.ndim == 4 and l.size >= batch * 6 * 6 * 64)
    assert carried == sorted([
        ("int8", (12, 12, 64, batch)), ("bfloat16", (12, 12, 64, batch)),
        ("float32", (batch, 12, 12, 64)),          # conv2's input
        ("int8", (6, 6, 64, batch)), ("bfloat16", (6, 6, 64, batch))])


def test_sharded_update_operands_keep_the_xla_expression(chip_branch,
                                                         capsys):
    """zero1: the moments are data-sharded, the rule keeps the XLA
    expression (no kernel to place), and the builder says so."""
    model_def = get_model("cnn")
    model_cfg, data_cfg = ModelConfig(), DataConfig()
    optim_cfg = OptimConfig(momentum=0.9, optimizer_sharding="zero1")
    mesh = _mesh(4)
    sh, state = _state_abs(model_def, model_cfg, data_cfg, optim_cfg, mesh,
                           zero1=True)
    step = step_lib.make_train_step(model_def, model_cfg, optim_cfg, mesh,
                                    state_sharding=sh)
    text = _tpu_text(step, state, *_batch_abs(data_cfg))
    assert "tpu_custom_call" not in text
    assert "update=xla " in capsys.readouterr().out


@pytest.mark.parametrize("ndev", [1, 4])
@pytest.mark.parametrize("attn", [{}, {"attn_causal": True,
                                       "attn_window": 128}])
def test_vit_step_keeps_flash_and_update_kernels(chip_branch, ndev, attn,
                                                 capsys):
    """64-pixel crops at patch 4 are 256 patch tokens (+cls): past the
    128-token threshold, so attention is the flash kernels, forward and
    both backward passes."""
    model_def = get_model("vit_tiny")
    model_cfg = ModelConfig(name="vit_tiny", vit_depth=2, **attn)
    data_cfg = DataConfig(image_height=64, image_width=64, crop_height=64,
                          crop_width=64)
    optim_cfg = OptimConfig(momentum=0.9)
    mesh = _mesh(ndev)
    sh, state = _state_abs(model_def, model_cfg, data_cfg, optim_cfg, mesh)
    step = step_lib.make_train_step(model_def, model_cfg, optim_cfg, mesh,
                                    state_sharding=sh)
    text = _tpu_text(step, state, *_batch_abs(data_cfg))
    # The depth-scanned block holds the flash forward (lse-emitting) and
    # the dQ and dK/dV kernels once each; the update is one per leaf.
    assert text.count("tpu_custom_call") == 3 + len(jax.tree.leaves(
        state.params))
    said = capsys.readouterr().out
    assert "attention=flash (257 tokens)" in said
    if ndev > 1:
        assert "shard_map[batch/data, heads/model]" in said


#: megablox's jitted launchers, whose own jaxprs are not looked into: they
#: count their row tiles a group with a scatter of floats
#: (``jnp.histogram``), a few numbers a call
_MEGABLOX_LAUNCHERS = ("gmm", "tgmm")


def _float_scatters(jaxpr, under=""):
    """The scopes of every scatter of floats in a jaxpr and the jaxprs it
    holds (a loop's body names its scopes from the loop's own on), except
    inside :data:`_MEGABLOX_LAUNCHERS`."""
    from jax._src import core
    found = []
    for eqn in jaxpr.eqns:
        scope = f"{under}/{eqn.source_info.name_stack}"
        if eqn.primitive.name.startswith("scatter") and jnp.issubdtype(
                eqn.outvars[0].aval.dtype, jnp.floating):
            found.append(scope)
        if eqn.params.get("name") in _MEGABLOX_LAUNCHERS:
            continue
        for sub in core.jaxprs_in_params(eqn.params):
            found += _float_scatters(sub, scope)
    return found


@pytest.mark.parametrize("name, counted", [("gmm", 0), ("tgmm", 0),
                                           ("load", 1)])
def test_float_scatters_skip_only_megablox_launchers(name, counted):
    """A scatter-add into a float vector is counted wherever it lies, a
    per-expert load under ``moe/route`` as much as a row of ``moe/``,
    unless it is inside a jitted function named as megablox's
    launchers."""
    def counts(slot, x):
        return jnp.zeros(8, jnp.float32).at[slot].add(x)
    counts.__name__ = name

    def step(slot, x):
        with jax.named_scope("moe"), jax.named_scope("route"):
            return jax.jit(counts)(slot, x)
    jaxpr = jax.make_jaxpr(step)(jnp.zeros(4, jnp.int32),
                                 jnp.ones(4, jnp.float32))
    found = _float_scatters(jaxpr.jaxpr)
    assert len(found) == counted, found
    assert all("moe/route" in s for s in found)


@pytest.mark.parametrize("ndev, top_k", [(1, 2), (4, 2), (1, 6), (4, 6)])
def test_hybrid_decoder_step_sums_the_experts_rows_by_token(
        chip_branch, ndev, top_k, capsys, tmp_path):
    """At widths of whole tiles on one device, in bfloat16, the experts'
    three grouped products are megablox's ``gmm`` / ``tgmm`` kernels and
    their rows reach their tokens through the sum-by-token kernel, forward
    and backward, once an expert layer (the buffer holds every block at
    this size, so no further round is built), at 6 choices a token on 8
    slots; on a mesh of four the same step keeps ``lax.ragged_dot`` and
    the XLA expression. Either way no scatter of floats is left under
    ``moe/``: the embedding's gradient is the step's only one."""
    import json

    from dml_cnn_cifar10_tpu.models import hybrid_decoder
    path = tmp_path / "sizes.json"
    # at 6 choices all 8 experts are held, so that the buffer holds every
    # block of the 768 rows and one round is built
    path.write_text(json.dumps({**hybrid_decoder.SMALL, "hidden_size": 1024,
                                "head_dim": 128,
                                "moe_intermediate_size": 256,
                                "num_experts_per_tok": top_k,
                                "num_experts": {2: 4, 6: 8}[top_k]}))
    model_def = get_model("hybrid_decoder")
    model_cfg = ModelConfig(name="hybrid_decoder", remat=True,
                            compute_dtype="bfloat16", config_file=str(path))
    data_cfg = DataConfig(dataset="tokens_synth", sequence_length=32)
    optim_cfg = OptimConfig(optimizer="adamw")
    mesh = _mesh(ndev)
    sh, state = _state_abs(model_def, model_cfg, data_cfg, optim_cfg, mesh)
    step = step_lib.make_train_step(model_def, model_cfg, optim_cfg, mesh,
                                    state_sharding=sh)
    batch = (model_def.batch_shape(model_cfg, data_cfg, 4 * ndev),
             jax.ShapeDtypeStruct((4 * ndev,), jnp.int32))
    traced = step.trace(state, *batch)
    text = traced.lower(lowering_platforms=("tpu",)).as_text()
    layers = hybrid_decoder.SMALL["num_hidden_layers"] \
        - hybrid_decoder.SMALL["num_dense_layers"]
    # 4 x 32 tokens of 2 choices: one block of 256 rows; of 6, three
    want = "pallas gmm (256,1024,256) (256,256,1024), pallas sum-by-token" \
        + {2: "", 6: " (6 of 8 slots)"}[top_k] \
        if ndev == 1 else "ragged_dot, xla (mesh)"
    assert f" experts={want}\n" in capsys.readouterr().out
    # (the jitted launcher is one function a distinct trace, called from
    # each place that takes it)
    assert ("tpu_custom_call" in text) == (ndev == 1)
    assert text.count("call @sum_rows_pallas") \
        == (2 * layers if ndev == 1 else 0)
    # a layer's forward loop runs three products, its backward loop the
    # three again, their three input gradients and three weight gradients
    assert text.count("call @gmm") == (9 * layers if ndev == 1 else 0)
    assert text.count("call @tgmm") == (3 * layers if ndev == 1 else 0)
    assert ("ragged_dot" in text) == (ndev > 1)
    scatters = _float_scatters(traced.jaxpr.jaxpr)
    assert len(scatters) == 1 and "embed" in scatters[0], scatters
    assert not [s for s in scatters if "moe" in s]

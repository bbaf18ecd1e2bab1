"""ResNet-18/50 model tests: shapes, param counts, BN state semantics,
cross-replica parity between auto-jit and explicit shard_map SPMD."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dml_cnn_cifar10_tpu.config import (DataConfig, ModelConfig, OptimConfig,
                                        ParallelConfig)
from dml_cnn_cifar10_tpu.models import resnet
from dml_cnn_cifar10_tpu.models.registry import get_model
from dml_cnn_cifar10_tpu.parallel import mesh as mesh_lib
from dml_cnn_cifar10_tpu.parallel import step as step_lib


def _cfgs(name="resnet18", classes=10):
    return (ModelConfig(name=name, num_classes=classes, logit_relu=False),
            DataConfig())


def _batch(rng, n=16, hw=24):
    images = rng.normal(0.0, 1.0, (n, hw, hw, 3)).astype(np.float32)
    labels = rng.integers(0, 10, n).astype(np.int32)
    return images, labels


@pytest.fixture(scope="module")
def r18():
    cfg, data = _cfgs()
    params = resnet.init_params(jax.random.key(0), cfg, data, depth=18)
    state = resnet.init_state(params)
    return cfg, data, params, state


@pytest.mark.slow
def test_resnet18_shapes_and_params(r18):
    cfg, data, params, state = r18
    rng = np.random.default_rng(0)
    images, _ = _batch(rng)
    logits, new_state = resnet.apply(params, state, jnp.asarray(images), cfg,
                                     train=True)
    assert logits.shape == (16, 10)
    assert logits.dtype == jnp.float32
    # torchvision resnet18 is 11.69M with a 7x7 stem; the CIFAR 3x3 stem
    # drops ~9.4k stem weights => ~11.18M
    n = resnet.param_count(params)
    assert 11_000_000 < n < 11_300_000, n
    # state tree must be structurally identical in and out (no silent
    # recompile on step 2)
    assert (jax.tree.structure(state) == jax.tree.structure(new_state))


@pytest.mark.slow
def test_resnet50_bottleneck_shapes():
    cfg, data = _cfgs("resnet50")
    params = resnet.init_params(jax.random.key(0), cfg, data, depth=50)
    state = resnet.init_state(params)
    rng = np.random.default_rng(0)
    images, _ = _batch(rng, n=4)
    logits, _ = resnet.apply(params, state, jnp.asarray(images), cfg,
                             train=True)
    assert logits.shape == (4, 10)
    n = resnet.param_count(params)
    # torchvision resnet50 = 25.56M with a 1000-class head (2048x1000 =
    # 2.05M); the 10-class head drops that to ~23.5M
    assert 23_400_000 < n < 23_700_000, n


@pytest.mark.slow
def test_imagenet_stem_for_large_inputs():
    cfg, _ = _cfgs("resnet50")
    data = DataConfig(image_height=224, image_width=224, crop_height=224,
                      crop_width=224)
    params = resnet.init_params(jax.random.key(0), cfg, data, depth=50)
    assert params["stem"]["conv"].shape == (7, 7, 3, 64)
    state = resnet.init_state(params)
    images = np.random.default_rng(0).normal(
        0, 1, (2, 224, 224, 3)).astype(np.float32)
    logits, _ = resnet.apply(params, state, jnp.asarray(images), cfg,
                             train=False)
    assert logits.shape == (2, 10)
    assert resnet.param_count(params) > 23_400_000


def test_bn_state_updates_in_train_frozen_in_eval(r18):
    cfg, data, params, state = r18
    rng = np.random.default_rng(1)
    images, _ = _batch(rng)
    _, ns_train = resnet.apply(params, state, jnp.asarray(images), cfg,
                               train=True)
    stem0 = state["stem"]["bn"]["mean"]
    stem1 = ns_train["stem"]["bn"]["mean"]
    assert not np.allclose(stem0, stem1), "train must move running stats"
    _, ns_eval = resnet.apply(params, state, jnp.asarray(images), cfg,
                              train=False)
    chex_equal = jax.tree.map(
        lambda a, b: np.array_equal(np.asarray(a), np.asarray(b)),
        state, ns_eval)
    assert all(jax.tree.leaves(chex_equal)), "eval must not touch stats"


def test_eval_deterministic_batch_independent(r18):
    """Eval uses running stats: each example's logits must not depend on the
    rest of the batch."""
    cfg, data, params, state = r18
    rng = np.random.default_rng(2)
    images, _ = _batch(rng, n=8)
    full, _ = resnet.apply(params, state, jnp.asarray(images), cfg,
                           train=False)
    half, _ = resnet.apply(params, state, jnp.asarray(images[:4]), cfg,
                           train=False)
    np.testing.assert_allclose(np.asarray(full)[:4], np.asarray(half),
                               rtol=1e-5, atol=1e-5)


def test_gamma_zero_blocks_start_as_identity(r18):
    """Residual branches are gamma-zero-initialized, so at init the net is
    stem + projections only — logits finite and loss ~= log(10)."""
    cfg, data, params, state = r18
    rng = np.random.default_rng(3)
    images, labels = _batch(rng)
    logits, _ = resnet.apply(params, state, jnp.asarray(images), cfg,
                             train=True)
    assert np.isfinite(np.asarray(logits)).all()
    from dml_cnn_cifar10_tpu.train.loss import softmax_cross_entropy
    loss = softmax_cross_entropy(logits, jnp.asarray(labels))
    assert abs(float(loss) - np.log(10)) < 1.0


@pytest.mark.slow
def test_explicit_shard_map_matches_auto_jit():
    """Cross-replica BN: shard_map with axis_name pmean of (E[x],E[x²]) must
    produce the same update as jit auto-partitioning's global batch stats."""
    model_def = get_model("resnet18")
    cfg, data = _cfgs()
    optim = OptimConfig(learning_rate=0.05, dead_lr_decay=False)
    mesh = mesh_lib.build_mesh(ParallelConfig())
    rng = np.random.default_rng(4)
    images, labels = _batch(rng, n=32)
    im, lb = mesh_lib.shard_batch(mesh, images, labels)

    results = []
    for explicit in (False, True):
        st = step_lib.init_train_state(jax.random.key(0), model_def, cfg,
                                       data, optim, mesh)
        train = step_lib.make_train_step(model_def, cfg, optim, mesh,
                                         explicit_collectives=explicit)
        st, metrics = train(st, im, lb)
        results.append((st, metrics))

    (s_auto, m_auto), (s_exp, m_exp) = results
    np.testing.assert_allclose(float(m_auto["loss"]), float(m_exp["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s_auto.params),
                    jax.tree.leaves(s_exp.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=5e-5)
    # BN running stats must agree too (the pmean'd sufficient statistics)
    for a, b in zip(jax.tree.leaves(s_auto.model_state),
                    jax.tree.leaves(s_exp.model_state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=5e-5)


@pytest.mark.slow
def test_two_steps_no_structure_change():
    """Treedef stability: step 2 reuses the compiled step (same structure)."""
    model_def = get_model("resnet18")
    cfg, data = _cfgs()
    optim = OptimConfig(learning_rate=0.05)
    st = step_lib.init_train_state(jax.random.key(0), model_def, cfg, data,
                                   optim)
    train = step_lib.make_train_step(model_def, cfg, optim)
    rng = np.random.default_rng(5)
    for _ in range(2):
        images, labels = _batch(rng)
        st, metrics = train(st, jnp.asarray(images), jnp.asarray(labels))
    assert int(st.step) == 2
    assert np.isfinite(float(metrics["loss"]))


def test_s2d_stem_folded_kernel_equivalence():
    """The space-to-depth stem (--resnet_s2d) computes the SAME function
    as the 7x7/2 stem when the 7x7 kernel is folded into the 4x4x(4C)
    parameterization (zero-pad to 8x8; ws[m,n,(a,b,c)] = w8[2m+a,2n+b,c]
    with the XLA SAME pad lo=2 mapping to folded pad (1,2)) — the MLPerf
    transform is a re-parameterization, not a different model."""
    from dml_cnn_cifar10_tpu.models import resnet

    cfg7 = ModelConfig(name="resnet50", logit_relu=False)
    cfgs = ModelConfig(name="resnet50", logit_relu=False, resnet_s2d=True)
    data = DataConfig(crop_height=96, crop_width=96, num_classes=10)
    k = jax.random.key(0)
    p7 = resnet.init_params(k, cfg7, data, depth=50)
    ps = resnet.init_params(k, cfgs, data, depth=50)
    assert ps["stem"]["conv"].shape == (4, 4, 12, 64)

    w7 = np.asarray(p7["stem"]["conv"])
    w8 = np.zeros((8, 8, 3, 64), np.float32)
    w8[:7, :7] = w7
    ws = np.zeros((4, 4, 12, 64), np.float32)
    for m in range(4):
        for n in range(4):
            for a in range(2):
                for b in range(2):
                    ws[m, n, a * 6 + b * 3: a * 6 + b * 3 + 3] = \
                        w8[2 * m + a, 2 * n + b]
    ps["stem"]["conv"] = jnp.asarray(ws)

    x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (2, 96, 96, 3)),
                    jnp.float32)
    o7, _ = resnet.apply(p7, resnet.init_state(p7), x, cfg7, train=True)
    os_, _ = resnet.apply(ps, resnet.init_state(ps), x, cfgs, train=True)
    np.testing.assert_allclose(np.asarray(os_), np.asarray(o7), atol=1e-4)


def test_nf_resnet_init_structure_and_identity_start():
    """--resnet_norm=nf: no BN anywhere (state is all-None), weight-
    standardized convs + SkipInit zero scalar make every residual block
    start as identity + projection — the NF analog of gamma-zero BN."""
    cfg = ModelConfig(name="resnet18", logit_relu=False, resnet_norm="nf")
    data = DataConfig()
    params = resnet.init_params(jax.random.key(0), cfg, data, depth=18)
    state = resnet.init_state(params)
    assert all(leaf is None for leaf in jax.tree.leaves(
        state, is_leaf=lambda x: x is None))
    blk = params["stage1"][0]
    assert "bn1" not in blk and "skip_gain" in blk
    assert float(blk["skip_gain"]) == 0.0
    # Identity start: a non-projection block must pass relu(x) through.
    x = jnp.abs(jax.random.normal(jax.random.key(1), (2, 8, 8, 64))) + 0.1
    out, ns = resnet._nf_basic_block(x, blk, None, 1, cfg, True, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), rtol=1e-6)
    assert set(ns) == set(blk)


@pytest.mark.slow
def test_nf_resnet_trains_and_state_is_stateless():
    """The nf rung trains (loss decreases over a few steps) with the
    standard step machinery; model_state carries no running stats."""
    from dml_cnn_cifar10_tpu.parallel import shardings

    data = DataConfig(normalize="scale")
    cfg = ModelConfig(name="resnet18", logit_relu=False, resnet_norm="nf")
    mesh = mesh_lib.build_mesh(ParallelConfig(data_axis=8))
    model_def = get_model("resnet18")
    optim = OptimConfig(learning_rate=0.05)
    sh = step_lib.train_state_shardings(mesh, model_def, cfg, data, optim)
    state = step_lib.init_train_state(jax.random.key(0), model_def, cfg,
                                      data, optim, mesh, state_sharding=sh)
    train = step_lib.make_train_step(model_def, cfg, optim, mesh,
                                     state_sharding=sh)
    rng = np.random.default_rng(0)
    images, labels = _batch(rng, n=32)
    im, lb = mesh_lib.shard_batch(mesh, images, labels)
    losses = []
    for _ in range(6):
        state, m = train(state, im, lb)
        losses.append(float(jax.device_get(m["loss"])))
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses
    assert not jax.tree.leaves(state.model_state)  # truly stateless


def test_nf_weight_standardization_properties():
    """_ws_conv output has zero mean and 1/fan_in variance per output
    channel (times gain^2) — the scaled-WS contract."""
    w = jax.random.normal(jax.random.key(0), (3, 3, 16, 32)) * 2.0 + 0.5
    g = jnp.full((32,), 1.5)
    ws = resnet._ws_conv(w, g)
    mu = np.asarray(jnp.mean(ws, axis=(0, 1, 2)))
    np.testing.assert_allclose(mu, 0.0, atol=1e-6)
    var = np.asarray(jnp.var(ws, axis=(0, 1, 2)))
    fan_in = 3 * 3 * 16
    np.testing.assert_allclose(var, 1.5**2 / fan_in, rtol=1e-3)


def test_lowered_text_holds_every_layer_scope():
    """Stem, every block's convolutions, batch norms, shortcut and sum,
    and the head are each under a ``jax.named_scope`` (tracing only: the
    ImageNet stem at 72 px, bottlenecks)."""
    from dml_cnn_cifar10_tpu.utils import devprof

    cfg = ModelConfig(name="resnet50", num_classes=10, logit_relu=False)
    data = DataConfig(crop_height=72, crop_width=72)
    params = jax.eval_shape(
        lambda k: resnet.init_params(k, cfg, data, depth=50),
        jax.random.key(0))
    state = jax.eval_shape(resnet.init_state, params)
    x = jax.ShapeDtypeStruct((2, 72, 72, 3), jnp.float32)
    text = jax.jit(lambda p, s, x: resnet.apply(p, s, x, cfg, train=True)
                   ).lower(params, state, x).as_text(debug_info=True)
    want = ["stem/conv", "stem/bn", "stem/pool", "head/pool", "head/fc"]
    for si, n in enumerate((3, 4, 6, 3), start=1):
        for bi in range(n):
            want += [f"stage{si}/block{bi}/{leaf}" for leaf in (
                "conv1", "bn1", "conv2", "bn2", "conv3", "bn3", "add")]
        want += [f"stage{si}/block0/shortcut/conv",
                 f"stage{si}/block0/shortcut/bn"]
    for scope in want:
        assert f"/{scope}/" in text, scope
        assert devprof.parse_op_name(f"jit(f)/{scope}/op")[1] != "none"
    assert "stage1/block1/shortcut" not in text
    kinds = {devprof.parse_op_name(f"jit(f)/{s}/op")[1] for s in want}
    assert kinds == {"conv", "norm_act", "pool", "dense"}


def test_logits_are_bit_equal_without_the_scopes(monkeypatch):
    """Scopes are metadata: no numeric effect, on logits or BN state."""
    import contextlib

    cfg, data = _cfgs()
    params = resnet.init_params(jax.random.key(0), cfg, data, depth=18)
    state = resnet.init_state(params)
    images, _ = _batch(np.random.default_rng(0), n=2, hw=16)
    run = jax.jit(lambda p, s, x: resnet.apply(p, s, x, cfg, train=True))
    with_scopes = run(params, state, jnp.asarray(images))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    run = jax.jit(lambda p, s, x: resnet.apply(p, s, x, cfg, train=True))
    without = run(params, state, jnp.asarray(images))
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(with_scopes), jax.tree.leaves(without)))

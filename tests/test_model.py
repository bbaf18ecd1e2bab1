"""Model unit tests: shapes, param counts, init statistics, quirk switches
(SURVEY §4)."""

import pytest
import jax
import jax.numpy as jnp
import numpy as np

from dml_cnn_cifar10_tpu.config import DataConfig, ModelConfig
from dml_cnn_cifar10_tpu.models import cnn
from dml_cnn_cifar10_tpu.ops import layers as L


def test_param_shapes_and_count():
    """24x24 input → two 3x3/2 SAME pools → 6x6x64 = 2304 flatten, exactly
    the reference's hardcoded reshaped_dim (cifar10cnn.py:126-131)."""
    params = cnn.init_params(jax.random.key(0), ModelConfig(), DataConfig())
    assert params["conv1"]["kernel"].shape == (5, 5, 3, 64)
    assert params["conv2"]["kernel"].shape == (5, 5, 64, 64)
    assert params["full1"]["kernel"].shape == (2304, 384)
    assert params["full2"]["kernel"].shape == (384, 192)
    assert params["full3"]["kernel"].shape == (192, 10)
    want = (5*5*3*64 + 64) + (5*5*64*64 + 64) + (2304*384 + 384) \
        + (384*192 + 192) + (192*10 + 10)
    assert cnn.param_count(params) == want


def test_init_statistics():
    """Truncated normal sigma=0.05 within ±2 sigma (cifar10cnn.py:97-98),
    biases constant 0.1 (cifar10cnn.py:100-101)."""
    params = cnn.init_params(jax.random.key(1), ModelConfig(), DataConfig())
    w = np.asarray(params["full1"]["kernel"]).ravel()
    assert np.abs(w).max() <= 0.1 + 1e-6          # hard truncation at 2 sigma
    assert abs(w.mean()) < 2e-3
    assert 0.03 < w.std() < 0.05                  # truncated std ≈ 0.88*sigma
    assert np.allclose(params["conv1"]["bias"], 0.1)


def test_forward_shape_and_faithful_logit_relu():
    data, model = DataConfig(), ModelConfig(logit_relu=True)
    params = cnn.init_params(jax.random.key(0), model, data)
    x = jnp.asarray(np.random.default_rng(0).normal(
        127, 50, (4, 24, 24, 3)).astype(np.float32))
    logits = cnn.apply(params, x, model)
    assert logits.shape == (4, 10)
    assert (logits >= 0).all()                    # faithful: ReLU'd logits

    fixed = ModelConfig(logit_relu=False)
    raw = cnn.apply(params, x, fixed)
    assert (raw < 0).any()                        # fixed mode exposes negatives
    np.testing.assert_allclose(jax.nn.relu(raw), logits, rtol=1e-5)


def test_full_resolution_input_changes_flatten_dim():
    """Config-driven flatten (no hardcoded 2304): 32x32 input → 8x8x64."""
    data = DataConfig(crop_height=32, crop_width=32)
    params = cnn.init_params(jax.random.key(0), ModelConfig(), data)
    assert params["full1"]["kernel"].shape == (4096, 384)
    x = jnp.zeros((2, 32, 32, 3))
    assert cnn.apply(params, x, ModelConfig()).shape == (2, 10)


def test_cifar100_head_swap():
    model = ModelConfig(num_classes=100)
    params = cnn.init_params(jax.random.key(0), model, DataConfig())
    assert params["full3"]["kernel"].shape == (192, 100)
    x = jnp.zeros((2, 24, 24, 3))
    assert cnn.apply(params, x, model).shape == (2, 100)


def test_max_pool_matches_reference_semantics():
    """3x3 window stride 2 SAME (cifar10cnn.py:113): 24→12, overlapping max."""
    x = jnp.arange(16, dtype=jnp.float32).reshape(1, 4, 4, 1)
    out = L.max_pool(x)
    assert out.shape == (1, 2, 2, 1)
    # windows centered per SAME/stride2: max over x[0:3,0:3] = 10
    assert float(out[0, 0, 0, 0]) == 10.0
    assert float(out[0, 1, 1, 0]) == 15.0


def test_conv2d_matches_manual_nhwc():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 5, 5, 2)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(3, 3, 2, 4)).astype(np.float32))
    out = L.conv2d(x, k)
    assert out.shape == (1, 5, 5, 4)
    # centre output pixel = full 3x3 valid correlation at that location
    want = np.einsum("hwc,hwco->o", np.asarray(x)[0, 1:4, 1:4], np.asarray(k))
    np.testing.assert_allclose(np.asarray(out)[0, 2, 2], want,
                               rtol=1e-2, atol=1e-2)


@pytest.mark.slow
def test_bfloat16_compute_path():
    model = ModelConfig(compute_dtype="bfloat16")
    params = cnn.init_params(jax.random.key(0), model, DataConfig())
    x = jnp.ones((2, 24, 24, 3))
    logits = cnn.apply(params, x, model)
    assert logits.dtype == jnp.float32             # outputs upcast for loss
    ref = cnn.apply(params, x, ModelConfig())
    np.testing.assert_allclose(logits, ref, rtol=0.1, atol=2.0)


def _scopes_off(monkeypatch):
    import contextlib
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())


CNN_SCOPES = ("conv1", "pool1", "conv2", "pool2", "fc1", "fc2", "logits")


def test_lowered_text_holds_every_layer_scope():
    """Each layer of the model is under its own ``jax.named_scope``: the
    names reach the lowered program's locations, which is where
    ``utils/devprof.scope_map`` reads a compiled instruction's layer."""
    from dml_cnn_cifar10_tpu.utils import devprof

    cfg, data = ModelConfig(logit_relu=False), DataConfig()
    params = cnn.init_params(jax.random.key(0), cfg, data)
    x = jnp.zeros((2, data.crop_height, data.crop_width, 3), jnp.float32)
    text = jax.jit(lambda p, x: cnn.apply(p, x, cfg)).lower(
        params, x).as_text(debug_info=True)
    for scope in CNN_SCOPES:
        assert f"/{scope}/" in text or f"/{scope}\"" in text, scope
        # and the table of kinds knows each of them
        assert devprof.parse_op_name(f"jit(f)/{scope}/op")[1] != "none"


def test_logits_are_bit_equal_without_the_scopes(monkeypatch):
    """Scopes are metadata: no numeric effect."""
    cfg, data = ModelConfig(logit_relu=False), DataConfig()
    params = cnn.init_params(jax.random.key(0), cfg, data)
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(4, data.crop_height, data.crop_width, 3)), jnp.float32)
    with_scopes = np.asarray(cnn.apply(params, x, cfg))
    _scopes_off(monkeypatch)
    without = np.asarray(cnn.apply(params, x, cfg))
    assert np.array_equal(with_scopes, without)

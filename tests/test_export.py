"""Serving export (jax.export) + the eval/export CLI modes.

The reference's only artifact is its checkpoint dir (``cifar10cnn.py:222``)
— no deployment story. ``export.py`` serializes the trained forward
(weights embedded, uint8 input contract, symbolic batch) to StableHLO
bytes loadable without the framework.
"""

import numpy as np
import pytest

import jax

from dml_cnn_cifar10_tpu import export as export_lib
from dml_cnn_cifar10_tpu.config import DataConfig, ModelConfig
from dml_cnn_cifar10_tpu.models.registry import get_model


@pytest.fixture(scope="module")
def cnn_setup():
    model_def = get_model("cnn")
    model_cfg = ModelConfig(logit_relu=False)
    data_cfg = DataConfig(normalize="scale")
    params = model_def.init(jax.random.key(0), model_cfg, data_cfg)
    return model_def, model_cfg, data_cfg, params


def test_export_roundtrip_matches_live_forward(tmp_path, cnn_setup, rng):
    model_def, model_cfg, data_cfg, params = cnn_setup
    blob = export_lib.export_forward(model_def, model_cfg, data_cfg, params)
    path = str(tmp_path / "model.jaxexport")
    export_lib.save_exported(path, blob)

    served = export_lib.load_exported(path)
    images = rng.integers(0, 256, (4, 32, 32, 3)).astype(np.uint8)
    got = np.asarray(jax.device_get(served(images)))

    live = export_lib.make_serving_fn(model_def, model_cfg, data_cfg,
                                      params)
    want = np.asarray(jax.device_get(jax.jit(live)(images)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert got.shape == (4, 10)


def test_export_symbolic_batch(cnn_setup, rng):
    """One artifact serves any batch size (symbolic leading dim)."""
    model_def, model_cfg, data_cfg, params = cnn_setup
    blob = export_lib.export_forward(model_def, model_cfg, data_cfg, params)
    served = export_lib.load_exported_bytes(blob)
    for b in (1, 4, 7):
        images = rng.integers(0, 256, (b, 32, 32, 3)).astype(np.uint8)
        out = np.asarray(jax.device_get(served(images)))
        assert out.shape == (b, 10)


@pytest.mark.slow
def test_export_resnet_with_bn_state(rng):
    """Stateful models (BatchNorm running stats) export too."""
    model_def = get_model("resnet18")
    model_cfg = ModelConfig(name="resnet18", logit_relu=False)
    data_cfg = DataConfig(normalize="scale")
    params = model_def.init(jax.random.key(0), model_cfg, data_cfg)
    mstate = model_def.init_state(params, model_cfg)
    blob = export_lib.export_forward(model_def, model_cfg, data_cfg, params,
                                     model_state=mstate)
    served = export_lib.load_exported_bytes(blob)
    images = rng.integers(0, 256, (2, 32, 32, 3)).astype(np.uint8)
    out = np.asarray(jax.device_get(served(images)))
    assert out.shape == (2, 10)
    assert np.isfinite(out).all()


@pytest.mark.slow
def test_cli_eval_and_export_modes(tmp_path, capsys):
    """--mode train then --mode eval (full sweep, reference format line)
    then --mode export (artifact on disk, loadable)."""
    from dml_cnn_cifar10_tpu.cli.main import main

    data_dir = str(tmp_path / "data")
    log_dir = str(tmp_path / "logs")
    common = ["--dataset", "synthetic", "--data_dir", data_dir,
              "--log_dir", log_dir, "--batch_size", "32",
              "--use_native_loader", "false", "--fidelity", "fixed",
              "--learning_rate", "0.02"]
    assert main(common + ["--total_steps", "6", "--output_every", "2",
                          "--eval_every", "3", "--checkpoint_every",
                          "6"]) == 0
    capsys.readouterr()

    assert main(common + ["--mode", "eval"]) == 0
    out = capsys.readouterr().out
    assert " --- Test Accuracy = " in out
    assert "eval at step 6" in out

    path = str(tmp_path / "m.jaxexport")
    assert main(common + ["--mode", "export", "--export_path", path]) == 0
    out = capsys.readouterr().out
    assert "exported step-6 forward" in out
    served = export_lib.load_exported(path)
    images = np.zeros((2, 32, 32, 3), np.uint8)
    assert np.asarray(jax.device_get(served(images))).shape == (2, 10)

"""Test env: simulate an 8-device mesh on CPU (SURVEY §4) before jax loads."""

import os

# The test suite runs on the 8-virtual-device CPU mesh: JAX_PLATFORMS=cpu
# alone would give one device, so the device-count XLA flag is set here,
# before jax initializes a backend.
from dml_cnn_cifar10_tpu.utils.platform import force_cpu

force_cpu(virtual_devices=8)

import jax  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from dml_cnn_cifar10_tpu.config import DataConfig, TrainConfig  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy integration test (multi-process runs, long compiles, "
        "full Trainer e2e). The smoke pass excludes them: "
        "pytest -m 'not slow' finishes in ~1-2 min; the full suite runs "
        "everything (ARCHITECTURE §7).")


@pytest.fixture(scope="session")
def synth_data_dir(tmp_path_factory) -> str:
    return str(tmp_path_factory.mktemp("cifar_synth"))


@pytest.fixture(scope="session")
def data_cfg(synth_data_dir) -> DataConfig:
    """Small synthetic CIFAR-format dataset, generated once per session."""
    cfg = DataConfig(
        dataset="synthetic",
        data_dir=synth_data_dir,
        synthetic_train_records=640,
        synthetic_test_records=160,
        shuffle_buffer=256,
        use_native_loader=False,
    )
    from dml_cnn_cifar10_tpu.data import ensure_dataset
    ensure_dataset(cfg)
    return cfg


def tiny_train_cfg(data_cfg: DataConfig, tmpdir: str, **kw) -> TrainConfig:
    """Small, numerically tame config: the faithful LR-0.1-on-raw-pixels
    combination NaNs within steps (a reference property), so integration
    tests normalize inputs and drop the LR."""
    import dataclasses
    cfg = TrainConfig(
        batch_size=32,
        total_steps=40,
        output_every=10,
        eval_every=20,
        checkpoint_every=20,
        log_dir=os.path.join(tmpdir, "logs"),
        data=dataclasses.replace(data_cfg, normalize="scale"),
    )
    cfg.optim.learning_rate = 0.05
    cfg.model.logit_relu = False
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


@pytest.fixture
def rng():
    return np.random.default_rng(0)

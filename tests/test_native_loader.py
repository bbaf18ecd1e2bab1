"""Native C++ loader tests: decode parity with the NumPy path, bounded
shuffle-pool semantics, label/pixel integrity, error paths."""

import os

import numpy as np
import pytest

from dml_cnn_cifar10_tpu.config import DataConfig
from dml_cnn_cifar10_tpu.data import download, native
from dml_cnn_cifar10_tpu.data import pipeline as pipe
from dml_cnn_cifar10_tpu.data import records as rec


@pytest.fixture(scope="module")
def lib():
    return native.load_library()


def _native_it(data_cfg, batch_size=32, **kw):
    files = download.train_files(data_cfg)
    return native.NativeShuffleBatchIterator(files, data_cfg, batch_size,
                                             **kw)


def test_library_builds_and_loads(lib):
    assert lib is not None


def test_batch_shapes_and_ranges(data_cfg):
    it = _native_it(data_cfg)
    batch = next(it)
    assert batch.images.shape == (32, 24, 24, 3)
    assert batch.images.dtype == np.float32
    assert batch.labels.shape == (32,)
    assert batch.labels.dtype == np.int32
    assert (batch.labels >= 0).all() and (batch.labels < 10).all()
    assert 0.0 <= batch.images.min() and batch.images.max() <= 255.0
    it.close()


def test_decode_parity_with_numpy(data_cfg):
    """Every (label, decoded image) pair the native loader emits must exist
    in the NumPy-decoded split — bitwise (uint8 decode + same center
    crop)."""
    it = _native_it(data_cfg, batch_size=64)
    # Reference decode of the whole split, cropped the same way.
    ref_imgs = rec.center_crop(it.images.astype(np.float32), 24, 24)
    # Index reference images by label for fast membership check.
    by_label = {}
    for i in range(ref_imgs.shape[0]):
        by_label.setdefault(int(it.labels[i]), []).append(ref_imgs[i])
    batch = next(it)
    for img, lab in zip(batch.images, batch.labels):
        candidates = by_label.get(int(lab), [])
        assert any(np.array_equal(img, c) for c in candidates), (
            "native-decoded image not found in NumPy-decoded split "
            f"(label {lab})")
    it.close()


def test_bounded_pool_reaches_min_after(data_cfg):
    it = _native_it(data_cfg, batch_size=8)
    next(it)  # first dequeue waits for min_after
    assert it.buffered() >= 1
    it.close()


def test_stream_is_shuffled_and_endless(data_cfg):
    """More batches than the dataset holds (endless epochs), and two
    differently-seeded streams disagree on order."""
    n_total = data_cfg.synthetic_train_records
    it1 = _native_it(data_cfg, batch_size=64, seed=1)
    it2 = _native_it(data_cfg, batch_size=64, seed=2)
    l1, l2 = [], []
    for _ in range(n_total // 64 + 3):  # > one epoch
        l1.append(next(it1).labels)
        l2.append(next(it2).labels)
    l1, l2 = np.concatenate(l1), np.concatenate(l2)
    assert not np.array_equal(l1, l2), "different seeds must differ"
    # Long-run label distribution should cover all classes.
    assert len(np.unique(l1)) == 10
    it1.close()
    it2.close()


def test_create_rejects_bad_geometry(lib, data_cfg):
    files = download.train_files(data_cfg)
    paths = b"\0".join(p.encode() for p in files) + b"\0"
    handle = lib.recordio_create(paths, len(files), 3073, 1, 0,
                                 32, 32, 3, 100, 50, 7, 0)  # min_after>capacity
    assert not handle


def test_missing_file_surfaces_error(lib):
    import ctypes
    paths = b"/nonexistent/nope.bin\0"
    handle = lib.recordio_create(paths, 1, 3073, 1, 0, 32, 32, 3, 10, 50, 7, 0)
    assert handle
    imgs = np.empty((8, 32, 32, 3), np.uint8)
    labs = np.empty((8,), np.int32)
    ret = lib.recordio_next_batch(
        handle, 8, imgs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        labs.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    assert ret == -1
    assert b"cannot open" in lib.recordio_error(handle)
    lib.recordio_destroy(handle)


def test_empty_record_files_surface_error(lib, tmp_path):
    """Files that exist but hold zero complete records must error, not hang
    the consumer while the producer spins epochs."""
    import ctypes
    f = tmp_path / "empty.bin"
    f.write_bytes(b"\x01" * 100)  # < one 3073-byte record
    paths = str(f).encode() + b"\0"
    handle = lib.recordio_create(paths, 1, 3073, 1, 0, 32, 32, 3, 10, 50, 7, 0)
    assert handle
    imgs = np.empty((4, 32, 32, 3), np.uint8)
    labs = np.empty((4,), np.int32)
    ret = lib.recordio_next_batch(
        handle, 4, imgs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        labs.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    assert ret == -1
    assert b"no complete records" in lib.recordio_error(handle)
    lib.recordio_destroy(handle)


def test_closed_iterator_raises(data_cfg):
    it = _native_it(data_cfg, batch_size=8)
    next(it)
    it.close()
    with pytest.raises(RuntimeError, match="closed"):
        next(it)
    with pytest.raises(RuntimeError, match="closed"):
        it.buffered()


def test_pipeline_uses_native_when_enabled(data_cfg):
    import dataclasses
    cfg = dataclasses.replace(data_cfg, use_native_loader=True)
    it = pipe.input_pipeline(cfg, 16, train=True)
    assert isinstance(it, native.NativeShuffleBatchIterator)
    batch = next(it)
    assert batch.images.shape == (16, 24, 24, 3)
    it.close()


def test_wide_label_decode_parity(tmp_path):
    """imagenet_synth wide labels (big-endian uint16) through the C++
    pool: every streamed label must be a label that exists in the NumPy
    decode of the same files, and ids past 255 must appear."""
    cfg = DataConfig(dataset="imagenet_synth", data_dir=str(tmp_path),
                     image_height=8, image_width=8, crop_height=8,
                     crop_width=8, num_classes=1000,
                     synthetic_train_records=256,
                     synthetic_test_records=32, shuffle_buffer=64)
    download.generate_synthetic_dataset(cfg)
    imgs, labs = pipe._load_split(download.train_files(cfg), cfg)
    want = set(int(x) for x in labs)
    assert max(want) > 255
    it = _native_it(cfg, batch_size=64)
    seen = set()
    for _ in range(4):
        batch = next(it)
        seen.update(int(x) for x in batch.labels)
    it.close()
    assert seen <= want
    assert max(seen) > 255


def test_stale_abi_fails_loudly(tmp_path, monkeypatch):
    """ADVICE r2: a prebuilt .so that predates an ABI change must be
    rejected at load (the mtime rebuild heuristic can miss, e.g. sources
    absent on a deploy host) — silently mis-bound arguments would decode
    wrong training data."""
    import subprocess

    from dml_cnn_cifar10_tpu.data import native

    src = tmp_path / "stub.cc"
    # A v1-era library: has entry points but no recordio_abi_version.
    src.write_text('extern "C" { void* recordio_create() { return 0; } }\n')
    so = tmp_path / "librecordio.so"
    subprocess.run(["g++", "-shared", "-fPIC", "-o", str(so), str(src)],
                   check=True, capture_output=True)
    monkeypatch.setattr(native, "_LIB_PATH", str(so))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_needs_build", lambda: False)
    with pytest.raises(RuntimeError, match="ABI v1 != expected"):
        native.load_library()


def test_failed_build_is_reported_not_swallowed(data_cfg, tmp_path,
                                                monkeypatch):
    """With ``use_native_loader`` on, a library that cannot be built must
    stop the pipeline with the build's own error — not hand back the
    NumPy iterator as if nothing happened."""
    import dataclasses

    (tmp_path / "Makefile").write_text(
        "all:\n\t@echo 'recordio.cc: no such toolchain' >&2; exit 1\n")
    monkeypatch.setattr(native, "_RUNTIME_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_LIB_PATH",
                        str(tmp_path / "librecordio.so"))
    monkeypatch.setattr(native, "_lib", None)
    cfg = dataclasses.replace(data_cfg, use_native_loader=True)
    with pytest.raises(RuntimeError, match="no such toolchain") as e:
        pipe.input_pipeline(cfg, 16, train=True)
    assert "--use_native_loader false" in str(e.value)

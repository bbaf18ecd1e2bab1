"""ctypes bindings for the native record loader (runtime/recordio.cc).

The C++ side replaces the reference's input-queue runtime — file-order
shuffling, fixed-length record reads, the bounded RandomShuffleQueue
(``min_after_dequeue=5000, capacity=5000+3*batch``,
``cifar10cnn.py:85-90``), and the CHW→HWC decode — all off the GIL on a
producer thread. Python keeps only the batched crop/augment/normalize step
(vectorized NumPy) and the host→device prefetch.

Fidelity note: this is the path that reproduces the reference's *bounded*
shuffle semantics exactly; the pure-NumPy fallback
(:class:`~dml_cnn_cifar10_tpu.data.pipeline.ShuffleBatchIterator`) uses
full-permutation shuffling (strictly stronger mixing). Tests cover both.

The shared library is built on demand with ``make -C runtime`` (g++ only,
no pybind11 — plain C ABI + ctypes).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import List, Optional

import numpy as np

from dml_cnn_cifar10_tpu.config import DataConfig
from dml_cnn_cifar10_tpu.data import download
from dml_cnn_cifar10_tpu.data import pipeline as pipe
from dml_cnn_cifar10_tpu.data import records as rec

_RUNTIME_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "runtime")
_LIB_PATH = os.path.join(_RUNTIME_DIR, "librecordio.so")

_lib = None
_lib_lock = threading.Lock()


def _build_library() -> None:
    # Serialize concurrent builders (multi-process tests on one box): a
    # relink racing another process's dlopen would hand out a truncated
    # .so. fcntl lock on a sidecar file; make itself is then idempotent.
    import fcntl
    lock_path = os.path.join(_RUNTIME_DIR, ".build.lock")
    with open(lock_path, "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        try:
            subprocess.run(["make", "-C", _RUNTIME_DIR], check=True,
                           capture_output=True, text=True)
        except (OSError, subprocess.CalledProcessError) as e:
            raise RuntimeError(
                f"building {_LIB_PATH} with `make -C {_RUNTIME_DIR}` "
                f"failed: {e}\n{getattr(e, 'stderr', '') or ''}\n"
                f"Fix the toolchain, or run with --use_native_loader "
                f"false (DataConfig.use_native_loader=False) to use the "
                f"NumPy iterator.") from e


def _needs_build() -> bool:
    """True when the .so is missing or older than its sources. The
    timestamp check lives HERE (not in an unconditional make) so a host
    with a prebuilt .so and no toolchain never shells out — but a stale
    binary after a recordio.cc edit still rebuilds (loading it against
    newer argtypes would silently mis-decode)."""
    if not os.path.exists(_LIB_PATH):
        return True
    so_mtime = os.path.getmtime(_LIB_PATH)
    for src in ("recordio.cc", "Makefile"):
        path = os.path.join(_RUNTIME_DIR, src)
        if os.path.exists(path) and os.path.getmtime(path) > so_mtime:
            return True
    return False


def load_library() -> ctypes.CDLL:
    """Load (building if needed) librecordio.so; raises on failure."""
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        if _needs_build():
            _build_library()
        lib = ctypes.CDLL(_LIB_PATH)
        # ABI handshake before any argtypes are trusted: the mtime-based
        # rebuild heuristic can miss (prebuilt .so shipped without
        # sources, or mtimes not newer), and a stale library would
        # silently mis-bind recordio_create's arguments — e.g. dropping
        # label_wide decodes imagenet_synth labels as their low byte
        # only: silently wrong training data.
        expected_abi = 2
        try:
            lib.recordio_abi_version.restype = ctypes.c_int64
            got = int(lib.recordio_abi_version())
        except AttributeError:
            got = 1  # pre-versioning builds had no such symbol
        if got != expected_abi:
            raise RuntimeError(
                f"librecordio.so ABI v{got} != expected v{expected_abi} "
                f"at {_LIB_PATH}: stale prebuilt library — rebuild with "
                f"`make -C runtime` (or delete the .so to rebuild on "
                f"demand)")
        lib.recordio_create.restype = ctypes.c_void_p
        lib.recordio_create.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_uint64, ctypes.c_int64,
        ]
        lib.recordio_next_batch.restype = ctypes.c_int
        lib.recordio_next_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int32),
        ]
        lib.recordio_error.restype = ctypes.c_char_p
        lib.recordio_error.argtypes = [ctypes.c_void_p]
        lib.recordio_buffered.restype = ctypes.c_int64
        lib.recordio_buffered.argtypes = [ctypes.c_void_p]
        lib.recordio_destroy.restype = None
        lib.recordio_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


class NativeShuffleBatchIterator(pipe.ShuffleBatchIterator):
    """Streaming batches from the C++ loader.

    Subclasses the NumPy iterator so the sweep/eval/clone contract (backed
    by the in-memory decoded split) is shared; ``__next__`` — the training
    hot path — streams from the native bounded shuffle pool instead of the
    in-memory permutation.
    """

    def __init__(self, files: List[str], cfg: DataConfig, batch_size: int,
                 train: bool = True, seed: int = 0, shard: int = 0,
                 num_shards: int = 1):
        lib = load_library()  # raise *before* any base-class work
        super().__init__(files, cfg, batch_size, train=train, seed=seed,
                         shard=shard, num_shards=num_shards)
        # Per-process shard of the file list (multi-host): strided like the
        # record-level sharding of the base class. With fewer files than
        # shards every process reads everything (the reference's behavior —
        # no sharding at all, cifar10cnn.py:73-91).
        if num_shards > 1 and len(files) >= num_shards:
            files = files[shard::num_shards]
        self._lib = lib
        nlb = download.label_bytes(cfg)
        record_bytes = cfg.record_bytes + (nlb - 1)
        capacity = cfg.shuffle_buffer + 3 * batch_size  # cifar10cnn.py:86
        paths = b"\0".join(p.encode() for p in files) + b"\0"
        self._handle = lib.recordio_create(
            paths, len(files), record_bytes, nlb, nlb - 1,
            cfg.image_height, cfg.image_width, cfg.num_channels,
            min(cfg.shuffle_buffer, capacity), capacity,
            np.uint64(seed * 2654435761 + 97531 + shard),
            int(download.wide_label(cfg)))
        if not self._handle:
            raise RuntimeError("recordio_create failed (bad geometry?)")
        self._img_buf = np.empty(
            (batch_size, cfg.image_height, cfg.image_width,
             cfg.num_channels), np.uint8)
        self._lab_buf = np.empty((batch_size,), np.int32)

    # The C++ pool streams records by VALUE (bounded-shuffle parity with
    # the reference's RandomShuffleQueue); it has no index view into the
    # decoded arrays, so the HBM-resident path can't reproduce its stream.
    supports_index_stream = False
    # The C++ pool's draw stream is not replayable from Python.
    supports_skip = False

    def next_index_chunk(self, k: int):
        raise NotImplementedError(
            "the native bounded-shuffle stream has no index view; use the "
            "raw-chunk path, or use_native_loader=False for the "
            "HBM-resident path")

    def _fill(self, img_buf: np.ndarray, lab_buf: np.ndarray) -> None:
        """One ``recordio_next_batch`` into caller buffers (shared by the
        per-batch and raw-chunk paths)."""
        if not self._handle:
            raise RuntimeError("native loader is closed")
        ret = self._lib.recordio_next_batch(
            self._handle, self.batch_size,
            img_buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            lab_buf.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        if ret != 0:
            raise RuntimeError(
                "native loader: "
                + self._lib.recordio_error(self._handle).decode())

    def __next__(self) -> pipe.Batch:
        self._fill(self._img_buf, self._lab_buf)
        return pipe.Batch(self._finish(self._img_buf),
                          self._lab_buf.copy())

    def next_raw_chunk(self, k: int) -> pipe.Batch:
        """``k`` stacked raw uint8 batches straight from the native bounded
        shuffle pool (same stream as ``__next__``, no decode) — the chunked
        training path's input, keeping the reference's bounded-shuffle
        semantics instead of the base class's in-memory permutation."""
        cfg = self.cfg
        ims = np.empty((k, self.batch_size, cfg.image_height,
                        cfg.image_width, cfg.num_channels), np.uint8)
        lbs = np.empty((k, self.batch_size), np.int32)
        for j in range(k):
            self._fill(ims[j], lbs[j])
        return pipe.Batch(ims, lbs)

    def buffered(self) -> int:
        """Records currently in the native shuffle pool (observability)."""
        if not self._handle:
            raise RuntimeError("native loader is closed")
        return int(self._lib.recordio_buffered(self._handle))

    def close(self) -> None:
        handle, self._handle = getattr(self, "_handle", None), None
        if handle:
            self._lib.recordio_destroy(handle)

    def __del__(self):  # best-effort; close() is the real API
        try:
            self.close()
        except Exception:
            pass

"""Dataset acquisition: download-or-reuse, plus an offline synthetic mode.

Mirrors ``download_data`` (``cifar10cnn.py:34-52``): fetch
``cifar-10-binary.tar.gz`` from cs.toronto.edu with a progress callback,
extract into ``<data_dir>/cifar-10-batches-bin``, and skip the download when
the tarball is already present. Additionally supports CIFAR-100 (same binary
framing, 1 coarse + 1 fine label byte) and a fully offline *synthetic* mode
that writes files in the exact CIFAR binary layout so every downstream stage
(reader, shuffle buffer, crop, training) is exercised without network access.
"""

from __future__ import annotations

import hashlib
import os
import tarfile
import time
import urllib.error
import urllib.request
from typing import List, Optional

import numpy as np

from dml_cnn_cifar10_tpu.config import DataConfig

CIFAR10_URL = "http://www.cs.toronto.edu/~kriz/cifar-10-binary.tar.gz"
CIFAR100_URL = "http://www.cs.toronto.edu/~kriz/cifar-100-binary.tar.gz"
# Published size/md5 of the archives — verified BEFORE extraction so a
# truncated or tampered download is caught at the byte layer instead of
# surfacing later as a record-framing decode error mid-training.
KNOWN_ARCHIVES = {
    CIFAR10_URL: {"bytes": 170052171,
                  "md5": "c32a1d4ab5d03f1284b67883e8d87530"},
    CIFAR100_URL: {"bytes": 169001437,
                   "md5": "03b5dce01913d631647c71ecec9e9cb8"},
}


class DownloadError(RuntimeError):
    """Dataset acquisition failed after bounded retries. ``fault`` names
    the class — ``"network"`` (unreachable/timeout) or ``"integrity"``
    (bad size/checksum/archive) — so ``ensure_dataset`` can report WHY
    it degraded to synthetic data."""

    def __init__(self, fault: str, msg: str):
        super().__init__(msg)
        self.fault = fault
CIFAR10_FOLDER = "cifar-10-batches-bin"   # extract_folder (cifar10cnn.py:27)
CIFAR100_FOLDER = "cifar-100-binary"
# ImageNet-shaped synthetic rung (BASELINE.json configs[3] — "ResNet-50 on
# ImageNet-1k"): same fixed-length binary framing at configurable geometry
# (e.g. 256x256x3, 1000 classes). >255 classes no longer fit CIFAR's single
# label byte, so these records lead with a 2-byte BIG-ENDIAN label
# (wide_label below). ImageNet itself has no binary-record distribution and
# this box has no egress; the shards are always generated synthetically.
IMAGENET_SYNTH_FOLDER = "imagenet-synth-bin"
# Token rows for a model over tokens: each record ``sequence_length + 1``
# little-endian int32 ids, no label. Always generated: no corpus is here.
TOKENS_SYNTH_FOLDER = "tokens-synth-bin"


def _progress(url: str):
    # Console progress bar, same format as cifar10cnn.py:47-49.
    def cb(block_num, block_size, total_size):
        pct = float(block_num * block_size) / float(max(total_size, 1)) * 100.0
        print("\r Downloading {} - {:.2f}%".format(url, pct), end="")
    return cb


def _fetch(url: str, dest: str, timeout: float) -> None:
    """One bounded-timeout download attempt, atomic (tmp + rename) so a
    dropped connection can never leave a half tarball that a later run
    would treat as already-downloaded (the reference's exact trap,
    ``cifar10cnn.py:43-44``)."""
    tmp = dest + ".tmp"
    cb = _progress(url)
    with urllib.request.urlopen(url, timeout=timeout) as r, \
            open(tmp, "wb") as f:
        total = int(r.headers.get("Content-Length") or 0)
        block = 1 << 16
        n = 0
        while True:
            chunk = r.read(block)
            if not chunk:
                break
            f.write(chunk)
            n += 1
            cb(n, block, total)
    print()
    os.replace(tmp, dest)


def _verify_archive(url: str, path: str) -> Optional[str]:
    """Failure reason when ``path`` mismatches the published size/md5 of
    ``url``; None when it matches (or the URL has no published record)."""
    want = KNOWN_ARCHIVES.get(url)
    if want is None:
        return None
    size = os.path.getsize(path)
    if size != want["bytes"]:
        return f"size {size} != expected {want['bytes']}"
    h = hashlib.md5()
    with open(path, "rb") as f:
        while True:
            chunk = f.read(1 << 20)
            if not chunk:
                break
            h.update(chunk)
    if h.hexdigest() != want["md5"]:
        return f"md5 {h.hexdigest()} != expected {want['md5']}"
    return None


def download_and_extract(data_dir: str, url: str, retries: int = 3,
                         timeout: float = 30.0,
                         backoff_s: float = 1.0) -> str:
    """Fetch + verify + untar ``url`` into ``data_dir``, with bounded
    retry/backoff around the network and integrity steps.

    Unlike the reference (which skips extraction whenever the tarball exists,
    ``cifar10cnn.py:43-44`` — leaving a half-extracted dir broken forever),
    extraction re-runs whenever this is called: callers only call it when
    the target .bin files are missing. A tarball that fails its size/md5
    check is deleted and re-fetched; exhausted retries raise a
    classified :class:`DownloadError`.
    """
    os.makedirs(data_dir, exist_ok=True)
    data_file = os.path.join(data_dir, os.path.basename(url))
    last: Optional[BaseException] = None
    fault = "network"
    for attempt in range(max(1, retries)):
        if attempt:
            time.sleep(min(backoff_s * 2 ** (attempt - 1), 30.0))
        if not os.path.isfile(data_file):
            try:
                _fetch(url, data_file, timeout)
            except (urllib.error.URLError, OSError) as e:
                # URLError covers HTTP errors and DNS failures; OSError
                # covers socket timeouts/resets. Anything else is a bug
                # and propagates.
                last, fault = e, "network"
                print(f"\n[data] download attempt {attempt + 1}/"
                      f"{retries} failed: {e!r}")
                continue
        bad = _verify_archive(url, data_file)
        if bad:
            last, fault = DownloadError("integrity", bad), "integrity"
            print(f"[data] archive failed verification ({bad}); "
                  f"deleting and re-fetching")
            os.remove(data_file)
            continue
        try:
            tarfile.open(data_file, "r:gz").extractall(data_dir)
        except (tarfile.TarError, EOFError) as e:
            # Undetectable-by-table corruption (unknown URL, or a stale
            # pre-verification tarball): treat like an integrity failure
            # and re-fetch.
            last, fault = e, "integrity"
            print(f"[data] extraction failed ({e!r}); deleting the "
                  f"archive and re-fetching")
            os.remove(data_file)
            continue
        return data_dir
    raise DownloadError(
        fault, f"failed to acquire {url} after {retries} attempts; "
               f"last error: {last!r}") from last


def train_files(cfg: DataConfig) -> List[str]:
    """Training shards. CIFAR-10: ``data_batch_{1..5}.bin`` (cifar10cnn.py:78)."""
    if cfg.dataset in ("cifar10", "synthetic"):
        base = os.path.join(cfg.data_dir, CIFAR10_FOLDER)
        return [os.path.join(base, f"data_batch_{i}.bin") for i in range(1, 6)]
    if cfg.dataset == "cifar100":
        return [os.path.join(cfg.data_dir, CIFAR100_FOLDER, "train.bin")]
    if cfg.dataset == "imagenet_synth":
        base = os.path.join(cfg.data_dir, IMAGENET_SYNTH_FOLDER)
        return [os.path.join(base, f"train_{i}.bin") for i in range(1, 5)]
    if cfg.dataset == "tokens_synth":
        base = os.path.join(cfg.data_dir, TOKENS_SYNTH_FOLDER)
        return [os.path.join(base, f"train_{i}.bin") for i in range(1, 5)]
    raise ValueError(f"unknown dataset {cfg.dataset!r}")


def test_files(cfg: DataConfig) -> List[str]:
    """Test shard: ``test_batch.bin`` (cifar10cnn.py:80)."""
    if cfg.dataset in ("cifar10", "synthetic"):
        return [os.path.join(cfg.data_dir, CIFAR10_FOLDER, "test_batch.bin")]
    if cfg.dataset == "cifar100":
        return [os.path.join(cfg.data_dir, CIFAR100_FOLDER, "test.bin")]
    if cfg.dataset == "imagenet_synth":
        return [os.path.join(cfg.data_dir, IMAGENET_SYNTH_FOLDER, "val.bin")]
    if cfg.dataset == "tokens_synth":
        return [os.path.join(cfg.data_dir, TOKENS_SYNTH_FOLDER, "val.bin")]
    raise ValueError(f"unknown dataset {cfg.dataset!r}")


def label_bytes(cfg: DataConfig) -> int:
    """CIFAR-10 records lead with 1 label byte; CIFAR-100 with 2
    (coarse+fine); imagenet_synth with 2 (one big-endian uint16)."""
    return 2 if cfg.dataset in ("cifar100", "imagenet_synth") else 1


def wide_label(cfg: DataConfig) -> bool:
    """True when the 2 leading label bytes encode ONE big-endian uint16
    (class counts past 255) rather than CIFAR-100's coarse+fine byte
    pair."""
    return cfg.dataset == "imagenet_synth"


def generate_synthetic_dataset(cfg: DataConfig, seed: int = 0) -> None:
    """Write CIFAR-layout binary files with class-separable random images.

    Byte layout per record is identical to the real dataset (label byte(s) +
    CHW uint8 image, ``cifar10cnn.py:24-25,58-62``). Images are Gaussian noise
    around a per-class mean color so a real model can overfit them — that lets
    integration tests assert "loss decreases / accuracy beats chance" offline.
    """
    rng = np.random.default_rng(seed)
    nlb = label_bytes(cfg)
    wide = wide_label(cfg)
    img_len = cfg.image_height * cfg.image_width * cfg.num_channels
    # One per-class mean-color table for the WHOLE dataset (train and test
    # shards must share the class→color mapping or nothing generalizes).
    means = rng.integers(30, 226, size=(cfg.num_classes, cfg.num_channels))

    def write(path: str, n: int) -> None:
        # Skip only when the existing file matches the REQUESTED geometry
        # and record count — a stale shard generated under different
        # --image_size/--crop_size/--synthetic_*_records would otherwise
        # be silently reused and mis-decoded downstream.
        want_bytes = n * (nlb + img_len)
        if os.path.isfile(path) and os.path.getsize(path) == want_bytes:
            return
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            # Bounded chunks: one float32 normal draw per chunk instead of
            # a whole-shard float64 array (tens of GB at ImageNet
            # geometry).
            step = max(1, min(n, (64 << 20) // max(img_len, 1)))
            for lo in range(0, n, step):
                m = min(step, n - lo)
                labels = rng.integers(0, cfg.num_classes, size=m,
                                      dtype=np.int32)
                recs = np.empty((m, nlb + img_len), dtype=np.uint8)
                if wide:
                    # big-endian uint16
                    recs[:, 0] = (labels >> 8).astype(np.uint8)
                    recs[:, 1] = (labels & 0xFF).astype(np.uint8)
                else:
                    for lb in range(nlb):
                        # coarse == fine for synthetic CIFAR-100
                        recs[:, lb] = labels.astype(np.uint8)
                chw = rng.normal(
                    means[labels][:, :, None, None], 40.0,
                    size=(m, cfg.num_channels, cfg.image_height,
                          cfg.image_width)).astype(np.float32)
                recs[:, nlb:] = np.clip(chw, 0, 255).astype(
                    np.uint8).reshape(m, img_len)
                f.write(recs.tobytes())
        os.replace(tmp, path)

    per_shard = max(1, cfg.synthetic_train_records // len(train_files(cfg)))
    for path in train_files(cfg):
        write(path, per_shard)
    for path in test_files(cfg):
        write(path, cfg.synthetic_test_records)


def generate_token_dataset(cfg: DataConfig, seed: int = 0) -> None:
    """Write the ``tokens_synth`` shards: rows of ``sequence_length + 1``
    little-endian int32 ids. Each row counts on from its own start by its
    own stride over the ``num_classes`` ids of the vocabulary, one token in
    ten replaced by noise, so that a model has something to learn and every
    row differs. A shard that already has the requested size is kept (the
    benchmark writes its own rows there first)."""
    rng = np.random.default_rng(seed)
    width, vocab = cfg.sequence_length + 1, cfg.num_classes

    def write(path: str, n: int) -> None:
        if os.path.isfile(path) and os.path.getsize(path) == n * width * 4:
            return
        os.makedirs(os.path.dirname(path), exist_ok=True)
        start = rng.integers(0, vocab, size=(n, 1))
        stride = rng.integers(1, 8, size=(n, 1))
        tokens = (start + stride * np.arange(width)[None, :]) % vocab
        noise = rng.integers(0, vocab, size=tokens.shape)
        tokens = np.where(rng.random(tokens.shape) < 0.1, noise, tokens)
        tmp = path + ".tmp"
        tokens.astype("<i4").tofile(tmp)
        os.replace(tmp, path)

    per_shard = max(1, cfg.synthetic_train_records // len(train_files(cfg)))
    for path in train_files(cfg):
        write(path, per_shard)
    for path in test_files(cfg):
        write(path, cfg.synthetic_test_records)


def ensure_dataset(cfg: DataConfig) -> None:
    """Make sure the binary shards exist: download, or synthesize offline.

    Parity entrypoint for ``download_data()`` (``cifar10cnn.py:34-52``). In
    ``synthetic`` mode (or when the download fails — e.g. an air-gapped host)
    it falls back to :func:`generate_synthetic_dataset`.
    """
    if cfg.tokens:
        generate_token_dataset(cfg, seed=cfg.seed)
        return
    if cfg.dataset in ("synthetic", "imagenet_synth"):
        # imagenet_synth is generate-only: ImageNet has no fixed-length
        # binary distribution to download; the rung's record framing is
        # this framework's own (wide labels + configurable geometry).
        generate_synthetic_dataset(cfg, seed=cfg.seed)
        return
    needed = train_files(cfg) + test_files(cfg)
    if all(os.path.isfile(p) for p in needed):
        return
    url = CIFAR100_URL if cfg.dataset == "cifar100" else CIFAR10_URL
    try:
        download_and_extract(cfg.data_dir, url)
    except DownloadError as e:
        # Only classified acquisition failures (network unreachable,
        # integrity exhausted) degrade to synthetic data — and the
        # warning names which class, so an air-gapped box and a
        # corrupted mirror are distinguishable in the logs. Anything
        # else (disk full, permission, a bug) propagates loudly.
        print(f"[data] {e.fault} failure acquiring {url} ({e}); "
              f"generating synthetic CIFAR-format data instead")
        generate_synthetic_dataset(cfg, seed=cfg.seed)

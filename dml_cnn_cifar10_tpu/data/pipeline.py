"""Shuffled batching + host→device prefetch.

The reference's input pipeline is a C++ queue graph: filename queue →
``FixedLengthRecordReader`` → per-record decode/crop → ``RandomShuffleQueue``
(``min_after_dequeue=5000``) drained 128 at a time by the train step, all fed
by background queue-runner threads (``cifar10cnn.py:72-91,223``). The
TPU-native equivalent keeps the same *contract* — an endless stream of
shuffled, decoded, cropped batches — but runs it as vectorized NumPy on the
host with a background prefetch thread that lands batches in device memory
ahead of the step, so the compiled step never blocks on input.

Shuffling note: the in-memory path shuffles by drawing from a fresh uniform
permutation each epoch — strictly *stronger* mixing than the reference's
bounded 5000-element shuffle buffer (``DataConfig.shuffle_buffer`` is kept
for the streaming native loader, where a bounded buffer is the right tool).
"""

from __future__ import annotations

import queue
import threading
from typing import Callable, Iterator, List, NamedTuple, Optional

import numpy as np

from dml_cnn_cifar10_tpu.config import DataConfig
from dml_cnn_cifar10_tpu.data import download, records as rec


class Batch(NamedTuple):
    images: np.ndarray  # [B, crop_h, crop_w, C] float32
    labels: np.ndarray  # [B] int32


class DataPipelineError(RuntimeError):
    """A failure raised while drawing the next batch/chunk. The training
    loop wraps its data-seam exceptions in this so the run supervisor
    (``train/supervisor.py``) can classify them as recoverable — restore
    the last checkpoint, rebuild the pipeline, resume — instead of
    treating an input hiccup like a model bug."""


def _load_split(files: List[str], cfg: DataConfig):
    """Decode all shards once, as uint8 HWC (cast happens per batch). A
    token dataset's rows come as they are, ``[N, S+1]`` int32, in the
    images' place, with a label column of zeros that nothing reads."""
    if cfg.tokens:
        rows = np.concatenate([
            np.fromfile(path, dtype="<i4").reshape(
                -1, cfg.sequence_length + 1) for path in files])
        return rows.astype(np.int32), np.zeros(len(rows), np.int32)
    nlb = download.label_bytes(cfg)
    record_bytes = cfg.record_bytes + (nlb - 1)
    label_offset = nlb - 1  # CIFAR-100: fine label is the 2nd byte
    wide = download.wide_label(cfg)  # imagenet_synth: big-endian uint16
    imgs, labs = [], []
    for path in files:
        r = rec.read_record_file(path, record_bytes)
        i, l = rec.decode_records(r, cfg, label_offset=label_offset,
                                  dtype=np.uint8, wide_label=wide)
        imgs.append(i)
        labs.append(l)
    return np.concatenate(imgs, axis=0), np.concatenate(labs, axis=0)


class ShuffleBatchIterator:
    """Endless shuffled batches over an in-memory decoded split.

    Contract parity with ``tf.train.shuffle_batch`` (``cifar10cnn.py:85-90``):
    endless repetition, per-epoch reshuffle, fixed batch size. Like the
    reference, every worker sees all shards by default
    (``cifar10cnn.py:73-91`` has no per-worker sharding); ``shard``/
    ``num_shards`` adds the disjoint per-process split multi-host runs want.
    """

    def __init__(
        self,
        files: List[str],
        cfg: DataConfig,
        batch_size: int,
        train: bool = True,
        seed: int = 0,
        shard: int = 0,
        num_shards: int = 1,
        _arrays=None,
    ):
        self.cfg = cfg
        self.batch_size = batch_size
        self.train = train
        self.rng = np.random.default_rng(seed)
        if _arrays is not None:
            images, labels = _arrays
        else:
            images, labels = _load_split(files, cfg)
        # Pre-shard total, the denominator for distributed full-split eval.
        self.total_records = images.shape[0]
        self.num_shards = num_shards
        if num_shards > 1:
            images, labels = images[shard::num_shards], labels[shard::num_shards]
        self.images, self.labels = images, labels
        self.n = images.shape[0]
        self._perm = self.rng.permutation(self.n)
        self._cursor = 0

    def clone(self, seed: int, train: Optional[bool] = None
              ) -> "ShuffleBatchIterator":
        """Second independent stream over the SAME decoded arrays (no extra
        host RAM) — e.g. the fresh-batch train-accuracy stream
        (``cifar10cnn.py:235``)."""
        it = ShuffleBatchIterator(
            [], self.cfg, self.batch_size,
            train=self.train if train is None else train,
            seed=seed, _arrays=(self.images, self.labels))
        it.total_records = self.total_records
        it.num_shards = self.num_shards
        return it

    def _next_indices(self, k: int) -> np.ndarray:
        out = np.empty(k, dtype=np.int64)
        filled = 0
        while filled < k:
            take = min(k - filled, self.n - self._cursor)
            out[filled : filled + take] = self._perm[
                self._cursor : self._cursor + take
            ]
            filled += take
            self._cursor += take
            if self._cursor == self.n:  # epoch boundary: reshuffle, repeat
                self._perm = self.rng.permutation(self.n)
                self._cursor = 0
        return out

    def _finish(self, images: np.ndarray) -> np.ndarray:
        """uint8 [N,H,W,C] → cropped/augmented/normalized float32 batch."""
        cfg = self.cfg
        if cfg.tokens:
            return images         # token rows: nothing to crop or scale
        images = images.astype(np.float32)
        if self.train and cfg.random_crop:
            images = rec.random_crop(images, cfg.crop_height, cfg.crop_width,
                                     self.rng)
        else:
            images = rec.center_crop(images, cfg.crop_height, cfg.crop_width)
        if self.train and cfg.random_flip:
            images = rec.random_flip(images, self.rng)
        if self.train and cfg.random_brightness:
            images = rec.random_brightness(images, cfg.random_brightness,
                                           self.rng)
        if self.train and cfg.random_contrast:
            images = rec.random_contrast(images, cfg.random_contrast,
                                         self.rng)
        return np.ascontiguousarray(rec.normalize(images, cfg.normalize))

    def __iter__(self) -> Iterator[Batch]:
        return self

    def __next__(self) -> Batch:
        idx = self._next_indices(self.batch_size)
        return Batch(self._finish(self.images[idx]), self.labels[idx])

    # True when next_index_chunk draws from the same stream as
    # __next__/next_raw_chunk. The native C++ iterator streams records by
    # value from its bounded pool (no index view), so it sets this False
    # and the resident data path is gated off (train/loop.py).
    supports_index_stream = True

    # True when skip_batches can fast-forward the stream — the basis of
    # exact-resume data order (train/loop.py). The native loader's C++
    # pool has no replayable draw stream, so it sets this False.
    supports_skip = True

    # The augmentations skip_batches knows how to replay. New fields in
    # DataConfig._AUG_OFF must get a mirror draw below (and coverage in
    # tests/test_exact_resume.py::test_skip_batches_matches_consumed_
    # stream) — skip_batches raises loudly otherwise, so drift between
    # _finish's draws and the replay can't be silent.
    _SKIP_MIRRORED_AUGS = frozenset(
        {"random_crop", "random_flip", "random_brightness",
         "random_contrast"})

    def skip_batches(self, n: int, aug: bool = False) -> None:
        """Fast-forward the stream by ``n`` batches WITHOUT materializing
        them: replays exactly the index draws (and, with ``aug=True``,
        the per-batch augmentation draws ``_finish`` makes on the
        host-decode path) so batch ``n`` after a skip is bit-identical
        to batch ``n`` of an unskipped same-seed iterator. This is how a
        resumed run continues the data stream where the previous run's
        CONSUMPTION stopped — prefetch lookahead regenerates, it is not
        part of the consumed position. tests/test_exact_resume.py::
        test_skip_batches_matches_consumed_stream pins the equivalence;
        keep the draw mirror in sync with ``_finish``."""
        cfg = self.cfg
        b = self.batch_size
        burn_aug = aug and self.train and cfg.augmented
        if not burn_aug:
            # No per-batch rng draws besides the index stream, and a
            # chunked draw is cursor-equivalent to n single draws (the
            # same equivalence next_index_chunk relies on). Draw at most
            # one epoch of indices at a time so resuming a 500k-step run
            # fast-forwards in O(dataset) memory, not O(consumed).
            remaining = b * n
            cap = max(self.n, 1)
            while remaining > 0:
                take = min(remaining, cap)
                self._next_indices(take)
                remaining -= take
            return
        active = {name for name, off in cfg._AUG_OFF
                  if getattr(cfg, name) != off}
        unmirrored = active - self._SKIP_MIRRORED_AUGS
        if unmirrored:
            raise NotImplementedError(
                f"skip_batches has no draw mirror for {sorted(unmirrored)}"
                " — add its rng replay here and to the exact-resume test"
                " before using it with exact resume")
        for _ in range(n):
            self._next_indices(b)
            if cfg.random_crop:
                self.rng.integers(
                    0, cfg.image_height - cfg.crop_height + 1, size=b)
                self.rng.integers(
                    0, cfg.image_width - cfg.crop_width + 1, size=b)
            if cfg.random_flip:
                self.rng.random(b)
            if cfg.random_brightness:
                self.rng.uniform(-cfg.random_brightness,
                                 cfg.random_brightness, b)
            if cfg.random_contrast:
                self.rng.uniform(1.0 - cfg.random_contrast,
                                 1.0 + cfg.random_contrast, b)

    def next_index_chunk(self, k: int) -> np.ndarray:
        """``[k, B]`` int32 shuffled indices into the local decoded arrays
        (``self.images``/``self.labels``) — the same stream as
        ``next_raw_chunk`` minus the gather, for the HBM-resident data path
        (``parallel/step.py:make_train_chunk_resident``) where the gather
        runs on device."""
        idx = self._next_indices(self.batch_size * k)
        return idx.reshape(k, self.batch_size).astype(np.int32)

    def next_raw_chunk(self, k: int) -> Batch:
        """``k`` stacked shuffled batches of RAW uint8 full-size images
        ([k, B, H, W, C] — no crop/cast/normalize) for device-side
        preprocessing (``ops/preprocess.py``). One fancy-index gather per
        chunk: the host's only per-chunk work is a byte memcpy."""
        idx = self._next_indices(self.batch_size * k)
        ims = self.images[idx].reshape(
            k, self.batch_size, *self.images.shape[1:])
        return Batch(ims, self.labels[idx].reshape(k, self.batch_size))

    def full_sweep(self) -> Iterator[Batch]:
        """Deterministic single pass over the local shard (variable-size
        final batch). For multi-process collective eval use
        :meth:`full_sweep_padded`."""
        for start in range(0, self.n, self.batch_size):
            stop = start + self.batch_size
            yield Batch(self._finish(self.images[start:stop]),
                        self.labels[start:stop])

    def num_padded_sweep_batches(self) -> int:
        """Number of fixed-size batches every process must contribute so a
        sharded full-split sweep issues the SAME number of collective steps
        on every host (strided shards differ by ≤1 record)."""
        max_shard = -(-self.total_records // max(self.num_shards, 1))
        return -(-max_shard // self.batch_size)

    def full_sweep_padded(self) -> Iterator[Batch]:
        """Fixed-shape single pass: every batch has exactly ``batch_size``
        rows, pad rows carry label -1 (never matches an argmax in [0, K), so
        they contribute 0 correct predictions). All processes yield the same
        batch count — safe to drive a collective eval step in lockstep."""
        for b in range(self.num_padded_sweep_batches()):
            start = min(b * self.batch_size, self.n)
            stop = min(start + self.batch_size, self.n)
            images = self._finish(self.images[start:stop])
            labels = self.labels[start:stop]
            pad = self.batch_size - images.shape[0]
            if pad:
                images = np.pad(images,
                                ((0, pad), (0, 0), (0, 0), (0, 0)))
                labels = np.pad(labels, (0, pad), constant_values=-1)
            yield Batch(images, labels)


class PrefetchIterator:
    """Background-thread prefetch: overlap host batching + device transfer
    with the running step (the queue-runner role, ``cifar10cnn.py:223``).

    ``place`` maps a host :class:`Batch` to device arrays (e.g.
    ``jax.device_put`` with a NamedSharding); it runs on the prefetch thread
    so H2D transfer overlaps compute.
    """

    _DONE = object()

    def __init__(self, it: Iterator[Batch], depth: int = 2,
                 place: Optional[Callable] = None):
        self._it = it
        self._place = place or (lambda b: b)
        self._q: queue.Queue = queue.Queue(maxsize=max(1, depth))
        self._err: Optional[BaseException] = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _put(self, item) -> bool:
        """Bounded put that re-checks the stop flag — never parks forever."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _run(self):
        try:
            for item in self._it:
                if self._stop.is_set() or not self._put(self._place(item)):
                    return
        except BaseException as e:  # surfaced on the consumer side
            self._err = e
        finally:
            if not self._stop.is_set():
                self._put(self._DONE)

    def __iter__(self):
        return self

    def __next__(self):
        item = self._q.get()
        if item is self._DONE:
            if self._err is not None:
                raise self._err
            raise StopIteration
        return item

    def close(self):
        """Stop the producer and join it (drains so its pending put can
        observe the stop flag)."""
        self._stop.set()
        while self._thread.is_alive():
            try:
                self._q.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=0.05)


def input_pipeline(
    cfg: DataConfig,
    batch_size: int,
    train: bool = True,
    seed: int = 0,
    shard: int = 0,
    num_shards: int = 1,
) -> ShuffleBatchIterator:
    """Build the batch iterator for the train or test split.

    Parity entrypoint for ``input_pipeline(batch_size, train_logical)``
    (``cifar10cnn.py:72-91``). Note the reference shuffle-batches the *test*
    split too — eval draws random test batches — so this does the same; use
    :meth:`ShuffleBatchIterator.full_sweep_padded` for proper full-test-set
    eval.
    """
    download.ensure_dataset(cfg)
    files = download.train_files(cfg) if train else download.test_files(cfg)
    if cfg.use_native_loader and not cfg.tokens:
        # (the native loader reads image records only)
        # No quiet NumPy stand-in: runtime/librecordio.so is built on
        # demand, and a failed build or load raises (data/native.py
        # says how to switch the native loader off).
        from dml_cnn_cifar10_tpu.data import native
        return native.NativeShuffleBatchIterator(
            files, cfg, batch_size, train=train, seed=seed,
            shard=shard, num_shards=num_shards)
    return ShuffleBatchIterator(
        files, cfg, batch_size, train=train, seed=seed,
        shard=shard, num_shards=num_shards)

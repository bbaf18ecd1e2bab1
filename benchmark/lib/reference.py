"""Plain reference trainer: the same K steps the timed dispatch ran, in
straightforward jax.numpy, float32 at the highest matmul precision.

Nothing here imports the program. What the program's first dispatch does
to the state is re-derived from the published description of each piece.
Every configuration shares the row of the dataset each batch position
reads (a cycle-walking Feistel permutation per epoch, written out again
below in NumPy) and the K steps around it (:func:`make_chunk`). What a
run is given, how a batch is made of it, the loss and the update are the
configuration's :class:`Task`. One that states none is an image
classifier (:func:`image_task`):

- decode: cast, per-image random crop window and mirror (the draws are
  ``jax.random`` calls on ``fold_in(key(seed), step)``), per-image
  standardisation;
- the configuration's forward pass and mean softmax cross-entropy
  (``benchmark/configs/<name>.py``);
- SGD with coupled weight decay, a momentum trace and a linear warm-up
  of the learning rate.

``numerics`` selects how products are formed (:class:`Numerics`). A
configuration states one of ``float32`` and ``tpu_default`` for its
reference. ``bfloat16`` and ``float8`` are the controls of "How correct
is decided": the reference put in the program's place, one precision
below what the configuration states.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.lib import datagen

# --- the shuffled index stream ------------------------------------------------

_M32 = np.uint64(0xFFFFFFFF)


def _u32(x):
    return (x & _M32).astype(np.uint64)


def _mix(x):
    """lowbias32 on uint32 values carried in uint64 (no overflow warnings)."""
    x = _u32((x ^ (x >> np.uint64(16))) * np.uint64(0x7FEB352D))
    x = _u32((x ^ (x >> np.uint64(15))) * np.uint64(0x846CA68B))
    return x ^ (x >> np.uint64(16))


def _feistel(pos, key, half_bits: int):
    mask = np.uint64((1 << half_bits) - 1)
    hi, lo = pos >> np.uint64(half_bits), pos & mask
    for r in range(4):
        f = _mix(lo ^ _mix(key ^ _u32(np.uint64(r) * np.uint64(0xC2B2AE35)))) \
            & mask
        hi, lo = lo, hi ^ f
    return (hi << np.uint64(half_bits)) | lo


def stream_rows(seed: int, first_position: int, count: int, n: int
                ) -> np.ndarray:
    """Rows of the endless shuffled stream ``perm_0 ++ perm_1 ++ ...`` at
    positions ``first_position ..``: a four-round balanced Feistel network
    over the next even power of two, keyed on (seed, epoch), cycle-walked
    back into ``[0, n)``."""
    bits = max(2, (n - 1).bit_length())
    bits += bits % 2
    half = bits // 2
    domain = np.uint64(1 << bits)
    j = _u32(np.uint64(first_position) + np.arange(count, dtype=np.uint64))
    epoch, pos = j // np.uint64(n), j % np.uint64(n)
    key = _mix(_u32(np.uint64(seed & 0xFFFFFFFF) * np.uint64(0x9E3779B9))
               ^ _u32(epoch * np.uint64(0x85EBCA6B)))
    out = _feistel(pos, key, half)
    while True:
        outside = out >= np.uint64(n)
        if not outside.any():
            break
        out = np.where(outside, _feistel(out, key, half) % domain, out)
    return out.astype(np.int32)


# --- decode -------------------------------------------------------------------

def decode(images_u8, key, crop_h: int, crop_w: int, random_crop: bool,
           random_flip: bool, normalize: str):
    """uint8 ``[N,H,W,C]`` -> float32 ``[N,crop_h,crop_w,C]``."""
    n, h, w, _ = images_u8.shape
    x = images_u8.astype(jnp.float32)
    kc, kf, _, _ = jax.random.split(key, 4)
    if random_crop:
        kt, kl = jax.random.split(kc)
        tops = jax.random.randint(kt, (n,), 0, h - crop_h + 1)
        lefts = jax.random.randint(kl, (n,), 0, w - crop_w + 1)
    else:
        tops = jnp.full((n,), (h - crop_h) // 2)
        lefts = jnp.full((n,), (w - crop_w) // 2)
    rows = tops[:, None] + jnp.arange(crop_h)[None, :]
    cols = lefts[:, None] + jnp.arange(crop_w)[None, :]
    if random_flip:
        flip = jax.random.bernoulli(kf, 0.5, (n,))
        if random_crop:
            mirrored = (w - 1 - lefts)[:, None] - jnp.arange(crop_w)[None, :]
        else:
            mirrored = cols[:, ::-1]
        cols = jnp.where(flip[:, None], mirrored, cols)
    x = jnp.take_along_axis(x, rows[:, :, None, None], axis=1)
    x = jnp.take_along_axis(x, cols[:, None, :, None], axis=2)
    if normalize == "scale":
        return x / 255.0
    if normalize == "standardize":
        mean = jnp.mean(x, axis=(1, 2, 3), keepdims=True)
        std = jnp.std(x, axis=(1, 2, 3), keepdims=True)
        return (x - mean) / jnp.maximum(
            std, 1.0 / np.sqrt(float(crop_h * crop_w * x.shape[-1])))
    if normalize != "none":
        raise ValueError(f"unknown normalize {normalize!r}")
    return x


# --- how products are formed --------------------------------------------------

def _rounder(dtype) -> Callable:
    """``x -> x`` rounded to ``dtype`` and carried on in float32."""
    if dtype is None:
        return lambda x: x
    if dtype == jnp.float8_e4m3fn:
        def to_fp8(x):
            # per-tensor scaling into the format's range, as fp8 recipes do
            scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
            return (x / scale).astype(dtype).astype(jnp.float32) * scale
        return to_fp8
    return lambda x: x.astype(dtype).astype(jnp.float32)


def _rounded_product(product: Callable, r: Callable) -> Callable:
    """``product(x, w)`` as a matrix unit forms it that takes operands
    rounded by ``r``: forward from the rounded operands, and each of the
    two backward products from the rounded cotangent and the other
    rounded operand. Sums stay in float32."""
    @jax.custom_vjp
    def f(x, w):
        return product(r(x), r(w))

    def fwd(x, w):
        return jax.vjp(product, r(x), r(w))

    def bwd(vjp, g):
        return vjp(r(g))

    f.defvjp(fwd, bwd)
    return f


def _rounded_store(r: Callable) -> Callable:
    """A tensor kept in a narrower type: the value rounded on the way
    forward, its cotangent on the way back."""
    @jax.custom_vjp
    def f(x):
        return r(x)

    f.defvjp(lambda x: (r(x), None), lambda _, g: (r(g),))
    return f


class Numerics:
    """``conv``, ``dense`` and ``einsum`` as the reference or a control
    forms them;
    every product is taken at HIGHEST precision from what the mode leaves
    of its operands.

    ``float32``: operands, sums and stored tensors in float32.
    ``tpu_default``: what a TPU makes of float32 operands at its default
    precision: products of operands rounded to bfloat16, forward and
    backward, summed and stored in float32.
    ``bfloat16``: as ``tpu_default``, and activations and their
    cotangents stored in bfloat16.
    ``float8``: operands rounded to float8_e4m3fn with a per-tensor
    scale, activations stored in bfloat16.
    """

    MODES = {"float32": (None, None),
             "tpu_default": (jnp.bfloat16, None),
             "bfloat16": (jnp.bfloat16, jnp.bfloat16),
             "float8": (jnp.float8_e4m3fn, jnp.bfloat16)}

    def __init__(self, mode: str = "float32"):
        if mode not in self.MODES:
            raise ValueError(f"unknown numerics {mode!r}")
        self.mode = mode
        operand, stored = self.MODES[mode]
        self._operand = _rounder(operand)
        self._exact = operand is None
        self.store = (lambda x: x) if stored is None \
            else _rounded_store(_rounder(stored))

    def _product(self, product: Callable) -> Callable:
        return product if self._exact \
            else _rounded_product(product, self._operand)

    def conv(self, x, w, stride: int = 1):
        def product(a, b):
            return lax.conv_general_dilated(
                a, b, (stride, stride), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=lax.Precision.HIGHEST)
        return self.store(self._product(product)(x, w))

    def dense(self, x, w):
        def product(a, b):
            return jnp.dot(a, b, precision=lax.Precision.HIGHEST)
        return self.store(self._product(product)(x, w))

    def einsum(self, spec: str, a, b):
        """A batched product of two operands, formed like ``dense``:
        attention's two products, a per-expert product."""
        def product(x, y):
            return jnp.einsum(spec, x, y, precision=lax.Precision.HIGHEST)
        return self.store(self._product(product)(a, b))


def max_pool_3x3_s2(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                             (1, 2, 2, 1), "SAME")


def softmax_cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


# --- the K steps --------------------------------------------------------------

class Hyper(NamedTuple):
    """What every task shares: the shuffled stream's seed and size, and
    the dispatch's batch, length and first step."""

    seed: int
    batch: int
    steps: int                 # K: steps in one dispatch
    records: int
    step0: int = 0


class Task(NamedTuple):
    """What a configuration states of its training, beside its sizes: the
    four things that differ between an image classifier and any other
    model. A configuration's module gives one from ``task(spec, flags)``;
    one that states none gets :func:`image_task`.

    ``write_records(seed, n, paths) -> records``: what a run is given,
    made from the seed (``n`` training records; ``paths`` is where the
    program looks, ``{"train": [...], "test": [...]}``), written there,
    and returned as a tree of host arrays with ``n`` leading rows.
    ``feed(records, rows [B], key, step) -> batch``: any tree with ``B``
    leading rows, from the records on the device.
    ``loss(nm, params, model_state, batch) -> (loss, new_model_state)``.
    ``init_opt(params) -> {name: tree}`` and ``update(params, opt, grads,
    step) -> (params, opt)``: the optimizer's state, each tree under the
    name the program's own state gives it, and one step of it.
    ``fault(name) -> Task``: this task with one fault planted in the
    reference (``FAULTS``), to read what the comparison makes of it.
    """

    write_records: Callable
    feed: Callable
    loss: Callable
    init_opt: Callable
    update: Callable
    fault: Callable
    whole_chunk: bool = False  # one feed for the K*B rows of a dispatch
    grad_blocks: int = 1       # the gradient taken in this many blocks of
    #                            the batch, one after the other, and
    #                            averaged: a reference at a chip-filling
    #                            size fits beside its optimizer's state


FAULTS = ("half_batch",    # half of the batch left out of the loss's mean
          "no_exchange")   # one chip's quarter of the rows, nothing exchanged


class ChunkResult(NamedTuple):
    params: Any
    model_state: Any
    opt: Dict[str, Any]        # the optimizer's trees, by name
    losses: jax.Array          # [K]
    first_grad_norms: Any      # per leaf: the norm of step 0's gradient as
    #                            the optimizer got it


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


def _value_and_grad(loss_fn: Callable, blocks: int) -> Callable:
    """``(params, model_state, batch) -> ((loss, new_model_state), grads)``;
    in ``blocks`` > 1 the batch goes through in equal blocks, the model's
    state from one to the next, and loss and gradient are their means."""
    grad = jax.value_and_grad(loss_fn, has_aux=True)
    if blocks == 1:
        return grad

    def blocked(p, ms, batch):
        split = jax.tree.map(
            lambda x: x.reshape(blocks, x.shape[0] // blocks, *x.shape[1:]),
            batch)

        def one_block(carry, block):
            ms, loss, g = carry
            (block_loss, ms), gg = grad(p, ms, block)
            return (ms, loss + block_loss, jax.tree.map(jnp.add, g, gg)), None

        (ms, loss, g), _ = lax.scan(
            one_block, (ms, jnp.float32(0.0), jax.tree.map(jnp.zeros_like, p)),
            split)
        return (loss / blocks, ms), jax.tree.map(lambda x: x / blocks, g)

    return blocked


def make_chunk(task: Task, hyper: Hyper, numerics: str = "float32",
               batch_sharding=None) -> Callable:
    """The jitted ``(params, model_state, records, idx [K,B], data_key) ->
    ChunkResult``: K steps of training from step ``hyper.step0``. Trace it
    under ``jax.default_matmul_precision("highest")``.

    ``batch_sharding`` (a ``NamedSharding`` over the batch dimension) lets
    a four-chip cell's reference use all four chips; the arithmetic is the
    same.
    """
    nm = Numerics(numerics)
    k, b, step0 = hyper.steps, hyper.batch, hyper.step0
    if b % task.grad_blocks:
        raise ValueError(f"a batch of {b} does not divide into "
                         f"{task.grad_blocks} blocks")

    def constrain(x):
        if batch_sharding is None:
            return x
        return lax.with_sharding_constraint(x, batch_sharding(x.ndim))

    grad = _value_and_grad(functools.partial(task.loss, nm),
                           task.grad_blocks)

    @jax.jit
    def chunk(p, ms, records, idx, data_key):
        def one_step(carry, xs):
            p, ms, opt, first = carry
            i, rows, batch = xs
            if batch is None:
                batch = task.feed(records, constrain(rows), data_key,
                                  step0 + i)
            batch = jax.tree.map(constrain, batch)
            (loss, new_ms), g = grad(p, ms, batch)
            # in a branch of its own: a reduction that the compiler may
            # fuse into the gradient's producers changes their round-off
            first = lax.cond(i == 0, lambda: jax.tree.map(_norm, g),
                             lambda: first)
            p, opt = task.update(p, opt, g, step0 + i)
            return (p, new_ms, opt, first), loss

        first = jax.tree.map(lambda x: jnp.float32(0.0), p)
        whole = None
        if task.whole_chunk:
            flat = task.feed(records, idx.reshape(-1), data_key, step0)
            whole = jax.tree.map(lambda x: x.reshape(k, b, *x.shape[1:]),
                                 flat)
        (p, ms, opt, first), losses = lax.scan(
            one_step, (p, ms, task.init_opt(p), first),
            (jnp.arange(k), idx, whole))
        return ChunkResult(p, ms, opt, losses, first)

    return chunk


def run_chunk(task: Task, hyper: Hyper, params, model_state, records,
              numerics: str = "float32", batch_sharding=None) -> ChunkResult:
    """K steps of training from ``(params, model_state)`` at step
    ``hyper.step0`` (see :func:`make_chunk`)."""
    chunk = make_chunk(task, hyper, numerics, batch_sharding)
    idx = stream_rows(hyper.seed, hyper.step0 * hyper.batch,
                      hyper.steps * hyper.batch, hyper.records
                      ).reshape(hyper.steps, hyper.batch)
    with jax.default_matmul_precision("highest"):
        return chunk(params, model_state, records, jnp.asarray(idx),
                     jax.random.key(hyper.seed))


# --- the default task: an image classifier under SGD --------------------------

TEST_RECORDS = 512          # the program's default size of its test split
WHOLE_CHUNK_DECODE_BYTES = 1 << 30


class ImageHyper(NamedTuple):
    """What the default task reads from the configuration's file (the
    records' shape, the decode) and from the cell's flags (the update)."""

    classes: int
    image_size: int
    channels: int
    crop: int
    random_crop: bool
    random_flip: bool
    normalize: str
    learning_rate: float
    warmup_steps: int          # linear ramp: lr * min((step+1)/warmup, 1)
    momentum: float
    weight_decay: float
    decode_whole_chunk: bool   # one key for K*B images, else one per step
    fault: Optional[str] = None   # one of FAULTS, planted


def image_hyper(spec: dict, flags: dict) -> ImageHyper:
    side = max(spec["image_size"], spec["crop_size"])
    decoded = flags["steps_per_dispatch"] * flags["batch_size"] \
        * side * side * spec["num_channels"] * 4
    return ImageHyper(
        classes=spec["num_classes"], image_size=spec["image_size"],
        channels=spec["num_channels"], crop=spec["crop_size"],
        random_crop=spec["decode"]["random_crop"],
        random_flip=spec["decode"]["random_flip"],
        normalize=spec["decode"]["normalize"],
        learning_rate=flags["learning_rate"],
        warmup_steps=flags.get("warmup_steps", 0),
        momentum=flags.get("momentum", 0.0),
        weight_decay=flags.get("weight_decay", 0.0),
        decode_whole_chunk=decoded <= WHOLE_CHUNK_DECODE_BYTES)


def image_task(ih: ImageHyper, forward: Callable) -> Task:
    """uint8 images with a label each, a crop, a mirror and a
    normalisation, the mean softmax cross-entropy over rows, SGD with
    coupled weight decay, a momentum trace (under the name ``momentum``)
    and a linear warm-up of the learning rate.

    ``forward(nm, params, model_state, images) -> (logits, new_model_state)``
    is the configuration's plain forward pass.
    """
    if ih.fault is not None and ih.fault not in FAULTS:
        raise ValueError(f"unknown fault {ih.fault!r}")

    def write_records(seed, n, paths):
        made = []
        for s, count, files in ((seed, n, paths["train"]),
                                (seed + 1, TEST_RECORDS, paths["test"])):
            images, labels = datagen.make_records(
                s, count, ih.classes, ih.image_size, ih.image_size,
                ih.channels)
            datagen.write_record_files(files, images, labels, ih.classes)
            made.append((images, labels))
        return made[0]

    def feed(records, rows, key, step):
        images, labels = records
        x = decode(images[rows], jax.random.fold_in(key, step), ih.crop,
                   ih.crop, ih.random_crop, ih.random_flip, ih.normalize)
        return x, labels[rows]

    def loss(nm, p, ms, batch):
        x, y = batch
        if ih.fault == "no_exchange":
            seen = x.shape[0] // 4
            x, y = x[:seen], y[:seen]
        logits, new_ms = forward(nm, p, ms, x)
        if ih.fault == "half_batch":
            keep = logits.shape[0] // 2
            logits, y = logits[:keep], y[:keep]
        return softmax_cross_entropy(logits.astype(jnp.float32), y), new_ms

    def init_opt(p):
        return {"momentum": jax.tree.map(jnp.zeros_like, p)} \
            if ih.momentum else {}

    def update(p, opt, g, step):
        if ih.weight_decay:
            g = jax.tree.map(lambda gg, pp: gg + ih.weight_decay * pp, g, p)
        if ih.momentum:
            g = jax.tree.map(lambda m, gg: ih.momentum * m + gg,
                             opt["momentum"], g)
            opt = {"momentum": g}
        lr = jnp.float32(ih.learning_rate)
        if ih.warmup_steps:
            lr = lr * jnp.clip(
                (jnp.asarray(step).astype(jnp.float32) + 1.0)
                / ih.warmup_steps, 0.0, 1.0)
        return jax.tree.map(lambda pp, gg: pp - lr * gg, p, g), opt

    return Task(write_records, feed, loss, init_opt, update,
                fault=lambda name: image_task(ih._replace(fault=name),
                                              forward),
                whole_chunk=ih.decode_whole_chunk)

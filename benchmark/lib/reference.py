"""Plain reference trainer: the same K steps the timed dispatch ran, in
straightforward jax.numpy, float32 at the highest matmul precision.

Nothing here imports the program. What the program's first dispatch does
to the state is re-derived from the published description of each piece:

- the row of the dataset each batch position reads (a cycle-walking
  Feistel permutation per epoch, written out again below in NumPy);
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

from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

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
    """``conv`` and ``dense`` as the reference or a control forms them;
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


def max_pool_3x3_s2(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                             (1, 2, 2, 1), "SAME")


def softmax_cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


# --- the K steps --------------------------------------------------------------

class Hyper(NamedTuple):
    """What the cell's flags say about the feed and the update."""

    seed: int
    batch: int
    steps: int                 # K: steps in one dispatch
    records: int
    crop: int
    random_crop: bool
    random_flip: bool
    normalize: str
    learning_rate: float
    warmup_steps: int          # linear ramp: lr * min((step+1)/warmup, 1)
    momentum: float
    weight_decay: float
    decode_whole_chunk: bool   # one key for K*B images, else one per step
    # faults, planted to read what the comparison makes of them:
    batch_keep: Optional[int] = None   # the loss's mean over these rows only
    rows_seen: Optional[int] = None    # the step sees these rows only (one
    #                                    chip's share, nothing exchanged)


class ChunkResult(NamedTuple):
    params: Any
    model_state: Any
    momentum: Any
    losses: jax.Array          # [K]
    first_grad: Any            # gradient of step 0 as the optimizer got it


def make_chunk(forward: Callable, hyper: Hyper, numerics: str = "float32",
               batch_sharding=None, step0: int = 0) -> Callable:
    """The jitted ``(params, model_state, images_u8, labels, idx [K,B],
    data_key) -> ChunkResult``: K steps of training from step ``step0``.
    Trace it under ``jax.default_matmul_precision("highest")``.

    ``forward(nm, params, model_state, images) -> (logits, new_model_state)``
    is the configuration's plain forward pass. ``batch_sharding`` (a
    ``NamedSharding`` over the batch dimension) lets a four-chip cell's
    reference use all four chips; the arithmetic is the same.
    """
    nm = Numerics(numerics)
    k, b = hyper.steps, hyper.batch

    def constrain(x):
        if batch_sharding is None:
            return x
        return lax.with_sharding_constraint(x, batch_sharding(x.ndim))

    def loss_fn(p, ms, x, y):
        if hyper.rows_seen is not None:
            x, y = x[:hyper.rows_seen], y[:hyper.rows_seen]
        logits, new_ms = forward(nm, p, ms, x)
        keep = hyper.batch_keep
        if keep is not None:
            logits, y = logits[:keep], y[:keep]
        return softmax_cross_entropy(logits.astype(jnp.float32), y), new_ms

    @jax.jit
    def chunk(p, ms, imgs, lbls, idx, data_key):
        def one_step(carry, xs):
            p, ms, mom, first = carry
            i, rows, x = xs
            y = constrain(lbls[rows])
            if x is None:
                key = jax.random.fold_in(data_key, step0 + i)
                x = decode(constrain(imgs[rows]), key, hyper.crop,
                           hyper.crop, hyper.random_crop, hyper.random_flip,
                           hyper.normalize)
            x = constrain(x)
            (loss, new_ms), g = jax.value_and_grad(loss_fn, has_aux=True)(
                p, ms, x, y)
            first = jax.tree.map(lambda f, gg: jnp.where(i == 0, gg, f),
                                 first, g)
            if hyper.weight_decay:
                g = jax.tree.map(lambda gg, pp: gg + hyper.weight_decay * pp,
                                 g, p)
            if mom is not None:
                mom = jax.tree.map(lambda m, gg: hyper.momentum * m + gg,
                                   mom, g)
                g = mom
            lr = jnp.float32(hyper.learning_rate)
            if hyper.warmup_steps:
                step = (step0 + i).astype(jnp.float32)
                lr = lr * jnp.clip((step + 1.0) / hyper.warmup_steps,
                                   0.0, 1.0)
            p = jax.tree.map(lambda pp, gg: pp - lr * gg, p, g)
            return (p, new_ms, mom, first), loss

        mom = jax.tree.map(jnp.zeros_like, p) if hyper.momentum else None
        first = jax.tree.map(jnp.zeros_like, p)
        whole = None
        if hyper.decode_whole_chunk:
            key = jax.random.fold_in(data_key, step0)
            flat = decode(imgs[idx.reshape(-1)], key, hyper.crop, hyper.crop,
                          hyper.random_crop, hyper.random_flip,
                          hyper.normalize)
            whole = flat.reshape(k, b, *flat.shape[1:])
        (p, ms, mom, first), losses = lax.scan(
            one_step, (p, ms, mom, first), (jnp.arange(k), idx, whole))
        return ChunkResult(p, ms, mom, losses, first)

    return chunk


def run_chunk(forward: Callable, hyper: Hyper, params, model_state,
              images_u8, labels, numerics: str = "float32",
              batch_sharding=None, step0: int = 0) -> ChunkResult:
    """K steps of training from ``(params, model_state)`` at step ``step0``
    (see :func:`make_chunk`)."""
    chunk = make_chunk(forward, hyper, numerics, batch_sharding, step0)
    idx = stream_rows(hyper.seed, step0 * hyper.batch,
                      hyper.steps * hyper.batch, hyper.records
                      ).reshape(hyper.steps, hyper.batch)
    with jax.default_matmul_precision("highest"):
        return chunk(params, model_state, images_u8, labels,
                     jnp.asarray(idx), jax.random.key(hyper.seed))

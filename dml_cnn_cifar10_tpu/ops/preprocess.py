"""Device-side input preprocessing (cast / crop / augment / normalize).

The reference does all decode work on host CPU threads
(``cifar10cnn.py:54-70``: reader → transpose → cast → crop inside the
queue-runner graph). On TPU the roles invert: the tiny reference CNN is
~1 ms of MXU work per step, so a host that also casts to float32 and crops
cannot keep up (measured: host-decoded pipeline tops out ~2 orders of
magnitude below device compute). The TPU-native split is **host does IO
and shuffling of raw uint8 bytes; the device does the math** — uint8 H2D
is 4x less PCIe/ICI traffic than float32, and the cast/crop/normalize fuse
into the training step for free.

Used by the chunked training path (``parallel/step.py:make_train_chunk``
with ``data_cfg=``). Deterministic center-crop pipelines (faithful parity
+ bench) need no key; augmented configs (``random_crop``/``random_flip``,
fixed mode — any ``cfg.augmented`` field) pass a PRNG ``key`` and the
augmentation runs on device too: per-image random crop windows as one-hot
selection matmuls (MXU work, flips folded in), brightness/contrast as
per-image affine maps, all fused into the step.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from dml_cnn_cifar10_tpu.config import DataConfig


def device_preprocess(images_u8: jax.Array, cfg: DataConfig,
                      key: Optional[jax.Array] = None) -> jax.Array:
    """uint8 ``[..., H, W, C]`` full-size images → float32
    ``[..., crop_h, crop_w, C]``, cropped/augmented and normalized per
    ``cfg`` — the device-side mirror of the host pipeline's ``_finish``.
    Any randomized augmentation (``cfg.augmented``) requires ``key``."""
    if cfg.augmented and key is None:
        raise ValueError(
            "random crop/flip/brightness/contrast on device need a PRNG "
            "key; pass key= or use the host pipeline")
    with jax.named_scope("decode"):
        x = images_u8.astype(jnp.float32)
        if cfg.augmented:
            kc, kf, kb, kn = jax.random.split(key, 4)
        if cfg.random_crop:
            # Flip folds into the crop's column-selection matmul for free.
            x = _random_crop(x, cfg, kc,
                             flip_key=kf if cfg.random_flip else None)
        else:
            x = _center_crop(x, cfg)
            if cfg.random_flip:
                x = _random_flip(x, kf)
        if cfg.random_brightness:
            x = _random_brightness(x, cfg.random_brightness, kb)
        if cfg.random_contrast:
            x = _random_contrast(x, cfg.random_contrast, kn)
        return _normalize(x, cfg)


def _center_crop(x: jax.Array, cfg: DataConfig) -> jax.Array:
    h, w = x.shape[-3], x.shape[-2]
    if cfg.crop_height > h or cfg.crop_width > w:
        # Pad-if-smaller, same as the host records.center_crop (parity with
        # tf.image.resize_image_with_crop_or_pad).
        ph, pw = max(cfg.crop_height - h, 0), max(cfg.crop_width - w, 0)
        pad = ([(0, 0)] * (x.ndim - 3)
               + [(ph // 2, ph - ph // 2), (pw // 2, pw - pw // 2), (0, 0)])
        x = jnp.pad(x, pad)
        h, w = x.shape[-3], x.shape[-2]
    oh, ow = (h - cfg.crop_height) // 2, (w - cfg.crop_width) // 2
    return x[..., oh:oh + cfg.crop_height, ow:ow + cfg.crop_width, :]


def _random_crop(x: jax.Array, cfg: DataConfig, key: jax.Array,
                 flip_key: Optional[jax.Array] = None) -> jax.Array:
    """Per-image random window (the augmentation the reference's comment
    at ``cifar10cnn.py:67`` intended), with optional fused horizontal
    flip.

    TPU-native formulation: the per-image row/column selections are
    one-hot matrices and the crop is two batched matmuls — MXU work
    instead of per-image gathers (measured ~9x faster than
    ``vmap(dynamic_slice)`` and exact, since each output element is
    1·input). A flipped image's crop is the same column matmul with the
    column indices mirrored, so flip costs nothing extra.
    """
    lead = x.shape[:-3]
    h, w, c = x.shape[-3:]
    ch, cw = cfg.crop_height, cfg.crop_width
    flat = x.reshape((-1, h, w, c))
    n = flat.shape[0]
    kt, kl = jax.random.split(key)
    tops = jax.random.randint(kt, (n,), 0, h - ch + 1)
    lefts = jax.random.randint(kl, (n,), 0, w - cw + 1)
    rows = tops[:, None] + jnp.arange(ch)[None, :]            # [N, ch]
    cols = lefts[:, None] + jnp.arange(cw)[None, :]           # [N, cw]
    if flip_key is not None:
        flip = jax.random.bernoulli(flip_key, 0.5, (n,))
        cols = jnp.where(flip[:, None],
                         (w - 1 - lefts)[:, None] - jnp.arange(cw)[None, :],
                         cols)
    rsel = jax.nn.one_hot(rows, h, dtype=flat.dtype)          # [N, ch, H]
    csel = jax.nn.one_hot(cols, w, dtype=flat.dtype)          # [N, cw, W]
    out = jnp.einsum("nrh,nhwc->nrwc", rsel, flat)
    out = jnp.einsum("nkw,nrwc->nrkc", csel, out)
    return out.reshape(lead + (ch, cw, c))


def _random_flip(x: jax.Array, key: jax.Array) -> jax.Array:
    """Per-image horizontal flip with p=0.5 (mirrors records.random_flip)."""
    lead = x.shape[:-3]
    h, w, c = x.shape[-3:]
    flat = x.reshape((-1, h, w, c))
    flip = jax.random.bernoulli(key, 0.5, (flat.shape[0],))
    out = jnp.where(flip[:, None, None, None], flat[:, :, ::-1, :], flat)
    return out.reshape(lead + (h, w, c))


def _random_brightness(x: jax.Array, max_delta: float,
                       key: jax.Array) -> jax.Array:
    """Per-image additive brightness (mirrors records.random_brightness)."""
    lead = x.shape[:-3]
    n = int(np.prod(lead)) if lead else 1
    deltas = jax.random.uniform(key, (n,), minval=-max_delta,
                                maxval=max_delta)
    return x + deltas.reshape(lead + (1, 1, 1))


def _random_contrast(x: jax.Array, max_dev: float,
                     key: jax.Array) -> jax.Array:
    """Per-image contrast about the per-channel mean (mirrors
    records.random_contrast)."""
    lead = x.shape[:-3]
    n = int(np.prod(lead)) if lead else 1
    f = jax.random.uniform(key, (n,), minval=1.0 - max_dev,
                           maxval=1.0 + max_dev).reshape(lead + (1, 1, 1))
    mean = jnp.mean(x, axis=(-3, -2), keepdims=True)
    return (x - mean) * f + mean


def _normalize(x: jax.Array, cfg: DataConfig) -> jax.Array:
    if cfg.normalize == "scale":
        return x / 255.0
    if cfg.normalize == "standardize":
        axes = tuple(range(x.ndim - 3, x.ndim))
        mean = jnp.mean(x, axis=axes, keepdims=True)
        std = jnp.std(x, axis=axes, keepdims=True)
        # tf.image.per_image_standardization's min stddev guard
        n = cfg.crop_height * cfg.crop_width * x.shape[-1]
        return (x - mean) / jnp.maximum(std, 1.0 / jnp.sqrt(float(n)))
    if cfg.normalize != "none":
        raise ValueError(f"unknown normalize mode {cfg.normalize!r}")
    return x

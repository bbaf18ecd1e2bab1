"""Everything a run is given, made from ``--seed``: the dataset records on
disk in the layout the program reads, and the starting weights.

Records: a label, then a CHW uint8 image of noise around a colour that
the label fixes, so that a model has something to learn and every row
differs. The layout is the one `cifar10cnn.py` of the modelled system
reads: one label byte (two, big-endian, past 255 classes), then the
image.

Weights: one jitted call fills the program's own tree of shapes. A
matrix or kernel gets normal values of variance 1/(2 fan-in) (half the
He-normal deviation: with it the paper's CNN trains from the first step
at the cells' learning rates, where He-normal diverges), a ``scale``
values around 1, every other vector small values around 0, so that no
leaf's gradient vanishes at the start. The fan-in of a matrix or kernel is
the product of all its dimensions but the last, unless the configuration's
module states another for the leaf (``fan_in(path, shape)``: an
embedding's ``[V, D]``, an expert-major ``[E, D, H]``).
"""

from __future__ import annotations

import os
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def make_records(seed: int, n: int, classes: int, height: int, width: int,
                 channels: int = 3) -> Tuple[np.ndarray, np.ndarray]:
    """``(images [n,H,W,C] uint8, labels [n] int32)``."""
    rng = np.random.default_rng([seed, n, classes, height])
    labels = rng.integers(0, classes, size=n, dtype=np.int32)
    colour = rng.integers(0, 129, size=(classes, channels), dtype=np.uint8)
    noise = np.frombuffer(rng.bytes(n * height * width * channels),
                          dtype=np.uint8).reshape(n, height, width, channels)
    images = (noise >> 1) + colour[labels][:, None, None, :]
    return images, labels


def write_record_files(paths, images: np.ndarray, labels: np.ndarray,
                       classes: int) -> None:
    """Split the records evenly over ``paths``, in order."""
    n = len(labels)
    if n % len(paths):
        raise ValueError(f"{n} records do not divide over {len(paths)} files")
    wide = classes > 256
    per = n // len(paths)
    chw = np.ascontiguousarray(images.transpose(0, 3, 1, 2)).reshape(n, -1)
    head = np.stack([labels >> 8, labels & 0xFF], axis=1) if wide \
        else labels[:, None]
    recs = np.concatenate([head.astype(np.uint8), chw], axis=1)
    for i, path in enumerate(paths):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "wb") as f:
            f.write(recs[i * per:(i + 1) * per].tobytes())


def make_params(seed: int, abstract_params: Any, sharding=None,
                fan_in: Optional[Callable] = None) -> Any:
    """Fill ``abstract_params`` (a tree of shapes) from the seed, on the
    device, in one jitted call. ``fan_in(path, shape)`` is the
    configuration's own word on a leaf (``path`` as ``keystr`` prints
    it), or None for the rule above."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract_params)

    def build(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            name = str(getattr(path[-1], "key", getattr(path[-1], "idx", "")))
            noise = jax.random.normal(k, leaf.shape, jnp.float32)
            if leaf.ndim >= 2:
                fan = fan_in and fan_in(jax.tree_util.keystr(path),
                                        leaf.shape)
                v = noise * np.sqrt(
                    0.5 / (fan or int(np.prod(leaf.shape[:-1]))))
            elif name == "scale":
                v = 1.0 + 0.1 * noise
            else:
                v = 0.05 * noise
            out.append(v.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    fn = jax.jit(build, out_shardings=sharding) if sharding is not None \
        else jax.jit(build)
    return fn(jax.random.key(seed))

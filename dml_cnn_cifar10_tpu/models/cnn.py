"""The reference 5-layer CNN (2 conv + 3 FC), as a functional JAX model.

Exact architecture from ``create_cnn`` (``cifar10cnn.py:94-147``):

  conv1 5×5×C→64 s1 SAME + bias + ReLU   (:105-110)
  maxpool 3×3 s2 SAME                    (:113)
  conv2 5×5×64→64 s1 SAME + bias + ReLU  (:116-121)
  maxpool 3×3 s2 SAME                    (:123)
  flatten                                (:126-127)
  FC →384 + ReLU                         (:130-133)
  FC 384→192 + ReLU                      (:136-139)
  FC 192→num_classes (+ReLU in faithful mode — the reference clamps its
  logits at 0, ``:145``; ``ModelConfig.logit_relu`` controls this)

Init: truncated normal σ=0.05 for weights (``:97-98``), constant 0.1 for
biases (``:100-101``). Parameters live in a flat dict pytree; the weight
sharing the reference gets from ``tf.get_variable`` reuse (``:204-210``)
falls out of functional purity — the same pytree is passed to the train and
eval applications.

For CIFAR-100 the only change is ``num_classes=100`` (the "head swap"
config); for bigger inputs the flatten dim is derived from the config, not
hardcoded to 2304.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from dml_cnn_cifar10_tpu.config import DataConfig, ModelConfig
from dml_cnn_cifar10_tpu.ops import layers as L
from dml_cnn_cifar10_tpu.ops.relu_pool import bias_relu_max_pool

Params = Dict[str, Any]


def init_params(key: jax.Array, cfg: ModelConfig, data: DataConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    h, w = L.pooled_hw(data.crop_height, data.crop_width, n_pools=2)
    flat = h * w * 64
    ks = jax.random.split(key, 5)
    tn = lambda k, shape: L.truncated_normal_init(k, shape, cfg.init_stddev,
                                                  dtype)
    bias = lambda shape: L.bias_init(shape, cfg.bias_init, dtype)
    return {
        "conv1": {"kernel": tn(ks[0], (5, 5, data.num_channels, 64)),
                  "bias": bias((64,))},
        "conv2": {"kernel": tn(ks[1], (5, 5, 64, 64)), "bias": bias((64,))},
        "full1": {"kernel": tn(ks[2], (flat, 384)), "bias": bias((384,))},
        "full2": {"kernel": tn(ks[3], (384, 192)), "bias": bias((192,))},
        "full3": {"kernel": tn(ks[4], (192, cfg.num_classes)),
                  "bias": bias((cfg.num_classes,))},
    }


def apply(params: Params, images: jax.Array, cfg: ModelConfig,
          train: bool = True, mesh=None) -> jax.Array:
    """Forward pass: NHWC images → logits [B, num_classes].

    ``train`` is accepted for registry uniformity (this model has no
    BatchNorm/dropout, ``cifar10cnn.py:94-147``). ``mesh`` is the mesh of
    the enclosing GSPMD program, if any: the pools' kernels need it to
    place themselves (``ops/relu_pool.py``).
    """
    del train
    cdt = jnp.dtype(cfg.compute_dtype)
    x = images.astype(cdt)
    p = jax.tree.map(lambda a: a.astype(cdt), params)

    # One named scope a layer: metadata only, it is what
    # utils/devprof.scope_map reads a compiled instruction's layer from.
    # A convolution's bias and ReLU are with the pool behind it
    # (bias_relu_max_pool shares one backward pass between the three), a
    # dense layer's with it. Every reader of a pool's output and of its
    # input's gradient is one of the products below, so the pool's
    # kernels may store both as what a product rounds them to.
    store = L.product_operand_dtype(cdt)
    with jax.named_scope("conv1"):
        x = L.conv2d(x, p["conv1"]["kernel"])
    with jax.named_scope("pool1"):
        x = bias_relu_max_pool(x, p["conv1"]["bias"], mesh, store)
    with jax.named_scope("conv2"):
        x = L.conv2d(x, p["conv2"]["kernel"])
    with jax.named_scope("pool2"):
        x = bias_relu_max_pool(x, p["conv2"]["bias"], mesh, store)
    x = x.reshape(x.shape[0], -1)
    with jax.named_scope("fc1"):
        x = jax.nn.relu(L.dense(x, p["full1"]["kernel"], p["full1"]["bias"]))
    with jax.named_scope("fc2"):
        x = jax.nn.relu(L.dense(x, p["full2"]["kernel"], p["full2"]["bias"]))
    with jax.named_scope("logits"):
        logits = L.dense(x, p["full3"]["kernel"], p["full3"]["bias"])
        if cfg.logit_relu:  # faithful: reference ReLUs its logits (:145)
            logits = jax.nn.relu(logits)
        return logits.astype(jnp.float32)


# Shared implementation: models.param_count
from dml_cnn_cifar10_tpu.models import param_count  # noqa: E402,F401

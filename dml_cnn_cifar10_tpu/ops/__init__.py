"""XLA compute primitives.

The reference leans on TF's C++ op kernels — conv2d, max_pool, matmul,
bias_add, relu, softmax-CE, argmax (``cifar10cnn.py:107-145,154,173``). On
TPU the native layer is XLA: these wrappers lower to
``lax.conv_general_dilated`` / ``lax.reduce_window`` / ``jnp.dot`` so the
MXU sees large fused matmul/conv ops, with Pallas kernels
for the ops XLA doesn't schedule well (flash attention for the ViT
config, the CNN's bias + ReLU + max-pool pairs, the fused SGD update).
"""

from dml_cnn_cifar10_tpu.ops.layers import (  # noqa: F401
    bias_init,
    conv2d,
    dense,
    max_pool,
    truncated_normal_init,
)

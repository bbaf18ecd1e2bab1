"""Loss functions.

``softmax_cross_entropy`` is the parity loss: sparse softmax cross-entropy
averaged over the batch (``cifar_loss``, ``cifar10cnn.py:150-157`` —
squeeze/cast of targets happens in the data layer, which already yields int32
labels).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array,
                          label_smoothing: float = 0.0) -> jax.Array:
    """Mean sparse softmax CE. logits [B, K] float, labels [B] int.

    ``label_smoothing`` ε mixes the one-hot target with uniform:
    ``(1-ε)·onehot + ε/K`` (the ladder-config regularizer; 0 = parity).
    """
    with jax.named_scope("loss"):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32),
                                   axis=-1)[:, 0]
        if label_smoothing:
            uniform = -jnp.mean(logp, axis=-1)  # ε/K on every class
            nll = (1.0 - label_smoothing) * nll + label_smoothing * uniform
        return jnp.mean(nll)

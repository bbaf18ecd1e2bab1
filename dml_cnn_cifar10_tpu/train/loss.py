"""Loss functions.

``softmax_cross_entropy`` is the parity loss: sparse softmax cross-entropy
averaged over the batch (``cifar_loss``, ``cifar10cnn.py:150-157`` —
squeeze/cast of targets happens in the data layer, which already yields int32
labels).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from dml_cnn_cifar10_tpu.ops.layers import low_dot


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


# --- a loss over a sequence's tokens (models/looped_decoder.py) --------------

def _token_blocks(n: int, num_blocks: int) -> int:
    if n % num_blocks:
        raise ValueError(f"{n} tokens do not divide into {num_blocks} "
                         f"blocks")
    return n // num_blocks


def _map_blocks(fn, xs, num_blocks: int):
    """``fn`` over the leading blocks of ``xs``, one after the other; a
    plain call where there is one block (no loop in the program)."""
    if num_blocks == 1:
        return jax.tree.map(lambda y: y[None],
                            fn(jax.tree.map(lambda x: x[0], xs)))
    return lax.map(fn, xs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def blockwise_cross_entropy(h: jax.Array, w: jax.Array, targets: jax.Array,
                            num_blocks: int, dtype):
    """Per-token cross-entropy of the logits ``h [N, D] @ w [D, V]`` against
    ``targets [N]``, and whether the largest logit is the target's, without
    ever holding ``[N, V]``: the head's product, the log-sum-exp and the
    target's logit are taken ``N / num_blocks`` tokens at a time, and the
    backward pass forms ``g (softmax - onehot)`` again a block at a time
    from the kept log-sum-exp. The product's operands are rounded to
    ``dtype`` and summed in float32, forward and backward, as
    ``ops.layers.mixed_matmul`` does; everything else is float32.
    Returns ``(ce [N] float32, hit [N] float32)``; ``hit`` carries no
    gradient."""
    return _blockwise_ce_fwd(h, w, targets, num_blocks, dtype)[0]


def _blockwise_ce_fwd(h, w, targets, num_blocks, dtype):
    n, d = h.shape
    nb = _token_blocks(n, num_blocks)
    h_low = h.astype(dtype)

    def block(xs):
        hb, yb = xs
        logits = low_dot(hb, w, dtype)
        lse = jax.nn.logsumexp(logits, axis=-1)
        at = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
        hit = (at >= jnp.max(logits, axis=-1)).astype(jnp.float32)
        return lse - at, lse, hit

    with jax.named_scope("loss"):
        ce, lse, hit = _map_blocks(
            block, (h_low.reshape(num_blocks, nb, d),
                    targets.reshape(num_blocks, nb)), num_blocks)
    return (ce.reshape(n), hit.reshape(n)), \
        (h_low, w, targets, lse.reshape(n))


def _blockwise_ce_bwd(num_blocks, dtype, res, cot):
    h_low, w, targets, lse = res
    g = cot[0]
    n, d = h_low.shape
    nb = _token_blocks(n, num_blocks)
    w_low = w.astype(dtype)

    def block(dw, xs):
        hb, yb, lb, gb = xs
        logits = low_dot(hb, w_low, dtype)
        soft = jnp.exp(logits - lb[:, None])
        onehot = jax.nn.one_hot(yb, logits.shape[-1], dtype=jnp.float32)
        dlogits = (gb[:, None] * (soft - onehot)).astype(dtype)
        dh = jnp.dot(dlogits, w_low.T, preferred_element_type=jnp.float32)
        dw = dw + jnp.dot(hb.T, dlogits, preferred_element_type=jnp.float32)
        return dw, dh

    xs = (h_low.reshape(num_blocks, nb, d), targets.reshape(num_blocks, nb),
          lse.reshape(num_blocks, nb), g.reshape(num_blocks, nb))
    with jax.named_scope("loss"):
        zero = jnp.zeros(w.shape, jnp.float32)
        if num_blocks == 1:
            dw, dh = block(zero, jax.tree.map(lambda x: x[0], xs))
        else:
            dw, dh = lax.scan(block, zero, xs)
    return dh.reshape(n, d), dw.astype(w.dtype), None


blockwise_cross_entropy.defvjp(_blockwise_ce_fwd, _blockwise_ce_bwd)


def exit_distribution_log(gate_logits: jax.Array) -> jax.Array:
    """``log p`` of leaving at each pass, from the exit gates' logits
    ``[..., T]``: ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` with
    ``lambda = sigmoid(logit)``, and the last pass takes what is left.
    In logs throughout, so no product of small numbers is formed."""
    log_go = jax.nn.log_sigmoid(gate_logits)
    log_stay = jax.nn.log_sigmoid(-gate_logits)
    stayed = jnp.cumsum(log_stay, axis=-1) - log_stay   # sum over j < t
    return jnp.concatenate([(log_go + stayed)[..., :-1], stayed[..., -1:]],
                           axis=-1)


def exit_weighted_loss(ce: jax.Array, gate_logits: jax.Array,
                       beta: float) -> jax.Array:
    """Mean over tokens of ``sum_t p_t ce_t - beta H(p)``: the expected
    cross-entropy under each token's exit distribution, less ``beta``
    times that distribution's entropy. ``ce`` and ``gate_logits`` are
    ``[N, T]``."""
    with jax.named_scope("loss"):
        logp = exit_distribution_log(gate_logits.astype(jnp.float32))
        p = jnp.exp(logp)
        entropy = -jnp.sum(p * logp, axis=-1)
        return jnp.mean(jnp.sum(p * ce, axis=-1) - beta * entropy)

"""A looped causal decoder over tokens: the stack of layers runs
``total_ut_steps`` times on the same weights, with an exit head and a
learned exit gate after every pass (the LoopLM family, arXiv:2510.25741).

With tokens ``x [B, S]``: ``h = E[x]``. In each pass, for each layer:
``h = h + rms(attention(rms(h)) Wo)``, ``h = h + rms(mlp(rms(h)))``:
rotary causal attention over all heads, a gated SiLU MLP, an RMS norm
before and after each sublayer. After the layers of a pass ``h = rms(h)``
is the pass's exit state and the next pass's input; the head gives the
pass's logits from it and the gate, one linear unit through a sigmoid,
the chance of leaving there. The training loss is each token's expected
cross-entropy under its exit distribution less ``exit_entropy_beta`` times
that distribution's entropy (``train/loss.py``).

Sizes come from a JSON file in the shape of a published ``config.json``
(``--model_config_file``: ``hidden_size``, ``num_attention_heads``,
``head_dim``, ``intermediate_size``, ``num_hidden_layers``,
``vocab_size``, ``rms_norm_eps``, ``rope_theta``, ``total_ut_steps``,
``exit_entropy_beta``); without one, :data:`SMALL`.

The model states its own loss (``ModelDef.loss``): a batch is ``[B, S+1]``
int32 rows of a token dataset, inputs ``[:, :-1]`` and next-token targets
``[:, 1:]``, and no ``[tokens, vocabulary]`` array is ever held.

Numerics: parameters and the residual stream float32; every product of
operands rounded to ``compute_dtype`` and summed in float32
(``ops.layers.mixed_matmul``, the flash kernels); norms, rotary, softmax
and the loss float32.

How the loop is built: the passes are one ``lax.scan`` of length
``total_ut_steps`` whose body holds the layers (a Python loop: each layer
has its own weights) and the pass's exit terms. The weights are the scan's
constants, so each one's gradient is summed over the passes by the scan's
transpose, and the program holds one copy of a layer's code. With
``remat`` the activations kept for the backward pass are each layer
application's input and, by name (:data:`KEPT`), its flash kernel's output
and log-sum-exp: the backward pass recomputes a layer but its attention
kernel, whose recomputed launch nothing reads any more. Under the scan a
pass has one name scope, ``pass``, for all its rounds.
"""

from __future__ import annotations

import functools
import json
import os
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dml_cnn_cifar10_tpu.config import DataConfig, ModelConfig
from dml_cnn_cifar10_tpu.ops import attention as attention_lib
from dml_cnn_cifar10_tpu.ops import kernel_paths
from dml_cnn_cifar10_tpu.ops.layers import mixed_matmul, rms_norm
from dml_cnn_cifar10_tpu.train import loss as loss_lib

#: The sizes of a run that names no file: what the tests and the chip's
#: smoke run use.
SMALL: Dict[str, Any] = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "head_dim": 16, "intermediate_size": 128, "num_hidden_layers": 2,
    "vocab_size": 96, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "total_ut_steps": 4, "exit_entropy_beta": 0.1}

#: What a layer application keeps across its recomputation beside its
#: input, by the names ``ops/flash_attention.py`` gives the flash kernel's
#: residuals: its output and log-sum-exp, so that nothing in the backward
#: pass reads a recomputed forward kernel and the compiler drops it.
KEPT = ("flash_out", "flash_lse")

#: Tokens whose logits the loss holds at once, at most.
LOSS_BLOCK_TOKENS = 1024

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def config_path(path: str) -> str:
    """Where a ``--model_config_file`` is: looked for from the working
    directory, then from the repository's root."""
    if not os.path.isabs(path) and not os.path.isfile(path):
        path = os.path.join(_REPO, path)
    return os.path.abspath(path)


def read_config_file(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def _read_sizes(path: str) -> Dict[str, Any]:
    spec = read_config_file(path)
    missing = sorted(set(SMALL) - set(spec))
    if missing:
        raise ValueError(f"{path} lacks {missing}")
    return {k: spec[k] for k in SMALL}


def sizes(cfg: ModelConfig) -> Dict[str, Any]:
    """The model's sizes: the file ``cfg.config_file`` names
    (:func:`config_path`), else :data:`SMALL`."""
    if not cfg.config_file:
        return SMALL
    return _read_sizes(config_path(cfg.config_file))


def init_params(key: jax.Array, cfg: ModelConfig, data_cfg: DataConfig):
    """Normal weights of variance 1 / fan-in (the embedding: 1 / hidden),
    norm scales 1, the gate's bias 0."""
    del data_cfg
    sz = sizes(cfg)
    d, f, v = sz["hidden_size"], sz["intermediate_size"], sz["vocab_size"]
    a = sz["num_attention_heads"] * sz["head_dim"]
    kv = sz["num_key_value_heads"] * sz["head_dim"]
    dtype = jnp.dtype(cfg.dtype)
    keys = iter(jax.random.split(key, 3 + 7 * sz["num_hidden_layers"]))

    def matrix(fan_in, *shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)

    def scale():
        return {"scale": jnp.ones((d,), dtype)}

    def layer():
        return {"attn_norm": scale(), "wq": matrix(d, d, a),
                "wk": matrix(d, d, kv), "wv": matrix(d, d, kv),
                "wo": matrix(a, a, d), "attn_post_norm": scale(),
                "mlp_norm": scale(), "gate": matrix(d, d, f),
                "up": matrix(d, d, f), "down": matrix(f, f, d),
                "mlp_post_norm": scale()}

    return {"embed": matrix(d, v, d),
            "layers": [layer() for _ in range(sz["num_hidden_layers"])],
            "final_norm": scale(),
            "head": matrix(d, d, v),
            "exit_gate": {"w": matrix(d, d, 1), "b": jnp.zeros((1,), dtype)}}


def _layer(h, p, sz, cfg: ModelConfig, mesh):
    """One decoder layer on ``h [B, S, D]`` (float32)."""
    eps, low = sz["rms_norm_eps"], jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("attn_norm"):
        a = rms_norm(h, p["attn_norm"]["scale"], eps)
    with jax.named_scope("attn"):
        o = attention_lib.causal_self_attention(
            a, p, heads=sz["num_attention_heads"],
            kv_heads=sz["num_key_value_heads"], head_dim=sz["head_dim"],
            rope=sz["rope_theta"], low=low,
            use_pallas=cfg.use_pallas_attention, mesh=mesh)
    with jax.named_scope("attn_post_norm"):
        h = h + rms_norm(o, p["attn_post_norm"]["scale"], eps)
    with jax.named_scope("mlp_norm"):
        m = rms_norm(h, p["mlp_norm"]["scale"], eps)
    with jax.named_scope("mlp"):
        f = mixed_matmul(jax.nn.silu(mixed_matmul(m, p["gate"], low))
                         * mixed_matmul(m, p["up"], low), p["down"], low)
    with jax.named_scope("mlp_post_norm"):
        return h + rms_norm(f, p["mlp_post_norm"]["scale"], eps)


def token_blocks(tokens: int) -> int:
    """The least number of equal blocks of at most
    :data:`LOSS_BLOCK_TOKENS` tokens."""
    blocks = -(-tokens // LOSS_BLOCK_TOKENS)
    while tokens % blocks:
        blocks += 1
    return blocks


def exit_terms(params, rows, cfg: ModelConfig, mesh=None, passes=None,
               loss_blocks=None, scan_passes: bool = True):
    """``rows [B, S+1]`` int32 -> ``(ce, gate_logits, hit)``, each
    ``[T, B*S]``: every pass's per-token cross-entropy of the next token,
    its exit gate's logit, and whether its largest logit is the target's.

    ``passes`` overrides the file's ``total_ut_steps``; ``loss_blocks``
    the number of blocks the exit loss is taken in; ``scan_passes=False``
    writes the passes out one after the other (a program without the
    loop, for counting its operations)."""
    sz = sizes(cfg)
    low = jnp.dtype(cfg.compute_dtype)
    eps = sz["rms_norm_eps"]
    passes = passes or sz["total_ut_steps"]
    inputs, targets = rows[:, :-1], rows[:, 1:].reshape(-1)
    n = targets.shape[0]
    blocks = loss_blocks or token_blocks(n)

    def one_layer(h, p):
        return _layer(h, p, sz, cfg, mesh)

    if cfg.remat:
        kernel_paths.note("remat", "layer, keeps " + " ".join(KEPT))
        one_layer = jax.checkpoint(
            one_layer,
            policy=jax.checkpoint_policies.save_only_these_names(*KEPT))

    def one_pass(h, _):
        with jax.named_scope("pass"):
            for i, p in enumerate(params["layers"]):
                with jax.named_scope(f"layer{i}"):
                    h = one_layer(h, p)
        with jax.named_scope("exit"):
            with jax.named_scope("norm"):
                h = rms_norm(h, params["final_norm"]["scale"], eps)
            flat = h.reshape(n, h.shape[-1])
            with jax.named_scope("head"):
                ce, hit = loss_lib.blockwise_cross_entropy(
                    flat, params["head"], targets, blocks, low)
            with jax.named_scope("gate"):
                gate = params["exit_gate"]
                gate = mixed_matmul(flat, gate["w"], low)[:, 0] + gate["b"][0]
        return h, (ce, gate, hit)

    with jax.named_scope("embed"):
        h = params["embed"][inputs].astype(jnp.float32)
    if scan_passes:
        _, out = lax.scan(one_pass, h, None, length=passes)
        return out
    outs = []
    for _ in range(passes):
        h, o = one_pass(h, None)
        outs.append(o)
    return tuple(jnp.stack(x) for x in zip(*outs))


def loss(params, rows, cfg: ModelConfig, train: bool = True, mesh=None,
         **kwargs):
    """The model's own loss over a batch of token rows ``[B, S+1]`` ->
    ``(loss, {"accuracy": the last pass's share of next tokens right})``.
    ``kwargs`` go to :func:`exit_terms`."""
    del train                     # no dropout, no running statistics
    ce, gate, hit = exit_terms(params, rows, cfg, mesh=mesh, **kwargs)
    value = loss_lib.exit_weighted_loss(ce.T, gate.T,
                                        sizes(cfg)["exit_entropy_beta"])
    return value, {"accuracy": lax.stop_gradient(jnp.mean(hit[-1]))}


def batch_shape(cfg: ModelConfig, data_cfg: DataConfig, batch: int):
    """What a batch of this model is: rows of the token dataset."""
    del cfg
    return jax.ShapeDtypeStruct((batch, data_cfg.sequence_length + 1),
                                jnp.int32)


def param_count(cfg: ModelConfig) -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg,
                                                DataConfig()))
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))


def step_flops(cfg: ModelConfig, data_cfg: DataConfig, batch: int) -> float:
    """Operations of one training step on ``batch`` sequences, from the
    shapes: three times the forward's multiply-adds, two operations each.
    The layers' and the head's matrices once a pass; causal attention as
    the half square it is, ``S (S + 1) / 2`` pairs of a query and a key.
    What the backward pass computes a second time is not counted."""
    sz = sizes(cfg)
    s, d = data_cfg.sequence_length, sz["hidden_size"]
    a = sz["num_attention_heads"] * sz["head_dim"]
    kv = sz["num_key_value_heads"] * sz["head_dim"]
    layers = sz["num_hidden_layers"]
    per_token = layers * (2 * d * (a + kv)
                          + 3 * d * sz["intermediate_size"]) \
        + d * sz["vocab_size"]
    attention = layers * 2 * a * (s * (s + 1) // 2)
    return float(batch * 6 * sz["total_ut_steps"]
                 * (s * per_token + attention))

"""Plain reference of ``ouro_2p6b_l6``: a causal decoder whose stack of
layers runs ``total_ut_steps`` times on the same weights, with an exit
head and a learned exit gate after every pass (Ouro / LoopLM,
arXiv:2510.25741; ``modeling_ouro.py`` beside the published config).
Nothing here imports the program.

With tokens ``x [B, S]``: ``h = E[x]``. For each pass, with the same
weights in every pass, for each layer: ``a = rms(h; g1)``; ``q, k, v = a
Wq, a Wk, a Wv``; rotary positions on ``q`` and ``k`` (rotate-half over the
whole head dimension, positions ``0..S-1``); ``o = softmax(q k^T /
sqrt(head_dim) + causal mask) v``; ``h = h + rms(o Wo; g2)``; ``m =
rms(h; g3)``; ``f = (silu(m Wg) * (m Wu)) Wd``; ``h = h + rms(f; g4)``.
After the layers of a pass ``h = rms(h; g_f)``: the pass's exit state and
the next pass's input. ``logits_t = h W_head``, ``lambda_t = sigmoid(h
w_gate + b_gate)``. A token's exit distribution is ``p_1 = lambda_1``,
``p_t = lambda_t prod_{j<t} (1 - lambda_j)``, and the last pass takes what
is left. The loss is the mean over target tokens of ``sum_t p_t ce_t -
beta H(p)``.

Every product goes through ``nm.dense`` / ``nm.einsum``; norms, softmax,
rotary and the loss are float32. Straight ``jax.numpy``: the loop is a
Python ``for`` over passes and layers, attention is the full masked
softmax. Departures from the shortest way to write it, each for memory at
the published widths on one chip and none for arithmetic:

- each sequence's loss, each pass in it, each layer application, each
  head's attention and each block of the exit loss is a
  ``jax.checkpoint``, one inside the other: the backward pass then holds
  one sequence's four pass inputs, one pass's layer inputs and one
  layer's activations at a time, beside ONE tree of gradients (the
  gradient is not taken in blocks of the batch: ``reference.Task``'s
  ``grad_blocks`` keeps a second and a third tree of gradients, 2 GB
  each, and the compiler counted 18 GB of the chip's 16.9 that way);
- attention goes head by head (``lax.map``): one head's 4,096 x 4,096
  float32 scores are 67 MB, sixteen at once with their softmax and both
  cotangents over 4 GB;
- the exit loss holds the logits of ``sequence_length /
  reference_loss_blocks`` tokens at a time (whole logits where the file
  states 1 or nothing).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.lib import reference

TEST_RECORDS = 512          # the program's default size of its test split
LAYER_NORMS = ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm")


def param_shapes(spec: dict):
    """The tree the program holds (``models/looped_decoder.py``): compared
    with its ``init`` by ``jax.eval_shape`` in the tests."""
    v, d, f = spec["vocab_size"], spec["hidden_size"], \
        spec["intermediate_size"]
    a = spec["num_attention_heads"] * spec["head_dim"]

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    def layer():
        return {**{n: {"scale": f32(d)} for n in LAYER_NORMS},
                "wq": f32(d, a), "wk": f32(d, a), "wv": f32(d, a),
                "wo": f32(a, d), "gate": f32(d, f), "up": f32(d, f),
                "down": f32(f, d)}

    return {"embed": f32(v, d),
            "layers": [layer() for _ in range(spec["num_hidden_layers"])],
            "final_norm": {"scale": f32(d)},
            "head": f32(d, v),
            "exit_gate": {"w": f32(d, 1), "b": f32(1)}}


def fan_in(path: str, shape):
    if path == "['embed']":
        return shape[-1]          # rows are looked up, not summed over
    return None


def init_model_state(params):
    del params
    return {}


def param_count(spec: dict) -> int:
    return sum(int(np.prod(x.shape))
               for x in jax.tree.leaves(param_shapes(spec)))


def train_flops_per_image(spec: dict) -> int:
    """Per example: one sequence of ``sequence_length`` tokens, forward and
    backward (three times the forward's multiply-adds, two operations
    each). The layers' and the head's matrices are applied once a pass;
    causal attention is the half square it is, ``S (S + 1) / 2`` pairs of
    a query and a key, each a product of ``head_dim`` for the score and
    one for the value, in every head. Not counted: the embedding's gather,
    the gate's ``hidden_size`` multiply-adds a token, norms, softmax, and
    anything computed a second time in the backward pass."""
    s, d, f = spec["sequence_length"], spec["hidden_size"], \
        spec["intermediate_size"]
    a = spec["num_attention_heads"] * spec["head_dim"]
    layers, passes = spec["num_hidden_layers"], spec["total_ut_steps"]
    per_token = layers * (4 * d * a + 3 * d * f) + d * spec["vocab_size"]
    attention = layers * 2 * a * (s * (s + 1) // 2)
    return 3 * 2 * passes * (s * per_token + attention)


# --- the model ---------------------------------------------------------------

def rms_norm(x, p, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * p["scale"]


def rotary(x, theta: float):
    """``x [S, H, Dh]``: each pair ``(x[i], x[i + Dh/2])`` turned by
    ``position * theta ** (-2 i / Dh)``."""
    s, _, dh = x.shape
    inv_freq = theta ** (-np.arange(0, dh, 2, dtype=np.float64) / dh)
    angle = jnp.asarray(np.arange(s)[:, None] * inv_freq[None, :],
                        jnp.float32)
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None, :]
    half = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], -1)
    return x * cos + half * sin


def make_forward(spec: dict):
    heads, dh = spec["num_attention_heads"], spec["head_dim"]
    eps, theta = spec["rms_norm_eps"], spec["rope_theta"]
    if spec["num_key_value_heads"] != heads:
        raise ValueError("grouped key/value heads are not written here")

    def attention(nm, x, p):
        """One sequence ``x [S, D]``."""
        s = x.shape[0]
        a = rms_norm(x, p["attn_norm"], eps)
        q, k, v = (nm.dense(a, p[w]).reshape(s, heads, dh)
                   for w in ("wq", "wk", "wv"))
        q, k = rotary(q, theta), rotary(k, theta)
        causal = jnp.tril(jnp.ones((s, s), bool))

        @jax.checkpoint
        def one_head(qkv):
            qh, kh, vh = qkv
            scores = nm.einsum("qd,kd->qk", qh, kh) / np.sqrt(dh)
            prob = jax.nn.softmax(jnp.where(causal, scores, -1e30), -1)
            return nm.einsum("qk,kd->qd", prob, vh)

        out = lax.map(one_head, tuple(t.transpose(1, 0, 2)
                                      for t in (q, k, v)))
        out = out.transpose(1, 0, 2).reshape(s, heads * dh)
        return x + rms_norm(nm.dense(out, p["wo"]), p["attn_post_norm"],
                            eps)

    def layer(nm, x, p):
        x = attention(nm, x, p)
        m = rms_norm(x, p["mlp_norm"], eps)
        f = nm.dense(jax.nn.silu(nm.dense(m, p["gate"]))
                     * nm.dense(m, p["up"]), p["down"])
        return x + rms_norm(f, p["mlp_post_norm"], eps)

    def exit_states(nm, params, tokens, passes=None):
        """``tokens [S]`` -> the normed state after each pass, ``[S, D]``
        each."""
        one_layer = jax.checkpoint(lambda x, p: layer(nm, x, p))

        @jax.checkpoint
        def one_pass(h, layers, final_norm):
            for p in layers:
                h = one_layer(h, p)
            return rms_norm(h, final_norm, eps)

        h = params["embed"][tokens]
        out = []
        for _ in range(passes or spec["total_ut_steps"]):
            h = one_pass(h, params["layers"], params["final_norm"])
            out.append(h)
        return out

    return exit_states


def exit_distribution(lam):
    """``lam [..., T]`` -> ``p [..., T]``: the chance of leaving at each
    pass; the last pass takes what is left."""
    stay = jnp.cumprod(1.0 - lam, axis=-1)
    before = jnp.concatenate([jnp.ones_like(stay[..., :1]),
                              stay[..., :-1]], -1)
    p = lam * before
    return jnp.concatenate([p[..., :-1], before[..., -1:]], -1)


def make_loss(spec: dict, fault=None):
    exit_states = make_forward(spec)
    beta = spec["exit_entropy_beta"]
    blocks = spec.get("reference_loss_blocks", 1)

    def sequence_terms(nm, params, tokens, targets, passes=None):
        """One sequence -> ``ce [S, T]`` and ``lam [S, T]``."""
        @jax.checkpoint
        def block_terms(h, y, head, gate):
            logp = jax.nn.log_softmax(nm.dense(h, head), -1)
            ce = -jnp.take_along_axis(logp, y[:, None], -1)[:, 0]
            lam = jax.nn.sigmoid(nm.dense(h, gate["w"])[:, 0] + gate["b"][0])
            return ce, lam

        ce, lam = [], []
        for h in exit_states(nm, params, tokens, passes):
            c, g = lax.map(
                lambda hy: block_terms(*hy, params["head"],
                                       params["exit_gate"]),
                (h.reshape(blocks, -1, h.shape[-1]),
                 targets.reshape(blocks, -1)))
            ce.append(c.reshape(-1))
            lam.append(g.reshape(-1))
        return jnp.stack(ce, -1), jnp.stack(lam, -1)

    def sequence_loss(nm, params, tokens, y, passes):
        """One sequence -> its tokens' losses ``[S]``."""
        ce, lam = sequence_terms(nm, params, tokens, y, passes)
        p = exit_distribution(lam)
        entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), -1)
        return jnp.sum(p * ce, -1) - beta * entropy

    def loss(nm, params, model_state, batch, passes=None):
        inputs, targets = batch
        one = jax.checkpoint(
            lambda p, tokens, y: sequence_loss(nm, p, tokens, y, passes))
        # one sequence after the other (``lax.map``, not a Python loop: the
        # compiler otherwise runs both backward passes side by side)
        per_token = lax.map(lambda ty: one(params, *ty), (inputs, targets))
        # the faults, in tokens: a batch is two sequences, so what is left
        # out is part of each
        if fault == "half_batch":
            per_token = per_token[:, :per_token.shape[1] // 2]
        elif fault == "no_exchange":
            per_token = per_token[:, :per_token.shape[1] // 4]
        return jnp.mean(per_token), model_state

    return loss


# --- the task ----------------------------------------------------------------

def make_records(seed: int, n: int, vocab: int, length: int) -> np.ndarray:
    """``[n, length + 1]`` int32 token ids, uniform over the whole
    vocabulary."""
    rng = np.random.default_rng([seed, n, vocab, length])
    return rng.integers(0, vocab, size=(n, length + 1), dtype=np.int32)


def task(spec: dict, flags: dict, fault=None) -> reference.Task:
    vocab, length = spec["vocab_size"], flags["sequence_length"]
    if length != spec["sequence_length"]:
        raise ValueError("the traffic's sequence_length is not the one the "
                         "configuration's count of operations assumes")
    if fault is not None and fault not in reference.FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    b1, b2, eps = (flags[k] for k in ("adam_b1", "adam_b2", "adam_eps"))
    lr, decay = flags["learning_rate"], flags["weight_decay"]
    warmup = flags.get("warmup_steps", 0)

    def write_records(seed, n, paths):
        made = []
        for s, count, files in ((seed, n, paths["train"]),
                                (seed + 1, TEST_RECORDS, paths["test"])):
            tokens = make_records(s, count, vocab, length)
            for part, path in zip(np.array_split(tokens, len(files)), files):
                os.makedirs(os.path.dirname(path), exist_ok=True)
                part.astype("<i4").tofile(path)
            made.append(tokens)
        return made[0]

    def feed(records, rows, key, step):
        del key, step             # nothing is drawn: no crop, no mask
        tokens = records[rows]
        return tokens[:, :-1], tokens[:, 1:]

    def init_opt(params):
        return {"mu": jax.tree.map(jnp.zeros_like, params),
                "nu": jax.tree.map(jnp.zeros_like, params)}

    def update(params, opt, grads, step):
        t = jnp.asarray(step + 1).astype(jnp.float32)
        rate = jnp.float32(lr)
        if warmup:
            rate = rate * jnp.clip(t / warmup, 0.0, 1.0)
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt["mu"],
                          grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * jnp.square(g),
                          opt["nu"], grads)
        params = jax.tree.map(
            lambda p, m, v: p - rate * ((m / (1 - b1 ** t))
                                        / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
                                        + decay * p),
            params, mu, nu)
        return params, {"mu": mu, "nu": nu}

    return reference.Task(
        write_records, feed, make_loss(spec, fault), init_opt, update,
        fault=lambda name: task(spec, flags, name))

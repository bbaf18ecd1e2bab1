"""Plain reference of ``lfm2_8b_a1b_l5_e8``: a causal decoder whose
layers differ by a list (LFM2-8B-A1B, ``modeling_lfm2_moe.py`` beside the
published config): a gated short convolution or grouped-head attention,
then a dense gated MLP or sigmoid-routed experts, of which this chip holds
``num_experts`` of the router's ``router_num_experts``. Nothing here
imports the program.

With tokens ``x [S]`` of one sequence, no bias anywhere: ``h = E[x]``.
Every layer ``i``: ``a = rms(h; g_op_i)``, then by ``layer_types[i]``

- ``conv``: ``[Bg, Cg, X] = split3(a W_in)`` (that order); ``u = Bg * X``;
  ``c[t] = sum_{j<L} w[:, j] u[t - (L - 1) + j]`` per channel, zeros
  before the sequence's start; ``o = (Cg * c) W_out``;
- ``full_attention``: ``q = a Wq``, ``k = a Wk``, ``v = a Wv``; ``q =
  rms(q; g_q)``, ``k = rms(k; g_k)`` over each head's ``head_dim``; rotary
  positions on ``q`` and ``k`` (rotate-half, positions ``0..S-1``); query
  head ``j`` reads key/value head ``j // (heads / kv_heads)``; ``softmax(q
  k^T / sqrt(head_dim) + causal mask) v``; ``o = concat(heads) Wo``;

``h = h + o``; ``m = rms(h; g_ffn_i)``; for ``i < num_dense_layers`` ``f =
(silu(m W1) * (m W3)) W2``; else ``s = sigmoid(m Wr)`` over all
``router_num_experts`` experts, ``sel`` = the ``num_experts_per_tok``
experts with the largest ``s + b`` (the bias ``b`` decides the choice
only; it is a buffer of the model's state, zero at the start, and every
training step moves each expert's by ``expert_bias_update_rate`` toward an
even load: up where the expert got fewer of the batch's slots than the
mean over all of the router's experts, down where it got more),
``w_e = s_e / (sum_{e' in sel} s_e' + 1e-6)`` times
``routed_scaling_factor``, and ``f = sum_{e in sel, e held here} w_e
(silu(m W1_e) * (m W3_e)) W2_e``: what the absent experts would add is
left out, as in the program; ``h = h + f``. ``logits = rms(h; g_f) E^T``,
the loss the mean next-token cross-entropy over the ``vocab_size`` rows
held here. The config names no auxiliary loss, so there is none.

Every product goes through ``nm.dense`` / ``nm.einsum`` but the router's,
which the configuration states in float32 at the highest precision (the
choice of experts hangs on it); norms, softmax, rotary, the filter's taps
and gates and the loss are float32. Straight ``jax.numpy``: the experts
are a loop over those held, each a dense product over ALL tokens masked
by the choice (no sort, no grouped product). Departures from the shortest
way to write it, each for memory at the published widths on one chip and
none for arithmetic:

- the batch goes one sequence after the other (``lax.map``), and each
  sequence's loss, each layer, each head's attention, each expert and each
  block of the loss is a ``jax.checkpoint``, one inside the other;
- attention goes head by head: one head's 8,192 x 8,192 float32 scores
  are 268 MB, thirty-two at once with their softmax and both cotangents
  over 30 GB;
- the loss holds the logits of ``sequence_length /
  reference_loss_blocks`` tokens at a time.

Faults beside the harness's two: ``no_expert_bias`` (the choice by the
scores alone; with a bias that starts at zero it first differs in the
second step) and ``wrong_experts`` (the weights held answer to the ids
after those the configuration states).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.lib import reference

TEST_RECORDS = 512          # the program's default size of its test split
FAULTS = reference.FAULTS + ("no_expert_bias", "wrong_experts")


def _head_dim(spec: dict) -> int:
    return spec.get("head_dim") or \
        spec["hidden_size"] // spec["num_attention_heads"]


def param_shapes(spec: dict):
    """The tree the program holds (``models/hybrid_decoder.py``): compared
    with its ``init`` by ``jax.eval_shape`` in the tests."""
    v, d = spec["vocab_size"], spec["hidden_size"]
    f, hm = spec["intermediate_size"], spec["moe_intermediate_size"]
    dh = _head_dim(spec)
    a, kv = spec["num_attention_heads"] * dh, \
        spec["num_key_value_heads"] * dh
    e, e_all = spec["num_experts"], spec.get("router_num_experts",
                                             spec["num_experts"])

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    def layer(i, kind):
        p = {"op_norm": {"scale": f32(d)}, "ffn_norm": {"scale": f32(d)}}
        if kind == "conv":
            p["conv"] = {"w_in": f32(d, 3 * d),
                         "w": f32(d, spec["conv_L_cache"]),
                         "w_out": f32(d, d)}
        else:
            p["attn"] = {"wq": f32(d, a), "wk": f32(d, kv), "wv": f32(d, kv),
                         "wo": f32(a, d), "q_norm": {"scale": f32(dh)},
                         "k_norm": {"scale": f32(dh)}}
        if i < spec["num_dense_layers"]:
            p["mlp"] = {"w1": f32(d, f), "w3": f32(d, f), "w2": f32(f, d)}
        else:
            p["moe"] = {"router": f32(d, e_all), "w1": f32(e, d, hm),
                        "w3": f32(e, d, hm), "w2": f32(e, hm, d)}
        return p

    return {"embed": f32(v, d),
            "layers": [layer(i, kind)
                       for i, kind in enumerate(spec["layer_types"])],
            "final_norm": {"scale": f32(d)}}


def fan_in(path: str, shape):
    if path == "['embed']" or path.endswith("['conv']['w']"):
        return shape[-1]          # rows looked up; a filter of 3 taps
    if "['moe']['w" in path:
        return shape[-2]          # expert-major [E, in, out]
    return None


def init_model_state(params):
    """A layer's experts' bias, zero, where it has experts."""
    return {"layers": [
        {"expert_bias": jnp.zeros((p["moe"]["router"].shape[1],),
                                  jnp.float32)} if "moe" in p else {}
        for p in params["layers"]]}


def param_count(spec: dict) -> int:
    """Every number the model holds: the parameters and, where the file
    says ``use_expert_bias``, each expert layer's bias (a buffer: the
    published count has it)."""
    shapes = param_shapes(spec)
    buffers = sum(p["moe"]["router"].shape[1] for p in shapes["layers"]
                  if "moe" in p) if spec["use_expert_bias"] else 0
    return buffers + sum(int(np.prod(x.shape))
                         for x in jax.tree.leaves(shapes))


def train_flops_per_image(spec: dict) -> int:
    """Per example: one sequence of ``sequence_length`` tokens, forward and
    backward (three times the forward's multiply-adds, two operations
    each). The experts under uniform routing: of a token's
    ``num_experts_per_tok`` slots the share ``num_experts /
    router_num_experts`` falls on an expert held here (one slot a layer at
    4 of 32 with 8 held). Causal attention is the half square, ``S (S +
    1) / 2`` pairs of a query and a key, two products of ``head_dim`` a
    pair in every query head, forward once and backward two and a half
    times (five products for the forward's two). Not counted: the
    embedding's gather, the filter's taps and gates, norms, softmax, and
    anything computed a second time in the backward pass."""
    s, d = spec["sequence_length"], spec["hidden_size"]
    dh = _head_dim(spec)
    a, kv = spec["num_attention_heads"] * dh, \
        spec["num_key_value_heads"] * dh
    kinds = spec["layer_types"]
    conv, attn = kinds.count("conv"), kinds.count("full_attention")
    dense = spec["num_dense_layers"]
    e_all = spec.get("router_num_experts", spec["num_experts"])
    held_slots = spec["num_experts_per_tok"] * spec["num_experts"]
    per_token = conv * 4 * d * d + attn * 2 * d * (a + kv) \
        + dense * 3 * d * spec["intermediate_size"] \
        + (len(kinds) - dense) * (
            d * e_all
            + held_slots * 3 * d * spec["moe_intermediate_size"] // e_all) \
        + d * spec["vocab_size"]
    pairs = s * (s + 1) // 2
    return 6 * s * per_token + 7 * attn * 2 * a * pairs


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


def make_layers(spec: dict, fault=None) -> dict:
    """The model's pieces by name, each on one sequence: ``short_conv(nm,
    a, p)``, ``attention(nm, a, p)``, ``experts(nm, m, p)``, ``layer(nm,
    h, p, kind)`` and ``last_state(nm, params, tokens)``."""
    heads, kv_heads = spec["num_attention_heads"], \
        spec["num_key_value_heads"]
    dh = _head_dim(spec)
    eps, theta = spec["norm_eps"], spec["rope_theta"]
    taps, top_k = spec["conv_L_cache"], spec["num_experts_per_tok"]
    first = spec.get("expert_first_id", 0)
    if fault == "wrong_experts":
        first += spec["num_experts"]
    if spec["conv_bias"]:
        raise ValueError("conv_bias is not written here")

    def short_conv(nm, a, p):
        """One sequence ``a [S, D]``."""
        s = a.shape[0]
        gate_b, gate_c, x = jnp.split(nm.dense(a, p["w_in"]), 3, axis=-1)
        u = jnp.pad(gate_b * x, ((taps - 1, 0), (0, 0)))
        conv = sum(u[j:j + s] * p["w"][:, j] for j in range(taps))
        return nm.dense(gate_c * conv, p["w_out"])

    def attention(nm, a, p):
        s = a.shape[0]
        q = nm.dense(a, p["wq"]).reshape(s, heads, dh)
        k = nm.dense(a, p["wk"]).reshape(s, kv_heads, dh)
        v = nm.dense(a, p["wv"]).reshape(s, kv_heads, dh)
        q = rotary(rms_norm(q, p["q_norm"], eps), theta)
        k = rotary(rms_norm(k, p["k_norm"], eps), theta)
        # query head j reads key/value head j // (heads / kv_heads)
        k, v = (jnp.repeat(t, heads // kv_heads, axis=1) for t in (k, v))
        causal = jnp.tril(jnp.ones((s, s), bool))

        @jax.checkpoint
        def one_head(qkv):
            qh, kh, vh = qkv
            scores = nm.einsum("qd,kd->qk", qh, kh) / np.sqrt(dh)
            prob = jax.nn.softmax(jnp.where(causal, scores, -1e30), -1)
            return nm.einsum("qk,kd->qd", prob, vh)

        out = lax.map(one_head, tuple(t.transpose(1, 0, 2)
                                      for t in (q, k, v)))
        return nm.dense(out.transpose(1, 0, 2).reshape(s, heads * dh),
                        p["wo"])

    def gated_mlp(nm, m, w1, w3, w2):
        return nm.dense(jax.nn.silu(nm.dense(m, w1)) * nm.dense(m, w3), w2)

    def experts(nm, m, p, bias=None):
        """What the experts held here add to each token of ``m [S, D]``,
        and how many of the sequence's slots each of the router's experts
        got."""
        score = jax.nn.sigmoid(jnp.dot(m, p["router"],
                                       precision=lax.Precision.HIGHEST))
        ranked = score
        if bias is not None and fault != "no_expert_bias":
            ranked = score + bias
        _, chosen = lax.top_k(lax.stop_gradient(ranked), top_k)
        weight = jnp.take_along_axis(score, chosen, axis=-1)
        if spec["norm_topk_prob"]:
            weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-6)
        weight = weight * spec["routed_scaling_factor"]

        @jax.checkpoint
        def one_expert(m, w1, w3, w2, mine):
            return mine[:, None] * gated_mlp(nm, m, w1, w3, w2)

        out = jnp.zeros_like(m)
        for e in range(spec["num_experts"]):
            mine = jnp.sum(jnp.where(chosen == first + e, weight, 0.0), -1)
            out = out + one_expert(m, p["w1"][e], p["w3"][e], p["w2"][e],
                                   mine)
        load = jnp.sum(chosen[..., None] == jnp.arange(score.shape[-1]),
                       axis=(0, 1))
        return out, load

    def layer(nm, h, p, kind, bias=None):
        """-> the layer's output and its experts' load (None without)."""
        a = rms_norm(h, p["op_norm"], eps)
        h = h + (short_conv(nm, a, p["conv"]) if kind == "conv"
                 else attention(nm, a, p["attn"]))
        m = rms_norm(h, p["ffn_norm"], eps)
        if "mlp" in p:
            return h + gated_mlp(nm, m, p["mlp"]["w1"], p["mlp"]["w3"],
                                 p["mlp"]["w2"]), None
        f, load = experts(nm, m, p["moe"], bias)
        return h + f, load

    def last_state(nm, params, model_state, tokens):
        """``tokens [S]`` -> the normed state under the head, ``[S, D]``,
        and each layer's experts' load (None for a layer without)."""
        h, loads = params["embed"][tokens], []
        for kind, p, state in zip(spec["layer_types"], params["layers"],
                                  model_state["layers"]):
            bias = state.get("expert_bias") if spec["use_expert_bias"] \
                else None
            h, load = jax.checkpoint(
                lambda h, p, bias, kind=kind: layer(nm, h, p, kind, bias))(
                    h, p, bias)
            loads.append(load)
        return rms_norm(h, params["final_norm"], eps), loads

    return {"short_conv": short_conv, "attention": attention,
            "experts": experts, "layer": layer, "last_state": last_state}


def make_loss(spec: dict, fault=None):
    last_state = make_layers(spec, fault)["last_state"]
    blocks = spec.get("reference_loss_blocks", 1)

    rate = spec.get("expert_bias_update_rate", 0.0)

    def sequence_loss(nm, params, model_state, tokens, targets):
        """One sequence -> its tokens' cross-entropies ``[S]`` and the
        loads of its layers' experts."""
        @jax.checkpoint
        def block_ce(h, y, embed):
            logp = jax.nn.log_softmax(nm.dense(h, embed.T), -1)
            return -jnp.take_along_axis(logp, y[:, None], -1)[:, 0]

        h, loads = last_state(nm, params, model_state, tokens)
        ce = lax.map(lambda hy: block_ce(*hy, params["embed"]),
                     (h.reshape(blocks, -1, h.shape[-1]),
                      targets.reshape(blocks, -1)))
        return ce.reshape(-1), loads

    def loss(nm, params, model_state, batch):
        inputs, targets = batch
        one = jax.checkpoint(
            lambda p, tokens, y: sequence_loss(nm, p, model_state, tokens,
                                               y))
        # one sequence after the other (``lax.map``, not a Python loop: the
        # compiler otherwise runs the backward passes side by side)
        per_token, loads = lax.map(lambda ty: one(params, *ty),
                                   (inputs, targets))
        # the experts' bias, one step toward an even load over the batch
        new_state = {"layers": [
            state if load is None or "expert_bias" not in state else
            {"expert_bias": state["expert_bias"] + rate * jnp.sign(
                jnp.mean(jnp.sum(load, 0).astype(jnp.float32))
                - jnp.sum(load, 0))}
            for state, load in zip(model_state["layers"], loads)]}
        # the harness's faults, in tokens: what is left out is part of each
        # sequence
        if fault == "half_batch":
            per_token = per_token[:, :per_token.shape[1] // 2]
        elif fault == "no_exchange":
            per_token = per_token[:, :per_token.shape[1] // 4]
        return jnp.mean(per_token), jax.tree.map(lax.stop_gradient,
                                                 new_state)

    return loss


# --- the task ----------------------------------------------------------------

def make_records(seed: int, n: int, vocab: int, length: int) -> np.ndarray:
    """``[n, length + 1]`` int32 token ids, uniform over the slice of the
    vocabulary held here."""
    rng = np.random.default_rng([seed, n, vocab, length])
    return rng.integers(0, vocab, size=(n, length + 1), dtype=np.int32)


def task(spec: dict, flags: dict, fault=None) -> reference.Task:
    vocab, length = spec["vocab_size"], flags["sequence_length"]
    if length != spec["sequence_length"]:
        raise ValueError("the traffic's sequence_length is not the one the "
                         "configuration's count of operations assumes")
    if fault is not None and fault not in FAULTS:
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

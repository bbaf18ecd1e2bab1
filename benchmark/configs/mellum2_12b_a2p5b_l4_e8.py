"""Plain reference of ``mellum2_12b_a2p5b_l4_e8``: a causal decoder whose
layers are attention then experts, the attention over a sliding window in
three layers of four and over the whole prefix in the fourth, each kind
with a rotary rule of its own (Mellum2-12B-A2.5B, from the keys of its
published config; its modeling code is not here). Of the router's
``router_num_experts`` experts this chip holds ``num_experts``. Nothing
here imports the program.

With tokens ``x [S]`` of one sequence, no bias anywhere: ``h = E[x]``.
Every layer ``i``, of kind ``layer_types[i]``: ``a = rms(h; g_op_i)``; ``q
= a Wq`` (``heads`` of ``head_dim``), ``k = a Wk``, ``v = a Wv``
(``kv_heads`` of ``head_dim``); rotary on ``q`` and ``k`` by the rule
``rope_parameters[kind]`` (rotate-half over the whole head, positions
``0..S-1``); query head ``j`` reads key/value head ``j // (heads /
kv_heads)``; scores ``q k^T / sqrt(head_dim)``; the mask keeps ``col <=
row`` and, in a ``sliding_attention`` layer, also ``col > row -
sliding_window``; softmax in float32; ``o = concat(heads) Wo``; ``h = h +
o``. ``m = rms(h; g_ffn_i)``; ``p = softmax(m Wr)`` over ALL the router's
logits; ``sel`` = the ``num_experts_per_tok`` experts with the largest
``p``; ``w_e = p_e / sum_{e' in sel} p_e'`` (``norm_topk_prob``; no 1e-6
in the sum); ``f = sum_{e in sel, e held here} w_e (silu(m W1_e) * (m
W3_e)) W2_e``: what the absent experts would add is left out, as in the
program; ``h = h + f``. ``logits = rms(h; g_f) W_head``, ``W_head [D, V]``
a parameter of its own; the loss the mean next-token cross-entropy over
the ``vocab_size`` rows held here. No auxiliary loss, no state.

The rotary rules. ``default``: ``inv_freq_j = theta ** (-2 j /
head_dim)``, cos and sin as they come. ``yarn`` (as the ``transformers``
library computes it): ``dim(n) = head_dim ln(original / (2 pi n)) / (2 ln
theta)``, ``low = floor(dim(beta_fast))``, ``high = ceil(dim(beta_slow))``
(clipped to ``0 .. head_dim - 1``), ``ramp_j = clip((j - low) / (high -
low), 0, 1)``, ``inv_freq_j = (1 - ramp_j) theta ** (-2 j / head_dim) +
ramp_j theta ** (-2 j / head_dim) / factor``, and cos and sin both times
``attention_factor``, at every length.

Every product goes through ``nm.dense`` / ``nm.einsum`` but the router's,
which the configuration states in float32 at the highest precision (the
choice of experts hangs on it); norms, both softmaxes, rotary and the loss
are float32. Straight ``jax.numpy``: the band is a mask on the full ``[S,
S]`` scores, the experts are a loop over those held, each a dense product
over ALL tokens masked by the choice (no sort, no grouped product, no
kernel). Departures from the shortest way to write it, each for memory at
the published widths on one chip and none for arithmetic: the batch goes
one sequence after the other (``lax.map``), attention head by head, the
loss ``sequence_length / reference_loss_blocks`` tokens at a time, and
each sequence, layer, head, expert and block of the loss is a
``jax.checkpoint``, one inside the other.

Faults beside the harness's two: ``no_window`` (full attention in the
window layers), ``one_rope`` (the sliding layers' plain rotary in the full
layer too) and ``wrong_experts`` (the weights held answer to the ids after
those the configuration states).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.lib import reference, window_costs

TEST_RECORDS = 512          # the program's default size of its test split
FAULTS = reference.FAULTS + ("no_window", "one_rope", "wrong_experts")


def param_shapes(spec: dict):
    """The tree the program holds (``models/hybrid_decoder.py``): compared
    with its ``init`` by ``jax.eval_shape`` in the tests."""
    v, d, hm = spec["vocab_size"], spec["hidden_size"], \
        spec["moe_intermediate_size"]
    dh = spec["head_dim"]
    a, kv = spec["num_attention_heads"] * dh, \
        spec["num_key_value_heads"] * dh
    e, e_all = spec["num_experts"], spec.get("router_num_experts",
                                             spec["num_experts"])

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    def layer():
        return {"op_norm": {"scale": f32(d)}, "ffn_norm": {"scale": f32(d)},
                "attn": {"wq": f32(d, a), "wk": f32(d, kv), "wv": f32(d, kv),
                         "wo": f32(a, d)},
                "moe": {"router": f32(d, e_all), "w1": f32(e, d, hm),
                        "w3": f32(e, d, hm), "w2": f32(e, hm, d)}}

    return {"embed": f32(v, d),
            "layers": [layer() for _ in spec["layer_types"]],
            "final_norm": {"scale": f32(d)}, "head": f32(d, v)}


def fan_in(path: str, shape):
    if path == "['embed']":
        # rows looked up, of variance 1 (the benchmark draws variance 1 /
        # (2 fan-in)): the head is a matrix of its own, so the embedding
        # need not serve as one, and rows as small as a product's weights
        # would leave the residual stream of the first layers to
        # attention's output, which is nearly the same vector for every
        # token of a neighbourhood (the mean of a window's values): the
        # router then scores every token alike and a seed's weights decide
        # which experts fill (the configuration's file, ``assumed``)
        return 0.5
    if "['moe']['w" in path:
        return shape[-2]          # expert-major [E, in, out]
    return None


def init_model_state(params):
    """No bias on the choice, no running statistic: nothing."""
    return {"layers": [{} for _ in params["layers"]]}


def param_count(spec: dict) -> int:
    return sum(int(np.prod(x.shape))
               for x in jax.tree.leaves(param_shapes(spec)))


def train_flops_per_image(spec: dict) -> int:
    """Per example: one sequence of ``sequence_length`` tokens, forward and
    backward (three times the forward's multiply-adds, two operations
    each). The experts under uniform routing: of a token's
    ``num_experts_per_tok`` slots the share ``num_experts /
    router_num_experts`` falls on an expert held here (one slot a layer at
    8 of 64 with 8 held). Attention is the pairs the mask keeps
    (``window_costs.band_pairs``: the band in a window layer, 23.4% of the half
    square at 8,192 and 1,024), two products of ``head_dim`` a pair in
    every query head, forward once and backward two and a half times. The
    head once. Not counted: the embedding's gather, norms, softmax, and
    anything computed a second time in the backward pass."""
    s, d = spec["sequence_length"], spec["hidden_size"]
    dh = spec["head_dim"]
    a, kv = spec["num_attention_heads"] * dh, \
        spec["num_key_value_heads"] * dh
    kinds = spec["layer_types"]
    e_all = spec.get("router_num_experts", spec["num_experts"])
    held_slots = spec["num_experts_per_tok"] * spec["num_experts"]
    per_token = len(kinds) * (
        2 * d * (a + kv) + d * e_all
        + held_slots * 3 * d * spec["moe_intermediate_size"] // e_all) \
        + d * spec["vocab_size"]
    pairs = sum(window_costs.band_pairs(
        s, spec["sliding_window"] if kind == "sliding_attention" else None)
        for kind in kinds)
    return 6 * s * per_token + 7 * 2 * a * pairs


# --- the model ---------------------------------------------------------------

def rms_norm(x, p, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * p["scale"]


def yarn_range(rule: dict, dh: int):
    """``(low, high)``: the pairs between which YaRN's ramp runs."""
    theta, original = rule["rope_theta"], \
        rule["original_max_position_embeddings"]

    def dim(turns):
        return dh * np.log(original / (2 * np.pi * turns)) \
            / (2 * np.log(theta))

    return max(int(np.floor(dim(rule.get("beta_fast", 32)))), 0), \
        min(int(np.ceil(dim(rule.get("beta_slow", 1)))), dh - 1)


def inv_frequencies(rule: dict, dh: int):
    """``(inv_freq [dh / 2] float64, what cos and sin are multiplied by)``
    of one rotary rule (module docstring)."""
    j = np.arange(dh // 2, dtype=np.float64)
    plain = rule["rope_theta"] ** (-2 * j / dh)
    if rule.get("rope_type", "default") == "default":
        return plain, 1.0
    low, high = yarn_range(rule, dh)
    ramp = np.clip((j - low) / max(high - low, 0.001), 0.0, 1.0)
    factor = rule.get("attention_factor")
    if factor is None:
        factor = 0.1 * np.log(rule["factor"]) + 1.0
    return (1 - ramp) * plain + ramp * plain / rule["factor"], float(factor)


def rotary(x, rule: dict):
    """``x [S, H, Dh]``: each pair ``(x[i], x[i + Dh/2])`` turned by
    ``position * inv_freq_i``, cos and sin times the rule's factor."""
    s, _, dh = x.shape
    inv_freq, factor = inv_frequencies(rule, dh)
    angle = jnp.asarray(np.arange(s)[:, None] * inv_freq[None, :],
                        jnp.float32)
    cos = factor * jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None, :]
    sin = factor * jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None, :]
    half = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], -1)
    return x * cos + half * sin


def make_layers(spec: dict, fault=None) -> dict:
    """The model's pieces by name, each on one sequence: ``attention(nm,
    a, p, kind)``, ``experts(nm, m, p)``, ``layer(nm, h, p, kind)`` and
    ``last_state(nm, params, tokens)``."""
    heads, kv_heads = spec["num_attention_heads"], \
        spec["num_key_value_heads"]
    dh, eps = spec["head_dim"], spec["rms_norm_eps"]
    top_k = spec["num_experts_per_tok"]
    rules, window = spec["rope_parameters"], spec["sliding_window"]
    first = spec.get("expert_first_id", 0)
    if fault == "wrong_experts":
        first += spec["num_experts"]

    def attention(nm, a, p, kind):
        s = a.shape[0]
        rule = rules["sliding_attention" if fault == "one_rope" else kind]
        q = rotary(nm.dense(a, p["wq"]).reshape(s, heads, dh), rule)
        k = rotary(nm.dense(a, p["wk"]).reshape(s, kv_heads, dh), rule)
        v = nm.dense(a, p["wv"]).reshape(s, kv_heads, dh)
        # query head j reads key/value head j // (heads / kv_heads)
        k, v = (jnp.repeat(t, heads // kv_heads, axis=1) for t in (k, v))
        row, col = np.arange(s)[:, None], np.arange(s)[None, :]
        seen = col <= row
        if kind == "sliding_attention" and fault != "no_window":
            seen = seen & (col > row - window)
        seen = jnp.asarray(seen)

        @jax.checkpoint
        def one_head(qkv):
            qh, kh, vh = qkv
            scores = nm.einsum("qd,kd->qk", qh, kh) / np.sqrt(dh)
            prob = jax.nn.softmax(jnp.where(seen, scores, -1e30), -1)
            return nm.einsum("qk,kd->qd", prob, vh)

        out = lax.map(one_head, tuple(t.transpose(1, 0, 2)
                                      for t in (q, k, v)))
        return nm.dense(out.transpose(1, 0, 2).reshape(s, heads * dh),
                        p["wo"])

    def gated_mlp(nm, m, w1, w3, w2):
        return nm.dense(jax.nn.silu(nm.dense(m, w1)) * nm.dense(m, w3), w2)

    def experts(nm, m, p):
        """What the experts held here add to each token of ``m [S, D]``."""
        prob = jax.nn.softmax(jnp.dot(m, p["router"],
                                      precision=lax.Precision.HIGHEST), -1)
        _, chosen = lax.top_k(lax.stop_gradient(prob), top_k)
        weight = jnp.take_along_axis(prob, chosen, axis=-1)
        if spec["norm_topk_prob"]:
            weight = weight / jnp.sum(weight, -1, keepdims=True)

        @jax.checkpoint
        def one_expert(m, w1, w3, w2, mine):
            return mine[:, None] * gated_mlp(nm, m, w1, w3, w2)

        out = jnp.zeros_like(m)
        for e in range(spec["num_experts"]):
            mine = jnp.sum(jnp.where(chosen == first + e, weight, 0.0), -1)
            out = out + one_expert(m, p["w1"][e], p["w3"][e], p["w2"][e],
                                   mine)
        return out

    def layer(nm, h, p, kind):
        h = h + attention(nm, rms_norm(h, p["op_norm"], eps), p["attn"],
                          kind)
        return h + experts(nm, rms_norm(h, p["ffn_norm"], eps), p["moe"])

    def last_state(nm, params, tokens):
        """``tokens [S]`` -> the normed state under the head, ``[S, D]``."""
        h = params["embed"][tokens]
        for kind, p in zip(spec["layer_types"], params["layers"]):
            h = jax.checkpoint(
                lambda h, p, kind=kind: layer(nm, h, p, kind))(h, p)
        return rms_norm(h, params["final_norm"], eps)

    return {"attention": attention, "experts": experts, "layer": layer,
            "last_state": last_state}


def make_loss(spec: dict, fault=None):
    last_state = make_layers(spec, fault)["last_state"]
    blocks = spec.get("reference_loss_blocks", 1)

    def sequence_loss(nm, params, tokens, targets):
        """One sequence -> its tokens' cross-entropies ``[S]``."""
        @jax.checkpoint
        def block_ce(h, y, head):
            logp = jax.nn.log_softmax(nm.dense(h, head), -1)
            return -jnp.take_along_axis(logp, y[:, None], -1)[:, 0]

        h = last_state(nm, params, tokens)
        ce = lax.map(lambda hy: block_ce(*hy, params["head"]),
                     (h.reshape(blocks, -1, h.shape[-1]),
                      targets.reshape(blocks, -1)))
        return ce.reshape(-1)

    def loss(nm, params, model_state, batch):
        inputs, targets = batch
        one = jax.checkpoint(
            lambda p, tokens, y: sequence_loss(nm, p, tokens, y))
        # one sequence after the other (``lax.map``, not a Python loop: the
        # compiler otherwise runs the backward passes side by side)
        per_token = lax.map(lambda ty: one(params, *ty), (inputs, targets))
        # the harness's faults, in tokens: what is left out is part of each
        # sequence
        if fault == "half_batch":
            per_token = per_token[:, :per_token.shape[1] // 2]
        elif fault == "no_exchange":
            per_token = per_token[:, :per_token.shape[1] // 4]
        return jnp.mean(per_token), model_state

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

"""Plain reference of ``deepseek_v2_lite_l5_e8``: a causal decoder whose
layers are multi-head latent attention then a dense MLP (the first) or
routed experts beside two shared ones (the rest), with DeepSeek-V2's
sequence-wise balance loss (DeepSeek-V2-Lite, from the keys of its
published config and arXiv:2405.04434 §2.1-2.2; its modeling code is not
here). Of the router's ``router_num_experts`` experts this chip holds
``n_routed_experts``. Nothing here imports the program.

With tokens ``x [S]`` of one sequence, no bias anywhere: ``h = E[x]``.
Every layer ``i``: ``a = rms(h; g_op_i)``; ``q = a Wq`` (``heads`` of
``qk_nope_head_dim + qk_rope_head_dim``, the rope part last); ``[c, k_pe]
= a Wkva`` (``kv_lora_rank`` + ``qk_rope_head_dim``); ``c = rms(c;
g_kv)``; ``[k_nope, v] = c Wkvb`` (``qk_nope_head_dim + v_head_dim`` a
head); rotary on ``q``'s rope part and on ``k_pe`` (one for all heads);
``q = [q_nope, q_pe]``, ``k = [k_nope, k_pe]``; scores ``q k^T x
qk_head_dim^-0.5 x mscale(factor, mscale_all_dim)^2`` under ``col <=
row``; softmax in float32; ``o = concat(heads v) Wo``; ``h = h + o``. ``m
= rms(h; g_ffn_i)``; for ``i < first_k_dense_replace`` ``f = (silu(m W1)
* (m W3)) W2``, else ``p = softmax(m Wr)`` over ALL the router's logits;
``sel`` = the ``num_experts_per_tok`` experts with the largest ``p``;
``w_e = p_e`` (``norm_topk_prob`` false) times ``routed_scaling_factor``;
``f = sum_{e in sel, e held here} w_e (silu(m W1_e) * (m W3_e)) W2_e +
(silu(m S1) * (m S3)) S2``, the last the shared experts, one MLP of
``n_shared_experts x moe_intermediate_size``: what the absent experts
would add is left out, as in the program; ``h = h + f``. The layer's
balance term: ``sum_i f_i P_i`` with ``f_i = E_all / (k S)`` times the
sequence's slots on expert ``i`` (a count, no gradient) and ``P_i`` its
mean score of expert ``i``. ``logits = rms(h; g_f) W_head``, ``W_head [D,
V]`` a parameter of its own; the loss the mean next-token cross-entropy
over the ``vocab_size`` rows held here, plus ``aux_loss_alpha`` times the
mean over the batch's sequences of their balance terms summed over the
layers. No state.

The rotary rule (YaRN as DeepSeek's code computes it, on the rope slice
of ``Dr = qk_rope_head_dim``): ``dim(n) = Dr ln(original / (2 pi n)) / (2
ln theta)``, ``low = floor(dim(beta_fast))``, ``high =
ceil(dim(beta_slow))`` (clipped to ``0 .. Dr - 1``), ``ramp_j = clip((j -
low) / (high - low), 0, 1)``, ``inv_freq_j = (1 - ramp_j) theta ** (-2 j
/ Dr) + ramp_j theta ** (-2 j / Dr) / factor``, cos and sin times
``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``, with
``mscale(f, m) = 0.1 m ln f + 1``; rotate-half over the slice (the file's
``assumed.rope_pairing``), positions ``0..S-1``.

Every product goes through ``nm.dense`` / ``nm.einsum`` but the router's,
which the configuration states in float32 at the highest precision (the
choice of experts hangs on it); norms, both softmaxes, rotary, the balance
term and the loss are float32. Straight ``jax.numpy``: attention on the
full ``[S, S]`` scores, the experts a loop over those held, each a dense
product over ALL tokens masked by the choice (no sort, no grouped product,
no kernel). Departures from the shortest way to write it, each for memory
at the published widths on one chip and none for arithmetic: the batch
goes one sequence after the other (``lax.map``), attention head by head,
the dense MLP and the shared experts ``sequence_length /
reference_loss_blocks`` tokens at a time, the loss as many tokens at a
time, and each sequence, layer, head, expert and block is a
``jax.checkpoint``, one inside the other.

Faults beside the harness's two: ``no_shared`` (the shared experts left
out), ``no_mscale`` (the softmax scale ``qk_head_dim^-0.5`` alone) and
``wrong_experts`` (the weights held answer to the ids after those the
configuration states).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.lib import reference

TEST_RECORDS = 512          # the program's default size of its test split
FAULTS = reference.FAULTS + ("no_shared", "no_mscale", "wrong_experts")


def _sizes(spec: dict):
    """``(heads, nope, rope, v, rank)``."""
    return (spec["num_attention_heads"], spec["qk_nope_head_dim"],
            spec["qk_rope_head_dim"], spec["v_head_dim"],
            spec["kv_lora_rank"])


def param_shapes(spec: dict):
    """The tree the program holds (``models/hybrid_decoder.py``): compared
    with its ``init`` by ``jax.eval_shape`` in the tests."""
    v, d, hm = spec["vocab_size"], spec["hidden_size"], \
        spec["moe_intermediate_size"]
    heads, nope, rope, vd, rank = _sizes(spec)
    e = spec["n_routed_experts"]
    e_all = spec.get("router_num_experts", e)
    hs = spec["n_shared_experts"] * hm

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    def layer(i):
        out = {"op_norm": {"scale": f32(d)}, "ffn_norm": {"scale": f32(d)},
               "mla": {"wq": f32(d, heads * (nope + rope)),
                       "wkv_a": f32(d, rank + rope),
                       "kv_norm": {"scale": f32(rank)},
                       "wkv_b": f32(rank, heads * (nope + vd)),
                       "wo": f32(heads * vd, d)}}
        if i < spec["first_k_dense_replace"]:
            f = spec["intermediate_size"]
            out["mlp"] = {"w1": f32(d, f), "w3": f32(d, f), "w2": f32(f, d)}
        else:
            out["moe"] = {"router": f32(d, e_all), "w1": f32(e, d, hm),
                          "w3": f32(e, d, hm), "w2": f32(e, hm, d),
                          "shared": {"w1": f32(d, hs), "w3": f32(d, hs),
                                     "w2": f32(hs, d)}}
        return out

    return {"embed": f32(v, d),
            "layers": [layer(i) for i in range(spec["num_hidden_layers"])],
            "final_norm": {"scale": f32(d)}, "head": f32(d, v)}


def fan_in(path: str, shape):
    if path == "['embed']":
        # rows looked up, of variance 1 (the benchmark draws variance 1 /
        # (2 fan-in)): the head is a matrix of its own, so the embedding
        # need not serve as one, and rows as small as a product's weights
        # would leave the residual stream of the first layers to
        # attention's output, nearly the same vector for every token of a
        # prefix: the router then scores every token alike and a seed's
        # weights decide which experts fill (the configuration's file,
        # ``assumed.weights``)
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
    each). The routed experts under uniform routing: of a token's
    ``num_experts_per_tok`` slots the share ``n_routed_experts /
    router_num_experts`` falls on an expert held here (0.75 of a slot a
    layer at 6 of 64 with 8 held); the shared experts on every token.
    Attention is the half square of pairs in every head, two products a
    pair forward (the score at the query/key width, the value at the value
    width) and five backward (the score, ``dQ`` and ``dK`` at the
    query/key width, ``dP`` and ``dV`` at the value width). The head once.
    Not counted: the embedding's gather, norms, softmax, rotary, the
    balance term, and anything computed a second time in the backward
    pass."""
    s, d = spec["sequence_length"], spec["hidden_size"]
    heads, nope, rope, vd, rank = _sizes(spec)
    qk, hm = nope + rope, spec["moe_intermediate_size"]
    layers, dense = spec["num_hidden_layers"], spec["first_k_dense_replace"]
    e_all = spec.get("router_num_experts", spec["n_routed_experts"])
    held_slots = spec["num_experts_per_tok"] * spec["n_routed_experts"]
    mla = d * heads * qk + d * (rank + rope) + rank * heads * (nope + vd) \
        + heads * vd * d
    per_token = layers * mla + dense * 3 * d * spec["intermediate_size"] \
        + (layers - dense) * (d * e_all
                              + (held_slots / e_all
                                 + spec["n_shared_experts"]) * 3 * d * hm) \
        + d * spec["vocab_size"]
    pairs = s * (s + 1) // 2
    attention = layers * 2 * heads * pairs * ((qk + vd) + (3 * qk + 2 * vd))
    return 6 * s * per_token + attention


# --- the model ---------------------------------------------------------------

def rms_norm(x, p, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * p["scale"]


def mscale(factor: float, m: float) -> float:
    return 0.1 * m * float(np.log(factor)) + 1.0 if factor > 1 else 1.0


def yarn_range(rule: dict, dr: int):
    """``(low, high)``: the pairs between which YaRN's ramp runs."""
    theta, original = rule["rope_theta"], \
        rule["original_max_position_embeddings"]

    def dim(turns):
        return dr * np.log(original / (2 * np.pi * turns)) \
            / (2 * np.log(theta))

    return max(int(np.floor(dim(rule["beta_fast"]))), 0), \
        min(int(np.ceil(dim(rule["beta_slow"]))), dr - 1)


def inv_frequencies(rule: dict, dr: int):
    """``(inv_freq [dr / 2] float64, what cos and sin are multiplied by)``
    of the YaRN rule on a rope slice of ``dr`` (module docstring)."""
    j = np.arange(dr // 2, dtype=np.float64)
    plain = rule["rope_theta"] ** (-2 * j / dr)
    low, high = yarn_range(rule, dr)
    ramp = np.clip((j - low) / max(high - low, 0.001), 0.0, 1.0)
    factor = mscale(rule["factor"], rule["mscale"]) \
        / mscale(rule["factor"], rule["mscale_all_dim"])
    return (1 - ramp) * plain + ramp * plain / rule["factor"], float(factor)


def softmax_scale(spec: dict, fault=None) -> float:
    """``qk_head_dim^-0.5 x mscale(factor, mscale_all_dim)^2``."""
    qk = spec["qk_nope_head_dim"] + spec["qk_rope_head_dim"]
    rule = spec["rope_scaling"]
    if fault == "no_mscale":
        return qk ** -0.5
    return qk ** -0.5 * mscale(rule["factor"], rule["mscale_all_dim"]) ** 2


def rotary(x, rule: dict):
    """``x [S, H, Dr]``: each pair ``(x[i], x[i + Dr/2])`` turned by
    ``position * inv_freq_i``, cos and sin times the rule's factor."""
    s, _, dr = x.shape
    inv_freq, factor = inv_frequencies(rule, dr)
    angle = jnp.asarray(np.arange(s)[:, None] * inv_freq[None, :],
                        jnp.float32)
    cos = factor * jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None, :]
    sin = factor * jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None, :]
    half = jnp.concatenate([-x[..., dr // 2:], x[..., :dr // 2]], -1)
    return x * cos + half * sin


def make_layers(spec: dict, fault=None) -> dict:
    """The model's pieces by name, each on one sequence: ``attention(nm,
    a, p)``, ``experts(nm, m, p) -> (f, balance term)``, ``layer(nm, h, p,
    i) -> (h, balance term)`` and ``last_state(nm, params, tokens) ->
    (state under the head, the layers' balance terms summed)``."""
    heads, nope, rope, vd, rank = _sizes(spec)
    eps, top_k = spec["rms_norm_eps"], spec["num_experts_per_tok"]
    rule = {**spec["rope_scaling"], "rope_theta": spec["rope_theta"]}
    scale = softmax_scale(spec, fault)
    e_all = spec.get("router_num_experts", spec["n_routed_experts"])
    first = spec.get("expert_first_id", 0)
    if fault == "wrong_experts":
        first += spec["n_routed_experts"]
    blocks = spec.get("reference_loss_blocks", 1)

    def attention(nm, a, p):
        s = a.shape[0]
        q = nm.dense(a, p["wq"]).reshape(s, heads, nope + rope)
        down = nm.dense(a, p["wkv_a"])
        c = rms_norm(down[:, :rank], p["kv_norm"], eps)
        kv = nm.dense(c, p["wkv_b"]).reshape(s, heads, nope + vd)
        q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], rule)], -1)
        k_pe = rotary(down[:, None, rank:], rule)     # one for all heads
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (s, heads, rope))], -1)
        v = kv[..., nope:]
        rows = s // blocks

        @jax.checkpoint
        def query_block(qb, row0, kh, vh):
            scores = nm.einsum("qd,kd->qk", qb, kh) * scale
            seen = jnp.arange(s)[None, :] <= row0 + jnp.arange(rows)[:, None]
            prob = jax.nn.softmax(jnp.where(seen, scores, -1e30), -1)
            return nm.einsum("qk,kd->qd", prob, vh)

        @jax.checkpoint
        def one_head(qkv):
            qh, kh, vh = qkv
            return lax.map(
                lambda b: query_block(*b, kh, vh),
                (qh.reshape(blocks, rows, -1),
                 jnp.arange(blocks) * rows)).reshape(s, vd)

        out = lax.map(one_head, tuple(t.transpose(1, 0, 2)
                                      for t in (q, k, v)))
        return nm.dense(out.transpose(1, 0, 2).reshape(s, heads * vd),
                        p["wo"])

    def gated_mlp(nm, m, w1, w3, w2):
        return nm.dense(jax.nn.silu(nm.dense(m, w1)) * nm.dense(m, w3), w2)

    def in_blocks(nm, m, w1, w3, w2):
        """The gated MLP on every token, a block of tokens at a time."""
        one = jax.checkpoint(lambda x: gated_mlp(nm, x, w1, w3, w2))
        return lax.map(one, m.reshape(blocks, -1, m.shape[-1])).reshape(
            m.shape)

    def experts(nm, m, p):
        """What the experts held here and the shared experts add to each
        token of ``m [S, D]``, and the sequence's balance term."""
        prob = jax.nn.softmax(jnp.dot(m, p["router"],
                                      precision=lax.Precision.HIGHEST), -1)
        _, chosen = lax.top_k(lax.stop_gradient(prob), top_k)
        weight = jnp.take_along_axis(prob, chosen, axis=-1)
        if spec["norm_topk_prob"]:
            weight = weight / jnp.sum(weight, -1, keepdims=True)
        weight = weight * spec["routed_scaling_factor"]

        @jax.checkpoint
        def one_expert(m, w1, w3, w2, mine):
            return mine[:, None] * gated_mlp(nm, m, w1, w3, w2)

        out = jnp.zeros_like(m)
        for e in range(spec["n_routed_experts"]):
            mine = jnp.sum(jnp.where(chosen == first + e, weight, 0.0), -1)
            out = out + one_expert(m, p["w1"][e], p["w3"][e], p["w2"][e],
                                   mine)
        if fault != "no_shared":
            sh = p["shared"]
            out = out + in_blocks(nm, m, sh["w1"], sh["w3"], sh["w2"])
        s = m.shape[0]
        slots = jnp.sum(chosen.reshape(-1)[:, None] == jnp.arange(e_all),
                        0).astype(jnp.float32)
        balance = jnp.sum(slots * e_all / (top_k * s) * jnp.mean(prob, 0))
        return out, balance

    def layer(nm, h, p, i):
        h = h + attention(nm, rms_norm(h, p["op_norm"], eps), p["mla"])
        m = rms_norm(h, p["ffn_norm"], eps)
        if "mlp" in p:
            w = p["mlp"]
            return h + in_blocks(nm, m, w["w1"], w["w3"], w["w2"]), \
                jnp.float32(0.0)
        f, balance = experts(nm, m, p["moe"])
        return h + f, balance

    def last_state(nm, params, tokens):
        """``tokens [S]`` -> the normed state under the head ``[S, D]``
        and the sequence's balance terms, summed over the layers."""
        h = params["embed"][tokens]
        total = jnp.float32(0.0)
        for i, p in enumerate(params["layers"]):
            h, balance = jax.checkpoint(
                lambda h, p, i=i: layer(nm, h, p, i))(h, p)
            total = total + balance
        return rms_norm(h, params["final_norm"], eps), total

    return {"attention": attention, "experts": experts, "layer": layer,
            "last_state": last_state}


def make_loss(spec: dict, fault=None):
    last_state = make_layers(spec, fault)["last_state"]
    blocks = spec.get("reference_loss_blocks", 1)
    alpha = spec["aux_loss_alpha"] if spec["seq_aux"] else 0.0

    def sequence_loss(nm, params, tokens, targets):
        """One sequence -> its tokens' cross-entropies ``[S]`` and its
        balance terms."""
        @jax.checkpoint
        def block_ce(h, y, head):
            logp = jax.nn.log_softmax(nm.dense(h, head), -1)
            return -jnp.take_along_axis(logp, y[:, None], -1)[:, 0]

        h, balance = last_state(nm, params, tokens)
        ce = lax.map(lambda hy: block_ce(*hy, params["head"]),
                     (h.reshape(blocks, -1, h.shape[-1]),
                      targets.reshape(blocks, -1)))
        return ce.reshape(-1), balance

    def loss(nm, params, model_state, batch):
        inputs, targets = batch
        one = jax.checkpoint(
            lambda p, tokens, y: sequence_loss(nm, p, tokens, y))
        # one sequence after the other (``lax.map``, not a Python loop: the
        # compiler otherwise runs the backward passes side by side)
        per_token, balance = lax.map(lambda ty: one(params, *ty),
                                     (inputs, targets))
        # the harness's faults, in tokens: what is left out is part of each
        # sequence
        if fault == "half_batch":
            per_token = per_token[:, :per_token.shape[1] // 2]
        elif fault == "no_exchange":
            per_token = per_token[:, :per_token.shape[1] // 4]
        return jnp.mean(per_token) + alpha * jnp.mean(balance), model_state

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

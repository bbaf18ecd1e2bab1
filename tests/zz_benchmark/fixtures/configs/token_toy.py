"""Plain reference of a fixture that is no image classifier: a two-layer
causal decoder over tokens, a few dozen wide. It is in no
``BENCHMARK.json``; the tests of ``tests/zz_benchmark`` run it through the
harness to show that nothing there reads an image's keys.

Layer 1: attention and a gated dense MLP. Layer 2: attention and four
gated experts, one a token by the router's largest softmax score, the
expert's output weighted by that score. RMS norms before each half, an
untied head. Attention's two products and the experts' three go through
``nm.einsum``, every other product through ``nm.dense``.

It states a task (``benchmark/lib/reference.py``, :class:`Task`):
int32 token records ``[n, S+1]``; a feed of inputs ``[:, :-1]`` and
next-token targets ``[:, 1:]``; the mean cross-entropy over tokens plus
``router_z_coef`` times the mean over tokens of the squared log-sum-exp of
the router's logits; AdamW with decoupled decay, its moments under the
names the program's optimizer gives them (``mu``, ``nu``). And a fan-in
for its ``[V, D]`` embedding (D) and its expert-major ``[E, D, H]`` leaves
(the middle dimension)."""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import reference

TEST_RECORDS = 16


def param_shapes(spec: dict):
    """The tree a program of this model would hold (the fixture has no
    program to take it from)."""
    v, d = spec["vocab_size"], spec["hidden_size"]
    a = spec["num_attention_heads"] * spec["head_dim"]
    e, h = spec["num_experts"], spec["moe_intermediate_size"]

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    def attention():
        return {"attn_norm": {"scale": f32(d)}, "wq": f32(d, a),
                "wk": f32(d, a), "wv": f32(d, a), "wo": f32(a, d),
                "mlp_norm": {"scale": f32(d)}}

    f = spec["intermediate_size"]
    return {"embed": f32(v, d),
            "layers": [
                {**attention(), "gate": f32(d, f), "up": f32(d, f),
                 "down": f32(f, d)},
                {**attention(), "router": f32(d, e),
                 "experts": {"gate": f32(e, d, h), "up": f32(e, d, h),
                             "down": f32(e, h, d)}}],
            "final_norm": {"scale": f32(d)},
            "head": f32(d, v)}


def fan_in(path: str, shape):
    if path == "['embed']":
        return shape[-1]          # rows are looked up, not summed over
    if len(shape) == 3:
        return shape[1]           # expert-major: each expert is [in, out]
    return None


def init_model_state(params):
    del params
    return {}


def param_count(spec: dict) -> int:
    return sum(int(np.prod(x.shape))
               for x in jax.tree.leaves(param_shapes(spec)))


def train_flops_per_image(spec: dict) -> int:
    """Per example: one sequence of ``sequence_length`` tokens."""
    s, d = spec["sequence_length"], spec["hidden_size"]
    a = spec["num_attention_heads"] * spec["head_dim"]
    per_token = 2 * 4 * d * a + 3 * d * spec["intermediate_size"] \
        + d * spec["num_experts"] + spec["num_experts_per_tok"] * 3 * d \
        * spec["moe_intermediate_size"] + d * spec["vocab_size"]
    attention = 2 * 2 * s * s * a           # scores and values, two layers
    return 3 * 2 * (s * per_token + attention)


def make_forward(spec: dict):
    heads, dh = spec["num_attention_heads"], spec["head_dim"]
    eps = spec["rms_norm_eps"]

    def norm(x, p):
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                                 + eps) * p["scale"]

    def attention(nm, x, p):
        b, s, _ = x.shape
        h = norm(x, p["attn_norm"])
        q, k, v = (nm.dense(h, p[w]).reshape(b, s, heads, dh)
                   for w in ("wq", "wk", "wv"))
        scores = nm.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dh)
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -1e30)
        out = nm.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        return x + nm.dense(out.reshape(b, s, heads * dh), p["wo"])

    def forward(nm, params, tokens):
        """``tokens [B, S]`` -> logits ``[B, S, V]`` and the routers'
        logits ``[B, S, E]``, one entry an expert layer."""
        x = params["embed"][tokens]
        routed = []
        for p in params["layers"]:
            x = attention(nm, x, p)
            h = norm(x, p["mlp_norm"])
            if "router" in p:
                r = nm.dense(h, p["router"])
                routed.append(r)
                score = jax.nn.softmax(r, -1)
                first = jax.nn.one_hot(jnp.argmax(r, -1), r.shape[-1])
                e = p["experts"]
                inner = jax.nn.silu(nm.einsum("bsd,edh->bseh", h, e["gate"])) \
                    * nm.einsum("bsd,edh->bseh", h, e["up"])
                y = nm.einsum("bseh,ehd->bsed", inner, e["down"])
                x = x + jnp.sum(y * (score * first)[..., None], axis=2)
            else:
                x = x + nm.dense(jax.nn.silu(nm.dense(h, p["gate"]))
                                 * nm.dense(h, p["up"]), p["down"])
        x = norm(x, params["final_norm"])
        return nm.dense(x, params["head"]), routed

    return forward


def make_records(seed: int, n: int, vocab: int, length: int) -> np.ndarray:
    """``[n, length + 1]`` int32: each row counts on from its own start by
    its own stride, one token in ten replaced by noise, so that a model
    has something to learn and every row differs."""
    rng = np.random.default_rng([seed, n, vocab, length])
    start = rng.integers(0, vocab, size=(n, 1))
    stride = rng.integers(1, 8, size=(n, 1))
    tokens = (start + stride * np.arange(length + 1)[None, :]) % vocab
    noise = rng.integers(0, vocab, size=tokens.shape)
    return np.where(rng.random(tokens.shape) < 0.1, noise,
                    tokens).astype(np.int32)


def task(spec: dict, flags: dict, fault=None) -> reference.Task:
    forward = make_forward(spec)
    vocab, length = spec["vocab_size"], spec["sequence_length"]
    b1, b2, eps = (spec["adam"][k] for k in ("b1", "b2", "eps"))
    lr, decay = flags["learning_rate"], flags["weight_decay"]
    if fault is not None and fault not in reference.FAULTS:
        raise ValueError(f"unknown fault {fault!r}")

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

    def loss(nm, params, model_state, batch):
        inputs, targets = batch
        if fault == "no_exchange":
            seen = inputs.shape[0] // 4
            inputs, targets = inputs[:seen], targets[:seen]
        logits, routed = forward(nm, params, inputs)
        if fault == "half_batch":
            keep = inputs.shape[0] // 2
            logits, targets = logits[:keep], targets[:keep]
            routed = [r[:keep] for r in routed]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        nll = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))
        z = sum(jnp.mean(jnp.square(jax.nn.logsumexp(r, -1)))
                for r in routed)
        return nll + spec["router_z_coef"] * z, model_state

    def init_opt(params):
        return {"mu": jax.tree.map(jnp.zeros_like, params),
                "nu": jax.tree.map(jnp.zeros_like, params)}

    def update(params, opt, grads, step):
        t = jnp.asarray(step + 1).astype(jnp.float32)
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt["mu"],
                          grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * jnp.square(g),
                          opt["nu"], grads)
        params = jax.tree.map(
            lambda p, m, v: p - lr * ((m / (1 - b1 ** t))
                                      / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
                                      + decay * p),
            params, mu, nu)
        return params, {"mu": mu, "nu": nu}

    return reference.Task(
        write_records, feed, loss, init_opt, update,
        fault=lambda name: task(spec, flags, name),
        grad_blocks=spec.get("reference_grad_blocks", 1))

"""A causal decoder over tokens whose layers differ by a list: an operator
of one of four kinds (a gated short convolution, rotary attention with
grouped key/value heads over the whole sequence, the same over a sliding
window, or multi-head latent attention), then a feed-forward of one of two
kinds (a dense gated SiLU MLP in the leading layers, routed experts after
them, with or without shared experts), and a head that is the embedding's
transpose or a matrix of its own.

With tokens ``x [B, S]``: ``h = E[x]``. Every layer ``i``: ``a = rms(h;
g_op_i)``; by ``layer_types[i]``

- ``conv``: ``[Bg, Cg, X] = split3(a W_in)``, ``u = Bg * X``, ``c[t] =
  sum_j w[:, j] u[t - (L - 1) + j]`` (depthwise, causal), ``o = (Cg * c)
  W_out`` (``ops.layers.gated_short_conv`` between the two products);
- ``full_attention``: ``ops.attention.causal_self_attention``, the
  sublayer the looped decoder runs too: ``q = a Wq``, ``k = a Wk``, ``v =
  a Wv``, an RMS norm on each head's query and key where ``qk_norm``,
  rotary by the rule of the layer's kind, query head ``j`` on key/value
  head ``j // (heads / kv_heads)``, scores ``q k^T / sqrt(head_dim)``
  under ``col <= row``, softmax in float32, ``o = concat(heads) Wo``;
- ``sliding_attention``: the same with the mask also ``col > row -
  sliding_window`` (a token sees itself and the ``sliding_window - 1``
  before it) and the rotary rule of its own kind;
- ``latent_attention``: ``ops.attention.latent_attention`` (DeepSeek-V2's
  MLA without a query latent): keys and values from one RMS-normed latent
  of ``kv_lora_rank``, queries and keys of ``qk_nope_head_dim +
  qk_rope_head_dim`` over values of ``v_head_dim``, rotary on the
  ``qk_rope_head_dim`` slice alone with one rotary key for all heads, the
  softmax scale times YaRN's ``mscale_all_dim`` factor squared;

``h = h + o``. The rotary rule is ``rope_theta`` for every layer, or
``rope_parameters``, a rule a kind of layer (``rope_type`` ``default``:
``inv_freq_j = theta ** (-2 j / head_dim)``; ``yarn``: the slow pairs'
frequencies divided by ``factor`` over a ramp and cos and sin times
``attention_factor``: ``ops.layers.rope_frequencies``). A file in
DeepSeek's shape names ``rope_scaling`` beside ``rope_theta`` instead: that
rule, for every attention layer.

``m = rms(h; g_ffn_i)``; for ``i < num_dense_layers`` ``f = (silu(m W1) *
(m W3)) W2``, else the experts' layer: ``s = sigmoid(m Wr)`` or, with
``router_score`` ``softmax``, ``s = softmax(m Wr)`` over ALL
``router_num_experts`` experts, a token takes the ``num_experts_per_tok``
with the largest ``s + b`` and weighs them by their ``s`` over its sum
(``norm_topk_prob``), and the ``num_experts`` experts held here (ids
``expert_first_id ..``) add their part (``ops.moe.routed_experts``: no
capacity, nothing dropped, nothing stands in for the experts that live
elsewhere); with ``n_shared_experts`` every token also takes one gated
SiLU MLP of ``n_shared_experts x moe_intermediate_size`` (the shared
experts, counted once: every chip computes them for its own tokens);
``h = h + f``. The bias ``b`` (``use_expert_bias``; without
it the choice is by ``s`` alone and the model has no state) is a
buffer of the model's state, zero at the start, that no gradient reaches:
every training step moves each expert's by ``expert_bias_update_rate``
toward an even load over all of the router's experts
(``ops.moe.balanced_bias``). ``logits = rms(h; g_f) W_head`` with ``W_head
= E^T`` where ``tie_word_embeddings``, else a parameter ``head [D, V]``
of its own; the loss is the mean next-token cross-entropy, over the
``vocab_size`` rows held here, and, where the file names an
``aux_loss_alpha`` (with ``seq_aux``), in training each experts' layer's
sequence-wise balance loss (``ops.moe.sequence_balance_loss``), summed
over the layers as DeepSeek-V2 adds them.

Sizes come from a JSON file in the shape of a published ``config.json``
(``--model_config_file``, the keys of :data:`SMALL` and :data:`OPTIONAL`,
some under the published names of :data:`ALIASES`); without one,
:data:`SMALL`. The model states its own loss
(``ModelDef.loss``): a batch is ``[B, S+1]`` int32 rows of a token
dataset, and no ``[tokens, vocabulary]`` array is ever held.

Numerics: parameters and the residual stream float32; every product (the
grouped ones too) of operands rounded to ``compute_dtype`` and summed in
float32; the router's product, score, choice and weights, the norms,
rotary, softmax, the short convolution's taps and gates and the loss
float32.

Memory: the sublayers that treat each sequence alone (the operators, the
dense MLP) take :data:`OP_CHUNK_TOKENS` tokens at a time, one group of
sequences after the other; the experts take all of a step's tokens, their
rows a block at a time, and hold the blocks' float32 results (an even
load's worth, :func:`expert_block_rows`) until each token has summed its
own. With ``remat`` the backward pass recomputes an
operator but its flash kernel (:data:`KEPT`), a group's dense MLP, and a
block of the experts' rows; what is kept is each sublayer's input.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dml_cnn_cifar10_tpu.config import DataConfig, ModelConfig
from dml_cnn_cifar10_tpu.models import looped_decoder
from dml_cnn_cifar10_tpu.ops import attention as attention_lib
from dml_cnn_cifar10_tpu.ops import flash_attention as flash_lib
from dml_cnn_cifar10_tpu.ops import kernel_paths
from dml_cnn_cifar10_tpu.ops import moe as moe_lib
from dml_cnn_cifar10_tpu.ops.layers import (gated_short_conv, mixed_matmul,
                                            rms_norm)
from dml_cnn_cifar10_tpu.train import loss as loss_lib

#: The sizes of a run that names no file: what the tests and the chip's
#: smoke run use. Half of the router's experts are held.
SMALL: Dict[str, Any] = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 3,
    "layer_types": ["conv", "full_attention", "conv"],
    "num_dense_layers": 1, "num_experts": 4, "router_num_experts": 8,
    "expert_first_id": 0, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "expert_bias_update_rate": 0.001, "vocab_size": 96,
    "norm_eps": 1e-5, "rope_theta": 1000000, "conv_L_cache": 3,
    "conv_bias": False}

#: Keys a file may leave out, with what their absence means: no window
#: layer, one rotary rule (``rope_theta``) for every layer, a head tied to
#: the embedding, sigmoid scores, a norm on each head's query and key, no
#: latent attention's sizes, no shared expert, no balance loss.
OPTIONAL: Dict[str, Any] = {
    "sliding_window": None, "rope_parameters": None,
    "tie_word_embeddings": True, "router_score": "sigmoid", "qk_norm": True,
    "kv_lora_rank": None, "qk_nope_head_dim": None,
    "qk_rope_head_dim": None, "v_head_dim": None, "n_shared_experts": None,
    "aux_loss_alpha": 0.0, "seq_aux": False}

#: Published keys that name one of :data:`SMALL`'s, as DeepSeek's config
#: does.
ALIASES = {"rms_norm_eps": "norm_eps", "n_routed_experts": "num_experts",
           "first_k_dense_replace": "num_dense_layers",
           "scoring_func": "router_score"}

#: Published keys of which one value is built, and that value: a query
#: latent, experts in some layers only, routing limited to groups of
#: experts, other than the greedy top-k are not.
ONLY: Dict[str, Any] = {"q_lora_rank": None, "moe_layer_freq": 1,
                        "n_group": 1, "topk_group": 1,
                        "topk_method": "greedy"}

#: The operator kinds of ``layer_types``; each kind of attention is told
#: apart by its scope (``attn`` / ``attn_window`` / ``mla``).
KINDS = ("conv", "full_attention", "sliding_attention", "latent_attention")
#: The sizes a ``latent_attention`` layer reads.
LATENT = ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
          "v_head_dim")

KEPT = looped_decoder.KEPT

#: Tokens that a sublayer which treats each sequence alone takes at once,
#: at most (whole sequences: one where a sequence is longer).
OP_CHUNK_TOKENS = 8192
#: Tokens of the dense MLP, which treats each token alone, at once.
MLP_CHUNK_TOKENS = 4096
#: Rows of the experts' buffer: the load expected under uniform routing
#: (what the experts' bias steers toward) with a sixteenth of slack, in as
#: many blocks of about this many rows as that takes, each a multiple of
#: :data:`ROW_TILE`. Further blocks, up to the worst case, are visited
#: only when routing is that skewed, and fill the buffer again (a round
#: more of ``ops.moe.routed_experts``).
EXPERT_BLOCK_ROWS = 8192
ROW_TILE = 512


def _unused(sz: Dict[str, Any]) -> set:
    """Keys of :data:`SMALL` that these sizes never read: a file may leave
    them out."""
    out = set()
    if "conv" not in sz.get("layer_types", ("conv",)):
        out |= {"conv_L_cache", "conv_bias"}
    if sz.get("rope_parameters") is not None:
        out.add("rope_theta")
    if not sz.get("use_expert_bias", True):
        out.add("expert_bias_update_rate")
    return out


@functools.lru_cache(maxsize=None)
def _read_sizes(path: str) -> Dict[str, Any]:
    spec = looped_decoder.read_config_file(path)
    for published, key in ALIASES.items():
        if key not in spec and published in spec:
            spec[key] = spec[published]
    for key, built in ONLY.items():
        if spec.get(key, built) != built:
            raise NotImplementedError(
                f"{path}: {key} {spec[key]!r} is not built (only "
                f"{built!r})")
    if spec.get("rope_scaling") and spec.get("rope_parameters") is None:
        # DeepSeek's one rule for every attention layer, `type` its kind
        rule = dict(spec["rope_scaling"])
        rule = {"rope_type": rule.pop("type", "default"), **rule,
                "rope_theta": spec["rope_theta"]}
        spec["rope_parameters"] = {kind: rule for kind in
                                   set(spec.get("layer_types", ())) - {"conv"}}
    sz = {**OPTIONAL,
          **{k: spec[k] for k in (*SMALL, *OPTIONAL) if k in spec}}
    missing = sorted(set(SMALL) - set(sz) - _unused(sz))
    if missing:
        raise ValueError(f"{path} lacks {missing}")
    sz["head_dim"] = spec.get("head_dim") or \
        sz["hidden_size"] // sz["num_attention_heads"]
    return _checked(sz, path)


def _checked(sz: Dict[str, Any], where: str) -> Dict[str, Any]:
    kinds = set(sz["layer_types"])
    if len(sz["layer_types"]) != sz["num_hidden_layers"] \
            or not kinds <= set(KINDS):
        raise ValueError(f"{where}: layer_types has to name one of "
                         f"{', '.join(KINDS)} for each of "
                         f"num_hidden_layers")
    if sz.get("conv_bias"):
        raise NotImplementedError(f"{where}: conv_bias is not built")
    window = sz["sliding_window"]
    if "sliding_attention" in kinds and not (window and window >= 1):
        raise ValueError(f"{where}: a sliding_attention layer needs a "
                         f"sliding_window of at least 1")
    rules = sz["rope_parameters"]
    for kind in sorted(kinds - {"conv"}) if rules is not None else ():
        if kind not in rules:
            raise ValueError(f"{where}: rope_parameters lacks a rule for "
                             f"{kind}, which layer_types uses")
        if rules[kind].get("rope_type", "default") not in ("default",
                                                           "yarn"):
            raise ValueError(f"{where}: rope_type "
                             f"{rules[kind]['rope_type']!r} of {kind} is "
                             f"not default or yarn")
    if sz["router_score"] not in ("sigmoid", "softmax"):
        raise ValueError(f"{where}: router_score {sz['router_score']!r} is "
                         f"not sigmoid or softmax")
    last = sz["expert_first_id"] + sz["num_experts"]
    if not 0 <= sz["expert_first_id"] < last <= sz["router_num_experts"]:
        raise ValueError(f"{where}: experts {sz['expert_first_id']}..{last} "
                         f"are not among the router's "
                         f"{sz['router_num_experts']}")
    if sz["num_attention_heads"] % sz["num_key_value_heads"]:
        raise ValueError(f"{where}: the key/value heads do not divide the "
                         f"query heads")
    if "latent_attention" in kinds and not all(
            isinstance(sz[k], int) and sz[k] >= 1 for k in LATENT):
        raise ValueError(f"{where}: a latent_attention layer needs "
                         f"{', '.join(LATENT)}")
    if sz["aux_loss_alpha"] and not sz["seq_aux"]:
        raise NotImplementedError(f"{where}: only the sequence-wise balance "
                                  f"loss (seq_aux) is built")
    return sz


def sizes(cfg: ModelConfig) -> Dict[str, Any]:
    """The model's sizes: the file ``cfg.config_file`` names, else
    :data:`SMALL`; a key of :data:`OPTIONAL` the file lacks has its value
    there, and ``head_dim`` where the file has none is ``hidden_size /
    num_attention_heads``."""
    if not cfg.config_file:
        return _checked({**OPTIONAL, **SMALL, "head_dim": 16}, "SMALL")
    return _read_sizes(looped_decoder.config_path(cfg.config_file))


def rope_rule(sz: Dict[str, Any], kind: str):
    """The rotary rule of a layer of ``kind``: its entry of
    ``rope_parameters``, else the one ``rope_theta``."""
    rules = sz["rope_parameters"]
    return sz["rope_theta"] if rules is None else rules[kind]


def init_params(key: jax.Array, cfg: ModelConfig, data_cfg: DataConfig):
    """Normal weights of variance 1 / fan-in (the embedding: 1 / hidden;
    the filter: 1 / taps), norm scales 1."""
    del data_cfg
    sz = sizes(cfg)
    d, f, hm = sz["hidden_size"], sz["intermediate_size"], \
        sz["moe_intermediate_size"]
    dh, heads = sz["head_dim"], sz["num_attention_heads"]
    a, kv = heads * dh, sz["num_key_value_heads"] * dh
    e, e_all = sz["num_experts"], sz["router_num_experts"]
    hs = (sz["n_shared_experts"] or 0) * hm
    dtype = jnp.dtype(cfg.dtype)
    # nine draws a layer were enough until latent attention and shared
    # experts; the files without them draw as they did
    per_layer = 12 if hs or "latent_attention" in sz["layer_types"] else 9
    keys = iter(jax.random.split(key, 2 + per_layer
                                 * sz["num_hidden_layers"]))

    def matrix(fan_in, *shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)

    def scale(n=d):
        return {"scale": jnp.ones((n,), dtype)}

    def layer(i, kind):
        p = {"op_norm": scale(), "ffn_norm": scale()}
        if kind == "conv":
            p["conv"] = {"w_in": matrix(d, d, 3 * d),
                         "w": matrix(sz["conv_L_cache"], d,
                                     sz["conv_L_cache"]),
                         "w_out": matrix(d, d, d)}
        elif kind == "latent_attention":
            rank, nope, rope, vd = (sz[k] for k in LATENT)
            p["mla"] = {"wq": matrix(d, d, heads * (nope + rope)),
                        "wkv_a": matrix(d, d, rank + rope),
                        "kv_norm": scale(rank),
                        "wkv_b": matrix(rank, rank, heads * (nope + vd)),
                        "wo": matrix(heads * vd, heads * vd, d)}
        else:
            p["attn"] = {"wq": matrix(d, d, a), "wk": matrix(d, d, kv),
                         "wv": matrix(d, d, kv), "wo": matrix(a, a, d)}
            if sz["qk_norm"]:
                p["attn"].update(q_norm=scale(dh), k_norm=scale(dh))
        if i < sz["num_dense_layers"]:
            p["mlp"] = {"w1": matrix(d, d, f), "w3": matrix(d, d, f),
                        "w2": matrix(f, f, d)}
        else:
            p["moe"] = {"router": matrix(d, d, e_all),
                        "w1": matrix(d, e, d, hm), "w3": matrix(d, e, d, hm),
                        "w2": matrix(hm, e, hm, d)}
            if hs:
                p["moe"]["shared"] = {"w1": matrix(d, d, hs),
                                      "w3": matrix(d, d, hs),
                                      "w2": matrix(hs, hs, d)}
        return p

    params = {"embed": matrix(d, sz["vocab_size"], d),
              "layers": [layer(i, kind)
                         for i, kind in enumerate(sz["layer_types"])],
              "final_norm": scale()}
    if not sz["tie_word_embeddings"]:
        params["head"] = matrix(d, d, sz["vocab_size"])
    return params


def init_state(params, cfg: ModelConfig):
    """The model's state: for each layer that has experts their bias,
    zero (``{}`` for a layer without, and for every layer where the file
    says no ``use_expert_bias``: the state then holds nothing)."""
    biased = sizes(cfg)["use_expert_bias"]
    return {"layers": [
        {"expert_bias": jnp.zeros((p["moe"]["router"].shape[1],),
                                  jnp.float32)}
        if biased and "moe" in p else {}
        for p in params["layers"]]}


def _in_groups(fn, h, rows: int, most: int):
    """``fn`` on ``h``, whose ``rows`` leading rows go in equal groups of at
    most ``most`` (at least one row), one group after the other; a plain
    call where one group is all of them."""
    per = max(1, min(most, rows))
    while rows % per:
        per -= 1
    if per == rows:
        return fn(h)
    grouped = h.reshape(rows // per, per, *h.shape[1:])
    return lax.map(fn, grouped).reshape(h.shape)


def _over_sequences(fn, h):
    """``fn`` on ``h [B, S, D]``, as many whole sequences at a time as hold
    at most :data:`OP_CHUNK_TOKENS` tokens."""
    return _in_groups(fn, h, h.shape[0], OP_CHUNK_TOKENS // h.shape[1])


def _over_tokens(fn, h):
    """``fn``, which treats each token alone, on ``h [B, S, D]``,
    :data:`MLP_CHUNK_TOKENS` tokens at a time."""
    b, s, d = h.shape
    return _in_groups(fn, h.reshape(b * s, d), b * s,
                      MLP_CHUNK_TOKENS).reshape(b, s, d)


def expert_block_rows(slots: int, expected: float) -> int:
    """Rows of one block of the experts' buffer (see
    :data:`EXPERT_BLOCK_ROWS`), at most all ``slots``."""
    blocks = max(1, round(expected / EXPERT_BLOCK_ROWS))
    rows = -(-int(1.0625 * expected / blocks) // ROW_TILE) * ROW_TILE
    return min(slots, max(rows, ROW_TILE))


def _operator(h, p, kind: str, sz, cfg: ModelConfig, mesh):
    """``h + operator(rms(h))`` on ``h [B, S, D]``."""
    eps, low = sz["norm_eps"], jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("op_norm"):
        a = rms_norm(h, p["op_norm"]["scale"], eps)
    if kind == "conv":
        # not `conv`: that scope is the image models' convolutions
        with jax.named_scope("short_conv"):
            with jax.named_scope("in"):
                bcx = mixed_matmul(a, p["conv"]["w_in"], low)
            with jax.named_scope("gate_conv"):
                gated = gated_short_conv(bcx, p["conv"]["w"])
            with jax.named_scope("out"):
                return h + mixed_matmul(gated, p["conv"]["w_out"], low)
    if kind == "latent_attention":
        rank, nope, rope, vd = (sz[k] for k in LATENT)
        with jax.named_scope("mla"):
            return h + attention_lib.latent_attention(
                a, p["mla"], heads=sz["num_attention_heads"], nope_dim=nope,
                rope_dim=rope, v_dim=vd, rope=rope_rule(sz, kind), low=low,
                use_pallas=cfg.use_pallas_attention, mesh=mesh, norm_eps=eps)
    windowed = kind == "sliding_attention"
    # a scope of its own for the window sublayer: the innermost scope that
    # names a kind decides (utils/devprof.py), so the two are told apart
    with jax.named_scope("attn_window" if windowed else "attn"):
        return h + attention_lib.causal_self_attention(
            a, p["attn"], heads=sz["num_attention_heads"],
            kv_heads=sz["num_key_value_heads"], head_dim=sz["head_dim"],
            rope=rope_rule(sz, kind), low=low,
            use_pallas=cfg.use_pallas_attention, mesh=mesh, norm_eps=eps,
            window=sz["sliding_window"] if windowed else None)


def _gated_mlp(m, p, low):
    return mixed_matmul(jax.nn.silu(mixed_matmul(m, p["w1"], low))
                        * mixed_matmul(m, p["w3"], low), p["w2"], low)


def _dense_ffn(h, p, sz, cfg: ModelConfig):
    """``h + mlp(rms(h))``."""
    low = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("ffn_norm"):
        m = rms_norm(h, p["ffn_norm"]["scale"], sz["norm_eps"])
    with jax.named_scope("mlp"):
        return h + _gated_mlp(m, p["mlp"], low)


def _expert_ffn(h, p, bias, sz, cfg: ModelConfig, mesh, train: bool):
    """``h + experts(rms(h))`` on all of a step's tokens (with the shared
    experts, :data:`MLP_CHUNK_TOKENS` at a time, each chunk recomputed in
    the backward pass under ``remat``), and the layer's counters; in
    training with an ``aux_loss_alpha`` also its balance loss."""
    b, s, d = h.shape
    k, e_all = sz["num_experts_per_tok"], sz["router_num_experts"]
    slots = b * s * k
    low = jnp.dtype(cfg.compute_dtype)
    shared = None
    if "shared" in p["moe"]:
        def one(m):
            return _gated_mlp(m, p["moe"]["shared"], low)

        one = jax.checkpoint(one) if cfg.remat else one

        def shared(m):
            return _in_groups(one, m, b * s, MLP_CHUNK_TOKENS)
    # the layer's norm is formed where the router and the experts read it
    # (scope `ffn_norm` is theirs to open: kind `route` / `expert` decide)
    with jax.named_scope("moe"):
        f, stats = moe_lib.routed_experts(
            h.reshape(b * s, d), p["moe"],
            first_expert=sz["expert_first_id"], top_k=k,
            dtype=jnp.dtype(cfg.compute_dtype), bias=bias,
            norm_topk=sz["norm_topk_prob"],
            scaling=sz["routed_scaling_factor"],
            block_rows=expert_block_rows(
                slots, slots * sz["num_experts"] / e_all),
            norm_scale=p["ffn_norm"]["scale"], norm_eps=sz["norm_eps"],
            mesh=mesh, score=sz["router_score"], shared=shared,
            balance_alpha=sz["aux_loss_alpha"] if train else 0.0,
            sequences=b)
    return h + f.reshape(b, s, d), stats


def window_blocks_frac(sz: Dict[str, Any], seq: int, low) -> float:
    """Block pairs the window layers' flash schedule visits over those the
    causal schedule visits, at the blocks a call of ``seq`` tokens in
    ``low`` runs at (``ops.flash_attention.band_blocks_frac``)."""
    block = flash_lib.auto_block(seq, sz["head_dim"] * low.itemsize)
    return flash_lib.band_blocks_frac(seq, sz["sliding_window"], block)


def _note_paths(sz: Dict[str, Any]) -> None:
    """Adds to the step's line what only the model knows: in how many
    layers the attention the dispatch noted runs under a window, and a
    router that is not the sigmoid with a balancing bias."""
    kinds = sz["layer_types"]
    windowed = kinds.count("sliding_attention")
    path = kernel_paths.noted("attention")
    if windowed and path:
        path = path.replace(f", window {sz['sliding_window']}", "")
        kernel_paths.note(
            "attention", f"{path}, window {sz['sliding_window']} in "
                         f"{windowed} of {len(kinds)} layers")
    path = kernel_paths.noted("experts")
    if path:
        if sz["router_score"] != "sigmoid":
            path += f", {sz['router_score']} router"
        if not sz["use_expert_bias"]:
            path += ", no bias"
        kernel_paths.note("experts", path)


def loss(params, rows, cfg: ModelConfig, train: bool = True, mesh=None,
         model_state=None, loss_blocks=None):
    """The model's own loss over a batch of token rows ``[B, S+1]`` ->
    ``(mean next-token cross-entropy, stats, new model state)``. ``stats``:
    ``accuracy``, the share of next tokens whose logit is the largest,
    the mean over the experts' layers of ``moe_rows_here_frac``,
    ``moe_load_max_over_mean`` and ``moe_buffer_rounds``
    (``ops.moe.routed_experts``) and, in training with an
    ``aux_loss_alpha``, of ``moe_aux_loss`` (each layer's balance loss,
    which the returned loss holds summed over the layers), and, where a
    layer has a window,
    ``attn_window_blocks_frac`` (:func:`window_blocks_frac`). The state is
    :func:`init_state`'s (None: as at the start); in training each
    experts' layer's bias comes back moved one step toward an even load.
    ``loss_blocks`` overrides the number of blocks the loss is taken in."""
    sz = sizes(cfg)
    if model_state is None:
        model_state = init_state(params, cfg)
    rate = sz.get("expert_bias_update_rate", 0.0) if train else 0.0
    low = jnp.dtype(cfg.compute_dtype)
    inputs, targets = rows[:, :-1], rows[:, 1:].reshape(-1)
    n = targets.shape[0]

    def remat(fn, **kwargs):
        return jax.checkpoint(fn, **kwargs) if cfg.remat else fn

    if cfg.remat:
        kernel_paths.note("remat", "sublayer, keeps " + " ".join(KEPT))
    with jax.named_scope("embed"):
        h = params["embed"][inputs].astype(jnp.float32)
    moe_stats, new_state = [], {"layers": []}
    for i, (kind, p) in enumerate(zip(sz["layer_types"], params["layers"])):
        state = model_state["layers"][i]
        with jax.named_scope(f"layer{i}"):
            operator = remat(
                lambda x, p=p, kind=kind: _operator(x, p, kind, sz, cfg,
                                                    mesh),
                policy=jax.checkpoint_policies.save_only_these_names(*KEPT))
            h = _over_sequences(operator, h)
            if "mlp" in p:
                h = _over_tokens(
                    remat(lambda x, p=p: _dense_ffn(x, p, sz, cfg)), h)
            else:
                bias = state.get("expert_bias")
                h, stats = _expert_ffn(h, p, bias, sz, cfg, mesh, train)
                if bias is not None:
                    state = {"expert_bias": moe_lib.balanced_bias(
                        bias, stats.pop("expert_load"), rate)}
                moe_stats.append(stats)
        new_state["layers"].append(state)
    with jax.named_scope("final_norm"):
        h = rms_norm(h, params["final_norm"]["scale"], sz["norm_eps"])
    with jax.named_scope("head"):
        head = params["embed"].T if sz["tie_word_embeddings"] \
            else params["head"]
        ce, hit = loss_lib.blockwise_cross_entropy(
            h.reshape(n, h.shape[-1]), head, targets,
            loss_blocks or looped_decoder.token_blocks(n), low)
    balance = [s.pop("balance_loss") for s in moe_stats
               if "balance_loss" in s]
    with jax.named_scope("loss"):
        value = jnp.mean(ce)
        if balance:
            value = value + sum(balance)
    _note_paths(sz)
    stats = {"accuracy": lax.stop_gradient(jnp.mean(hit))}
    for name in ("rows_here_frac", "load_max_over_mean", "buffer_rounds"):
        if moe_stats:
            stats["moe_" + name] = sum(s[name] for s in moe_stats) \
                / len(moe_stats)
    if balance:
        stats["moe_aux_loss"] = lax.stop_gradient(sum(balance)
                                                  / len(balance))
    if "sliding_attention" in sz["layer_types"]:
        stats["attn_window_blocks_frac"] = jnp.float32(
            window_blocks_frac(sz, inputs.shape[1], low))
    return value, stats, jax.tree.map(lax.stop_gradient, new_state)


def batch_shape(cfg: ModelConfig, data_cfg: DataConfig, batch: int):
    """What a batch of this model is: rows of the token dataset."""
    del cfg
    return jax.ShapeDtypeStruct((batch, data_cfg.sequence_length + 1),
                                jnp.int32)


def param_count(cfg: ModelConfig) -> int:
    """Every number the model holds: its parameters and the buffers of
    its state (the experts' bias, which a published count has)."""
    def build():
        params = init_params(jax.random.key(0), cfg, DataConfig())
        return params, init_state(params, cfg)
    return sum(int(np.prod(x.shape))
               for x in jax.tree.leaves(jax.eval_shape(build)))


def step_flops(cfg: ModelConfig, data_cfg: DataConfig, batch: int) -> float:
    """Operations of one training step on ``batch`` sequences, from the
    shapes: three times the forward's multiply-adds, two operations each.
    The experts under uniform routing: of a token's ``num_experts_per_tok``
    slots the share ``num_experts / router_num_experts`` falls on an
    expert held here; the shared experts on every token. Causal attention
    as the pairs it has: the half square, ``S (S + 1) / 2``, in a full or
    latent layer and the band, ``W (W + 1) / 2 + (S - W) W``, in a layer
    with a window ``W < S``; a pair costs each head two products forward,
    of the query/key width and of the value width, and five backward: the
    score again, ``dQ`` and ``dK`` at the query/key width, ``dP`` and
    ``dV`` at the value width (two and a half times the forward where the
    widths are one). The head once, tied or not. Not counted: the
    embedding's gather, the filter's taps and gates, norms, softmax, the
    balance loss, and what the backward pass computes a second time."""
    sz = sizes(cfg)
    s, d = data_cfg.sequence_length, sz["hidden_size"]
    heads = sz["num_attention_heads"]
    a = heads * sz["head_dim"]
    kv = sz["num_key_value_heads"] * sz["head_dim"]
    kinds = sz["layer_types"]
    conv, full = kinds.count("conv"), kinds.count("full_attention")
    windowed = kinds.count("sliding_attention")
    latent = kinds.count("latent_attention")
    dense = sz["num_dense_layers"]
    here = sz["num_experts_per_tok"] * sz["num_experts"] \
        / sz["router_num_experts"]
    hm = sz["moe_intermediate_size"]
    per_token = conv * 4 * d * d + (full + windowed) * 2 * d * (a + kv) \
        + dense * 3 * d * sz["intermediate_size"] \
        + (len(kinds) - dense) * (
            d * sz["router_num_experts"]
            + (here + (sz["n_shared_experts"] or 0)) * 3 * d * hm) \
        + d * sz["vocab_size"]
    w = min(sz["sliding_window"] or s, s)
    half = s * (s + 1) // 2
    pairs = full * half + windowed * (w * (w + 1) // 2 + (s - w) * w)
    attention = 2 * 3.5 * 2 * a * pairs
    if latent:
        rank, nope, rope, vd = (sz[k] for k in LATENT)
        qk = nope + rope
        per_token += latent * (d * heads * qk + d * (rank + rope)
                               + rank * heads * (nope + vd) + heads * vd * d)
        attention += latent * 2 * heads * half * ((qk + vd)
                                                  + (3 * qk + 2 * vd))
    return float(batch * (6 * s * per_token + attention))

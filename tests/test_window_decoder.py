"""Window and full attention in one stack (``models/hybrid_decoder.py`` on
a file of Mellum2's shape) at a small size on the CPU: against the plain
reference of its benchmark configuration, the flash kernels under a window,
the two rotary rules, a softmax router without a bias, an untied head, the
counts from shapes, the scopes and the gauge of its step; and that a file
of the expert decoder's shape builds what it built before."""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import cells, datagen, reference
from dml_cnn_cifar10_tpu.config import (DataConfig, ModelConfig, OptimConfig,
                                        ParallelConfig)
from dml_cnn_cifar10_tpu.models import hybrid_decoder as m
from dml_cnn_cifar10_tpu.models.registry import get_model
from dml_cnn_cifar10_tpu.ops import attention as attention_lib
from dml_cnn_cifar10_tpu.ops import flash_attention as fa
from dml_cnn_cifar10_tpu.ops import kernel_paths, moe
from dml_cnn_cifar10_tpu.ops.layers import (mixed_matmul, rope_frequencies,
                                            rotary)
from dml_cnn_cifar10_tpu.parallel import mesh as mesh_lib
from dml_cnn_cifar10_tpu.parallel import step as step_lib
from dml_cnn_cifar10_tpu.utils import devprof

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmark", "configs", "mellum2_12b_a2p5b_l4_e8")
S, VOCAB, WINDOW = 40, 96, 12
NM = reference.Numerics("float32")
YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}


def _published(**over) -> dict:
    with open(CONFIG + ".json") as f:
        return {**json.load(f), **over}


#: A small file of the model's shape: three window layers and a full one,
#: a window shorter than the sequence and no multiple of a kernel's block,
#: both rotary rules (YaRN's ramp over a head of 16: pairs 1..4 at an
#: original length of 64), a softmax router of 8 with 4 experts held, no
#: bias, no per-head norm, a head of its own.
SMALL = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
         "moe_intermediate_size": 32, "num_hidden_layers": 4,
         "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
         "mlp_layer_types": ["sparse"] * 4, "sliding_window": WINDOW,
         "rope_parameters": {
             "sliding_attention": {"rope_type": "default",
                                   "rope_theta": 10000},
             "full_attention": {**YARN, "rope_theta": 10000, "factor": 4,
                                "original_max_position_embeddings": 64,
                                "attention_factor": None}},
         "num_dense_layers": 0, "num_experts": 4, "router_num_experts": 8,
         "expert_first_id": 0, "num_experts_per_tok": 2,
         "norm_topk_prob": True, "routed_scaling_factor": 1,
         "use_expert_bias": False, "router_score": "softmax",
         "qk_norm": False, "tie_word_embeddings": False, "vocab_size": VOCAB,
         "rms_norm_eps": 1e-6, "sequence_length": S}


@pytest.fixture(scope="module")
def ref():
    return cells.load_module(CONFIG + ".py")


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    """``(ModelConfig, spec)`` of the small file."""
    path = tmp_path_factory.mktemp("sizes") / "small.json"
    path.write_text(json.dumps(SMALL))
    return ModelConfig(name="hybrid_decoder", compute_dtype="float32",
                       config_file=str(path)), SMALL


@pytest.fixture(scope="module")
def params(ref, small):
    cfg, _ = small
    shapes = jax.eval_shape(
        lambda: m.init_params(jax.random.key(0), cfg, DataConfig()))
    return datagen.make_params(7, shapes, fan_in=ref.fan_in)


def _leaves(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(p), x) for p, x in flat]


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_every_gradient_leaf_equal_the_references(ref, small,
                                                           params, remat):
    """float32 on both sides at the highest matmul precision; what is left
    is the order of float32 sums. The model holds no state, the head is a
    leaf of its own whose gradient is the loss's alone, and the embedding's
    is the gather's alone: rows no input holds get none."""
    cfg, spec = small
    cfg = dataclasses.replace(cfg, remat=remat)
    rows = jax.random.randint(jax.random.key(1), (3, S + 1), 0, VOCAB)
    assert set(_published()) >= set(spec) - {"sequence_length"}
    assert m.init_state(params, cfg) == {"layers": [{}] * 4}

    def mine(p):
        value, stats, state = m.loss(p, rows, cfg)
        return value, (stats, state)

    ref_loss = ref.make_loss(spec)
    with jax.default_matmul_precision("highest"):
        (value, (stats, state)), g_mine = jax.value_and_grad(
            mine, has_aux=True)(params)
        (theirs, _), g_theirs = jax.value_and_grad(
            lambda p: ref_loss(NM, p, ref.init_model_state(p),
                               (rows[:, :-1], rows[:, 1:])),
            has_aux=True)(params)
    assert float(value) == pytest.approx(float(theirs), rel=2e-6)
    assert state == {"layers": [{}] * 4}
    assert 0.0 < float(stats["moe_rows_here_frac"]) < 1.0
    # 40 tokens in blocks of 40: the one block pair of either schedule
    assert float(stats["attn_window_blocks_frac"]) == 1.0
    assert [name for name, _ in _leaves(g_mine)] \
        == [name for name, _ in _leaves(ref.param_shapes(spec))]
    for (name, a), (_, b) in zip(_leaves(g_mine), _leaves(g_theirs)):
        assert float(jnp.max(jnp.abs(a - b))) \
            <= 2e-5 * float(jnp.max(jnp.abs(b))), name
        assert float(jnp.max(jnp.abs(b))) > 0, name
    unseen = np.setdiff1d(np.arange(VOCAB), np.asarray(rows[:, :-1]))
    assert unseen.size and not np.asarray(g_mine["embed"][unseen]).any()
    assert float(jnp.min(jnp.max(jnp.abs(g_mine["head"]), 0))) > 0


@pytest.mark.parametrize("fault", ["no_window", "one_rope", "wrong_experts"])
def test_each_fault_of_the_reference_moves_the_loss(ref, small, params,
                                                    fault):
    """What the limits on the chip have to see is there to be seen."""
    _, spec = small
    rows = jax.random.randint(jax.random.key(1), (2, S + 1), 0, VOCAB)
    batch = (rows[:, :-1], rows[:, 1:])
    sound = ref.make_loss(spec)(NM, params, None, batch)[0]
    broken = ref.make_loss(spec, fault)(NM, params, None, batch)[0]
    assert abs(float(sound) - float(broken)) > 1e-4 * float(sound)


# --- the flash kernels under a window ----------------------------------------

def _kernel_scopes(jaxpr, outer=""):
    """The name-scope path of every ``pallas_call`` in ``jaxpr`` and in the
    jaxprs its equations hold."""
    found = []
    for eqn in jaxpr.eqns:
        path = f"{outer}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "pallas_call":
            found.append(path)
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _kernel_scopes(sub, path)
    return found


@pytest.mark.parametrize("s,window,block", [(256, 96, 64), (300, 70, 128)])
def test_the_flash_kernels_with_a_window_equal_the_plain_softmax(s, window,
                                                                 block):
    """The Pallas kernels in the interpreter, the band's schedule (blocks
    off the band never visited), against ``xla_attention`` with the same
    window: value, ``dq``, ``dk``, ``dv``; at a length the blocks divide
    and one they do not, a window that is no multiple of a block; under a
    ``jax.checkpoint`` that keeps ``flash_out`` and ``flash_lse``, as the
    decoder's recomputed sublayer does."""
    b, h, d = 2, 2, 32
    q, k, v, g = (jax.random.normal(key, (b, s, h, d)) / 2
                  for key in jax.random.split(jax.random.key(9), 4))

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=window,
                                  block_q=block, block_k=block,
                                  interpret=True)

    kept = jax.checkpoint(
        flash, policy=jax.checkpoint_policies.save_only_these_names(*m.KEPT))

    def plain(q, k, v):
        return attention_lib.xla_attention(q, k, v, causal=True,
                                           window=window)

    n = -(-s // block)
    visited = fa._fold_schedule(n, n, block, block, True, window, "q")
    assert visited.shape[1] < n * (n + 1) // 2     # blocks are skipped
    with jax.default_matmul_precision("highest"):
        want = plain(q, k, v)
        np.testing.assert_allclose(flash(q, k, v), want, rtol=1e-4,
                                   atol=1e-5)
        # the window is felt: full attention differs
        assert float(jnp.max(jnp.abs(
            want - attention_lib.xla_attention(q, k, v, causal=True)))) > 0.01
        dwant = jax.grad(lambda *a: jnp.sum(g * plain(*a)), (0, 1, 2))(q, k,
                                                                       v)
        for fn in (flash, kept):
            dgot = jax.grad(lambda *a: jnp.sum(g * fn(*a)), (0, 1, 2))(q, k,
                                                                       v)
            for a, b_ in zip(dgot, dwant):
                np.testing.assert_allclose(a, b_, rtol=1e-4, atol=1e-5)
    # the kept residuals leave no forward kernel to the backward pass
    paths = _kernel_scopes(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(g * kept(*a)), (0, 1, 2)))(q, k, v).jaxpr)
    assert sorted(p.rsplit("/", 1)[-1] for p in paths) == [
        "flash_window_bwd_dkv", "flash_window_bwd_dq", "flash_window_fwd"]


def test_a_call_with_a_window_names_its_kernels_apart():
    """``flash_window_fwd`` / ``_bwd_dq`` / ``_bwd_dkv`` in the lowered
    text of a call with a window, ``flash_fwd`` etc. without: what a
    device trace's instructions are named after."""
    q = jnp.zeros((1, 256, 2, 32))

    def text(window):
        return jax.jit(jax.grad(lambda q: jnp.sum(fa.flash_attention(
            q, q, q, causal=True, window=window, interpret=True)))
        ).lower(q).as_text(debug_info=True)

    with_window, without = text(64), text(None)
    for kernel in ("fwd", "bwd_dq", "bwd_dkv"):
        assert f"flash_window_{kernel}" in with_window
        assert f"flash_{kernel}" not in with_window
        assert f"flash_{kernel}" in without
        assert "flash_window" not in without


def test_the_blocks_give_back_a_third_of_the_bands_saving():
    """8,192 tokens, window 1,024, blocks of 512: the first block row
    visits 1 block, the second 2, the other fourteen 3 each, 45 of the
    causal schedule's 136, where the band's pairs are 23.4% of the half
    square's; the dK/dV kernel's schedule visits as many."""
    assert fa.band_blocks_frac(8192, 1024, 512) == 45 / 136
    assert fa._fold_schedule(16, 16, 512, 512, True, 1024, "k").shape[1] == 45
    assert fa.auto_block(8192, 128 * 2) == 512
    band = 1024 * 1025 // 2 + (8192 - 1024) * 1024
    assert band == 7_864_832 and band / (8192 * 8193 // 2) \
        == pytest.approx(0.2344, abs=1e-4)
    # a window as long as the sequence is the causal schedule
    assert fa.band_blocks_frac(2048, 2048, 512) == 1.0


# --- rotary ------------------------------------------------------------------

def test_yarns_frequencies_and_factor_by_hand(ref):
    """theta 500,000, head 128, factor 16 over 8,192 original positions:
    ``dim(32) = 18.08`` and ``dim(1) = 34.98``, so the ramp runs over pairs
    18..35; pair 0 turns as trained, pair 25 is 7/17 of the way to a
    sixteenth, pair 63 a sixteenth; cos and sin times ``0.1 ln 16 + 1``.
    The program's rule and the reference's, each its own code."""
    rule = _published()["rope_parameters"]["full_attention"]
    assert rule == YARN
    assert ref.yarn_range(rule, 128) == (18, 35)
    for inv_freq, factor in (rope_frequencies(rule, 128),
                             ref.inv_frequencies(rule, 128)):
        assert inv_freq.shape == (64,)
        assert inv_freq[0] == 1.0
        assert inv_freq[25] == pytest.approx(0.0036474337, rel=1e-7)
        assert inv_freq[63] == pytest.approx(1.5344630e-07, rel=1e-7)
        # below the ramp untouched, above it a sixteenth
        plain = 500000.0 ** (-2 * np.arange(64) / 128)
        np.testing.assert_allclose(inv_freq[:19], plain[:19], rtol=1e-12)
        np.testing.assert_allclose(inv_freq[35:], plain[35:] / 16,
                                   rtol=1e-12)
        assert factor == 1.2772588722239782 \
            == pytest.approx(0.1 * np.log(16) + 1, rel=1e-15)
    # the sliding layers' rule, and a bare theta, are the plain one
    plain_rule = _published()["rope_parameters"]["sliding_attention"]
    for rope in (plain_rule, 500000):
        inv_freq, factor = rope_frequencies(rope, 128)
        np.testing.assert_array_equal(inv_freq, plain)
        assert factor == 1.0
    with pytest.raises(ValueError, match="not default or yarn"):
        rope_frequencies({"rope_type": "llama3", "rope_theta": 1e4}, 128)


def test_rotary_turns_by_the_rule(ref):
    """The program's ``rotary`` against the reference's on one sequence,
    both rules; under YaRN position 0 is scaled by the factor and nothing
    else."""
    x = jax.random.normal(jax.random.key(4), (1, 24, 3, 128))
    for rule in (YARN, {"rope_type": "default", "rope_theta": 500000}):
        np.testing.assert_allclose(rotary(x, rule)[0],
                                   ref.rotary(x[0], rule), rtol=1e-6,
                                   atol=1e-6)
    np.testing.assert_allclose(rotary(x, YARN)[:, 0],
                               x[:, 0] * YARN["attention_factor"], rtol=1e-6)


# --- a softmax router, no bias -----------------------------------------------

T, D, H, E_ALL, K = 40, 16, 12, 8, 3


@pytest.fixture(scope="module")
def expert_layer():
    ks = jax.random.split(jax.random.key(2), 5)
    return jax.random.normal(ks[0], (T, D)), {
        "router": jax.random.normal(ks[1], (D, E_ALL)) / 2,
        "w1": jax.random.normal(ks[2], (E_ALL, D, H)) / 4,
        "w3": jax.random.normal(ks[3], (E_ALL, D, H)) / 4,
        "w2": jax.random.normal(ks[4], (E_ALL, H, D)) / 4}


def test_the_four_shares_of_a_softmax_router_add_up(ref, expert_layer):
    """Experts 0-1, 2-3, 4-5 and 6-7 of a router of 8, each share told
    which it holds, softmax over all 8 logits, no bias, the weights over
    the chosen three's sum without an epsilon: against the reference's
    layer with all 8, values and the gradients of the input, the router
    and every expert."""
    x, p = expert_layer
    spec = {**SMALL, "num_experts": E_ALL, "router_num_experts": E_ALL,
            "num_experts_per_tok": K}
    uncut = ref.make_layers(spec)["experts"]

    def share(x, p, first):
        held = {"router": p["router"],
                **{k: p[k][first:first + 2] for k in ("w1", "w3", "w2")}}
        return moe.routed_experts(x, held, first_expert=first, top_k=K,
                                  dtype=jnp.float32, score="softmax",
                                  block_rows=16)

    def shares(x, p):
        return sum(share(x, p, f)[0] for f in (0, 2, 4, 6))

    with jax.default_matmul_precision("highest"):
        want = uncut(NM, x, p)
        np.testing.assert_allclose(shares(x, p), want, rtol=1e-4, atol=1e-5)
        g = jax.random.normal(jax.random.key(3), want.shape)
        got = jax.grad(lambda x, p: jnp.sum(g * shares(x, p)), (0, 1))(x, p)
        wanted = jax.grad(lambda x, p: jnp.sum(g * uncut(NM, x, p)),
                          (0, 1))(x, p)
    for (name, a), (_, b) in zip(_leaves(got), _leaves(wanted)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)
        assert np.asarray(b).any(), name
    assert sum(float(share(x, p, f)[1]["rows_here_frac"])
               for f in (0, 2, 4, 6)) == pytest.approx(1.0, abs=1e-6)


def test_softmax_weights_are_the_chosen_scores_over_their_sum(expert_layer):
    x, p = expert_layer
    with jax.default_matmul_precision("highest"):
        chosen, weights = moe.route_top_k(x, p["router"], None, K,
                                          score="softmax")
        prob = jax.nn.softmax(x @ p["router"], -1)
        raw = moe.route_top_k(x, p["router"], None, K, norm_topk=False,
                              score="softmax")[1]
    top = np.sort(np.asarray(prob), -1)[:, ::-1][:, :K]
    np.testing.assert_allclose(raw, top, rtol=1e-6)
    np.testing.assert_allclose(weights, top / top.sum(-1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_array_equal(
        np.sort(chosen, -1), np.sort(np.argsort(-prob, -1)[:, :K], -1))
    with pytest.raises(ValueError, match="sigmoid or softmax"):
        moe.route_top_k(x, p["router"], None, K, score="tanh")


# --- counts from shapes ------------------------------------------------------

@pytest.mark.parametrize("whole,count", [(False, 340_349_184),
                                         (True, 12_149_915_904)])
def test_parameters_at_the_published_widths(ref, tmp_path, whole, count):
    """By ``jax.eval_shape``: nothing of that size is built. The file's
    four layers, 8 experts and eighth of the vocabulary, and the published
    keys (28 layers, 64 experts, 98,304 rows): the name's 12B."""
    spec = _published()
    if whole:
        spec = {**spec, **{k: v for k, v in spec["published"].items()
                           if k != "parameters"}}
        assert spec["published"]["parameters"] == count
        assert len(spec["layer_types"]) == 28
    else:
        assert spec["parameters"] == count
    path = tmp_path / "sizes.json"
    path.write_text(json.dumps(spec))
    cfg = ModelConfig(name="hybrid_decoder", config_file=str(path))
    assert m.param_count(cfg) == ref.param_count(spec) == count
    mine = jax.eval_shape(
        lambda: m.init_params(jax.random.key(0), cfg, DataConfig()))
    theirs = ref.param_shapes(spec)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert [x.shape for x in jax.tree.leaves(mine)] \
        == [x.shape for x in jax.tree.leaves(theirs)]
    assert mine["head"].shape == (2304, spec["vocab_size"])
    assert "q_norm" not in mine["layers"][0]["attn"]


def test_the_count_of_operations_against_xlas(ref, tmp_path, monkeypatch):
    """The step written out (the loss in one block, no kernel) as XLA's
    cost analysis counts it, with every expert of the router held. The
    module counts the products and, in a window layer, the band's pairs,
    forward once and backward two and a half times, where the plain
    attention of this path multiplies the whole square in six products in
    every layer: the module's count with that difference put back is XLA's
    count less norms, softmaxes, rotary, SiLU and the routing, 0.97-1.0 of
    it at a hidden size of 128 (the band the other decoders' tests use)."""
    sz = {**SMALL, "hidden_size": 128, "head_dim": 32,
          "moe_intermediate_size": 64, "num_experts": 8}
    path = tmp_path / "sizes.json"
    path.write_text(json.dumps(sz))
    cfg = ModelConfig(name="hybrid_decoder", compute_dtype="float32",
                      config_file=str(path))
    data = DataConfig(dataset="tokens_synth", sequence_length=S)
    batch = 4
    rows = jnp.zeros((batch, S + 1), jnp.int32)
    shapes = jax.eval_shape(
        lambda: m.init_params(jax.random.key(0), cfg, data))
    monkeypatch.setattr(
        moe, "grouped_matmul",
        lambda x, w, sizes, dtype, mesh: mixed_matmul(x, w[0], dtype))

    def grads(p, rows):
        return jax.grad(lambda p: m.loss(p, rows, cfg, loss_blocks=1)[0])(p)

    counted = jax.jit(grads).lower(shapes, rows).compile() \
        .cost_analysis()["flops"]
    a = sz["num_attention_heads"] * sz["head_dim"]
    mine = m.step_flops(cfg, data, batch)
    band = WINDOW * (WINDOW + 1) // 2 + (S - WINDOW) * WINDOW
    pairs = 3 * band + S * (S + 1) // 2
    assert ref.window_costs.band_pairs(S, WINDOW) == band < S * (S + 1) // 2
    whole_square = batch * a * (4 * 12 * S * S - 14 * pairs)
    assert 0.97 <= (mine + whole_square) / counted <= 1.0
    # and the benchmark's module counts what the program counts
    assert ref.train_flops_per_image(sz) * batch == mine


def test_operations_a_sequence_by_hand(ref):
    """1.23 GFLOP a token at the cell's sizes: 138,608,640 multiply-adds a
    token in products (one of a token's eight slots on an expert held here,
    a layer; the head once) times 6, and attention's pairs, the half square
    in the full layer and the band in the three window layers, forward once
    and backward two and a half times."""
    spec = _published()
    s = spec["sequence_length"]
    attn = 2 * 2304 * 4096 + 2 * 2304 * 512
    per_token = 4 * (attn + 2304 * 64 + 3 * 2304 * 896) + 2304 * 12288
    assert per_token == 138_608_640
    pairs = s * (s + 1) // 2 + 3 * 7_864_832
    by_hand = 6 * s * per_token + 7 * 2 * 4096 * pairs
    assert ref.train_flops_per_image(spec) == by_hand
    assert 1.22e9 < by_hand / s < 1.24e9
    cfg = ModelConfig(name="hybrid_decoder", config_file=CONFIG + ".json")
    data = DataConfig(dataset="tokens_synth", sequence_length=s)
    assert m.step_flops(cfg, data, 4) == 4 * by_hand
    # were the window not counted, the masked pairs would read as done
    assert 7 * 2 * 4096 * 3 * (s * (s + 1) // 2 - 7_864_832) * 4 \
        == pytest.approx(17.68e12, rel=1e-3)


# --- scopes, kinds, the step's line, the gauge -------------------------------

def test_the_lowered_step_holds_the_scopes_and_the_map_their_kinds(small,
                                                                   capsys):
    cfg, _ = small
    cfg = ModelConfig(name="hybrid_decoder", remat=True,
                      config_file=cfg.config_file)
    model_def = get_model("hybrid_decoder")
    data = DataConfig(dataset="tokens_synth", sequence_length=S)
    optim = OptimConfig(optimizer="adamw")
    mesh = mesh_lib.build_mesh(ParallelConfig(), devices=jax.devices()[:1])
    state = jax.eval_shape(
        lambda k: step_lib.init_train_state(k, model_def, cfg, data, optim),
        jax.random.key(0))
    assert not jax.tree.leaves(state.model_state)      # no bias, no state
    step = step_lib.make_train_step(model_def, cfg, optim, mesh)
    batch = (model_def.batch_shape(cfg, data, 2),
             jax.ShapeDtypeStruct((2,), jnp.int32))
    lowered = step.lower(state, *batch)
    said = capsys.readouterr().out
    assert f"attention=xla ({S} tokens), window {WINDOW} in 3 of 4 layers " \
        in said
    assert said.rstrip().endswith(
        "experts=ragged_dot, xla, softmax router, no bias")
    import re
    named = ["/" + n for n in set(re.findall(
        r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))]
    for scope in ("layer0", "layer3", "op_norm", "attn_window/qkv",
                  "attn_window/rotary", "attn_window/flash",
                  "attn_window/out", "attn/qkv", "attn/flash", "moe",
                  "route", "experts", "final_norm", "head", "loss"):
        assert any(f"/{scope}/" in n or f"({scope})" in n
                   for n in named), scope
    assert not any("qk_norm" in n or "short_conv" in n for n in named)
    kinds = {e.kind for e in devprof.scope_map(lowered.compile()).values()}
    assert {"window_attention", "attention", "route", "expert", "norm",
            "embed", "optimizer"} <= kinds
    for scope, kind in (("layer0/attn_window/qkv", "window_attention"),
                        ("layer2/attn_window/flash/flash_window_fwd",
                         "window_attention"),
                        ("layer1/attn_window/out", "window_attention"),
                        ("layer3/attn/flash/flash_fwd", "attention"),
                        ("layer0/op_norm", "norm"),
                        ("layer0/moe/ffn_norm", "norm"),
                        ("layer0/moe/route", "route")):
        assert devprof.parse_op_name(
            f"jit(step)/fwd_bwd/{scope}/dot_general")[1] == kind, scope


def test_the_dispatchs_note_says_the_window():
    q = jnp.zeros((1, 16, 2, 8))
    for window, want in ((None, "xla (16 tokens)"),
                         (4, "xla (16 tokens), window 4")):
        with kernel_paths.recording() as rec:
            attention_lib.dispatch_attention(q, q, q, causal=True,
                                             window=window)
            assert kernel_paths.noted("attention") == want
        assert rec == {"attention": want}
    assert kernel_paths.noted("attention") is None


def test_the_flash_paths_note_names_the_grouping_and_keeps_its_rewrite(small):
    """At 128 tokens through the kernels (the interpreter here): 4 query
    heads over 2 key/value heads are named in the note, and the model's
    rewrite of the window still finds its words in it."""
    cfg, _ = small
    q, kv = jnp.zeros((1, 128, 4, 16)), jnp.zeros((1, 128, 2, 16))
    said = "flash-interpret (128 tokens), window 12, 2 query heads a " \
        "key/value head"
    with kernel_paths.recording() as rec:
        attention_lib.dispatch_attention(q, kv, kv, use_pallas=True,
                                         causal=True, window=WINDOW)
        assert kernel_paths.noted("attention") == said
        m._note_paths(m.sizes(cfg))
    assert rec["attention"] == (
        "flash-interpret (128 tokens), 2 query heads a key/value head, "
        f"window {WINDOW} in 3 of 4 layers")


def test_the_cli_trains_it_and_the_records_carry_the_gauge(small, tmp_path):
    """``python cifar10cnn.py --model hybrid_decoder --model_config_file
    ...`` through ``Trainer.fit`` on the resident K-step dispatch: the
    loss falls, and each ``train`` record and the registry carry
    ``attn_window_blocks_frac`` beside the experts' three counters."""
    from dml_cnn_cifar10_tpu.cli.main import main
    from dml_cnn_cifar10_tpu.utils import metrics_registry
    cfg, _ = small
    out = tmp_path / "m.jsonl"
    main(["--model", "hybrid_decoder", "--model_config_file",
          cfg.config_file, "--dataset", "tokens_synth",
          "--sequence_length", str(S), "--data_dir", str(tmp_path / "d"),
          "--log_dir", str(tmp_path / "l"), "--batch_size", "4",
          "--steps_per_dispatch", "2", "--total_steps", "16",
          "--output_every", "4", "--eval_every", "1000",
          "--checkpoint_every", "1000", "--optimizer", "adamw",
          "--learning_rate", "0.003", "--adam_b2", "0.95",
          "--weight_decay", "0.1", "--schedule", "constant", "--remat",
          "true", "--resident_data", "true", "--device_index_stream",
          "true", "--synthetic_train_records", "64", "--metrics_jsonl",
          str(out)])
    records = [json.loads(line) for line in out.read_text().splitlines()]
    train = [r for r in records if r.get("kind") == "train"]
    assert len(train) == 4 and train[-1]["loss"] < train[0]["loss"]
    for r in train:
        assert r["attn_window_blocks_frac"] == 1.0
        assert 0.0 < r["moe_rows_here_frac"] < 1.0
        assert r["moe_buffer_rounds"] >= 1.0
    reg = metrics_registry.default_registry()
    assert next(iter(reg.get("dml_attn_window_blocks_frac")
                     .values().values())) == 1.0


# --- what a file may not say -------------------------------------------------

@pytest.mark.parametrize("over,said", [
    ({"sliding_window": None}, "needs a sliding_window"),
    ({"layer_types": ["sliding_attention"] * 3 + ["local"]},
     "conv, full_attention, sliding_attention"),
    ({"rope_parameters": {"sliding_attention": {"rope_theta": 1e4}}},
     "lacks a rule for full_attention"),
    ({"rope_parameters": {
        "sliding_attention": {"rope_theta": 1e4},
        "full_attention": {"rope_type": "longrope", "rope_theta": 1e4}}},
     "'longrope' of full_attention is not default or yarn"),
    ({"router_score": "tanh"}, "not sigmoid or softmax"),
    ({"rope_parameters": None}, "lacks ['rope_theta']"),
])
def test_sizes_that_cannot_be_run_are_refused(tmp_path, over, said):
    path = tmp_path / "sizes.json"
    path.write_text(json.dumps({**SMALL, **over}))
    cfg = ModelConfig(name="hybrid_decoder", config_file=str(path))
    with pytest.raises(ValueError) as e:
        m.sizes(cfg)
    assert said in str(e.value) and str(path) in str(e.value)


# --- the expert decoder's shape, as before -----------------------------------

@pytest.mark.parametrize("dtype,loss_bits", [
    ("float32", "0x1.4474460000000p+2"),
    ("bfloat16", "0x1.446f7e0000000p+2")])
def test_a_file_of_the_expert_decoders_shape_builds_what_it_built(
        dtype, loss_bits, tmp_path):
    """A file with the single ``rope_theta`` and no ``sliding_window``, no
    ``router_score``, no ``qk_norm``, no ``tie_word_embeddings`` (the
    expert decoder's: here the built-in sizes, and the same written to a
    file): the parameter tree, bit for bit, and the loss, to the last bit,
    that the parent of the PR that added the window built on the CPU
    (recorded from it: tree hash, loss, and the bias's first step)."""
    def built(cfg):
        params = m.init_params(jax.random.key(5), cfg, DataConfig())
        digest = hashlib.sha256()
        for name, x in _leaves(params):
            digest.update(name.encode())
            digest.update(np.asarray(x).tobytes())
        rows = jax.random.randint(jax.random.key(6), (2, 33), 0, 96)
        value, stats, state = m.loss(params, rows, cfg)
        return digest.hexdigest()[:16], float(value).hex(), stats, state

    cfg = ModelConfig(name="hybrid_decoder", compute_dtype=dtype, remat=True)
    path = tmp_path / "sizes.json"
    path.write_text(json.dumps(m.SMALL))
    from_file = ModelConfig(name="hybrid_decoder", compute_dtype=dtype,
                            remat=True, config_file=str(path))
    for c in (cfg, from_file):
        tree, value, stats, state = built(c)
        assert tree == "631c24b7ec88b531"
        assert value == loss_bits
        assert "attn_window_blocks_frac" not in stats
        assert np.asarray(state["layers"][1]["expert_bias"]).tobytes() \
            .hex()[:32] == "000000006f12833a000000006f1283ba"
        assert set(state["layers"][0]) == set()

"""Multi-head latent attention, shared experts and the sequence-wise
balance loss (``models/hybrid_decoder.py`` on a file of DeepSeek-V2-Lite's
shape) at a small size on the CPU: against the plain reference of its
benchmark configuration, the flash kernels with values of a head size of
their own, DeepSeek's YaRN keys, the shares of an expert layer with shared
experts, the counts from shapes, the scopes and the counter of its step."""

import dataclasses
import hashlib
import json
import os
import re

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
                                            rope_softmax_factor, rotary,
                                            yarn_mscale)
from dml_cnn_cifar10_tpu.parallel import mesh as mesh_lib
from dml_cnn_cifar10_tpu.parallel import step as step_lib
from dml_cnn_cifar10_tpu.utils import devprof

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmark", "configs", "deepseek_v2_lite_l5_e8")
S, VOCAB = 40, 96
NM = reference.Numerics("float32")
ROPE = {"type": "yarn", "factor": 4, "original_max_position_embeddings": 64,
        "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
        "mscale_all_dim": 0.707}


def _published(**over) -> dict:
    with open(CONFIG + ".json") as f:
        return {**json.load(f), **over}


#: A small file of the model's shape, under the published names: three
#: latent layers (the first dense), queries and keys of 16 + 8 over values
#: of 12, a latent of 32, a softmax router of 8 with 4 experts held and 3
#: a token, two shared experts, the raw scores as weights, a balance loss
#: large enough to be seen.
SMALL = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 4, "kv_lora_rank": 32, "q_lora_rank": None,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 12,
         "intermediate_size": 128, "moe_intermediate_size": 32,
         "n_shared_experts": 2, "num_hidden_layers": 3,
         "layer_types": ["latent_attention"] * 3, "first_k_dense_replace": 1,
         "moe_layer_freq": 1, "n_routed_experts": 4,
         "router_num_experts": 8, "expert_first_id": 0,
         "num_experts_per_tok": 3, "norm_topk_prob": False,
         "routed_scaling_factor": 1, "use_expert_bias": False,
         "scoring_func": "softmax", "topk_method": "greedy", "n_group": 1,
         "topk_group": 1, "tie_word_embeddings": False, "vocab_size": VOCAB,
         "rms_norm_eps": 1e-6, "rope_theta": 10000, "rope_scaling": ROPE,
         "aux_loss_alpha": 0.01, "seq_aux": True, "sequence_length": S}


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
    is the order of float32 sums. The loss holds the balance terms of the
    two expert layers; every leaf has a gradient, the shared experts' and
    the latent's norm among them."""
    cfg, spec = small
    cfg = dataclasses.replace(cfg, remat=remat)
    rows = jax.random.randint(jax.random.key(1), (3, S + 1), 0, VOCAB)
    assert set(_published()) >= set(spec) - {"sequence_length"}

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
        without = m.loss(params, rows, cfg, train=False)[0]
    assert float(value) == pytest.approx(float(theirs), rel=2e-6)
    assert state == {"layers": [{}] * 3}
    # the balance terms: about alpha a layer under a near-even load, and
    # the loss holds both layers' (not in evaluation)
    aux = float(stats["moe_aux_loss"])
    assert 0.5 * 0.01 < aux < 2 * 0.01
    assert float(value) - float(without) == pytest.approx(2 * aux, rel=1e-4)
    assert [name for name, _ in _leaves(g_mine)] \
        == [name for name, _ in _leaves(ref.param_shapes(spec))]
    for (name, a), (_, b) in zip(_leaves(g_mine), _leaves(g_theirs)):
        assert float(jnp.max(jnp.abs(a - b))) \
            <= 2e-5 * float(jnp.max(jnp.abs(b))), name
        assert float(jnp.max(jnp.abs(b))) > 0, name


@pytest.mark.parametrize("fault", ["no_shared", "no_mscale", "wrong_experts"])
def test_each_fault_of_the_reference_moves_the_loss(ref, small, params,
                                                    fault):
    """What the limits on the chip have to see is there to be seen."""
    _, spec = small
    rows = jax.random.randint(jax.random.key(1), (2, S + 1), 0, VOCAB)
    batch = (rows[:, :-1], rows[:, 1:])
    sound = ref.make_loss(spec)(NM, params, None, batch)[0]
    broken = ref.make_loss(spec, fault)(NM, params, None, batch)[0]
    assert abs(float(sound) - float(broken)) > 1e-4 * float(sound)


def test_the_balance_term_by_hand():
    """Two sequences of four tokens, 4 experts, 2 a token: the slots of
    each sequence on each expert times ``E / (k S)`` against the mean
    scores; an even load and even scores read 1; only the scores carry a
    gradient."""
    s = jnp.asarray(np.random.default_rng(0).dirichlet(np.ones(4), 8),
                    jnp.float32)
    chosen = jnp.asarray([[0, 1], [0, 1], [2, 3], [0, 2],
                          [1, 1], [3, 3], [3, 3], [2, 2]], jnp.int32)
    want = 0
    for b in range(2):
        counts = np.bincount(np.asarray(chosen[4 * b:4 * b + 4]).ravel(),
                             minlength=4)
        want += np.sum(counts * 4 / (2 * 4) * np.asarray(s[4 * b:4 * b + 4])
                       .mean(0))
    got = moe.sequence_balance_loss(s, chosen, 2, 0.5)
    assert float(got) == pytest.approx(0.5 * want / 2, rel=1e-6)
    even = jnp.full((8, 4), 0.25)
    spread = jnp.asarray([[0, 1], [2, 3]] * 4, jnp.int32)
    assert float(moe.sequence_balance_loss(even, spread, 2, 1.0)) \
        == pytest.approx(1.0)
    g = jax.grad(lambda s: moe.sequence_balance_loss(s, chosen, 2, 1.0))(s)
    assert np.asarray(g).any()


# --- the shares of an expert layer with shared experts ------------------------

T, D, H, E_ALL, K = 40, 16, 12, 8, 3


@pytest.fixture(scope="module")
def expert_layer():
    ks = jax.random.split(jax.random.key(2), 8)
    return jax.random.normal(ks[0], (T, D)), {
        "router": jax.random.normal(ks[1], (D, E_ALL)) / 2,
        "w1": jax.random.normal(ks[2], (E_ALL, D, H)) / 4,
        "w3": jax.random.normal(ks[3], (E_ALL, D, H)) / 4,
        "w2": jax.random.normal(ks[4], (E_ALL, H, D)) / 4,
        "shared": {"w1": jax.random.normal(ks[5], (D, 2 * H)) / 4,
                   "w3": jax.random.normal(ks[6], (D, 2 * H)) / 4,
                   "w2": jax.random.normal(ks[7], (2 * H, D)) / 4}}


def test_eight_shares_and_the_shared_experts_once_equal_the_whole_layer(
        ref, expert_layer):
    """Experts 0..7 of a router of 8, one a share, each told which it
    holds, softmax over all 8 logits, the raw scores as weights; the shared
    experts counted once (one share computes them, as one chip does for its
    own tokens): against the reference's layer with all 8, values and the
    gradients of the input, the router, every expert and the shared ones.
    Every share's balance term is the whole layer's."""
    x, p = expert_layer
    spec = {**SMALL, "n_routed_experts": E_ALL, "router_num_experts": E_ALL,
            "num_experts_per_tok": K, "reference_loss_blocks": 1}
    uncut = ref.make_layers(spec)["experts"]
    shared = p["shared"]

    def share(x, p, first, with_shared):
        held = {"router": p["router"],
                **{k: p[k][first:first + 1] for k in ("w1", "w3", "w2")}}
        return moe.routed_experts(
            x, held, first_expert=first, top_k=K, dtype=jnp.float32,
            score="softmax", norm_topk=False, block_rows=16,
            shared=(lambda m: mixed_matmul(
                jax.nn.silu(mixed_matmul(m, p["shared"]["w1"], jnp.float32))
                * mixed_matmul(m, p["shared"]["w3"], jnp.float32),
                p["shared"]["w2"], jnp.float32)) if with_shared else None,
            balance_alpha=1.0, sequences=2)

    def shares(x, p):
        return sum(share(x, p, f, f == 0)[0] for f in range(E_ALL))

    def whole(x, p):
        halves = [uncut(NM, h, p) for h in (x[:T // 2], x[T // 2:])]
        return jnp.concatenate([f for f, _ in halves]), \
            (halves[0][1] + halves[1][1]) / 2

    assert shared["w1"].shape == (D, 2 * H)
    with jax.default_matmul_precision("highest"):
        want, balance = whole(x, p)
        np.testing.assert_allclose(shares(x, p), want, rtol=1e-4, atol=1e-5)
        for f in (0, 5):
            assert float(share(x, p, f, False)[1]["balance_loss"]) \
                == pytest.approx(float(balance), rel=1e-5)
        g = jax.random.normal(jax.random.key(3), want.shape)
        got = jax.grad(lambda x, p: jnp.sum(g * shares(x, p)), (0, 1))(x, p)
        wanted = jax.grad(lambda x, p: jnp.sum(g * whole(x, p)[0]),
                          (0, 1))(x, p)
    for (name, a), (_, b) in zip(_leaves(got), _leaves(wanted)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)
        assert np.asarray(b).any(), name
    # no alpha, no term: the counters as before and nothing more
    _, stats = moe.routed_experts(x, p, first_expert=0,
                                  top_k=K, dtype=jnp.float32,
                                  score="softmax")
    assert set(stats) == {"rows_here_frac", "load_max_over_mean",
                          "buffer_rounds", "expert_load"}


# --- the flash kernels with values of their own head size ---------------------

@pytest.mark.parametrize("s,dqk,dv,block", [(256, 48, 32, 64),
                                            (200, 192, 128, 128)])
def test_flash_kernels_with_values_of_their_own_width(s, dqk, dv, block):
    """Queries and keys of one width, values of another: the Pallas kernels
    in the interpreter against ``xla_attention``, causal, value and the
    three gradients (``dq`` and ``dk`` at the query width, ``dv`` at the
    value width), at a length the blocks divide and one they do not; and
    under a ``jax.checkpoint`` that keeps ``flash_out`` and ``flash_lse``."""
    b, h = 2, 2
    ks = jax.random.split(jax.random.key(9), 4)
    q, k = (jax.random.normal(key, (b, s, h, dqk)) / 2 for key in ks[:2])
    v, g = (jax.random.normal(key, (b, s, h, dv)) / 2 for key in ks[2:])
    scale = 0.114722

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, scale=scale, causal=True,
                                  block_q=block, block_k=block,
                                  interpret=True)

    kept = jax.checkpoint(
        flash, policy=jax.checkpoint_policies.save_only_these_names(*m.KEPT))

    def plain(q, k, v):
        return attention_lib.xla_attention(q, k, v, scale=scale, causal=True)

    with jax.default_matmul_precision("highest"):
        want = plain(q, k, v)
        assert want.shape == (b, s, h, dv)
        np.testing.assert_allclose(flash(q, k, v), want, rtol=1e-4,
                                   atol=1e-5)
        dwant = jax.grad(lambda *a: jnp.sum(g * plain(*a)), (0, 1, 2))(q, k,
                                                                       v)
        for fn in (flash, kept):
            dgot = jax.grad(lambda *a: jnp.sum(g * fn(*a)), (0, 1, 2))(q, k,
                                                                       v)
            for a, b_, width in zip(dgot, dwant, (dqk, dqk, dv)):
                assert a.shape[-1] == width
                np.testing.assert_allclose(a, b_, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("group,window,digest", [
    (1, None, "33e34543d0c8fd77"), (1, 64, "89094169e5c4c508"),
    (2, None, "a3bcf023614ea6fa"), (2, 64, "ab26969e5045d439")])
def test_equal_widths_trace_to_the_kernels_of_before(group, window, digest):
    """A call whose values are as wide as its queries traces to the same
    program as before the kernels took a value width of their own: the
    jaxpr of its gradient (the three kernels, their block specs, grids and
    bodies), recorded from the parent of that change, whole heads and
    grouped, with and without a window."""
    q = jnp.zeros((1, 256, 2, 32))
    k = jnp.zeros((1, 256, 2 // group, 32))

    def f(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True, window=window,
                                          block_q=128, block_k=128,
                                          interpret=True))

    text = str(jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(q, k, k))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


def test_the_blocks_follow_the_wider_row():
    """192-wide bfloat16 queries over 128-wide values at 8,192 tokens run
    at blocks of 512, as a 128-wide head does; a 32-wide float32 call that
    keeps 64-wide values runs at the 64 rows' 512, not the 32's 1,024."""
    q = jnp.zeros((1, 8192, 1, 192), jnp.bfloat16)
    v = jnp.zeros((1, 8192, 1, 128), jnp.bfloat16)
    assert fa._resolve(q, None, None, None, True, v)[1:3] == (512, 512)
    narrow = jnp.zeros((1, 4096, 1, 32), jnp.float32)
    wide = jnp.zeros((1, 4096, 1, 64), jnp.float32)
    assert fa._resolve(narrow, None, None, None, True)[1] == 1024
    assert fa._resolve(narrow, None, None, None, True, wide)[1] == 512
    assert fa._resolve(q, None, None, None, True, v)[0] \
        == pytest.approx(192 ** -0.5)


def test_the_dispatchs_note_says_both_widths():
    q, v = jnp.zeros((1, 16, 2, 24)), jnp.zeros((1, 16, 2, 12))
    with kernel_paths.recording():
        attention_lib.dispatch_attention(q, q, v, causal=True)
        assert kernel_paths.noted("attention") == "xla (16 tokens), qk 24 v 12"
        attention_lib.dispatch_attention(q, q, q, causal=True)
        assert kernel_paths.noted("attention") == "xla (16 tokens)"
    q, v = jnp.zeros((1, 128, 2, 24)), jnp.zeros((1, 128, 2, 12))
    with kernel_paths.recording():
        attention_lib.dispatch_attention(q, q, v, use_pallas=True,
                                         causal=True)
        assert kernel_paths.noted("attention") \
            == "flash-interpret (128 tokens), qk 24 v 12"


# --- rotary: DeepSeek's keys -------------------------------------------------

def test_deepseeks_yarn_by_hand(ref):
    """theta 10,000 over the 64-wide rope slice, factor 40 over 4,096
    original positions: ``dim(32) = 10.47`` and ``dim(1) = 22.51``, so the
    ramp runs over pairs 10..23; cos and sin times mscale(40, 0.707) /
    mscale(40, 0.707) = 1, and the softmax scale 192^-0.5 x mscale(40,
    0.707)^2 = 0.114722. The program's rule (the file's rope_scaling, its
    ``type`` read as ``rope_type``) and the reference's, each its own
    code."""
    spec = _published()
    rule = m.rope_rule(m.sizes(ModelConfig(name="hybrid_decoder",
                                           config_file=CONFIG + ".json")),
                       "latent_attention")
    assert rule["rope_type"] == "yarn" and rule["rope_theta"] == 10000
    assert ref.yarn_range({**spec["rope_scaling"], "rope_theta": 10000},
                          64) == (10, 23)
    plain = 10000.0 ** (-np.arange(32) / 32)
    for inv_freq, factor in (
            rope_frequencies(rule, 64),
            ref.inv_frequencies({**spec["rope_scaling"],
                                 "rope_theta": 10000}, 64)):
        assert inv_freq.shape == (32,) and factor == 1.0
        np.testing.assert_allclose(inv_freq[:11], plain[:11], rtol=1e-12)
        np.testing.assert_allclose(inv_freq[23:], plain[23:] / 40,
                                   rtol=1e-12)
        assert plain[16] / 40 < inv_freq[16] < plain[16]
    assert yarn_mscale(40, 0.707) == pytest.approx(1.260804, abs=1e-6)
    assert 192 ** -0.5 * rope_softmax_factor(rule) \
        == pytest.approx(0.114722, abs=1e-6) == ref.softmax_scale(spec)
    assert ref.softmax_scale(spec, "no_mscale") == 192 ** -0.5


@pytest.mark.parametrize("rule,factor", [
    # DeepSeek's keys decide, never the library's default
    ({"mscale": 1.0, "mscale_all_dim": 0.5},
     (1 + 0.1 * np.log(40)) / (1 + 0.05 * np.log(40))),
    # mscale 1 where a rule names only mscale_all_dim, as DeepSeek's code
    ({"mscale_all_dim": 0.707},
     (1 + 0.1 * np.log(40)) / (1 + 0.0707 * np.log(40))),
    # a rule that names a factor keeps it (mellum2's), one that names none
    # of the three takes 0.1 ln(factor) + 1
    ({"attention_factor": 1.25, "mscale": 0.707}, 1.25),
    ({}, 0.1 * np.log(40) + 1.0)])
def test_the_yarn_factor_is_the_rules_own(rule, factor):
    base = {"rope_type": "yarn", "rope_theta": 10000, "factor": 40,
            "original_max_position_embeddings": 4096}
    assert rope_frequencies({**base, **rule}, 64)[1] \
        == pytest.approx(factor, rel=1e-12)
    assert rope_softmax_factor({**base, **rule}) == pytest.approx(
        yarn_mscale(40, rule["mscale_all_dim"]) ** 2
        if rule.get("mscale_all_dim") else 1.0)
    assert rope_softmax_factor(10000) == 1.0


def test_rotary_on_the_rope_slice_matches_the_reference(ref):
    x = jax.random.normal(jax.random.key(4), (1, 24, 3, 64))
    rule = {**_published()["rope_scaling"], "rope_theta": 10000}
    mine = {**rule, "rope_type": rule.pop("type")}
    np.testing.assert_allclose(rotary(x, mine)[0], ref.rotary(x[0], rule),
                               rtol=1e-6, atol=1e-6)


# --- counts from shapes ------------------------------------------------------

@pytest.mark.parametrize("whole,count", [(False, 535_060_992),
                                         (True, 15_706_484_224)])
def test_parameters_at_the_published_widths(ref, tmp_path, whole, count):
    """By ``jax.eval_shape``: nothing of that size is built. The file's
    five layers, 8 experts and eighth of the vocabulary, and the published
    keys (27 layers, 64 experts, 102,400 rows): the name's 15.7B."""
    spec = _published()
    if whole:
        spec = {**spec, **{k: v for k, v in spec["published"].items()
                           if k != "parameters"}}
        assert spec["published"]["parameters"] == count
        assert len(spec["layer_types"]) == 27
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
    mla = mine["layers"][0]["mla"]
    assert (mla["wq"].shape, mla["wkv_a"].shape, mla["wkv_b"].shape,
            mla["wo"].shape) == ((2048, 3072), (2048, 576), (512, 4096),
                                 (2048, 2048))
    assert mine["layers"][1]["moe"]["shared"]["w2"].shape == (2816, 2048)
    assert "moe" not in mine["layers"][0]


def test_the_count_of_operations_against_xlas(ref, small, tmp_path,
                                              monkeypatch):
    """The step written out (the loss in one block, no kernel) as XLA's
    cost analysis counts it, with every expert of the router held. The
    module counts the products and attention's half square, two products a
    pair forward and five backward at their own widths, where the plain
    attention of this path multiplies the whole square in three products
    at each width: the module's count with that difference put back is
    XLA's count less norms, softmaxes, rotary, SiLU, the routing and the
    balance loss, 0.97-1.0 of it at a hidden size of 128."""
    _, spec = small
    sz = {**spec, "hidden_size": 128, "kv_lora_rank": 64,
          "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 24,
          "moe_intermediate_size": 64, "n_routed_experts": 8}
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
    mine = m.step_flops(cfg, data, batch)
    heads, qk, vd = 4, 48, 24
    half = S * (S + 1) // 2
    square = 3 * batch * 2 * heads * (
        S * S * 3 * (qk + vd) - half * (4 * qk + 3 * vd))
    assert 0.97 <= (mine + square) / counted <= 1.0
    # and the benchmark's module counts what the program counts
    assert ref.train_flops_per_image(sz) * batch == mine


def test_operations_a_sequence_by_hand(ref):
    """2.30 GFLOP a token at the cell's sizes: the five projections of
    attention, the dense MLP in one layer, the router, 0.75 of a routed
    slot and the shared experts in four, the head, times 6; attention's
    half square at 2 x 16 x (192 + 128) operations a pair forward and 2 x
    16 x (3 x 192 + 2 x 128) backward in five layers."""
    spec = _published()
    s = spec["sequence_length"]
    mla = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    per_token = 5 * mla + 3 * 2048 * 10944 + 4 * (
        2048 * 64 + (0.75 + 2) * 3 * 2048 * 1408) + 2048 * 12800
    assert mla == 13_762_560 and per_token == 257_949_696
    pairs = s * (s + 1) // 2
    by_hand = 6 * s * per_token + 5 * 2 * 16 * pairs * (320 + 832)
    assert ref.train_flops_per_image(spec) == by_hand
    assert 2.29e9 < by_hand / s < 2.31e9
    cfg = ModelConfig(name="hybrid_decoder", config_file=CONFIG + ".json")
    data = DataConfig(dataset="tokens_synth", sequence_length=s)
    assert m.step_flops(cfg, data, 4) == 4 * by_hand


# --- scopes, kinds, the step's line, the counter ------------------------------

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
    assert not jax.tree.leaves(state.model_state)
    step = step_lib.make_train_step(model_def, cfg, optim, mesh)
    batch = (model_def.batch_shape(cfg, data, 2),
             jax.ShapeDtypeStruct((2,), jnp.int32))
    lowered = step.lower(state, *batch)
    said = capsys.readouterr().out
    assert f"attention=xla ({S} tokens), qk 24 v 12 " in said
    assert said.rstrip().endswith(
        "experts=ragged_dot, xla, softmax router, no bias")
    named = ["/" + n for n in set(re.findall(
        r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))]
    for scope in ("layer0", "layer2", "mla/q", "mla/kv_down", "mla/kv_norm",
                  "mla/kv_up", "mla/rotary", "mla/flash", "mla/out",
                  "moe/shared", "route", "experts", "head", "loss"):
        assert any(f"/{scope}/" in n or f"({scope})" in n
                   for n in named), scope
    entries = devprof.scope_map(lowered.compile()).values()
    kinds = {e.kind for e in entries}
    assert {"latent_attention", "shared_expert", "route", "expert", "mlp",
            "norm", "embed", "optimizer"} <= kinds
    parts = {e.part for e in entries if e.kind == "latent_attention"}
    assert {"q", "kv_down", "kv_norm", "kv_up", "rotary", "flash",
            "out"} <= parts
    assert {"forward", "recompute", "backward"} <= {
        e.pass_ for e in entries if e.kind == "shared_expert"}


def test_the_cli_trains_it_and_the_records_carry_the_counter(small, tmp_path):
    """``python cifar10cnn.py --model hybrid_decoder --model_config_file
    ...`` through ``Trainer.fit`` on the resident K-step dispatch: the
    loss falls, and each ``train`` record and the registry carry
    ``moe_aux_loss`` beside the experts' three counters; the stream lints
    strict."""
    from dml_cnn_cifar10_tpu.cli.main import main
    from dml_cnn_cifar10_tpu.utils import metrics_registry
    from tools import check_jsonl_schema
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
    lines = out.read_text().splitlines()
    records = [json.loads(line) for line in lines]
    train = [r for r in records if r.get("kind") == "train"]
    assert len(train) == 4 and train[-1]["loss"] < train[0]["loss"]
    for r in train:
        assert 0.005 < r["moe_aux_loss"] < 0.02
        assert 0.0 < r["moe_rows_here_frac"] < 1.0
    reg = metrics_registry.default_registry()
    assert next(iter(reg.get("dml_moe_aux_loss").values().values())) \
        == pytest.approx(train[-1]["moe_aux_loss"])
    assert check_jsonl_schema.check_lines(lines, str(out), strict=True) == []


# --- what a file may not say -------------------------------------------------

@pytest.mark.parametrize("over,said", [
    ({"q_lora_rank": 1536}, "q_lora_rank 1536 is not built"),
    ({"moe_layer_freq": 2}, "moe_layer_freq 2 is not built"),
    ({"topk_method": "group_limited_greedy"},
     "topk_method 'group_limited_greedy' is not built"),
    ({"n_group": 8}, "n_group 8 is not built"),
    ({"seq_aux": False}, "only the sequence-wise balance loss"),
    ({"kv_lora_rank": None}, "a latent_attention layer needs kv_lora_rank"),
])
def test_sizes_that_cannot_be_run_are_refused(tmp_path, over, said):
    path = tmp_path / "sizes.json"
    path.write_text(json.dumps({**SMALL, **over}))
    cfg = ModelConfig(name="hybrid_decoder", config_file=str(path))
    with pytest.raises((ValueError, NotImplementedError)) as e:
        m.sizes(cfg)
    assert said in str(e.value) and str(path) in str(e.value)

"""The decoder whose layers differ by a list (``models/hybrid_decoder.py``)
at a small size on the CPU: against the plain reference of its benchmark
configuration, one chip's share of an expert layer that drops nothing, the
gated short convolution, grouped key/value heads, the counts from shapes,
and the name scopes and counters of its step."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmark.lib import cells, datagen, reference
from dml_cnn_cifar10_tpu.config import (DataConfig, ModelConfig, OptimConfig,
                                        ParallelConfig)
from dml_cnn_cifar10_tpu.models import hybrid_decoder as m
from dml_cnn_cifar10_tpu.models.registry import get_model
from dml_cnn_cifar10_tpu.ops import attention as attention_lib
from dml_cnn_cifar10_tpu.ops import flash_attention as fa
from dml_cnn_cifar10_tpu.ops import moe
from dml_cnn_cifar10_tpu.ops import sum_rows
from dml_cnn_cifar10_tpu.ops.layers import (gated_short_conv, grouped_matmul,
                                            mixed_matmul)
from dml_cnn_cifar10_tpu.parallel import mesh as mesh_lib
from dml_cnn_cifar10_tpu.parallel import step as step_lib
from dml_cnn_cifar10_tpu.utils import devprof

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmark", "configs", "lfm2_8b_a1b_l5_e8")
S, VOCAB = 32, m.SMALL["vocab_size"]
CFG = ModelConfig(name="hybrid_decoder", compute_dtype="float32")
SPEC = dict(m.SMALL, head_dim=16, sequence_length=S)
NM = reference.Numerics("float32")


@pytest.fixture(scope="module")
def ref():
    return cells.load_module(CONFIG + ".py")


@pytest.fixture(scope="module")
def params(ref):
    """Seeded random weights as the benchmark makes them, every leaf away
    from its initial 1 or 0."""
    shapes = jax.eval_shape(
        lambda: m.init_params(jax.random.key(0), CFG, DataConfig()))
    return datagen.make_params(7, shapes, fan_in=ref.fan_in)


@pytest.fixture(scope="module")
def rows():
    return jax.random.randint(jax.random.key(1), (4, S + 1), 0, VOCAB)


def _leaves(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(p), x) for p, x in flat]


def _state(params, seed=11):
    """A state whose biases are away from their initial zero."""
    state = m.init_state(params, CFG)
    keys = iter(jax.random.split(jax.random.key(seed), 8))
    return jax.tree.map(
        lambda b: 0.05 * jax.random.normal(next(keys), b.shape), state)


def _value_and_grads(params, rows, cfg=CFG, state=None):
    """``((loss, stats, new state), gradients)``."""
    def value_and_aux(p):
        value, stats, new_state = m.loss(p, rows, cfg, model_state=state)
        return value, (stats, new_state)

    with jax.default_matmul_precision("highest"):
        (value, (stats, new_state)), grads = jax.value_and_grad(
            value_and_aux, has_aux=True)(params)
    return (value, stats, new_state), grads


def test_loss_and_every_gradient_leaf_equal_the_references(ref, params,
                                                           rows):
    """float32 on both sides at the highest matmul precision; what is left
    is the order of float32 sums (grouped products over sorted rows against
    masked dense ones, a loss in blocks with its own backward rule). The
    tied embedding's gradient is the gather's plus the head's on both
    sides. The experts' bias, a buffer, is away from zero here, and both
    sides move each expert's by the rate toward an even load."""
    ref_loss = ref.make_loss(SPEC)
    state = _state(params)
    (mine, stats, new_state), g_mine = _value_and_grads(params, rows,
                                                        state=state)
    with jax.default_matmul_precision("highest"):
        (theirs, ref_state), g_theirs = jax.value_and_grad(
            lambda p: ref_loss(NM, p, state, (rows[:, :-1], rows[:, 1:])),
            has_aux=True)(params)
    assert float(mine) == pytest.approx(float(theirs), rel=2e-6)
    for new, theirs_, old in zip(new_state["layers"], ref_state["layers"],
                                 state["layers"]):
        assert set(new) == set(old)
        if old:
            np.testing.assert_array_equal(new["expert_bias"],
                                          theirs_["expert_bias"])
            moved = np.abs(np.asarray(new["expert_bias"]
                                      - old["expert_bias"]))
            np.testing.assert_allclose(moved[moved > 0], 0.001, rtol=1e-3)
            assert (moved > 0).sum() >= 6     # of the router's 8
    assert 0.0 <= float(stats["accuracy"]) <= 1.0
    assert 0.0 < float(stats["moe_rows_here_frac"]) < 1.0
    assert float(stats["moe_load_max_over_mean"]) >= 1.0
    for (name, a), (_, b) in zip(_leaves(g_mine), _leaves(g_theirs)):
        assert float(jnp.max(jnp.abs(a - b))) \
            <= 2e-5 * float(jnp.max(jnp.abs(b))), name
        assert float(jnp.max(jnp.abs(b))) > 0, name
    # the head is the embedding: a head of its own would leave the rows of
    # ids that no input holds without a gradient
    unseen = np.setdiff1d(np.arange(VOCAB), np.asarray(rows[:, :-1]))
    assert unseen.size and float(jnp.min(jnp.max(jnp.abs(
        g_mine["embed"][unseen]), -1))) > 0


@pytest.mark.parametrize("change", ["remat", "groups_and_blocks"])
def test_recomputation_and_blocks_change_no_gradient(params, rows, change,
                                                     monkeypatch):
    """A sublayer recomputed in the backward pass, the sequences taken a
    group at a time and the experts' rows a block at a time are memory
    only."""
    (want, _, _), g_want = _value_and_grads(params, rows)
    cfg = CFG
    if change == "remat":
        cfg = ModelConfig(name="hybrid_decoder", compute_dtype="float32",
                          remat=True)
    else:
        monkeypatch.setattr(m, "OP_CHUNK_TOKENS", 2 * S)
        monkeypatch.setattr(m, "EXPERT_BLOCK_ROWS", 24)
        monkeypatch.setattr(m, "ROW_TILE", 8)
        assert m.expert_block_rows(4 * S * 2, 4 * S) == 32    # 8 blocks
    (got, _, _), g_got = _value_and_grads(params, rows, cfg)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for (name, a), (_, b) in zip(_leaves(g_got), _leaves(g_want)):
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=2e-6 * float(jnp.max(jnp.abs(b))) + 1e-12,
            err_msg=name)


# --- one chip's share of an expert layer -------------------------------------

T, D, H, E_ALL, K = 40, 16, 12, 8, 3


@pytest.fixture(scope="module")
def expert_layer():
    ks = jax.random.split(jax.random.key(2), 6)
    return jax.random.normal(ks[0], (T, D)), {
        "router": jax.random.normal(ks[1], (D, E_ALL)) / 4,
        "bias": 0.3 * jax.random.normal(ks[2], (E_ALL,)),
        "w1": jax.random.normal(ks[3], (E_ALL, D, H)) / 4,
        "w3": jax.random.normal(ks[4], (E_ALL, D, H)) / 4,
        "w2": jax.random.normal(ks[5], (E_ALL, H, D)) / 4}


def _share(x, p, first, count, block_rows=None):
    held = {"router": p["router"],
            **{k: p[k][first:first + count] for k in ("w1", "w3", "w2")}}
    return moe.routed_experts(x, held, first_expert=first, top_k=K,
                              dtype=jnp.float32, bias=p["bias"],
                              block_rows=block_rows)


def _uncut(ref, x, p):
    """The reference's layer with all of the router's experts: what they
    add to each token, and each expert's load."""
    spec = {**SPEC, "num_experts": E_ALL, "router_num_experts": E_ALL,
            "expert_first_id": 0, "num_experts_per_tok": K}
    return ref.make_layers(spec)["experts"](NM, x, p, p["bias"])


@pytest.mark.parametrize("block_rows", [None, 32, 8])
def test_the_four_shares_add_up_to_the_uncut_reference_layer(
        ref, expert_layer, block_rows):
    """Experts 0-1, 2-3, 4-5 and 6-7 of a router of 8, each share told
    which it holds, against the reference's layer with all 8: values, and
    the gradients of the input, the router and every expert."""
    x, p = expert_layer

    def shares(x, p):
        return sum(_share(x, p, f, 2, block_rows)[0] for f in (0, 2, 4, 6))

    with jax.default_matmul_precision("highest"):
        want, load = _uncut(ref, x, p)
        np.testing.assert_allclose(shares(x, p), want, rtol=1e-4, atol=1e-5)
        g = jax.random.normal(jax.random.key(3), want.shape)
        got = jax.grad(lambda x, p: jnp.sum(g * shares(x, p)), (0, 1))(x, p)
        wanted = jax.grad(lambda x, p: jnp.sum(g * _uncut(ref, x, p)[0]),
                          (0, 1))(x, p)
    for (name, a), (_, b) in zip(_leaves(got), _leaves(wanted)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)
        assert ("bias" in name) == (not np.asarray(b).any()), name
    stats = [_share(x, p, f, 2)[1] for f in (0, 2, 4, 6)]
    assert sum(float(s["rows_here_frac"]) for s in stats) \
        == pytest.approx(1.0, abs=1e-6)
    # every share counts the slots of all of the router's experts alike
    for s in stats:
        np.testing.assert_array_equal(s["expert_load"], load)
    assert int(jnp.sum(load)) == T * K


@pytest.mark.parametrize("block_rows", [None, 16])
def test_routing_so_skewed_that_every_token_chooses_the_same_experts(
        ref, expert_layer, block_rows):
    """A bias that sends every token to experts 0, 1 and 2: the share that
    holds 0-1 gets two of every token's three slots (a capacity sized for
    uniform routing would drop three in four), loses no row, and the share
    that holds none of them returns zero."""
    x, p = expert_layer
    p = {**p, "bias": jnp.array([9., 9., 9., 0, 0, 0, 0, 0])}
    with jax.default_matmul_precision("highest"):
        first, stats = _share(x, p, 0, 2, block_rows)
        second, _ = _share(x, p, 2, 2, block_rows)
        none, none_stats = _share(x, p, 4, 4, block_rows)
        want, _ = _uncut(ref, x, p)
    assert float(stats["rows_here_frac"]) == pytest.approx(2 / 3)
    assert float(stats["load_max_over_mean"]) == pytest.approx(1.0)
    np.testing.assert_allclose(first + second, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.min(jnp.max(jnp.abs(first), -1))) > 0   # every token
    np.testing.assert_array_equal(none, jnp.zeros_like(none))
    assert float(none_stats["rows_here_frac"]) == 0.0


@pytest.mark.parametrize("block_rows,rounds", [(40, 2), (16, 3)])
def test_a_load_that_exceeds_the_buffer_takes_further_rounds(
        ref, expert_layer, block_rows, rounds):
    """The same skew with the rows a block at a time: the buffer holds the
    30 rows of an even load (one block of 40, two of 16) and 80 come, so
    the share that holds experts 0-1 fills it twice or three times and
    says so; value and every gradient are the uncut reference layer's."""
    x, p = expert_layer
    p = {**p, "bias": jnp.array([9., 9., 9., 0, 0, 0, 0, 0])}

    def shares(x, p):
        return sum(_share(x, p, f, 2, block_rows)[0] for f in (0, 2, 4, 6))

    with jax.default_matmul_precision("highest"):
        stats = [_share(x, p, f, 2, block_rows)[1] for f in (0, 2, 4, 6)]
        want, _ = _uncut(ref, x, p)
        np.testing.assert_allclose(shares(x, p), want, rtol=1e-4, atol=1e-5)
        g = jax.random.normal(jax.random.key(3), want.shape)
        got = jax.grad(lambda x, p: jnp.sum(g * shares(x, p)), (0, 1))(x, p)
        wanted = jax.grad(lambda x, p: jnp.sum(g * _uncut(ref, x, p)[0]),
                          (0, 1))(x, p)
    assert [float(s["buffer_rounds"]) for s in stats] \
        == [rounds, {40: 1, 16: 2}[block_rows], 1, 1]
    assert float(stats[0]["rows_here_frac"]) == pytest.approx(2 / 3)
    for (name, a), (_, b) in zip(_leaves(got), _leaves(wanted)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)


# --- each token's sum of the rows it has in a buffer -------------------------

def _rows_of_tokens(tokens, k, seed):
    """``pos [tokens, k]``, a permutation of the slots in which token ``t``
    has ``t % (k + 1)`` of its choices among the first ``here`` rows, and
    ``here``."""
    rng = np.random.default_rng(seed)
    mine = np.zeros((tokens, k), bool)
    for t in range(tokens):
        mine[t, rng.permutation(k)[:t % (k + 1)]] = True
    here = int(mine.sum())
    pos = np.empty((tokens, k), np.int32)
    pos[mine] = rng.permutation(here)
    pos[~mine] = here + rng.permutation(tokens * k - here)
    return jnp.asarray(pos), here


@pytest.mark.parametrize("k", [4, 6, 3])
@pytest.mark.parametrize("width", [256, 2048, 2304])
@pytest.mark.parametrize("cut", ["whole", "cuts_a_tokens_rows", "empty",
                                 "second_round"])
def test_each_token_sums_the_rows_it_has_in_the_buffer(width, cut, k):
    """The kernel in the Pallas interpreter, bit for bit the XLA expression
    (the same float32 terms in the same order), and both
    ``jax.ops.segment_sum`` of the rows by their tokens: tokens with 0 to
    ``k`` rows here, a range that cuts a token's rows, an empty one, and
    one that starts past the buffer's first row; at 6 and 3 choices a
    token the kernel pads its slots to 8 and 4."""
    tokens = 40
    pos, here = _rows_of_tokens(tokens, k, seed=width)
    lo, hi = {"whole": (0, here), "cuts_a_tokens_rows": (0, here - 9),
              "empty": (0, 0), "second_round": (32, here)}[cut]
    room = here - lo
    buffer = jax.random.normal(jax.random.key(4), (room, width))
    expression = sum_rows.sum_rows_xla(buffer, pos, lo, hi)
    kernel = sum_rows.sum_rows_pallas(
        buffer.reshape(room, width // 128, 128),
        sum_rows._in_range(pos, lo, hi), True)
    np.testing.assert_array_equal(kernel, expression)
    flat = np.asarray(pos).reshape(-1)
    slots = np.nonzero((flat >= lo) & (flat < hi))[0]
    want = jax.ops.segment_sum(buffer[flat[slots] - lo], slots // k,
                               num_segments=tokens)
    np.testing.assert_allclose(expression, want, rtol=1e-6, atol=1e-6)
    rows_of = np.bincount(slots // k, minlength=tokens)
    if cut == "whole":
        assert set(rows_of) == set(range(k + 1))
    else:       # some token has rows on both sides of the cut
        assert np.any((rows_of > 0) | (cut == "empty"))
        assert np.any(rows_of < np.arange(tokens) % (k + 1))
    np.testing.assert_array_equal(
        np.asarray(expression)[rows_of == 0], 0.0)


def test_tiles_of_padded_slots_sum_as_the_expression(monkeypatch):
    """Where a tile holds fewer tokens than there are, at 6 choices a token
    (8 slots, so a tile of 128 tokens is one block of 1,024 scalars): the
    kernel in the interpreter over two tiles, bit for bit the XLA
    expression."""
    tokens, k, width = 256, 6, 256
    monkeypatch.setattr(sum_rows, "_ROWS_BYTES", 128 * k * width * 4)
    assert sum_rows._tile(tokens, k, width) == 128
    pos, here = _rows_of_tokens(tokens, k, seed=6)
    buffer = jax.random.normal(jax.random.key(6), (here, width))
    rel = sum_rows._in_range(pos, 0, here)
    kernel = sum_rows.sum_rows_pallas(
        buffer.reshape(here, width // 128, 128), rel, True)
    np.testing.assert_array_equal(
        kernel, sum_rows.sum_rows_xla(buffer, pos, 0, here))


def test_who_takes_the_kernel(monkeypatch):
    """A TPU backend, one device, a width of whole lanes; the buffer's
    shape carries the choice to the sum."""
    from dml_cnn_cifar10_tpu.ops import kernel_paths
    from dml_cnn_cifar10_tpu.utils import platform as platform_lib
    four = mesh_lib.build_mesh(ParallelConfig(), devices=jax.devices()[:4])
    one = mesh_lib.build_mesh(ParallelConfig(), devices=jax.devices()[:1])
    with kernel_paths.recording() as rec:
        assert sum_rows.row_shape(64, 4, 2048) == (2048,)
        assert rec == {"experts": "xla"}
        monkeypatch.setattr(platform_lib, "on_tpu", lambda: True)
        assert sum_rows.row_shape(64, 4, 2048, one) == (16, 128)
        assert rec == {"experts": "pallas sum-by-token"}
        assert sum_rows.row_shape(64, 4, 2048, four) == (2048,)
        assert rec == {"experts": "xla (mesh)"}
        # 2,304 = 18 lanes: a row of two tiles and a quarter
        assert sum_rows.row_shape(64, 4, 2304, one) == (18, 128)
        assert sum_rows.row_shape(64, 4, 2048 + 64) == (2048 + 64,)
        assert sum_rows.row_shape(60, 4, 2048) == (2048,)
        # 8,200 tokens of 3 choices: no tile of them is a block of scalars
        assert sum_rows._tile(8200, 3, 2048) is None
        assert sum_rows.row_shape(8200, 3, 2048) == (2048,)
        assert sum_rows._tile(32768, 4, 2048) == 256
        # a tile's slots are a block of 1,024 scalars, whatever k is
        assert sum_rows._tile(32768, 8, 2304) == 128
        assert rec == {"experts": "xla"}
        # 6 choices a token take 8 slots: 2,048 slots a tile of 256, whose
        # rows of 6 choices are the budget's 12 MiB to the byte
        assert sum_rows._tile(32768, 6, 2048) == 256
        assert sum_rows.row_shape(32768, 6, 2048, one) == (16, 128)
        assert rec == {"experts": "pallas sum-by-token (6 of 8 slots)"}
        assert sum_rows.row_shape(32768, 4, 2048, one) == (16, 128)
        assert rec == {"experts": "pallas sum-by-token"}


def test_the_bias_changes_the_choice_and_not_the_weights(expert_layer):
    x, p = expert_layer
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(x @ p["router"])
    plain, w_plain = moe.route_top_k(x, p["router"], None, K)
    biased, w_biased = moe.route_top_k(x, p["router"], p["bias"], K)
    moved = np.any(np.sort(plain, -1) != np.sort(biased, -1), -1)
    assert moved.any() and not moved.all()
    for chosen, w in ((plain, w_plain), (biased, w_biased)):
        at = jnp.take_along_axis(s, chosen, -1)
        np.testing.assert_allclose(
            w, at / (jnp.sum(at, -1, keepdims=True) + 1e-6), rtol=1e-5)
    # the choice is the K largest of score + bias
    np.testing.assert_array_equal(
        np.sort(biased, -1),
        np.sort(np.argsort(-(s + p["bias"]), -1)[:, :K], -1))
    # and no gradient reaches the bias
    g = jax.grad(lambda b: jnp.sum(moe.route_top_k(
        x, p["router"], b, K)[1] ** 2))(p["bias"])
    np.testing.assert_array_equal(g, jnp.zeros_like(g))


def test_the_bias_moves_toward_an_even_load():
    """Up by the rate where an expert got fewer slots than the mean, down
    where it got more, still where it got the mean; repeated on a fixed
    batch it evens the load out."""
    bias = jnp.array([0.5, -0.5, 0.0, 0.1])
    np.testing.assert_allclose(
        moe.balanced_bias(bias, jnp.array([10, 2, 6, 6]), 0.01),
        [0.49, -0.49, 0.0, 0.1], rtol=1e-6)
    ks = jax.random.split(jax.random.key(12), 2)
    x = jax.random.normal(ks[0], (512, 16)) + 0.5      # a common part
    router = jax.random.normal(ks[1], (16, 8)) / 4

    def load(bias):
        chosen, _ = moe.route_top_k(x, router, bias, 2)
        return jnp.sum(chosen.reshape(-1)[:, None] == jnp.arange(8), 0)

    bias = jnp.zeros(8)
    before = load(bias)
    for _ in range(200):
        bias = moe.balanced_bias(bias, load(bias), 0.002)
    after = load(bias)
    assert int(before.max()) > 1.5 * 128 and int(after.max()) < 1.1 * 128
    assert int(after.min()) > 0.9 * 128


def test_rows_past_the_groups_cost_nothing_and_read_as_nothing():
    """``grouped_matmul`` against one dense product a group; rows past the
    groups' sum come back zero on this backend and take no gradient."""
    x = jax.random.normal(jax.random.key(4), (12, 5))
    w = jax.random.normal(jax.random.key(5), (3, 5, 4))
    sizes = jnp.array([3, 0, 6])
    expert = jnp.array([0] * 3 + [2] * 6 + [0] * 3)
    live = (jnp.arange(12) < 9)[:, None]

    def dense(x, w):
        return jnp.einsum("mk,mkn->mn", x, w[expert]) * live

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(grouped_matmul(x, w, sizes, jnp.float32),
                                   dense(x, w), rtol=1e-5, atol=1e-6)
        got = jax.grad(lambda x, w: jnp.sum(jnp.sin(grouped_matmul(
            x, w, sizes, jnp.float32))[:9]), (0, 1))(x, w)
        want = jax.grad(lambda x, w: jnp.sum(jnp.sin(dense(x, w))[:9]),
                        (0, 1))(x, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    assert not np.asarray(got[0][9:]).any() and not np.asarray(
        got[1][1]).any()



#: Sizes a group in a block of 384 rows, in row tiles of 128: empty
#: groups, a group over the first tile's end (rows 100-299 straddle tiles
#: 0-2), and 34 rows past the groups' sum; then every row taken.
_BLOCKS = {"empty_and_past": [100, 0, 200, 0, 50, 0, 0, 0],
           "whole": [0, 128, 0, 0, 130, 0, 126, 0]}


@pytest.mark.parametrize("widths", [(1024, 896), (2304, 896)],
                         ids=["lfm2_8to7", "mellum2_18to7"])
@pytest.mark.parametrize("block", sorted(_BLOCKS))
def test_the_grouped_kernels_are_the_grouped_product(widths, block,
                                                     monkeypatch):
    """megablox's ``gmm`` / ``tgmm`` in the Pallas interpreter at the tiles
    the rule chooses for the chip, forward and both gradients, against one
    dense product a group and against ``lax.ragged_dot``, at the two
    cells' ratios of widths in whole lanes (2,048 : 1,792 = 8 : 7 and
    2,304 : 896 = 18 : 7): rows past the groups' sum come back zero and
    take a zero gradient, an empty group's weights none."""
    from dml_cnn_cifar10_tpu.ops import layers
    from dml_cnn_cifar10_tpu.utils import platform as platform_lib
    k, n = widths
    sizes = jnp.array(_BLOCKS[block], jnp.int32)
    m, live = 384, int(sizes.sum())
    monkeypatch.setattr(platform_lib, "on_tpu", lambda: True)
    tiles = layers.grouped_tiles(m, k, n, 8, jnp.bfloat16)
    monkeypatch.undo()
    assert tiles.fwd[0] == 128
    x = jax.random.normal(jax.random.key(4), (m, k))
    w = jax.random.normal(jax.random.key(5), (8, k, n)) / k ** 0.5
    g = jax.random.normal(jax.random.key(6), (m, n))
    group = jnp.repeat(jnp.arange(8), sizes, total_repeat_length=m)
    low = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)

    def dense(x, w):
        with jax.default_matmul_precision("highest"):
            y = jnp.einsum("mk,mkn->mn", low(x), low(w)[group])
        return jnp.where((jnp.arange(m) < live)[:, None], y, 0.0)

    def kernels(x, w):
        return layers.grouped_matmul_tiled(x, w, sizes, jnp.bfloat16, tiles,
                                           True)

    def ragged(x, w):
        return grouped_matmul(x, w, sizes, jnp.bfloat16)

    def both(f):
        y, vjp = jax.vjp(f, x, w)
        return (y, *vjp(g))

    got, xla = both(kernels), both(ragged)
    y_want = dense(x, w)
    np.testing.assert_allclose(got[0], y_want, rtol=1e-5, atol=1e-5)
    with jax.default_matmul_precision("highest"):
        dx_want = jnp.einsum("mn,mkn->mk", low(g), low(w)[group]) \
            * (jnp.arange(m) < live)[:, None]
        dw_want = jnp.zeros_like(w).at[group[:live]].add(jnp.einsum(
            "mk,mn->mkn", low(x)[:live], low(g)[:live]))
    np.testing.assert_allclose(got[1], dx_want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[2], dw_want, rtol=1e-5, atol=1e-4)
    for a, b in zip(got, xla):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-4)
    assert not np.asarray(got[0][live:]).any()
    assert not np.asarray(got[1][live:]).any()
    assert not np.asarray(got[2][np.asarray(sizes) == 0]).any()


def test_who_takes_the_grouped_kernels_and_at_which_tiles(monkeypatch):
    """The path and the tiles from what the chooser can see: a TPU, one
    device, whole lanes, whole row tiles and bfloat16; the tiles at the
    two cells' blocks of 8,704 rows, and their blocks within the scoped
    vector memory by the docstring's arithmetic."""
    from dml_cnn_cifar10_tpu.ops import layers
    from dml_cnn_cifar10_tpu.utils import platform as platform_lib
    one = mesh_lib.build_mesh(ParallelConfig(), devices=jax.devices()[:1])
    four = mesh_lib.build_mesh(ParallelConfig(), devices=jax.devices()[:4])
    bf16 = jnp.bfloat16
    assert layers.grouped_tiles(8704, 2048, 1792, 8, bf16) is None   # CPU
    monkeypatch.setattr(platform_lib, "on_tpu", lambda: True)
    assert layers.grouped_tiles(8704, 2048, 1792, 8, bf16, one) is not None
    assert layers.grouped_tiles(8704, 2048, 1792, 8, bf16, four) is None
    assert layers.grouped_tiles(8704, 2048, 1792 + 64, 8, bf16) is None
    assert layers.grouped_tiles(8704, 2048 + 64, 1792, 8, bf16) is None
    assert layers.grouped_tiles(8704 + 64, 2048, 1792, 8, bf16) is None
    assert layers.grouped_tiles(8704, 2048, 1792, 8, jnp.float32) is None
    assert layers.grouped_tiles(8704 + 256, 2048, 1792, 8, bf16).fwd[0] \
        == 256

    def vmem(tiles, transposed):
        tm, tk, tn = tiles
        fetched = tm * tk + (tm if transposed else tk) * tn
        return 2 * fetched * 2 + 3 * 4 * (tk if transposed else tm) * tn

    want = {(2048, 1792): ((512, 1024, 896), (512, 896, 1024),
                           (512, 512, 896)),
            (1792, 2048): ((512, 896, 1024), (512, 1024, 896),
                           (512, 896, 512)),
            (2304, 896): ((512, 1152, 896), (512, 896, 1152),
                          (512, 768, 896)),
            (896, 2304): ((512, 896, 1152), (512, 1152, 896),
                          (512, 896, 768))}
    for (k, n), tiles in want.items():
        got = layers.grouped_tiles(8704, k, n, 8, bf16)
        assert tuple(got) == tiles, (k, n, got)
        for (tm, tk, tn), (kk, nn) in zip(got, ((k, n), (n, k), (k, n))):
            assert 8704 % tm == 0 and kk % tk == 0 and nn % tn == 0
        assert max(vmem(got.fwd, False), vmem(got.dx, False),
                   vmem(got.dw, True)) <= layers._GMM_VMEM < 16 << 20


@pytest.mark.parametrize("chip, want", [
    (True, "pallas gmm (512,1024,896) (512,896,1024)"),
    (False, "ragged_dot")], ids=["tpu", "cpu"])
def test_the_grouped_product_notes_its_own_path(chip, want, monkeypatch):
    """The chooser notes the path it took for the step's line, each
    distinct forward tile once, in the order the products were traced:
    the gated MLP's two products into the hidden width, then the one out
    of it."""
    from dml_cnn_cifar10_tpu.ops import kernel_paths, layers
    from dml_cnn_cifar10_tpu.utils import platform as platform_lib
    monkeypatch.setattr(platform_lib, "on_tpu", lambda: chip)
    sizes = jax.ShapeDtypeStruct((8,), jnp.int32)
    with kernel_paths.recording() as rec:
        for k, n in ((2048, 1792), (2048, 1792), (1792, 2048)):
            jax.eval_shape(
                lambda x, w, s: layers.grouped_matmul(x, w, s, jnp.bfloat16),
                jax.ShapeDtypeStruct((8704, k), jnp.float32),
                jax.ShapeDtypeStruct((8, k, n), jnp.float32), sizes)
    assert rec == {"grouped": want}

# --- the two operators -------------------------------------------------------

def test_the_short_convolution_is_causal_and_a_plain_depthwise_one():
    b, s, c, taps = 2, 24, 6, 3
    bcx = jax.random.normal(jax.random.key(6), (b, s, 3 * c))
    w = jax.random.normal(jax.random.key(7), (c, taps))
    got = gated_short_conv(bcx, w)
    gate_b, gate_c, x = jnp.split(bcx, 3, -1)
    plain = lax.conv_general_dilated(
        gate_b * x, w.T[:, None, :], window_strides=(1,),
        padding=[(taps - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=c, precision=lax.Precision.HIGHEST)
    np.testing.assert_allclose(got, gate_c * plain, rtol=1e-5, atol=1e-6)
    # a change at t moves nothing before t, and moves t
    t = 10
    changed = gated_short_conv(bcx.at[:, t].add(1.0), w)
    np.testing.assert_array_equal(changed[:, :t], got[:, :t])
    assert float(jnp.min(jnp.max(jnp.abs(changed[:, t] - got[:, t]), -1))) > 0
    # the first output sees its own input alone: the last tap
    np.testing.assert_allclose(
        got[:, 0], gate_c[:, 0] * (gate_b * x)[:, 0] * w[:, -1], rtol=1e-5)


def _attention(a, p, heads, kv_heads, use_pallas=False):
    return attention_lib.causal_self_attention(
        a, p, heads=heads, kv_heads=kv_heads, head_dim=16, rope=1e6,
        low=jnp.float32, use_pallas=use_pallas, norm_eps=1e-5)


@pytest.mark.parametrize("s,use_pallas", [(24, False), (128, True)])
def test_grouped_heads_equal_repeated_heads(s, use_pallas):
    """4 query heads over 2 key/value heads against 4 over 4 whose key and
    value matrices hold each grouped head's columns twice: the same output,
    and the grouped matrices' gradient is the sum over each group (at 128
    tokens through the flash kernels in the interpreter, head size 16)."""
    d, heads, kv, dh = 32, 4, 2, 16
    ks = jax.random.split(jax.random.key(8), 8)
    a = jax.random.normal(ks[0], (2, s, d))
    p = {"wq": jax.random.normal(ks[1], (d, heads * dh)) / 6,
         "wk": jax.random.normal(ks[2], (d, kv * dh)) / 6,
         "wv": jax.random.normal(ks[3], (d, kv * dh)) / 6,
         "wo": jax.random.normal(ks[4], (heads * dh, d)) / 8,
         "q_norm": {"scale": 1 + 0.1 * jax.random.normal(ks[5], (dh,))},
         "k_norm": {"scale": 1 + 0.1 * jax.random.normal(ks[6], (dh,))}}

    def repeated(w):
        return jnp.repeat(w.reshape(d, kv, dh), heads // kv, 1).reshape(
            d, heads * dh)

    g = jax.random.normal(ks[7], (2, s, d))
    with jax.default_matmul_precision("highest"):
        grouped = _attention(a, p, heads, kv, use_pallas)
        full = _attention(a, {**p, "wk": repeated(p["wk"]),
                              "wv": repeated(p["wv"])}, heads, heads,
                          use_pallas)
        np.testing.assert_allclose(grouped, full, rtol=1e-4, atol=1e-5)
        dk = jax.grad(lambda wk: jnp.sum(g * _attention(
            a, {**p, "wk": wk}, heads, kv, use_pallas)))(p["wk"])
        dk_full = jax.grad(lambda wk: jnp.sum(g * _attention(
            a, {**p, "wk": wk, "wv": repeated(p["wv"])}, heads, heads,
            use_pallas)))(repeated(p["wk"]))
    summed = dk_full.reshape(d, kv, heads // kv, dh).sum(2).reshape(d,
                                                                    kv * dh)
    np.testing.assert_allclose(dk, summed, rtol=1e-3, atol=1e-5)


def test_flash_attention_at_head_size_64_causal():
    """The Pallas kernels in the interpreter against the plain softmax at
    the published head size, 8 heads of which each pair holds one repeated
    key/value head: values and all three gradients."""
    b, s, h, d = 1, 256, 8, 64
    q, k, v, g = (jax.random.normal(key, (b, s, h, d)) / 2
                  for key in jax.random.split(jax.random.key(9), 4))
    k, v = (jnp.repeat(t[:, :, ::2], 2, axis=2) for t in (k, v))

    def flash(q, k, v):
        return jnp.sum(g * fa.flash_attention(q, k, v, causal=True,
                                              interpret=True))

    def plain(q, k, v):
        return jnp.sum(g * attention_lib.xla_attention(q, k, v, causal=True))

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            fa.flash_attention(q, k, v, causal=True, interpret=True),
            attention_lib.xla_attention(q, k, v, causal=True),
            rtol=1e-4, atol=1e-5)
        dwant = jax.grad(plain, (0, 1, 2))(q, k, v)
        dgot = jax.grad(flash, (0, 1, 2))(q, k, v)
    for a, b_ in zip(dgot, dwant):
        np.testing.assert_allclose(a, b_, rtol=1e-4, atol=1e-5)


# --- counts from shapes ------------------------------------------------------

def _published(**over) -> dict:
    with open(CONFIG + ".json") as f:
        spec = json.load(f)
    return {**spec, **over}


@pytest.mark.parametrize("whole,count", [(False, 507_820_288),
                                         (True, 8_339_930_560)])
def test_parameters_at_the_published_widths(ref, tmp_path, whole, count):
    """By ``jax.eval_shape``: nothing of that size is built. The file's
    five layers, 8 experts and quarter of the vocabulary, and the
    published keys (24 layers, 2 dense, 32 experts, 65,536 rows)."""
    spec = _published()
    if whole:
        spec = {**spec, **{k: v for k, v in spec["published"].items()
                           if k != "parameters"}}
        assert spec["published"]["parameters"] == count
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


def test_the_count_of_operations_against_xlas(ref, tmp_path, monkeypatch):
    """The step written out (no group of sequences, the loss in one block,
    no kernel) as XLA's cost analysis counts it (nine products an expert
    layer, as the module counts: doubling the experts' width moves XLA's
    count by 9.1 products a layer, so the three that the experts'
    written-out backward loop forms again are not in it),
    with every expert of the router held, so that uniform routing's share
    is all of a token's slots and the grouped products' buffer holds
    exactly the rows counted. A grouped product costs what one dense
    product over its rows costs, and is counted as that: on the CPU
    ``lax.ragged_dot`` is lowered to one masked dense product a group,
    which is that backend's way and no part of the step's work. The
    module counts the products and nothing
    else, attention as the half square forward once and backward two and a
    half times, where the plain attention of this path multiplies the
    whole square in six products: the module's count with that difference
    put back is XLA's count less norms, softmax, rotary, SiLU, the filter
    and the routing, 0.97-1.0 of it at a hidden size of 128."""
    sz = {**m.SMALL, "hidden_size": 128, "head_dim": 32,
          "intermediate_size": 256, "moe_intermediate_size": 64,
          "num_experts": 8}
    path = tmp_path / "sizes.json"
    path.write_text(json.dumps(sz))
    cfg = ModelConfig(name="hybrid_decoder", compute_dtype="float32",
                      config_file=str(path))
    data = DataConfig(dataset="tokens_synth", sequence_length=S)
    batch = 4
    rows = jnp.zeros((batch, S + 1), jnp.int32)
    shapes = jax.eval_shape(
        lambda: m.init_params(jax.random.key(0), cfg, data))
    assert m.expert_block_rows(batch * S * 2, batch * S * 2) == batch * S * 2
    monkeypatch.setattr(
        moe, "grouped_matmul",
        lambda x, w, sizes, dtype, mesh: mixed_matmul(x, w[0], dtype))

    def grads(p, rows):
        return jax.grad(lambda p: m.loss(p, rows, cfg, loss_blocks=1)[0])(p)

    counted = jax.jit(grads).lower(shapes, rows).compile() \
        .cost_analysis()["flops"]
    a = sz["num_attention_heads"] * sz["head_dim"]
    mine = m.step_flops(cfg, data, batch)
    whole_square = batch * a * (12 * S * S - 7 * S * (S + 1))
    assert 0.97 <= (mine + whole_square) / counted <= 1.0
    # and the benchmark's module counts what the program counts
    assert ref.train_flops_per_image(dict(sz, sequence_length=S)) * batch \
        == mine


def test_operations_a_sequence_by_hand(ref):
    """1.31 GFLOP a token at the cell's sizes: 199,491,584 multiply-adds a
    token in products (one of a token's four slots on an expert held here,
    a layer) times 6, and attention's half square forward once and
    backward two and a half times."""
    spec = _published()
    s = spec["sequence_length"]
    conv = 2048 * 6144 + 2048 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    per_token = 4 * conv + attn + 3 * 2048 * 7168 \
        + 4 * (2048 * 32 + 3 * 2048 * 1792) + 2048 * 16384
    assert per_token == 199_491_584
    by_hand = 6 * s * per_token + 7 * 2 * 2048 * (s * (s + 1) // 2)
    assert ref.train_flops_per_image(spec) == by_hand
    assert 1.30e9 < by_hand / s < 1.32e9
    cfg = ModelConfig(name="hybrid_decoder", config_file=CONFIG + ".json")
    data = DataConfig(dataset="tokens_synth", sequence_length=s)
    assert m.step_flops(cfg, data, 4) == 4 * by_hand


# --- scopes, kinds, counters -------------------------------------------------

def test_the_lowered_step_holds_the_scopes_and_the_map_their_kinds():
    model_def = get_model("hybrid_decoder")
    data = DataConfig(dataset="tokens_synth", sequence_length=S)
    optim = OptimConfig(optimizer="adamw")
    cfg = ModelConfig(name="hybrid_decoder", remat=True)
    mesh = mesh_lib.build_mesh(ParallelConfig(), devices=jax.devices()[:1])
    state = jax.eval_shape(
        lambda k: step_lib.init_train_state(k, model_def, cfg, data, optim),
        jax.random.key(0))
    step = step_lib.make_train_step(model_def, cfg, optim, mesh)
    batch = (model_def.batch_shape(cfg, data, 2),
             jax.ShapeDtypeStruct((2,), jnp.int32))
    lowered = step.lower(state, *batch)
    import re
    named = ["/" + n for n in set(re.findall(
        r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))]
    # (a recomputed sublayer's scopes sit under `layerN/checkpoint`)
    for scope in ("embed", "layer0", "layer2", "op_norm", "short_conv/in",
                  "short_conv/gate_conv", "short_conv/out", "ffn_norm", "mlp",
                  "attn/qkv", "attn/qk_norm", "attn/rotary", "attn/flash",
                  "attn/out", "moe", "route", "dispatch", "experts",
                  "combine", "final_norm", "head", "loss", "optimizer",
                  "fwd_bwd"):
        assert any(f"/{scope}/" in n or f"({scope})" in n
                   for n in named), scope
    kinds = {e.kind for e in devprof.scope_map(lowered.compile()).values()}
    assert {"short_conv", "route", "expert", "attention", "mlp", "norm",
            "embed", "optimizer"} <= kinds
    for scope, kind in (("layer0/short_conv/in", "short_conv"),
                        ("layer2/short_conv/gate_conv", "short_conv"),
                        ("layer1/attn/qk_norm", "attention"),
                        ("layer1/attn/out", "attention"),
                        ("layer0/mlp", "mlp"), ("layer3/op_norm", "norm"),
                        ("layer1/moe/route", "route"),
                        ("layer1/moe/dispatch", "route"),
                        ("layer1/moe/combine", "route"),
                        ("layer1/moe/experts", "expert"),
                        ("layer1/moe/cond/branch_1_fun/experts", "expert"),
                        ("final_norm", "norm"), ("embed", "embed"),
                        ("head/loss", "dense"),
                        # the image models' `conv` does not catch it
                        ("conv1", "conv")):
        assert devprof.parse_op_name(
            f"jit(step)/fwd_bwd/{scope}/dot_general")[1] == kind, scope


def test_the_cli_trains_it_and_the_records_carry_the_counters(tmp_path):
    """``python cifar10cnn.py --model hybrid_decoder ...``: the flag
    parser, ``Trainer.fit``, the resident K-step dispatch with the
    on-device index stream, AdamW; the loss falls, and each ``train``
    record and the registry carry the experts' three counters."""
    from dml_cnn_cifar10_tpu.cli.main import main
    from dml_cnn_cifar10_tpu.utils import metrics_registry
    out = tmp_path / "m.jsonl"
    main(["--model", "hybrid_decoder", "--dataset", "tokens_synth",
          "--sequence_length", str(S), "--data_dir", str(tmp_path / "d"),
          "--log_dir", str(tmp_path / "l"), "--batch_size", "4",
          "--steps_per_dispatch", "2", "--total_steps", "24",
          "--output_every", "4", "--eval_every", "1000",
          "--checkpoint_every", "1000", "--optimizer", "adamw",
          "--learning_rate", "0.003", "--adam_b2", "0.95",
          "--weight_decay", "0.1", "--schedule", "constant", "--remat",
          "true", "--resident_data", "true", "--device_index_stream", "true",
          "--synthetic_train_records", "64", "--metrics_jsonl", str(out)])
    train = [r for r in map(json.loads, out.read_text().splitlines())
             if r.get("kind") == "train"]
    assert len(train) == 6 and train[-1]["loss"] < train[0]["loss"]
    for r in train:
        assert 0.0 < r["moe_rows_here_frac"] < 1.0
        assert r["moe_load_max_over_mean"] >= 1.0
        assert r["moe_buffer_rounds"] >= 1.0
    reg = metrics_registry.default_registry()
    for name in ("dml_moe_rows_here_frac", "dml_moe_load_max_over_mean",
                 "dml_moe_buffer_rounds"):
        assert list(reg.get(name).values().values()) \
            == [train[-1][name[len("dml_"):]]]


@pytest.mark.parametrize("argv,said", [
    (["--model", "hybrid_decoder"], "go together"),
    (["--model", "hybrid_decoder", "--dataset", "tokens_synth",
      "--mode", "export"], "trains and evaluates"),
])
def test_the_cli_refuses_what_a_token_model_cannot_do(argv, said):
    from dml_cnn_cifar10_tpu.cli.main import build_parser, config_from_args
    with pytest.raises(SystemExit) as e:
        config_from_args(build_parser().parse_args(argv))
    assert said in str(e.value)


def test_the_cli_takes_sizes_from_a_file(tmp_path, monkeypatch):
    from dml_cnn_cifar10_tpu.cli.main import build_parser, config_from_args
    cfg = config_from_args(build_parser().parse_args(
        ["--model", "hybrid_decoder", "--dataset", "tokens_synth",
         "--model_config_file", "benchmark/configs/lfm2_8b_a1b_l5_e8.json",
         "--sequence_length", "8192", "--optimizer", "adamw"]))
    assert cfg.data.tokens and cfg.data.sequence_length == 8192
    assert cfg.data.num_classes == cfg.model.num_classes == 16384
    monkeypatch.chdir(tmp_path)      # found from the repository's root
    sz = m.sizes(cfg.model)
    assert (sz["hidden_size"], sz["head_dim"], sz["num_experts"],
            sz["router_num_experts"], sz["expert_first_id"]) \
        == (2048, 64, 8, 32, 0)
    assert sz["layer_types"] == ["conv", "full_attention", "conv", "conv",
                                 "conv"]
    small = config_from_args(build_parser().parse_args(
        ["--model", "hybrid_decoder", "--dataset", "tokens_synth"]))
    assert small.data.num_classes == VOCAB


@pytest.mark.parametrize("over,said", [
    ({"layer_types": ["conv", "window", "conv"]}, "layer_types"),
    ({"expert_first_id": 6}, "not among the router's"),
    ({"conv_bias": True}, "conv_bias"),
    ({"num_key_value_heads": 3}, "do not divide")])
def test_sizes_that_cannot_be_run_are_refused(tmp_path, over, said):
    path = tmp_path / "sizes.json"
    path.write_text(json.dumps({**m.SMALL, **over}))
    with pytest.raises((ValueError, NotImplementedError), match=said):
        m.sizes(ModelConfig(name="hybrid_decoder", config_file=str(path)))

"""The looped decoder (``models/looped_decoder.py``) at a small size on
the CPU: against the plain reference of its benchmark configuration, the
loop's shared weights, the loss in blocks, the exit distribution, flash
attention at the published head size, the counts from shapes, and the
name scopes of its lowered step."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import cells, reference
from dml_cnn_cifar10_tpu.config import (DataConfig, ModelConfig, OptimConfig,
                                        ParallelConfig)
from dml_cnn_cifar10_tpu.models import looped_decoder as m
from dml_cnn_cifar10_tpu.models.registry import get_model
from dml_cnn_cifar10_tpu.ops import attention as attention_lib
from dml_cnn_cifar10_tpu.ops import flash_attention as fa
from dml_cnn_cifar10_tpu.ops.layers import mixed_matmul, rms_norm
from dml_cnn_cifar10_tpu.parallel import mesh as mesh_lib
from dml_cnn_cifar10_tpu.parallel import step as step_lib
from dml_cnn_cifar10_tpu.train import loss as loss_lib
from dml_cnn_cifar10_tpu.utils import devprof

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmark", "configs", "ouro_2p6b_l6")
S, VOCAB = 32, m.SMALL["vocab_size"]
CFG = ModelConfig(name="looped_decoder", compute_dtype="float32")


@pytest.fixture(scope="module")
def params():
    """Seeded random weights, every leaf away from its initial 1 or 0."""
    shapes = jax.eval_shape(
        lambda: m.init_params(jax.random.key(0), CFG, DataConfig()))
    from benchmark.lib import datagen
    return datagen.make_params(
        7, shapes, fan_in=lambda path, shape: shape[-1]
        if path == "['embed']" else None)


@pytest.fixture(scope="module")
def rows():
    return jax.random.randint(jax.random.key(1), (3, S + 1), 0, VOCAB)


def _leaves(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(p), x) for p, x in flat]


def test_loss_and_every_gradient_leaf_equal_the_references(params, rows):
    """float32 on both sides at the highest matmul precision. What is left
    is the order of float32 sums: the program scans the passes and takes
    the loss in blocks through its own backward rule, the reference is
    loops and autodiff. 2e-5 of a leaf's largest entry is ten times what
    that gives here (1e-6 to 3e-6)."""
    ref = cells.load_module(CONFIG + ".py")
    spec = dict(m.SMALL, sequence_length=S)
    ref_loss = ref.make_loss(spec)
    nm = reference.Numerics("float32")
    with jax.default_matmul_precision("highest"):
        (mine, stats), g_mine = jax.value_and_grad(
            lambda p: m.loss(p, rows, CFG), has_aux=True)(params)
        (theirs, _), g_theirs = jax.value_and_grad(
            lambda p: ref_loss(nm, p, {}, (rows[:, :-1], rows[:, 1:])),
            has_aux=True)(params)
    assert float(mine) == pytest.approx(float(theirs), rel=2e-6)
    assert 0.0 <= float(stats["accuracy"]) <= 1.0
    for (name, a), (_, b) in zip(_leaves(g_mine), _leaves(g_theirs)):
        assert float(jnp.max(jnp.abs(a - b))) \
            <= 2e-5 * float(jnp.max(jnp.abs(b))), name
        assert float(jnp.max(jnp.abs(b))) > 0, name


def _untied_loss(per_pass_layers, params, rows):
    """The model's loss with a set of layer weights of its own for each
    pass: the loop written out, so that one application's gradient can be
    taken apart from the others'."""
    sz = m.sizes(CFG)
    targets = rows[:, 1:].reshape(-1)
    h = params["embed"][rows[:, :-1]]
    ce, gate = [], []
    for layers in per_pass_layers:
        for p in layers:
            h = m._layer(h, p, sz, CFG, None)
        h = rms_norm(h, params["final_norm"]["scale"], sz["rms_norm_eps"])
        flat = h.reshape(-1, h.shape[-1])
        ce.append(loss_lib.blockwise_cross_entropy(
            flat, params["head"], targets, 1, jnp.float32)[0])
        gate.append(mixed_matmul(flat, params["exit_gate"]["w"],
                                 jnp.float32)[:, 0]
                    + params["exit_gate"]["b"][0])
    return loss_lib.exit_weighted_loss(jnp.stack(ce, -1),
                                       jnp.stack(gate, -1),
                                       sz["exit_entropy_beta"])


def test_the_loop_shares_its_weights(params, rows):
    with jax.default_matmul_precision("highest"):
        by_passes = [float(m.loss(params, rows, CFG, passes=t)[0])
                     for t in (1, 2, 4)]
        assert len({round(x, 4) for x in by_passes}) == 3, by_passes
        tied = jax.grad(lambda p: m.loss(p, rows, CFG)[0])(params)
        apart = jax.grad(_untied_loss)([params["layers"]] * 4, params, rows)
        assert float(_untied_loss([params["layers"]] * 4, params, rows)) \
            == pytest.approx(by_passes[-1], rel=1e-6)
    for i, layer in enumerate(tied["layers"]):
        for name, g in layer.items():
            parts = [jax.tree.leaves(apart[t][i][name])[0] for t in range(4)]
            g = jax.tree.leaves(g)[0]
            # four applications, four different gradients, one sum
            assert float(jnp.max(jnp.abs(parts[0] - parts[3]))) > 0
            np.testing.assert_allclose(g, sum(parts), rtol=1e-4,
                                       atol=1e-6 * float(jnp.max(jnp.abs(g))))


@pytest.mark.parametrize("blocks", [1, 2, 3])
def test_the_loss_in_blocks_equals_the_loss_over_whole_logits(blocks):
    n, d, v = 96, 16, 40
    kh, kw, ky, kg = jax.random.split(jax.random.key(3), 4)
    h = jax.random.normal(kh, (n, d))
    w = jax.random.normal(kw, (d, v)) / 4
    y = jax.random.randint(ky, (n,), 0, v)
    weight = jax.random.normal(kg, (n,))

    def whole(h, w):
        logp = jax.nn.log_softmax(h @ w, -1)
        return jnp.sum(weight * -jnp.take_along_axis(logp, y[:, None],
                                                     -1)[:, 0])

    def in_blocks(h, w):
        ce, hit = loss_lib.blockwise_cross_entropy(h, w, y, blocks,
                                                   jnp.float32)
        return jnp.sum(weight * ce), hit

    with jax.default_matmul_precision("highest"):
        want, (dh, dw) = jax.value_and_grad(whole, (0, 1))(h, w)
        (got, hit), (gh, gw) = jax.value_and_grad(
            in_blocks, (0, 1), has_aux=True)(h, w)
        np.testing.assert_array_equal(
            hit, (jnp.argmax(h @ w, -1) == y).astype(jnp.float32))
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(gh, dh, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(gw, dw, rtol=1e-4, atol=1e-6)


def test_the_step_keeps_no_array_of_tokens_by_vocabulary(params, rows):
    """What the backward pass is handed from the forward pass, by shape:
    nothing of a vocabulary's width with as many rows as the batch has
    tokens, with the loss in one block and in three (the head's own
    ``[hidden, vocabulary]`` has fewer rows here)."""
    tokens = rows.shape[0] * S
    for blocks in (1, 3):
        kept = jax.tree.leaves(jax.eval_shape(
            lambda p: jax.vjp(lambda p: m.loss(
                p, rows, CFG, loss_blocks=blocks)[0], p)[1], params))
        assert kept
        shapes = [tuple(x.shape) for x in kept]
        logits = [s for s in shapes if s[-1:] == (VOCAB,)
                  and int(np.prod(s[:-1])) >= tokens]
        assert not logits, logits
        # and the per-token terms that ARE kept: [passes, tokens]
        assert (m.SMALL["total_ut_steps"], tokens) in shapes


def test_the_exit_distribution_and_a_gate_forced_shut(params, rows):
    logits = jax.random.normal(jax.random.key(4), (50, 4)) * 3
    p = jnp.exp(loss_lib.exit_distribution_log(logits))
    np.testing.assert_allclose(jnp.sum(p, -1), 1.0, rtol=1e-6)
    lam = jax.nn.sigmoid(logits)
    # (float32 logs of sigmoids summed against their product: 1e-4)
    np.testing.assert_allclose(p[:, 1], lam[:, 1] * (1 - lam[:, 0]),
                               rtol=1e-4)
    np.testing.assert_allclose(p[:, 3], jnp.prod(1 - lam[:, :3], -1),
                               rtol=1e-4)
    # beta 0, the gate shut: every token leaves at the last pass, and the
    # loss is that pass's plain cross-entropy
    shut = {**params, "exit_gate": {
        "w": jnp.zeros_like(params["exit_gate"]["w"]),
        "b": jnp.full_like(params["exit_gate"]["b"], -1e4)}}
    ce, gate, _ = m.exit_terms(shut, rows, CFG)
    assert float(loss_lib.exit_weighted_loss(ce.T, gate.T, 0.0)) \
        == pytest.approx(float(jnp.mean(ce[-1])), rel=1e-6)


def test_flash_attention_at_head_size_128_causal():
    """The Pallas kernels in the interpreter against the plain softmax at
    the published head size: values and all three gradients, float32
    operands (one block of 128 on the diagonal, so the mask inside a block
    is what is tested)."""
    b, s, h, d = 1, 256, 2, 128
    q, k, v, g = (jax.random.normal(key, (b, s, h, d)) / 2
                  for key in jax.random.split(jax.random.key(5), 4))

    def flash(q, k, v):
        return jnp.sum(g * fa.flash_attention(q, k, v, causal=True,
                                              interpret=True))

    def plain(q, k, v):
        return jnp.sum(g * attention_lib.xla_attention(q, k, v, causal=True))

    with jax.default_matmul_precision("highest"):
        want = attention_lib.xla_attention(q, k, v, causal=True)
        got = fa.flash_attention(q, k, v, causal=True, interpret=True)
        dwant = jax.grad(plain, (0, 1, 2))(q, k, v)
        dgot = jax.grad(flash, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    for a, b_ in zip(dgot, dwant):
        np.testing.assert_allclose(a, b_, rtol=1e-4, atol=1e-5)


def _published_cfg(tmp_path, layers):
    with open(CONFIG + ".json") as f:
        spec = json.load(f)
    path = tmp_path / f"l{layers}.json"
    path.write_text(json.dumps({**spec, "num_hidden_layers": layers}))
    return ModelConfig(name="looped_decoder", config_file=str(path))


@pytest.mark.parametrize("layers,count", [(6, 509_661_185),
                                          (48, 2_667_974_657)])
def test_parameters_at_the_published_widths(tmp_path, layers, count):
    """By ``jax.eval_shape``: nothing of that size is built."""
    cfg = _published_cfg(tmp_path, layers)
    assert m.param_count(cfg) == count
    ref = cells.load_module(CONFIG + ".py")
    with open(CONFIG + ".json") as f:
        spec = {**json.load(f), "num_hidden_layers": layers}
    assert ref.param_count(spec) == count
    mine = jax.eval_shape(
        lambda: m.init_params(jax.random.key(0), cfg, DataConfig()))
    assert jax.tree.structure(mine) == jax.tree.structure(
        ref.param_shapes(spec))
    assert [x.shape for x in jax.tree.leaves(mine)] \
        == [x.shape for x in jax.tree.leaves(ref.param_shapes(spec))]


def test_the_count_of_operations_against_xlas(tmp_path):
    """The step written out (no scan over passes, no loop over blocks, no
    kernel, nothing computed twice) as XLA's cost analysis counts it. The
    module counts the products and nothing else, and attention as the half
    square, where the plain attention of this path multiplies the whole
    one: the module's count with the whole square in it is XLA's count
    less norms, softmax, rotary and SiLU, 0.97-1.0 of it at a hidden size
    of 128 (at 64 the same arithmetic is 4% of the step)."""
    sz = {**m.SMALL, "hidden_size": 128, "head_dim": 32,
          "intermediate_size": 256}
    path = tmp_path / "sizes.json"
    path.write_text(json.dumps(sz))
    cfg = ModelConfig(name="looped_decoder", compute_dtype="float32",
                      config_file=str(path))
    data = DataConfig(dataset="tokens_synth", sequence_length=S)
    batch = 4
    rows = jnp.zeros((batch, S + 1), jnp.int32)
    shapes = jax.eval_shape(
        lambda: m.init_params(jax.random.key(0), cfg, data))

    def grads(p, rows):
        return jax.grad(lambda p: m.loss(p, rows, cfg, loss_blocks=1,
                                         scan_passes=False)[0])(p)

    counted = jax.jit(grads).lower(shapes, rows).compile() \
        .cost_analysis()["flops"]
    a = sz["num_attention_heads"] * sz["head_dim"]
    half = m.step_flops(cfg, data, batch)
    other_half = batch * 6 * sz["total_ut_steps"] \
        * sz["num_hidden_layers"] * 2 * a * (S * (S - 1) // 2)
    assert 0.97 <= (half + other_half) / counted <= 1.0
    # and the benchmark's module counts what the program counts
    ref = cells.load_module(CONFIG + ".py")
    assert ref.train_flops_per_image(dict(sz, sequence_length=S)) * batch \
        == half


def test_the_lowered_step_holds_the_scopes_and_the_map_their_kinds():
    model_def = get_model("looped_decoder")
    data = DataConfig(dataset="tokens_synth", sequence_length=S)
    optim = OptimConfig(optimizer="adamw")
    cfg = ModelConfig(name="looped_decoder", remat=True)
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
    for scope in ("embed", "pass/layer0/attn_norm", "pass/layer1/attn/qkv",
                  "attn/rotary", "attn/flash", "attn/out", "attn_post_norm",
                  "mlp_norm", "layer1/mlp", "mlp_post_norm", "exit/norm",
                  "exit/head", "exit/gate", "head/loss", "loss", "optimizer",
                  "fwd_bwd"):
        # (outside the scan a scope sits inside its transform: `jvp(embed)`)
        assert any(f"/{scope}/" in n or f"({scope})" in n
                   for n in named), scope
    kinds = {e.kind for e in devprof.scope_map(lowered.compile()).values()}
    assert {"attention", "mlp", "norm", "embed", "exit_head", "dense",
            "optimizer"} <= kinds
    for scope, kind in (("pass/layer0/attn/qkv", "attention"),
                        ("pass/layer0/attn/flash/flash_fwd", "attention"),
                        ("pass/layer3/mlp", "mlp"),
                        ("pass/layer0/attn_post_norm", "norm"),
                        ("exit/norm", "norm"), ("exit/head", "exit_head"),
                        ("exit/gate", "exit_head"), ("embed", "embed"),
                        ("exit/head/loss", "dense")):
        assert devprof.parse_op_name(
            f"jit(step)/fwd_bwd/{scope}/dot_general")[1] == kind, scope


def test_token_rows_reach_the_step_as_they_are(tmp_path):
    """``tokens_synth``: shards of ``S + 1`` int32 ids below the
    vocabulary, loaded in the images' place with a label column of zeros,
    and a batch of the per-step path is rows of the split, untouched."""
    from dml_cnn_cifar10_tpu.data import download, pipeline
    data = DataConfig(dataset="tokens_synth", data_dir=str(tmp_path),
                      sequence_length=S, num_classes=VOCAB,
                      synthetic_train_records=64, synthetic_test_records=16,
                      use_native_loader=True)
    it = pipeline.input_pipeline(data, 8, train=True, seed=3)
    assert it.images.shape == (64, S + 1) and it.images.dtype == np.int32
    assert it.images.min() >= 0 and it.images.max() < VOCAB
    assert not it.labels.any() and it.supports_index_stream
    batch = next(it)
    assert batch.images.dtype == np.int32
    assert {tuple(r) for r in batch.images} <= {tuple(r) for r in it.images}
    # a shard of the requested size is kept (the benchmark writes its own)
    path = download.train_files(data)[0]
    before = os.path.getmtime(path), open(path, "rb").read()
    download.ensure_dataset(data)
    assert (os.path.getmtime(path), open(path, "rb").read()) == before
    # and one of another size is written anew
    download.ensure_dataset(DataConfig(**{**data.__dict__,
                                          "sequence_length": S // 2}))
    assert os.path.getsize(path) == 16 * (S // 2 + 1) * 4


@pytest.mark.parametrize("argv,said", [
    (["--model", "looped_decoder"], "go together"),
    (["--dataset", "tokens_synth"], "go together"),
    (["--model", "looped_decoder", "--dataset", "tokens_synth",
      "--mode", "serve"], "trains and evaluates"),
])
def test_the_cli_refuses_what_a_token_model_cannot_do(argv, said):
    from dml_cnn_cifar10_tpu.cli.main import build_parser, config_from_args
    with pytest.raises(SystemExit) as e:
        config_from_args(build_parser().parse_args(argv))
    assert said in str(e.value)


def test_the_cli_takes_sizes_from_a_file(tmp_path, monkeypatch):
    from dml_cnn_cifar10_tpu.cli.main import build_parser, config_from_args
    cfg = config_from_args(build_parser().parse_args(
        ["--model", "looped_decoder", "--dataset", "tokens_synth",
         "--model_config_file", "benchmark/configs/ouro_2p6b_l6.json",
         "--sequence_length", "4096", "--optimizer", "adamw",
         "--adam_b2", "0.95"]))
    assert cfg.data.tokens and cfg.data.sequence_length == 4096
    assert cfg.data.num_classes == cfg.model.num_classes == 49152
    assert cfg.optim.adam_b2 == 0.95
    # found from the repository's root, whatever the working directory
    monkeypatch.chdir(tmp_path)
    assert m.sizes(cfg.model)["hidden_size"] == 2048
    small = config_from_args(build_parser().parse_args(
        ["--model", "looped_decoder", "--dataset", "tokens_synth"]))
    assert small.data.num_classes == VOCAB


# --- what a layer keeps across its recomputation ---------------------------

FLASH_S = 128          # the fewest tokens the dispatch hands to the kernels
FLASH_CFG = ModelConfig(name="looped_decoder", compute_dtype="float32",
                        use_pallas_attention=True)


def _kernel_scopes(jaxpr, outer=""):
    """The name-scope path of every ``pallas_call`` in ``jaxpr`` and in the
    jaxprs its equations hold (scan, checkpoint, jit, ``custom_vjp``)."""
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


def _launches(paths, kernel, within=""):
    return sum(p.endswith("/" + kernel) and within in p for p in paths)


@pytest.fixture(scope="module")
def flash_rows():
    return jax.random.randint(jax.random.key(2), (2, FLASH_S + 1), 0, VOCAB)


def test_a_recomputed_layer_runs_no_forward_kernel_again(params, flash_rows,
                                                         monkeypatch):
    """In the gradient's jaxpr: the flash forward kernel once a layer with
    ``remat`` as without it (the scan holds one copy of a layer), and the
    two backward kernels once each. With a policy that keeps nothing, as
    the bare ``jax.checkpoint`` did, the forward kernel is there twice:
    that is what the kept output and log-sum-exp take away."""
    def scopes(cfg):
        return _kernel_scopes(jax.make_jaxpr(jax.grad(
            lambda p: m.loss(p, flash_rows, cfg)[0]))(params).jaxpr)

    for remat in (False, True):
        paths = scopes(dataclasses.replace(FLASH_CFG, remat=remat))
        for i in range(m.SMALL["num_hidden_layers"]):
            for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
                assert _launches(paths, kernel, f"/layer{i}/") == 1, \
                    (remat, i, kernel, paths)
        assert len(paths) == 3 * m.SMALL["num_hidden_layers"]
    monkeypatch.setattr(m, "KEPT", ())
    paths = scopes(dataclasses.replace(FLASH_CFG, remat=True))
    assert _launches(paths, "flash_fwd") \
        == 2 * m.SMALL["num_hidden_layers"], paths


def test_the_kept_arrays_are_the_recomputed_ones(params, flash_rows):
    """Loss and every gradient leaf with ``remat`` against without: the
    kept output is what the recomputation would have written, so what is
    left is how the CPU compiler fuses the two programs."""
    def value_and_grads(remat):
        cfg = dataclasses.replace(FLASH_CFG, remat=remat)
        return jax.jit(jax.value_and_grad(
            lambda p: m.loss(p, flash_rows, cfg)[0]))(params)

    with jax.default_matmul_precision("highest"):
        plain, g_plain = value_and_grads(False)
        kept, g_kept = value_and_grads(True)
    assert float(kept) == pytest.approx(float(plain), rel=2e-6)
    for (name, a), (_, b) in zip(_leaves(g_kept), _leaves(g_plain)):
        assert float(jnp.max(jnp.abs(b))) > 0, name
        assert float(jnp.max(jnp.abs(a - b))) \
            <= 2e-5 * float(jnp.max(jnp.abs(b))), name


@pytest.mark.parametrize("remat", [False, True])
def test_the_names_change_nothing_under_a_bare_checkpoint(remat,
                                                          monkeypatch):
    """The image transformer's block (``models/vit.py``, recomputed under
    a ``jax.checkpoint`` without a policy) through ``dispatch_attention``:
    its gradient with the residuals named is the gradient with the names
    taken out again, bit for bit, and the same kernels are launched (under
    the bare checkpoint the forward kernel twice)."""
    from dml_cnn_cifar10_tpu.models import vit
    heads, dim = 2, 32
    bp = vit._init_block(jax.random.key(6), dim, jnp.float32)
    x = jax.random.normal(jax.random.key(7), (1, FLASH_S, dim))

    def block(h, bp):
        return vit._block(h, bp, heads, True, 1.0, causal=True)[0]

    unnamed = []

    def grads():
        # jax keeps the rule's trace (and `flash_attention` is a jit of its
        # own): without this the second run is handed the first one's
        jax.clear_caches()
        fn = jax.checkpoint(block) if remat else block
        grad = jax.grad(lambda h, bp: jnp.sum(fn(h, bp) ** 2), (0, 1))
        return (jax.jit(grad)(x, bp),
                _kernel_scopes(jax.make_jaxpr(grad)(x, bp).jaxpr))

    named, named_paths = grads()
    monkeypatch.setattr(fa, "checkpoint_name",
                        lambda value, name: unnamed.append(name) or value)
    bare, bare_paths = grads()
    jax.clear_caches()
    assert set(unnamed) == {"flash_out", "flash_lse"}
    assert [p.rsplit("/", 1)[-1] for p in named_paths] \
        == [p.rsplit("/", 1)[-1] for p in bare_paths]
    assert _launches(named_paths, "flash_fwd") == 1 + remat
    for a, b in zip(jax.tree.leaves(named), jax.tree.leaves(bare)):
        np.testing.assert_array_equal(a, b)

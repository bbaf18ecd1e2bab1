"""ZeRO-1 sharded weight update + partition-rule engine + fused
single-pass optimizer kernel (ISSUE 9; docs/SHARDING.md).

Pins: the regex rule engine (ordering, alignment, strict mode, the CLI
grammar, the report); the zero1 layout being REAL (optimizer moments
allocated sharded 1/N on the live state) and PURE (final params within
1e-6 of the replicated path on the 8-device CPU sim, every step
builder); checkpoints interchanging across layouts through both codecs
including the sha256-sidecar fallback walk; and the fused optimizer's
equivalence tolerances (PARITY.md "Update-path equivalence").
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dml_cnn_cifar10_tpu.config import (DataConfig, ModelConfig, OptimConfig,
                                        ParallelConfig)
from dml_cnn_cifar10_tpu.models.registry import get_model
from dml_cnn_cifar10_tpu.ops import optimizer as fused_lib
from dml_cnn_cifar10_tpu.parallel import mesh as mesh_lib
from dml_cnn_cifar10_tpu.parallel import shardings
from dml_cnn_cifar10_tpu.parallel import step as step_lib
from dml_cnn_cifar10_tpu.train import optim as optim_lib

DATA = DataConfig(normalize="scale")


def _mesh(data=8, model=1):
    return mesh_lib.build_mesh(
        ParallelConfig(data_axis=data, model_axis=model))


def _batch(rng, n=16, hw=24):
    images = rng.normal(0.5, 0.25, (n, hw, hw, 3)).astype(np.float32)
    labels = rng.integers(0, 10, n).astype(np.int32)
    return images, labels


def _optim(**kw):
    kw.setdefault("learning_rate", 0.01)
    kw.setdefault("momentum", 0.9)
    kw.setdefault("weight_decay", 1e-4)
    return OptimConfig(**kw)


def _build(mesh, optim, model_cfg=None):
    model_cfg = model_cfg or ModelConfig(logit_relu=False)
    model_def = get_model(model_cfg.name)
    zero1 = optim.optimizer_sharding == "zero1"
    sh = step_lib.train_state_shardings(mesh, model_def, model_cfg, DATA,
                                        optim, zero1=zero1)
    state = step_lib.init_train_state(
        jax.random.key(0), model_def, model_cfg, DATA, optim, mesh,
        state_sharding=sh)
    train = step_lib.make_train_step(model_def, model_cfg, optim, mesh,
                                     state_sharding=sh)
    return state, train, sh


# ---------------------------------------------------------------------------
# partition-rule engine
# ---------------------------------------------------------------------------

def test_rules_first_match_wins_and_alignment():
    tree = {"blocks": {"qkv": {"kernel": jax.ShapeDtypeStruct(
                (4, 64, 192), jnp.float32)},
                       "step": jax.ShapeDtypeStruct((), jnp.int32)}}
    rules = (shardings.PartitionRule(r"qkv/kernel$", P("model")),
             shardings.PartitionRule(r".*", P("data", None)))
    specs = shardings.match_partition_rules(rules, tree)
    # First match wins (the catch-all never fires for qkv), spec is
    # right-aligned to rank 3; scalars never partition.
    assert specs["blocks"]["qkv"]["kernel"] == P(None, None, "model")
    assert specs["blocks"]["step"] == P()
    # Left alignment anchors at the leading axis, untrimmed.
    left = (shardings.PartitionRule(r".*", P("pipe"), align="left"),)
    assert shardings.match_partition_rules(
        left, tree)["blocks"]["qkv"]["kernel"] == P("pipe")
    # A spec wider than the leaf rank is a loud error, not silent junk.
    wide = (shardings.PartitionRule(
        r"step", P("model", None)),)
    with pytest.raises(ValueError, match="rank"):
        shardings.match_partition_rules(
            wide, {"step": jax.ShapeDtypeStruct((3,), jnp.float32)})


def test_rules_strict_mode_errors_on_unmatched():
    tree = {"a": jax.ShapeDtypeStruct((8,), jnp.float32),
            "b": jax.ShapeDtypeStruct((8,), jnp.float32)}
    rules = (shardings.PartitionRule(r"^a$", P("model")),)
    # Non-strict replicates the miss...
    assert shardings.match_partition_rules(rules, tree)["b"] == P()
    # ...strict names it.
    with pytest.raises(ValueError, match="b"):
        shardings.match_partition_rules(rules, tree, strict=True)
    # The built-in tables all end in a catch-all: strict never trips.
    model_def = get_model("cnn")
    params = jax.eval_shape(
        lambda k: model_def.init(k, ModelConfig(), DATA), jax.random.key(0))
    strict = shardings.param_pspecs("cnn", params, strict=True)
    assert strict["full1"]["kernel"] == P(None, "model")


def test_parse_partition_rules_grammar():
    rules = shardings.parse_partition_rules(
        "full1/(kernel|bias)$=model; full2/kernel$=model,-; "
        "blocks/=^pipe; odd=data+model,*; .*=replicated")
    assert [r.pattern for r in rules] == [
        "full1/(kernel|bias)$", "full2/kernel$", "blocks/", "odd", ".*"]
    assert rules[0].spec == P("model") and rules[0].align == "right"
    assert rules[1].spec == P("model", None)
    assert rules[2].spec == P("pipe") and rules[2].align == "left"
    assert rules[3].spec == P(("data", "model"), None)
    assert rules[4].spec == P()
    assert shardings.parse_partition_rules(None) is None
    assert shardings.parse_partition_rules("") is None
    with pytest.raises(ValueError, match="regex=spec"):
        shardings.parse_partition_rules("no-equals-sign")
    with pytest.raises(ValueError, match="bad regex"):
        shardings.parse_partition_rules("([unclosed=model")
    # The CNN default expressed as an override string reproduces the
    # built-in table's specs leaf-for-leaf.
    model_def = get_model("cnn")
    params = jax.eval_shape(
        lambda k: model_def.init(k, ModelConfig(), DATA), jax.random.key(0))
    override = shardings.parse_partition_rules(
        "full1/(kernel|bias)$=model; full2/kernel$=model,-; .*=")
    assert shardings.param_pspecs("cnn", params, rules=override) \
        == shardings.param_pspecs("cnn", params)


def test_partition_report_names_rule_per_param():
    model_def = get_model("cnn")
    params = jax.eval_shape(
        lambda k: model_def.init(k, ModelConfig(), DATA), jax.random.key(0))
    rows = shardings.explain_partition_rules(shardings.rule_for("cnn"),
                                             params)
    by_path = {r["path"]: r for r in rows}
    assert by_path["full1/kernel"]["rule"] == r"full1/(kernel|bias)$"
    assert by_path["full1/kernel"]["spec"] == P(None, "model")
    assert by_path["conv1/kernel"]["rule"] == r".*"
    report = shardings.format_partition_report(rows)
    assert "full1/kernel" in report and r"full1/(kernel|bias)$" in report


# ---------------------------------------------------------------------------
# zero1: real sharding + HBM win, asserted on the LIVE state
# ---------------------------------------------------------------------------

def test_zero1_state_allocated_sharded_and_smaller():
    """Acceptance: per-replica optimizer-state bytes drop by the dp
    factor on the live state — not computed on paper."""
    mesh = _mesh()
    state_z, _, _ = _build(mesh, _optim(optimizer_sharding="zero1"))
    state_n, _, _ = _build(mesh, _optim())

    k = state_z.opt["momentum"]["full1"]["kernel"]      # [2304, 384]
    assert "data" in str(k.sharding.spec)
    assert k.addressable_shards[0].data.shape[0] == 2304 // 8
    # Params stay in the model layout (replicated here) — zero1 shards
    # the UPDATE state only.
    assert state_z.params["full1"]["kernel"].sharding.spec == P(
        None, "model")
    assert not shardings.specs_name_axis(
        jax.tree.map(lambda x: x.sharding, state_z.params), "data")

    def device0_bytes(tree):
        total = 0
        for leaf in jax.tree.leaves(tree):
            shard = leaf.addressable_shards[0]
            total += int(np.prod(shard.data.shape)) * leaf.dtype.itemsize
        return total

    z = device0_bytes(state_z.opt["momentum"])
    n = device0_bytes(state_n.opt["momentum"])
    # Every dp-divisible moment leaf holds 1/8 per replica; only the
    # handful of tiny non-divisible biases stay whole.
    assert z < n / 4, (z, n)


def test_zero1_rejects_invalid_compositions():
    mesh = _mesh()
    model_def = get_model("cnn")
    cfg = ModelConfig(logit_relu=False)
    with pytest.raises(ValueError, match="none | zero1"):
        step_lib.make_train_step(model_def, cfg,
                                 _optim(optimizer_sharding="zero3"), mesh)
    with pytest.raises(ValueError, match="explicit_collectives"):
        step_lib.make_train_step(model_def, cfg,
                                 _optim(optimizer_sharding="zero1"),
                                 mesh, explicit_collectives=True)
    with pytest.raises(ValueError, match="async_staleness"):
        step_lib.make_train_step(
            model_def, cfg,
            _optim(optimizer_sharding="zero1", async_staleness=2,
                   weight_decay=0.0), mesh)


def test_zero1_matches_replicated(rng):
    """Acceptance: zero1 is a pure layout/schedule change — final params
    within 1e-6 absolute of the replicated path after 3 steps on the
    8-device sim (the reduce-scatter may reorder the gradient sum;
    PARITY.md pins the tolerance)."""
    mesh = _mesh()
    images, labels = _batch(rng)
    im, lb = mesh_lib.shard_batch(mesh, images, labels)

    def run(optim):
        state, train, _ = _build(mesh, optim)
        for _ in range(3):
            state, metrics = train(state, im, lb)
        return state, float(jax.device_get(metrics["loss"]))

    st_n, loss_n = run(_optim())
    st_z, loss_z = run(_optim(optimizer_sharding="zero1"))
    assert np.isfinite(loss_n) and np.isfinite(loss_z)
    np.testing.assert_allclose(loss_n, loss_z, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(st_n.params),
                    jax.tree.leaves(st_z.params)):
        np.testing.assert_allclose(np.asarray(jax.device_get(a)),
                                   np.asarray(jax.device_get(b)),
                                   rtol=0, atol=1e-6)
    # The momentum trace agrees too (it IS the sharded state).
    for a, b in zip(jax.tree.leaves(st_n.opt["momentum"]),
                    jax.tree.leaves(st_z.opt["momentum"])):
        np.testing.assert_allclose(np.asarray(jax.device_get(a)),
                                   np.asarray(jax.device_get(b)),
                                   rtol=0, atol=1e-6)


@pytest.mark.slow
def test_zero1_chunked_matches_plain_step(rng):
    """The chunked builder rides the same _step_body seam: K scanned
    zero1 steps == K plain-step zero1 steps == K replicated steps."""
    mesh = _mesh()
    images, labels = _batch(rng, n=32)
    k = 2
    ims = images.reshape(k, 16, 24, 24, 3)
    lbs = labels.reshape(k, 16)
    optim = _optim(optimizer_sharding="zero1")
    model_def = get_model("cnn")
    cfg = ModelConfig(logit_relu=False)
    sh = step_lib.train_state_shardings(mesh, model_def, cfg, DATA, optim,
                                        zero1=True)
    state = step_lib.init_train_state(
        jax.random.key(0), model_def, cfg, DATA, optim, mesh,
        state_sharding=sh)
    chunk = step_lib.make_train_chunk(model_def, cfg, optim, mesh,
                                      state_sharding=sh)
    im, lb = mesh_lib.shard_batch(mesh, ims, lbs, leading_dims=1)
    state, _ = chunk(state, im, lb)

    ref, train, _ = _build(mesh, optim)
    for i in range(k):
        b = mesh_lib.shard_batch(mesh, ims[i], lbs[i])
        ref, _ = train(ref, *b)
    for a, b in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(ref.params)):
        np.testing.assert_allclose(np.asarray(jax.device_get(a)),
                                   np.asarray(jax.device_get(b)),
                                   rtol=0, atol=1e-6)


@pytest.mark.slow
def test_zero1_composes_with_tp(rng):
    """data=4 x model=2: the col-parallel kernel's momentum carries BOTH
    axes, and zero1 on that mesh matches the replicated update ON THE
    SAME MESH within the pinned tolerance (comparing against a
    different mesh shape would fold unrelated tp-reduction reorderings
    into the delta)."""
    mesh = _mesh(data=4, model=2)
    images, labels = _batch(rng)
    im, lb = mesh_lib.shard_batch(mesh, images, labels)

    state, train, _ = _build(mesh, _optim(optimizer_sharding="zero1"))
    m = state.opt["momentum"]["full1"]["kernel"]
    assert m.sharding.spec == P("data", "model")
    assert m.addressable_shards[0].data.shape == (2304 // 4, 384 // 2)
    for _ in range(2):
        state, metrics = train(state, im, lb)
    assert np.isfinite(float(jax.device_get(metrics["loss"])))

    ref, rtrain, _ = _build(mesh, _optim())
    for _ in range(2):
        ref, _ = rtrain(ref, im, lb)
    for a, b in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(ref.params)):
        np.testing.assert_allclose(np.asarray(jax.device_get(a)),
                                   np.asarray(jax.device_get(b)),
                                   rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# checkpoints interchange across layouts (both codecs + sidecar fallback)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", ["msgpack", "sharded"])
def test_checkpoint_cross_layout_roundtrip(tmp_path, rng, fmt):
    """Save under zero1, restore under none — and the reverse — through
    the flat AND sharded codecs: params bit-identical, restored state
    trains on (donated-buffer layouts line up)."""
    from dml_cnn_cifar10_tpu.ckpt import checkpoint as ckpt_lib

    mesh = _mesh()
    images, labels = _batch(rng)
    im, lb = mesh_lib.shard_batch(mesh, images, labels)

    state_z, train_z, sh_z = _build(mesh, _optim(optimizer_sharding="zero1"))
    state_z, _ = train_z(state_z, im, lb)
    state_n, train_n, sh_n = _build(mesh, _optim())
    state_n, _ = train_n(state_n, im, lb)

    # zero1 -> none
    d1 = str(tmp_path / f"z2n_{fmt}")
    ckpt_lib.save_checkpoint(d1, state_z, step=1, fmt=fmt)
    fresh = step_lib.init_train_state(
        jax.random.key(7), get_model("cnn"), ModelConfig(logit_relu=False),
        DATA, _optim(), mesh, state_sharding=sh_n)
    restored = ckpt_lib.restore_checkpoint(d1, fresh, sharding=sh_n)
    for a, b in zip(jax.tree.leaves(state_z.params),
                    jax.tree.leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(jax.device_get(a)),
                                      np.asarray(jax.device_get(b)))
    assert restored.opt["momentum"]["full1"]["kernel"].sharding.spec \
        == P(None, "model")
    restored, metrics = train_n(restored, im, lb)
    assert np.isfinite(float(jax.device_get(metrics["loss"])))

    # none -> zero1 (the moments re-shard onto the data axis)
    d2 = str(tmp_path / f"n2z_{fmt}")
    ckpt_lib.save_checkpoint(d2, state_n, step=1, fmt=fmt)
    fresh = step_lib.init_train_state(
        jax.random.key(7), get_model("cnn"), ModelConfig(logit_relu=False),
        DATA, _optim(optimizer_sharding="zero1"), mesh, state_sharding=sh_z)
    restored = ckpt_lib.restore_checkpoint(d2, fresh, sharding=sh_z)
    for a, b in zip(jax.tree.leaves(state_n.params),
                    jax.tree.leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(jax.device_get(a)),
                                      np.asarray(jax.device_get(b)))
    m = restored.opt["momentum"]["full1"]["kernel"]
    assert "data" in str(m.sharding.spec)
    assert m.addressable_shards[0].data.shape[0] == 2304 // 8
    restored, metrics = train_z(restored, im, lb)
    assert np.isfinite(float(jax.device_get(metrics["loss"])))


def test_checkpoint_cross_layout_sidecar_fallback(tmp_path, rng):
    """A corrupt LATEST checkpoint (sha256 sidecar catches it) falls
    back to the older candidate, which still restores cross-layout."""
    from dml_cnn_cifar10_tpu.ckpt import checkpoint as ckpt_lib

    mesh = _mesh()
    images, labels = _batch(rng)
    im, lb = mesh_lib.shard_batch(mesh, images, labels)
    state_z, train_z, sh_z = _build(mesh, _optim(optimizer_sharding="zero1"))
    state_z, _ = train_z(state_z, im, lb)
    good = jax.device_get(state_z.params)
    d = str(tmp_path / "fb")
    ckpt_lib.save_checkpoint(d, state_z, step=1)
    state_z, _ = train_z(state_z, im, lb)
    path2 = ckpt_lib.save_checkpoint(d, state_z, step=2)
    # Flip a byte mid-file: the sidecar digest no longer matches.
    with open(path2, "r+b") as f:
        f.seek(os.path.getsize(path2) // 2)
        byte = f.read(1)
        f.seek(-1, 1)
        f.write(bytes([byte[0] ^ 0xFF]))

    fallbacks = []
    _, sh_n = _build(mesh, _optim())[1:]
    fresh = step_lib.init_train_state(
        jax.random.key(7), get_model("cnn"), ModelConfig(logit_relu=False),
        DATA, _optim(), mesh, state_sharding=sh_n)
    restored = ckpt_lib.restore_checkpoint(
        d, fresh, sharding=sh_n,
        on_fallback=lambda step, path, reason, walk_ms: fallbacks.append(
            step))
    assert fallbacks == [2]
    assert int(jax.device_get(restored.step)) == 1
    for a, b in zip(jax.tree.leaves(good), jax.tree.leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(jax.device_get(b)))


# ---------------------------------------------------------------------------
# fused single-pass optimizer (ops/optimizer.py)
# ---------------------------------------------------------------------------

def _leaves(rng):
    # Deliberately tile-hostile shapes: a sub-tile vector, a ragged
    # matrix, and a lane-aligned one — the pad/reshape must be exact.
    shapes = [(37,), (130, 7), (256, 128)]
    mk = lambda: {f"l{i}": jnp.asarray(rng.normal(size=s), jnp.float32)
                  for i, s in enumerate(shapes)}
    return mk(), mk(), mk()


def test_fused_kernel_matches_fallback_interpret(rng):
    """The Pallas kernel (interpret mode on CPU) vs the XLA fallback:
    within a few f32 ULPs (FMA contraction; PARITY.md pins <= 5e-7)."""
    params, grads, mom = _leaves(rng)
    lr = jnp.float32(0.05)
    for m, mu, wd in ((mom, 0.9, 1e-4), (mom, 0.9, 0.0), (None, 0.0, 0.0)):
        pk, mk = fused_lib.fused_sgd_update(
            params, grads, m, lr, mu, wd, use_pallas=True, interpret=True)
        pf, mf = fused_lib.fused_sgd_update(
            params, grads, m, lr, mu, wd, use_pallas=False)
        for key in params:
            np.testing.assert_allclose(np.asarray(pk[key]),
                                       np.asarray(pf[key]),
                                       rtol=0, atol=5e-7)
            if m is not None:
                np.testing.assert_allclose(np.asarray(mk[key]),
                                           np.asarray(mf[key]),
                                           rtol=0, atol=5e-7)
        if m is None:
            assert mk is None and mf is None


def test_fused_update_bit_identical_to_legacy_chain(rng):
    """sgd_update with fused_optimizer on vs off (the historical
    tree_map chain): bit-identical on the XLA path — same expression."""
    params, grads, _ = _leaves(rng)
    for mu, wd in ((0.9, 1e-4), (0.9, 0.0), (0.0, 0.0), (0.0, 1e-4)):
        def run(fused):
            cfg = OptimConfig(learning_rate=0.05, momentum=mu,
                              weight_decay=wd, fused_optimizer=fused)
            state = optim_lib.sgd_init(params, cfg)
            return jax.jit(
                lambda g, s, p: optim_lib.sgd_update(g, s, p, cfg))(
                    grads, state, params)
        (p1, s1), (p0, s0) = run(True), run(False)
        for key in params:
            np.testing.assert_array_equal(np.asarray(p1[key]),
                                          np.asarray(p0[key]))
        if mu:
            for key in params:
                np.testing.assert_array_equal(
                    np.asarray(s1["momentum"][key]),
                    np.asarray(s0["momentum"][key]))
        assert int(s1["step"]) == int(s0["step"]) == 1


def test_fused_platform_selection():
    """The Pallas lowering is TPU-only and never engages under a
    GSPMD-sharded (zero1) update — the partitioner cannot split an
    opaque custom call."""
    assert fused_lib._use_pallas("none") == (
        jax.default_backend() == "tpu")
    assert fused_lib._use_pallas("zero1") is False


# ---------------------------------------------------------------------------
# optimizer_ms attribution (satellite; utils/devprof.py)
# ---------------------------------------------------------------------------

def test_devtime_optimizer_scope_bucket():
    from dml_cnn_cifar10_tpu.utils import devprof

    doc = {"traceEvents": [
        {"ph": "M", "name": "process_name", "pid": 7,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "X", "name": "fwd_bwd/conv.1", "pid": 7, "tid": 0,
         "ts": 0.0, "dur": 900.0},
        {"ph": "X", "name": "optimizer/fusion.2", "pid": 7, "tid": 0,
         "ts": 1000.0, "dur": 250.0},
        # Scope carried in profiler metadata args, not the short name.
        {"ph": "X", "name": "fusion.9", "pid": 7, "tid": 0,
         "ts": 1300.0, "dur": 50.0,
         "args": {"long_name": "optimizer/add.3"}},
    ]}
    lane = devprof.parse_trace_doc(doc)[0]
    assert lane["optimizer_ms"] == pytest.approx(0.3)
    # Overlapping scope total: also counted in the exclusive buckets.
    assert lane["compute_ms"] == pytest.approx(1.2)
    assert lane["total_ms"] == pytest.approx(1.2)


def test_profile_window_feeds_optimizer_step_ms(tmp_path, monkeypatch):
    from dml_cnn_cifar10_tpu.utils import devprof

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    lanes = [{"device": "/device:TPU:0", "total_ms": 10.0,
              "compute_ms": 10.0, "collective_ms": 0.0, "infeed_ms": 0.0,
              "optimizer_ms": 4.0, "window_ms": 12.0, "top_ops": []}]
    monkeypatch.setattr(devprof, "parse_profile_dir",
                        lambda d, top_k=12: lanes)
    sink = []

    class Logger:
        def log(self, kind, **fields):
            sink.append({"kind": kind, **fields})

    win = devprof.ProfileWindow(10, 4, str(tmp_path), logger=Logger())
    win.maybe_start(10)
    win.maybe_stop(18, drained=True)        # 8 steps in the window
    assert win.optimizer_step_ms == pytest.approx(0.5)
    assert sink and sink[0]["kind"] == "devtime"
    assert sink[0]["optimizer_ms"] == 4.0


"""Model unit tests: shapes, param counts, init statistics, quirk switches
(SURVEY §4)."""

import pytest
import jax
import jax.numpy as jnp
import numpy as np

from dml_cnn_cifar10_tpu.config import DataConfig, ModelConfig
from dml_cnn_cifar10_tpu.models import cnn
from dml_cnn_cifar10_tpu.ops import layers as L


def test_param_shapes_and_count():
    """24x24 input → two 3x3/2 SAME pools → 6x6x64 = 2304 flatten, exactly
    the reference's hardcoded reshaped_dim (cifar10cnn.py:126-131)."""
    params = cnn.init_params(jax.random.key(0), ModelConfig(), DataConfig())
    assert params["conv1"]["kernel"].shape == (5, 5, 3, 64)
    assert params["conv2"]["kernel"].shape == (5, 5, 64, 64)
    assert params["full1"]["kernel"].shape == (2304, 384)
    assert params["full2"]["kernel"].shape == (384, 192)
    assert params["full3"]["kernel"].shape == (192, 10)
    want = (5*5*3*64 + 64) + (5*5*64*64 + 64) + (2304*384 + 384) \
        + (384*192 + 192) + (192*10 + 10)
    assert cnn.param_count(params) == want


def test_init_statistics():
    """Truncated normal sigma=0.05 within ±2 sigma (cifar10cnn.py:97-98),
    biases constant 0.1 (cifar10cnn.py:100-101)."""
    params = cnn.init_params(jax.random.key(1), ModelConfig(), DataConfig())
    w = np.asarray(params["full1"]["kernel"]).ravel()
    assert np.abs(w).max() <= 0.1 + 1e-6          # hard truncation at 2 sigma
    assert abs(w.mean()) < 2e-3
    assert 0.03 < w.std() < 0.05                  # truncated std ≈ 0.88*sigma
    assert np.allclose(params["conv1"]["bias"], 0.1)


def test_forward_shape_and_faithful_logit_relu():
    data, model = DataConfig(), ModelConfig(logit_relu=True)
    params = cnn.init_params(jax.random.key(0), model, data)
    x = jnp.asarray(np.random.default_rng(0).normal(
        127, 50, (4, 24, 24, 3)).astype(np.float32))
    logits = cnn.apply(params, x, model)
    assert logits.shape == (4, 10)
    assert (logits >= 0).all()                    # faithful: ReLU'd logits

    fixed = ModelConfig(logit_relu=False)
    raw = cnn.apply(params, x, fixed)
    assert (raw < 0).any()                        # fixed mode exposes negatives
    np.testing.assert_allclose(jax.nn.relu(raw), logits, rtol=1e-5)


def test_full_resolution_input_changes_flatten_dim():
    """Config-driven flatten (no hardcoded 2304): 32x32 input → 8x8x64."""
    data = DataConfig(crop_height=32, crop_width=32)
    params = cnn.init_params(jax.random.key(0), ModelConfig(), data)
    assert params["full1"]["kernel"].shape == (4096, 384)
    x = jnp.zeros((2, 32, 32, 3))
    assert cnn.apply(params, x, ModelConfig()).shape == (2, 10)


def test_cifar100_head_swap():
    model = ModelConfig(num_classes=100)
    params = cnn.init_params(jax.random.key(0), model, DataConfig())
    assert params["full3"]["kernel"].shape == (192, 100)
    x = jnp.zeros((2, 24, 24, 3))
    assert cnn.apply(params, x, model).shape == (2, 100)


def test_max_pool_matches_reference_semantics():
    """3x3 window stride 2 SAME (cifar10cnn.py:113): 24→12, overlapping max."""
    x = jnp.arange(16, dtype=jnp.float32).reshape(1, 4, 4, 1)
    out = L.max_pool(x)
    assert out.shape == (1, 2, 2, 1)
    # windows centered per SAME/stride2: max over x[0:3,0:3] = 10
    assert float(out[0, 0, 0, 0]) == 10.0
    assert float(out[0, 1, 1, 0]) == 15.0


def _pool_input(shape, dtype, ties):
    rng = np.random.default_rng(shape[1] * 31 + shape[2])
    z, bias = rng.normal(size=shape), rng.normal(size=shape[-1])
    if ties:
        # A grid of halves: every window holds ties, at zero (where the
        # ReLU's mask decides), above it and below it.
        z, bias = np.round(z * 2) / 2, np.round(bias * 2) / 2
    return jnp.asarray(z, dtype), jnp.asarray(bias, dtype)


def _plain_pool(z, bias):
    return L.max_pool(jax.nn.relu(z + bias))


# (H = W, a block's byte budget): whole images in one block, and images
# cut into row blocks that read a halo row from their neighbour.
POOL_SIZES = [(24, None), (12, None), (32, 10_000), (112, 10_000)]


def _bf16_once(a):
    return np.asarray(jnp.asarray(a, jnp.float32).astype(jnp.bfloat16),
                      np.float32)


@pytest.mark.parametrize("ties", [False, True], ids=["random", "ties"])
@pytest.mark.parametrize("size,block_bytes", POOL_SIZES,
                         ids=[f"{s}" for s, _ in POOL_SIZES])
@pytest.mark.parametrize("dtype,store", [
    (jnp.float32, None), (jnp.bfloat16, None), (jnp.float32, jnp.bfloat16)],
    ids=["float32", "bfloat16", "float32_stores_bfloat16"])
def test_pool_kernels_match_max_pool_of_relu(dtype, store, size, block_bytes,
                                             ties, monkeypatch):
    """The kernels (in the Pallas interpreter) against ``jax.vjp`` of
    ``max_pool(relu(z + bias))``: forward bit-equal, gradients equal up to
    the order in which the (at most four) windows that share an input
    position, and the windows of a channel, are summed. With a narrower
    ``store`` the pooled output and the input's gradient are those same
    float32 values rounded once, and nothing else changes."""
    from dml_cnn_cifar10_tpu.ops import relu_pool

    if block_bytes:
        monkeypatch.setattr(relu_pool, "_BLOCK_BYTES", block_bytes)
    shape = (1, size, size, 2) if size > 32 else (2, size, size, 4)
    rows = relu_pool._blocks(size // 2, size, shape[3], shape[0],
                             jnp.dtype(dtype).itemsize)[0]
    assert (rows < size // 2) == bool(block_bytes)
    z, bias = _pool_input(shape, dtype, ties)
    want, ref_vjp = jax.vjp(_plain_pool, z, bias)
    got, vjp = jax.vjp(
        lambda z, b: relu_pool.fused_bias_relu_max_pool(z, b, True, store),
        z, bias)
    stored = jnp.dtype(store or dtype)
    assert got.dtype == want.dtype      # handed on in the input's dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want.astype(stored), np.float32))
    ct = jnp.asarray(np.random.default_rng(1).normal(size=want.shape), dtype)
    eps = float(jnp.finfo(dtype).eps)
    (dz, dbias), (dz_want, dbias_want) = [
        [np.asarray(t, np.float32) for t in f(ct)] for f in (vjp, ref_vjp)]
    assert np.abs(dz_want).max() > 1.0          # the comparison is not empty
    if store is None:
        np.testing.assert_allclose(dz, dz_want, rtol=0,
                                   atol=4 * eps * np.abs(dz_want).max())
    else:
        # the float32 sum rounded ONCE: a stored value, and the reference's
        # rounded alike but where the order of the float32 terms moved a
        # sum across a rounding boundary (then one step of bfloat16 away)
        np.testing.assert_array_equal(dz, _bf16_once(dz))
        off = dz != _bf16_once(dz_want)
        assert off.mean() < 1e-3
        np.testing.assert_allclose(dz[off], dz_want[off], rtol=2.0 ** -7)
        # the bias's gradient never passes through the store
        dbias_f32 = jax.vjp(lambda z, b: relu_pool.fused_bias_relu_max_pool(
            z, b, True), z, bias)[1](ct)[1]
        np.testing.assert_array_equal(dbias, dbias_f32)
    # a sum over every window of a channel: a rounding a term at most
    terms = np.abs(np.asarray(ct, np.float32)).sum(axis=(0, 1, 2)).max()
    np.testing.assert_allclose(dbias, dbias_want, rtol=0, atol=eps * terms)
    # What the forward pass leaves for the backward pass: the winning tap
    # and the pooled output as stored (and the bias, for its dtype).
    carried = sorted((str(l.dtype), l.size) for l in jax.tree.leaves(vjp))
    assert carried == sorted([("int8", want.size),
                              (str(stored), want.size),
                              (str(jnp.dtype(dtype)), bias.size)])


@pytest.mark.parametrize("store", [None, jnp.bfloat16],
                         ids=["float32", "stores_bfloat16"])
def test_pool_kernels_over_data_sum_the_bias_gradient(store):
    """On a mesh the kernels run a device, each on its own images; the
    bias is replicated, so its gradient is the sum over the devices."""
    from dml_cnn_cifar10_tpu.config import ParallelConfig
    from dml_cnn_cifar10_tpu.ops import relu_pool
    from dml_cnn_cifar10_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.build_mesh(ParallelConfig(), devices=jax.devices()[:4])
    z, bias = _pool_input((8, 12, 12, 4), jnp.float32, True)
    want, ref_vjp = jax.vjp(_plain_pool, z, bias)
    got, vjp = jax.vjp(
        jax.jit(relu_pool.over_data(mesh, interpret=True, store=store)),
        z, bias)
    np.testing.assert_array_equal(got, want)    # halves: exact in bfloat16
    assert got.sharding.spec[0] == "data"
    (dz, dbias), (dz_want, dbias_want) = vjp(want), ref_vjp(want)
    np.testing.assert_allclose(
        dz, _bf16_once(dz_want) if store else dz_want, rtol=0, atol=1e-5)
    np.testing.assert_allclose(dbias, dbias_want, rtol=0, atol=1e-5)
    kept = {str(l.dtype) for l in jax.tree.leaves(vjp) if l.size == got.size}
    assert kept == {"int8", str(jnp.dtype(store or jnp.float32))}


@pytest.mark.parametrize("shape,fits", [
    ((128, 24, 24, 64), True), ((256, 12, 12, 32), True),
    ((128, 7, 24, 64), False),      # odd H
    ((128, 24, 9, 64), False),      # odd W
    ((8, 24, 24, 64), False),       # a batch that leaves lanes empty
    ((128, 24, 24, 3), False),      # channels short of an int8 tile
])
def test_pool_path_is_chosen_by_shape(shape, fits, monkeypatch):
    """On a TPU the shapes the kernels were written for take them; every
    other shape, and every shape off TPU, runs ``max_pool(relu(z +
    bias))`` itself, value and gradient."""
    from dml_cnn_cifar10_tpu.ops import kernel_paths, relu_pool
    from dml_cnn_cifar10_tpu.utils import platform as platform_lib

    def path_taken():
        with kernel_paths.recording() as rec:
            jax.eval_shape(
                lambda z, b: relu_pool.bias_relu_max_pool(z, b),
                jax.ShapeDtypeStruct(shape, jnp.float32),
                jax.ShapeDtypeStruct(shape[-1:], jnp.float32))
        return rec

    assert relu_pool.fits_kernels(shape, jnp.float32) == fits
    assert not relu_pool.fits_kernels(shape, jnp.int32)
    assert path_taken() == {"pool": "xla"}          # the CPU
    monkeypatch.setattr(platform_lib, "on_tpu", lambda: True)
    assert path_taken() == {"pool": "pallas" if fits else "xla"}
    if not fits:
        z, bias = _pool_input((2, *shape[1:3], 4), jnp.float32, True)
        want, ref_vjp = jax.vjp(_plain_pool, z, bias)
        got, vjp = jax.vjp(relu_pool.bias_relu_max_pool, z, bias)
        np.testing.assert_array_equal(got, want)
        for a, b in zip(vjp(want), ref_vjp(want)):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("tpu,dtype,precision,path,stored", [
    (True, "float32", None, "pallas, stores bfloat16", "bfloat16"),
    (True, "float32", "bfloat16", "pallas, stores bfloat16", "bfloat16"),
    (True, "float32", "highest", "pallas", "float32"),
    (True, "float32", "float32", "pallas", "float32"),
    (True, "bfloat16", None, "pallas", "bfloat16"),
    (False, "float32", None, "xla", None),
])
def test_pool_store_is_chosen_by_dtype_and_precision(
        tpu, dtype, precision, path, stored, monkeypatch):
    """The narrow store engages where a product rounds what the model
    computes in: float32 on a TPU at a one-pass bfloat16 precision. It is
    read from the input and the backend, as ``models/cnn.py`` reads it;
    the op's own default is the input's dtype."""
    from dml_cnn_cifar10_tpu.ops import kernel_paths, relu_pool
    from dml_cnn_cifar10_tpu.utils import platform as platform_lib

    monkeypatch.setattr(platform_lib, "on_tpu", lambda: tpu)
    shape = (128, 24, 24, 64)
    args = (jax.ShapeDtypeStruct(shape, dtype),
            jax.ShapeDtypeStruct(shape[-1:], dtype))

    def carried(as_the_model_does):
        def f(z, b):
            store = (L.product_operand_dtype(z.dtype)
                     if as_the_model_does else None)
            return relu_pool.bias_relu_max_pool(z, b, store=store)
        with kernel_paths.recording() as rec:
            out, vjp = jax.eval_shape(lambda z, b: jax.vjp(f, z, b), *args)
        assert out.dtype == jnp.dtype(dtype) and out.shape == (128, 12, 12,
                                                               64)
        return rec["pool"], {str(l.dtype) for l in jax.tree.leaves(vjp)
                             if l.shape == (12, 12, 64, 128)}

    with jax.default_matmul_precision(precision):
        got = carried(True)
        default = carried(False)
    assert got == (path, {"int8", stored} if stored else set())
    assert default == (("pallas", {"int8", dtype}) if tpu
                       else ("xla", set()))


def _rounding_products(monkeypatch):
    """The TPU's float32 products on the CPU: ``L.conv2d`` and ``L.dense``
    with every operand rounded to bfloat16, forward and backward, summed
    and kept in float32 (what the configuration's ``matmul_precision``
    states). The CPU multiplies float32 as it is, so without this the
    test below could not tell a rounding that every product makes anyway
    from one that nobody makes."""
    def rounds(f):
        r = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)

        @jax.custom_vjp
        def product(x, w):
            return f(r(x), r(w))

        def fwd(x, w):
            return product(x, w), (r(x), r(w))

        def bwd(res, g):
            return jax.vjp(f, *res)[1](r(g))

        product.defvjp(fwd, bwd)
        return product

    conv, dot = rounds(L.conv2d), rounds(jnp.dot)
    monkeypatch.setattr(L, "conv2d", conv)
    monkeypatch.setattr(L, "dense", lambda x, w, b: dot(x, w) + b)


def test_narrow_store_is_the_rounding_the_products_make(monkeypatch):
    """The loss gradient of ``models/cnn.apply`` through the kernels with
    the bfloat16 store equals, leaf for leaf within float32 summation
    order, that of the plain model with everything stored in float32,
    once every product rounds its operands as the TPU's do: the store
    rounds nothing that is not rounded anyway. Rounding conv1's output
    (which an add, a ReLU and a compare read, not a product) as well is
    another gradient."""
    from dml_cnn_cifar10_tpu.ops import relu_pool
    from dml_cnn_cifar10_tpu.utils import platform as platform_lib

    cfg = ModelConfig(logit_relu=False)
    data = DataConfig(crop_height=8, crop_width=8)
    params = cnn.init_params(jax.random.key(0), cfg, data)
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.normal(size=(128, 8, 8, 3)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 10, size=128))
    _rounding_products(monkeypatch)

    def grad(apply):
        def loss(p):
            lp = jax.nn.log_softmax(apply(p, images, cfg))
            return -jnp.take_along_axis(lp, labels[:, None], 1).mean()
        return jax.tree.leaves(jax.jit(jax.grad(loss))(params))

    plain = grad(cnn.apply)                  # the CPU: XLA's pool, float32

    def z1_rounded_too(p, x, cfg):
        conv = L.conv2d
        monkeypatch.setattr(L, "conv2d", lambda x, w: (
            conv(x, w) if w.shape[2] != 3 else
            conv(x, w).astype(jnp.bfloat16).astype(jnp.float32)))
        try:
            return cnn.apply(p, x, cfg)
        finally:
            monkeypatch.setattr(L, "conv2d", conv)

    other = grad(z1_rounded_too)

    # the chip's path: the kernels (here in the interpreter), and the
    # store that models/cnn.py reads from the backend
    monkeypatch.setattr(platform_lib, "on_tpu", lambda: True)
    fused = relu_pool.fused_bias_relu_max_pool
    stores = []
    monkeypatch.setattr(
        relu_pool, "fused_bias_relu_max_pool",
        lambda z, b, interpret, store: (
            stores.append(store), fused(z, b, True, store))[1])
    narrow = grad(cnn.apply)
    assert stores == [jnp.bfloat16, jnp.bfloat16]

    for a, b in zip(narrow, plain):
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(a, b, rtol=0, atol=5e-6 * scale)
    moved = [float(jnp.abs(c - b).max() / jnp.abs(b).max())
             for b, c in zip(plain, other)]
    assert min(moved) > 2e-4 and max(moved) > 1e-3, moved


def test_conv2d_matches_manual_nhwc():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 5, 5, 2)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(3, 3, 2, 4)).astype(np.float32))
    out = L.conv2d(x, k)
    assert out.shape == (1, 5, 5, 4)
    # centre output pixel = full 3x3 valid correlation at that location
    want = np.einsum("hwc,hwco->o", np.asarray(x)[0, 1:4, 1:4], np.asarray(k))
    np.testing.assert_allclose(np.asarray(out)[0, 2, 2], want,
                               rtol=1e-2, atol=1e-2)


@pytest.mark.slow
def test_bfloat16_compute_path():
    model = ModelConfig(compute_dtype="bfloat16")
    params = cnn.init_params(jax.random.key(0), model, DataConfig())
    x = jnp.ones((2, 24, 24, 3))
    logits = cnn.apply(params, x, model)
    assert logits.dtype == jnp.float32             # outputs upcast for loss
    ref = cnn.apply(params, x, ModelConfig())
    np.testing.assert_allclose(logits, ref, rtol=0.1, atol=2.0)


def _scopes_off(monkeypatch):
    import contextlib
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())


CNN_SCOPES = ("conv1", "pool1", "conv2", "pool2", "fc1", "fc2", "logits")


def test_lowered_text_holds_every_layer_scope():
    """Each layer of the model is under its own ``jax.named_scope``: the
    names reach the lowered program's locations, which is where
    ``utils/devprof.scope_map`` reads a compiled instruction's layer."""
    from dml_cnn_cifar10_tpu.utils import devprof

    cfg, data = ModelConfig(logit_relu=False), DataConfig()
    params = cnn.init_params(jax.random.key(0), cfg, data)
    x = jnp.zeros((2, data.crop_height, data.crop_width, 3), jnp.float32)
    text = jax.jit(lambda p, x: cnn.apply(p, x, cfg)).lower(
        params, x).as_text(debug_info=True)
    for scope in CNN_SCOPES:
        assert f"/{scope}/" in text or f"/{scope}\"" in text, scope
        # and the table of kinds knows each of them
        assert devprof.parse_op_name(f"jit(f)/{scope}/op")[1] != "none"


def test_logits_are_bit_equal_without_the_scopes(monkeypatch):
    """Scopes are metadata: no numeric effect."""
    cfg, data = ModelConfig(logit_relu=False), DataConfig()
    params = cnn.init_params(jax.random.key(0), cfg, data)
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(4, data.crop_height, data.crop_width, 3)), jnp.float32)
    with_scopes = np.asarray(cnn.apply(params, x, cfg))
    _scopes_off(monkeypatch)
    without = np.asarray(cnn.apply(params, x, cfg))
    assert np.array_equal(with_scopes, without)

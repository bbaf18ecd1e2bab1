"""The reference's numerics and feed, piece by piece: what each mode
leaves of a product's operands forward and backward, the warm-up of the
learning rate, and the shuffled stream against the program's own."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmark.lib import reference
from dml_cnn_cifar10_tpu.data import device_stream


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _dot(a, b):
    return jnp.dot(a, b, precision=lax.Precision.HIGHEST)


@pytest.fixture(scope="module")
def operands():
    kx, kw, kc = jax.random.split(jax.random.key(3), 3)
    return (jax.random.normal(kx, (8, 32)), jax.random.normal(kw, (32, 16)),
            jax.random.normal(kc, (8, 16)))


def test_float32_products_are_exact(operands):
    x, w, _ = operands
    nm = reference.Numerics("float32")
    np.testing.assert_array_equal(nm.dense(x, w), _dot(x, w))


def test_tpu_default_rounds_the_operands_of_all_three_products(operands):
    x, w, c = operands
    nm = reference.Numerics("tpu_default")
    np.testing.assert_array_equal(nm.dense(x, w), _dot(_bf16(x), _bf16(w)))
    dx, dw = jax.grad(lambda a, b: jnp.sum(nm.dense(a, b) * c),
                      argnums=(0, 1))(x, w)
    # (to the order of a float32 sum: far inside one bfloat16 rounding)
    np.testing.assert_allclose(dx, _dot(_bf16(c), _bf16(w).T), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(dw, _dot(_bf16(x).T, _bf16(c)), rtol=1e-5,
                               atol=1e-5)
    unrounded = _dot(c, _bf16(w).T)
    assert float(jnp.max(jnp.abs(dx - unrounded))) > 1e-3
    # sums and stored tensors stay float32: the result is not a bfloat16
    assert not np.array_equal(nm.dense(x, w), _bf16(nm.dense(x, w)))


@pytest.mark.parametrize("mode", ["bfloat16", "float8"])
def test_a_control_stores_its_activations_in_bfloat16(operands, mode):
    x, w, c = operands
    nm = reference.Numerics(mode)
    y = nm.dense(x, w)
    np.testing.assert_array_equal(y, _bf16(y))
    # and lies further from float32 than the mode above it
    exact = _dot(x, w)
    above = reference.Numerics(
        "tpu_default" if mode == "bfloat16" else "bfloat16").dense(x, w)
    assert float(jnp.linalg.norm(y - exact)) \
        > float(jnp.linalg.norm(above - exact))
    g = jax.grad(lambda a: jnp.sum(nm.dense(a, w) * c))(x)
    assert np.all(np.isfinite(g))


def test_conv_takes_the_same_rounding(operands):
    del operands
    kx, kw = jax.random.split(jax.random.key(4))
    x = jax.random.normal(kx, (2, 8, 8, 3))
    w = jax.random.normal(kw, (3, 3, 3, 4))
    want = lax.conv_general_dilated(
        _bf16(x), _bf16(w), (2, 2), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)
    np.testing.assert_array_equal(
        reference.Numerics("tpu_default").conv(x, w, stride=2), want)


@pytest.mark.parametrize("n,step0,batch,k", [(50000, 0, 1024, 4),
                                             (2048, 23, 256, 3),
                                             (320, 7, 100, 7)])
def test_the_stream_is_the_programs(n, step0, batch, k):
    """The NumPy permutation against ``data/device_stream.py``, across
    epoch borders."""
    mine = reference.stream_rows(7, step0 * batch, k * batch, n)
    theirs = np.asarray(device_stream.chunk_shuffle_indices(
        7, jnp.uint32(step0), batch, k, n))
    np.testing.assert_array_equal(mine.reshape(k, batch), theirs)
    assert sorted(reference.stream_rows(7, 0, n, n)) == list(range(n))


def test_warm_up_ramps_the_rate_as_the_program_does():
    """One parameter whose gradient stays near 1/2: the change is half
    the sum of the rates."""
    hyper = reference.Hyper(seed=1, batch=4, steps=3, records=8)
    image = reference.ImageHyper(
        classes=2, image_size=4, channels=3, crop=4, random_crop=False,
        random_flip=False, normalize="none", learning_rate=0.5,
        warmup_steps=4, momentum=0.0, weight_decay=0.0,
        decode_whole_chunk=True)

    def forward(nm, params, state, x):
        del nm
        n = x.shape[0]
        logits = jnp.zeros((n, 2)).at[:, 0].set(params["b"])
        return logits, state

    images = jnp.zeros((8, 4, 4, 3), jnp.uint8)
    labels = jnp.ones((8,), jnp.int32)
    out = reference.run_chunk(reference.image_task(image, forward), hyper,
                              {"b": jnp.float32(0.0)}, {}, (images, labels))
    # d loss / d b = softmax(b, 0)[0] - [label == 0] = 0.5 at b = 0, and
    # stays near it; the rates are 0.5 * (1, 2, 3) / 4
    rates = 0.5 * np.array([1, 2, 3]) / 4
    assert float(out.params["b"]) == pytest.approx(-0.5 * rates.sum(),
                                                   rel=0.2)
    first = float(out.first_grad_norms["b"])
    assert first == pytest.approx(0.5) and out.opt == {}


def test_a_gap_of_norms_is_blind_to_a_turn_and_the_difference_is_not():
    """Why ``check`` reads both: rounding that points anywhere turns a
    leaf's change without lengthening it."""
    from benchmark.lib import check
    start = {"w": np.zeros(2), "b": np.zeros(2)}
    ref = {"w": np.array([1.0, 0.0]), "b": np.array([0.0, 2.0])}
    turned = {"w": np.array([1.0, 0.01]), "b": np.array([0.0, 2.0])}
    gaps = check.norm_gaps(check._norms(turned, start),
                           check._norms(ref, start))
    diffs = check.diff_norms(turned, ref, start)
    # against the larger of the leaf's own norm (1) and the median leaf's
    # (1.5): the turn reads 5e-5 / 1.5 as a gap and 0.01 / 1.5 as a difference
    assert gaps["['w']"] == pytest.approx(5e-5 / 1.5, rel=1e-3)
    assert diffs["['w']"] == pytest.approx(0.01 / 1.5)
    assert gaps["['b']"] == diffs["['b']"] == 0.0
    # a leaf that did not move reads 1 on both, by its own norm
    stuck = {"w": np.array([1.0, 0.0]), "b": np.zeros(2)}
    assert check.norm_gaps(check._norms(stuck, start),
                           check._norms(ref, start))["['b']"] == 1.0
    assert check.diff_norms(stuck, ref, start)["['b']"] == 1.0
    with pytest.raises(ValueError, match="trees differ"):
        check.diff_norms({"w": np.zeros(2)}, ref, start)

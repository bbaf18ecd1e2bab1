"""Flash-attention backward + causal masking (ops/flash_attention.py).

The reference trains every op it exposes (``minimize`` builds the backward
for the whole graph, cifar10cnn.py:163); round 2's verdict confirmed the
flash path was forward-only — ``jax.grad`` through it crashed, taking any
≥128-token ViT train config down with it. These tests pin the custom_vjp
contract: values AND gradients match the dense XLA reference (fp32
tolerance), causal and non-divisible sequence lengths included, through
the bare kernel, dispatch, ring, Ulysses, and a full ViT train step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from dml_cnn_cifar10_tpu.ops import attention as attn
from dml_cnn_cifar10_tpu.ops import flash_attention as fa


def _qkv(shape, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


def _grads(f, q, k, v):
    # sin() keeps the cotangent non-trivial (varied sign/magnitude).
    return jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))), argnums=(0, 1, 2))(
        q, k, v)


def _assert_close(got, want, atol):
    for name, g, w in zip("qkv", got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=atol,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_xla_s512(causal):
    """VERDICT round-2 done-condition (a): S=512 gradient parity."""
    q, k, v = _qkv((1, 512, 2, 32), seed=1)
    g_flash = _grads(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=causal), q, k, v)
    g_ref = _grads(
        lambda q, k, v: attn.xla_attention(q, k, v, causal=causal), q, k, v)
    _assert_close(g_flash, g_ref, atol=2e-5)


@pytest.mark.slow
@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_ragged_seq(causal):
    """S=300 is not a multiple of any block size: the zero-padded rows and
    masked columns must contribute exactly nothing to every gradient."""
    q, k, v = _qkv((2, 300, 2, 16), seed=2)
    g_flash = _grads(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=causal), q, k, v)
    g_ref = _grads(
        lambda q, k, v: attn.xla_attention(q, k, v, causal=causal), q, k, v)
    _assert_close(g_flash, g_ref, atol=2e-5)


def test_flash_causal_forward_parity():
    q, k, v = _qkv((2, 256, 2, 32), seed=3)
    out = fa.flash_attention(q, k, v, causal=True)
    ref = attn.xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


@pytest.mark.slow
def test_flash_bf16_trains():
    """bf16 inputs: grads come back bf16 and finite, close to the f32 ref."""
    q, k, v = _qkv((1, 256, 2, 32), seed=4, dtype=jnp.bfloat16)
    g = _grads(lambda q, k, v: fa.flash_attention(q, k, v), q, k, v)
    g_ref = _grads(lambda q, k, v: attn.xla_attention(q, k, v),
                   *(t.astype(jnp.float32) for t in (q, k, v)))
    for got, want in zip(g, g_ref):
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want), atol=0.05)


def test_fwd_lse_matches_dense_logsumexp():
    """The saved residual itself: lse == logsumexp(scores) per row."""
    q, k, v = _qkv((1, 256, 2, 16), seed=5)
    _, lse = fa.flash_attention_fwd_lse(q, k, v)
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    want = jnp.transpose(jax.nn.logsumexp(scores, axis=-1), (0, 2, 1))
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want), atol=1e-5)


@pytest.mark.slow
def test_dispatch_attention_differentiates_long_seq():
    """The user-facing face of round 2's confirmed crash: dispatch routes
    ≥128 tokens through the flash kernel, which must now differentiate."""
    q, k, v = _qkv((2, 128, 2, 16), seed=6)
    g = _grads(lambda q, k, v: attn.dispatch_attention(
        q, k, v, use_pallas=True), q, k, v)
    g_ref = _grads(lambda q, k, v: attn.xla_attention(q, k, v), q, k, v)
    _assert_close(g, g_ref, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bwd_standalone_blockwise(causal):
    """flash_attention_bwd (the ring building block) against autodiff of
    the dense reference, driven with an arbitrary upstream cotangent."""
    q, k, v = _qkv((1, 256, 2, 16), seed=7)
    do = jax.random.normal(jax.random.PRNGKey(99), q.shape)
    out, lse = fa.flash_attention_fwd_lse(q, k, v, causal=causal)
    delta = fa.attention_delta(out, do)
    dq, dk, dv = fa.flash_attention_bwd(q, k, v, do, lse, delta,
                                        causal=causal)
    _, vjp = jax.vjp(
        lambda q, k, v: attn.xla_attention(q, k, v, causal=causal), q, k, v)
    _assert_close((dq, dk, dv), vjp(do), atol=2e-5)


@pytest.mark.slow
@pytest.mark.parametrize("sp_mode", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [False, True])
def test_sp_attention_pallas_grads(sp_mode, causal):
    """VERDICT round-2 done-condition (d): ring and Ulysses with
    use_pallas=True differentiate, causal included, on a data×seq mesh."""
    from dml_cnn_cifar10_tpu.parallel import ring_attention as ring
    from dml_cnn_cifar10_tpu.parallel import ulysses

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "seq"))
    # S_local = 128 ≥ the pallas threshold, so the kernels really engage.
    q, k, v = _qkv((2, 256, 4, 16), seed=8)
    sp_fn = ring.ring_attention if sp_mode == "ring" \
        else ulysses.ulysses_attention
    g = _grads(lambda q, k, v: sp_fn(q, k, v, mesh, use_pallas=True,
                                     causal=causal), q, k, v)
    g_ref = _grads(lambda q, k, v: attn.xla_attention(q, k, v,
                                                      causal=causal),
                   q, k, v)
    _assert_close(g, g_ref, atol=3e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_segment_ids_fwd_and_grads(causal):
    """Packed sequences: segment_ids restrict attention to same-segment
    pairs in both directions (packed-causal = the LM batching layout).
    Ragged S=300 on purpose — padded Q rows are segment-mask-exempt so
    their lse stays finite; their grads must still be exactly absent."""
    q, k, v = _qkv((2, 300, 2, 16), seed=10)
    seg = jnp.concatenate(
        [jnp.zeros((2, 100), jnp.int32), jnp.ones((2, 120), jnp.int32),
         jnp.full((2, 80), 2, jnp.int32)], axis=1)
    out = fa.flash_attention(q, k, v, causal=causal, segment_ids=seg)
    ref = attn.xla_attention(q, k, v, causal=causal, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-6)
    g = _grads(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=causal, segment_ids=seg), q, k, v)
    g_ref = _grads(lambda q, k, v: attn.xla_attention(
        q, k, v, causal=causal, segment_ids=seg), q, k, v)
    _assert_close(g, g_ref, atol=2e-5)


def test_segment_isolation_is_exact():
    """Tokens in one segment must see zero influence from another: compare
    a packed two-segment batch against the two segments attended alone."""
    q, k, v = _qkv((1, 256, 2, 16), seed=11)
    seg = jnp.concatenate([jnp.zeros((1, 128), jnp.int32),
                           jnp.ones((1, 128), jnp.int32)], axis=1)
    packed = fa.flash_attention(q, k, v, segment_ids=seg)
    alone_a = fa.flash_attention(q[:, :128], k[:, :128], v[:, :128])
    alone_b = fa.flash_attention(q[:, 128:], k[:, 128:], v[:, 128:])
    np.testing.assert_allclose(np.asarray(packed[:, :128]),
                               np.asarray(alone_a), atol=5e-6)
    np.testing.assert_allclose(np.asarray(packed[:, 128:]),
                               np.asarray(alone_b), atol=5e-6)


@pytest.mark.slow
def test_ring_pallas_causal_bf16_grads():
    """bf16 is the realistic long-context training dtype: the causal ring
    backward's lax.switch once crashed on mismatched branch dtypes (f32
    skip zeros vs bf16 kernel partials). Per-step partials now stay f32
    through the accumulation on both engines."""
    from dml_cnn_cifar10_tpu.parallel import ring_attention as ring

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "seq"))
    q, k, v = _qkv((2, 256, 4, 16), seed=9, dtype=jnp.bfloat16)
    g = _grads(lambda q, k, v: ring.ring_attention(
        q, k, v, mesh, use_pallas=True, causal=True), q, k, v)
    g_ref = _grads(lambda q, k, v: attn.xla_attention(q, k, v, causal=True),
                   *(t.astype(jnp.float32) for t in (q, k, v)))
    for got, want in zip(g, g_ref):
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want), atol=0.05)


@pytest.mark.slow
def test_vit_256_tokens_trains_end_to_end():
    """VERDICT round-2 done-condition (c): the exact crashing config —
    vit_tiny at crop 64 (16×16 patches + cls = 257 tokens ≥128 → pallas
    path) — runs a jitted value_and_grad step with finite loss and
    non-trivial grads."""
    from dml_cnn_cifar10_tpu.config import DataConfig, ModelConfig
    from dml_cnn_cifar10_tpu.models import vit

    mc = ModelConfig(name="vit_tiny", use_pallas_attention=True,
                     logit_relu=False)
    dc = DataConfig(crop_height=64, crop_width=64)
    params = vit.init_params(jax.random.PRNGKey(0), mc, dc)
    imgs = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 64, 3))
    labels = jnp.arange(4) % 10

    def loss_fn(p):
        logits = vit.apply(p, imgs, mc)
        return jnp.mean(-jax.nn.log_softmax(logits)[jnp.arange(4), labels])

    val, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    assert jnp.isfinite(val)
    gsum = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda g: float(jnp.sum(jnp.abs(g))), grads))
    assert gsum > 0.0


@pytest.mark.slow
@pytest.mark.parametrize("sp_mode", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [False, True])
def test_sp_attention_segment_ids(sp_mode, causal):
    """Packed sequences through sequence parallelism: ring carries each
    K/V shard's segment ids around the ring with it; Ulysses all-gathers
    the ids for its full-sequence local kernel. Segment boundaries
    (96/64/96) intentionally straddle the 128-token shard boundary, so
    cross-shard spans are real. Values and grads vs the masked dense
    reference."""
    from dml_cnn_cifar10_tpu.parallel import ring_attention as ring
    from dml_cnn_cifar10_tpu.parallel import ulysses

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "seq"))
    q, k, v = _qkv((2, 256, 4, 16), seed=12)
    seg = jnp.concatenate(
        [jnp.zeros((2, 96), jnp.int32), jnp.ones((2, 64), jnp.int32),
         jnp.full((2, 96), 2, jnp.int32)], axis=1)
    sp_fn = ring.ring_attention if sp_mode == "ring" \
        else ulysses.ulysses_attention
    out = sp_fn(q, k, v, mesh, use_pallas=True, causal=causal,
                segment_ids=seg)
    ref = attn.xla_attention(q, k, v, causal=causal, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-6)
    g = _grads(lambda q, k, v: sp_fn(q, k, v, mesh, use_pallas=True,
                                     causal=causal, segment_ids=seg),
               q, k, v)
    g_ref = _grads(lambda q, k, v: attn.xla_attention(
        q, k, v, causal=causal, segment_ids=seg), q, k, v)
    _assert_close(g, g_ref, atol=5e-5)


def test_cross_length_causal_bwd():
    """kv_len > q_len with causal: trailing K rows have no live Q block,
    and the dK/dV q-side index clamp must stay in range on those
    fully-dead grid rows (review r3 edge case)."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (2, 200, 2, 32))
    k = jax.random.normal(ks[1], (2, 512, 2, 32))
    v = jax.random.normal(ks[2], (2, 512, 2, 32))
    out, lse = fa.flash_attention_fwd_lse(q, k, v, causal=True)
    ref = attn.xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-6)
    do = jax.random.normal(jax.random.PRNGKey(9), q.shape)
    delta = fa.attention_delta(out, do)
    grads = fa.flash_attention_bwd(q, k, v, do, lse, delta, causal=True)
    _, vjp = jax.vjp(
        lambda q, k, v: attn.xla_attention(q, k, v, causal=True), q, k, v)
    _assert_close(grads, vjp(do), atol=5e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_sliding_window(causal):
    """Sliding-window/local attention: the band |row-col| < W (lower half
    only under causal) in both directions, vs the banded dense
    reference; ragged S so padded rows (window-mask-exempt) stay
    finite."""
    q, k, v = _qkv((1, 300, 2, 16), seed=13)
    for W in (64, 200):
        out = fa.flash_attention(q, k, v, causal=causal, window=W)
        ref = attn.xla_attention(q, k, v, causal=causal, window=W)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=5e-6)
        g = _grads(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=causal, window=W), q, k, v)
        g_ref = _grads(lambda q, k, v: attn.xla_attention(
            q, k, v, causal=causal, window=W), q, k, v)
        _assert_close(g, g_ref, atol=2e-5)


@pytest.mark.slow
def test_flash_window_composes_with_segments():
    """window x segment_ids x causal in one kernel call — the packed
    local-attention LM layout."""
    q, k, v = _qkv((2, 256, 2, 16), seed=14)
    seg = jnp.concatenate([jnp.zeros((2, 120), jnp.int32),
                           jnp.ones((2, 136), jnp.int32)], axis=1)
    kw = dict(causal=True, window=48, segment_ids=seg)
    out = fa.flash_attention(q, k, v, **kw)
    ref = attn.xla_attention(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-6)
    g = _grads(lambda q, k, v: fa.flash_attention(q, k, v, **kw), q, k, v)
    g_ref = _grads(lambda q, k, v: attn.xla_attention(q, k, v, **kw),
                   q, k, v)
    _assert_close(g, g_ref, atol=2e-5)


@pytest.mark.parametrize("sp_mode", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [True, False])
def test_sp_attention_window(sp_mode, causal):
    """Sliding-window attention through sequence parallelism (round-4):
    the ring only visits the diagonal and adjacent shards (W <= S_local,
    static kv_start offsets in the block masks); Ulysses passes the
    window to its full-sequence local kernel. S_local=128 clears the
    ring's >=128 Pallas gate, so the PALLAS kv_start path really runs
    (a 64-token shard silently fell back to the jnp engine — round-4
    review finding); W=100 < S_local straddles every shard boundary.
    Values and grads vs the global dense reference."""
    from dml_cnn_cifar10_tpu.parallel import ring_attention as ring
    from dml_cnn_cifar10_tpu.parallel import ulysses

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 4), ("data", "seq"))
    q, k, v = _qkv((1, 512, 4, 16), seed=21)   # 4 heads: ulysses needs
    W = 100                                    # heads % seq_axis == 0
    sp_fn = ring.ring_attention if sp_mode == "ring" \
        else ulysses.ulysses_attention
    out = sp_fn(q, k, v, mesh, use_pallas=True, causal=causal, window=W)
    ref = attn.xla_attention(q, k, v, causal=causal, window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-6)
    g = _grads(lambda q, k, v: sp_fn(q, k, v, mesh, use_pallas=True,
                                     causal=causal, window=W), q, k, v)
    g_ref = _grads(lambda q, k, v: attn.xla_attention(
        q, k, v, causal=causal, window=W), q, k, v)
    _assert_close(g, g_ref, atol=5e-5)


@pytest.mark.parametrize("kv_start", [-192, 0, 192])
def test_flash_kv_start_unaligned_parity(kv_start):
    """kv_start (ring neighbor offsets) with an UNALIGNED kv length
    (192, not a block multiple): the padded-column bound must key on the
    LOCAL column while the window band sees the SHIFTED global column —
    conflating them attends zero-padding (kv_start<0) or masks the whole
    shard (kv_start>0) (round-4 review finding, reproduced both ways)."""
    q, k, v = _qkv((1, 192, 1, 16), seed=31)
    W = 64
    out, lse = fa.flash_attention_fwd_lse(q, k, v, window=W, causal=False,
                                          kv_start=kv_start, block_q=128,
                                          block_k=128)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (16 ** -0.5)
    s = attn.mask_scores(s, 192, 192, window=W, kv_start=kv_start)
    probs = jax.nn.softmax(s, axis=-1)
    live = jnp.max(s, axis=-1, keepdims=True) > -5e29
    probs = jnp.where(live, probs, 0.0)
    ref = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-6)


def test_ring_window_composes_with_segments():
    """window x segment_ids through the ring: the packed local-attention
    LM layout at sequence-parallel scale. Segment boundary (100) and the
    W=40 band both straddle the 64-token shard boundaries."""
    from dml_cnn_cifar10_tpu.parallel import ring_attention as ring

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 4), ("data", "seq"))
    q, k, v = _qkv((2, 256, 2, 16), seed=22)
    seg = jnp.concatenate([jnp.zeros((2, 100), jnp.int32),
                           jnp.ones((2, 156), jnp.int32)], axis=1)
    kw = dict(causal=True, window=40, segment_ids=seg)
    out = ring.ring_attention(q, k, v, mesh, use_pallas=True, **kw)
    ref = attn.xla_attention(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-6)
    g = _grads(lambda q, k, v: ring.ring_attention(
        q, k, v, mesh, use_pallas=True, **kw), q, k, v)
    g_ref = _grads(lambda q, k, v: attn.xla_attention(q, k, v, **kw),
                   q, k, v)
    _assert_close(g, g_ref, atol=5e-5)


def test_ring_window_rejects_oversized_window():
    """W > S_local cannot be dispatched by the adjacent-shard ring switch
    and must fail loudly, not return silently wrong attention."""
    from dml_cnn_cifar10_tpu.parallel import ring_attention as ring

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 4), ("data", "seq"))
    q, k, v = _qkv((1, 256, 2, 16), seed=23)
    with pytest.raises(ValueError, match="exceeds the local shard"):
        ring.ring_attention(q, k, v, mesh, window=65)


def test_window_fully_dead_rows_are_finite_and_inert():
    """A cross-length window geometry can leave Q rows with NO keys at
    all (row - window + 1 >= kv_len). Those rows must emit zeros, not
    NaN, and their (arbitrary) cotangents must not leak into other rows'
    dK/dV — the forward publishes a large lse so the backward's
    p = exp(s - lse) is exactly zero there."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 512, 1, 64))
    k = jax.random.normal(ks[1], (1, 128, 1, 64))
    v = jax.random.normal(ks[2], (1, 128, 1, 64))
    out = fa.flash_attention(q, k, v, window=64)
    assert bool(jnp.all(jnp.isfinite(out)))
    assert bool(jnp.all(out[:, 256:] == 0))       # rows past kv+window
    g = _grads(lambda q, k, v: fa.flash_attention(q, k, v, window=64),
               q, k, v)
    for t in g:
        assert bool(jnp.all(jnp.isfinite(t)))
    # Live-region gradients still match the dense reference exactly
    # (no contamination from the dead rows).
    g_live = _grads(lambda q, k, v: fa.flash_attention(
        q, k, v, window=64)[:, :190], q, k, v)
    g_ref = _grads(lambda q, k, v: attn.xla_attention(
        q, k, v, window=64)[:, :190], q, k, v)
    _assert_close(g_live, g_ref, atol=5e-6)


# --- grouped key/value heads: k, v [B, S, Hk, D] under q [B, S, H, D] -------

def _grouped(group, d, s=256, b=1, hk=2, seed=11, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, s, hk * group, d), dtype),
            jax.random.normal(ks[1], (b, s, hk, d), dtype),
            jax.random.normal(ks[2], (b, s, hk, d), dtype))


def _assert_grouped_equals_repeated(q, k, v, **kw):
    """The kernels on ``k, v`` at their own head count against the same
    kernels on the heads repeated (the program before the kernels took
    groups, and the XLA path's definition): values and all three gradients,
    ``dk`` / ``dv`` the repeated heads' gradients summed over each group."""
    b, s, hk, d = k.shape
    group = q.shape[2] // hk
    out = fa.flash_attention(q, k, v, **kw)
    got = _grads(lambda *a: fa.flash_attention(*a, **kw), q, k, v)
    assert got[1].shape == got[2].shape == (b, s, hk, d)
    # the heads' own gradients, then the float32 sum over each group
    repeated, per_head = jax.vjp(
        lambda q, k, v: fa.flash_attention(q, k, v, **kw),
        q, jnp.repeat(k, group, 2), jnp.repeat(v, group, 2))
    np.testing.assert_allclose(out, repeated, atol=2e-6)
    dq, dk, dv = per_head(jnp.cos(out))
    want = (dq, *(t.reshape(b, s, hk, group, d).sum(3) for t in (dk, dv)))
    _assert_close(got, want, atol=2e-5)


@pytest.mark.parametrize("window", [None, 96])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("group", [1, 4, 8])
def test_grouped_heads_causal(group, d, window):
    _assert_grouped_equals_repeated(*_grouped(group, d), causal=True,
                                    window=window)


@pytest.mark.parametrize("window", [None, 96])
@pytest.mark.parametrize("group", [4, 8])
def test_grouped_heads_ragged_seq(group, window):
    """200 tokens in blocks of 128: the padded rows and columns."""
    _assert_grouped_equals_repeated(*_grouped(group, 64, s=200, b=2, hk=1),
                                    causal=True, window=window)


@pytest.mark.parametrize("group", [4, 8])
def test_grouped_heads_full_attention(group):
    """No mask: the forward and dQ passes walk the rectangular grid, the
    dK/dV pass a schedule with every block live."""
    _assert_grouped_equals_repeated(*_grouped(group, 64, b=2), causal=False)


@pytest.mark.parametrize("causal", [False, True])
def test_grouped_heads_segment_ids(causal):
    q, k, v = _grouped(4, 64, b=2)
    seg = jnp.stack([(jnp.arange(256) >= 100).astype(jnp.int32),
                     (jnp.arange(256) >= 180).astype(jnp.int32)])
    _assert_grouped_equals_repeated(q, k, v, causal=causal, segment_ids=seg)


def test_grouped_heads_bf16_sum_is_rounded_once():
    """bfloat16 operands: the group's ``dk`` / ``dv`` are one float32 sum
    rounded once, so they lie at least as near the float32 gradients as
    the repeat's transpose, a sum of eight bfloat16 arrays in bfloat16."""
    q, k, v = _grouped(8, 64, hk=1, dtype=jnp.bfloat16)
    do = jax.random.normal(jax.random.PRNGKey(5), q.shape, jnp.bfloat16)
    rep = [jnp.repeat(t, 8, 2) for t in (k, v)]
    out, lse = fa.flash_attention_fwd_lse(q, k, v, causal=True)
    delta = fa.attention_delta(out, do)
    _, dk, dv = fa.flash_attention_bwd(q, k, v, do, lse, delta, causal=True)
    assert dk.dtype == dv.dtype == jnp.bfloat16 and dk.shape == k.shape
    heads32 = fa.flash_attention_bwd(q, *rep, do, lse, delta, causal=True,
                                     out_dtype=jnp.float32)[1:]
    heads16 = fa.flash_attention_bwd(q, *rep, do, lse, delta,
                                     causal=True)[1:]
    for got, h32, h16 in zip((dk, dv), heads32, heads16):
        exact = h32.reshape(1, 256, 1, 8, 64).sum(3)
        before = h16.reshape(1, 256, 1, 8, 64).sum(3)   # bfloat16 sum
        err = float(jnp.linalg.norm(got.astype(jnp.float32) - exact))
        err_before = float(jnp.linalg.norm(
            before.astype(jnp.float32) - exact))
        assert err <= err_before and err < 0.004 * float(
            jnp.linalg.norm(exact))


@pytest.mark.parametrize("causal,window", [(True, None), (True, 300),
                                           (False, 300), (False, None)])
@pytest.mark.parametrize("group", [1, 4, 8])
def test_dkv_schedule_visits_each_live_block_once_a_member(group, causal,
                                                           window):
    """Under every key block, exactly the live query blocks of equal head
    counts, ``group`` times over in the members' order, with one
    ``is_first`` (the first tick) and one ``is_last`` (the last)."""
    nq = nk = 7
    sched = fa._fold_schedule(nq, nk, 128, 128, causal, window, "k",
                              group=group)
    live = [[i for i in range(nq)
             if fa._band_live(i * 128, 128, j * 128, 128, causal, window)
             in (None, True)] for j in range(nk)]
    assert all(live)
    if group == 1:
        base = fa._fold_schedule(nq, nk, 128, 128, causal, window, "k")
        if not causal and window is None:
            assert sched is None and base is None
            return
        np.testing.assert_array_equal(sched, base)
        assert sched.shape[0] == 4
    else:
        assert sched.shape == (5, group * sum(map(len, live)))
    ticks = sched.T.tolist()
    for j in range(nk):
        mine = [t for t in ticks if t[0] == j]
        assert ticks.index(mine[0]) + len(mine) - 1 == ticks.index(mine[-1])
        assert [t[1] for t in mine] == live[j] * group
        if group > 1:
            assert [t[4] for t in mine] == [
                r for r in range(group) for _ in live[j]]
        assert [t[2] for t in mine] == [1] + [0] * (len(mine) - 1)
        assert [t[3] for t in mine] == [0] * (len(mine) - 1) + [1]


@pytest.mark.parametrize("entry", ["flash_attention",
                                   "flash_attention_fwd_lse",
                                   "flash_attention_stats",
                                   "flash_attention_bwd"])
def test_head_counts_that_do_not_group_are_refused(entry):
    q = jax.ShapeDtypeStruct((1, 256, 6, 64), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, 256, 4, 64), jnp.float32)
    stat = jax.ShapeDtypeStruct((1, 256, 6), jnp.float32)
    args = (q, kv, kv) + ((q, stat, stat) if entry.endswith("bwd") else ())
    with pytest.raises(ValueError, match=r"6 query heads.* 4 key/value"):
        jax.eval_shape(getattr(fa, entry), *args)


@pytest.mark.parametrize("hk,heads_axis", [(2, "model"), (1, None)])
def test_dispatch_splits_grouped_heads_only_where_both_counts_divide(
        hk, heads_axis):
    """Under a mesh the heads' axis splits ``q`` and ``k, v`` alike, so it
    has to divide the key/value heads too; else the heads are replicated,
    the note says so, and the values are the same."""
    from dml_cnn_cifar10_tpu.ops import kernel_paths
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    group = 4 // hk
    q, k, v = _grouped(group, 16, s=128, b=2, hk=hk)
    with kernel_paths.recording() as rec:
        got = jax.jit(lambda q, k, v: attn.dispatch_attention(
            q, k, v, use_pallas=True, causal=True, mesh=mesh))(q, k, v)
    assert rec["attention"] == (
        f"flash-interpret (128 tokens), {group} query heads a key/value "
        f"head/shard_map[batch/data, heads/{heads_axis}]")
    want = attn.xla_attention(q, jnp.repeat(k, group, 2),
                              jnp.repeat(v, group, 2), causal=True)
    np.testing.assert_allclose(got, want, atol=2e-5)

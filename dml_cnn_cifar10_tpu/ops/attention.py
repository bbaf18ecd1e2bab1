"""Multi-head attention ops.

No reference counterpart (the reference model is attention-free,
``cifar10cnn.py:94-147``, SURVEY §2.3); this backs the ViT-Tiny ladder
config (BASELINE.json) and the long-context machinery
(:mod:`~dml_cnn_cifar10_tpu.parallel.ring_attention`).

Two implementations with one contract::

    attention(q, k, v) -> out          # [B, S, H, D] each

- :func:`xla_attention` — the reference path: one fused
  softmax(QKᵀ/√d)V in pure lax; XLA fuses it well at short sequence
  lengths (ViT on CIFAR is 37 tokens — materializing S×S is optimal there).
- :func:`flash_attention` (ops/flash_attention.py) — blocked online-softmax
  Pallas kernel for long sequences where the S×S score matrix must never
  hit HBM.

``dispatch_attention`` picks per config + backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dml_cnn_cifar10_tpu.ops import kernel_paths
from dml_cnn_cifar10_tpu.ops.layers import (mixed_matmul, rms_norm,
                                            rope_softmax_factor, rotary)
from dml_cnn_cifar10_tpu.utils import platform as platform_lib


NEG_INF = -1e30  # finite: exp(-inf - -inf) would NaN a fully-masked row


def mask_scores(scores: jax.Array, q_len: int, kv_len: int,
                causal: bool = False,
                segment_ids: jax.Array | None = None,
                window: int | None = None,
                kv_start: int = 0) -> jax.Array:
    """Apply the shared attention-validity mask to dense ``[..., Sq, Sk]``
    scores (jnp counterpart of the flash kernels' ``_score_mask``): causal
    keeps col ≤ row; segment_ids [B, S] keep same-segment pairs only
    (``scores`` must then be [B, H, Sq, Sk]). One definition, used by the
    XLA reference path and the ring's jnp block engines, so the masking
    semantics can't drift between the parity-tested implementations.
    ``kv_start`` offsets the columns' global coordinates (ring window
    blocks attend a neighbor shard sitting ``±S_local`` away)."""
    if window is not None and window < 1:
        # Same contract as the flash path: a non-positive window would
        # silently mask EVERY score and softmax would emit uniform
        # garbage.
        raise ValueError(f"window must be >= 1, got {window}")
    row = jnp.arange(q_len)[:, None]
    col = kv_start + jnp.arange(kv_len)[None, :]
    if causal:
        scores = jnp.where(col <= row, scores, NEG_INF)
    if window is not None:
        band = col > row - window
        if not causal:
            band = band & (col < row + window)
        scores = jnp.where(band, scores, NEG_INF)
    if segment_ids is not None:
        if isinstance(segment_ids, (tuple, list)):
            q_seg, kv_seg = segment_ids
        else:
            q_seg = kv_seg = segment_ids
        same = (q_seg[:, :, None] == kv_seg[:, None, :])
        scores = jnp.where(same[:, None, :, :], scores, NEG_INF)
    return scores


def xla_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  scale: float | None = None,
                  causal: bool = False,
                  segment_ids: jax.Array | None = None,
                  window: int | None = None) -> jax.Array:
    """softmax(q kᵀ · scale) v over [B, S, H, D] tensors.

    Computed in float32 regardless of input dtype (softmax in bf16 loses
    mass at S large); output is cast back to q.dtype. ``causal=True``
    masks scores above the diagonal (the flash kernel's contract-identical
    reference for parity tests).

    Rows with NO live key (possible under window/cross-length/segment
    geometries) emit exact zeros, matching the flash kernels' ``_safe_l``
    behavior — a plain softmax over all-NEG_INF scores would instead emit
    a uniform average of V (round-3 advisor finding).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * scale
    scores = mask_scores(scores, q.shape[1], k.shape[1], causal=causal,
                         segment_ids=segment_ids, window=window)
    probs = jax.nn.softmax(scores, axis=-1)
    # A fully-masked row's max is exactly NEG_INF (real scores are many
    # orders of magnitude above it); zero such rows like the flash path.
    live = jnp.max(scores, axis=-1, keepdims=True) > NEG_INF * 0.5
    probs = jnp.where(live, probs, 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def dispatch_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                       use_pallas: bool = False,
                       scale: float | None = None,
                       causal: bool = False,
                       segment_ids: jax.Array | None = None,
                       window: int | None = None,
                       mesh=None) -> jax.Array:
    """Pick the attention impl: Pallas flash kernel when asked for and the
    sequence is long enough to benefit; XLA fused attention otherwise.
    Both paths differentiate (the flash path via its custom_vjp backward
    kernels) and both honor ``causal``.

    ``k`` and ``v`` may hold fewer heads than ``q``, ``[B, S, Hk, D]`` with
    ``H % Hk == 0``: query head ``j`` reads key/value head ``j // (H //
    Hk)``. The flash kernels find it in their index maps and sum a group's
    key/value gradients in float32 before their one store; the XLA path
    repeats the heads, which is the definition the kernels are tested
    against (the repeat's transpose sums the group's gradients). ``v`` may
    be of a head size of its own, and the output is then of that size
    (the step's line says both: ``qk 192 v 128``).

    ``mesh`` is the mesh of the enclosing GSPMD program, if any (callers
    already inside a ``shard_map`` pass none). A compiled ``pallas_call``
    cannot sit bare in a program partitioned over more than one device,
    so there the flash call runs under a ``shard_map`` over the whole
    mesh: batch over ``data``, heads over ``model`` — attention is
    independent per (batch, head), so each device runs the kernel on
    its own slice with no collective. A dim its axis does not divide is
    replicated instead (and the printed path says so); the heads' axis
    has to divide the key/value heads as well as the query heads."""
    b, seq, h, d = q.shape
    hk = k.shape[2]
    group = h // hk
    size = f"({seq} tokens)" + (f", window {window}" if window else "") + (
        f", qk {d} v {v.shape[-1]}" if v.shape[-1] != d else "")
    if not (use_pallas and seq >= 128):
        kernel_paths.note("attention", f"xla {size}")
        if group > 1:
            k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
        return xla_attention(q, k, v, scale=scale, causal=causal,
                             segment_ids=segment_ids, window=window)
    from dml_cnn_cifar10_tpu.ops import flash_attention as fa

    # interpret is passed, not left to resolve inside flash_attention's
    # own jit: it is static there, so it keys that cache.
    interpret = not platform_lib.on_tpu()
    flash = functools.partial(fa.flash_attention, scale=scale,
                              causal=causal, window=window,
                              interpret=interpret)
    path = f"flash{'-interpret' if interpret else ''} {size}" + (
        f", {group} query heads a key/value head" if group > 1 else "")
    if mesh is None or mesh.size == 1:
        kernel_paths.note("attention", path)
        return flash(q, k, v, segment_ids=segment_ids)
    bax = "data" if b % mesh.shape["data"] == 0 else None
    heads_axis = mesh.shape["model"]
    hax = "model" if h % heads_axis == 0 and hk % heads_axis == 0 else None
    kernel_paths.note(
        "attention", f"{path}/shard_map[batch/{bax}, heads/{hax}]")
    qkv = P(bax, None, hax, None)
    return jax.shard_map(
        lambda q, k, v, seg: flash(q, k, v, segment_ids=seg),
        mesh=mesh, in_specs=(qkv, qkv, qkv, P(bax, None)),
        out_specs=qkv, check_vma=False)(q, k, v, segment_ids)


def causal_self_attention(a: jax.Array, p, *, heads: int, kv_heads: int,
                          head_dim: int, rope, low, use_pallas: bool,
                          mesh=None, norm_eps: float | None = None,
                          window: int | None = None) -> jax.Array:
    """The attention sublayer of a decoder over tokens, between its norm
    and its residual add: ``a [B, S, D]`` (float32, normed) -> ``[B, S,
    D]``. ``p`` holds ``wq [D, heads * head_dim]``, ``wk`` and ``wv`` ``[D,
    kv_heads * head_dim]``, ``wo``, and, where the model norms each head's
    query and key, ``q_norm`` / ``k_norm`` (one ``scale [head_dim]`` for
    all heads). ``q = a wq``, ``k = a wk``, ``v = a wv``, no bias; rotary
    positions on ``q`` and ``k`` by the rule ``rope`` (a number, theta, or
    a mapping: ``ops.layers.rope_frequencies``, plain or YaRN); query head
    ``j`` reads key/value head ``j // (heads / kv_heads)``: ``k`` and ``v``
    go to :func:`dispatch_attention` at their own head count (the flash
    kernels find a query head's key/value head in their index maps and sum
    each group's gradient in float32 inside the dK/dV kernel; only the XLA
    path repeats the heads); scores ``q k^T / sqrt(head_dim)`` under the mask
    ``col <= row`` and, with ``window``, also ``col > row - window`` (a
    token sees itself and the ``window - 1`` before it); softmax in
    float32; ``concat(heads) wo``. Products of operands rounded to ``low``,
    summed in float32; norms and rotary float32. One scope a step, under
    the caller's."""
    b, s, _ = a.shape
    with jax.named_scope("qkv"):
        q = mixed_matmul(a, p["wq"], low).reshape(b, s, heads, head_dim)
        k, v = (mixed_matmul(a, p[w], low).reshape(b, s, kv_heads, head_dim)
                for w in ("wk", "wv"))
    if "q_norm" in p:
        with jax.named_scope("qk_norm"):
            q = rms_norm(q, p["q_norm"]["scale"], norm_eps)
            k = rms_norm(k, p["k_norm"]["scale"], norm_eps)
    with jax.named_scope("rotary"):
        q, k = (rotary(t, rope).astype(low) for t in (q, k))
    with jax.named_scope("flash"):
        o = dispatch_attention(q, k, v.astype(low), use_pallas=use_pallas,
                               causal=True, window=window, mesh=mesh)
    with jax.named_scope("out"):
        return mixed_matmul(
            o.reshape(b, s, heads * head_dim).astype(jnp.float32), p["wo"],
            low)


def latent_attention(a: jax.Array, p, *, heads: int, nope_dim: int,
                     rope_dim: int, v_dim: int, rope, low, use_pallas: bool,
                     mesh=None, norm_eps: float = 1e-6) -> jax.Array:
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1) as
    a decoder trains it, between its norm and its residual add: ``a [B,
    S, D]`` (float32, normed) -> ``[B, S, D]``. No query latent. ``p``
    holds ``wq [D, heads (nope_dim + rope_dim)]``, ``wkv_a [D, rank +
    rope_dim]``, ``kv_norm`` (``scale [rank]``), ``wkv_b [rank, heads
    (nope_dim + v_dim)]`` and ``wo [heads v_dim, D]``, no bias:

    - ``q = a wq``, each head ``[q_nope (nope_dim), q_pe (rope_dim)]``;
    - ``[c, k_pe] = a wkv_a``: the latent ``c`` shared by keys and values,
      and ONE rotary key ``k_pe`` for all heads; ``c = rms(c)``;
    - ``c wkv_b``, each head ``[k_nope (nope_dim), v (v_dim)]``;
    - rotary by the rule ``rope`` on ``q_pe`` and ``k_pe`` alone
      (rotate-half over the ``rope_dim`` slice, ``ops.layers.rotary``),
      ``q = [q_nope, q_pe]``, ``k = [k_nope, k_pe]`` with ``k_pe``
      broadcast to every head;
    - causal softmax of ``q k^T`` times ``(nope_dim + rope_dim)^-0.5 x
      ops.layers.rope_softmax_factor(rope)`` (YaRN's ``mscale_all_dim``),
      in float32, times ``v``; ``o = concat(heads) wo``.

    Queries and keys are ``nope_dim + rope_dim`` wide and values
    ``v_dim``: :func:`dispatch_attention` takes them at their own widths
    (the flash kernels too), and nothing is padded. Products of operands
    rounded to ``low``, summed in float32; the norm and rotary float32.
    Scopes ``q``, ``kv_down``, ``kv_norm``, ``kv_up``, ``rotary``,
    ``flash``, ``out`` under the caller's."""
    b, s, _ = a.shape
    rank = p["kv_norm"]["scale"].shape[0]
    with jax.named_scope("q"):
        q = mixed_matmul(a, p["wq"], low).reshape(b, s, heads,
                                                  nope_dim + rope_dim)
    with jax.named_scope("kv_down"):
        down = mixed_matmul(a, p["wkv_a"], low)
    with jax.named_scope("kv_norm"):
        c = rms_norm(down[..., :rank], p["kv_norm"]["scale"], norm_eps)
    with jax.named_scope("kv_up"):
        kv = mixed_matmul(c, p["wkv_b"], low).reshape(b, s, heads,
                                                      nope_dim + v_dim)
    with jax.named_scope("rotary"):
        q_pe = rotary(q[..., nope_dim:], rope)
        k_pe = rotary(down[..., None, rank:], rope)
        q = jnp.concatenate([q[..., :nope_dim], q_pe], -1).astype(low)
        k = jnp.concatenate(
            [kv[..., :nope_dim],
             jnp.broadcast_to(k_pe, (b, s, heads, rope_dim))], -1).astype(low)
    scale = (nope_dim + rope_dim) ** -0.5 * rope_softmax_factor(rope)
    with jax.named_scope("flash"):
        o = dispatch_attention(q, k, kv[..., nope_dim:].astype(low),
                               use_pallas=use_pallas, scale=scale,
                               causal=True, mesh=mesh)
    with jax.named_scope("out"):
        return mixed_matmul(
            o.reshape(b, s, heads * v_dim).astype(jnp.float32), p["wo"], low)

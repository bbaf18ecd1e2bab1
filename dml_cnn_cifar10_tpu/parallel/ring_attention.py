"""Ring attention — sequence/context parallelism over the ``seq`` mesh axis.

Long-context support (SURVEY §5 "Long-context / sequence parallelism"; no
reference counterpart — the reference is attention-free with fixed 24×24
inputs, ``cifar10cnn.py:15-18,94-147`` — but sequence parallelism is a
first-class capability of this framework, not an afterthought).

Design (the ring/blockwise-attention recipe): Q, K, V are sharded on the
sequence dimension over the ``seq`` mesh axis. Each device keeps its Q
shard resident and walks the ring: compute blockwise attention of local Q
against the currently-held K/V shard, fold the result into FlashAttention
running statistics (m, l, acc), then ``lax.ppermute`` the K/V shard to the
next ring neighbor. After ``seq`` steps every Q shard has attended to the
full sequence while only ever holding 1/seq of K/V — attention memory per
chip stays O(S·D/seq + block²), and the K/V transfers ride ICI neighbor
links, overlappable with the block compute by XLA's latency-hiding
scheduler.

**Backward is a second ring**, not autodiff through the forward scan
(which would checkpoint every ring step's K/V — O(S) per device, exactly
what the ring exists to avoid). ``ring_attention_local`` carries a
``jax.custom_vjp``: the forward saves only ``(q, k, v, out, lse)`` — all
local, O(S/seq) — and the backward rotates ``(k, v, dk, dv)`` around the
ring. Because the saved ``lse`` is the *global* row logsumexp, each ring
step can rebuild its block's exact softmax probabilities and apply the
standard FlashAttention-2 block backward (``ops.flash_attention.
flash_attention_bwd`` — the Pallas kernels — or a jnp twin for short
shards); per-block dK/dV contributions travel with the visiting shard and
arrive home after the full loop.

Causality: shards are equal-sized and aligned, so a (Q shard i, K/V shard
j) pair is entirely below the diagonal (full attention), entirely above
(skipped — a ``lax.switch`` branch that does no FLOPs, the ~2× causal
saving), or exactly on it (j == i — local causal mask, no offsets needed).

The per-block math has two local engines: plain jnp (each ring step
materializes only the local S/seq × S/seq score block, which XLA fuses
on-chip — right for short shards) or, with ``use_pallas=True`` and shards
≥128, the Pallas flash kernels so even the local block never materializes
its score matrix — the long-context configuration.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


NEG_INF = -1e30


def _block_stats(q, k, v, scale, causal=False, segment_ids=None,
                 window=None, kv_start=0):
    """One blockwise attention piece → (m, l, unnormalized acc).

    q: [B,Sq,H,D]; k,v: [B,Sk,H,D]. Returns per-row stats for the online
    softmax merge: m=[B,H,Sq,1] row max, l=[B,H,Sq,1] sum exp, acc
    [B,Sq,H,D] = exp(s-m)·V. ``causal`` masks above the local diagonal
    (used only for the on-diagonal ring block, where local row/col indices
    align with the global ones).
    """
    from dml_cnn_cifar10_tpu.ops.attention import mask_scores

    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = mask_scores(s, q.shape[1], k.shape[1], causal=causal,
                    segment_ids=segment_ids, window=window,
                    kv_start=kv_start)
    m = jnp.max(s, axis=-1, keepdims=True)            # [B,H,Sq,1]
    p = jnp.exp(s - m)
    # Dead rows (every key masked) have m == NEG_INF, so exp(s - m) = 1
    # for masked entries; zero them so such rows keep l = 0 and the
    # final normalize emits zeros, matching the flash kernels and
    # xla_attention (one dead-row contract across all engines).
    p = jnp.where(s > NEG_INF * 0.5, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)            # [B,H,Sq,1]
    acc = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return m, l, acc


def _merge(m1, l1, a1, m2, l2, a2):
    """Fold two online-softmax partials into one (the flash merge rule)."""
    m = jnp.maximum(m1, m2)
    w1 = jnp.exp(m1 - m)
    w2 = jnp.exp(m2 - m)
    l = l1 * w1 + l2 * w2
    # broadcast [B,H,Sq,1] weights onto [B,Sq,H,D] accumulators
    wa1 = jnp.transpose(w1, (0, 2, 1, 3))
    wa2 = jnp.transpose(w2, (0, 2, 1, 3))
    return m, l, a1 * wa1 + a2 * wa2


def _block_stats_pallas(q, k, v, scale, causal=False, segment_ids=None,
                        window=None, kv_start=0):
    """The same ``(m, l, acc)`` partials as :func:`_block_stats`, computed
    by the Pallas flash kernel (``flash_attention_stats``): the local
    S/seq × S/seq block runs blocked on the MXU with the score matrix
    never leaving VMEM — the long-context ring configuration."""
    from dml_cnn_cifar10_tpu.ops import flash_attention as fa

    acc, m, l = fa.flash_attention_stats(q, k, v, scale=scale,
                                         causal=causal,
                                         segment_ids=segment_ids,
                                         window=window, kv_start=kv_start)
    m_ = jnp.transpose(m, (0, 2, 1))[..., None]       # [B,H,Sq,1]
    l_ = jnp.transpose(l, (0, 2, 1))[..., None]
    return m_, l_, acc                                # acc already f32


def _block_bwd_jnp(q, k, v, do, lse, delta, scale, causal=False,
                   segment_ids=None, window=None, kv_start=0):
    """FlashAttention-2 block backward in plain jnp (the short-shard twin
    of ``ops.flash_attention.flash_attention_bwd``): rebuild the block's
    scores, recover exact probabilities from the global ``lse``
    ([B,Sq,H]), and apply the ``D = rowsum(dO ∘ O)`` softmax Jacobian
    (``delta`` [B,Sq,H])."""
    from dml_cnn_cifar10_tpu.ops.attention import mask_scores

    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * scale
    s = mask_scores(s, q.shape[1], k.shape[1], causal=causal,
                    segment_ids=segment_ids, window=window,
                    kv_start=kv_start)
    lse_t = jnp.transpose(lse, (0, 2, 1))[..., None]      # [B,H,Sq,1]
    delta_t = jnp.transpose(delta, (0, 2, 1))[..., None]  # [B,H,Sq,1]
    p = jnp.exp(s - lse_t)                                # exact probs
    dv = jnp.einsum("bhqk,bqhd->bkhd", p, dof)
    dp = jnp.einsum("bqhd,bkhd->bhqk", dof, vf)
    ds = p * (dp - delta_t) * scale
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, kf)
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, qf)
    return dq, dk, dv


def _zero_partials(b, h, sq, d):
    return (jnp.full((b, h, sq, 1), NEG_INF, jnp.float32),
            jnp.zeros((b, h, sq, 1), jnp.float32),
            jnp.zeros((b, sq, h, d), jnp.float32))


def _ring_perm(nsteps):
    return [(i, (i + 1) % nsteps) for i in range(nsteps)]


def _causal_switch(src, my, full, diag, skip):
    """The shared causal ring-step dispatch: a held shard whose home index
    ``src`` is < ``my`` lies fully below the diagonal (full attention),
    == ``my`` is the diagonal block (local causal mask), > ``my`` is fully
    above (skipped — no FLOPs spent). Shards are equal-sized and aligned,
    so these three cases are exhaustive."""
    branch = jnp.where(src < my, 0, jnp.where(src == my, 1, 2))
    return lax.switch(branch, [full, diag, skip], None)


def _window_switch(src, my, causal, diag, left, right, skip):
    """Ring-step dispatch for sliding-window attention with W ≤ S_local:
    the band ``|row − col| < W`` only ever reaches the IMMEDIATELY
    adjacent shards, so a held shard is the diagonal block (local
    causal+window mask), the left neighbor (columns sit S_local below —
    static ``kv_start=-S_local`` in the block mask), the right neighbor
    (bidirectional windows only, ``kv_start=+S_local``), or fully
    out-of-band (skipped — no FLOPs, no fetch). The W ≤ S_local
    precondition is asserted at the public entry."""
    delta = my - src
    if causal:
        branch = jnp.where(delta == 0, 0, jnp.where(delta == 1, 1, 2))
        return lax.switch(branch, [diag, left, skip], None)
    branch = jnp.where(delta == 0, 0,
                       jnp.where(delta == 1, 1,
                                 jnp.where(delta == -1, 2, 3)))
    return lax.switch(branch, [diag, left, right, skip], None)


# ---------------------------------------------------------------------------
# custom_vjp core. Forward: ring of flash partials, saving (q,k,v,out,lse).
# Backward: second ring rotating (k, v, dk, dv).
# ---------------------------------------------------------------------------


def _ring_fwd_scan(q, k, v, seg, my, axis_name, scale, use_pallas, causal,
                   window=None):
    nsteps = lax.axis_size(axis_name)
    b, sq, h, d = q.shape
    stats = _block_stats_pallas if use_pallas else _block_stats
    perm = _ring_perm(nsteps)
    # Segment ids are sequence-sharded like Q; the K/V shard's ids must
    # travel the ring WITH it (a visiting shard's positions keep their
    # home segments). ~2 bytes/token of extra ppermute traffic.
    kv_seg0 = seg

    def body(carry, t):
        k, v, kv_seg, m, l, acc = carry
        src = (my - t) % nsteps          # home index of the held shard
        pair = None if seg is None else (seg, kv_seg)

        if window is not None:
            bm, bl, bacc = _window_switch(
                src, my, causal,
                lambda _: stats(q, k, v, scale, causal=causal,
                                window=window, segment_ids=pair),
                lambda _: stats(q, k, v, scale, causal=False,
                                window=window, kv_start=-sq,
                                segment_ids=pair),
                lambda _: stats(q, k, v, scale, causal=False,
                                window=window, kv_start=sq,
                                segment_ids=pair),
                lambda _: _zero_partials(b, h, sq, d))
        elif causal:
            bm, bl, bacc = _causal_switch(
                src, my,
                lambda _: stats(q, k, v, scale, causal=False,
                                segment_ids=pair),
                lambda _: stats(q, k, v, scale, causal=True,
                                segment_ids=pair),
                lambda _: _zero_partials(b, h, sq, d))
        else:
            bm, bl, bacc = stats(q, k, v, scale, segment_ids=pair)
        m, l, acc = _merge(m, l, acc, bm, bl, bacc)
        # Rotate K/V one ring hop (neighbor ppermute over ICI). The final
        # rotation returns the shards to their home device, so the carry
        # stays consistent for any caller that reuses K/V.
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        if kv_seg is not None:
            kv_seg = lax.ppermute(kv_seg, axis_name, perm)
        return (k, v, kv_seg, m, l, acc), None

    m0, l0, a0 = _zero_partials(b, h, sq, d)
    (k, v, _, m, l, acc), _ = lax.scan(
        body, (k, v, kv_seg0, m0, l0, a0), jnp.arange(nsteps))
    # Dead rows (no live key on ANY ring step) end with m == NEG_INF —
    # the jnp engine also keeps l = 0 there while the Pallas stats
    # engine may carry garbage l/acc (exp(NEG_INF - NEG_INF) = 1), so
    # the guard keys on m: emit exact zeros and a LARGE lse so the
    # backward's p = exp(s - lse) is exactly 0 — the same dead-row
    # contract as the flash kernels' finalizers (_dead_rows).
    live = m > NEG_INF * 0.5                                  # [B,H,Sq,1]
    l_t = jnp.transpose(l, (0, 2, 1, 3))
    live_t = jnp.transpose(live, (0, 2, 1, 3))
    out = jnp.where(live_t, acc / jnp.maximum(l_t, 1e-30), 0.0)
    out = out.astype(q.dtype)
    lse4 = jnp.where(live, m + jnp.log(jnp.maximum(l, 1e-30)), 1e30)
    lse = jnp.transpose(lse4[..., 0], (0, 2, 1))              # [B,Sq,H]
    return out, lse


# ``my`` (this device's ring position, ``lax.axis_index``) is computed by
# the caller and passed through as a traced argument: a partition-id op
# inside the custom_vjp closed-call body lands outside the SPMD manual
# section on older JAX and fails to partition.
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _ring_core(q, k, v, seg, my, axis_name, scale, use_pallas, causal,
               window):
    out, _ = _ring_fwd_scan(q, k, v, seg, my, axis_name, scale, use_pallas,
                            causal, window=window)
    return out


def _ring_core_fwd(q, k, v, seg, my, axis_name, scale, use_pallas, causal,
                   window):
    out, lse = _ring_fwd_scan(q, k, v, seg, my, axis_name, scale,
                              use_pallas, causal, window=window)
    return out, (q, k, v, seg, my, out, lse)


def _ring_core_bwd(axis_name, scale, use_pallas, causal, window, res, do):
    from dml_cnn_cifar10_tpu.ops import flash_attention as fa

    q, k, v, seg, my, out, lse = res
    nsteps = lax.axis_size(axis_name)
    delta = fa.attention_delta(out, do)               # [B,Sq,H] f32
    perm = _ring_perm(nsteps)

    # Per-step partials are f32 from either engine (out_dtype=f32 keeps
    # the Pallas kernels from quantizing each step to the input dtype
    # before the cross-step accumulation, matching the jnp twin); the
    # carry accumulates in f32 and casts once at the end.
    if use_pallas:
        def block_bwd(k_, v_, causal_local, pair, kv_start=0):
            return fa.flash_attention_bwd(q, k_, v_, do, lse, delta,
                                          scale=scale, causal=causal_local,
                                          out_dtype=jnp.float32,
                                          segment_ids=pair, window=window,
                                          kv_start=kv_start)
    else:
        def block_bwd(k_, v_, causal_local, pair, kv_start=0):
            return _block_bwd_jnp(q, k_, v_, do, lse, delta, scale,
                                  causal=causal_local, segment_ids=pair,
                                  window=window, kv_start=kv_start)

    def body(carry, t):
        k, v, kv_seg, dk, dv, dq = carry
        src = (my - t) % nsteps
        pair = None if seg is None else (seg, kv_seg)

        if window is not None:
            sq_ = q.shape[1]
            dq_c, dk_c, dv_c = _window_switch(
                src, my, causal,
                lambda _: block_bwd(k, v, causal, pair),
                lambda _: block_bwd(k, v, False, pair, kv_start=-sq_),
                lambda _: block_bwd(k, v, False, pair, kv_start=sq_),
                lambda _: (jnp.zeros_like(dq), jnp.zeros_like(dk),
                           jnp.zeros_like(dv)))
        elif causal:
            dq_c, dk_c, dv_c = _causal_switch(
                src, my,
                lambda _: block_bwd(k, v, False, pair),
                lambda _: block_bwd(k, v, True, pair),
                lambda _: (jnp.zeros_like(dq), jnp.zeros_like(dk),
                           jnp.zeros_like(dv)))
        else:
            dq_c, dk_c, dv_c = block_bwd(k, v, False, pair)
        dq = dq + dq_c
        # dK/dV partials travel WITH the visiting shard: after n hops they
        # have collected a contribution on every device and are home.
        dk = dk + dk_c
        dv = dv + dv_c
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        if kv_seg is not None:
            kv_seg = lax.ppermute(kv_seg, axis_name, perm)
        dk = lax.ppermute(dk, axis_name, perm)
        dv = lax.ppermute(dv, axis_name, perm)
        return (k, v, kv_seg, dk, dv, dq), None

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    (k, v, _, dk, dv, dq), _ = lax.scan(
        body, (k, v, seg, dk0, dv0, dq0), jnp.arange(nsteps))
    dseg = jax.tree.map(
        lambda s: np.zeros(s.shape, jax.dtypes.float0), seg)
    dmy = np.zeros((), jax.dtypes.float0)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dseg, dmy)


_ring_core.defvjp(_ring_core_fwd, _ring_core_bwd)


def ring_attention_local(q: jax.Array, k: jax.Array, v: jax.Array,
                         axis_name: str, scale: Optional[float] = None,
                         use_pallas: bool = False,
                         causal: bool = False,
                         segment_ids: Optional[jax.Array] = None,
                         window: Optional[int] = None,
                         my: Optional[jax.Array] = None
                         ) -> jax.Array:
    """Per-device body: runs under ``shard_map`` with Q/K/V sequence-sharded
    on ``axis_name``. Shapes [B, S_local, H, D] → [B, S_local, H, D].

    Differentiable (custom_vjp: the backward is a second ring pass with
    O(S/seq) memory — see module docstring). ``use_pallas`` routes each
    local block through the flash kernels when the local shard is long
    enough to benefit (same ≥128 threshold as ``dispatch_attention``);
    ``causal`` masks the global lower triangle and skips above-diagonal
    ring steps entirely. ``segment_ids`` is THIS shard's [B, S_local]
    slice of the packed-sequence ids; visiting K/V shards bring their
    own ids around the ring. ``window`` is the sliding-window band
    (global coordinates, same semantics as the flash kernels); it must
    satisfy ``window <= S_local`` so the band reaches at most the
    adjacent ring shard (see :func:`_window_switch`)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if window is not None and window > q.shape[1]:
        raise ValueError(
            f"ring window {window} exceeds the local shard length "
            f"{q.shape[1]}; the ring dispatch only visits adjacent "
            f"shards. Use fewer seq-axis devices (longer shards) or a "
            f"smaller window.")
    if my is None:
        my = lax.axis_index(axis_name)
    return _ring_core(q, k, v, segment_ids, my,
                      axis_name, float(scale),
                      bool(use_pallas and q.shape[1] >= 128), bool(causal),
                      None if window is None else int(window))


def sp_partition_spec(mesh: Mesh, axis_name: str, seq_len: int,
                      num_heads: int):
    """The shared sequence-parallel layout rule → ``(spec, head_axis)``.

    ``[B, S, H, D]`` partition spec for any SP attention kernel (ring or
    Ulysses): batch over ``data``, sequence over ``axis_name``. Heads are
    batch-like inside the local bodies, so when the mesh also has a
    nontrivial ``model`` (tensor-parallel) axis the heads dim shards over
    it — sp × tp compose with zero resharding at the kernel edge. When the
    head count doesn't divide the axis (e.g. default ViT-Ti's 3 heads on
    model=2), fall back to replicated heads: correct, just an all-gather
    at the kernel edge instead of a free composition. Raises on a sequence
    length the ``seq`` axis can't split.
    """
    nseq = mesh.shape[axis_name]
    if seq_len % nseq:
        raise ValueError(
            f"sequence length {seq_len} not divisible by seq axis {nseq}")
    nmodel = mesh.shape.get("model", 1)
    head_axis = "model" if nmodel > 1 and num_heads % nmodel == 0 else None
    return P("data", axis_name, head_axis, None), head_axis


def sp_shard_map(local_fn, mesh: Mesh, axis_name: str, seq_len: int,
                 num_heads: int, with_segments: bool = False,
                 extra_in_specs=()):
    """Wrap an SP-local attention body in the standard shard_map: one
    ``(q, k, v[, segment_ids]) -> out`` callable with all tensors laid
    out per :func:`sp_partition_spec` (segment ids, when present, shard
    ``[B, S]`` as ``(data, axis_name)`` — the same sequence split).
    ``extra_in_specs`` appends specs for trailing positional inputs."""
    spec, _ = sp_partition_spec(mesh, axis_name, seq_len, num_heads)
    in_specs = (spec, spec, spec)
    if with_segments:
        in_specs += (P("data", axis_name),)
    in_specs += tuple(extra_in_specs)
    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=spec,
        check_vma=False,
    )


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                   scale: Optional[float] = None,
                   axis_name: str = "seq",
                   use_pallas: bool = False,
                   causal: bool = False,
                   segment_ids: Optional[jax.Array] = None,
                   window: Optional[int] = None) -> jax.Array:
    """Sequence-parallel attention over the mesh's ``seq`` axis.

    Global-view entrypoint: [B, S, H, D] arrays (sharded or not); S must be
    divisible by the ``seq`` axis size. Batch stays sharded on ``data`` so
    dp × sp compose. ``use_pallas`` runs each local block on the Pallas
    flash kernels (long-shard configs); ``causal`` applies the global
    lower-triangular mask with above-diagonal ring steps skipped;
    ``segment_ids`` [B, S] int32 (global view, sharded like the sequence)
    restricts attention to same-segment pairs — packed sequences through
    the ring.
    """
    kw = dict(axis_name=axis_name, scale=scale, use_pallas=use_pallas,
              causal=causal, window=window)
    # The ring position rides in as a sequence-sharded iota (each
    # device's shard IS its index) instead of ``lax.axis_index``: a
    # partition-id op inside the body fails SPMD partitioning under an
    # outer jit on older JAX (it lands in a non-inlined called
    # computation).
    pos = jnp.arange(mesh.shape[axis_name], dtype=jnp.int32)
    if segment_ids is None:
        def local(q, k, v, pos):
            return ring_attention_local(q, k, v, my=pos[0], **kw)
        args = (q, k, v, pos)
    else:
        def local(q, k, v, seg, pos):
            return ring_attention_local(q, k, v, segment_ids=seg,
                                        my=pos[0], **kw)
        args = (q, k, v, segment_ids.astype(jnp.int32), pos)
    fn = sp_shard_map(local, mesh, axis_name, q.shape[1], q.shape[2],
                      with_segments=segment_ids is not None,
                      extra_in_specs=(P(axis_name),))
    return fn(*args)


def sequence_sharding(mesh: Mesh) -> NamedSharding:
    """[B, S, H, D] sharding: batch over ``data``, sequence over ``seq``."""
    return NamedSharding(mesh, P("data", "seq", None, None))

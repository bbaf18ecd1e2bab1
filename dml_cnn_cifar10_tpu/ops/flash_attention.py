"""Blocked online-softmax attention — the Pallas TPU kernels, forward AND
backward.

The long-sequence attention path (SURVEY §5 "long-context"; BASELINE.json
ViT config "attention via Pallas"). The S×S score matrix never
materializes in HBM in either direction:

- forward: walk K/V blocks per Q block keeping the FlashAttention running
  statistics (row max ``m``, normalizer ``l``, unnormalized accumulator
  ``acc``) in VMEM scratch; emit the output and, for autodiff, the row
  logsumexp ``lse = m + log l``.
- backward (the FlashAttention-2 recompute form): two kernels that rebuild
  each score block from Q/K and the saved ``lse`` (so ``p = exp(s − lse)``
  is the exact softmax probability without storing it), using the
  ``D = rowsum(dO ∘ O)`` identity for the softmax Jacobian:
  * dQ kernel — grid (b·h, q_blocks, k_blocks): accumulates
    ``dQ_i = Σ_j dS_ij K_j · scale`` in VMEM scratch;
  * dK/dV kernel — grid (b·h, k_blocks, q_blocks): accumulates
    ``dV_j = Σ_i P_ijᵀ dO_i`` and ``dK_j = Σ_i dS_ijᵀ Q_i · scale``.

Grouped key/value heads: ``k`` and ``v`` may come with fewer heads than
``q``, ``[B, S, Hk, D]`` with ``H % Hk == 0``; query head ``j`` reads
key/value head ``j // (H // Hk)``. Nothing is repeated: the forward and
dQ passes find the key/value head in their index maps, and the dK/dV
pass runs over the ``B·Hk`` key/value heads, each key block visiting the
whole group's query blocks before its one store, so a group's gradients
are summed in the float32 scratch. Equal head counts are the same program
as before the kernels took groups.

``flash_attention`` carries a ``jax.custom_vjp`` wiring the three kernels
together, so the whole long-context stack (ViT blocks, Ulysses all-to-all
attention, ring attention's per-block engine) differentiates. The
reference trains every op it exposes (``minimize`` builds the backward for
the whole graph, ``cifar10cnn.py:163``); this gives the flash path the
same property.

``causal=True`` applies a lower-triangular mask inside the kernels and
*skips* score blocks strictly above the diagonal (``@pl.when`` on the
block indices — on TPU the grid runs sequentially per core, so a skipped
block really is ~free), recovering the ~2× FLOP saving causal attention
allows in both directions.

Grid = (batch·heads, outer_blocks, inner_blocks), inner fastest-varying.
On TPU the grid is executed sequentially per core, so VMEM scratch carries
running state across the inner iterations of one outer block;
``@pl.when(inner == 0)`` resets it and the last inner iteration writes the
finished tile. Scores and all accumulators are f32 (VPU/MXU accumulate
dtype) regardless of input dtype.

On non-TPU backends the same kernels run under the Pallas interpreter
(tests exercise them on CPU); ``ops.attention.dispatch_attention`` routes
short sequences to the fused XLA path where materializing S×S is faster.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from dml_cnn_cifar10_tpu.utils import platform as platform_lib

NEG_INF = -1e30  # not -inf: exp(-inf - -inf) would NaN the first block

# ---------------------------------------------------------------------------
# Layout helpers. Per-row statistics (m, l, lse, delta) live in [rows, 128]
# f32 tiles with only lane column 0 meaningful: (8, 128) is the minimum f32
# TPU tile, and keeping stats sublane-oriented means the kernels read
# ``ref[:, :1]`` — a [rows, 1] slice that broadcasts against [rows, cols]
# score blocks with no lane→sublane transpose.
# ---------------------------------------------------------------------------


def _resolve(q, scale, block_q, block_k, interpret, v=None):
    """Fill in the static kernel parameters from the input shapes (the
    block size from the wider of a query's and a value's row)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = not platform_lib.on_tpu()
    s = q.shape[1]
    # Auto block size (None), chosen from what the call can see: the
    # sequence length and the bytes in one head row. What Mosaic in
    # libtpu 0.0.34 accepted on a TPU v5e inside the real ViT-Tiny train
    # step, head dim 64, forward and both backward kernels (my chip
    # runs, PR 21):
    # - S < 2048: 128. S=257 (64-px crops) compiled and ran in f32, on
    #   one chip and under the dispatch's shard_map on four.
    # - S >= 2048, rows of <= 128 bytes (head dim 64 in bf16): 1024.
    #   S=2117 (184-px crops) compiled and ran, full and causal W=1024.
    # - S >= 2048, wider rows (head dim 64 in f32, head dim 128): 512.
    #   S=2117 in f32 compiled and ran at 512, full and causal W=1024.
    #   At 1024 the same f32 kernels compiled in a bare fwd+bwd call but
    #   not inside the train step: XLA refused the dK/dV kernel with
    #   "Ran out of memory in memory space vmem while allocating on
    #   stack". That is the default 16 MiB scoped-VMEM limit, not the
    #   chip: with vmem_limit_bytes=64 MiB the step compiled and ran.
    #   (The [block, block] f32 score/probability intermediates do not
    #   shrink with the input dtype; 2048 was not retried here.)
    # Which of the accepted sizes is fastest, and the earlier findings
    # that asymmetric folds (bq != bk) and in-tile K-half gating lose to
    # symmetric blocks on the W=1024 causal band, were measured on an
    # earlier chip only: not measured on the current one.
    width = q.shape[-1] if v is None else max(q.shape[-1], v.shape[-1])
    auto = auto_block(s, width * q.dtype.itemsize)
    block_q = auto if block_q is None else block_q
    block_k = auto if block_k is None else block_k
    return float(scale), block_q, block_k, interpret


def auto_block(seq: int, row_bytes: int) -> int:
    """The block size a call that names none runs at (see
    :func:`_resolve`'s notes)."""
    return (1024 if row_bytes <= 128 else 512) if seq >= 2048 else 128


def _static_kv_start(kv_start):
    """``kv_start`` parameterizes the Python-level schedule and mask
    construction, so it MUST be a static int — a traced value would
    reach ``_fold_schedule``'s lru_cache (TypeError) or silently bake
    wrong masks. The ring passes ``±S_local`` from static shapes; any
    traced value is a caller bug worth a clear message."""
    if isinstance(kv_start, jax.core.Tracer):
        raise TypeError(
            "kv_start must be a static Python int (it selects the block "
            "schedule and mask offsets at trace time); got a traced "
            "value. Pass shard offsets from static shapes, e.g. "
            "q.shape[1].")
    return int(kv_start)


def _group(q, k):
    """``H // Hk`` of ``q [B, S, H, D]`` over ``k [B, Skv, Hk, D]``: query
    head ``j`` reads key/value head ``j // group``. A fact of the shapes,
    not an argument; 1 is plain multi-head attention."""
    h, hk = q.shape[2], k.shape[2]
    if hk < 1 or h % hk:
        raise ValueError(
            f"{h} query heads do not divide into groups over {hk} "
            f"key/value heads: the first must be a multiple of the second")
    return h // hk


def _to_bh(x, block, group=1):
    """[B, S, H, D] → [B·H, S_padded, D], S padded to a ``block`` multiple.
    The key/value side of a call with grouped heads (``group`` > 1) keeps
    batch and heads apart, ``[B, Hk, S_padded, D]``: every 3-D
    ``[rows, S, D]`` operand or result of a kernel is then a query-side
    one, ``rows = B·H``, which is what a reader of the kernel's trace event
    takes for the work done."""
    b, s, h, d = x.shape
    x = jnp.transpose(x, (0, 2, 1, 3))
    if group == 1:
        x = x.reshape(b * h, s, d)
    pad = (-s) % block
    if pad:
        x = jnp.pad(x, ((0, 0),) * (x.ndim - 2) + ((0, pad), (0, 0)))
    return x


def _kv_block(bk, d, group):
    """Block shape of one key/value head's ``[bk, D]`` tile in
    :func:`_to_bh`'s layout; the kernels see ``[1, bk, D]`` either way."""
    return (1, bk, d) if group == 1 else (None, 1, bk, d)


def _from_bh(x, b, s, h):
    """[B·H, S_padded, ...] → [B, S, H, ...]."""
    x = x[:, :s]
    x = x.reshape(b, h, s, *x.shape[2:])
    return jnp.swapaxes(x, 1, 2)


def _kv_from_bh(x, b, s, hk):
    """:func:`_to_bh`'s key/value layout → [B, S, Hk, D]."""
    if x.ndim == 3:
        return _from_bh(x, b, s, hk)
    return jnp.swapaxes(x[:, :, :s], 1, 2)


def _stat_to_tile(x, block):
    """[B, S, H] f32 stat → [B·H, S_padded, 128] tile (lane col 0)."""
    b, s, h = x.shape
    t = jnp.transpose(x, (0, 2, 1)).reshape(b * h, s)
    pad = (-s) % block
    if pad:
        t = jnp.pad(t, ((0, 0), (0, pad)))
    return jnp.pad(t[:, :, None], ((0, 0), (0, 0), (0, 127)))


# ---------------------------------------------------------------------------
# Forward kernels.
# ---------------------------------------------------------------------------


def _score_mask(shape, *, kv_len, q_len, row0, col0, causal,
                qseg=None, kseg=None, window=None,
                kv_aligned=False, q_aligned=False, col_shift=0):
    """The shared validity mask for one [bq, bk] score block: padded K/V
    columns off; optionally causal (col ≤ row in global coordinates);
    optionally same-segment only (packed sequences); optionally a
    sliding window (band |row − col| < window; with causal only the
    lower half remains — Mistral-style local attention). Padded Q rows
    (row ≥ q_len) are *exempt* from the segment and window masks so
    every padded row keeps l > 0 — their lse stays finite, and their
    gradient contributions vanish anyway because dO is zero-padded.

    ``kv_aligned``/``q_aligned`` are compile-time facts from the caller
    (sequence length divides the block size): they elide the padded-col
    bound and the pad-row exemption entirely — the masked variants'
    whole chain runs fused on the VPU, so dropping terms buys real
    per-tick time on the aligned (common, benchmarked) geometry.

    ``col0`` is the LOCAL column base (block offset into the K/V array
    — the padded-column bound keys on it), while ``col_shift`` is the
    ring-window global displacement (``kv_start``) that only the
    positional (causal/window) comparisons see: a visiting ring shard's
    columns sit ``±S_local`` away in global coordinates, but its array
    padding is at its own local tail (round-4 review finding)."""
    col = None
    mask = None
    if not kv_aligned:
        col_local = col0 + lax.broadcasted_iota(jnp.int32, shape, 1)
        mask = col_local < kv_len
        col = col_local + col_shift
    if causal or window is not None:
        if col is None:
            col = (col0 + col_shift
                   + lax.broadcasted_iota(jnp.int32, shape, 1))
        row = row0 + lax.broadcasted_iota(jnp.int32, shape, 0)
    pad_row = None
    if not q_aligned and (window is not None or qseg is not None):
        if causal or window is not None:
            pad_row = row >= q_len
        else:
            pad_row = (row0 + lax.broadcasted_iota(jnp.int32, shape, 0)
                       >= q_len)

    def _and(m, term):
        return term if m is None else m & term

    if causal:
        mask = _and(mask, col <= row)
    if window is not None:
        band = col > row - window
        if not causal:
            band = band & (col < row + window)
        mask = _and(mask, band if pad_row is None else (band | pad_row))
    if qseg is not None:
        same = qseg == kseg
        mask = _and(mask, same if pad_row is None else (same | pad_row))
    return mask


def _flash_update(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, kv_len: int, q_len: int, block_q: int,
                  block_k: int, causal: bool, window=None, kv_start=0,
                  qseg_ref=None, kseg_ref=None, coords=None):
    """One K/V-block update of the running (m, l, acc) — shared by the
    plain, lse-emitting, and stats-emitting kernels.

    ``coords``: ``(ib, kb, init)`` for the folded (live-blocks-only)
    schedule — block coordinates come from the prefetched schedule and
    every tick is live; ``None`` for the rectangular grid, where they
    derive from the program ids and dead band blocks are skipped."""
    if coords is None:
        ib = pl.program_id(1)
        kb = pl.program_id(2)
        init = kb == 0
        first_tick = (pl.program_id(0) == 0) & (ib == 0) & init
    else:
        ib, kb, init = coords
        first_tick = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)

    @pl.when(first_tick)
    def _zero_all():
        # Once per launch: VMEM scratch starts as garbage that could be
        # NaN/Inf, which the alpha=0 rescale below cannot kill (0·NaN).
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(init)
    def _init():
        # Per-row init only resets the row max (column 0 is all the
        # kernels read). l/acc keep the PREVIOUS row's values: the first
        # live tick has alpha = exp(NEG_INF − m_cur) = 0, which zeroes
        # the stale state for free. Rows that never go live keep m ==
        # NEG_INF and finalize through the _dead_rows guard, so their
        # stale l/acc are never observable.
        m_scr[:, :1] = jnp.full_like(m_scr[:, :1], NEG_INF)

    def _update():
        q = q_ref[0]                      # [bq, d]
        k = k_ref[0]                      # [bk, d]
        v = v_ref[0]                      # [bk, d]

        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        mask = _score_mask(
            s.shape, kv_len=kv_len, q_len=q_len, row0=ib * block_q,
            col0=kb * block_k, col_shift=kv_start, causal=causal,
            window=window,
            qseg=None if qseg_ref is None else qseg_ref[0][:, :1],
            kseg=None if kseg_ref is None else kseg_ref[0, :1],
            kv_aligned=kv_len % block_k == 0,
            q_aligned=q_len % block_q == 0)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]                                   # [bq, 1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        # Dead rows (EVERY key masked so far) keep m_cur == NEG_INF, so
        # exp(s - m_cur) = exp(0) = 1 for their masked entries and l/acc
        # accumulate garbage (masked entries in live-max rows underflow
        # to exactly 0, so only dead rows are affected). Rather than a
        # per-tick select on p, the finalizers detect dead rows by
        # ``m == NEG_INF`` and emit zeros + a LARGE lse — see _dead_rows.
        p = jnp.exp(s - m_cur)                                  # [bq, bk]
        l_scr[:, :1] = (l_scr[:, :1] * alpha
                        + jnp.sum(p, axis=-1, keepdims=True))
        acc_scr[:] = acc_scr[:] * alpha + lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:, :1] = m_cur

    if coords is not None:
        # Folded schedule: every tick IS a live block by construction
        # (or a dead placeholder whose element mask kills everything and
        # whose row finalizes to zeros via _dead_rows).
        _update()
        return
    live = _band_live(ib * block_q, block_q, kv_start + kb * block_k,
                      block_k, causal, window)
    if live is not None:
        @pl.when(live)
        def _live():
            _update()
    else:
        _update()


def _unpack(refs, n_out, has_segments, n_base=3):
    """Split a kernel's positional refs into (base inputs…, qseg, kseg),
    outs, scratch. ``n_base`` is the count of always-present inputs (3 for
    the forward kernels: q/k/v; 6 for the backward: +do/lse/delta); the
    two segment-id refs are only present when asked for, so the
    non-segmented path pays zero extra bandwidth."""
    n_in = n_base + (2 if has_segments else 0)
    ins, outs, scratch = refs[:n_in], refs[n_in:n_in + n_out], \
        refs[n_in + n_out:]
    if not has_segments:
        ins = ins + (None, None)
    return ins, outs, scratch


def _safe_l(l_col):
    """Divide-by-zero guard for the normalizer: fully-dead rows (every
    block skipped — window/cross-length geometries) keep l == 0 and the
    plain division would emit NaN that poisons the backward."""
    return jnp.maximum(l_col, 1e-30)


def _dead_rows(m_col):
    """Dead-row predicate at finalize time: a row with NO live key ever
    (blocks skipped by the schedule, or visited but fully masked —
    segment/window geometries) still has ``m == NEG_INF``; any live
    score is many orders of magnitude above NEG_INF/2. Visited-but-dead
    rows accumulate garbage (``exp(NEG_INF − NEG_INF) = 1`` per masked
    entry ⇒ l = #keys, acc = Σ V), so the finalizers must zero their
    output and publish a LARGE lse — otherwise the backward's
    ``p = exp(s − lse)`` becomes 1/#keys and leaks gradient into dK/dV
    (round-3 advisor finding, extended to the visited-block case)."""
    return m_col <= NEG_INF * 0.5


def _fold_coords(refs, folded):
    """Split off the prefetched schedule ref (folded mode) and derive
    ``(remaining_refs, coords, last)``: coords feed ``_flash_update``,
    ``last`` gates the finalizer. Rect mode reads the program ids."""
    if not folded:
        return refs, None, pl.program_id(2) == pl.num_programs(2) - 1
    info_ref, refs = refs[0], refs[1:]
    t = pl.program_id(1)
    coords = (info_ref[0, t], info_ref[1, t], info_ref[2, t] == 1)
    return refs, coords, info_ref[3, t] == 1


def _flash_kernel(*refs, has_segments: bool = False, folded: bool = False,
                  **kw):
    refs, coords, last = _fold_coords(refs, folded)
    (q_ref, k_ref, v_ref, qseg_ref, kseg_ref), (o_ref,), \
        (m_scr, l_scr, acc_scr) = _unpack(refs, 1, has_segments)
    _flash_update(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                  qseg_ref=qseg_ref, kseg_ref=kseg_ref, coords=coords, **kw)

    @pl.when(last)
    def _finalize():
        o = acc_scr[:] / _safe_l(l_scr[:, :1])
        o_ref[0] = jnp.where(_dead_rows(m_scr[:, :1]), 0.0,
                             o).astype(o_ref.dtype)


def _flash_fwd_kernel(*refs, has_segments: bool = False,
                      folded: bool = False, **kw):
    """Forward that additionally emits the row logsumexp — the single
    statistic the FlashAttention-2 backward needs."""
    refs, coords, last = _fold_coords(refs, folded)
    (q_ref, k_ref, v_ref, qseg_ref, kseg_ref), (o_ref, lse_ref), \
        (m_scr, l_scr, acc_scr) = _unpack(refs, 2, has_segments)
    _flash_update(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                  qseg_ref=qseg_ref, kseg_ref=kseg_ref, coords=coords, **kw)

    @pl.when(last)
    def _finalize():
        o = acc_scr[:] / _safe_l(l_scr[:, :1])
        o_ref[0] = jnp.where(_dead_rows(m_scr[:, :1]), 0.0,
                             o).astype(o_ref.dtype)
        # The stat computes on column 0 ONLY (a [bq, 1] log instead of a
        # full-tile one — the [bq, 128] log was ~45 % of a short row's
        # finalize cost) and broadcast-stores across the tile; only
        # col 0 is ever read back. Dead rows publish a LARGE lse so the
        # backward's p = exp(s − lse) is exactly 0 (see _dead_rows).
        m_col = m_scr[:, :1]
        lse_col = jnp.where(_dead_rows(m_col), 1e30,
                            m_col + jnp.log(_safe_l(l_scr[:, :1])))
        lse_ref[0] = jnp.broadcast_to(lse_col, lse_ref.shape[1:])


def _flash_stats_kernel(*refs, has_segments: bool = False,
                        folded: bool = False, **kw):
    """Like ``_flash_kernel`` but emits the raw running state — f32
    UNNORMALIZED accumulator plus row max ``m`` and normalizer ``l`` —
    the partial-softmax interface the ring-attention merge rule needs
    (parallel/ring_attention.py). Emitting ``acc_scr`` directly keeps the
    partial in f32 regardless of input dtype (normalizing to the input
    dtype and re-multiplying by ``l`` would quantize every ring step's
    partial)."""
    refs, coords, last = _fold_coords(refs, folded)
    (q_ref, k_ref, v_ref, qseg_ref, kseg_ref), (acc_ref, m_ref, l_ref), \
        (m_scr, l_scr, acc_scr) = _unpack(refs, 3, has_segments)
    _flash_update(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                  qseg_ref=qseg_ref, kseg_ref=kseg_ref, coords=coords, **kw)

    @pl.when(last)
    def _finalize():
        acc_ref[0] = acc_scr[:]
        # Only m_scr[:, :1] is ever written (the per-row init); lanes
        # 1..127 are launch-lifetime VMEM garbage — broadcast the col-0
        # stat so the published tile has no uninitialized values (a NaN
        # scanner or a future full-tile consumer would otherwise see
        # garbage; round-4 advisor). l_scr's lanes 1..127 were zeroed by
        # _zero_all and never touched again, so l publishes clean as-is.
        m_ref[0] = jnp.broadcast_to(m_scr[:, :1], m_ref.shape[1:])
        l_ref[0] = l_scr[:]


def _seg_tile(seg, block):
    """[B, S] int32 → [B, S_padded, 128] Q-side tile (lane col 0; pad
    value irrelevant — padded rows are mask-exempt)."""
    b, s = seg.shape
    pad = (-s) % block
    if pad:
        seg = jnp.pad(seg, ((0, 0), (0, pad)), constant_values=-1)
    return jnp.pad(seg[:, :, None], ((0, 0), (0, 0), (0, 127)))


def _seg_lane(seg, block):
    """[B, S] int32 → [B, 8, S_padded] K-side lane layout (padded cols
    are already killed by the kv_len mask). The middle dim exists purely
    for TPU tiling: a (1, bk) block of a [B, S] array has a sublane dim
    of 1, which Mosaic rejects for B > 1 (must be divisible by 8 or the
    full dim); an 8-row broadcast makes the block (1, 8, bk) — legal,
    and only row 0 is ever read."""
    pad = (-seg.shape[1]) % block
    if pad:
        seg = jnp.pad(seg, ((0, 0), (0, pad)), constant_values=-1)
    return jnp.broadcast_to(seg[:, None, :],
                            (seg.shape[0], 8, seg.shape[1]))


import numpy as _np


@functools.lru_cache(maxsize=256)
def _fold_schedule(nq, nk, bq, bk, causal, window, major="q", kv_start=0,
                   group=1):
    """The folded (live-blocks-only) grid schedule → int32 ``[4, T]``
    rows ``(outer_block, inner_block, is_first, is_last)`` — or ``None``
    when nothing can be skipped (full attention runs the plain
    rectangular grid: no SMEM prefetch needed).

    ``group`` > 1 is the dK/dV pass over grouped heads: under each outer
    (key) block the live inner blocks come ``group`` times over, once for
    each query head that reads this key/value head, ``is_first`` only on
    the first tick of member 0 and ``is_last`` only on the last of member
    ``group - 1``, so the accumulators sum the whole group before their
    one store. A fifth row names the member, and full attention gets a
    schedule too (every block live): ``[5, T · group]``, never ``None``.

    Instead of walking the full ``outer × inner`` rectangle and
    ``pl.when``-skipping dead band blocks (which still pay per-grid-step
    overhead — round-3 measured dead ticks at ~0.4 µs each, ~45 % of the
    W=1024 forward), the grid's second dimension enumerates ONLY the
    blocks that intersect the causal/window band, flattened row-major:
    ~half the ticks for causal, ``O(W/block)`` per row for a window.
    Block coordinates ride a scalar-prefetch array (SMEM), the standard
    TPU sparse-schedule technique. ``major='q'`` orders by q block
    (forward + dQ kernels), ``'k'`` by k block (dK/dV kernel). An outer
    block with NO live inner block (cross-length geometries) gets one
    placeholder tick — its element mask kills every score, so the row
    finalizes as dead (zero output, LARGE lse). ``kv_start`` shifts
    the K/V columns' global coordinates (ring window steps attend a
    neighbor shard whose columns sit ``±S_local`` away)."""
    if not causal and window is None and group == 1:
        return None
    ticks = []
    n_outer, n_inner = (nq, nk) if major == "q" else (nk, nq)
    for r in range(n_outer):
        cols = []
        for c in range(n_inner):
            i, j = (r, c) if major == "q" else (c, r)
            live = _band_live(i * bq, bq, kv_start + j * bk, bk, causal,
                              window)
            if live is None or bool(live):
                cols.append(c)
        if not cols:
            cols = [0]
        last = (group - 1, len(cols) - 1)
        for member in range(group):
            for n, c in enumerate(cols):
                ticks.append((r, c, int((member, n) == (0, 0)),
                              int((member, n) == last), member))
    return _np.asarray(ticks, _np.int32).T[:4 if group == 1 else 5].copy()


def _band_live(row0, rows, col0, cols, causal, window):
    """Block-liveness predicate for a [rows, cols] score block whose
    top-left is global (row0, col0): does the block intersect the valid
    causal/window band? None when nothing can be skipped. ONE definition
    for all three kernels (fwd, dQ, dK/dV) so the skip logic cannot
    drift from ``_score_mask``'s element mask."""
    live = None
    if causal:
        live = col0 <= row0 + rows - 1
    if window is not None:
        lo = col0 + cols - 1 > row0 - window
        live = lo if live is None else live & lo
        if not causal:
            live = live & (col0 < row0 + rows - 1 + window)
    return live


def _kernel_name(kernel: str, window) -> str:
    return f"flash_{kernel}" if window is None else f"flash_window_{kernel}"


def band_blocks_frac(seq: int, window: int, block: int) -> float:
    """Block pairs the causal schedule with ``window`` visits over those it
    visits without, at ``seq`` tokens in blocks of ``block``: how much of
    the band's saving (``W (W + 1) / 2 + (S - W) W`` pairs of the half
    square's ``S (S + 1) / 2``) the blocks give back. 45 of 136 at 8,192
    tokens, window 1,024, blocks of 512."""
    b = min(block, seq)
    n = -(-seq // b)
    band, whole = (_fold_schedule(n, n, b, b, True, w, "q").shape[1]
                   for w in (window, None))
    return band / whole


def _norm_segments(segment_ids):
    """``None`` | ``[B, S]`` (self-attention) | ``(q_seg, kv_seg)``
    (cross/sharded attention — ring blocks see different shards) →
    ``(q_seg, kv_seg)`` int32 or ``(None, None)``."""
    if segment_ids is None:
        return None, None
    if isinstance(segment_ids, (tuple, list)):
        q_seg, kv_seg = segment_ids
        return q_seg.astype(jnp.int32), kv_seg.astype(jnp.int32)
    seg = segment_ids.astype(jnp.int32)
    return seg, seg


def _index_maps(folded: bool, h: int, q_major: bool = True,
                group: int = 1):
    """The four pallas index maps (q-side, kv-side, and their segment-id
    variants) for one kernel pass — ONE definition so the folded/rect and
    q-major/k-major variants cannot drift (round-4 review finding).

    Folded grids read block coordinates from the prefetched schedule:
    row 0 of the schedule is the OUTER (accumulator) block, row 1 the
    inner — which is (q, k) for the q-major passes (forward, dQ) and
    (k, q) for the k-major dK/dV pass. Rect grids read the grid indices
    directly, whose order is (outer, inner) the same way. Segment maps
    fold the head out of the batch·head grid axis (ids are per batch).

    Grouped heads (``group`` > 1; ``h`` query heads, the key/value side
    ``[B, Hk, S, D]``, see :func:`_to_bh`): the q-major passes' first grid
    axis still runs over the ``B·H`` query heads ``g`` and only the
    key/value side's map changes, to batch ``g // h`` and key/value head
    ``g % h // group``. The k-major pass's first axis runs over the
    ``B·Hk`` key/value heads ``g``; its schedule (always folded,
    :func:`_fold_schedule`) names the group's member ``r`` in row 4 and
    the query side reads head ``g · group + r``."""
    qrow, krow = (0, 1) if q_major else (1, 0)
    if folded:
        q_blk = lambda t, info: info[qrow, t]                 # noqa: E731
        k_blk = lambda t, info: info[krow, t]                 # noqa: E731
    else:
        q_blk = lambda *ij: ij[qrow]                          # noqa: E731
        k_blk = lambda *ij: ij[krow]                          # noqa: E731
    per_batch = h if q_major else h // group   # heads of axis 0 a batch
    q_head = kv_head = lambda g, *a: (g,)                     # noqa: E731
    if group > 1 and q_major:
        kv_head = lambda g, *a: (g // h, g % h // group)      # noqa: E731
    elif group > 1:
        q_head = lambda g, t, info: (g * group + info[4, t],)  # noqa: E731
        kv_head = lambda g, *a: (g // per_batch, g % per_batch)  # noqa: E731
    qi = lambda g, *a: (*q_head(g, *a), q_blk(*a), 0)         # noqa: E731
    kj = lambda g, *a: (*kv_head(g, *a), k_blk(*a), 0)        # noqa: E731
    qi_seg = lambda g, *a: (g // per_batch, q_blk(*a), 0)     # noqa: E731
    kj_seg = lambda g, *a: (g // per_batch, 0, k_blk(*a))     # noqa: E731
    return qi, kj, qi_seg, kj_seg


def _fwd_call(q, k, v, scale, block_q, block_k, interpret, causal,
              mode: str, segment_ids=None, window=None, kv_start=0):
    """Shared forward pallas_call builder.

    mode: "out" → out; "lse" → (out, lse [B,S,H]);
    "stats" → (acc, m, l) — the ring merge interface.
    ``k, v`` [B, Skv, Hk, D] with ``H % Hk == 0``: only their index map
    knows of the group (:func:`_index_maps`). ``v`` may be of a head size
    of its own, ``Dv``, and so is the output.
    ``segment_ids`` [B, S] int32 restricts attention to equal-id pairs
    (packed sequences).
    """
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, d = q.shape
    dv = v.shape[-1]
    group = _group(q, k)
    kv_len = k.shape[1]
    bq, bk = min(block_q, s), min(block_k, kv_len)

    qb = _to_bh(q, bq)
    kb_ = _to_bh(k, bk, group)
    vb = _to_bh(v, bk, group)
    spq, spk = qb.shape[1], kb_.shape[-2]
    nq, nk = spq // bq, spk // bk
    has_seg = segment_ids is not None
    sched = _fold_schedule(nq, nk, bq, bk, causal, window, "q",
                           kv_start=kv_start)
    folded = sched is not None

    kw = dict(scale=scale, kv_len=kv_len, q_len=s, block_q=bq, block_k=bk,
              causal=causal, window=window, kv_start=kv_start,
              has_segments=has_seg, folded=folded)
    qi, kj, qi_seg, kj_seg = _index_maps(folded, h, group=group)
    in_specs = [
        pl.BlockSpec((1, bq, d), qi),
        pl.BlockSpec(_kv_block(bk, d, group), kj),
        pl.BlockSpec(_kv_block(bk, dv, group), kj),
    ]
    inputs = [qb, kb_, vb]
    if has_seg:
        q_seg, kv_seg = _norm_segments(segment_ids)
        # Segment ids are per (batch, position) — the index maps fold the
        # head out of the grid's batch·head axis.
        in_specs += [
            pl.BlockSpec((1, bq, 128), qi_seg),
            pl.BlockSpec((1, 8, bk), kj_seg),
        ]
        inputs += [_seg_tile(q_seg, bq), _seg_lane(kv_seg, bk)]

    o_spec = pl.BlockSpec((1, bq, dv), qi)
    o_shape = (b * h, spq, dv)
    stat_spec = pl.BlockSpec((1, bq, 128), qi)
    stat_shape = jax.ShapeDtypeStruct((b * h, spq, 128), jnp.float32)
    if mode == "out":
        kernel, out_shape, out_specs = (
            _flash_kernel, jax.ShapeDtypeStruct(o_shape, q.dtype), o_spec)
    elif mode == "lse":
        kernel = _flash_fwd_kernel
        out_shape = [jax.ShapeDtypeStruct(o_shape, q.dtype), stat_shape]
        out_specs = [o_spec, stat_spec]
    else:
        kernel = _flash_stats_kernel
        out_shape = [jax.ShapeDtypeStruct(o_shape, jnp.float32),
                     stat_shape, stat_shape]
        out_specs = [o_spec, stat_spec, stat_spec]

    scratch = [
        pltpu.VMEM((bq, 128), jnp.float32),   # m (col 0 used)
        pltpu.VMEM((bq, 128), jnp.float32),   # l (col 0 used)
        pltpu.VMEM((bq, dv), jnp.float32),    # acc
    ]
    # LOAD-BEARING: every grid below (incl. the b*h axis) must execute
    # SEQUENTIALLY on one core — _flash_update zeroes l/acc only at the
    # very first tick of the launch and relies on the alpha =
    # exp(NEG_INF − m) = 0 rescale to clear stale scratch between rows
    # (0·NaN = NaN would break that for unzeroed scratch). That holds
    # for Pallas-TPU's default 'arbitrary' dimension semantics; if
    # dimension_semantics is ever added here, the b*h axis must NOT be
    # marked 'parallel' unless _zero_all becomes per-row (round-4
    # advisor).
    # The kernels' instructions take the scopes' names in a device trace:
    # `flash_fwd.<n>`, `flash_bwd_dq.<n>`, `flash_bwd_dkv.<n>`, and with a
    # window `flash_window_fwd.<n>` etc., so a trace tells a stack's window
    # layers from its full ones.
    with jax.named_scope(_kernel_name("fwd", window)):
        if folded:
            res = pl.pallas_call(
                functools.partial(kernel, **kw),
                out_shape=out_shape,
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1,
                    grid=(b * h, sched.shape[1]),
                    in_specs=in_specs,
                    out_specs=out_specs,
                    scratch_shapes=scratch),
                interpret=interpret,
            )(jnp.asarray(sched), *inputs)
        else:
            res = pl.pallas_call(
                functools.partial(kernel, **kw),
                out_shape=out_shape,
                grid=(b * h, nq, nk),
                in_specs=in_specs,
                out_specs=out_specs,
                scratch_shapes=scratch,
                interpret=interpret,
            )(*inputs)

    if mode == "out":
        return _from_bh(res, b, s, h)
    if mode == "lse":
        o, lse = res
        return _from_bh(o, b, s, h), _from_bh(lse[:, :, 0], b, s, h)
    acc, m, l = res
    # Stats live in lane column 0 of their [bq, 128] tiles.
    return (_from_bh(acc, b, s, h), _from_bh(m[:, :, 0], b, s, h),
            _from_bh(l[:, :, 0], b, s, h))


# ---------------------------------------------------------------------------
# Backward kernels (FlashAttention-2 recompute form).
# ---------------------------------------------------------------------------


def _bwd_block(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               qseg_ref, kseg_ref, *, scale, kv_len, q_len, row0, col0,
               causal, window=None, col_shift=0):
    """Rebuild one score block and its softmax-Jacobian products:
    returns ``(p, ds, do_f32)`` with ``p = exp(s − lse)`` the exact
    softmax probabilities and ``ds = p ∘ (dp − delta) · scale``."""
    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0][:, :1]               # [bq, 1]
    delta = delta_ref[0][:, :1]           # [bq, 1]

    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    mask = _score_mask(
        s.shape, kv_len=kv_len, q_len=q_len, row0=row0, col0=col0,
        col_shift=col_shift, causal=causal, window=window,
        qseg=None if qseg_ref is None else qseg_ref[0][:, :1],
        kseg=None if kseg_ref is None else kseg_ref[0, :1],
        kv_aligned=kv_len % s.shape[1] == 0,
        q_aligned=q_len % s.shape[0] == 0)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)

    p = jnp.exp(s - lse)                  # [bq, bk], true probabilities
    dp = lax.dot_general(do, v.astype(jnp.float32),
                         (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    return p, ds, do


def _flash_bwd_dq_kernel(*refs, scale, kv_len, q_len, block_q, block_k,
                         causal, window=None, kv_start=0,
                         has_segments=False, folded=False):
    """Grid (b·h, q_blocks, k_blocks) — or the folded q-major live-block
    enumeration: dQ_i = Σ_j dS_ij K_j (scale folded into dS)."""
    refs, coords, last = _fold_coords(refs, folded)
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref,
     kseg_ref), (dq_ref,), (dq_scr,) = _unpack(refs, 1, has_segments,
                                               n_base=6)
    if coords is None:
        ib, jb = pl.program_id(1), pl.program_id(2)
        init = jb == 0
    else:
        ib, jb, init = coords

    @pl.when(init)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute():
        _, ds, _ = _bwd_block(q_ref, k_ref, v_ref, do_ref, lse_ref,
                              delta_ref, qseg_ref, kseg_ref, scale=scale,
                              kv_len=kv_len, q_len=q_len,
                              row0=ib * block_q,
                              col0=jb * block_k, col_shift=kv_start,
                              causal=causal, window=window)
        dq_scr[:] += lax.dot_general(
            ds, k_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if folded:
        _compute()
    else:
        live = _band_live(ib * block_q, block_q,
                          kv_start + jb * block_k, block_k,
                          causal, window)
        if live is not None:
            @pl.when(live)
            def _live():
                _compute()
        else:
            _compute()

    @pl.when(last)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(*refs, scale, kv_len, q_len, block_q, block_k,
                          causal, window=None, kv_start=0,
                          has_segments=False, folded=False):
    """Grid (b·h, k_blocks, q_blocks) — or the folded k-major live-block
    enumeration: dV_j = Σ_i P_ijᵀ dO_i and dK_j = Σ_i dS_ijᵀ Q_i (scale
    folded into dS). Padded Q rows contribute exactly zero because their
    dO rows are zero-padded."""
    refs, coords, last = _fold_coords(refs, folded)
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref,
     kseg_ref), (dk_ref, dv_ref), (dk_scr, dv_scr) = _unpack(
        refs, 2, has_segments, n_base=6)
    if coords is None:
        jb, ib = pl.program_id(1), pl.program_id(2)
        init = ib == 0
    else:
        jb, ib, init = coords

    @pl.when(init)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute():
        p, ds, do = _bwd_block(q_ref, k_ref, v_ref, do_ref, lse_ref,
                               delta_ref, qseg_ref, kseg_ref, scale=scale,
                               kv_len=kv_len, q_len=q_len,
                               row0=ib * block_q,
                               col0=jb * block_k, col_shift=kv_start,
                               causal=causal, window=window)
        dv_scr[:] += lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        dk_scr[:] += lax.dot_general(
            ds, q_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if folded:
        _compute()
    else:
        # Same band, transposed view: the block is live iff its row range
        # intersects the k block's attended-row band — which is exactly
        # the q-major predicate with the same coordinates.
        live = _band_live(ib * block_q, block_q,
                          kv_start + jb * block_k, block_k,
                          causal, window)
        if live is not None:
            @pl.when(live)
            def _live():
                _compute()
        else:
            _compute()

    @pl.when(last)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, do, lse, delta, scale=None,
                        block_q=None, block_k=None, interpret=None,
                        causal: bool = False, out_dtype=None,
                        segment_ids=None, window=None, kv_start: int = 0):
    """The flash backward as a standalone op: ``(dq, dk, dv)`` from saved
    forward state. ``lse``/``delta`` are [B, S, H] f32 — the row logsumexp
    from the forward and ``rowsum(dO ∘ O)``. ``k, v`` and so ``dk, dv``
    are ``[B, Skv, Hk, D]``, ``H % Hk == 0`` (see :func:`flash_attention`);
    ``v``, ``do`` and so ``dv`` may be of a head size of their own.
    Exposed (not just wired into the custom_vjp) because ring attention's
    backward reuses it per ring step with the *global* lse/delta
    (parallel/ring_attention.py).

    ``out_dtype`` overrides the gradient dtype (default: match each
    input's). The ring backward passes f32 so its per-step partials are
    never quantized before the cross-step accumulation — matching its jnp
    twin engine."""
    from jax.experimental.pallas import tpu as pltpu

    scale, block_q, block_k, interpret = _resolve(
        q, scale, block_q, block_k, interpret, v)
    kv_start = _static_kv_start(kv_start)
    b, s, h, d = q.shape
    dv = v.shape[-1]
    group = _group(q, k)
    hk = h // group
    kv_len = k.shape[1]
    bq, bk = min(block_q, s), min(block_k, kv_len)
    dq_dt = q.dtype if out_dtype is None else out_dtype
    dk_dt = k.dtype if out_dtype is None else out_dtype
    dv_dt = v.dtype if out_dtype is None else out_dtype

    qb, dob = _to_bh(q, bq), _to_bh(do, bq)
    kb_, vb = _to_bh(k, bk, group), _to_bh(v, bk, group)
    lse_t = _stat_to_tile(lse.astype(jnp.float32), bq)
    delta_t = _stat_to_tile(delta.astype(jnp.float32), bq)
    spq, spk = qb.shape[1], kb_.shape[-2]
    nq, nk = spq // bq, spk // bk

    has_seg = segment_ids is not None
    sched_q = _fold_schedule(nq, nk, bq, bk, causal, window, "q",
                             kv_start=kv_start)
    folded = sched_q is not None
    kw = dict(scale=scale, kv_len=kv_len, q_len=s, block_q=bq, block_k=bk,
              causal=causal, window=window, kv_start=kv_start,
              has_segments=has_seg, folded=folded)

    # dQ pass: q-major — outer/inner = (q block i, k block j).
    qi, kj, qi_seg, kj_seg = _index_maps(folded, h, group=group)
    q_spec_i = pl.BlockSpec((1, bq, d), qi)
    kv_spec_j = pl.BlockSpec(_kv_block(bk, d, group), kj)
    v_spec_j = pl.BlockSpec(_kv_block(bk, dv, group), kj)
    do_spec_i = pl.BlockSpec((1, bq, dv), qi)
    stat_spec_i = pl.BlockSpec((1, bq, 128), qi)

    in_specs = [q_spec_i, kv_spec_j, v_spec_j, do_spec_i, stat_spec_i,
                stat_spec_i]
    inputs = [qb, kb_, vb, dob, lse_t, delta_t]
    if has_seg:
        q_seg, kv_seg = _norm_segments(segment_ids)
        in_specs += [
            pl.BlockSpec((1, bq, 128), qi_seg),
            pl.BlockSpec((1, 8, bk), kj_seg),
        ]
        inputs += [_seg_tile(q_seg, bq), _seg_lane(kv_seg, bk)]

    dq_scratch = [pltpu.VMEM((bq, d), jnp.float32)]
    dq_shape = jax.ShapeDtypeStruct(qb.shape, dq_dt)
    with jax.named_scope(_kernel_name("bwd_dq", window)):
        if folded:
            dq = pl.pallas_call(
                functools.partial(_flash_bwd_dq_kernel, **kw),
                out_shape=dq_shape,
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1,
                    grid=(b * h, sched_q.shape[1]),
                    in_specs=in_specs,
                    out_specs=q_spec_i,
                    scratch_shapes=dq_scratch),
                interpret=interpret,
            )(jnp.asarray(sched_q), *inputs)
        else:
            dq = pl.pallas_call(
                functools.partial(_flash_bwd_dq_kernel, **kw),
                out_shape=dq_shape,
                grid=(b * h, nq, nk),
                in_specs=in_specs,
                out_specs=q_spec_i,
                scratch_shapes=dq_scratch,
                interpret=interpret,
            )(*inputs)

    # dK/dV pass: k-major — outer/inner = (k block j, q block i), the
    # first grid axis over the B·Hk key/value heads. Grouped heads: each
    # key block's inner ticks run over the group's members times the live
    # query blocks (the schedule's job, so the kernel's body is the one of
    # equal head counts), dk_scr/dv_scr sum the WHOLE GROUP in float32 and
    # are rounded and stored once, [B, Hk, S, D] — where a repeat's
    # transpose would sum `group` arrays already rounded to the operands'
    # type. The LOAD-BEARING note in _fwd_call holds here as there: the
    # scratch carries a key block's sums across its ticks only because
    # the grid runs sequentially on one core.
    sched_k = _fold_schedule(nq, nk, bq, bk, causal, window, "k",
                             kv_start=kv_start, group=group)
    folded_k = sched_k is not None      # full attention too, if grouped
    kw = dict(kw, folded=folded_k)
    qi2, kj2, qi2_seg, kj2_seg = _index_maps(folded_k, h, q_major=False,
                                             group=group)
    q_spec = pl.BlockSpec((1, bq, d), qi2)
    kv_spec = pl.BlockSpec(_kv_block(bk, d, group), kj2)
    v_spec = pl.BlockSpec(_kv_block(bk, dv, group), kj2)
    do_spec = pl.BlockSpec((1, bq, dv), qi2)
    stat_spec = pl.BlockSpec((1, bq, 128), qi2)
    in_specs2 = [q_spec, kv_spec, v_spec, do_spec, stat_spec, stat_spec]
    if has_seg:
        in_specs2 += [
            pl.BlockSpec((1, bq, 128), qi2_seg),
            pl.BlockSpec((1, 8, bk), kj2_seg),
        ]
    dkv_shapes = [jax.ShapeDtypeStruct(kb_.shape, dk_dt),
                  jax.ShapeDtypeStruct(vb.shape, dv_dt)]
    dkv_scratch = [pltpu.VMEM((bk, d), jnp.float32),
                   pltpu.VMEM((bk, dv), jnp.float32)]
    with jax.named_scope(_kernel_name("bwd_dkv", window)):
        if folded_k:
            dk, dv = pl.pallas_call(
                functools.partial(_flash_bwd_dkv_kernel, **kw),
                out_shape=dkv_shapes,
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1,
                    grid=(b * hk, sched_k.shape[1]),
                    in_specs=in_specs2,
                    out_specs=[kv_spec, v_spec],
                    scratch_shapes=dkv_scratch),
                interpret=interpret,
            )(jnp.asarray(sched_k), *inputs)
        else:
            dk, dv = pl.pallas_call(
                functools.partial(_flash_bwd_dkv_kernel, **kw),
                out_shape=dkv_shapes,
                grid=(b * h, nk, nq),
                in_specs=in_specs2,
                out_specs=[kv_spec, v_spec],
                scratch_shapes=dkv_scratch,
                interpret=interpret,
            )(*inputs)

    return (_from_bh(dq, b, s, h), _kv_from_bh(dk, b, kv_len, hk),
            _kv_from_bh(dv, b, kv_len, hk))


def attention_delta(o, do):
    """``D = rowsum(dO ∘ O)`` [B, S, H] f32 — the softmax-Jacobian row
    term. Plain XLA: an elementwise multiply-reduce fuses fine."""
    return jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)


# ---------------------------------------------------------------------------
# custom_vjp wiring + public API.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, segment_ids, scale, block_q, block_k, interpret,
           causal, window):
    return _fwd_call(q, k, v, scale, block_q, block_k, interpret, causal,
                     mode="out", segment_ids=segment_ids, window=window)


def _flash_fwd_rule(q, k, v, segment_ids, scale, block_q, block_k,
                    interpret, causal, window):
    out, lse = _fwd_call(q, k, v, scale, block_q, block_k, interpret,
                         causal, mode="lse", segment_ids=segment_ids,
                         window=window)
    # Named for a caller's ``jax.checkpoint`` policy: one that keeps both
    # (``models/looped_decoder.py``) leaves nothing that reads a recomputed
    # forward kernel, which is then dropped as dead code. Where no policy
    # asks for a name it is an identity. The named output is also the
    # primal one, so that whoever reads it reads the kept array.
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, segment_ids, out, lse)


def _flash_bwd_rule(scale, block_q, block_k, interpret, causal, window,
                    res, do):
    import numpy as np

    q, k, v, segment_ids, out, lse = res
    delta = attention_delta(out, do)
    dq, dk, dv = flash_attention_bwd(q, k, v, do, lse, delta, scale=scale,
                                     block_q=block_q, block_k=block_k,
                                     interpret=interpret, causal=causal,
                                     segment_ids=segment_ids,
                                     window=window)
    # Integer segment ids carry no gradient: float0 cotangent (None stays
    # None — it's an empty pytree; tuples map per-leaf).
    dseg = jax.tree.map(
        lambda s: np.zeros(s.shape, jax.dtypes.float0), segment_ids)
    return dq, dk, dv, dseg


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.jit,
                   static_argnames=("scale", "block_q", "block_k",
                                    "interpret", "causal", "window"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    scale: float | None = None,
                    block_q: int | None = None,
                    block_k: int | None = None,
                    interpret: bool | None = None,
                    causal: bool = False,
                    segment_ids: jax.Array | None = None,
                    window: int | None = None) -> jax.Array:
    """FlashAttention: ``q [B, S, H, D]``, ``k, v [B, Skv, Hk, D]`` →
    ``[B, S, H, D]``, for any ``H % Hk == 0`` (the group ``H // Hk`` is
    read from the shapes; query head ``j`` attends key/value head ``j //
    group``; ``dk`` and ``dv`` come back ``[B, Skv, Hk, D]``, each group's
    sum taken in float32 and rounded once). ``v`` may be of a head size
    ``Dv`` of its own (latent attention's queries and keys of 192 over
    values of 128): the output and ``dv`` are then ``Dv`` wide, ``q``,
    ``k``, ``dq`` and ``dk`` ``D`` wide, and the blocks are sized by the
    wider row. Equal sizes are the program of before.

    At equal head counts contract-identical to
    :func:`ops.attention.xla_attention` (including under ``jax.grad`` —
    the custom_vjp runs the Pallas backward kernels), with fewer key/value
    heads to the same on the heads repeated;
    tests assert numerical agreement of both values and gradients.
    Sequence lengths that aren't multiples of the block sizes are
    zero-padded and masked inside the kernels. ``causal=True`` masks above
    the diagonal and skips fully-masked blocks. ``segment_ids`` [B, S]
    int32 restricts attention to same-segment pairs (packed sequences) in
    both directions; combine with ``causal`` for packed causal LM
    batches. A ``(q_seg [B, Sq], kv_seg [B, Skv])`` pair serves
    cross-shard callers (the ring walks K/V shards whose ids differ from
    the local Q shard's). ``window=W`` restricts attention to the band
    ``|row − col| < W`` (with ``causal`` only the lower half —
    sliding-window/local attention); out-of-band blocks are skipped
    fetch-free, so cost scales with W·S instead of S².
    """
    scale, block_q, block_k, interpret = _resolve(
        q, scale, block_q, block_k, interpret, v)
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    return _flash(q, k, v, segment_ids, scale, block_q, block_k, interpret,
                  causal, window)


@functools.partial(jax.jit,
                   static_argnames=("scale", "block_q", "block_k",
                                    "interpret", "causal", "window",
                                    "kv_start"))
def flash_attention_fwd_lse(q: jax.Array, k: jax.Array, v: jax.Array,
                            scale: float | None = None,
                            block_q: int | None = None,
                            block_k: int | None = None,
                            interpret: bool | None = None,
                            causal: bool = False,
                            segment_ids: jax.Array | None = None,
                            window: int | None = None,
                            kv_start: int = 0):
    """Forward with residual: ``(out [B,S,H,D], lse [B,S,H] f32)``.

    The save-for-backward interface: ``lse`` is the row logsumexp, the
    one statistic :func:`flash_attention_bwd` needs alongside O and dO —
    for any caller that manages its own residuals instead of going
    through :func:`flash_attention`'s custom_vjp. (Ring attention derives
    its residual lse from the merged stats inside its own forward scan —
    parallel/ring_attention.py — and pairs it with
    :func:`flash_attention_bwd` in its backward ring.)
    """
    scale, block_q, block_k, interpret = _resolve(
        q, scale, block_q, block_k, interpret, v)
    return _fwd_call(q, k, v, scale, block_q, block_k, interpret, causal,
                     mode="lse", segment_ids=segment_ids, window=window,
                     kv_start=_static_kv_start(kv_start))


@functools.partial(jax.jit,
                   static_argnames=("scale", "block_q", "block_k",
                                    "interpret", "causal", "window",
                                    "kv_start"))
def flash_attention_stats(q: jax.Array, k: jax.Array, v: jax.Array,
                          scale: float | None = None,
                          block_q: int | None = None,
                          block_k: int | None = None,
                          interpret: bool | None = None,
                          causal: bool = False,
                          segment_ids: jax.Array | None = None,
                          window: int | None = None,
                          kv_start: int = 0):
    """FlashAttention's raw partial-softmax state:
    ``(acc [B,S,H,D] f32 UNNORMALIZED accumulator, m [B,S,H] f32 row max,
    l [B,S,H] f32 normalizer)``; the normalized output is ``acc / l``.

    This is the partial-attention interface: partials over different K/V
    shards merge with the standard flash rule in full f32 — exactly what
    the ring-attention body needs to run its local block on the MXU via
    Pallas (:func:`parallel.ring_attention.ring_attention`).
    """
    scale, block_q, block_k, interpret = _resolve(
        q, scale, block_q, block_k, interpret, v)
    return _fwd_call(q, k, v, scale, block_q, block_k, interpret, causal,
                     mode="stats", segment_ids=segment_ids, window=window,
                     kv_start=_static_kv_start(kv_start))

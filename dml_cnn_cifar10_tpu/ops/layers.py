"""Core layer primitives: init schemes + conv/pool/dense on XLA.

Parity notes (all against ``/root/reference/cifar10cnn.py``):
- ``truncated_normal_init`` == ``tf.truncated_normal_initializer(stddev=0.05)``
  (``:97-98``): normal samples truncated to ±2σ (resampled, not clipped),
  NOT variance-rescaled — ``jax.random.truncated_normal`` has exactly these
  semantics.
- ``bias_init`` == ``tf.constant_initializer(0.1)`` (``:100-101``).
- ``conv2d`` == ``tf.nn.conv2d(..., strides=[1,1,1,1], padding='SAME')``
  (``:107,118``) in NHWC/HWIO layout.
- ``max_pool`` == ``tf.nn.max_pool(ksize=[1,3,3,1], strides=[1,2,2,1],
  'SAME')`` (``:113,123``): overlapping 3×3/2 windows, -inf padding.
"""

from __future__ import annotations

import functools
import importlib
from typing import Mapping, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dml_cnn_cifar10_tpu.ops import kernel_paths
from dml_cnn_cifar10_tpu.utils import platform as platform_lib

#: Settings of ``jax_default_matmul_precision`` under which a TPU
#: multiplies float32 operands in one bfloat16 pass.
_ONE_BF16_PASS = (None, "default", "bfloat16", "BF16_BF16_F32")


def truncated_normal_init(key, shape, stddev: float = 0.05,
                          dtype=jnp.float32) -> jax.Array:
    """Truncated-normal (±2σ) init, TF-compatible (no rescaling)."""
    return stddev * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                                dtype=dtype)


def bias_init(shape, value: float = 0.1, dtype=jnp.float32) -> jax.Array:
    return jnp.full(shape, value, dtype=dtype)


def conv2d(x: jax.Array, kernel: jax.Array, stride: int = 1,
           padding: str = "SAME") -> jax.Array:
    """NHWC conv with HWIO kernel → NHWC out (MXU-friendly layout on TPU)."""
    return lax.conv_general_dilated(
        x, kernel,
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def product_operand_dtype(dtype) -> jnp.dtype:
    """What :func:`conv2d`, :func:`dense` and their gradients round a
    ``dtype`` operand to before they multiply, on this backend at the
    precision in force: bfloat16 for float32 on a TPU at the default
    precision, else ``dtype`` itself (the CPU multiplies float32 as it
    is; so does a TPU under ``jax.default_matmul_precision("highest")``).
    An array that only such products read holds the same values for them
    stored in this dtype (``ops/relu_pool.py``)."""
    dtype = jnp.dtype(dtype)
    if (dtype == jnp.float32 and platform_lib.on_tpu()
            and jax.config.jax_default_matmul_precision in _ONE_BF16_PASS):
        return jnp.dtype(jnp.bfloat16)
    return dtype


def max_pool(x: jax.Array, window: int = 3, stride: int = 2,
             padding: str = "SAME") -> jax.Array:
    """Max pool over NHWC spatial dims via ``lax.reduce_window``.

    Backward is XLA's select-and-scatter, which reads the activation
    again to find each window's argmax. What has been tried in its place:

    - Round 3 (a ResNet-50 profile on an earlier chip): a 9-shift
      compare-mask-pad VJP, nine float32 accumulation passes over the
      112x112 grid with the activation still read for the compares.
      MEASURED WORSE (-27% step time).
    - PR 25, on the v5e, for the CNN's ``conv -> bias -> ReLU -> pool``
      pairs: keep each window's winning tap (int8) in the forward pass,
      fold the ReLU in (its mask is ``pooled > 0`` at the argmax) and
      gather the gradient from tensors at the output's resolution. As XLA
      expressions (nine strided slices or a variadic ``reduce_window`` for
      the tap; stack-and-reshape, interior pads or broadcasts for the
      interleave) every variant MEASURED WORSE than select-and-scatter
      (63-106 ms a step against 46.8). As two Pallas kernels it is
      ``ops/relu_pool.py``, 31.6 ms a step, and what ``models/cnn.py``
      calls. PERF.md, Findings, PR 25 has the numbers.
    - PR 25, the ResNet-50 stem's pool (bfloat16, batch 256, 112x112)
      through those kernels: 2,513.9 img/s/chip against 2,523.5 with this
      function, ``step.device_ms`` 101.73 against 101.35. The
      select-and-scatter (1.5 ms a step) leaves and more comes back;
      where was not taken apart (at batch 256 the kernels' batch-in-the-
      lanes view need not be that model's layout). The stem keeps this
      function.
    - PR 31, the CNN's pairs again: the kernels write the pooled output
      and the input's gradient as bfloat16 where the model is float32 on
      a TPU at the default precision (:func:`product_operand_dtype`):
      every reader is a product that rounds them so anyway. 35.14 ->
      29.57 ms a step. The convolution's output in front of the bias
      stays float32: no product reads it, and rounding it is another
      result. PERF.md, Findings, PR 31.
    """
    return lax.reduce_window(
        x,
        -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
        else jnp.iinfo(x.dtype).min,
        lax.max,
        window_dimensions=(1, window, window, 1),
        window_strides=(1, stride, stride, 1),
        padding=padding,
    )


def dense(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """x @ w + b — a single MXU matmul; keep inputs 2-D [B, D]."""
    return jnp.dot(x, w) + b


def he_normal_init(key, shape, dtype=jnp.float32) -> jax.Array:
    """He/Kaiming fan-in normal init for conv (HWIO) / dense (IO) weights.

    Used by the ResNet/ViT configs (no reference counterpart — the reference
    model predates normalized init, SURVEY §7 step 6).
    """
    fan_in = int(np.prod(shape[:-1]))
    return jax.random.normal(key, shape, dtype) * jnp.sqrt(2.0 / fan_in)


def batch_norm(
    x: jax.Array,
    params,
    state,
    train: bool,
    momentum: float = 0.9,
    eps: float = 1e-5,
    axis_name=None,
):
    """BatchNorm over NHWC (stats on N,H,W) with running-stat state.

    Cross-replica semantics (SURVEY §2.3): under ``jit`` auto-partitioning
    the batch axis is sharded over ``data`` and the ``jnp.mean`` below is a
    *global* mean — XLA compiles the cross-replica reduction in. Under the
    explicit ``shard_map`` step the batch the kernel sees is the local
    shard, so ``axis_name`` triggers a literal ``lax.pmean`` of the
    sufficient statistics (E[x], E[x²]) — the hand-written form of the same
    collective.

    Returns ``(y, new_state)``; ``new_state`` equals ``state`` in eval.
    The STATISTICS (mean/var, running stats) are computed in f32
    regardless of compute dtype — bf16 batch stats lose too much
    precision — but the per-element normalize runs in ``x.dtype``
    (round 3: BN's epilogue is memory-bound and the f32 upcast doubled
    its HBM traffic). Output dtype
    == input dtype in train and eval.
    """
    axes = tuple(range(x.ndim - 1))
    if train:
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axes)
        mean_sq = jnp.mean(jnp.square(xf), axes)
        if axis_name is not None:
            mean = lax.pmean(mean, axis_name)
            mean_sq = lax.pmean(mean_sq, axis_name)
        # Clamp: E[x²]−E[x]² can go (slightly) negative from f32
        # cancellation when mean² >> var (e.g. raw 0..255 faithful-mode
        # pixels), and rsqrt would NaN.
        var = jnp.maximum(mean_sq - jnp.square(mean), 0.0)
        new_state = {
            "mean": momentum * state["mean"] + (1.0 - momentum) * mean,
            "var": momentum * state["var"] + (1.0 - momentum) * var,
        }
    else:
        mean, var = state["mean"], state["var"]
        new_state = state
    inv = lax.rsqrt(var + eps) * params["scale"].astype(jnp.float32)
    # Normalize in the COMPUTE dtype: the statistics stay f32 (above —
    # bf16 batch stats lose too much precision) but the per-element
    # normalize chain runs at the activation width. BN's epilogue is
    # memory-bound, so in bf16 this halves its HBM traffic; for f32
    # activations the casts are no-ops and the math is unchanged.
    cdt = x.dtype
    y = (x - mean.astype(cdt)) * inv.astype(cdt) \
        + params["offset"].astype(cdt)
    return y, new_state


def bn_init(width: int, dtype=jnp.float32):
    """Params for one BatchNorm layer. The running-stat state pytree is
    derived structurally from the params (``resnet.init_state``) — one
    source of truth for its shape/dtype."""
    return {"scale": jnp.ones((width,), dtype),
            "offset": jnp.zeros((width,), dtype)}


def pooled_hw(h: int, w: int, n_pools: int, window: int = 3,
              stride: int = 2) -> Tuple[int, int]:
    """Spatial dims after ``n_pools`` SAME-padded stride-2 pools (ceil div)."""
    for _ in range(n_pools):
        h = -(-h // stride)
        w = -(-w // stride)
    return h, w


# --- decoder primitives (models/looped_decoder.py, hybrid_decoder.py) --------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """``x * rsqrt(mean(x^2) + eps) * scale`` over the last dim, float32."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's magnitude rule: ``0.1 mscale ln(factor) + 1``, and 1 where
    ``factor`` stretches nothing (1 or less)."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * float(np.log(factor)) + 1.0


def rope_frequencies(rope, head_dim: int):
    """A rotary rule -> ``(inv_freq [head_dim / 2] float64, factor)``: the
    angle a position turns pair ``j`` by, and what cos and sin are both
    multiplied by. ``rope`` is a number, theta, or a mapping with
    ``rope_theta`` and ``rope_type``:

    - ``default`` (or a number): ``inv_freq_j = theta ** (-2 j /
      head_dim)``, factor 1;
    - ``yarn`` (as the ``transformers`` library computes it; keys
      ``factor``, ``original_max_position_embeddings``, ``beta_fast`` 32,
      ``beta_slow`` 1): with ``dim(n) = head_dim ln(original / (2 pi n)) /
      (2 ln theta)``, ``low = floor(dim(beta_fast))`` and ``high =
      ceil(dim(beta_slow))`` (clipped to ``0 .. head_dim - 1``), ``ramp_j =
      clip((j - low) / (high - low), 0, 1)`` and ``inv_freq_j = (1 -
      ramp_j) theta ** (-2 j / head_dim) + ramp_j theta ** (-2 j /
      head_dim) / factor``: the fast pairs turn as they were trained, the
      slow ones ``factor`` times slower. The factor of cos and sin, at
      every length, is the rule's ``attention_factor``; where it names
      none but names ``mscale`` or ``mscale_all_dim`` (DeepSeek's keys,
      1 and 0 where absent) it is ``yarn_mscale(factor, mscale) /
      yarn_mscale(factor, mscale_all_dim)``; where it names none of the
      three it is ``yarn_mscale(factor) = 0.1 ln(factor) + 1``, the
      library's default. ``head_dim`` is the width rotary turns: the rope
      slice where only a slice turns (latent attention's 64)."""
    if not isinstance(rope, Mapping):
        rope = {"rope_type": "default", "rope_theta": rope}
    kind = rope.get("rope_type", "default")
    theta = rope["rope_theta"]
    plain = theta ** (-np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    if kind == "default":
        return plain, 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r} is not default or yarn")
    scale, original = rope["factor"], rope["original_max_position_embeddings"]

    def dim(turns):
        return head_dim * np.log(original / (2 * np.pi * turns)) \
            / (2 * np.log(theta))

    low = max(int(np.floor(dim(rope.get("beta_fast", 32)))), 0)
    high = min(int(np.ceil(dim(rope.get("beta_slow", 1)))), head_dim - 1)
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float64) - low)
                   / max(high - low, 0.001), 0.0, 1.0)
    factor = rope.get("attention_factor")
    if factor is None and ("mscale" in rope or "mscale_all_dim" in rope):
        factor = yarn_mscale(scale, rope.get("mscale", 1.0)) \
            / yarn_mscale(scale, rope.get("mscale_all_dim", 0.0))
    elif factor is None:
        factor = yarn_mscale(scale)
    return (1 - ramp) * plain + ramp * plain / scale, float(factor)


def rope_softmax_factor(rope) -> float:
    """What a YaRN rule multiplies attention's softmax scale by:
    ``yarn_mscale(factor, mscale_all_dim) ** 2`` where the rule names
    ``mscale_all_dim`` (DeepSeek's), else 1."""
    if not isinstance(rope, Mapping) or rope.get("rope_type") != "yarn" \
            or not rope.get("mscale_all_dim"):
        return 1.0
    return yarn_mscale(rope["factor"], rope["mscale_all_dim"]) ** 2


def rotary(x: jax.Array, rope) -> jax.Array:
    """Rotary positions on ``x [B, S, H, Dh]``, rotate-half over the whole
    head dimension: the pair ``(x[i], x[i + Dh/2])`` turns by ``position *
    inv_freq_i``, positions ``0..S-1``, cos and sin times the rule's
    factor (:func:`rope_frequencies`; a number is theta of the plain
    rule). float32."""
    s, dh = x.shape[1], x.shape[-1]
    inv_freq, factor = rope_frequencies(rope, dh)
    angle = jnp.asarray(np.arange(s)[:, None] * inv_freq[None, :],
                        jnp.float32)
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[None, :, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x = x.astype(jnp.float32)
    half = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], -1)
    return x * cos + half * sin


def low_dot(x, w, dtype):
    """``x @ w`` of operands rounded to ``dtype``, summed in float32."""
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def mixed_matmul(x: jax.Array, w: jax.Array, dtype) -> jax.Array:
    """``x [..., K] @ w [K, N]`` (``x`` float32) as a matrix unit forms it
    from float32 operands: the operands rounded to ``dtype``, the sum and
    the result in float32, and the same in both backward products, each from the
    cotangent rounded to ``dtype`` and the other rounded operand. Written
    out (not left to autodiff) so that ``dw`` is summed and handed on in
    float32 whatever ``dtype`` is, and the operand kept for the backward
    pass is the rounded one."""
    return low_dot(x, w, dtype)


def _mixed_matmul_fwd(x, w, dtype):
    x_low = x.astype(dtype)
    return low_dot(x_low, w, dtype), (x_low, w)


def _mixed_matmul_bwd(dtype, res, g):
    x_low, w = res
    g_low = g.astype(dtype)
    dx = jnp.dot(g_low, w.astype(dtype).T,
                 preferred_element_type=jnp.float32)
    k = x_low.shape[-1]
    dw = jnp.dot(x_low.reshape(-1, k).T, g_low.reshape(-1, g.shape[-1]),
                 preferred_element_type=jnp.float32)
    return dx, dw.astype(w.dtype)


mixed_matmul.defvjp(_mixed_matmul_fwd, _mixed_matmul_bwd)


def gated_short_conv(bcx: jax.Array, w: jax.Array) -> jax.Array:
    """The gated short convolution between its two projections. ``bcx
    [..., S, 3 C]`` holds the gates ``B`` and ``C`` and the values ``X``
    (that order); ``w [C, L]`` is a depthwise causal filter of ``L`` taps:
    ``c[t] = sum_j w[:, j] * (B * X)[t - (L - 1) + j]``, zeros before the
    sequence's start; the result is ``C * c``. float32."""
    b, c, x = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
    u = b * x
    taps, s = w.shape[-1], u.shape[-2]
    lead = [(0, 0)] * (u.ndim - 2)
    padded = jnp.pad(u, lead + [(taps - 1, 0), (0, 0)])
    conv = sum(lax.slice_in_dim(padded, j, j + s, axis=-2) * w[:, j]
               for j in range(taps))
    return c * conv


#: Row tiles a grouped product's kernels may take, the largest first: a
#: block of the experts' rows is a whole number of 512 (``models/
#: hybrid_decoder.ROW_TILE``).
_ROW_TILES = (512, 256, 128)
_LANES = 128
#: What a grid step of the grouped kernels may hold in vector memory by
#: :func:`grouped_tiles`' arithmetic: 2 MiB under the 16 MiB of scoped
#: memory Mosaic gives a kernel that asks for no more (megablox asks for
#: no more), since the compiler took up to 0.94 MiB above the arithmetic
#: at the cells' shapes for a described v5e (PERF.md, Findings, PR 37).
_GMM_VMEM = 14 << 20
#: The v5e a grid step is modelled on: bfloat16 operations a second,
#: bytes a second from HBM, and the fixed cost of a step. The v5e is the
#: one chip the repo measures and ``on_tpu`` cannot tell a generation: on
#: another the model may rank the tiles otherwise, never let one past
#: :data:`_GMM_VMEM`.
_PEAK_FLOPS, _HBM_BYTES_S, _STEP_S = 197e12, 819e9, 0.35e-6


class GroupedTiles(NamedTuple):
    """``(tm, tk, tn)`` of each of :func:`grouped_matmul`'s three kernels,
    in megablox's terms: ``fwd`` of ``gmm(x, w)``, ``dx`` of ``gmm(g, w,
    transpose_rhs=True)`` (its ``k`` is the forward's ``n``), ``dw`` of
    ``tgmm(x.T, g)`` (its output tile is ``(tk, tn)`` of ``w``)."""

    fwd: Tuple[int, int, int]
    dx: Tuple[int, int, int]
    dw: Tuple[int, int, int]


def _lane_divisors(size: int):
    return [t for t in range(size, 0, -_LANES) if size % t == 0]


def _kernel_tiles(tm, m, k, n, groups, itemsize, transposed):
    """The ``(tk, tn)`` of least modelled time for one kernel (see
    :func:`grouped_tiles`); None where no tile fits."""
    best, least = None, None
    for tk in _lane_divisors(k):
        for tn in _lane_divisors(n):
            out = (tk if transposed else tm) * tn
            fetched = tm * tk + (tm if transposed else tk) * tn
            if 2 * fetched * itemsize + 3 * 4 * out > _GMM_VMEM:
                continue
            steps = (m // tm) * (k // tk) * (n // tn)
            stored = 4 * (groups * k * n if transposed else m * n)
            seconds = steps * (_STEP_S + max(
                2 * tm * tk * tn / _PEAK_FLOPS,
                (fetched * itemsize + stored / steps) / _HBM_BYTES_S))
            if least is None or seconds < least:
                best, least = (tm, tk, tn), seconds
    return best


def grouped_tiles(m: int, k: int, n: int, groups: int, dtype,
                  mesh=None):
    """The tiles of :func:`grouped_matmul`'s kernels for ``x [m, k]`` and
    ``w [groups, k, n]`` in ``dtype``, or None where the products stay
    ``lax.ragged_dot``: off a TPU, under a mesh of more than one device (a
    bare ``pallas_call`` cannot be partitioned by GSPMD), at a ``k`` or
    ``n`` that is no whole number of 128 lanes, at ``m`` that is no whole
    number of row tiles (:data:`_ROW_TILES`), or in another dtype than
    bfloat16 (the kernels multiply float32 operands at a precision of
    their own, which would be another result).

    ``tm`` is the largest row tile that divides ``m``. Each kernel's ``tk``
    and ``tn`` are the multiples of 128 that divide their dimensions and
    take the least time by a model of a grid step: the longer of its
    products at 197 TF/s and its bytes at 819 GB/s (the two operands'
    blocks, and its share of the float32 output, which leaves once a row
    tile in ``gmm`` and once a group in ``tgmm``), plus 0.35 us. Only tiles
    whose blocks fit :data:`_GMM_VMEM` are looked at, by the arithmetic

        gmm:  2 (tm tk + tk tn) s + 3 x 4 tm tn
        tgmm: 2 (tm tk + tm tn) s + 3 x 4 tk tn

    (``s`` the dtype's bytes; each operand's block double-buffered; the
    float32 output block double-buffered and its accumulator). At the two
    expert decoders' blocks of 8,704 rows, bfloat16:

        (2048 -> 1792) fwd (512, 1024,  896): 5.5 + 5.25 = 10.75 MiB
        (1792 -> 2048) fwd (512,  896, 1024): 5.25 + 6.0 = 11.25 MiB
        (2304 ->  896) fwd (512, 1152,  896): 6.19 + 5.25 = 11.44 MiB
        ( 896 -> 2304) fwd (512,  896, 1152): 5.69 + 6.75 = 12.44 MiB

    and ``dx`` the other direction's forward tiles."""
    if not (platform_lib.on_tpu() and (mesh is None or mesh.size == 1)
            and jnp.dtype(dtype) == jnp.bfloat16
            and k % _LANES == 0 and n % _LANES == 0):
        return None
    tm = next((t for t in _ROW_TILES if m % t == 0), None)
    if tm is None:
        return None
    s = jnp.dtype(dtype).itemsize
    tiles = GroupedTiles(_kernel_tiles(tm, m, k, n, groups, s, False),
                         _kernel_tiles(tm, m, n, k, groups, s, False),
                         _kernel_tiles(tm, m, k, n, groups, s, True))
    return None if None in tiles else tiles


def grouped_matmul(x: jax.Array, w: jax.Array, group_sizes: jax.Array,
                   dtype, mesh=None) -> jax.Array:
    """:func:`mixed_matmul` with a matrix a group of rows: the first
    ``group_sizes[0]`` rows of ``x [M, K]`` meet ``w[0]`` of ``w [G, K,
    N]``, the next ``group_sizes[1]`` rows ``w[1]``, and so on; rows past
    the groups' sum belong to no group, cost no product, come back zero
    and take a zero gradient. Operands rounded to ``dtype``, sums and
    result float32, forward and in both backward products. Where
    :func:`grouped_tiles` gives tiles (one TPU device, whole lanes and row
    tiles, bfloat16; ``mesh`` is the enclosing GSPMD program's, if any)
    the three products are megablox's Pallas kernels ``gmm`` / ``tgmm`` at
    those tiles, else ``lax.ragged_dot``, which the TPU's compiler makes
    one kernel each at tiles of its own. Neither writes the rows past the
    groups, so they are cleared here, forward and in the gradient of
    ``x``. The path is noted for the step's line (``ops.kernel_paths``,
    kind ``grouped``): ``ragged_dot``, or ``pallas gmm`` and each distinct
    forward tile in the order the products were traced."""
    tiles = grouped_tiles(x.shape[0], x.shape[1], w.shape[2], w.shape[0],
                          dtype, mesh)
    if tiles is None:
        path = "ragged_dot"
    else:
        tile = "({},{},{})".format(*tiles.fwd)
        said = kernel_paths.noted("grouped") or ""
        path = said if tile in said else (
            f"{said} {tile}" if said.startswith("pallas gmm")
            else f"pallas gmm {tile}")
    kernel_paths.note("grouped", path)
    return grouped_matmul_tiled(x, w, group_sizes, dtype, tiles)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def grouped_matmul_tiled(x, w, group_sizes, dtype, tiles=None,
                         interpret=False):
    """:func:`grouped_matmul` on the path ``tiles`` names (None:
    ``lax.ragged_dot``); ``interpret`` runs the kernels in the Pallas
    interpreter (tests, off TPU)."""
    return _grouped_matmul_fwd(x, w, group_sizes, dtype, tiles, interpret)[0]


def _megablox():
    # the module: the package's `gmm` is the custom-VJP'd function
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _clear_past_groups(y, group_sizes):
    live = jnp.arange(y.shape[0]) < jnp.sum(group_sizes)
    return jnp.where(live[:, None], y, 0.0)


def _grouped_matmul_fwd(x, w, group_sizes, dtype, tiles, interpret):
    x_low, w_low = x.astype(dtype), w.astype(dtype)
    if tiles is None:
        y = lax.ragged_dot(x_low, w_low, group_sizes,
                           preferred_element_type=jnp.float32)
    else:
        y = _megablox().gmm(x_low, w_low, group_sizes, jnp.float32,
                            tiles.fwd, interpret=interpret)
    return _clear_past_groups(y, group_sizes), (x_low, w, group_sizes)


_RAGGED_CONTRACTION = lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _grouped_matmul_bwd(dtype, tiles, interpret, res, g):
    x_low, w, group_sizes = res
    g_low = g.astype(dtype)
    if tiles is None:
        dx = lax.ragged_dot(g_low, jnp.swapaxes(w.astype(dtype), 1, 2),
                            group_sizes, preferred_element_type=jnp.float32)
        dw = lax.ragged_dot_general(x_low, g_low, group_sizes,
                                    _RAGGED_CONTRACTION,
                                    preferred_element_type=jnp.float32)
    else:
        mb = _megablox()
        dx = mb.gmm(g_low, w.astype(dtype), group_sizes, jnp.float32,
                    tiles.dx, transpose_rhs=True, interpret=interpret)
        dw = mb.tgmm(x_low.T, g_low, group_sizes, jnp.float32, tiles.dw,
                     interpret=interpret)
    return (_clear_past_groups(dx, group_sizes), dw.astype(w.dtype), None)


grouped_matmul_tiled.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)

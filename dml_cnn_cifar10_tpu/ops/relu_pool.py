"""Bias + ReLU + 3x3/2 SAME max-pool as one op whose backward pass reads
nothing at the input's resolution (what follows each of the CNN's two
convolutions).

``max_pool(relu(z + bias))`` differentiates into XLA's select-and-scatter,
which reads the activation a second time only to find again which element
of each window was the largest, a pass that packs the ReLU's mask, and a
reduction of the whole gradient for the bias. Behind a ReLU the gradient
of the pool's input is non-zero only at each window's argmax, there the
ReLU's mask equals ``pooled > 0``, and the bias's gradient is the sum of
what that mask lets through. So the forward kernel here writes the pooled
output and each window's winning tap (0..8 in scan order, int8), and the
backward kernel gathers ``dz`` from the incoming gradient and those two:
three reads at the output's resolution, one write at the input's. The
convolution's output is dead once the forward kernel has read it.

Two Pallas kernels, because the XLA expressions of the same rules measured
slower than select-and-scatter on the v5e (PERF.md, Findings, PR 25: the
tap index is a second pass of nine strided reads, the interleave of the
four parity planes a concatenate and a layout copy). The kernels see the
arrays as ``[H, W, C, B]``: at a batch that fills the lanes the compiler
keeps the batch in the lanes and the channels in the sublanes, so that
view is a bitcast of the convolution's own layout, H and W are untiled
leading dimensions, and a window's taps and the interleave are plain
addressing.

What is stored in which type. The kernels compute in float32 whatever
the data's type: the bias is added and the sum rounded as the input's
dtype rounds it, the taps are compared in float32, the backward kernel
reads the incoming gradient ``g`` as it comes and sums its (at most four)
terms and the bias's partial sums in float32. The two arrays a kernel
writes for somebody else, the pooled ``y`` and the input's gradient
``dz``, are written as ``store``: by default the input's dtype. A float32
model on a TPU asks for bfloat16 (``ops/layers.product_operand_dtype``),
because every reader of the two is a product at the default precision,
which rounds a float32 operand to bfloat16 before it multiplies, or the
backward kernel's own ``y > 0``: stored as bfloat16 they hold the values
every reader sees anyway, and a step of the CNN at batch 16,384 moves
4.8 GB less through HBM (PERF.md, Findings, PR 31). The op still hands
``y`` and ``dz`` on in the input's dtype; the compiler folds that
widening into the convolution or the dot that reads it, and no float32
copy exists. The convolution's own output ``z`` is NOT such an array (an
add, a ReLU and a compare read it: rounding it is a rounding that no
product makes), nor is ``g``.

Who takes the kernels: a TPU backend, even H and W, a float dtype, a
batch that fills the lanes and channels that fill an int8 tile; one
device, or a mesh whose ``data`` axis alone splits the batch (the kernels
then run under a ``shard_map``, as the fused update does). Everything
else, and every caller that asks for no gradient (eval, serving, the
boundary's accuracy pass), runs ``max_pool(relu(z + bias))`` itself, in
the input's dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dml_cnn_cifar10_tpu.ops import kernel_paths
from dml_cnn_cifar10_tpu.ops.layers import max_pool
from dml_cnn_cifar10_tpu.utils import platform as platform_lib

_LANES = 128          # the batch block
_SUBLANES = 32        # the channel block: an int8 tile is (32, 128)
_COLS = 16            # windows of a row that one traced piece of a kernel handles
#: Budget of the input-resolution block (float32 24x24 whole: 9.4 MB).
#: Taller images are cut into row blocks with a one-row halo.
_BLOCK_BYTES = 10 << 20
_VMEM_LIMIT = 64 << 20


def _plain(z, bias):
    return max_pool(jax.nn.relu(z + bias))


def fits_kernels(shape, dtype) -> bool:
    """What the kernels were written for (module docstring)."""
    if len(shape) != 4 or not jnp.issubdtype(dtype, jnp.floating):
        return False
    b, h, w, c = shape
    return (h % 2 == 0 and w % 2 == 0 and b % _LANES == 0
            and c % _SUBLANES == 0)


def bias_relu_max_pool(z: jax.Array, bias: jax.Array, mesh=None,
                       store=None) -> jax.Array:
    """``max_pool(relu(z + bias))`` for NHWC ``z`` and a bias a channel
    (window 3, stride 2, SAME).

    ``mesh`` is the mesh of the enclosing GSPMD program, if any. The
    choice of path reads the platform, the shape and the mesh only.
    ``store`` is the dtype in which the kernels write the pooled output
    and the input's gradient (module docstring): the dtype to which the
    caller's products round them anyway, by default the input's. The XLA
    expression has no such store and ignores it."""
    if not (platform_lib.on_tpu() and fits_kernels(z.shape, z.dtype)):
        kernel_paths.note("pool", "xla")
        return _plain(z, bias)
    store = jnp.dtype(z.dtype if store is None else store)
    stores = "" if store == z.dtype else f", stores {store.name}"
    if mesh is None or mesh.size == 1:
        kernel_paths.note("pool", "pallas" + stores)
        return fused_bias_relu_max_pool(z, bias, False, store)
    ndata = mesh.shape["data"]
    if mesh.size != ndata or z.shape[0] % (ndata * _LANES):
        # H over ``seq`` (spatial partitioning) needs halo exchanges the
        # kernels do not make; GSPMD's pool does.
        kernel_paths.note("pool", "xla (mesh)")
        return _plain(z, bias)
    kernel_paths.note("pool",
                      f"pallas/shard_map[batch/data x{ndata}]" + stores)
    return over_data(mesh, store=store)(z, bias)


def over_data(mesh, interpret: bool = False, store=None):
    """The kernel path with the batch split over ``data``: each device
    runs the kernels on its own images (a bare ``pallas_call`` cannot be
    partitioned by GSPMD); the bias is replicated, and its gradient is
    summed over the devices by ``shard_map``'s own transpose."""
    return jax.shard_map(
        lambda z, bias: fused_bias_relu_max_pool(z, bias, interpret, store),
        mesh=mesh, in_specs=(P("data", None, None, None), P()),
        out_specs=P("data", None, None, None), check_vma=False)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_bias_relu_max_pool(z, bias, interpret=False, store=None):
    """The kernel path itself; ``interpret`` runs the kernels in the
    Pallas interpreter (tests, off TPU); ``store`` as in
    :func:`bias_relu_max_pool`. Called for no gradient it is the plain
    expression, unrounded: its readers round it as they read."""
    del interpret, store
    return _plain(z, bias)


def _to_kernel(x):
    """NHWC -> [H, W, C, B], and ``_from_kernel`` back. Where the batch is
    in the lanes both are bitcasts, and the compiler roots the
    convolution that makes or takes ``x`` at them: the scope says so, for
    ``utils/devprof.scope_map`` reads a fusion's layer from its root."""
    with jax.named_scope("conv"):
        return jnp.transpose(x, (1, 2, 3, 0))


def _from_kernel(xt):
    with jax.named_scope("conv"):
        return jnp.transpose(xt, (3, 0, 1, 2))


def _fused_fwd(z, bias, interpret, store):
    yt, idx = _pool_fwd(_to_kernel(z), bias, interpret,
                        jnp.dtype(z.dtype if store is None else store))
    # Widened for a consumer that takes one dtype; on the chip the
    # convert folds into the product that reads it (module docstring).
    return _from_kernel(yt).astype(z.dtype), (idx, yt, bias)


def _fused_bwd(interpret, store, res, g):
    idx, yt, bias = res
    del store        # ``yt`` carries it
    dzt, dbias = _pool_bwd(idx, yt, _to_kernel(g), interpret)
    return (_from_kernel(dzt).astype(g.dtype),
            dbias.sum(axis=(0, 2)).astype(bias.dtype))


fused_bias_relu_max_pool.defvjp(_fused_fwd, _fused_bwd)


def _blocks(ho: int, w: int, c: int, b: int, itemsize: int):
    """(window rows, channels, batch) of a block."""
    cb = _SUBLANES if c % _SUBLANES == 0 else c
    bl = _LANES if b % _LANES == 0 else b
    row_bytes = 2 * w * cb * bl * itemsize
    rows = max(r for r in range(1, ho + 1)
               if ho % r == 0 and (r == 1 or r * row_bytes <= _BLOCK_BYTES))
    return rows, cb, bl


def _chunks(wo: int):
    return [(c0, min(_COLS, wo - c0)) for c0 in range(0, wo, _COLS)]


def _fwd_kernel(*refs, rows: int, wo: int, halo: bool):
    """One block of ``rows`` window rows: the taps of window (r, c) are
    input rows 2r..2r+2 and columns 2c..2c+2; row 2r+2 of the block's last
    window row is the halo's (the next block's first, or padding), column
    2c+2 of the last window is padding."""
    from jax.experimental import pallas as pl

    if halo:
        z_ref, bias_ref, halo_ref, y_ref, idx_ref = refs
        at_bottom = pl.program_id(2) == pl.num_programs(2) - 1
    else:
        z_ref, bias_ref, y_ref, idx_ref = refs
    bias = bias_ref[...]
    neg = jnp.full((1, *z_ref.shape[2:]), -jnp.inf, jnp.float32)

    def biased(t):
        """The bias goes on before the compare, rounded as the input's
        dtype rounds it: taps that differ may round to a tie, and the
        first wins."""
        return (t.astype(jnp.float32) + bias).astype(t.dtype).astype(
            jnp.float32)

    def taps(row, i, c0, n):
        """Columns 2c, 2c+1, 2c+2 of input row ``row`` for windows
        c0..c0+n-1: one load of the 2n columns, split by parity in the
        leading dimension (Mosaic loads 16-bit data with no stride), and
        the column right of them (padding right of the image)."""
        def load(cols):
            t = biased(z_ref[jnp.minimum(row, 2 * rows - 1), cols])
            if i < 2:
                return t
            edge = -jnp.inf
            if halo:
                edge = jnp.where(at_bottom, edge, biased(halo_ref[0, cols]))
            return jnp.where(row == 2 * rows, edge, t)

        pairs = load(pl.ds(2 * c0, 2 * n)).reshape(n, 2, *neg.shape[1:])
        right = load(pl.ds(2 * (c0 + n), 1)) if c0 + n < wo else neg
        return (pairs[:, 0], pairs[:, 1],
                jnp.concatenate([pairs[1:, 0], right]))

    def window_row(r, carry):
        for c0, n in _chunks(wo):
            best = idx = None
            for i in range(3):
                for j, t in enumerate(taps(2 * r + i, i, c0, n)):
                    if best is None:
                        best, idx = t, jnp.zeros(t.shape, jnp.int32)
                        continue
                    # Strict ``>`` in scan order: the first maximum wins,
                    # as in select-and-scatter's ``ge``. ReLU and max
                    # commute.
                    idx = jnp.where(t > best, 3 * i + j, idx)
                    best = jnp.maximum(best, t)
            y_ref[r, pl.ds(c0, n)] = jnp.maximum(best, 0.0).astype(
                y_ref.dtype)
            idx_ref[r, pl.ds(c0, n)] = idx.astype(jnp.int8)
        return carry

    jax.lax.fori_loop(0, rows, window_row, None)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _pool_fwd(zt, bias, interpret, store):
    """``zt`` [H, W, C, B], ``bias`` [C] -> (pooled [Ho, Wo, C, B] as
    ``store``, tap int8 alike)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h, w, c, b = zt.shape
    ho, wo = h // 2, w // 2
    rows, cb, bl = _blocks(ho, w, c, b, zt.dtype.itemsize)
    halo = rows < ho
    in_specs = [pl.BlockSpec((2 * rows, w, cb, bl),
                             lambda bi, ci, hi: (hi, 0, ci, bi)),
                pl.BlockSpec((cb, bl), lambda bi, ci, hi: (ci, 0))]
    # the bias as a kernel adds it: a float32 [channels, lanes] tile
    args = [zt, jnp.broadcast_to(bias.astype(jnp.float32)[:, None], (c, bl))]
    if halo:
        in_specs.append(pl.BlockSpec(
            (1, w, cb, bl),
            lambda bi, ci, hi: (jnp.minimum(2 * rows * (hi + 1), h - 1), 0,
                                ci, bi)))
        args.append(zt)
    out_spec = pl.BlockSpec((rows, wo, cb, bl),
                            lambda bi, ci, hi: (hi, 0, ci, bi))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, rows=rows, wo=wo, halo=halo),
        grid=(b // bl, c // cb, ho // rows),
        in_specs=in_specs, out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((ho, wo, c, b), store),
                   jax.ShapeDtypeStruct((ho, wo, c, b), jnp.int8)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="relu_max_pool_fwd", interpret=interpret)(*args)


def _bwd_kernel(*refs, rows: int, wo: int, halo: bool):
    """Window r covers input rows 2r, 2r+1, 2r+2: an even input row takes
    from window r (tap row 0) and window r-1 (tap row 2), an odd one from
    window r (tap row 1) alone; the same across. So each input position
    sums at most four terms, all read at the output's resolution. The
    bias's gradient is the sum of what the ReLU let through: a partial
    sum a block, over its windows."""
    from jax.experimental import pallas as pl

    if halo:
        idx_ref, y_ref, g_ref, idx_up, y_up, g_up, dz_ref, db_ref = refs
        at_top = pl.program_id(2) == 0
    else:
        idx_ref, y_ref, g_ref, dz_ref, db_ref = refs
    tile = g_ref.shape[2:]

    def window_row(r, dbias):
        def source(up, cols):
            """(winning tap, gradient that the ReLU let through) of the
            windows ``cols`` of window row r (``up``: r-1); the tap is -1
            where there is no such row."""
            at = jnp.maximum(r - 1, 0) if up else r
            idx = idx_ref[at, cols].astype(jnp.int32)
            y = y_ref[at, cols].astype(jnp.float32)
            g = g_ref[at, cols].astype(jnp.float32)
            if up:
                # Above the block's first window row is the halo, above
                # the image's nothing.
                edge = -1
                if halo:
                    edge = jnp.where(at_top, edge,
                                     idx_up[0, cols].astype(jnp.int32))
                    y = jnp.where(r == 0, y_up[0, cols].astype(
                        jnp.float32), y)
                    g = jnp.where(r == 0, g_up[0, cols].astype(
                        jnp.float32), g)
                idx = jnp.where(r == 0, edge, idx)
            return idx, jnp.where(y > 0, g, 0.0)

        def left_of(src, up, c0):
            """``src`` moved one window to the right: what the window
            left of each has (left of the image: no tap, -1)."""
            first = (source(up, pl.ds(c0 - 1, 1)) if c0 else
                     (jnp.full((1, *tile), -1, jnp.int32),
                      jnp.zeros((1, *tile), jnp.float32)))
            return tuple(jnp.concatenate([f, x[:-1]]) if x.shape[0] > 1
                         else f for f, x in zip(first, src))

        def won(src, tap):
            return jnp.where(src[0] == tap, src[1], 0.0)

        for c0, n in _chunks(wo):
            here, up = source(0, pl.ds(c0, n)), source(1, pl.ds(c0, n))
            here_l, up_l = left_of(here, 0, c0), left_of(up, 1, c0)
            # (even column, odd column) of the even and of the odd row
            planes = (
                (won(here, 0) + won(here_l, 2) + won(up, 6) + won(up_l, 8),
                 won(here, 1) + won(up, 7)),
                (won(here, 3) + won(here_l, 5), won(here, 4)))
            for odd_row, pair in enumerate(planes):
                # interleaved in the leading dimension: no strided store
                dz_ref[2 * r + odd_row, pl.ds(2 * c0, 2 * n)] = jnp.stack(
                    pair, axis=1).reshape(2 * n, *tile).astype(dz_ref.dtype)
            dbias = dbias + here[1].sum(axis=0)
        return dbias

    db_ref[0] = jax.lax.fori_loop(0, rows, window_row,
                                  jnp.zeros(tile, jnp.float32))


@functools.partial(jax.jit, static_argnums=3)
def _pool_bwd(idx, yt, gt, interpret):
    """Residuals and ``gt`` [Ho, Wo, C, B] -> (``dz`` [H, W, C, B] in the
    dtype ``yt`` was stored in, the bias's gradient in float32 partial
    sums [row blocks, C, B])."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    ho, wo, c, b = gt.shape
    rows, cb, bl = _blocks(ho, 2 * wo, c, b, gt.dtype.itemsize)
    halo = rows < ho
    block = pl.BlockSpec((rows, wo, cb, bl),
                         lambda bi, ci, hi: (hi, 0, ci, bi))
    in_specs, args = [block] * 3, [idx, yt, gt]
    if halo:
        in_specs += [pl.BlockSpec(
            (1, wo, cb, bl),
            lambda bi, ci, hi: (jnp.maximum(rows * hi - 1, 0), 0, ci,
                                bi))] * 3
        args += [idx, yt, gt]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, rows=rows, wo=wo, halo=halo),
        grid=(b // bl, c // cb, ho // rows),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((2 * rows, 2 * wo, cb, bl),
                                lambda bi, ci, hi: (hi, 0, ci, bi)),
                   pl.BlockSpec((1, cb, bl),
                                lambda bi, ci, hi: (hi, ci, bi))],
        out_shape=[jax.ShapeDtypeStruct((2 * ho, 2 * wo, c, b), yt.dtype),
                   jax.ShapeDtypeStruct((ho // rows, c, b), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="relu_max_pool_bwd", interpret=interpret)(*args)

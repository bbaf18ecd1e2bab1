"""Each token's sum of the rows it has in a buffer: the add-back of an
expert layer that drops nothing (``ops/moe.py``), without a scatter.

A buffer holds rows ``lo .. lo + R`` of some order (the experts' order of
a layer's slots), each of ``D`` float32; ``pos [T, k]`` says at which row
of that order each of a token's ``k`` choices lives.
:func:`sum_rows_by_token` gives token ``t`` the sum, in the order ``c = 0
.. k - 1``, of the buffer's rows ``pos[t, c] - lo`` over the choices with
``lo <= pos[t, c] < hi``, and zero where it has none: irregular reads, a
dense write, no read-modify-write, a fixed order of summation. The mirror
operation, adding rows to their tokens with XLA's scatter-add, took 2.7 ms
for 8,704 rows of 2,048 on the v5e where the gather of as many took 0.4
(PERF.md, Findings, PR 32 and PR 33).

One Pallas kernel: a grid over tiles of tokens, the output a plain dense
block, the buffer left in HBM and only the rows a token has in range
fetched, one row a DMA, all of a tile's copies in flight before the first
is waited for, then added to its token's sum in the order of the token's
choices; the rows a tile has in range, each with its token, sit in scalar
memory, put first in the tile by a sort outside the kernel. A token has
there as many slots as the power of two at or above ``k`` (8 at ``k`` =
6), the slots past its ``k`` choices absent, so that a tile's slots fill
whole blocks of scalar memory for any ``k``: the sort puts them last with
the other absent ones, and nothing fetches, waits for or adds them. A DMA
moves whole (8, 128) tiles, so the kernel's buffer holds a row as ``[D / 128,
128]``, tiles that lie together in HBM, and not as one sublane of ``[R,
D]``'s tiles: :func:`row_shape` says in which of the two shapes the caller
keeps a row, and that shape is what :func:`sum_rows_by_token` reads the
path from. Neither path reads a row outside ``lo .. hi`` into its sums, so
the buffer's other rows may hold anything.

Who takes the kernel: a TPU backend, one device, a width of whole lanes
(a multiple of 128; a row whose ``D / 128`` is no multiple of 8 is padded
to whole tiles by the buffer's layout) and tokens that fill the sublanes (a
multiple of 8) and divide into tiles: a tile's padded slots a whole number
of scalar blocks, the rows its ``k`` choices a token can fetch within the
budget of vector memory (:func:`_tile`). Everything else runs ``k`` masked
gathers summed in the same order, which is also the kernel's test oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from dml_cnn_cifar10_tpu.ops import kernel_paths
from dml_cnn_cifar10_tpu.utils import platform as platform_lib

_LANES = 128
_SUBLANES = 8
#: Budget of a tile's fetched rows in vector memory, counted on the rows it
#: can really fetch (k x tile x D float32, the padded slots fetch nothing):
#: with the sums and the output's blocks a quarter of the limit asked for.
_ROWS_BYTES = 12 << 20
#: A tile's slots in scalar memory, counted padded (:func:`_slots` a
#: token), are one block of a 1-D int32 array, and XLA lays such an array
#: out in tiles of 1,024 (``T(1024)``): Mosaic refuses a block of 512 of it
#: for the described v5e.
_SCALAR_BLOCK = 1024
_VMEM_LIMIT = 64 << 20
#: Tokens whose sums leave for the output block at once.
_CHUNK = 64


def row_shape(tokens: int, k: int, width: int, mesh=None) -> tuple:
    """The shape in which the buffer of :func:`sum_rows_by_token` holds one
    row of ``width`` numbers, for ``tokens`` tokens of ``k`` choices:
    ``(width // 128, 128)`` where the kernel will read it, else
    ``(width,)``. ``mesh`` is the mesh of the enclosing GSPMD program, if
    any; the choice reads the platform, the shapes and the mesh only, and
    is noted for the step's line."""
    if not (platform_lib.on_tpu() and width % _LANES == 0
            and tokens % _SUBLANES == 0 and _tile(tokens, k, width)):
        kernel_paths.note("experts", "xla")
        return (width,)
    if mesh is not None and mesh.size > 1:
        # a bare pallas_call cannot be partitioned by GSPMD, and a token's
        # rows may lie on any device
        kernel_paths.note("experts", "xla (mesh)")
        return (width,)
    wide = _slots(k)
    kernel_paths.note("experts", "pallas sum-by-token" if wide == k else
                      f"pallas sum-by-token ({k} of {wide} slots)")
    return (width // _LANES, _LANES)


def _in_range(pos, lo, hi):
    """``pos - lo`` where ``lo <= pos < hi``, else -1."""
    return jnp.where((pos >= lo) & (pos < hi), pos - lo, -1).astype(jnp.int32)


def sum_rows_xla(buffer: jax.Array, pos: jax.Array, lo, hi) -> jax.Array:
    """The XLA expression on ``buffer [R, D]``: a masked gather a choice,
    summed in order."""
    rel = _in_range(pos, lo, hi)
    total = jnp.zeros((pos.shape[0], buffer.shape[1]), buffer.dtype)
    for c in range(pos.shape[1]):
        rows = buffer[jnp.maximum(rel[:, c], 0)]
        total = total + jnp.where(rel[:, c:c + 1] >= 0, rows, 0)
    return total


def sum_rows_by_token(buffer: jax.Array, pos: jax.Array, lo, hi) -> jax.Array:
    """``[T, D]`` float32: each token's rows of ``buffer [R, *row_shape]``
    summed (module docstring)."""
    if buffer.ndim == 2:
        return sum_rows_xla(buffer, pos, lo, hi)
    return sum_rows_pallas(buffer, _in_range(pos, lo, hi), False)


def _slots(k: int) -> int:
    """A token's slots in the kernel: the power of two at or above ``k``."""
    return 1 << (k - 1).bit_length()


def _tile(tokens: int, k: int, width: int):
    """Tokens a grid step: all of them where their rows fit the budget,
    else the largest power of two times 8 that divides ``tokens``, keeps
    the rows of its ``k`` choices a token inside the budget and its
    :func:`_slots` a token a multiple of :data:`_SCALAR_BLOCK`; None where
    there is none."""
    def fits(tile):
        return tile * k * width * 4 <= _ROWS_BYTES

    if fits(tokens):
        return tokens
    best, tile = None, _SUBLANES
    while tokens % tile == 0 and fits(tile):
        if tile * _slots(k) % _SCALAR_BLOCK == 0:
            best = tile
        tile *= 2
    return best


@functools.partial(jax.jit, static_argnums=2)
def sum_rows_pallas(buffer, rel, interpret=False):
    """The kernel path itself: ``buffer [R, D / 128, 128]`` and ``rel [T,
    k]``, a row of the buffer or -1; ``interpret`` runs it in the Pallas
    interpreter (tests, off TPU). Jitted so that its body is traced once a
    shape."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tokens, k = rel.shape
    _, parts, lanes = buffer.shape
    tile = _tile(tokens, k, parts * lanes)
    chunk = _CHUNK if tile % _CHUNK == 0 else _SUBLANES
    wide = _slots(k)
    if wide > k:
        rel = jnp.pad(rel, ((0, 0), (0, wide - k)), constant_values=-1)
    # A tile's rows in range come first, in the order of their slots (a
    # token's choices stay in order), each with its token: the kernel's
    # scalar loops then run over rows that exist and take no branch, and
    # never reach the padded slots.
    slots = rel.reshape(tokens // tile, tile * wide)
    token = jnp.broadcast_to(
        jnp.arange(tile * wide, dtype=jnp.int32) // wide, slots.shape)
    absent, rows_of, token_of = lax.sort((slots < 0, slots, token),
                                         dimension=1, num_keys=1)
    count = jnp.sum(~absent, 1, dtype=jnp.int32)

    def kernel(count, rows_of, token_of, buf, out, rows, sums, sem):
        n = count[pl.program_id(0)]

        def row_copy(src, i):
            return pltpu.make_async_copy(buf.at[src], rows.at[i], sem)

        def start(i, _):
            row_copy(rows_of[i], i).start()

        def add(i, _):
            t = token_of[i]
            sums[t] = sums[t] + rows[i]

        lax.fori_loop(0, n, start, None)
        sums[...] = jnp.zeros(sums.shape, jnp.float32)
        # every copy moves one row: as many waits as copies were started
        lax.fori_loop(0, n, lambda i, _: row_copy(0, 0).wait(), None)
        lax.fori_loop(0, n, add, None)

        def leave(i, _):
            # [chunk, D / 128, 128] -> [chunk, D]: a token to a sublane
            at = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
            for j in range(parts):
                out[at, j * lanes:(j + 1) * lanes] = sums[at, j, :]

        lax.fori_loop(0, tile // chunk, leave, None)

    a_tiles_slots = pl.BlockSpec((tile * wide,), lambda i, count: (i,),
                                 memory_space=pltpu.SMEM)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((tokens, parts * lanes), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(tokens // tile,),
            in_specs=[a_tiles_slots, a_tiles_slots,
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, parts * lanes),
                                   lambda i, count: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((k * tile, parts, lanes), jnp.float32),
                pltpu.VMEM((tile, parts, lanes), jnp.float32),
                pltpu.SemaphoreType.DMA(())]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="sum_rows_by_token", interpret=interpret,
    )(count, rows_of.reshape(-1), token_of.reshape(-1), buffer)

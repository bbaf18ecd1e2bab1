"""Fused single-pass SGD(+momentum, +weight-decay) optimizer kernel.

The ``tree_map`` chain in ``train/optim.py``'s SGD branch materializes
three elementwise passes over every parameter byte: the decayed gradient
(``g + wd*p``), the momentum trace (``mu*m + g'``), and the apply
(``p - lr*m'``) — each a separate HBM read-modify-write when XLA does
not fuse across the tree_map boundaries. At the weight-update tail of a
small-step workload (the reference CNN is ~1 ms of MXU work; SGD+momentum
touches every param byte ~3x) this is pure bandwidth waste. This module
applies the whole update in ONE pass over the bytes:

- **Pallas TPU kernel** (:func:`_pallas_leaf`): the leaf is flattened,
  padded to the f32 tile (8x128), and a grid of VMEM blocks computes
  ``m' = mu*m + (g + wd*p); p' = p - lr*m'`` reading p/g/m once and
  writing p'/m' once. Engaged when the backend is TPU and every operand
  of the update is replicated. A ``pallas_call`` is an opaque custom
  call the partitioner cannot split and, on a mesh of more than one
  device, will not even place ("Mosaic kernels cannot be automatically
  partitioned"), so there the whole update runs under a ``shard_map``
  with replicated specs: every device applies the full update to its
  own replica, which is what the replicated layout means anyway.
- **XLA expression** (:func:`_xla_leaf`): the identical f32 elementwise
  expression, in the identical order, as one fused XLA loop — selected
  off TPU, so CPU tier-1 runs the exact same math, and for sharded
  (zero1 / fsdp / tensor-parallel) update operands, where GSPMD
  partitions it into one loop over the local shard (the same
  single-pass property).

Equivalence (PARITY.md "Update-path equivalence", pinned by
``tests/test_zero1.py``): the XLA fallback is BIT-IDENTICAL to the
legacy tree_map chain (same elementwise expression — asserted in the
compiled train step); the Pallas kernel agrees with the fallback within
a few f32 ULPs (pinned ≤ 5e-7 absolute) — the expressions are
identical, but XLA may contract multiply-add pairs into FMAs where the
kernel/interpreter rounds each op separately. No reductions anywhere,
so the bound is per-element and does not grow with model size. Non-f32
leaves (none in the default configs — params are f32 even under bf16
compute) take the fallback unconditionally: the kernel is written for
the f32 tile.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dml_cnn_cifar10_tpu.ops import kernel_paths
from dml_cnn_cifar10_tpu.utils import platform as platform_lib

#: f32 VMEM tile: (sublanes, lanes). Leaves pad to a whole number of
#: tiles; the grid walks blocks of ``_BLOCK_ROWS`` sublane rows.
_LANES = 128
_SUBLANES = 8
_BLOCK_ROWS = 512  # 512 x 128 x 4 B = 256 KiB per ref; 5 refs < 2 MiB VMEM


def _use_pallas(optimizer_sharding: str) -> bool:
    """Platform selection: the Pallas lowering only on a real TPU and
    only for the replicated (non-GSPMD-sharded) update layout."""
    return platform_lib.on_tpu() and optimizer_sharding != "zero1"


def _xla_leaf(p, g, m, lr, momentum: float, weight_decay: float):
    """One leaf, fallback form: the same expression (and order) as the
    kernel — XLA fuses the chain into a single loop over the bytes."""
    if weight_decay:
        g = g + weight_decay * p
    if m is not None:
        m = momentum * m + g
        g = m
    return p - lr * g.astype(p.dtype), m


def _sgd_kernel(lr_ref, p_ref, g_ref, m_ref, out_p_ref, out_m_ref, *,
                momentum: float, weight_decay: float):
    """Momentum-variant kernel body: one read of p/g/m, one write of
    p'/m' — the whole update in a single pass over the block."""
    p = p_ref[...]
    g = g_ref[...]
    if weight_decay:
        g = g + weight_decay * p
    m_new = momentum * m_ref[...] + g
    out_m_ref[...] = m_new
    out_p_ref[...] = p - lr_ref[0] * m_new


def _sgd_kernel_plain(lr_ref, p_ref, g_ref, out_p_ref, *,
                      weight_decay: float):
    """Momentum-free variant (the reference's plain SGD)."""
    p = p_ref[...]
    g = g_ref[...]
    if weight_decay:
        g = g + weight_decay * p
    out_p_ref[...] = p - lr_ref[0] * g


def _pad_rows(flat):
    """Flat [n] f32 → [rows, 128] with rows a multiple of the sublane
    tile (zero-padded; the pad lanes compute garbage that is sliced
    away)."""
    n = flat.shape[0]
    tile = _SUBLANES * _LANES
    padded = -(-n // tile) * tile
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
    return flat.reshape(padded // _LANES, _LANES)


def _pallas_leaf(p, g, m, lr, momentum: float, weight_decay: float,
                 interpret: bool):
    """One leaf through the Pallas kernel: flatten → pad to tiles →
    grid over row blocks → slice the pad back off."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    shape = p.shape
    n = p.size
    p2 = _pad_rows(p.reshape(-1))
    g2 = _pad_rows(g.reshape(-1))
    rows = p2.shape[0]
    block_rows = min(_BLOCK_ROWS, rows)
    grid = (-(-rows // block_rows),)
    lr1 = jnp.reshape(lr.astype(jnp.float32), (1,))

    def row_block(i):
        return (i, 0)

    lr_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    blk = pl.BlockSpec((block_rows, _LANES), row_block)
    out_shape = jax.ShapeDtypeStruct((rows, _LANES), jnp.float32)
    if m is not None:
        m2 = _pad_rows(m.reshape(-1))
        new_p, new_m = pl.pallas_call(
            functools.partial(_sgd_kernel, momentum=momentum,
                              weight_decay=weight_decay),
            grid=grid,
            in_specs=[lr_spec, blk, blk, blk],
            out_specs=[blk, blk],
            out_shape=[out_shape, out_shape],
            interpret=interpret,
        )(lr1, p2, g2, m2)
        return (new_p.reshape(-1)[:n].reshape(shape),
                new_m.reshape(-1)[:n].reshape(shape))
    new_p = pl.pallas_call(
        functools.partial(_sgd_kernel_plain, weight_decay=weight_decay),
        grid=grid,
        in_specs=[lr_spec, blk, blk],
        out_specs=blk,
        out_shape=out_shape,
        interpret=interpret,
    )(lr1, p2, g2)
    return new_p.reshape(-1)[:n].reshape(shape), None


def fused_sgd_update(params: Any, grads: Any, momentum_tree: Optional[Any],
                     lr, momentum: float, weight_decay: float,
                     optimizer_sharding: str = "none",
                     use_pallas: Optional[bool] = None,
                     interpret: Optional[bool] = None,
                     mesh=None) -> Tuple[Any, Optional[Any]]:
    """``(new_params, new_momentum_tree)`` — the whole SGD update in one
    pass per leaf. ``momentum_tree=None`` means plain SGD (no trace kept).

    ``use_pallas=None`` resolves by platform (:func:`_use_pallas`);
    ``interpret=None`` resolves to interpreter mode off-TPU (the
    kernel-parity tests force ``use_pallas=True`` on CPU and run the
    interpreter). Only f32 leaves enter the kernel; anything else takes
    the identical-math XLA expression. ``mesh`` is the mesh of the
    enclosing GSPMD program, if any: with more than one device the
    kernel path runs under a replicated ``shard_map`` over it (module
    docstring). Callers already inside a ``shard_map`` pass none.
    """
    if use_pallas is None:
        use_pallas = _use_pallas(optimizer_sharding)
    if interpret is None:
        interpret = not platform_lib.on_tpu()
    lr = jnp.asarray(lr, jnp.float32)
    wrap = use_pallas and mesh is not None and mesh.size > 1
    kernel_paths.note(
        "update", "xla" if not use_pallas else
        ("pallas" + ("-interpret" if interpret else "")
         + (f"/shard_map[{mesh.size} replicas]" if wrap else "")))

    def one(p, g, m, lr):
        if (use_pallas and p.dtype == jnp.float32
                and g.dtype == jnp.float32
                and (m is None or m.dtype == jnp.float32)):
            return _pallas_leaf(p, g, m, lr, momentum, weight_decay,
                                interpret)
        return _xla_leaf(p, g, m, lr, momentum, weight_decay)

    def update(params, grads, momentum_tree, lr):
        if momentum_tree is None:
            return jax.tree.map(lambda p, g: one(p, g, None, lr)[0],
                                params, grads), None
        out = jax.tree.map(lambda p, g, m: one(p, g, m, lr),
                           params, grads, momentum_tree)
        # Structural transpose (treedef-driven, like optim.py's adafactor
        # unzip): params-of-pairs → pair-of-params-trees.
        return jax.tree_util.tree_transpose(
            jax.tree.structure(params), jax.tree.structure((0, 0)), out)

    if wrap:
        update = jax.shard_map(update, mesh=mesh, in_specs=P(),
                               out_specs=P(), check_vma=False)
    return update(params, grads, momentum_tree, lr)

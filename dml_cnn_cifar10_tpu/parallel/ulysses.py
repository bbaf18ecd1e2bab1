"""Ulysses-style all-to-all sequence parallelism over the ``seq`` mesh axis.

The second long-context strategy next to
:mod:`~dml_cnn_cifar10_tpu.parallel.ring_attention` (no reference
counterpart — the reference is attention-free, ``cifar10cnn.py:94-147``;
SURVEY §2.3/§5 scope long-context as a first-class capability here).

Design (the DeepSpeed-Ulysses recipe, TPU-native): activations live
sequence-sharded ``[B, S/n, H, D]`` between blocks — identical layout to
the ring path, so the two are drop-in alternatives. At the attention
boundary an ``all_to_all`` over ``seq`` re-partitions from
sequence-sharded to *head*-sharded ``[B, S, H/n, D]``; each device then
runs ordinary full-sequence attention on its head slice (any local kernel
— the Pallas flash kernel for long S), and a second ``all_to_all``
restores sequence sharding.

Trade-off vs the ring: Ulysses moves Q, K, V and O each once through an
all-to-all (4·B·S·H·D/n per device, one shot, rides ICI), while the ring
moves K/V n−1 times but never re-partitions and has no head-count
constraint. Ulysses needs ``heads % n == 0``; its local attention is a
single dense kernel (best MXU utilization at moderate n), whereas the
ring's blockwise pieces win when S is too long for even one full-sequence
attention to fit.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from dml_cnn_cifar10_tpu.ops import attention as attn
from dml_cnn_cifar10_tpu.parallel.ring_attention import (
    sequence_sharding, sp_partition_spec, sp_shard_map)

__all__ = ["ulysses_attention", "ulysses_attention_local",
           "sequence_sharding"]


def ulysses_attention_local(q: jax.Array, k: jax.Array, v: jax.Array,
                            axis_name: str,
                            scale: Optional[float] = None,
                            use_pallas: bool = False,
                            causal: bool = False,
                            segment_ids: Optional[jax.Array] = None,
                            window: Optional[int] = None
                            ) -> jax.Array:
    """Per-device body under ``shard_map``: Q/K/V sequence-sharded
    ``[B, S_local, H, D]`` → out ``[B, S_local, H, D]``.

    ``all_to_all`` (seq→head re-partition) → full-seq local attention →
    ``all_to_all`` back. Heads must divide the axis size. Causality is
    position-exact here: the local kernel sees the full sequence, so the
    flag passes straight through. Differentiable end to end (all_to_all
    has a transpose rule; the flash path brings its custom_vjp).
    """
    n = lax.axis_size(axis_name)
    if n == 1:
        return attn.dispatch_attention(q, k, v, use_pallas=use_pallas,
                                       scale=scale, causal=causal,
                                       segment_ids=segment_ids,
                                       window=window)
    if segment_ids is not None:
        # Per-position ids are tiny (~2 B/token): all-gather the
        # sequence-sharded ids so the post-all-to-all full-sequence
        # kernel masks exactly.
        segment_ids = lax.all_gather(segment_ids, axis_name, axis=1,
                                     tiled=True)
    # [B, S/n, H, D] -> [B, S, H/n, D]: split the head dim over the axis,
    # concatenate the sequence dim. tiled=True keeps the dims in place.
    q, k, v = (
        lax.all_to_all(t, axis_name, split_axis=2, concat_axis=1, tiled=True)
        for t in (q, k, v))
    o = attn.dispatch_attention(q, k, v, use_pallas=use_pallas, scale=scale,
                                causal=causal, segment_ids=segment_ids,
                                window=window)
    # [B, S, H/n, D] -> [B, S/n, H, D]
    return lax.all_to_all(o, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                      scale: Optional[float] = None,
                      axis_name: str = "seq",
                      use_pallas: bool = False,
                      causal: bool = False,
                      segment_ids: Optional[jax.Array] = None,
                      window: Optional[int] = None) -> jax.Array:
    """Sequence-parallel attention via head/sequence all-to-all.

    Global-view entrypoint, same contract as
    :func:`~dml_cnn_cifar10_tpu.parallel.ring_attention.ring_attention`
    (layout rule shared via ``sp_partition_spec``): ``[B, S, H, D]``
    arrays, S divisible by the ``seq`` axis; batch stays sharded on
    ``data`` so dp × sp compose. Heads shard over ``model`` when they
    divide it (sp × tp), and the per-device head count must additionally
    divide the ``seq`` axis.
    """
    nseq = mesh.shape[axis_name]
    _, head_axis = sp_partition_spec(mesh, axis_name, q.shape[1],
                                     q.shape[2])
    local_heads = q.shape[2] // (mesh.shape["model"] if head_axis else 1)
    if local_heads % nseq:
        raise ValueError(
            f"{local_heads} per-device heads not divisible by seq axis "
            f"{nseq}; use ring attention for head counts the axis can't "
            f"split")
    kw = dict(axis_name=axis_name, scale=scale, use_pallas=use_pallas,
              causal=causal, window=window)
    if segment_ids is None:
        local = functools.partial(ulysses_attention_local, **kw)
        args = (q, k, v)
    else:
        def local(q, k, v, seg):
            return ulysses_attention_local(q, k, v, segment_ids=seg, **kw)
        args = (q, k, v, segment_ids.astype(jnp.int32))
    fn = sp_shard_map(local, mesh, axis_name, q.shape[1], q.shape[2],
                      with_segments=segment_ids is not None)
    return fn(*args)

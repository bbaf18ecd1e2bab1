#!/usr/bin/env python
"""Pipeline-parallel vs data-parallel benchmark (round-1 #7; round-3 1F1B).

Times the full ViT training step at a fixed global batch over several
mesh layouts of one multi-chip TPU host (a device count divisible by 4;
fails off TPU — a time comes only from a chip run), and reads the
compiled step's TEMP-ALLOCATION bytes from XLA's memory analysis — the
live-activation footprint the 1F1B schedule exists to cap:
GPipe-autodiff's saved scan carries grow O(M); 1F1B's ring buffer is
O(P), flat in M.

Usage: python tools/bench_pp.py [--steps 8] [--batch 32] [--depth 8]
Prints one markdown table.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from dml_cnn_cifar10_tpu.config import (DataConfig, ModelConfig,  # noqa: E402
                                        OptimConfig, ParallelConfig)
from dml_cnn_cifar10_tpu.models.registry import get_model  # noqa: E402
from dml_cnn_cifar10_tpu.parallel import mesh as mesh_lib  # noqa: E402
from dml_cnn_cifar10_tpu.parallel import step as step_lib  # noqa: E402


def time_layout(name, pcfg, model_cfg, batch, steps):
    mesh = mesh_lib.build_mesh(pcfg)
    data_cfg = DataConfig(crop_height=16, crop_width=16)
    optim_cfg = OptimConfig(learning_rate=0.01)
    model_def = get_model(model_cfg.name)
    sh = step_lib.train_state_shardings(mesh, model_def, model_cfg,
                                        data_cfg, optim_cfg)
    state = step_lib.init_train_state(
        jax.random.key(0), model_def, model_cfg, data_cfg, optim_cfg,
        mesh, state_sharding=sh)
    train = step_lib.make_train_step(model_def, model_cfg, optim_cfg,
                                     mesh, state_sharding=sh)
    rng = np.random.default_rng(0)
    im = rng.normal(0.5, 0.25, (batch, 16, 16, 3)).astype(np.float32)
    lb = rng.integers(0, 10, batch).astype(np.int32)
    im, lb = mesh_lib.shard_batch(mesh, im, lb)
    # Temp bytes of the compiled step: the transient (activation/workspace)
    # footprint — where the GPipe-vs-1F1B memory story shows up.
    # One AOT compile serves both the memory probe and the timed loop
    # (calling the jitted fn would compile the same program a second
    # time — the AOT path has its own executable cache).
    compiled = train.lower(state, im, lb).compile()
    temp_mb = None
    try:
        temp_mb = compiled.memory_analysis().temp_size_in_bytes / 2**20
    except Exception:
        pass
    state, m = compiled(state, im, lb)      # warm
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = compiled(state, im, lb)
    jax.block_until_ready(m["loss"])
    dt = (time.perf_counter() - t0) / steps
    loss = float(jax.device_get(m["loss"]))
    return name, dt * 1e3, batch / dt, temp_mb, loss


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--depth", type=int, default=8)
    p.add_argument("--dim", type=int, default=64,
                   help="vit_dim — the residual-ring-vs-recompute verdict "
                        "scales with it (ring IO is O(dim) per token, "
                        "recompute FLOPs O(dim^2))")
    args = p.parse_args()

    from bench import device_stamp
    stamp = device_stamp()  # fails off TPU / on an unknown device_kind
    n = stamp["device_count"]
    if n % 4:
        raise SystemExit(f"bench_pp needs a device count divisible by 4 "
                         f"for its pp=4 layouts, got {n}")

    base = dict(name="vit_tiny", pool="mean", logit_relu=False,
                vit_depth=args.depth, vit_dim=args.dim, vit_heads=2,
                patch_size=4,
                use_pallas_attention=False)
    pp4 = ParallelConfig(data_axis=n // 4, pipe_axis=4)
    d4 = f"dp={n // 4} x pp=4"
    layouts = [
        (f"dp={n}", ParallelConfig(data_axis=n), ModelConfig(**base)),
        (f"dp={n // 2} x pp=2 1f1b (M=P)",
         ParallelConfig(data_axis=n // 2, pipe_axis=2), ModelConfig(**base)),
        (f"{d4} gpipe (M=P)", pp4,
         ModelConfig(**base, pipe_schedule="gpipe")),
        (f"{d4} 1f1b-rec (M=P)", pp4, ModelConfig(**base)),
        (f"{d4} 1f1b-ring (M=P)", pp4,
         ModelConfig(**base, pipe_schedule="1f1b_ring")),
        (f"{d4} gpipe (M=4P)", pp4,
         ModelConfig(**base, pipe_schedule="gpipe", pipe_microbatches=16)),
        (f"{d4} 1f1b-rec (M=4P)", pp4,
         ModelConfig(**base, pipe_microbatches=16)),
        (f"{d4} 1f1b-ring (M=4P)", pp4,
         ModelConfig(**base, pipe_schedule="1f1b_ring",
                     pipe_microbatches=16)),
    ]
    rows = [time_layout(n, pc, mc, args.batch, args.steps)
            for n, pc, mc in layouts]
    ref = rows[0][1]
    print(f"\nViT depth={args.depth} dim={args.dim} global batch={args.batch}, "
          f"{args.steps} timed steps, {stamp}\n")
    print(f"| layout | step ms | images/sec | temp MiB | vs dp={n} | "
          "final loss |")
    print("|---|---|---|---|---|---|")
    for name, ms, ips, temp, loss in rows:
        t = f"{temp:.0f}" if temp is not None else "n/a"
        print(f"| {name} | {ms:.1f} | {ips:.0f} | {t} | {ref / ms:.2f}x | "
              f"{loss:.4f} |")


if __name__ == "__main__":
    main()

#!/usr/bin/env python
"""ResNet-50 @ 224² on the real chip — the bn-vs-nf byte-reduction A/B.

Round-4 verdict #6: the roofline retired the Pallas-kernel path (76.5 %
of step time bandwidth-bound at 86 % of the HBM roof ⇒ ~35 % MFU ceiling
for BatchNorm semantics) and named "BN-free variants" as the only lever
that moves fewer bytes. This benchmark measures that lever:
``--resnet_norm nf`` (scaled weight standardization + SkipInit,
models/resnet.py) against the BN baseline on identical geometry.

Method: synthetic ImageNet-shaped
uint8 records resident in HBM, in-scan device decode, K-step chunk,
bf16 compute, 3 timed repetitions with min/median/max.

Usage: python tools/bench_resnet.py [--batch 256] [--k 20] [--chunks 6]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def measure(norm: str, batch: int, k: int, chunks: int, reps: int,
            depth: int = 50, hw: int = 224, classes: int = 1000,
            s2d: bool = False) -> dict:
    import jax
    import numpy as np

    from dml_cnn_cifar10_tpu.config import (DataConfig, ModelConfig,
                                            OptimConfig, ParallelConfig)
    from dml_cnn_cifar10_tpu.models.registry import get_model
    from dml_cnn_cifar10_tpu.parallel import mesh as mesh_lib
    from dml_cnn_cifar10_tpu.parallel import step as step_lib
    from dml_cnn_cifar10_tpu.utils.profiling import (abstractify,
                                                     compiled_flops)

    name = f"resnet{depth}"
    mesh = mesh_lib.build_mesh(ParallelConfig())
    model_cfg = ModelConfig(name=name, logit_relu=False,
                            compute_dtype="bfloat16", num_classes=classes,
                            resnet_norm=norm, resnet_s2d=s2d, remat=False)
    data_cfg = DataConfig(image_height=hw, image_width=hw, crop_height=hw,
                          crop_width=hw, num_classes=classes,
                          normalize="scale")
    optim_cfg = OptimConfig(learning_rate=0.1)
    model_def = get_model(name)

    # Keyed compile store, shared with bench.py's dir convention: the
    # FLOPs probe below reads the entry's cost analysis instead of
    # paying a second AOT compile.
    from bench import _bench_cache_dir
    from dml_cnn_cifar10_tpu.compilecache import CompileCache
    cache = CompileCache(_bench_cache_dir())

    sh = step_lib.train_state_shardings(mesh, model_def, model_cfg,
                                        data_cfg, optim_cfg)
    state = step_lib.init_train_state(jax.random.key(0), model_def,
                                      model_cfg, data_cfg, optim_cfg, mesh,
                                      state_sharding=sh,
                                      compile_cache=cache)

    # Synthetic uint8 dataset resident in HBM (2 batches worth — the
    # gather indexes modulo n), decoded in-scan (the >1 GB rule).
    rng = np.random.default_rng(0)
    n = 2 * batch
    imgs = rng.integers(0, 256, (n, hw, hw, 3), dtype=np.uint8)
    lbls = rng.integers(0, classes, n).astype(np.int32)
    repl = mesh_lib.replicated(mesh)
    ds_images = jax.device_put(imgs, repl)
    ds_labels = jax.device_put(lbls, repl)
    chunk = step_lib.make_train_chunk_resident(
        model_def, model_cfg, optim_cfg, mesh, ds_images, ds_labels,
        state_sharding=sh, data_cfg=data_cfg,
        index_stream=(0, batch, k), compile_cache=cache)

    state, metrics = chunk(state)
    float(jax.device_get(metrics["loss"]))          # compile + drain
    rates = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(chunks):
            state, metrics = chunk(state)
        float(jax.device_get(metrics["loss"]))
        dt = time.perf_counter() - t0
        rates.append(chunks * k * batch / dt)
    med = statistics.median(rates)

    row = {
        "norm": norm,
        "img_s_median": round(med, 1),
        "img_s_min": round(min(rates), 1),
        "img_s_max": round(max(rates), 1),
        "reps": reps,
    }
    # FLOPs from the SCAN-FREE single step (the bench.py convention —
    # exact, no scan-body accounting assumption).
    train_step = step_lib.make_train_step(model_def, model_cfg, optim_cfg,
                                          mesh, state_sharding=sh,
                                          compile_cache=cache)
    img_abs = jax.ShapeDtypeStruct((batch, hw, hw, 3), np.float32)
    lab_abs = jax.ShapeDtypeStruct((batch,), np.int32)
    flops = compiled_flops(train_step,
                           (abstractify(state), img_abs, lab_abs))
    if flops:
        tflops = flops * (med / batch) / 1e12
        row["tflops_per_sec"] = round(tflops, 2)
        # Peak from the chip the bench actually ran on (bench.PEAKS).
        from bench import device_peaks
        peak = device_peaks(jax.devices()[0].device_kind)["tflops"]
        row["peak_tflops"] = peak
        row["mfu"] = round(tflops / peak, 4)
    return row


def main():
    from bench import _bench_cache_dir, device_stamp
    _bench_cache_dir()  # arms jax's cache before anything compiles
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--k", type=int, default=20)
    ap.add_argument("--chunks", type=int, default=6)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--norms", type=str, nargs="+", default=["bn", "nf"])
    args = ap.parse_args()
    stamp = device_stamp()  # fails off TPU / on an unknown device_kind
    for norm in args.norms:
        row = measure(norm, args.batch, args.k, args.chunks, args.reps)
        print(json.dumps({**row, **stamp}), flush=True)


if __name__ == "__main__":
    main()

#!/usr/bin/env python
"""Headline benchmark: steady-state training throughput, images/sec/chip.

Runs the faithful reference workload — the 5-layer CIFAR-10 CNN at global
batch 128 (``cifar10cnn.py:13,94-147``) — as one compiled SPMD step over all
available devices, fed by the real input pipeline, and measures steady-state
throughput after compile, in BOTH compute dtypes (fp32 and bf16 — the
MXU-native dtype). The headline value is the faster config; both rows ride
along with TFLOP/s + MFU from XLA's compiled cost analysis.

Round-5 (verdict #4/#5) methodology:

- Every row runs ``reps`` (default 3) INDEPENDENT timed repetitions after
  one shared warmup, and reports min/median/max + spread — a single
  sample cannot adjudicate few-percent deltas. The row value is the
  MEDIAN (robust to a slow outlier rep); ``spread_pct`` =
  (max−min)/median tells you how much to trust a comparison.
- The headline config uses the DEVICE index stream
  (``data/device_stream.py``): the training dispatch uploads nothing at
  all. A host-index A/B row rides along.
- Round 8: every row also records a per-dispatch step-time tail
  (``step_ms_p50`` / ``step_ms_p99`` + the raw series) from a separate
  drained sampling pass, so ``tools/bench_gate.py`` can flag tail
  regressions the windowed mean hides.

Baseline note: the reference publishes NO performance numbers
(``README.md``, SURVEY §6 — ``BASELINE.json.published == {}``).
``vs_baseline`` is therefore anchored to the driver's north-star throughput:
≥20,000 steps × batch 128 in <120 s on a v4-8 ⇒ 21,333 images/sec ÷ 8 chips
= 2,666.7 images/sec/chip. vs_baseline = measured / 2666.7.

Compile cost (round 6): every compile seam routes through the
persistent compilation cache (``compilecache/``). jax's own cache is
placed by the one resolver (``compilecache.arm_native_cache``:
``JAX_COMPILATION_CACHE_DIR`` if set, else ``<repo>/.jax_cache``) and
the repo's keyed store sits beside it in ``dml_keyed/``. Warm re-runs
skip the XLA recompile, each row reports ``compile_s`` + ``cache_hit``,
and the FLOPs figure is read from the SAME cached artifact the timed
path executes.

A benchmark number comes only from a chip: ``main()`` fails when the
platform is not ``tpu`` or the ``device_kind`` is not in :data:`PEAKS`,
and the top line and every row carry platform, ``device_kind`` and
device count.

Prints ONE JSON line:
  {"metric": "train_throughput", "value": N, "unit": "images/sec/chip",
   "vs_baseline": N, "platform": "tpu", "device_kind": "...",
   "device_count": N, "fp32": {...}, "bf16": {...}, ...}
"""

from __future__ import annotations

import json
import os
import statistics
import time

NORTH_STAR_IMAGES_PER_SEC_PER_CHIP = 20000 * 128 / 120.0 / 8.0  # 2666.7


def _bench_cache_dir():
    """The bench scripts' keyed compile store (``CompileCache``): beside
    jax's own cache, wherever the one resolver placed that. Calling it
    also arms jax's cache, so call it before anything compiles."""
    from dml_cnn_cifar10_tpu.compilecache import arm_native_cache
    jax_dir = arm_native_cache()
    return os.path.join(jax_dir, "dml_keyed") if jax_dir else None


# The one table of per-chip peaks, keyed by jax.devices()[0].device_kind
# exactly as the chip reports it. A device that is not here is an
# error, not a default: add it with its source.
# tflops is ONE number per part, NOT per dtype: under XLA's default
# precision, float32 matmuls/convs also execute on the bf16 MXU (bf16
# multiplies, fp32 accumulate), so MFU is utilization of the MXU the
# code actually runs on.
PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
    "TPU v5 lite": {"tflops": 197.0, "int8_tops": 393.0, "hbm_gbps": 819.0},
}


def device_peaks(device_kind: str) -> dict:
    """Peaks of ``device_kind`` from :data:`PEAKS`; unknown is an error."""
    if device_kind not in PEAKS:
        raise SystemExit(
            f"bench: no peak figures for device_kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}. Add it to bench.PEAKS with its "
            f"source.")
    return PEAKS[device_kind]


def device_stamp() -> dict:
    """What every row and the top line carry: the device as jax reports
    it. Fails unless that is a TPU whose peaks are known — a number from
    a CPU run is never written under the name of a device metric."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench: platform is {dev.platform!r}, not 'tpu'. Benchmark "
            f"numbers come only from a chip run; debug on CPU through "
            f"cifar10cnn.py at a tiny size instead.")
    device_peaks(dev.device_kind)
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def _optimizer_ms_probe(chunk, prefetch, state, chunk_k: int,
                        dispatches: int = 2):
    """``(state, per_step_optimizer_ms | None)`` — a short
    ``jax.profiler`` capture around ``dispatches`` extra chunk calls,
    parsed host-side (utils/devprof.py) into the per-step device time
    inside the step's ``named_scope("optimizer")``. The row then RECORDS
    the weight-update tail the fused kernel / zero1 sharding attack,
    instead of inferring it from throughput deltas. None means the
    trace came back with no device lanes to attribute (the backend
    reported nothing); a profiler or parse error is raised."""
    import jax

    import shutil
    import tempfile

    from dml_cnn_cifar10_tpu.utils import devprof

    tmp = tempfile.mkdtemp(prefix="bench_opt_ms_")
    try:
        jax.profiler.start_trace(tmp)
        try:
            for _ in range(dispatches):
                state, metrics = chunk(state, *next(prefetch))
            float(jax.device_get(metrics["loss"]))
        finally:
            jax.profiler.stop_trace()
        lanes = devprof.parse_profile_dir(tmp)
        if not lanes:
            return state, None
        per_step = (sum(ln.get("optimizer_ms") or 0.0 for ln in lanes)
                    / len(lanes) / (dispatches * chunk_k))
        return state, round(per_step, 4)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def measure(compute_dtype: str, chunk_k: int = 100, chunks: int = 60,
            dev_stream: bool = True, reps: int = 3,
            optimizer_sharding: str = "none") -> dict:
    """Steady-state throughput + MFU for one compute dtype —
    ``reps`` independently timed repetitions after one warmup.

    ``dev_stream`` (default ON — the headline config, round-4 verdict
    #5) generates the shuffled index stream on device
    (``data/device_stream.py``): the dispatch carries NO host data at
    all. ``False`` ships host-generated index arrays (the A/B row).
    ``optimizer_sharding="zero1"`` runs the ZeRO-1 sharded weight
    update (reduce-scatter / sharded update / all-gather over the data
    mesh; docs/SHARDING.md) — the ``fp32_zero1`` row."""
    import jax

    from dml_cnn_cifar10_tpu.config import reference_config
    from dml_cnn_cifar10_tpu.data import pipeline as pipe
    from dml_cnn_cifar10_tpu.parallel import mesh as mesh_lib
    from dml_cnn_cifar10_tpu.parallel import step as step_lib
    from dml_cnn_cifar10_tpu.train.loop import Trainer
    from dml_cnn_cifar10_tpu.utils.profiling import (abstractify,
                                                     compiled_flops)

    cfg = reference_config()
    cfg.data.dataset = "synthetic"           # zero-egress box: CIFAR-layout
    cfg.data.data_dir = "/tmp/bench_cifar"   # synthetic records, real pipeline
    cfg.data.synthetic_train_records = 20480
    cfg.data.synthetic_test_records = 1024
    cfg.batch_size = 128
    cfg.log_dir = "/tmp/bench_logs_unused"
    cfg.checkpoint_every = 10**9             # no checkpoint I/O in the loop
    cfg.data.prefetch = 4                    # measured +1.6% over depth 2
    # The raw-chunk path reads the base iterator's in-memory permutation
    # directly; the native loader's C++ shuffle pool would be dead weight.
    cfg.data.use_native_loader = False
    cfg.model.compute_dtype = compute_dtype
    cfg.optim.optimizer_sharding = optimizer_sharding
    # Compile-cache every seam (trainer step fns, the chunk below, the
    # FLOPs probes): warm bench re-runs skip XLA entirely.
    cfg.compile_cache_dir = _bench_cache_dir()

    trainer = Trainer(cfg)
    state = trainer.init_or_restore()
    n_chips = len(jax.devices())

    # HBM-resident data path (parallel/step.py:make_train_chunk_resident):
    # the full uint8 dataset lives in HBM, and gather + decode + K training
    # steps run as one compiled dispatch. The reference CNN is ~1 ms of MXU
    # work per step — host-side gather/decode/H2D (measured ~8 ms per
    # 20-step chunk) bounds every host-fed pipeline, so the dataset moves
    # to the device once instead.
    # Steps per dispatch: 100 divides the reference's 200/500
    # output/eval cadences, so the benched config is exactly what the
    # Trainer can run with observable-boundary parity; the K=320 row
    # shows what relaxing that buys. (The sweep that chose it is not
    # measured on the current chip.)
    train_it = pipe.input_pipeline(cfg.data, cfg.batch_size, train=True)
    repl = mesh_lib.replicated(trainer.mesh)
    ds_images = jax.device_put(train_it.images, repl)
    ds_labels = jax.device_put(train_it.labels.astype("int32"), repl)
    chunk = step_lib.make_train_chunk_resident(
        trainer.model_def, cfg.model, cfg.optim, trainer.mesh,
        ds_images, ds_labels, state_sharding=trainer.state_sharding,
        data_cfg=cfg.data,
        index_stream=((cfg.data.seed, cfg.batch_size, chunk_k)
                      if dev_stream else None),
        compile_cache=trainer.compile_cache)
    if dev_stream:
        def feed():
            return ()
        prefetch = pipe.PrefetchIterator(
            iter(feed, None), depth=1, place=None)
    else:
        idx_sh = mesh_lib.batch_sharding(trainer.mesh, 2, leading_dims=1)

        def next_idx():
            return (jax.device_put(train_it.next_index_chunk(chunk_k),
                                   idx_sh),)
        prefetch = pipe.PrefetchIterator(
            iter(next_idx, None), depth=cfg.data.prefetch, place=None)

    # Warmup: first call compiles, more to fill the pipeline. Each
    # window below ends in a device_get of the last loss: a full drain.
    for _ in range(3):
        state, metrics = chunk(state, *next(prefetch))
    float(jax.device_get(metrics["loss"]))

    # Timed steady state: reps independent windows, each drained.
    rates = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(chunks):
            state, metrics = chunk(state, *next(prefetch))
        float(jax.device_get(metrics["loss"]))  # full drain
        dt = time.perf_counter() - t0
        rates.append(chunks * chunk_k * cfg.batch_size / dt / n_chips)

    # Step-time tail (round 8): the windowed rates above report the
    # MEAN; a periodic stall (GC, allocator, a slow collective) hides
    # in it completely. A separate sampling pass times individual
    # dispatches, each drained — per-dispatch drains serialize host and
    # device, so these samples are NOT comparable to the throughput
    # windows (each carries one drain round trip); they exist to rank
    # p99 against p50, which tools/bench_gate.py gates on.
    from dml_cnn_cifar10_tpu.utils.telemetry import percentile
    tail_ms = []
    for _ in range(min(chunks, 30)):
        t0 = time.perf_counter()
        state, metrics = chunk(state, *next(prefetch))
        float(jax.device_get(metrics["loss"]))
        tail_ms.append((time.perf_counter() - t0) / chunk_k * 1e3)
    # Measured weight-update tail (docs/OBSERVABILITY.md): a short
    # post-measurement capture attributes the per-step device time in
    # the optimizer named_scope — None when the platform can't trace.
    state, optimizer_ms = _optimizer_ms_probe(chunk, prefetch, state,
                                              chunk_k)
    # One extra (unused) batch before the pipeline closes: its avals let
    # the flops probe below look the TIMED chunk program up in the
    # compile cache without rebuilding shardings by hand.
    probe_batch = () if dev_stream else next(prefetch)
    prefetch.close()

    med = statistics.median(rates)
    row = {
        "images_per_sec_per_chip": round(med, 1),
        "img_s_min": round(min(rates), 1),
        "img_s_max": round(max(rates), 1),
        "spread_pct": round(100.0 * (max(rates) - min(rates)) / med, 2),
        "reps": reps,
        # Per-step time distribution from the drained sampling pass
        # (see above: includes a drain per dispatch — gate on the
        # p99/p50 RATIO trajectory, not on these vs the mean rate).
        "step_ms_p50": round(percentile(tail_ms, 50), 4),
        "step_ms_p99": round(percentile(tail_ms, 99), 4),
        "step_ms_samples": len(tail_ms),
        "step_ms_series": [round(v, 4) for v in tail_ms],
        # Per-step device time in the optimizer named_scope (see the
        # probe above); null when the platform can't capture a trace.
        "optimizer_ms": optimizer_ms,
        "optimizer_sharding": optimizer_sharding,
    }

    # Per-step FLOPs. With the compile cache armed both figures come
    # from CACHED artifacts — zero recompiles after the timed section:
    # the primary source is the cost analysis of the chunk executable
    # the timed loop actually ran (read back through the cache entry),
    # cross-checked against the SCAN-FREE single step (exact for the
    # CNN; also cache-served) to verify the backend counted the K-step
    # scan body once — a chunk/step ratio near K means it was unrolled
    # and the chunk figure scales back by K. XLA cost analysis reports
    # the per-device share of the partitioned program in both cases.
    d = cfg.data
    import numpy as np
    img_abs = jax.ShapeDtypeStruct(
        (cfg.batch_size, d.crop_height, d.crop_width, d.num_channels),
        np.float32)
    lab_abs = jax.ShapeDtypeStruct((cfg.batch_size,), np.int32)
    step_flops = compiled_flops(trainer.train_step,
                                (abstractify(state), img_abs, lab_abs))
    flops = step_flops
    flops_source = "step_probe"
    cached = getattr(chunk, "cached", None)
    if cached is not None:
        ev = cached.last_event or {}
        row["cache_hit"] = bool(ev.get("hit"))
        row["compile_s"] = ev.get("compile_s")
        chunk_f = chunk.cached_flops(abstractify((state, *probe_batch)))
        if chunk_f and step_flops and \
                chunk_f >= (1 + chunk_k) / 2 * step_flops:
            chunk_f /= chunk_k
        if chunk_f:
            flops = chunk_f
            flops_source = "chunk_artifact"
    if flops:
        row["flops_source"] = flops_source
        # Per-DEVICE flop share x GLOBAL steps/sec (matches the verified
        # train/loop.py formula — no extra device_count divide): each
        # step's program runs once per step across the mesh, each chip
        # executing its 1/n flop share, so per-chip TF/s = per-device
        # flops x global steps/sec. MFU from the MEDIAN rep.
        steps_per_sec = med * n_chips / cfg.batch_size
        tflops = flops * steps_per_sec / 1e12
        row["tflops_per_sec_per_chip"] = round(tflops, 2)
        peak = device_peaks(jax.devices()[0].device_kind)["tflops"]
        row["mfu"] = round(tflops / peak, 4)
        row["peak_tflops"] = peak
    return row


def measure_int8_serve(batch: int = 128, reps: int = 3,
                       windows: int = 50) -> dict:
    """Serving-path A/B: the int8 quantized forward
    (``quant/convert.py`` — int8 ``dot_general``/``conv`` with
    ``preferred_element_type=int32``, dequant fused into the epilogue)
    vs the SAME weights served through the float program in bf16
    compute. Single device, one jitted dispatch per batch — the shape
    the serving engine's bucket fns execute, without batcher overhead,
    so the row isolates the numeric path. ``speedup_vs_bf16`` is what
    ``tools/bench_gate.py`` floors (TPU rows only; the ``backend`` key
    says which this row is)."""
    import dataclasses

    import jax
    import numpy as np

    from dml_cnn_cifar10_tpu.config import reference_config
    from dml_cnn_cifar10_tpu.export import make_variable_serving_fn
    from dml_cnn_cifar10_tpu.models.registry import get_model
    from dml_cnn_cifar10_tpu.quant.calibrate import calibrate
    from dml_cnn_cifar10_tpu.quant.convert import (
        make_quantized_serving_fn, quantize_params)
    from dml_cnn_cifar10_tpu.utils.telemetry import percentile

    cfg = reference_config()
    cfg.data.dataset = "synthetic"
    cfg.data.data_dir = "/tmp/bench_cifar"
    cfg.data.synthetic_train_records = 20480
    cfg.data.synthetic_test_records = 1024
    cfg.data.use_native_loader = False

    model_def = get_model(cfg.model.name)
    params = model_def.init(jax.random.key(0), cfg.model, cfg.data)
    d = cfg.data
    rng = np.random.default_rng(0)
    images = rng.integers(
        0, 256, (512, d.image_height, d.image_width, d.num_channels),
        dtype=np.uint8)
    scales = calibrate(params, images[:256], cfg.model, cfg.data,
                       batch_size=64, num_batches=4)
    qtree = quantize_params(params, scales)
    bf16_cfg = dataclasses.replace(cfg.model, compute_dtype="bfloat16")
    quant_fn = jax.jit(make_quantized_serving_fn(cfg.model, cfg.data))
    float_fn = jax.jit(make_variable_serving_fn(model_def, bf16_cfg,
                                                cfg.data))
    batch_imgs = images[:batch]

    def drive(fn, variables):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(variables, batch_imgs))  # compile
        compile_s = time.perf_counter() - t0
        rates, lat_ms = [], []
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(windows):
                out = fn(variables, batch_imgs)
            jax.block_until_ready(out)
            rates.append(windows * batch / (time.perf_counter() - t0))
        for _ in range(min(windows, 30)):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(variables, batch_imgs))
            lat_ms.append((time.perf_counter() - t0) * 1e3)
        return rates, lat_ms, compile_s

    q_rates, q_lat, q_compile = drive(quant_fn, (qtree, None))
    f_rates, f_lat, _ = drive(float_fn, (params, None))
    q_med, f_med = statistics.median(q_rates), statistics.median(f_rates)
    return {
        "images_per_sec_per_chip": round(q_med, 1),
        "img_s_min": round(min(q_rates), 1),
        "img_s_max": round(max(q_rates), 1),
        "spread_pct": round(
            100.0 * (max(q_rates) - min(q_rates)) / q_med, 2),
        "reps": reps,
        "batch": batch,
        "compile_s": round(q_compile, 4),
        "step_ms_p50": round(percentile(q_lat, 50), 4),
        "step_ms_p99": round(percentile(q_lat, 99), 4),
        "bf16_images_per_sec_per_chip": round(f_med, 1),
        "bf16_step_ms_p50": round(percentile(f_lat, 50), 4),
        "speedup_vs_bf16": round(q_med / f_med, 3),
        "backend": jax.default_backend(),
    }


def main() -> None:
    _bench_cache_dir()  # arms jax's cache before anything compiles
    stamp = device_stamp()
    rows = {
        # Headline pair: K=100 — the largest dispatch that still lands
        # on the reference's 200/500 observable-boundary cadence, i.e.
        # what the Trainer actually runs with full parity. Device index
        # stream (the default data path since round 5).
        "fp32": measure("float32", chunk_k=100),
        "bf16": measure("bfloat16", chunk_k=100),
        # Plateau: K=320 amortizes dispatch overhead past the cadence
        # constraint (measured sweep plateau) — the ceiling when
        # observable-boundary parity is relaxed.
        "fp32_k320": measure("float32", chunk_k=320, chunks=20),
        # A/B: host-generated index upload (the pre-round-5 default) —
        # pins that the device stream costs nothing.
        "fp32_hostidx": measure("float32", chunk_k=100, dev_stream=False),
        # ZeRO-1 sharded weight update (--optimizer_sharding zero1,
        # docs/SHARDING.md) on the same mesh: reduce-scatter + sharded
        # update + all-gather replacing the grad all-reduce. Joins the
        # perf-regression gate (tools/bench_gate.py row tolerances) so
        # the new path cannot regress silently.
        "fp32_zero1": measure("float32", chunk_k=100,
                              optimizer_sharding="zero1"),
        # Serving A/B: the post-training int8 path (docs/QUANT.md) vs
        # the same weights in bf16 compute. Joins the gate
        # (tools/bench_gate.py) with a speedup floor on TPU backends.
        "int8_serve": measure_int8_serve(),
    }
    # Headline = best PARITY config (K=100): the plateau row is reported
    # as data but may not claim the headline — it relaxes the
    # observable-boundary cadence the Trainer actually honors.
    headline = max((rows["fp32"], rows["bf16"]),
                   key=lambda r: r["images_per_sec_per_chip"])
    per_chip = headline["images_per_sec_per_chip"]
    print(json.dumps({
        "metric": "train_throughput",
        "value": per_chip,
        "unit": "images/sec/chip",
        "vs_baseline": round(
            per_chip / NORTH_STAR_IMAGES_PER_SEC_PER_CHIP, 3),
        **stamp,
        **{name: {**row, **stamp} for name, row in rows.items()},
    }))


if __name__ == "__main__":
    main()

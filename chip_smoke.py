#!/usr/bin/env python3
"""The quickest proof that the trainer still starts on the chip.

Run it from the root of a checkout, on a machine with a TPU:

    python3 chip_smoke.py

One process, no flags, no platform forced. It reads ``jax.devices()``,
prints what JAX found, and exits non-zero unless the platform is
``tpu`` (rehearse the same CLI arguments on a CPU through
``cifar10cnn.py``, not through this file). Then it drives the trainer —
the system's main path — in-process through
``dml_cnn_cifar10_tpu.cli.main.main``, which is what
``python cifar10cnn.py`` calls, at the paper's full geometry (the
five-layer CNN, global batch 128, 24x24 crops, ``--dataset synthetic``:
the records are generated from the seed, there is no network):

(a) the production path, ``--fidelity fixed --learning_rate 0.02
    --steps_per_dispatch 100``, for 400 steps with metrics boundaries,
    evals and checkpoints;
(b) the same ``--log_dir`` again with ``--total_steps 600``, which must
    resume from the step-400 checkpoint;
(c) 20 steps of the per-step path (``--steps_per_dispatch 1``, the CLI
    default), which feeds batches from the host through the native
    record loader;
(d) 20 steps of the looped decoder over tokens (``--model looped_decoder
    --dataset tokens_synth``) at a small size with the published head size
    (2 layers run 4 times, 2 heads of 128, 256 tokens, AdamW, a layer
    recomputed in the backward pass): the loss must be finite and fall,
    and the lowered step must hold the flash-attention kernels (forward,
    dq and dk/dv).

It fails on a non-finite loss, on a training accuracy that did not rise
on the separable synthetic set, on a step counter or checkpoint that is
not where it should be, on a ``--metrics_jsonl`` stream that does not
pass ``tools/check_jsonl_schema.py --strict``, and — the no-fallback
check — if the lowered training step it ran (read back from the keyed
compile store ``--compile_cache_dir`` fills) does not hold one Mosaic
``tpu_custom_call`` per parameter leaf, and four more where the batch of
a device fills the lanes (the two pools' forward and backward kernels,
``ops/relu_pool.py``: on one chip, not on four at this batch): the fused
optimizer update or the pools went to the XLA expression or to
interpret mode without saying so. On more
than one chip the same count must sit inside a manual computation (the
replicated ``shard_map`` of ``ops/optimizer.py``), the step's mesh must
give the ``data`` axis every device, and every device must hold a shard
of a batch placed the way the trainer places it.

It prints the seconds the first dispatch took to compile (cold or warm:
jax's persistent cache is at ``JAX_COMPILATION_CACHE_DIR`` if set, else
``<checkout>/.jax_cache``), copies the metrics streams to
``chiprun_out/chip_smoke/``, and ends with one JSON line:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import glob
import json
import math
import os
import shutil
import sys
import tempfile
import time
import zlib

HERE = os.path.dirname(os.path.abspath(__file__))
BATCH = 128
COMMON = ["--dataset", "synthetic", "--fidelity", "fixed",
          "--learning_rate", "0.02", "--batch_size", str(BATCH),
          "--telemetry", "true"]


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"[chip_smoke] ok: {what}", flush=True)


def run_cli(phase: str, work: str, log_dir: str, argv: list) -> list:
    """One in-process ``cli.main.main`` call → its metrics records."""
    from dml_cnn_cifar10_tpu.cli.main import main
    from tools.check_jsonl_schema import main as lint

    stream = os.path.join(work, f"{phase}.jsonl")
    argv = [*COMMON, "--data_dir", os.path.join(work, "data"),
            "--log_dir", os.path.join(work, log_dir),
            "--compile_cache_dir", os.path.join(work, "keyed_" + phase),
            "--metrics_jsonl", stream, *argv]
    print(f"[chip_smoke] phase {phase}: cifar10cnn.py {' '.join(argv)}",
          flush=True)
    t0 = time.perf_counter()
    rc = main(argv)
    print(f"[chip_smoke] phase {phase}: {time.perf_counter() - t0:.1f} s",
          flush=True)
    check(rc == 0, f"{phase}: cli.main.main returned 0")
    check(lint(["--strict", stream]) == 0,
          f"{phase}: metrics stream passes check_jsonl_schema --strict")
    with open(stream) as f:
        return [json.loads(line) for line in f if line.strip()]


def of_kind(records: list, kind: str) -> list:
    return [r for r in records if r["kind"] == kind]


def check_training(phase: str, records: list, first: int, every: int,
                   last: int) -> float:
    """Step counter, finite losses, accuracy off the floor → last loss."""
    train = of_kind(records, "train")
    check([r["step"] for r in train] == list(range(first, last + 1, every)),
          f"{phase}: train boundaries at steps {first}..{last} by {every}")
    losses = [r["loss"] for r in train]
    check(all(math.isfinite(v) for v in losses),
          f"{phase}: every loss finite ({losses})")
    done = of_kind(records, "done")
    check(len(done) == 1 and done[0]["step"] == last,
          f"{phase}: done at step {last}")
    return losses[-1]


def check_checkpoint(phase: str, log_dir: str, step: int) -> None:
    from dml_cnn_cifar10_tpu import ckpt

    path = ckpt.latest_checkpoint(log_dir)
    check(path is not None and step in ckpt.all_checkpoint_steps(log_dir)
          and os.path.basename(path).startswith(f"ckpt_{step}."),
          f"{phase}: newest checkpoint is step {step} ({path})")
    ok, reason = ckpt.verify_checkpoint(path)
    check(ok, f"{phase}: checkpoint verifies ({reason})")


def compile_seconds(records: list, phase_name: str) -> float:
    """Seconds the first dispatch of ``phase_name`` took to trace and
    compile (or load from jax's persistent cache)."""
    events = [r for r in of_kind(records, "compile")
              if r["phase"] == phase_name and r["source"] != "stablehlo"]
    if not events:
        raise SmokeFailure(f"no compile event for {phase_name}")
    return events[0]["compile_s"]


def lowered_text(store: str, phase_name: str) -> str:
    """The StableHLO of the step that ran, from the keyed compile store."""
    from dml_cnn_cifar10_tpu.compilecache import CompileCache

    text = None
    for key, meta in CompileCache(store).entries():
        if meta["phase"] == phase_name:
            with open(os.path.join(store, key + ".hlo.z"), "rb") as f:
                text = zlib.decompress(f.read()).decode()
    if text is None:
        raise SmokeFailure(f"the keyed compile store holds no lowered "
                           f"{phase_name} (cache machinery failed open?)")
    return text


def check_lowered_step(store: str, phase_name: str, n_devices: int,
                       n_leaves: int) -> None:
    """The no-fallback check, on the StableHLO of the step that ran."""
    import jax.numpy as jnp

    from dml_cnn_cifar10_tpu.ops import relu_pool

    # conv1's activation on one device, as the pools' chooser sees it
    pools = 4 * relu_pool.fits_kernels((BATCH // n_devices, 24, 24, 64),
                                       jnp.float32)

    text = lowered_text(store, phase_name)
    kernels = text.count("tpu_custom_call")
    check(kernels == n_leaves + pools,
          f"{phase_name}: {kernels} Mosaic tpu_custom_call in the lowered "
          f"step, one per parameter leaf ({n_leaves}) and {pools} of the "
          f"pools")
    if n_devices > 1:
        check(f'"data"={n_devices}' in text,
              f"{phase_name}: the step's mesh gives the data axis all "
              f"{n_devices} devices")
        check("sdy.manual_computation" in text
              or "SPMDFullToShardShape" in text,
              f"{phase_name}: the kernels sit in a manual computation "
              f"(replicated shard_map)")


DECODER_SIZES = {
    "hidden_size": 256, "num_attention_heads": 2, "num_key_value_heads": 2,
    "head_dim": 128, "intermediate_size": 512, "num_hidden_layers": 2,
    "vocab_size": 512, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "total_ut_steps": 4, "exit_entropy_beta": 0.1}


def smoke_decoder(work: str) -> dict:
    """(d): the looped decoder through the same CLI, resident token rows,
    two steps a dispatch."""
    sizes = os.path.join(work, "decoder_sizes.json")
    with open(sizes, "w") as f:
        json.dump(DECODER_SIZES, f)
    # after COMMON: the later flag wins
    d = run_cli("d_decoder", work, "logs_decoder",
                ["--model", "looped_decoder", "--model_config_file", sizes,
                 "--dataset", "tokens_synth", "--sequence_length", "256",
                 "--batch_size", "8", "--synthetic_train_records", "256",
                 "--compute_dtype", "bfloat16", "--remat", "true",
                 "--optimizer", "adamw", "--learning_rate", "0.003",
                 "--adam_b2", "0.95", "--weight_decay", "0.1",
                 "--schedule", "constant", "--steps_per_dispatch", "2",
                 "--total_steps", "20", "--output_every", "4",
                 "--eval_every", "20", "--checkpoint_every", "20"])
    check_training("d", d, 4, 4, 20)
    losses = [r["loss"] for r in of_kind(d, "train")]
    check(losses[-1] < losses[0],
          f"d: the decoder's loss fell over 20 steps ({losses})")
    kernels = lowered_text(os.path.join(work, "keyed_d_decoder"),
                           "train_chunk_resident").count("tpu_custom_call")
    check(kernels >= 3,
          f"d: {kernels} Mosaic tpu_custom_call in the decoder's lowered "
          f"step (at least the forward, the dq and the dk/dv kernel; "
          f"identical calls may share one lowered function)")
    return {"decoder_losses": losses}


def check_batch_placement(n_devices: int) -> None:
    """Every device holds its own shard of a batch placed the way the
    trainer places one (``mesh_lib.shard_batch`` on ``build_mesh``)."""
    import numpy as np

    from dml_cnn_cifar10_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.build_mesh()
    images, labels = mesh_lib.shard_batch(
        mesh, np.zeros((BATCH, 24, 24, 3), np.float32),
        np.arange(BATCH, dtype=np.int32))
    held = {}
    for shard in labels.addressable_shards:
        held[shard.device.id] = np.asarray(shard.data)
    rows = sorted(int(v) for part in held.values() for v in part)
    check(len(held) == n_devices
          and all(len(part) == BATCH // n_devices for part in held.values())
          and rows == list(range(BATCH))
          and len({s.device.id for s in images.addressable_shards})
          == n_devices,
          f"every one of {n_devices} device(s) holds its own "
          f"{BATCH // n_devices}-row shard of the batch")


def smoke(work: str, n_devices: int) -> dict:
    import jax

    from dml_cnn_cifar10_tpu.config import fixed_config
    from dml_cnn_cifar10_tpu.models.registry import get_model

    cfg = fixed_config()
    n_leaves = len(jax.tree.leaves(jax.eval_shape(
        lambda k: get_model(cfg.model.name).init(k, cfg.model, cfg.data),
        jax.random.key(0))))

    # (a) the production path: HBM-resident data, 100 steps a dispatch.
    chunked = ["--steps_per_dispatch", "100", "--output_every", "100",
               "--eval_every", "200", "--checkpoint_every", "200"]
    a = run_cli("a_production", work, "logs",
                [*chunked, "--total_steps", "400"])
    loss_400 = check_training("a", a, 100, 100, 400)
    acc = [r["train_accuracy"] for r in of_kind(a, "train")]
    check(acc[-1] > 0.5,
          f"a: training accuracy rose from chance (0.1) on the separable "
          f"set ({acc})")
    evals = of_kind(a, "eval")
    check([r["step"] for r in evals] == [200, 400]
          and evals[-1]["test_accuracy"] > 0.5,
          f"a: evals at 200 and 400, test accuracy "
          f"{[r['test_accuracy'] for r in evals]}")
    check_checkpoint("a", os.path.join(work, "logs"), 400)
    compile_s = compile_seconds(a, "train_chunk_resident")
    print(f"[chip_smoke] first dispatch (train_chunk_resident) compiled "
          f"in {compile_s:.2f} s", flush=True)
    check_lowered_step(os.path.join(work, "keyed_a_production"),
                       "train_chunk_resident", n_devices, n_leaves)

    # (b) the same log_dir, further: must resume, not start over.
    b = run_cli("b_resume", work, "logs", [*chunked, "--total_steps", "600"])
    loss_600 = check_training("b (resumed from 400)", b, 500, 100, 600)
    check_checkpoint("b", os.path.join(work, "logs"), 600)

    # (c) the per-step path: host-fed batches, one step a dispatch.
    c = run_cli("c_per_step", work, "logs_per_step",
                ["--total_steps", "20", "--output_every", "10",
                 "--eval_every", "20", "--checkpoint_every", "20"])
    loss_20 = check_training("c", c, 10, 10, 20)
    check_checkpoint("c", os.path.join(work, "logs_per_step"), 20)
    check_lowered_step(os.path.join(work, "keyed_c_per_step"),
                       "train_step", n_devices, n_leaves)

    check_batch_placement(n_devices)
    return {**smoke_decoder(work),
            "compile_s_first_dispatch": compile_s,
            "compile_s_per_step": compile_seconds(c, "train_step"),
            "loss_step_400": loss_400, "loss_step_600": loss_600,
            "per_step_loss_step_20": loss_20,
            "train_accuracy": acc,
            "test_accuracy": [r["test_accuracy"] for r in evals]}


def main() -> int:
    # The package, then the cache, then the devices: jax's persistent
    # compilation cache has to be placed before anything compiles.
    from dml_cnn_cifar10_tpu.compilecache import arm_native_cache
    cache_dir = arm_native_cache()

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"[chip_smoke] jax {jax.__version__} found {device}; "
          f"compile cache at {cache_dir}", flush=True)
    if dev.platform != "tpu":
        print(f"[chip_smoke] FAIL: platform is {dev.platform!r}, not "
              f"'tpu'; nothing was trained", file=sys.stderr, flush=True)
        return 2

    work = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        detail = smoke(work, device["count"])
    except SmokeFailure as e:
        print(f"[chip_smoke] FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        out = os.path.join(HERE, "chiprun_out", "chip_smoke")
        os.makedirs(out, exist_ok=True)
        for stream in glob.glob(os.path.join(work, "*.jsonl")):
            shutil.copy(stream, out)
        shutil.rmtree(work, ignore_errors=True)
    print("[chip_smoke] detail " + json.dumps({**detail, "device": device}),
          flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""CLI: keeps the reference's flags working, adds the framework's own.

Reference flags (``cifar10cnn.py:245-273``): ``--ps_hosts --worker_hosts
--job_name --task_index --data_dir --log_dir``. Mapping to the SPMD world:

- ``--job_name=ps`` — parameter servers don't exist under SPMD; the process
  prints a deprecation note and exits 0 so old 3-terminal launch scripts
  still "work" (the PS terminal just returns immediately).
- ``--worker_hosts`` + ``--task_index`` — become the ``jax.distributed``
  process set: ``num_processes=len(worker_hosts)``,
  ``process_id=task_index``, coordinator = first worker host.
- ``--ps_hosts`` — accepted and ignored (deprecation note).
- ``--data_dir`` — honored here. (The reference parses it but ignores it,
  using the hardcoded ``cifar10data`` — ``cifar10cnn.py:26`` vs ``:265-268``;
  we default to the same hardcoded value, honoring the flag when given.)
- ``--log_dir`` — checkpoint dir, as in the reference (``:222``).

New flags expose the config dataclasses (model/steps/batch/fidelity/mesh).
"""

from __future__ import annotations

import argparse
import sys
from typing import List, Optional

from dml_cnn_cifar10_tpu import config as config_lib


def _bool(v: str) -> bool:
    return v.lower() == "true"   # the reference's custom bool (:247)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="dml_cnn_cifar10_tpu",
        description="TPU-native distributed CNN training "
                    "(reference-compatible CLI)")
    p.register("type", "bool", _bool)
    # --- reference flags (cifar10cnn.py:249-272) ---
    p.add_argument("--ps_hosts", type=str, default="",
                   help="DEPRECATED: comma-separated ps hosts (ignored; "
                        "SPMD has no parameter servers)")
    p.add_argument("--worker_hosts", type=str, default="",
                   help="Comma-separated hostname:port list; becomes the "
                        "jax.distributed process set")
    p.add_argument("--job_name", type=str, default="",
                   help="One of 'ps', 'worker' (ps exits immediately)")
    p.add_argument("--task_index", type=int, default=0,
                   help="Index of task within the job (process_id)")
    p.add_argument("--data_dir", type=str, default="cifar10data",
                   help="Directory for input data")
    p.add_argument("--log_dir", type=str, default="/tmp/train_logs",
                   help="Checkpoint/log directory")
    # --- framework flags ---
    p.add_argument("--model", type=str, default="cnn",
                   choices=["cnn", "resnet18", "resnet50", "vit_tiny",
                            "vit_moe", "looped_decoder", "hybrid_decoder"],
                   help="looped_decoder: a causal decoder over tokens whose "
                        "layers run several times on the same weights; "
                        "hybrid_decoder: one whose layers differ by a list "
                        "(gated short convolution, grouped-head attention "
                        "or the same under a sliding window, then a dense "
                        "MLP or this chip's share of routed experts), "
                        "head tied to the embedding or its own "
                        "(both need --dataset tokens_synth)")
    p.add_argument("--model_config_file", type=str, default=None,
                   help="sizes of a model that reads them from a file in "
                        "the shape of a published config.json "
                        "(looped_decoder, hybrid_decoder; default: the "
                        "model's small built-in sizes). hybrid_decoder "
                        "reads hidden_size, num_attention_heads, "
                        "num_key_value_heads, head_dim (optional), "
                        "intermediate_size, moe_intermediate_size, "
                        "num_hidden_layers, layer_types (conv | "
                        "full_attention | sliding_attention), "
                        "num_dense_layers, num_experts "
                        "(held here), expert_first_id, router_num_experts, "
                        "num_experts_per_tok, norm_topk_prob, "
                        "routed_scaling_factor, use_expert_bias (with "
                        "expert_bias_update_rate), "
                        "vocab_size, norm_eps (or rms_norm_eps), rope_theta "
                        "or rope_parameters (a rule a kind of layer: "
                        "rope_type default | yarn), conv_L_cache and "
                        "conv_bias (conv layers), and where present "
                        "sliding_window, tie_word_embeddings (true), "
                        "router_score (sigmoid | softmax), qk_norm (true)")
    p.add_argument("--dataset", type=str, default="cifar10",
                   choices=["cifar10", "cifar100", "synthetic",
                            "imagenet_synth", "tokens_synth"],
                   help="imagenet_synth: generated ImageNet-shaped shards "
                        "(256x256, 1000 classes, wide 2-byte labels) — the "
                        "ResNet-50 ladder rung on an air-gapped box; "
                        "tokens_synth: generated rows of --sequence_length "
                        "+ 1 int32 token ids over the model's vocabulary")
    p.add_argument("--sequence_length", type=int, default=128,
                   help="tokens of a sequence (token datasets): a record "
                        "holds one more, inputs [:-1] and targets [1:]")
    p.add_argument("--image_size", type=int, default=None,
                   help="stored square image side (default: 32, or 256 "
                        "for imagenet_synth)")
    p.add_argument("--crop_size", type=int, default=None,
                   help="model input side after crop (default: 24, or 224 "
                        "for imagenet_synth)")
    p.add_argument("--synthetic_train_records", type=int, default=None,
                   help="generated train records for "
                        "synthetic/imagenet_synth datasets")
    p.add_argument("--batch_size", type=int, default=128)
    p.add_argument("--total_steps", type=int, default=20000)
    p.add_argument("--output_every", type=int, default=200,
                   help="train-metrics cadence (reference OUTPUT_EVERY)")
    p.add_argument("--eval_every", type=int, default=500,
                   help="eval cadence (reference EVAL_EVERY)")
    p.add_argument("--checkpoint_every", type=int, default=1000)
    p.add_argument("--checkpoint_every_secs", type=float, default=None,
                   help="wall-clock checkpoint cadence in addition to the "
                        "step cadence (the reference's MTS saved every "
                        "600 s by default)")
    p.add_argument("--mode", type=str, default="train",
                   choices=["train", "eval", "export", "serve", "fleet",
                            "run"],
                   help="train; eval = restore latest checkpoint and sweep "
                        "the full test split; export = restore and write a "
                        "self-contained jax.export serving artifact; serve "
                        "= run the micro-batching inference engine over "
                        "the artifact (or latest checkpoint) behind an "
                        "HTTP endpoint; fleet = router + N replicated "
                        "serve workers with heartbeat liveness, "
                        "zero-downtime checkpoint hot-swap, and a "
                        "closed-loop autoscaler (docs/SERVING.md); run = "
                        "the unified multi-job runtime: one process, one "
                        "mesh, --jobs running concurrently, every "
                        "committed checkpoint hot-swapped into the "
                        "in-process serving head, alerts optionally "
                        "triggering fine-tune jobs (docs/RUNTIME.md)")
    p.add_argument("--export_path", type=str, default=None,
                   help="output file for --mode export "
                        "(default <log_dir>/model.jaxexport)")
    p.add_argument("--serve_artifact", type=str, default=None,
                   help="artifact to serve (--mode serve); default "
                        "<log_dir>/model.jaxexport when present, else "
                        "the latest checkpoint is restored and served "
                        "live")
    p.add_argument("--serve_buckets", type=str, default="1,8,32,128",
                   help="comma-separated pre-compiled batch sizes; a "
                        "request batch pads up to the smallest bucket "
                        "that fits (avoids per-shape recompiles)")
    p.add_argument("--serve_queue_depth", type=int, default=256,
                   help="admission control: submits beyond this queue "
                        "depth are shed immediately (HTTP 503) instead "
                        "of growing an unbounded backlog")
    p.add_argument("--serve_batch_window_ms", type=float, default=2.0,
                   help="max extra latency the batcher may wait to "
                        "coalesce a fuller batch")
    p.add_argument("--serve_deadline_ms", type=float, default=None,
                   help="per-request deadline; requests queued past it "
                        "are shed at dispatch (default: none)")
    p.add_argument("--serve_port", type=int, default=8000,
                   help="HTTP port for --mode serve (0 = ephemeral)")
    p.add_argument("--serve_metrics_every_s", type=float, default=5.0,
                   help="cadence of `serve` JSONL window records")
    p.add_argument("--serve_drain_deadline_s", type=float, default=5.0,
                   help="graceful-shutdown budget for --mode serve: on "
                        "SIGTERM/SIGINT stop accepting, let queued "
                        "batches finish for at most this long, shed the "
                        "rest, flush metrics, exit 0")
    p.add_argument("--serve_slo_ms", type=float, default=None,
                   help="p99 latency objective in ms; the fleet "
                        "autoscaler scales up while the replicas' p99 "
                        "sits above it (declarative elsewhere)")
    p.add_argument("--serve_quantize", type=str, default=None,
                   choices=["int8"],
                   help="quantized serving path (docs/QUANT.md): int8 "
                        "post-training quantization with calibrated "
                        "scales; served versions carry a '+int8' "
                        "suffix. Default: float serving")
    p.add_argument("--quant_calib_batches", type=int, default=4,
                   help="eval-stream batches the activation "
                        "calibration observes before quantizing")
    p.add_argument("--quant_max_delta", type=float, default=0.005,
                   help="pinned accuracy contract: max allowed "
                        "(float top-1 - int8 top-1) on the calibration "
                        "holdout, as a fraction (0.005 = 0.5%%); a "
                        "candidate beyond it is rejected at publish "
                        "time (quant_rejected) and float keeps serving")
    p.add_argument("--serve_cache_size", type=int, default=0,
                   help="exact-match response cache capacity (entries) "
                        "keyed by (input digest, version); hits bypass "
                        "the batcher; flushed on hot-swap. 0 = off")
    # --- unified runtime flags (--mode run; docs/RUNTIME.md) ---
    p.add_argument("--jobs", type=str, default="train,serve",
                   help="--mode run job spec: comma-separated from "
                        "{train, serve, eval}. train is a task job (the "
                        "runtime exits when task jobs drain); serve/eval "
                        "are service jobs stopped at drain. finetune "
                        "jobs are never listed — they are born from "
                        "alert triggers (--finetune_steps)")
    p.add_argument("--runtime_eval_every_s", type=float, default=2.0,
                   help="EvalJob cadence: seconds between accuracy "
                        "evaluations of the latest published weights")
    p.add_argument("--runtime_eval_batches", type=int, default=1,
                   help="test batches per EvalJob tick (each one "
                        "serving forward on the shared mesh)")
    p.add_argument("--runtime_serve_warmup", type="bool", default=False,
                   help="pre-compile the in-process serving head's "
                        "bucket programs at first publish (off keeps "
                        "the train path's fetch-parity invariant; the "
                        "request path compiles lazily)")
    p.add_argument("--finetune_steps", type=int, default=0,
                   help="alert→job control loop: an emitted alert "
                        "firing triggers a FineTuneJob continuing "
                        "training this many extra steps from the last "
                        "in-process train state. 0 = off")
    p.add_argument("--finetune_rules", type=str, default=None,
                   help="comma-separated alert rule names allowed to "
                        "trigger FineTuneJobs (default: any emitted "
                        "firing, --max_finetunes permitting)")
    p.add_argument("--max_finetunes", type=int, default=1,
                   help="lifetime budget of alert-triggered "
                        "FineTuneJobs per runtime")
    p.add_argument("--trace_sample_rate", type=float, default=0.0,
                   help="distributed request tracing: head-sample this "
                        "fraction of serving requests at the trace root "
                        "(client or first hop) and emit one `rspan` "
                        "JSONL record per hop; shed or retried requests "
                        "are always captured regardless of the rate "
                        "(docs/OBSERVABILITY.md Request-tracing)")
    p.add_argument("--fleet_min_replicas", type=int, default=2,
                   help="serving-fleet floor: the pool starts this many "
                        "workers and a fleet below it always scales "
                        "back up (self-healing after a worker death)")
    p.add_argument("--fleet_max_replicas", type=int, default=4,
                   help="serving-fleet ceiling for the autoscaler")
    p.add_argument("--fleet_port", type=int, default=8100,
                   help="router HTTP port for --mode fleet (0 = "
                        "ephemeral; workers always bind ephemeral ports "
                        "and advertise them via heartbeats)")
    p.add_argument("--fleet_dir", type=str, default=None,
                   help="fleet coordination directory (heartbeats, "
                        "published-version file, per-replica telemetry); "
                        "default <log_dir>/fleet. Shared filesystem in "
                        "production, a tmpdir in tests")
    p.add_argument("--fleet_autoscale", type="bool", default=True,
                   help="closed-loop autoscaling from the replicas' "
                        "serve JSONL windows (queue depth, shed "
                        "fraction, p99 vs --serve_slo_ms); false pins "
                        "the fleet at --fleet_min_replicas (deaths are "
                        "still replaced)")
    p.add_argument("--fleet_replica_dead_after_s", type=float,
                   default=3.0,
                   help="a worker whose newest heartbeat is older than "
                        "this is evicted from routing and its in-flight "
                        "requests re-routed to surviving replicas")
    p.add_argument("--fleet_publish", type="bool", default=False,
                   help="trainer-side hot-swap publish hook: every "
                        "committed checkpoint (with its integrity "
                        "sidecar) is published to the fleet dir so live "
                        "serve workers swap to it between micro-batches "
                        "(the online train-and-serve scenario)")
    p.add_argument("--cell", type=str, default="default",
                   help="comma-separated fleet cell names (failure "
                        "domains): replica i lands in cell i %% "
                        "len(cells) and advertises it per heartbeat; "
                        "the router prefers a request's X-DML-Cell "
                        "target (tools/loadgen.py --target_cell) and "
                        "fails over cross-cell — logged as cell_route "
                        "and force-traced — when the cell has no live "
                        "replica")
    p.add_argument("--learning_rate", type=float, default=0.1)
    p.add_argument("--fidelity", type=str, default="faithful",
                   choices=["faithful", "fixed"],
                   help="faithful reproduces the reference quirks (ReLU'd "
                        "logits, dead LR decay, single-batch eval, raw "
                        "pixels); fixed applies the sane versions")
    p.add_argument("--model_axis", type=int, default=1,
                   help="tensor-parallel mesh degree")
    p.add_argument("--seq_axis", type=int, default=1,
                   help="sequence-parallel mesh degree")
    p.add_argument("--sp_mode", type=str, default="ring",
                   choices=["ring", "ulysses"],
                   help="sequence-parallel attention strategy: ring "
                        "(K/V ppermute walk) or ulysses (seq<->head "
                        "all-to-all; needs heads %% seq_axis == 0)")
    p.add_argument("--pool", type=str, default=None,
                   choices=["cls", "mean"],
                   help="ViT head pooling; defaults to cls, or mean when "
                        "seq_axis > 1 (sequence sharding excludes a lone "
                        "cls token)")
    p.add_argument("--resnet_s2d", type="bool", default=False,
                   help="space-to-depth ResNet stem (ImageNet stems only): "
                        "4x4/1 conv on the 2x2-folded [112,112,12] input "
                        "instead of 7x7/2 on [224,224,3] - the MLPerf MXU-"
                        "occupancy trick; changes stem param shape")
    p.add_argument("--resnet_norm", type=str, default="bn",
                   choices=["bn", "nf"],
                   help="ResNet normalization: bn (reference semantics, "
                        "cross-replica BatchNorm) or nf (normalizer-free "
                        "byte-reduction rung: weight standardization + "
                        "SkipInit scalars, no stats passes; different "
                        "training semantics)")
    p.add_argument("--attn_window", type=int, default=None,
                   help="sliding-window (local) attention width for the "
                        "ViT family: band |row-col| < W on every path "
                        "(XLA, flash kernels, ring, ulysses); under ring "
                        "SP the window must fit one sequence shard")
    p.add_argument("--attn_causal", type="bool", default=False,
                   help="causal (autoregressive) attention mask in the "
                        "ViT family's transformer blocks")
    p.add_argument("--vit_heads", type=int, default=None,
                   help="ViT attention heads (default 3; ulysses sp needs "
                        "heads divisible by seq_axis)")
    p.add_argument("--vit_dim", type=int, default=None,
                   help="ViT embed dim (default 192)")
    p.add_argument("--vit_depth", type=int, default=None,
                   help="ViT blocks (default 12)")
    p.add_argument("--remat", type="bool", default=False,
                   help="recompute block activations in the backward pass "
                        "(ViT transformer blocks / ResNet residual "
                        "blocks; activation memory O(1) in depth). The "
                        "looped decoder recomputes a layer but its flash "
                        "attention kernel, whose output and log-sum-exp "
                        "it keeps")
    p.add_argument("--pipe_axis", type=int, default=1,
                   help="pipeline-parallel mesh degree (stages; schedule "
                        "per --pipe_schedule)")
    p.add_argument("--pipe_schedule", type=str, default="1f1b",
                   choices=["1f1b", "1f1b_ring", "gpipe"],
                   help="pipeline schedule: 1f1b (no bubble compute, "
                        "recompute backward — minimal memory, measured "
                        "fastest), 1f1b_ring (2F+1B residual-ring "
                        "backward, opt-in) or gpipe (round-2 baseline)")
    p.add_argument("--pipe_microbatches", type=int, default=0,
                   help="pipeline microbatches per step (0 = one per "
                        "stage). More microbatches shrink 1f1b's live "
                        "activation footprint AND gpipe's bubble fraction "
                        "(M+P-1)/M at the cost of smaller per-microbatch "
                        "compute")
    p.add_argument("--moe_experts", type=int, default=0,
                   help="experts per MoE block (vit_moe); sharded over "
                        "the model axis (expert parallelism)")
    p.add_argument("--moe_top_k", type=int, default=1,
                   help="experts per token: 1 = Switch, 2 = GShard")
    p.add_argument("--moe_dispatch", type=str, default="einsum",
                   choices=["einsum", "scatter"],
                   help="MoE dispatch/combine: einsum ([T,E,C] one-hot "
                        "contractions, the ep-proven all-MXU path) or "
                        "scatter ((expert,slot) scatter/gather — O(T*D) "
                        "instead of O(T^2*f*D); fastest at long T on "
                        "one replica). Same semantics either way")
    p.add_argument("--resident_data", type="bool", default=True,
                   help="with --steps_per_dispatch >1, keep the uint8 "
                        "dataset in HBM and gather on device; multi-host "
                        "replicates the full split per process and ships "
                        "only index slices. The trainer auto-switches to "
                        "the NumPy pipeline for this path (the C++ "
                        "pool's bounded-shuffle stream has no index view)")
    p.add_argument("--device_index_stream", type="bool", default=True,
                   help="resident path only: generate the shuffled index "
                        "stream ON DEVICE inside the compiled chunk "
                        "(stateless per-epoch pseudo-permutation keyed on "
                        "the global step) — a training dispatch uploads "
                        "nothing and exact resume needs no sidecar. "
                        "Different (equally valid) permutation than the "
                        "host stream; toggling changes data order. "
                        "'false' restores the host numpy-PCG stream")
    p.add_argument("--use_native_loader", type="bool", default=True,
                   help="stream batches from the C++ bounded shuffle pool "
                        "(reference RandomShuffleQueue parity); false uses "
                        "the NumPy full-permutation pipeline")
    p.add_argument("--steps_per_dispatch", type=int, default=1,
                   help="train steps per device dispatch (lax.scan chunk; "
                        "output/eval/checkpoint cadences must be "
                        "multiples)")
    p.add_argument("--grad_accum", type=int, default=1,
                   help="microbatches per optimizer update (gradient "
                        "accumulation inside the compiled step)")
    p.add_argument("--explicit_collectives", type="bool", default=False,
                   help="use the shard_map+psum step instead of jit "
                        "auto-partitioning")
    p.add_argument("--fsdp", type="bool", default=False,
                   help="ZeRO/FSDP: shard params + optimizer moments over "
                        "the data axis (state memory 1/N; grads become "
                        "reduce-scatter)")
    p.add_argument("--optimizer_sharding", type=str, default="none",
                   choices=["none", "zero1"],
                   help="cross-replica weight-update sharding "
                        "(docs/SHARDING.md): zero1 allocates the "
                        "optimizer moments sharded 1/N over the data "
                        "axis from init on, reduce-scatters grads, "
                        "updates each replica's shard, and all-gathers "
                        "the new params for the next forward — same "
                        "math as replicated (pinned <=1e-6), "
                        "checkpoints interchange across modes. Needs "
                        "the GSPMD step; excludes --fsdp and "
                        "--async_staleness")
    p.add_argument("--fused_optimizer", type="bool", default=True,
                   help="fused single-pass SGD update (ops/optimizer.py: "
                        "momentum + weight decay + LR in one pass over "
                        "the param bytes; Pallas TPU kernel with an "
                        "identical-math XLA fallback by platform). "
                        "false restores the tree_map chain")
    p.add_argument("--partition_rules", type=str, default=None,
                   help="override the model's partition-rule table "
                        "(parallel/shardings.py engine; grammar in "
                        "docs/SHARDING.md): ordered ';'-separated "
                        "'regex=spec' rules matched against /-joined "
                        "param paths; spec is comma-separated per-dim "
                        "axis names, right-aligned ('-' = unsharded "
                        "dim, '^' prefix = left-aligned, empty = "
                        "replicated)")
    p.add_argument("--partition_rules_strict", type="bool", default=False,
                   help="error at build time on any param leaf no "
                        "partition rule matches (instead of silently "
                        "replicating it)")
    p.add_argument("--partition_report", type="bool", default=False,
                   help="print the which-rule-matched-which-param "
                        "report (path, shape, rule, spec) at Trainer "
                        "build")
    p.add_argument("--compute_dtype", type=str, default="float32",
                   choices=["float32", "bfloat16"])
    p.add_argument("--optimizer", type=str, default="sgd",
                   choices=["sgd", "adamw", "lars", "lamb", "adafactor"],
                   help="sgd = reference; adamw for the transformer "
                        "ladder; lars/lamb add the per-layer trust ratio "
                        "for large-global-batch scaling; adafactor keeps "
                        "factored O(n+m) second moments (the memory "
                        "choice for large models)")
    p.add_argument("--momentum", type=float, default=0.0,
                   help="SGD momentum (reference uses plain SGD)")
    p.add_argument("--adam_b1", type=float, default=0.9)
    p.add_argument("--adam_b2", type=float, default=0.999)
    p.add_argument("--adam_eps", type=float, default=1e-8)
    p.add_argument("--weight_decay", type=float, default=0.0)
    p.add_argument("--label_smoothing", type=float, default=0.0)
    p.add_argument("--random_brightness", type=float, default=0.0,
                   help="augment: per-image brightness delta (pixel "
                        "units; the TF tutorial used 63)")
    p.add_argument("--random_contrast", type=float, default=0.0,
                   help="augment: per-image contrast deviation (the TF "
                        "tutorial's [0.2,1.8] is 0.8)")
    p.add_argument("--grad_clip_norm", type=float, default=None,
                   help="global-norm gradient clipping")
    p.add_argument("--async_staleness", type=int, default=0,
                   help="emulate the reference's async-PS gradient "
                        "staleness deterministically: grads taken at a "
                        "snapshot S-1 updates old (0/1 = synchronous)")
    p.add_argument("--ema_decay", type=float, default=0.0,
                   help="parameter EMA decay for eval (0 = off; 0.999 "
                        "typical) — training optimizes raw params, eval "
                        "uses the average")
    p.add_argument("--schedule", type=str, default="exponential",
                   choices=["exponential", "cosine", "constant"],
                   help="LR schedule family (exponential = reference "
                        "parity; cosine for the ViT/ResNet ladder)")
    p.add_argument("--warmup_steps", type=int, default=0,
                   help="linear LR warmup prepended to any schedule")
    p.add_argument("--cosine_decay_steps", type=int, default=0,
                   help="cosine horizon (defaults to total_steps when "
                        "--schedule cosine and this is 0)")
    p.add_argument("--async_checkpoint", type="bool", default=False,
                   help="serialize+write checkpoints on a background "
                        "thread (training overlaps the disk IO)")
    p.add_argument("--ckpt_format", type=str, default="msgpack",
                   choices=["msgpack", "orbax", "sharded"],
                   help="checkpoint codec: single-file flax msgpack, the "
                        "orbax directory format, or per-process sharded "
                        "files (pod-scale: no full-state gather, each "
                        "process writes only its own shards; restore "
                        "auto-detects and is elastic across meshes)")
    p.add_argument("--shard_io_threads", type=int, default=4,
                   help="bounded thread pool for the sharded codec's "
                        "concurrent per-shard file IO: saves split the "
                        "local payload across up to this many part "
                        "files written in parallel, restores "
                        "read+verify+unpack shard files in parallel "
                        "(per-shard sha256 sidecars; shard_io JSONL "
                        "telemetry). 1 = fully serial, same bytes")
    p.add_argument("--check_numerics", type="bool", default=False,
                   help="halt at the next metrics boundary on non-finite "
                        "loss without checkpointing the poisoned state "
                        "(faithful parity runs NaN by design — keep off)")
    p.add_argument("--on_nonfinite", type=str, default="halt",
                   choices=["halt", "skip", "rollback"],
                   help="what a --check_numerics detection does: halt "
                        "raises without saving; skip discards the "
                        "updates since the last finite boundary and "
                        "keeps training; rollback raises a classified "
                        "failure the --supervise loop answers by "
                        "restoring the last good checkpoint (optionally "
                        "scaling LR by --rollback_lr_scale). skip/"
                        "rollback degrade to halt when the "
                        "--recovery_retries budget is exhausted "
                        "(docs/RESILIENCE.md)")
    p.add_argument("--supervise", type="bool", default=False,
                   help="wrap training in the recovery supervisor: "
                        "classified recoverable failures (non-finite "
                        "loss under rollback, data-pipeline errors, "
                        "checkpoint-restore errors) restore the last "
                        "verifiable checkpoint, rewind the exact-resume "
                        "data state, back off, and resume")
    p.add_argument("--recovery_retries", type=int, default=3,
                   help="shared recovery budget: max on_nonfinite=skip "
                        "events per run AND max supervisor restarts; "
                        "exhausted degrades to halt")
    p.add_argument("--retry_budget_window", type=int, default=0,
                   help="progress-based retry-budget reset: when > 0, "
                        "the supervisor's attempt counter resets after "
                        "the newest checkpoint advances this many "
                        "steps past the last retry — long runs "
                        "absorbing well-spaced faults keep recovering "
                        "while a fault burst still degrades to halt. "
                        "0 = lifetime budget (historical behavior)")
    p.add_argument("--recovery_backoff_s", type=float, default=0.5,
                   help="supervisor restart backoff base (doubles per "
                        "attempt, capped at 30s)")
    p.add_argument("--rollback_lr_scale", type=float, default=1.0,
                   help="LR multiplier applied at each supervisor "
                        "rollback of a non-finite failure (1.0 = keep "
                        "LR; a deterministically diverging run replayed "
                        "at the same LR diverges again)")
    p.add_argument("--fault_spec", type=str, default=None,
                   help="deterministic fault injection for recovery "
                        "drills: comma-separated kind@trigger with "
                        "kinds nan, ckpt_corrupt, sigterm, data_stall "
                        "— plus the cluster kinds heartbeat_stall, "
                        "host_lost, collective_hang, host_return, "
                        "decision_corrupt (need --cluster_dir). A "
                        "trigger is a global step (fires once at the "
                        "first dispatch at/after it; several faults "
                        "may share a step) or a recovery phase "
                        "restore|adopt|decide that fires inside the "
                        "supervisor's recovery paths (utils/faults.py; "
                        "tools/chaos.py fuzzes these). The network "
                        "kinds net_partition, net_delay, net_drop, "
                        "net_dup (need --cluster_transport net) arm a "
                        "deterministic fault on the coordination "
                        "service isolating the injecting process "
                        "(utils/netfaults.py)")
    p.add_argument("--cluster_dir", type=str, default=None,
                   help="shared directory arming the cluster-resilience "
                        "layer (parallel/cluster.py): per-process "
                        "heartbeats, a collective watchdog classifying "
                        "straggler vs. hang/host-loss at each dispatch "
                        "seam, and chief-recorded coordinated elastic "
                        "restarts (docs/RESILIENCE.md). NFS/GCS-fuse in "
                        "production, a tmpdir in the CPU simulation")
    p.add_argument("--heartbeat_interval_s", type=float, default=0.5,
                   help="background heartbeat cadence; beats publish "
                        "from a daemon thread so a compiling/blocked "
                        "host still looks alive")
    p.add_argument("--straggler_after_s", type=float, default=2.0,
                   help="dispatch-seam overrun after which the watchdog "
                        "classifies peers (straggler telemetry for "
                        "beating-but-behind peers)")
    p.add_argument("--peer_dead_after_s", type=float, default=10.0,
                   help="a peer whose newest heartbeat is older than "
                        "this is declared lost: the run aborts "
                        "deterministically (and elastically restarts "
                        "under --supervise) instead of blocking in an "
                        "XLA collective forever")
    p.add_argument("--collective_timeout_s", type=float, default=120.0,
                   help="armed-seam duration after which the watchdog "
                        "presumes the main thread wedged inside a "
                        "collective and aborts this process after "
                        "logging (a loud corpse beats a silent hang)")
    p.add_argument("--min_hosts", type=int, default=1,
                   help="floor for coordinated elastic restarts: the "
                        "chief halts instead of shrinking the world "
                        "below this many surviving hosts")
    p.add_argument("--elastic_expand", type="bool", default=False,
                   help="elastic scale-UP: a returning (or brand-new) "
                        "host announces itself with a rejoin-phase "
                        "heartbeat instead of staying fenced; the chief "
                        "records a monotone-epoch expand decision "
                        "growing the world to the live hosts and every "
                        "process re-enters restore at the larger size "
                        "(docs/RESILIENCE.md). false = shrink-only: "
                        "evicted hosts stay fenced")
    p.add_argument("--peer_redundancy", type="bool", default=False,
                   help="diskless recovery (ckpt/peerstore.py): at every "
                        "checkpoint boundary each host also pushes its "
                        "local shard payload to its ring-successor's "
                        "replica store under --cluster_dir (async, "
                        "off the step path, sha256 sidecars); on "
                        "host_lost the chief may decide source=peer and "
                        "survivors restore with ZERO checkpoint reads, "
                        "reconstructing the lost host's shards from its "
                        "replica; any missing/stale/corrupt replica "
                        "falls back to the disk restore walk. n=1: "
                        "no-op (flag legal)")
    p.add_argument("--replica_keep", type=int, default=2,
                   help="peer-replica retention: committed replica "
                        "payloads kept per owner (newest K checkpoint "
                        "boundaries)")
    p.add_argument("--restore_deadline_s", type=float, default=0.0,
                   help="wall-clock budget for the newest→oldest "
                        "checkpoint fallback walk at restore; exceeding "
                        "it raises a classified ckpt_restore error "
                        "instead of scanning a huge retention dir "
                        "forever (0 = unbounded)")
    p.add_argument("--cluster_transport", type=str, default="file",
                   choices=["file", "net"],
                   help="coordination transport (heartbeats, restart "
                        "decisions, peer-replica pushes, fleet "
                        "discovery): 'file' = the shared-directory "
                        "store (n=1 and test fallback); 'net' = a "
                        "socket service (parallel/net.py) hosted by "
                        "process 0 (the fleet controller in --mode "
                        "fleet) over the same directory — bounded "
                        "timeouts, classified transport errors, and "
                        "the seam the net_* chaos faults partition "
                        "(docs/RESILIENCE.md Transport selection)")
    p.add_argument("--net_timeout_s", type=float, default=5.0,
                   help="per-request socket timeout on the net "
                        "coordination transport; every operation is "
                        "bounded so a dead/partitioned coordinator "
                        "degrades to the classified peer_lost/eviction "
                        "paths, never a hang (lockstep sims run 0.5)")
    p.add_argument("--net_retries", type=int, default=2,
                   help="bounded retry budget per net-transport "
                        "operation (exponential backoff between "
                        "attempts; retried on timeout/unreachable/5xx)")
    p.add_argument("--cluster_lockstep", type="bool", default=False,
                   help="simulation only: make the dispatch seam a "
                        "software barrier over the heartbeat store so "
                        "multi-process CPU runs without real "
                        "collectives still block on (and recover from) "
                        "a lost peer; real pods leave this off")
    p.add_argument("--coordinator_timeout_s", type=float, default=60.0,
                   help="per-attempt jax.distributed.initialize wait "
                        "for the coordinator; a slow-to-start "
                        "coordinator is retried with bounded backoff "
                        "(--coordinator_retries), not crashed on")
    p.add_argument("--coordinator_retries", type=int, default=3,
                   help="bounded retry budget around the coordinator "
                        "bootstrap")
    p.add_argument("--preempt_sync_every", type=int, default=10,
                   help="steps between multi-host preemption/clock-save "
                        "agreement allgathers (single-process reacts "
                        "immediately)")
    p.add_argument("--compile_cache_dir", type=str, default=None,
                   help="the repo's keyed compile store "
                        "(compilecache/, docs/COMPILECACHE.md): every "
                        "compile seam's lowered StableHLO, cost analysis "
                        "and hit/miss telemetry persist here, keyed by "
                        "fingerprint. It does not move jax's own "
                        "persistent compilation cache, which gives the "
                        "warm restart and lives where "
                        "JAX_COMPILATION_CACHE_DIR says, else "
                        "<repo>/.jax_cache (executable deserialization "
                        "from this store is opt-in per backend via "
                        "DML_COMPILECACHE_EXEC_BACKENDS). Fail-open; "
                        "emits `compile` JSONL events")
    p.add_argument("--compile_cache_max_bytes", type=int,
                   default=2_000_000_000,
                   help="LRU size bound for --compile_cache_dir "
                        "(least-recently-used entries are evicted after "
                        "each store)")
    p.add_argument("--peak_tflops", type=float, default=None,
                   help="per-chip peak TFLOP/s; enables the MFU metric "
                        "in the jsonl stream")
    p.add_argument("--metrics_jsonl", type=str, default=None)
    p.add_argument("--stats_port", type=int, default=0,
                   help="live metrics export: serve GET /metrics "
                        "(Prometheus text exposition of the "
                        "process-local counter/gauge/histogram "
                        "registry) plus /healthz from a lightweight "
                        "stats-HTTP thread while the trainer runs. "
                        "0 = off. --mode serve and the fleet router "
                        "expose /metrics on their existing servers "
                        "(docs/OBSERVABILITY.md)")
    p.add_argument("--alert_rules", type=str, default=None,
                   help="custom streaming alert rules layered over the "
                        "built-in defaults (goodput collapse, "
                        "host-bound drain, nonfinite/recovery bursts, "
                        "heartbeat staleness, shed>1%%, p99 vs "
                        "--serve_slo_ms, HBM headroom): ';'-separated "
                        "name=expr[@window][!severity] with expr "
                        "'kind.field OP value' (threshold on "
                        "consecutive records), "
                        "'rate(kind[.field=value])>=N' (trailing "
                        "step/'Ns' second window), or 'absent(kind)' "
                        "(@Ns). Firing emits rate-limited alert/"
                        "alert_resolved JSONL records "
                        "(docs/OBSERVABILITY.md)")
    p.add_argument("--autopilot", type="bool", default=False,
                   help="alert-driven remediation: attach the autopilot "
                        "policy engine to the alert trigger seam and "
                        "answer qualifying alert firings with gated "
                        "remediation actions (rollback with "
                        "--rollback_lr_scale, memory shrink + recompile "
                        "through the compile cache, fleet scale-up + "
                        "tier shed, raising --replica_keep), each "
                        "emitting a `remediation` JSONL record linked "
                        "to the firing alert's id and postmortem "
                        "bundle (docs/AUTOPILOT.md)")
    p.add_argument("--autopilot_policies", type=str, default=None,
                   help="replace the built-in autopilot policy table: "
                        "';'-separated 'name=pattern[|pattern...]"
                        "->action[:k=v,...][@cooldown[s]]' where "
                        "pattern fnmatches alert rule names, action is "
                        "rollback | shrink_memory | scale_up_shed | "
                        "raise_replica_keep, and @N is a step cooldown "
                        "(@Ns seconds). Default: nonfinite_burst->"
                        "rollback, hbm_headroom->shrink_memory, "
                        "serve/fleet SLO+shed->scale_up_shed, "
                        "peer_churn->raise_replica_keep "
                        "(docs/AUTOPILOT.md)")
    p.add_argument("--autopilot_budget", type=int, default=8,
                   help="global remediation budget shared by all "
                        "autopilot policies (the --max_finetunes "
                        "pattern generalized): once spent, further "
                        "qualifying firings get explicit "
                        "suppressed_budget records and the plain alert "
                        "stands")
    p.add_argument("--postmortem_dir", type=str, default=None,
                   help="arm the alert-triggered flight recorder: keep "
                        "a bounded in-memory ring of the last "
                        "--flightrec_size metrics records and, when a "
                        "streaming alert fires, write an atomic "
                        "post-mortem bundle (ring + alert + config + "
                        "env + live context) under this directory — one "
                        "bundle per alert firing. Render with "
                        "tools/postmortem.py (docs/OBSERVABILITY.md)")
    p.add_argument("--flightrec_size", type=int, default=256,
                   help="flight-recorder ring capacity in records "
                        "(per process; needs --postmortem_dir)")
    p.add_argument("--telemetry", type="bool", default=False,
                   help="run-health telemetry: host-loop span tracing, "
                        "goodput fractions, and HBM snapshots emitted "
                        "into the metrics JSONL at the existing "
                        "boundaries (zero extra device fetches; see "
                        "docs/OBSERVABILITY.md)")
    p.add_argument("--health_metrics", type="bool", default=False,
                   help="compile global grad-norm / param-norm / "
                        "update-ratio scalars into the train step; they "
                        "ride the fused boundary fetch into the train "
                        "JSONL records (no extra round trips)")
    p.add_argument("--tensorboard_dir", type=str, default=None,
                   help="write TensorBoard event files (chief only; the "
                        "reference's MTS wrote summaries to --log_dir)")
    p.add_argument("--profile_dir", type=str, default=None)
    p.add_argument("--profile_at_steps", type=str, default=None,
                   help="device-time attribution window 'N:K': capture "
                        "a programmatic jax.profiler trace from global "
                        "step N for K steps (closing at the next "
                        "drained metrics boundary), parse it host-side, "
                        "and emit per-op `devtime` JSONL records "
                        "(top-k ops; compute/collective/infeed "
                        "buckets). Writes under --profile_dir when "
                        "set, else <log_dir>/devprof "
                        "(docs/OBSERVABILITY.md)")
    p.add_argument("--seed", type=int, default=0)
    return p


def config_from_args(args: argparse.Namespace) -> config_lib.TrainConfig:
    make = (config_lib.reference_config if args.fidelity == "faithful"
            else config_lib.fixed_config)
    cfg = make(
        batch_size=args.batch_size,
        total_steps=args.total_steps,
        output_every=args.output_every,
        eval_every=args.eval_every,
        checkpoint_every=args.checkpoint_every,
        checkpoint_every_secs=args.checkpoint_every_secs,
        log_dir=args.log_dir,
        metrics_jsonl=args.metrics_jsonl,
        stats_port=args.stats_port,
        alert_rules=args.alert_rules,
        telemetry=args.telemetry,
        health_metrics=args.health_metrics,
        peak_tflops=args.peak_tflops,
        preempt_sync_every=args.preempt_sync_every,
        check_numerics=args.check_numerics,
        on_nonfinite=args.on_nonfinite,
        supervise=args.supervise,
        recovery_retries=args.recovery_retries,
        retry_budget_window=args.retry_budget_window,
        recovery_backoff_s=args.recovery_backoff_s,
        rollback_lr_scale=args.rollback_lr_scale,
        fault_spec=args.fault_spec,
        compile_cache_dir=args.compile_cache_dir,
        compile_cache_max_bytes=args.compile_cache_max_bytes,
        ckpt_format=args.ckpt_format,
        tensorboard_dir=args.tensorboard_dir,
        profile_dir=args.profile_dir,
        profile_at_steps=args.profile_at_steps,
        seed=args.seed,
    )
    cfg.data.dataset = args.dataset
    cfg.data.data_dir = args.data_dir
    cfg.data.random_brightness = args.random_brightness
    cfg.data.random_contrast = args.random_contrast
    if args.dataset == "cifar100":
        cfg.data.num_classes = cfg.model.num_classes = 100
    if args.dataset == "imagenet_synth":
        # The ResNet-50 ImageNet-1k rung (BASELINE.json configs[3]):
        # canonical 256-stored / 224-crop geometry, 1000 classes.
        cfg.data.image_height = cfg.data.image_width = 256
        cfg.data.crop_height = cfg.data.crop_width = 224
        cfg.data.num_classes = cfg.model.num_classes = 1000
    if args.image_size is not None:
        cfg.data.image_height = cfg.data.image_width = args.image_size
    if args.crop_size is not None:
        cfg.data.crop_height = cfg.data.crop_width = args.crop_size
    if args.synthetic_train_records is not None:
        cfg.data.synthetic_train_records = args.synthetic_train_records
    cfg.model.name = args.model
    cfg.model.compute_dtype = args.compute_dtype
    cfg.model.config_file = args.model_config_file
    cfg.data.sequence_length = args.sequence_length
    over_tokens = args.model in ("looped_decoder", "hybrid_decoder")
    if (args.dataset == "tokens_synth") != over_tokens:
        raise SystemExit(
            f"--dataset tokens_synth and a model over tokens "
            f"(looped_decoder, hybrid_decoder) go together (got "
            f"{args.dataset} with {args.model}): a model over tokens reads "
            f"token rows and nothing else does")
    if over_tokens and args.mode not in ("train", "eval"):
        raise SystemExit(
            f"--model {args.model} trains and evaluates; --mode "
            f"{args.mode} takes an image classifier (the serving stack "
            f"has no token requests and no KV cache)")
    if over_tokens:
        # the generated ids cover the model's whole vocabulary
        from dml_cnn_cifar10_tpu.models import hybrid_decoder, looped_decoder
        module = {"looped_decoder": looped_decoder,
                  "hybrid_decoder": hybrid_decoder}[args.model]
        cfg.data.num_classes = cfg.model.num_classes = \
            module.sizes(cfg.model)["vocab_size"]
    cfg.optim.adam_b1 = args.adam_b1
    cfg.optim.adam_b2 = args.adam_b2
    cfg.optim.adam_eps = args.adam_eps
    cfg.optim.learning_rate = args.learning_rate
    cfg.optim.grad_accum = args.grad_accum
    cfg.optim.optimizer = args.optimizer
    cfg.optim.momentum = args.momentum
    cfg.optim.weight_decay = args.weight_decay
    cfg.optim.label_smoothing = args.label_smoothing
    cfg.optim.grad_clip_norm = args.grad_clip_norm
    cfg.optim.ema_decay = args.ema_decay
    cfg.optim.async_staleness = args.async_staleness
    cfg.optim.schedule = args.schedule
    cfg.optim.warmup_steps = args.warmup_steps
    cfg.optim.cosine_decay_steps = args.cosine_decay_steps
    if args.schedule == "cosine" and not args.cosine_decay_steps:
        cfg.optim.cosine_decay_steps = cfg.total_steps
    cfg.steps_per_dispatch = args.steps_per_dispatch
    cfg.resident_data = args.resident_data
    cfg.data.device_index_stream = args.device_index_stream
    cfg.data.use_native_loader = args.use_native_loader
    # Seed the data stream (shuffle + device-side augmentation draws) from
    # the run seed too — otherwise --seed would not vary augmentation.
    cfg.data.seed = args.seed
    cfg.async_checkpoint = args.async_checkpoint
    cfg.model.sp_mode = args.sp_mode
    cfg.model.attn_window = args.attn_window
    cfg.model.attn_causal = args.attn_causal
    cfg.model.resnet_s2d = args.resnet_s2d
    cfg.model.resnet_norm = args.resnet_norm
    if args.pool is not None:
        cfg.model.pool = args.pool
    elif args.seq_axis > 1:
        cfg.model.pool = "mean"
    for f in ("vit_heads", "vit_dim", "vit_depth"):
        if getattr(args, f) is not None:
            setattr(cfg.model, f, getattr(args, f))
    cfg.parallel.model_axis = args.model_axis
    cfg.parallel.seq_axis = args.seq_axis
    cfg.parallel.pipe_axis = args.pipe_axis
    cfg.parallel.cluster_dir = args.cluster_dir
    cfg.parallel.heartbeat_interval_s = args.heartbeat_interval_s
    cfg.parallel.straggler_after_s = args.straggler_after_s
    cfg.parallel.peer_dead_after_s = args.peer_dead_after_s
    cfg.parallel.collective_timeout_s = args.collective_timeout_s
    cfg.parallel.min_hosts = args.min_hosts
    cfg.parallel.elastic_expand = args.elastic_expand
    cfg.parallel.peer_redundancy = args.peer_redundancy
    cfg.parallel.replica_keep = args.replica_keep
    cfg.restore_deadline_s = args.restore_deadline_s
    cfg.parallel.cluster_transport = args.cluster_transport
    cfg.parallel.net_timeout_s = args.net_timeout_s
    cfg.parallel.net_retries = args.net_retries
    cfg.parallel.cluster_lockstep = args.cluster_lockstep
    cfg.shard_io_threads = args.shard_io_threads
    cfg.parallel.coordinator_timeout_s = args.coordinator_timeout_s
    cfg.parallel.coordinator_retries = args.coordinator_retries
    if args.pipe_microbatches and args.pipe_axis <= 1:
        # Silently measuring "plain dp" while believing it's an M=4P
        # schedule is exactly the trap the moe_experts guard below
        # already closes for its flag pair.
        raise SystemExit(
            f"--pipe_microbatches={args.pipe_microbatches} requires "
            f"--pipe_axis > 1 (got {args.pipe_axis}); without a pipe "
            f"axis there is no schedule to microbatch")
    if args.pipe_schedule != "1f1b" and args.pipe_axis <= 1:
        # Mirror the --pipe_microbatches guard: without a pipe axis the
        # sequential fast path runs and a requested gpipe schedule would
        # be silently ignored — reject instead of mislabeling a run.
        raise SystemExit(
            f"--pipe_schedule={args.pipe_schedule} requires --pipe_axis "
            f"> 1 (got {args.pipe_axis}); without a pipe axis there is "
            f"no schedule to select")
    cfg.model.pipe_microbatches = args.pipe_microbatches
    cfg.model.pipe_schedule = args.pipe_schedule
    if args.moe_experts and args.model != "vit_moe":
        raise SystemExit(
            f"--moe_experts requires --model vit_moe (got {args.model})")
    cfg.model.moe_experts = args.moe_experts
    if args.model == "vit_moe" and args.moe_experts == 0:
        cfg.model.moe_experts = 8
    cfg.model.moe_top_k = args.moe_top_k
    cfg.model.moe_dispatch = args.moe_dispatch
    cfg.model.remat = args.remat
    cfg.parallel.explicit_collectives = args.explicit_collectives
    cfg.parallel.fsdp = args.fsdp
    if args.fsdp and args.explicit_collectives:
        raise SystemExit("--fsdp needs the GSPMD (default) step, not "
                         "--explicit_collectives")
    cfg.optim.optimizer_sharding = args.optimizer_sharding
    cfg.optim.fused_optimizer = args.fused_optimizer
    cfg.parallel.partition_rules = args.partition_rules
    cfg.parallel.partition_rules_strict = args.partition_rules_strict
    cfg.parallel.partition_report = args.partition_report
    if args.optimizer_sharding == "zero1":
        # Mirror the builder-level checks with CLI-shaped errors (the
        # same trap the --fsdp guard above closes): a silently ignored
        # sharding mode would mislabel every run that rides it.
        if args.fsdp:
            raise SystemExit(
                "--optimizer_sharding zero1 does not compose with "
                "--fsdp (ZeRO-3 already shards the optimizer moments)")
        if args.explicit_collectives:
            raise SystemExit(
                "--optimizer_sharding zero1 needs the GSPMD (default) "
                "step, not --explicit_collectives")
    if args.alert_rules:
        # Fail a typo'd rule at flag-parse time with a CLI-shaped
        # error — a rule that silently never fires is the worst
        # failure mode an alerting layer can have.
        from dml_cnn_cifar10_tpu.utils.alerts import parse_alert_rules
        try:
            parse_alert_rules(args.alert_rules)
        except ValueError as e:
            raise SystemExit(f"--alert_rules: {e}")
    try:
        cfg.serve.buckets = tuple(
            int(b) for b in args.serve_buckets.split(",") if b.strip())
    except ValueError:
        raise SystemExit(
            f"--serve_buckets must be comma-separated ints, got "
            f"{args.serve_buckets!r}")
    cfg.serve.max_queue_depth = args.serve_queue_depth
    cfg.serve.batch_window_ms = args.serve_batch_window_ms
    cfg.serve.deadline_ms = args.serve_deadline_ms
    cfg.serve.port = args.serve_port
    cfg.serve.artifact_path = args.serve_artifact
    cfg.serve.metrics_every_s = args.serve_metrics_every_s
    cfg.serve.drain_deadline_s = args.serve_drain_deadline_s
    cfg.serve.slo_ms = args.serve_slo_ms
    cfg.serve.trace_sample_rate = args.trace_sample_rate
    cfg.serve.quantize = args.serve_quantize
    cfg.serve.quant_calib_batches = args.quant_calib_batches
    cfg.serve.quant_max_delta = args.quant_max_delta
    cfg.serve.cache_size = args.serve_cache_size
    cfg.postmortem_dir = args.postmortem_dir
    cfg.flightrec_size = args.flightrec_size
    cfg.autopilot.enabled = args.autopilot
    cfg.autopilot.policies = args.autopilot_policies
    cfg.autopilot.budget = args.autopilot_budget
    if args.autopilot_policies:
        # Same policy as the --alert_rules pre-parse above: a typo'd
        # policy that silently never remediates must fail the run at
        # flag-parse time.
        from dml_cnn_cifar10_tpu.autopilot import parse_policies
        try:
            parse_policies(args.autopilot_policies)
        except ValueError as e:
            raise SystemExit(f"--autopilot_policies: {e}")
    cfg.runtime.jobs = args.jobs
    cfg.runtime.eval_every_s = args.runtime_eval_every_s
    cfg.runtime.eval_batches = args.runtime_eval_batches
    cfg.runtime.serve_warmup = args.runtime_serve_warmup
    cfg.runtime.finetune_steps = args.finetune_steps
    cfg.runtime.finetune_rules = args.finetune_rules
    cfg.runtime.max_finetunes = args.max_finetunes
    if args.mode == "run":
        # Fail a typo'd job spec at flag-parse time, CLI-shaped — same
        # policy as the --alert_rules pre-parse above.
        from dml_cnn_cifar10_tpu.runtime.jobs import parse_jobs
        try:
            parse_jobs(args.jobs)
        except ValueError as e:
            raise SystemExit(f"--jobs: {e}")
    if args.fleet_min_replicas < 1 \
            or args.fleet_max_replicas < args.fleet_min_replicas:
        raise SystemExit(
            f"--fleet_min_replicas/--fleet_max_replicas must satisfy "
            f"1 <= min <= max, got {args.fleet_min_replicas}/"
            f"{args.fleet_max_replicas}")
    if args.mode == "fleet" and args.fleet_max_replicas > 1:
        from dml_cnn_cifar10_tpu.utils.platform import accelerator_expected
        if accelerator_expected():
            # One process for each chip: the controller starts one
            # worker PROCESS per replica and every worker opens the
            # accelerator; a TPU host's chips belong to the first
            # process that does (docs/SERVING.md, "One process for each
            # chip"). Refuse at flag parse instead of letting the
            # second worker die or hang at backend start-up.
            raise SystemExit(
                f"--mode fleet with --fleet_max_replicas="
                f"{args.fleet_max_replicas} cannot run on one "
                f"accelerator host: each replica is a worker process, "
                f"every worker opens the TPU, and a chip belongs to one "
                f"process at a time (on a multi-chip host the first "
                f"worker claims every chip). Use --fleet_max_replicas 1 "
                f"here, or run replicas on separate hosts; replicas "
                f"sharing a host is not built yet.")
    cfg.fleet.min_replicas = args.fleet_min_replicas
    cfg.fleet.max_replicas = args.fleet_max_replicas
    cfg.fleet.port = args.fleet_port
    cfg.fleet.dir = args.fleet_dir
    cfg.fleet.autoscale = args.fleet_autoscale
    cfg.fleet.replica_dead_after_s = args.fleet_replica_dead_after_s
    cfg.fleet.publish = args.fleet_publish
    cfg.fleet.cell = args.cell
    # The worker set also names the cluster-resilience world: process_id
    # feeds chiefness (multihost.is_chief) and the heartbeat identity
    # even when jax.distributed never initializes (the lockstep CPU
    # simulation runs one independent JAX world per process).
    workers = [h for h in args.worker_hosts.split(",") if h]
    if len(workers) > 1:
        cfg.parallel.coordinator_address = workers[0]
        cfg.parallel.num_processes = len(workers)
    cfg.parallel.process_id = args.task_index
    return cfg


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)

    # Before anything compiles: jax opens its persistent compilation
    # cache (the warm start, executable swapping being off) once, at
    # the first compile.
    from dml_cnn_cifar10_tpu.compilecache import arm_native_cache
    arm_native_cache()

    if args.job_name == "ps":
        # The reference blocks a whole process on server.join()
        # (cifar10cnn.py:191-192). SPMD has no parameter servers: parameters
        # live replicated/sharded in device HBM and gradients all-reduce
        # over ICI. The ps role exits successfully for launch-script compat.
        print("[cli] job_name=ps is obsolete under SPMD: parameters live on "
              "device, gradients all-reduce over ICI. Nothing to serve; "
              "exiting.")
        return 0

    workers = [h for h in args.worker_hosts.split(",") if h]
    if len(workers) > 1 and not args.cluster_lockstep:
        # Lockstep-simulation runs keep one independent JAX world per
        # process (the cluster layer, not XLA, provides the barrier) —
        # everything else bootstraps the real distributed runtime.
        from dml_cnn_cifar10_tpu.parallel import multihost
        multihost.initialize_from_hosts(workers, args.task_index)

    cfg = config_from_args(args)
    from dml_cnn_cifar10_tpu.train.loop import Trainer

    if args.mode == "eval":
        import jax

        from dml_cnn_cifar10_tpu.data import pipeline as pipe
        cfg.eval_full_test_set = True
        trainer = Trainer(cfg, task_index=args.task_index)
        state = trainer.init_or_restore()
        step = int(jax.device_get(state.step))
        if step == 0:
            print(f"[cli] warning: no checkpoint under {cfg.log_dir}; "
                  "evaluating fresh-initialized weights", file=sys.stderr)
        # Per-process shard of the split, like fit(): each process feeds
        # only its slice into the collective sweep — an unsharded pipeline
        # would count every record process_count times.
        num_shards = jax.process_count()
        shard = jax.process_index()
        test_it = pipe.input_pipeline(
            cfg.data, cfg.batch_size // num_shards, train=False,
            seed=cfg.seed + shard, shard=shard, num_shards=num_shards)
        acc = trainer.evaluate(state, test_it)
        print(f" --- Test Accuracy = {acc * 100:.2f}%.")
        print(f"[cli] eval at step {step}: {acc * 100:.2f}% on "
              f"{test_it.total_records} records")
        return 0

    if args.mode == "export":
        import os

        import jax

        from dml_cnn_cifar10_tpu import export as export_lib
        trainer = Trainer(cfg, task_index=args.task_index)
        state = trainer.init_or_restore()
        step = int(jax.device_get(state.step))
        if step == 0:
            print(f"[cli] warning: no checkpoint under {cfg.log_dir}; "
                  "exporting fresh-initialized weights", file=sys.stderr)
        path = args.export_path or f"{cfg.log_dir}/model.jaxexport"
        # The host fetch inside export_forward is a collective when state
        # is sharded multi-host: every process participates, the chief
        # writes.
        # Export the EMA weights (and EMA BN stats) when the optimizer
        # tracks them — the same weights eval mode scores.
        params = state.opt.get("ema", state.params)
        mstate = state.opt.get("ema_mstate", state.model_state) \
            if trainer.model_def.has_state else None
        if cfg.serve.quantize == "int8":
            # Quantized export: calibrate on the eval stream, then bake
            # the int8 weights + scales into the artifact. Default
            # output name advertises the path (model_int8.jaxexport).
            # import from the module path: the package re-exports a
            # `calibrate` FUNCTION that shadows the module name
            from dml_cnn_cifar10_tpu.quant.calibrate import (
                calibrate as quant_calibrate, calibration_sets)
            calib, _, _ = calibration_sets(
                cfg.data, 64, cfg.serve.quant_calib_batches, holdout=0)
            scales = quant_calibrate(
                params, calib, cfg.model, cfg.data, batch_size=64,
                num_batches=cfg.serve.quant_calib_batches)
            if not args.export_path:
                path = f"{cfg.log_dir}/model_int8.jaxexport"
            blob = export_lib.export_quantized_forward(
                cfg.model, cfg.data, params, scales)
        else:
            blob = export_lib.export_forward(
                trainer.model_def, cfg.model, cfg.data, params, mstate)
        if jax.process_index() == 0:
            os.makedirs(os.path.dirname(os.path.abspath(path)),
                        exist_ok=True)
            export_lib.save_exported(path, blob)
            kind = "int8 " if cfg.serve.quantize == "int8" else ""
            print(f"[cli] exported step-{step} {kind}forward "
                  f"({len(blob)} bytes, tpu+cpu, symbolic batch) to {path}")
        return 0

    if args.mode == "serve":
        from dml_cnn_cifar10_tpu.serve.server import main_serve
        return main_serve(cfg, task_index=args.task_index)

    if args.mode == "fleet":
        from dml_cnn_cifar10_tpu.fleet.controller import main_fleet
        return main_fleet(cfg)

    if args.mode == "run":
        from dml_cnn_cifar10_tpu.runtime import main_run
        return main_run(cfg, task_index=args.task_index)

    if cfg.supervise:
        from dml_cnn_cifar10_tpu.train.supervisor import fit_supervised
        result = fit_supervised(cfg, task_index=args.task_index)
        if result is None:
            # Fenced by a cluster restart decision (peers declared this
            # process dead): a clean, saveless exit is the contract.
            print("[cli] fenced by the cluster restart decision; "
                  "exiting cleanly")
            return 0
    else:
        result = Trainer(cfg, task_index=args.task_index).fit()
    print(f"[cli] done at step {result.final_step}; "
          f"{result.images_per_sec:.1f} images/sec")
    return 0


if __name__ == "__main__":
    sys.exit(main())

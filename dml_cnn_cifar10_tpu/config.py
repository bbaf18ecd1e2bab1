"""Typed configuration for the framework.

The reference keeps every hyperparameter as a module-level constant
(``cifar10cnn.py:9-27``) and exposes only cluster flags via argparse
(``cifar10cnn.py:245-273``). Here all of them are dataclass fields with the
reference values as defaults, so parity runs are the zero-config path and the
CLI can override anything.

Fidelity switches: the reference has three load-bearing quirks —
(1) ReLU applied to the logits (``cifar10cnn.py:145``),
(2) a dead LR-decay schedule (decay keyed on a never-incremented variable,
    ``cifar10cnn.py:161,216`` — effective LR is constant 0.1),
(3) eval on a single *shuffled* 128-image test batch rather than the full
    test set (``cifar10cnn.py:202,238``).
Each has a switch; ``faithful`` mode reproduces the quirk, ``fixed`` mode does
the sane thing. Defaults are faithful so parity runs match the reference.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass
class DataConfig:
    """Input pipeline config. Reference: ``cifar10cnn.py:9-27,34-91``."""

    # cifar10 | cifar100 | synthetic | imagenet_synth | tokens_synth
    dataset: str = "cifar10"
    data_dir: str = "cifar10data"         # reference constant (cifar10cnn.py:26)
    image_height: int = 32                # cifar10cnn.py:15
    image_width: int = 32                 # cifar10cnn.py:16
    crop_height: int = 24                 # cifar10cnn.py:17
    crop_width: int = 24                  # cifar10cnn.py:18
    num_channels: int = 3                 # cifar10cnn.py:19
    num_classes: int = 10                 # cifar10cnn.py:20 (NUM_TARGETS)
    shuffle_buffer: int = 5000            # min_after_dequeue (cifar10cnn.py:85)
    # Reference crop is a deterministic center crop despite the "Randomly
    # Crop" comment (cifar10cnn.py:67-68). random_crop=True enables the
    # augmentation the comment intended (fixed mode).
    random_crop: bool = False
    random_flip: bool = False
    # Color jitter (the TF CIFAR-tutorial lineage the reference derives
    # from used random_brightness(63) + random_contrast(0.2, 1.8)):
    # brightness adds U[-b, b] in pixel units per image; contrast scales
    # per-channel deviation-from-mean by U[1-c, 1+c]. 0 = off.
    random_brightness: float = 0.0
    random_contrast: float = 0.0
    # Pixel normalization. The reference feeds raw 0..255 floats
    # (cifar10cnn.py:66 — cast, no scaling), which with LR 0.1 makes training
    # numerically violent; faithful default keeps that. "scale" maps to
    # [0,1]; "standardize" does per-image zero-mean/unit-var (what the TF
    # CIFAR tutorial the reference derives from actually used).
    normalize: str = "none"               # none | scale | standardize
    prefetch: int = 2                     # host->HBM prefetch depth
    seed: int = 0
    # HBM-resident path only: generate the shuffled index stream ON
    # DEVICE inside the compiled chunk (data/device_stream.py stateless
    # per-epoch pseudo-permutation keyed on the global step) — a training
    # dispatch then uploads nothing at all. The shuffle is a different
    # (equally valid) permutation than the host stream's numpy-PCG one,
    # so toggling this flag changes the data order. Default ON (round-4
    # verdict #5: throughput parity with host indices, deletes the
    # exact-resume sidecar, and ships no per-process index arrays at
    # multi-host scale); --device_index_stream=false restores the host
    # numpy-PCG stream.
    device_index_stream: bool = True
    # Use the native C++ record loader (runtime/librecordio.so, built on
    # demand); a failed build or load raises rather than falling back.
    use_native_loader: bool = True
    # Synthetic mode generates CIFAR-format .bin files locally (same 3073-byte
    # record layout) for air-gapped testing/benchmarking.
    synthetic_train_records: int = 2048
    synthetic_test_records: int = 512
    # Token datasets (``tokens_synth``): a record is ``sequence_length + 1``
    # little-endian int32 token ids below ``num_classes`` (the vocabulary,
    # which the CLI takes from the model's sizes): inputs ``[:-1]``,
    # next-token targets ``[1:]``. No label, no decode: on the device a
    # batch is a gather of rows, and the shift is the model's.
    sequence_length: int = 128

    # Every randomized-augmentation field and its "off" value — the one
    # list ``augmented`` and ``without_augmentation`` both derive from, so
    # a new augmentation knob cannot drift between them.
    _AUG_OFF = (("random_crop", False), ("random_flip", False),
                ("random_brightness", 0.0), ("random_contrast", 0.0))

    @property
    def tokens(self) -> bool:
        """True for a dataset of token rows (no image, no label)."""
        return self.dataset == "tokens_synth"

    @property
    def augmented(self) -> bool:
        """True when ANY randomized augmentation is on — the single
        source of truth for "needs a PRNG key on the device decode path"
        (ops/preprocess.py) and for the chunk builders' key threading."""
        return any(getattr(self, name) != off for name, off in self._AUG_OFF)

    def without_augmentation(self) -> "DataConfig":
        """Eval-time decode config: every randomized augmentation off."""
        return dataclasses.replace(self, **dict(self._AUG_OFF))

    @property
    def record_bytes(self) -> int:
        """1 label byte + H*W*C image bytes (cifar10cnn.py:24-25)."""
        return 1 + self.image_height * self.image_width * self.num_channels

    @property
    def input_hw(self) -> Tuple[int, int]:
        return (self.crop_height, self.crop_width)


@dataclasses.dataclass
class ModelConfig:
    """Model selection + faithful-mode switches."""

    name: str = "cnn"                     # cnn | resnet18 | resnet50 | vit_tiny
    num_classes: int = 10
    # A file of sizes in the shape of a published ``config.json``, for a
    # model that reads one (models/looped_decoder.py,
    # models/hybrid_decoder.py) in place of a flat family of fields here.
    config_file: Optional[str] = None
    # Reference applies ReLU to the final logits (cifar10cnn.py:145). Faithful
    # mode keeps it; fixed mode emits raw logits.
    logit_relu: bool = True
    # Initializers: truncated normal sigma=0.05 (cifar10cnn.py:97-98),
    # bias constant 0.1 (cifar10cnn.py:100-101).
    init_stddev: float = 0.05
    bias_init: float = 0.1
    dtype: str = "float32"                # param dtype
    compute_dtype: str = "float32"        # activations; bfloat16 on TPU runs
    # BatchNorm knobs (ResNet configs; SURVEY §2.3 cross-replica stats).
    bn_momentum: float = 0.9
    bn_eps: float = 1e-5
    # ViT-specific knobs (ignored by CNN/ResNet).
    patch_size: int = 4
    vit_dim: int = 192
    vit_depth: int = 12
    vit_heads: int = 3
    use_pallas_attention: bool = True     # Pallas flash-attention on TPU
    # "cls" = prepend a class token (standard ViT head). "mean" = no class
    # token, mean-pool the tokens — the long-context/sequence-parallel mode,
    # where the token count must divide the ``seq`` mesh axis and a lone
    # cls token would break the even sharding.
    pool: str = "cls"                     # cls | mean
    # Rematerialization: recompute each block's activations in the
    # backward pass instead of storing them (jax.checkpoint around the
    # ViT transformer block / ResNet residual block). Trades ~1 extra
    # forward of FLOPs for activation memory that stays O(1) in depth —
    # the standard long-context / deep-stack memory lever on TPU. The
    # looped decoder recomputes a layer but its flash attention kernel:
    # the kernel's output and log-sum-exp are kept beside the layer's
    # input (models/looped_decoder.py, KEPT).
    remat: bool = False
    # Sequence-parallel attention strategy when the mesh's ``seq`` axis >1:
    # "ring" walks K/V shards around the ring (no head-count constraint,
    # best at very long S); "ulysses" all-to-alls seq→heads and runs one
    # dense full-sequence kernel per head slice (needs heads % seq_axis
    # == 0, best MXU utilization at moderate seq degree).
    sp_mode: str = "ring"                 # ring | ulysses
    # Sliding-window (local) attention width: None = full attention.
    # Band |row - col| < attn_window, composed with ``attn_causal`` the
    # Mistral-style local-LM mask. Applies to the ViT family's attention
    # on every path (XLA short-seq, flash kernels, ring, Ulysses); under
    # ring SP the window must not exceed the per-shard sequence length.
    attn_window: int | None = None
    # Causal (autoregressive) attention mask for the transformer blocks.
    attn_causal: bool = False
    # MLPerf-style space-to-depth stem for the ImageNet-stem ResNets:
    # [B,224,224,3] re-laid-out to [B,112,112,12] and the 7x7/2 stem conv
    # replaced by the equivalent 4x4/1 conv on the re-laid tensor (the
    # 7x7 kernel embeds in the 4x4x12 class, zero-padded to 8x8). C=3
    # tiles the MXU contraction at ~2% occupancy; 12 channels x 16 taps
    # quadruple it. Changes the stem param shape (checkpoints don't
    # interchange across this flag).
    resnet_s2d: bool = False
    # ResNet normalization: "bn" (reference semantics — cross-replica
    # BatchNorm) or "nf" (normalizer-free: scaled weight standardization
    # + SkipInit residual scalars, models/resnet.py). The round-4
    # roofline showed 76.5% of ResNet-50 step time bandwidth-bound with
    # BN's stats reductions + normalize store/re-read a big share of the
    # bytes; "nf" removes those passes entirely — the byte-reduction
    # rung. Different semantics than the BN ladder rows (no running
    # stats; checkpoints don't interchange across this flag).
    resnet_norm: str = "bn"
    # GPipe microbatches per step under pipeline parallelism (0 = one per
    # stage). The bubble fraction is (M+P-1)/M: at the M=P default every
    # stage idles ~half the ticks; M = 4P costs 1/4 the bubble in
    # exchange for microbatches 1/4 the size. The global batch must be
    # divisible by data_axis * M.
    pipe_microbatches: int = 0
    # Pipeline schedule: "1f1b" (default — bubbles skipped, recompute
    # backward: 3F+1B, minimal O(P·microbatch) memory; not measured
    # against the ring on the chip), "1f1b_ring" (2F+1B
    # residual-ring backward — opt-in; see parallel/pipeline.py's
    # docstring), or "gpipe" (the round-2 baseline: always-on
    # stage compute, autodiff through the scan; kept for comparison
    # benches).
    pipe_schedule: str = "1f1b"
    # Mixture-of-Experts (model name "vit_moe"): every block's MLP becomes
    # a routed expert bank (ops/moe.py) — moe_top_k=1 Switch routing,
    # 2 GShard — with experts sharded over the ``model`` mesh axis
    # (expert parallelism).
    moe_experts: int = 0                  # 0 = dense MLP
    # MoE dispatch/combine formulation (ops/moe.py): "einsum" ([T,E,C]
    # one-hot contractions — the all-MXU, ep-proven path whose dispatch
    # GSPMD compiles into the expert all-to-all) or "scatter"
    # ((expert, slot)-indexed scatter/gather — O(T·D) instead of the
    # einsum pair's O(T²·f·D); speed not measured on the current
    # chip). Identical
    # semantics, numerically equivalent (pinned to ~1e-5 by
    # test_scatter_dispatch_matches_einsum — reduction orders differ,
    # so outputs are close, not bit-identical).
    moe_dispatch: str = "einsum"
    # 1 = Switch, 2 = GShard routing: ``ops.moe.moe_mlp``'s two, with its
    # static capacity. (``ops.moe.routed_experts`` takes any number of
    # experts a token and drops none; its sizes come from the model's
    # ``config_file``, not from these fields.)
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_aux_coef: float = 0.01            # load-balance loss weight


@dataclasses.dataclass
class OptimConfig:
    """Optimizer/schedule. Reference: ``cifar10cnn.py:21-23,159-164``."""

    learning_rate: float = 0.1            # cifar10cnn.py:21
    lr_decay: float = 0.9                 # cifar10cnn.py:22
    decay_every: int = 250                # NUM_GENS_TO_WAIT (cifar10cnn.py:23)
    staircase: bool = True                # cifar10cnn.py:161
    # Faithful mode: the reference's decay is keyed on a variable that is
    # never incremented (cifar10cnn.py:216), so the effective LR is a
    # constant 0.1. dead_lr_decay=True reproduces that; False applies the
    # schedule the code *meant* (keyed on the global step).
    dead_lr_decay: bool = True
    momentum: float = 0.0                 # reference uses plain SGD
    weight_decay: float = 0.0
    # Schedule family: "exponential" is the reference's (with the
    # dead_lr_decay fidelity switch above); "cosine" (half-cosine to 0
    # over cosine_decay_steps) is the ViT/ResNet ladder standard;
    # "constant" is flat. warmup_steps prepends a linear ramp to any of
    # them.
    schedule: str = "exponential"         # exponential | cosine | constant
    warmup_steps: int = 0
    cosine_decay_steps: int = 0
    # Optimizer family. "sgd" (+ optional momentum) is the reference's;
    # "adamw" (decoupled weight decay, bias-corrected moments) is the
    # transformer-ladder standard; "lars"/"lamb" add the per-layer trust
    # ratio that makes LARGE global batches trainable — the natural
    # companion of wide ``data``-axis scaling (You et al. 2017/2019);
    # "adafactor" (Shazeer & Stern 2018) factors the second moment into
    # row/col statistics — O(n+m) optimizer state per matrix instead of
    # Adam's O(n*m), the TPU-era memory choice for large models.
    optimizer: str = "sgd"        # sgd | adamw | lars | lamb | adafactor
    # LARS trust coefficient (eta in the paper) and norm-guard epsilon.
    lars_trust_coef: float = 0.001
    lars_eps: float = 1e-9
    # Label smoothing ε for the CE loss (0 = reference parity).
    label_smoothing: float = 0.0
    adam_b1: float = 0.9
    adam_b2: float = 0.999
    adam_eps: float = 1e-8
    grad_clip_norm: Optional[float] = None
    # Async-PS staleness emulation (SURVEY §2.3's one semantic delta:
    # the reference's workers compute gradients on parameters that are
    # up to W-1 updates old, W = worker count — cifar10cnn.py:162,
    # no SyncReplicasOptimizer). S >= 2 reproduces that staleness
    # DETERMINISTICALLY: gradients are taken at a round-robin snapshot
    # S-1 updates old and applied to the live params, so async-vs-sync
    # convergence can be compared exactly. 0/1 = synchronous (default).
    # Costs S extra param copies in the optimizer state.
    async_staleness: int = 0
    # Exponential moving average of the params, updated every step and
    # used for EVAL only (the train step keeps optimizing the raw
    # params). 0 disables. The standard ViT/ResNet recipe stabilizer; no
    # reference counterpart.
    ema_decay: float = 0.0
    # Gradient accumulation: split each global batch into this many
    # microbatches inside the compiled step (lax.scan), average the grads,
    # apply ONE optimizer update. Trains large effective batches in bounded
    # activation memory (no reference counterpart — the reference's batch
    # always fits; this is a scale capability).
    grad_accum: int = 1
    # Cross-replica sharding of the WEIGHT UPDATE (arxiv 2004.13336;
    # docs/SHARDING.md). "none" = fully replicated params + optimizer
    # state (the historical layout). "zero1" = optimizer moments (+ EMA)
    # allocated sharded 1/|data| from init on; the step reduce-scatters
    # grads over ``data``, each replica updates its shard, and the new
    # params all-gather for the next forward — same math as replicated
    # to reduction-reorder tolerance (≤1e-6, pinned), checkpoints
    # interchange across modes. Needs the GSPMD (default) step; does
    # not compose with --fsdp (which already shards the update state)
    # or --async_staleness.
    optimizer_sharding: str = "none"     # none | zero1
    # Fused single-pass SGD update (ops/optimizer.py): momentum + weight
    # decay + LR applied in ONE pass over the param bytes — a Pallas TPU
    # kernel with an identical-math XLA fallback selected by platform
    # (bit-equal to the tree_map chain; PARITY.md). False restores the
    # historical per-transform tree_map chain.
    fused_optimizer: bool = True


@dataclasses.dataclass
class ParallelConfig:
    """Mesh / distribution. Replaces the PS cluster (``cifar10cnn.py:184-196``).

    The reference's asynchronous parameter-server data parallelism becomes
    synchronous SPMD data parallelism: batch sharded over the ``data`` mesh
    axis, gradient all-reduce compiled into the step (psum over ICI). The
    ``model`` axis enables tensor parallelism for the larger configs.
    """

    data_axis: int = -1                   # -1 => all remaining devices
    model_axis: int = 1                   # tensor-parallel degree
    seq_axis: int = 1                     # sequence/context-parallel degree
    pipe_axis: int = 1                    # pipeline-parallel degree (stages)
    # Multi-host bootstrap (replaces ClusterSpec/Server, cifar10cnn.py:188-189)
    coordinator_address: Optional[str] = None
    num_processes: int = 1
    process_id: int = 0
    # Coordinator bootstrap hardening (parallel/multihost.py): how long
    # one jax.distributed.initialize attempt may wait for the
    # coordinator, and how many attempts (with the shared bounded
    # exponential backoff, utils/backoff.py) before a slow-to-start
    # coordinator becomes a real failure.
    coordinator_timeout_s: float = 60.0
    coordinator_retries: int = 3
    # Cluster-resilience layer (parallel/cluster.py; docs/RESILIENCE.md
    # multi-host section). cluster_dir enables it: a shared directory
    # (NFS/GCS-fuse in production, a tmpdir in the CPU simulation)
    # holding per-process heartbeat beats and the chief's restart
    # decisions. None = layer off (the default; single-process runs
    # don't need it).
    cluster_dir: Optional[str] = None
    # Background beat cadence. Beats publish from a daemon thread so a
    # host that is merely compiling/blocked still looks ALIVE.
    heartbeat_interval_s: float = 0.5
    # Dispatch-seam overrun after which the watchdog starts classifying
    # peers (straggler telemetry for peers beating-but-behind).
    straggler_after_s: float = 2.0
    # A peer whose newest beat is older than this is declared lost —
    # the run aborts deterministically (PeerLostError) instead of
    # blocking in an XLA collective forever.
    peer_dead_after_s: float = 10.0
    # Armed-seam duration after which the watchdog presumes the main
    # thread is wedged inside a collective and aborts THIS process
    # (os._exit) after logging — a loud corpse beats a silent hang.
    collective_timeout_s: float = 120.0
    # Coordinated elastic restart shrinks the world by the lost hosts;
    # below this floor the chief halts instead of continuing degraded.
    min_hosts: int = 1
    # Elastic scale-UP: let returning (or brand-new) hosts back in. A
    # host a restart decision excluded announces itself with a
    # `rejoin`-phase heartbeat instead of fencing; the chief records a
    # monotone-epoch EXPAND decision growing the world to the live
    # hosts, and everyone re-enters restore at the larger size (the
    # device index stream reshards deterministically — no per-host
    # sidecar state to migrate). Off = the PR-4 shrink-only contract:
    # once evicted, fenced forever.
    elastic_expand: bool = False
    # Peer-redundant in-memory shards (ckpt/peerstore.py;
    # docs/RESILIENCE.md diskless-recovery section). At every checkpoint
    # boundary each host pushes its local shard payload to its
    # ring-successor's replica inbox under <cluster_dir>/replicas, so an
    # elastic restart can reconstruct the lost host's state from a
    # surviving peer instead of walking disk checkpoints. Requires
    # cluster_dir; a 1-process world degrades to a no-op (the flag stays
    # legal). Off = every restore reads disk, exactly as before.
    peer_redundancy: bool = False
    # Replica retention: committed replica step-dirs kept per owner
    # before the push thread prunes the oldest.
    replica_keep: int = 2
    # Coordination transport (parallel/net.py; docs/RESILIENCE.md
    # transport-selection section). "file": the shared-directory store
    # above — the n=1/test fallback and the shared-filesystem default.
    # "net": the same HeartbeatStore/RestartCoordinator contracts over
    # a stdlib-HTTP coordination service hosted by process 0 over
    # cluster_dir; every operation gets a bounded timeout, bounded
    # retries, and classified errors, so a dead/partitioned
    # coordinator degrades into the ordinary peer_lost/eviction paths
    # instead of a hang.
    cluster_transport: str = "file"
    # Per-request socket timeout of the net transport. The lockstep
    # sims run 0.5s; production WANs want the default.
    net_timeout_s: float = 5.0
    # Extra attempts per operation (bounded backoff between attempts)
    # before a transport failure is surfaced.
    net_retries: int = 2
    # Simulation only: make the dispatch seam a software barrier over
    # the heartbeat store (wait for every live peer to reach the local
    # step) so multi-process CPU runs without real collectives still
    # exercise straggler/hang/host-loss classification in lockstep.
    # Real multi-host runs leave this off — XLA already enforces it.
    cluster_lockstep: bool = False
    # Explicit shard_map + lax.psum step instead of jit auto-partitioning.
    explicit_collectives: bool = False
    # ZeRO/FSDP: shard params + optimizer moments over the ``data`` axis
    # (parallel/shardings.py:_add_fsdp). State memory scales 1/|data|;
    # GSPMD all-gathers weights before compute and reduce-scatters grads.
    # Composes with the model/seq/pipe axes. No reference counterpart —
    # the PS already "sharded" state round-robin over PS tasks
    # (cifar10cnn.py:195-196); this is the SPMD-native form of that idea.
    fsdp: bool = False
    # Partition-rule override (parallel/shardings.py engine;
    # docs/SHARDING.md grammar): ordered ";"-separated "regex=spec"
    # rules replacing the model's default table — specs are
    # comma-separated per-dim axis names, right-aligned ("-"/"*"/empty =
    # unsharded dim, "^" prefix = left-aligned, empty spec =
    # replicated). None keeps the model's built-in rules.
    partition_rules: Optional[str] = None
    # Strict rule matching: a leaf no rule covers is a build-time error
    # instead of silently replicating (applies to the override above
    # AND the built-in tables, which all end in a catch-all).
    partition_rules_strict: bool = False
    # Print the which-rule-matched-which-param report (path, shape,
    # matching rule, resulting spec) at Trainer build.
    partition_report: bool = False


@dataclasses.dataclass
class ServeConfig:
    """Serving runtime (``--mode serve``, ``serve/`` package).

    No reference counterpart at all — the reference's only output is a
    checkpoint directory (``cifar10cnn.py:222``). These knobs shape the
    dynamic micro-batcher documented in ``docs/SERVING.md``.
    """

    # Pre-compiled batch sizes. Each bucket jit-compiles once at warmup;
    # a request batch pads up to the smallest bucket that fits. More
    # buckets = tighter padding waste, more compiles and executable
    # cache; powers-of-~4 cover the range well.
    buckets: Tuple[int, ...] = (1, 8, 32, 128)
    # Admission control: submits beyond this queue depth are rejected
    # immediately (ShedError) instead of growing an unbounded backlog —
    # bounded worst-case queue wait, shed load instead of collapsing.
    max_queue_depth: int = 256
    # Max extra latency the batcher may add waiting to fill a batch:
    # the head request of a batch waits at most this long before
    # dispatch. Under saturation batches fill instantly and the window
    # never engages.
    batch_window_ms: float = 2.0
    # Per-request deadline: requests still queued past it are shed at
    # dispatch time (the client already gave up — don't spend device
    # lanes on them). None = no deadline.
    deadline_ms: Optional[float] = None
    # HTTP port for --mode serve (0 = ephemeral, the chosen port is
    # printed at startup).
    port: int = 8000
    # Explicit artifact to serve. None = <log_dir>/model.jaxexport when
    # present, else restore the latest checkpoint and serve live params.
    artifact_path: Optional[str] = None
    # Cadence of `serve` JSONL window records while the server runs.
    metrics_every_s: float = 5.0
    # Graceful-shutdown budget: on SIGTERM/SIGINT the server stops
    # accepting, lets already-queued batches finish for at most this
    # long, sheds the remainder, flushes metrics, and exits 0
    # (serve/server.py; reuses PreemptionGuard).
    drain_deadline_s: float = 5.0
    # Latency objective for the serving path (milliseconds at p99).
    # Purely declarative for a single server; under --mode fleet the
    # autoscaler treats a p99 above it as a scale-up signal
    # (fleet/autoscaler.py). None = no objective.
    slo_ms: Optional[float] = None
    # Head-sampling rate for distributed request tracing
    # (utils/reqtrace.py; docs/OBSERVABILITY.md request-tracing
    # section): this fraction of requests emit one `rspan` JSONL
    # record per hop (client, router attempt, worker, batcher queue,
    # engine dispatch, batch). Shed or retried requests are
    # force-sampled regardless. 0 = off.
    trace_sample_rate: float = 0.0
    # Quantized serving path (quant/ package, docs/QUANT.md): "int8"
    # serves the post-training-quantized forward (per-channel weight
    # scales + calibrated activation scales, XLA-native int8 compute);
    # versions carry a "+int8" suffix. None = float serving.
    quantize: Optional[str] = None
    # Eval-stream batches (of 64) the activation calibration observes.
    # More batches = tighter amax estimates; the holdout the publish
    # gate scores on is drawn disjointly after them.
    quant_calib_batches: int = 4
    # The pinned accuracy contract: an int8 candidate whose holdout
    # top-1 trails float top-1 by more than this FRACTION (0.005 =
    # 0.5%) is rejected at publish time (`quant_rejected` JSONL) and
    # the previous version keeps serving.
    quant_max_delta: float = 0.005
    # Exact-match response cache: LRU over (input digest, serving
    # version) entries; hits bypass the batcher entirely and count as
    # `cache_hit` in serve windows. Flushed whenever the serving
    # version changes, so a stale version can never answer. 0 = off.
    cache_size: int = 0


@dataclasses.dataclass
class FleetConfig:
    """Serving fleet (``--mode fleet``, ``fleet/`` package).

    One router/load-balancer process fronting N serve worker replicas
    (each a :class:`~serve.engine.ServingEngine` subprocess), with
    heartbeat liveness, zero-downtime checkpoint hot-swap, and a
    closed-loop autoscaler — docs/SERVING.md fleet section.
    """

    # Replica count bounds the autoscaler operates within. The pool
    # starts min_replicas workers; a fleet below min is always scaled
    # back up (the self-healing path after a worker death).
    min_replicas: int = 2
    max_replicas: int = 4
    # Router HTTP port (0 = ephemeral, printed at startup). Workers
    # always bind ephemeral ports and advertise them via heartbeats.
    port: int = 8100
    # Fleet coordination directory (heartbeats, the published-version
    # file, per-replica telemetry). None = <log_dir>/fleet. Shared
    # filesystem in production, a tmpdir in tests — same contract as
    # --cluster_dir.
    dir: Optional[str] = None
    # Worker beat cadence and the staleness threshold past which the
    # router evicts a replica and re-routes its traffic. Beats carry
    # {replica_id, version, queue_depth, phase, port}.
    heartbeat_interval_s: float = 0.25
    replica_dead_after_s: float = 3.0
    # Worker-side poll cadence on the published-version file, and
    # publisher-side watch cadence on the checkpoint dir.
    swap_poll_s: float = 0.25
    publish_poll_s: float = 0.5
    # Trainer-side publish hook: when true, every committed checkpoint
    # (integrity sidecar included) is published to <fleet dir> for the
    # online train-and-serve scenario (train/loop.py). The fleet's own
    # directory publisher watches the checkpoint dir regardless.
    publish: bool = False
    # Closed-loop autoscaler (fleet/autoscaler.py): decision cadence,
    # post-decision cooldown, and the queue-depth-per-replica level
    # treated as a scale-up signal. Decisions additionally key on shed
    # fraction and p99 vs serve.slo_ms from the replicas' serve JSONL
    # windows. autoscale=False pins the fleet at min_replicas (deaths
    # are still replaced — below-min always scales up).
    autoscale: bool = True
    autoscale_every_s: float = 2.0
    scale_cooldown_s: float = 10.0
    scale_up_queue_depth: float = 8.0
    # Max re-route attempts for one client request before the router
    # sheds it (each failed attempt also evicts the failing replica).
    route_retries: int = 3
    # Base inter-attempt delay of the router's bounded retry backoff
    # (utils/backoff.py, capped at 10x): a flapping replica must not
    # ping-pong a request across survivors at CPU speed.
    route_backoff_s: float = 0.05
    # Per-attempt router->worker proxy timeout.
    route_timeout_s: float = 30.0
    # Cadence of `fleet` JSONL window records from the router.
    metrics_every_s: float = 2.0
    # Test/drill hook: "<replica_id>:<kind>@<n>" arms utils/faults.py
    # kind (host_lost | heartbeat_stall) on that replica after n batch
    # dispatches — the fleet analogue of --fault_spec. None disables.
    worker_fault: Optional[str] = None
    # Named cells (comma-separated, e.g. "us-east,us-west"): replica i
    # belongs to cell i % len(cells), advertises it in its heartbeat,
    # and the router prefers a request's target cell (X-DML-Cell
    # header / loadgen --target_cell), failing over cross-cell — with
    # a `cell_route` record and a force-sampled trace — only when the
    # target cell has no live replica. One cell = the old behavior.
    cell: str = "default"


@dataclasses.dataclass
class RuntimeConfig:
    """Unified multi-job runtime (``--mode run``, ``runtime/`` package).

    One :class:`~runtime.core.Runtime` per process owns the mesh, the
    telemetry stream/registry, the alert engine, the stats server, and
    the serving compile cache exactly once; a job scheduler runs typed
    jobs (train / eval / serve / finetune) concurrently on that shared
    substrate — docs/RUNTIME.md.
    """

    # Comma-separated job spec: which jobs the runtime starts. "train"
    # and any triggered "finetune" are task jobs (the runtime exits when
    # they drain); "serve" and "eval" are service jobs (they run until
    # the task jobs finish, then stop). FineTuneJobs are never listed —
    # they are born from alert triggers (see finetune_steps).
    jobs: str = "train,serve"
    # EvalJob cadence: re-evaluate the latest published weights every
    # this many seconds (service job; needs "eval" in jobs).
    eval_every_s: float = 2.0
    # Test batches per EvalJob tick (each is one serving forward).
    eval_batches: int = 1
    # Pre-compile the serving engine's bucket programs at first publish.
    # Off by default: warmup fetches results (jax.device_get) and the
    # runtime's train path must keep the fetch-parity invariant — the
    # request path compiles lazily instead.
    serve_warmup: bool = False
    # Alert→job control loop: an EMITTED alert firing enqueues a
    # FineTuneJob continuing training for this many extra steps from the
    # last in-process train state (zero checkpoint reads when the
    # TrainJob ran in this process). 0 disables triggering.
    finetune_steps: int = 0
    # Comma-separated alert rule names that may trigger a FineTuneJob.
    # None = any emitted firing triggers (budget permitting).
    finetune_rules: Optional[str] = None
    # Lifetime budget of triggered FineTuneJobs per runtime.
    max_finetunes: int = 1
    # Where the runtime advertises its live state (bound serve port,
    # last published version) for tools/loadgen.py --runtime discovery.
    # None = <log_dir>/runtime.json.
    state_path: Optional[str] = None


@dataclasses.dataclass
class AutopilotConfig:
    """Alert-driven remediation (``--autopilot``, ``autopilot/``
    package; docs/AUTOPILOT.md).

    When enabled, an :class:`~autopilot.engine.AutopilotEngine`
    attaches to the alert engine's trigger seam and answers every
    emitted alert firing that matches a policy with a remediation
    action — rollback with LR scaling, memory shrink + recompile
    through the compile cache, fleet scale-up + tier shed, raising
    replica_keep — each gated by a per-policy cooldown and one global
    budget, and each recorded as a ``remediation`` JSONL record linked
    to the firing alert's id and its postmortem bundle.
    """

    enabled: bool = False
    # Policy table override (autopilot/engine.py grammar):
    # ";"-separated "name=pattern[|pattern...]->action[:k=v,...]
    # [@cooldown[s]]" where pattern fnmatches alert rule names,
    # action is one of rollback | shrink_memory | scale_up_shed |
    # raise_replica_keep, and @N is a step cooldown (@Ns = seconds).
    # None/empty = the built-in default table.
    policies: Optional[str] = None
    # Global remediation budget shared by all policies (the
    # --max_finetunes counter pattern generalized): once spent, every
    # further qualifying firing is answered by an explicit
    # suppressed_budget record and the plain alert stands.
    budget: int = 8


@dataclasses.dataclass
class TrainConfig:
    """Training driver. Reference: ``cifar10cnn.py:11-14,219-242``."""

    batch_size: int = 128                 # per-step GLOBAL batch (cifar10cnn.py:13)
    total_steps: int = 20000              # GENERATIONS (cifar10cnn.py:14)
    output_every: int = 200               # OUTPUT_EVERY (cifar10cnn.py:11)
    eval_every: int = 500                 # EVAL_EVERY (cifar10cnn.py:12)
    # Faithful mode evaluates one shuffled test batch (cifar10cnn.py:202,238);
    # fixed mode sweeps the full test set.
    eval_full_test_set: bool = False
    log_dir: str = "/tmp/train_logs"      # checkpoint dir (cifar10cnn.py:269-272)
    checkpoint_every: int = 1000          # steps; MTS default was 600s wall-clock
    # Wall-clock checkpoint cadence IN ADDITION to the step cadence — the
    # faithful MTS behavior (save_checkpoint_secs=600 default at
    # cifar10cnn.py:222). None disables the clock trigger. Multi-host runs
    # agree on it at the preemption-sync boundary (train/loop.py).
    checkpoint_every_secs: Optional[float] = None
    keep_checkpoints: int = 3
    # Checkpoint codec: "msgpack" (single flax file), "orbax" (the
    # JAX-ecosystem standard directory format — interoperable with
    # external orbax tooling), or "sharded" (per-process shard files,
    # the pod-scale path: no full-state gather, each process writes
    # O(state/N) bytes — ckpt/sharded.py). Restore auto-detects per
    # checkpoint. orbax is single-process only: its save is itself a
    # collective, which the chief-only writer would deadlock
    # (ckpt/checkpoint.py).
    ckpt_format: str = "msgpack"
    # Bounded thread-pool size for the sharded codec's concurrent
    # per-shard file IO (ckpt/sharded.py): saves split the local
    # payload across up to this many part files written in parallel,
    # restores read+verify+unpack shard files in parallel — elastic
    # transitions at large world sizes become network-bound, not
    # serialization-bound. 1 = fully serial (bit-identical results
    # either way; per-shard sha256 sidecars verify each file before
    # assembly).
    shard_io_threads: int = 4
    # Wall-clock budget for restore_checkpoint's newest→oldest fallback
    # walk (ckpt/checkpoint.py): a walk that exceeds it raises a
    # classified ckpt_restore error instead of silently scanning a huge
    # retention dir forever. 0 = no deadline.
    restore_deadline_s: float = 0.0
    # Overlap checkpoint serialize+write with training on a background
    # writer thread (the device->host fetch stays synchronous — donated
    # step buffers would otherwise race the reader).
    async_checkpoint: bool = False
    # Steps per device dispatch. >1 switches the Trainer to the chunked
    # path (parallel/step.py:make_train_chunk): lax.scan over K stacked
    # batches per dispatch, host ships raw uint8, decode/augment fused on
    # device — the dispatch-bound small-model regime needs this to keep
    # the MXU fed. output/eval/checkpoint cadences and total_steps must be
    # multiples of K so every observable boundary falls on a dispatch edge.
    steps_per_dispatch: int = 1
    # With steps_per_dispatch > 1, keep the whole uint8 dataset resident
    # in HBM and ship only shuffled index arrays (~10 KB/chunk) — the
    # device does the gather+decode (measured ~16x over the host-fed
    # chunk path on the reference CNN). Multi-host runs replicate the
    # FULL split into every process's HBM and each process contributes
    # its slice of the global index array (local shard rows translate to
    # full-split rows; bit-identical to the host-fed path by test).
    # Falls back to host-fed raw chunks when the full split exceeds
    # resident_data_max_bytes, or under the native loader (its
    # bounded-shuffle stream has no index view).
    resident_data: bool = True
    resident_data_max_bytes: int = 2_000_000_000
    # Multi-host runs agree on the preemption flag every this many steps
    # (a host-level allgather over DCN): under synchronous SPMD no process
    # may leave the step loop alone or the peers hang in the next
    # collective. Single-process runs react to the signal immediately.
    preempt_sync_every: int = 10
    # Failure detection: halt at the next metrics boundary when the train
    # loss goes non-finite, WITHOUT checkpointing the poisoned state (the
    # last good checkpoint stays the resume point). Off by default —
    # faithful-mode parity runs NaN by reference hyperparameter design
    # (LR 0.1 on raw 0-255 pixels) and must keep running like the
    # reference does.
    check_numerics: bool = False
    # What a check_numerics detection DOES (docs/RESILIENCE.md):
    # "halt" raises without checkpointing the poisoned state (the
    # original behavior); "skip" discards every update since the last
    # finite metrics boundary (a device-side snapshot kept at each
    # finite boundary) and keeps training forward; "rollback" raises a
    # classified failure the run supervisor (train/supervisor.py)
    # answers by restoring the last good checkpoint, rewinding the
    # exact-resume data state, and retrying with backoff. skip and
    # rollback share the recovery_retries budget and degrade to halt
    # when it is exhausted.
    on_nonfinite: str = "halt"            # halt | skip | rollback
    # Shared recovery budget: max skip events inside one fit() AND max
    # supervisor restart attempts across a run. Exhausted => halt.
    recovery_retries: int = 3
    # Supervisor restart backoff: base * 2^(attempt-1), capped.
    recovery_backoff_s: float = 0.5
    recovery_backoff_max_s: float = 30.0
    # Progress-based retry-budget reset: when > 0 and the newest
    # checkpoint has advanced by at least this many steps since the
    # budget was last charged, the supervisor's attempt counter resets
    # to 0 before the next failure is judged — long runs absorbing many
    # WELL-SPACED faults keep recovering, while a fault burst still
    # exhausts the budget and degrades to halt. 0 (default) keeps the
    # historical lifetime budget.
    retry_budget_window: int = 0
    # LR multiplier applied at each supervisor rollback of a non-finite
    # failure (1.0 = keep the configured LR). A deterministically
    # diverging run needs the step size reduced, not just replayed.
    rollback_lr_scale: float = 1.0
    # Deterministic fault injection (utils/faults.py):
    # "kind@step,..." with kinds nan | ckpt_corrupt | sigterm |
    # data_stall — each fires once at the first dispatch seam at/after
    # its step. Test/drill tooling; None disables.
    fault_spec: Optional[str] = None
    # Wrap fit() in the run supervisor (train/supervisor.py): classified
    # recoverable failures restore the last verified checkpoint and
    # resume instead of killing the run. Per-process scope — multi-host
    # whole-job restarts stay the scheduler's job.
    supervise: bool = False
    # Persistent compilation cache + AOT warm-start (compilecache/;
    # docs/COMPILECACHE.md). A directory holding cached programs keyed
    # by (StableHLO hash, mesh, shardings, donation, compute dtype,
    # jax/backend version): supervisor restarts, elastic re-entries,
    # and bench/serve warmups warm-start instead of recompiling —
    # time-to-first-step after a fault drops from the compile cost to a
    # disk load (jax's native persistent cache under <dir>/xla carries
    # the warm start; raw executable deserialization is opt-in per
    # backend). Fail-open: a corrupt/unwritable cache degrades to plain
    # recompiles, never to a failed run. None = off (every seam
    # compiles exactly as before).
    compile_cache_dir: Optional[str] = None
    # LRU size bound for the cache directory, applied after each store.
    compile_cache_max_bytes: int = 2_000_000_000
    metrics_jsonl: Optional[str] = None   # structured metrics sink
    # Alert-triggered flight recorder (utils/flightrec.py;
    # docs/OBSERVABILITY.md flight-recorder section). postmortem_dir
    # arms it: a bounded in-memory ring of the last flightrec_size
    # records (fed from the logger's observer hook — zero new
    # instrumentation) is snapshotted into an atomic post-mortem
    # bundle directory whenever a streaming alert FIRES, one bundle
    # per firing (suppressed re-fires capture nothing). Training
    # captures also arm a one-shot devprof window. None = off.
    postmortem_dir: Optional[str] = None
    flightrec_size: int = 256
    # Live metrics export (utils/metrics_registry.py;
    # docs/OBSERVABILITY.md "Live metrics"): serve `GET /metrics`
    # (Prometheus text exposition of the process-local registry) from a
    # lightweight stats-HTTP thread — the trainer's only HTTP surface.
    # 0 = off (default). `--mode serve` and the fleet router expose
    # /metrics on their existing HTTP servers instead.
    stats_port: int = 0
    # Custom streaming alert rules (utils/alerts.py grammar) layered
    # over the built-in defaults: ";"-separated
    # "name=expr[@window][!severity]" where expr is
    # "kind.field OP value" (threshold over consecutive records),
    # "rate(kind[.field=value]) >= N" (trailing step/second window),
    # or "absent(kind)" (no record for @Ns). Firing emits rate-limited
    # `alert` / `alert_resolved` JSONL records. None = built-ins only.
    alert_rules: Optional[str] = None
    # Run-health telemetry (utils/telemetry.py): host-loop span tracing
    # (compile, data wait, dispatch, drain, eval, checkpoint, preemption
    # sync), cumulative goodput fractions, and HBM snapshots — all riding
    # the JSONL stream at the existing metrics boundaries, zero extra
    # device fetches. Off by default: the span context managers then
    # reduce to a shared no-op.
    telemetry: bool = False
    # Training-health scalars compiled INTO the step (parallel/step.py):
    # global grad norm, param norm, update ratio — they ride the fused
    # boundary fetch (no extra round trips) into the train JSONL records.
    health_metrics: bool = False
    # Per-chip peak TFLOP/s for the MFU metric (e.g. ~49 fp32 / 197 bf16
    # on v5e). None logs achieved TFLOP/s only.
    peak_tflops: Optional[float] = None
    # TensorBoard event-file dir (chief only) — the MTS wrote summaries to
    # --log_dir by default (cifar10cnn.py:222); opt-in here.
    tensorboard_dir: Optional[str] = None
    seed: int = 0
    profile_dir: Optional[str] = None     # jax.profiler trace output
    # Device-time attribution window (utils/devprof.py): "N:K" captures
    # a programmatic jax.profiler trace from global step N for K steps
    # (stopping at the next DRAINED metrics boundary so the window
    # closes on quiesced devices), parses it host-side, and emits
    # per-op/per-lane `devtime` JSONL records (top-k ops, compute vs
    # collective vs infeed buckets). Writes under --profile_dir when
    # set, else <log_dir>/devprof. None = off. Unlike --profile_dir
    # alone (whole-run capture, UI analysis), this is a bounded window
    # with the analysis built in.
    profile_at_steps: Optional[str] = None

    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    model: ModelConfig = dataclasses.field(default_factory=ModelConfig)
    optim: OptimConfig = dataclasses.field(default_factory=OptimConfig)
    parallel: ParallelConfig = dataclasses.field(default_factory=ParallelConfig)
    serve: ServeConfig = dataclasses.field(default_factory=ServeConfig)
    fleet: FleetConfig = dataclasses.field(default_factory=FleetConfig)
    runtime: RuntimeConfig = dataclasses.field(default_factory=RuntimeConfig)
    autopilot: AutopilotConfig = dataclasses.field(
        default_factory=AutopilotConfig)


#: TrainConfig's nested dataclass fields, the single list the JSON
#: round-trip below and any future config tooling derive from.
_SUBCONFIGS = {"data": DataConfig, "model": ModelConfig,
               "optim": OptimConfig, "parallel": ParallelConfig,
               "serve": ServeConfig, "fleet": FleetConfig,
               "runtime": RuntimeConfig, "autopilot": AutopilotConfig}


def config_to_dict(cfg: TrainConfig) -> dict:
    """Plain-JSON-serializable dict of the full config tree. The fleet
    controller ships worker configs through this (one file, no CLI
    re-marshalling); ``config_from_dict`` inverts it."""
    return dataclasses.asdict(cfg)


def config_from_dict(d: dict) -> TrainConfig:
    """Rebuild a :class:`TrainConfig` from :func:`config_to_dict`
    output. Unknown keys fail loudly (a version-skewed worker must not
    silently drop a knob it was asked to honor)."""
    kw = {}
    for k, v in d.items():
        if k in _SUBCONFIGS:
            kw[k] = _SUBCONFIGS[k](**v)
        else:
            kw[k] = v
    cfg = TrainConfig(**kw)
    # JSON has no tuples; restore the fields typed as such.
    cfg.serve.buckets = tuple(cfg.serve.buckets)
    return cfg


def reference_config(**overrides) -> TrainConfig:
    """The exact reference hyperparameters (faithful quirks on)."""
    cfg = TrainConfig()
    for k, v in overrides.items():
        if not hasattr(cfg, k):
            raise AttributeError(f"unknown TrainConfig field {k!r}")
        setattr(cfg, k, v)
    return cfg


def fixed_config(**overrides) -> TrainConfig:
    """Reference hyperparameters with the quirks fixed (sane defaults)."""
    cfg = reference_config(**overrides)
    cfg.model.logit_relu = False
    cfg.optim.dead_lr_decay = False
    cfg.data.random_crop = True
    cfg.data.random_flip = True
    cfg.data.normalize = "standardize"
    cfg.eval_full_test_set = True
    return cfg

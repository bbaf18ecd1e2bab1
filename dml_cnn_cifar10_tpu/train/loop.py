"""The training driver.

Replaces the reference's worker branch (``cifar10cnn.py:193-242``): graph
construction becomes building the jitted SPMD step; MonitoredTrainingSession
becomes explicit restore-if-present + periodic checkpointing +
stop-at-step; the queue runners become the prefetching pipeline. Console
cadence is parity: the training line every ``output_every`` (200) local
steps, an eval line every ``eval_every`` (500) (``cifar10cnn.py:232-241``).

Faithful-mode details mirrored deliberately:
- Train accuracy at the 200-step mark is computed on a *fresh* train batch
  (the reference reruns ``accuracy_train``, pulling a new batch from the
  queue — ``cifar10cnn.py:235``), not the batch just trained on.
- Eval is one *shuffled* test batch (``cifar10cnn.py:202,238``);
  ``eval_full_test_set=True`` sweeps the whole split instead.
- The stop condition is the *global* step, like ``StopAtStepHook``
  (``cifar10cnn.py:219``), so restore + finish works.
"""

from __future__ import annotations

import contextlib
import dataclasses
import sys
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from dml_cnn_cifar10_tpu import ckpt as ckpt_lib
from dml_cnn_cifar10_tpu import compilecache
from dml_cnn_cifar10_tpu.ckpt import peerstore as peerstore_lib
from dml_cnn_cifar10_tpu.config import TrainConfig
from dml_cnn_cifar10_tpu.data import pipeline as pipe
from dml_cnn_cifar10_tpu.models.registry import get_model
from dml_cnn_cifar10_tpu.parallel import cluster as cluster_lib
from dml_cnn_cifar10_tpu.parallel import mesh as mesh_lib
from dml_cnn_cifar10_tpu.parallel import multihost
from dml_cnn_cifar10_tpu.parallel import shardings as shardings_lib
from dml_cnn_cifar10_tpu.parallel import step as step_lib
from dml_cnn_cifar10_tpu.utils import alerts as alerts_lib
from dml_cnn_cifar10_tpu.utils import devprof as devprof_lib
from dml_cnn_cifar10_tpu.utils import faults as faults_lib
from dml_cnn_cifar10_tpu.utils import metrics_registry
from dml_cnn_cifar10_tpu.utils import telemetry as telemetry_lib
from dml_cnn_cifar10_tpu.utils.logging import MetricsLogger
from dml_cnn_cifar10_tpu.utils.preemption import PreemptionGuard
from dml_cnn_cifar10_tpu.utils.profiling import (DrainMeter, abstractify,
                                                 compiled_flops,
                                                 compiled_with_flops,
                                                 correct_stack_flops,
                                                 metadata_keyed_compiles,
                                                 profile_trace)


@dataclasses.dataclass
class TrainResult:
    final_step: int
    train_loss: list
    test_accuracy: list
    images_per_sec: float
    state: step_lib.TrainState
    preempted: bool = False


class Trainer:
    def __init__(self, cfg: TrainConfig, mesh=None, task_index: int = 0,
                 fault_injector=None, cluster=None, alert_engine=None,
                 flight_recorder=None, logger=None, publish_hook=None,
                 autopilot=None):
        # Host telemetry (utils/telemetry.py): ONE span stream for the
        # object's life — its own making (`trainer_init`),
        # `init_or_restore`, then every `fit` from set-up to teardown with
        # the FLOP-probe thread and the garbage collector — emitted at the
        # existing metrics boundaries with zero extra device fetches.
        # Disabled, a span is a shared no-op context manager and nothing
        # else exists.
        self._tracer = telemetry_lib.SpanTracer(enabled=cfg.telemetry)
        with self._tracer.span("trainer_init"):
            self._build(cfg, mesh, task_index, fault_injector, cluster,
                        alert_engine, flight_recorder, logger, publish_hook,
                        autopilot)

    def _build(self, cfg, mesh, task_index, fault_injector, cluster,
               alert_engine, flight_recorder, logger, publish_hook,
               autopilot):
        self.cfg = cfg
        self.task_index = task_index
        # Alert-driven remediation (autopilot/engine.py): injected by
        # the supervisor/runtime only — a restart request needs a
        # supervisor above this Trainer to catch it, so a bare Trainer
        # never builds its own engine.
        self.autopilot = autopilot
        if cfg.on_nonfinite not in ("halt", "skip", "rollback"):
            raise ValueError(
                f"on_nonfinite={cfg.on_nonfinite!r} must be one of "
                f"halt | skip | rollback")
        # Deterministic fault injection (utils/faults.py). The supervisor
        # passes ONE injector across restart attempts so fired events
        # stay fired; a bare Trainer builds its own from the config.
        self.faults = fault_injector if fault_injector is not None \
            else faults_lib.FaultInjector.from_spec(cfg.fault_spec)
        self.mesh = mesh if mesh is not None else mesh_lib.build_mesh(
            cfg.parallel)
        self.model_def = get_model(cfg.model.name)
        # Logger before the step builders: the compile cache logs a
        # `compile` JSONL event at every seam, including the ones armed
        # below. The runtime (runtime/core.py) injects ITS logger so a
        # whole process shares one stream; an injected logger is never
        # closed here — its owner closes it.
        self.logger = logger if logger is not None else MetricsLogger(
            cfg.metrics_jsonl, task_index=task_index,
            tensorboard_dir=(cfg.tensorboard_dir
                             if jax.process_index() == 0 else None))
        # In-process publish hook (runtime/core.py): called as
        # ``hook(step, path, params, model_state)`` after a checkpoint
        # COMMITS, with an independent device-side copy of the weights a
        # server would restore from that checkpoint (EMA when armed).
        # Copies, never references: step buffers are donated, so handing
        # out the live pytree would dangle at the next dispatch. The
        # copy is device-to-device — zero jax.device_get, the
        # fetch-parity invariant holds.
        self._publish_hook = publish_hook
        # Live operational observability (docs/OBSERVABILITY.md): the
        # streaming alert engine watches every record this logger
        # writes (built-in SLO rules + --alert_rules), and --stats_port
        # serves GET /metrics from the process registry the same
        # records feed. The supervisor passes ONE engine across restart
        # attempts — alert state (an un-resolved nonfinite burst) must
        # survive the Trainer that detected it; a bare Trainer builds
        # its own. Both are pure host work: the fetch-parity test pins
        # zero extra device fetches.
        # Flight recorder BEFORE the alert observer (attach order is
        # run order): the record that trips a rule must reach the ring
        # before the engine's nested `alert` emission triggers the
        # capture. Like the alert engine, the supervisor passes ONE
        # recorder across restart attempts; a bare Trainer builds its
        # own (armed only by --postmortem_dir).
        from dml_cnn_cifar10_tpu.utils.flightrec import FlightRecorder
        self.flightrec = flight_recorder if flight_recorder is not None \
            else FlightRecorder.from_config(cfg, logger=self.logger)
        if self.flightrec is not None:
            self.flightrec.logger = self.logger
            self.logger.add_observer(self.flightrec.observer())
        self.alerts = alert_engine if alert_engine is not None \
            else alerts_lib.AlertEngine.from_config(cfg)
        if self.alerts is not None:
            self.logger.add_observer(self.alerts.observer(self.logger))
        metrics_registry.ensure_stats_server(cfg.stats_port)
        # Persistent compilation cache (compilecache/): every compile
        # seam this Trainer builds — train step/chunk, init, eval —
        # routes through it when --compile_cache_dir is set, so a
        # supervisor restart or elastic re-entry deserializes the
        # executables its predecessor compiled instead of recompiling.
        # The on_event hook feeds obtain-time into the goodput `compile`
        # fraction (of the fit that is running: its clock starts anew).
        self.compile_cache = compilecache.CompileCache.from_config(
            cfg, logger=self.logger, on_event=self._note_compile_event)
        # One sharding tree, computed once, used everywhere state is placed
        # (init, restore, train/eval in_shardings). The explicit-collectives
        # path is dp-only and expects replicated state.
        if cfg.parallel.explicit_collectives and cfg.parallel.fsdp:
            raise ValueError(
                "fsdp needs the GSPMD (default) step: the "
                "explicit_collectives shard_map path expects replicated "
                "state")
        zero1 = cfg.optim.optimizer_sharding == "zero1"
        if zero1 and cfg.parallel.fsdp:
            raise ValueError(
                "optimizer_sharding=zero1 does not compose with --fsdp: "
                "ZeRO-3 already shards the optimizer moments (and the "
                "params) over the data axis")
        # Partition-rule override (--partition_rules): parsed once, used
        # by every sharding-tree/step build below so the layouts agree.
        self.partition_rules = shardings_lib.parse_partition_rules(
            cfg.parallel.partition_rules)
        self.state_sharding = None if cfg.parallel.explicit_collectives \
            else step_lib.train_state_shardings(
                self.mesh, self.model_def, cfg.model, cfg.data, cfg.optim,
                fsdp=cfg.parallel.fsdp, zero1=zero1,
                rules=self.partition_rules,
                strict=cfg.parallel.partition_rules_strict)
        if cfg.parallel.partition_report and jax.process_index() == 0:
            # The which-rule-matched-which-param report, over the same
            # abstract params the sharding tree was computed from.
            abstract = jax.eval_shape(
                lambda k: step_lib.init_train_state(
                    k, self.model_def, cfg.model, cfg.data, cfg.optim),
                jax.random.key(0))
            table = self.partition_rules if self.partition_rules \
                is not None else shardings_lib.rule_for(
                    cfg.model.name,
                    pipe=self.mesh.shape.get("pipe", 1) > 1)
            print("[shardings] partition report (params):")
            print(shardings_lib.format_partition_report(
                shardings_lib.explain_partition_rules(table,
                                                      abstract.params)))
        self.train_step = step_lib.make_train_step(
            self.model_def, cfg.model, cfg.optim, self.mesh,
            explicit_collectives=cfg.parallel.explicit_collectives,
            state_sharding=self.state_sharding,
            health_metrics=cfg.health_metrics,
            compile_cache=self.compile_cache,
            rules=self.partition_rules)
        self.steps_per_dispatch = max(1, cfg.steps_per_dispatch)
        if self.steps_per_dispatch > 1:
            k = self.steps_per_dispatch
            # total_steps is validated in fit() against the actual resume
            # point (fit can override it).
            for name in ("output_every", "eval_every", "checkpoint_every"):
                if getattr(cfg, name) % k:
                    raise ValueError(
                        f"{name}={getattr(cfg, name)} must be a multiple "
                        f"of steps_per_dispatch={k} so every observable "
                        f"boundary lands on a dispatch edge")
            if cfg.parallel.explicit_collectives:
                raise ValueError(
                    "steps_per_dispatch > 1 needs the GSPMD (default) "
                    "step, not explicit_collectives")
            self.train_chunk = step_lib.make_train_chunk(
                self.model_def, cfg.model, cfg.optim, self.mesh,
                state_sharding=self.state_sharding, data_cfg=cfg.data,
                health_metrics=cfg.health_metrics,
                compile_cache=self.compile_cache,
                rules=self.partition_rules)
        self.eval_step = step_lib.make_eval_step(
            self.model_def, cfg.model, self.mesh,
            state_sharding=self.state_sharding,
            compile_cache=self.compile_cache)
        # Cluster-resilience monitor (parallel/cluster.py): heartbeats,
        # collective watchdog, eviction checks at the dispatch seam.
        # The supervisor passes ONE monitor across restart attempts
        # (epoch/world state must survive them); a bare Trainer builds
        # its own from the config and owns its lifecycle.
        self._owns_cluster = cluster is None \
            and cfg.parallel.cluster_dir is not None
        self.cluster = cluster if cluster is not None \
            else cluster_lib.ClusterMonitor.from_config(
                cfg.parallel, logger=self.logger)
        # Resident-eval fns; built per-fit when the resident path is active.
        self._resident_full_eval = None
        self._resident_test_eval = None
        self._resident_acc_eval = None
        self._idx1_sharding = None
        self._resident_idx = None

    def _note_compile_event(self, ev: dict) -> None:
        """Compile-cache event hook: attribute obtain time (trace +
        load-or-compile) to the goodput `compile` fraction. Pre-loop
        compiles (init, before a fit starts its goodput clock anew) are
        logged as JSONL events but not attributed."""
        self._tracer.add_secs("compile", ev.get("compile_s") or 0.0)

    def _register_scope_maps(self, compiled, state_abs, out_dir,
                             step: int) -> None:
        """Instruction-to-layer maps (utils/devprof.scope_map) of the
        dispatch this fit runs, from the executable the FLOP probe just
        compiled, and of the boundary's resident accuracy program where
        there is one (a forward pass: its own, small compile). Telemetry
        only, on the probe thread, fail-open: a map that cannot be built
        costs a warning, never the run."""
        try:
            if compiled is not None and hasattr(compiled, "as_text"):
                devprof_lib.register_scope_map(
                    compiled, out_dir, logger=self.logger, step=step)
            acc = self._resident_acc_eval
            if acc is not None and hasattr(acc, "lower"):
                idx = jax.ShapeDtypeStruct((self.cfg.batch_size,), jnp.int32,
                                           sharding=self._idx1_sharding)
                with metadata_keyed_compiles():
                    acc_compiled = acc.lower(state_abs, idx).compile()
                devprof_lib.register_scope_map(
                    acc_compiled, out_dir, logger=self.logger, step=step)
        except Exception as e:
            print(f"[devprof] scope map not built: {e!r}", file=sys.stderr)

    def init_or_restore(self) -> step_lib.TrainState:
        with self._tracer.span("init_or_restore"):
            return self._init_or_restore()

    def _init_or_restore(self) -> step_lib.TrainState:
        key = jax.random.key(self.cfg.seed)
        sharding = self.state_sharding if self.state_sharding is not None \
            else mesh_lib.replicated(self.mesh)
        state = step_lib.init_train_state(
            key, self.model_def, self.cfg.model, self.cfg.data,
            self.cfg.optim, self.mesh, state_sharding=sharding,
            compile_cache=self.compile_cache)

        def note_fallback(step, path, reason, walk_ms=None):
            # A skipped candidate during the newest-verifiable walk
            # (ckpt/checkpoint.py) — surfaced in the JSONL stream so a
            # restart that silently lost a checkpoint interval is
            # visible after the fact. walk_ms is the wall-clock spent
            # in the walk so far (--restore_deadline_s budgets it).
            self.logger.log("ckpt_fallback", step=step, path=path,
                            error=str(reason), walk_ms=walk_ms)

        if self.faults is not None:
            # Recovery-phase injection seam (utils/faults.py): a
            # `kind@restore` fault strikes here, right before the
            # restore walk reads anything — e.g. ckpt_corrupt@restore
            # corrupts the newest checkpoint at the exact moment a
            # recovery tries to restore it. Gated inside the injector
            # to RECOVERY restores (the supervisor arms it); a fresh
            # run's initial restore never fires.
            self.faults.phase_hook("restore", self.cfg.log_dir,
                                   logger=self.logger,
                                   cluster=self.cluster)

        restored = self._restore_from_peers(state, sharding)
        if restored is not None:
            return restored

        return ckpt_lib.restore_checkpoint(
            self.cfg.log_dir, state, sharding=sharding,
            on_fallback=note_fallback,
            shard_io_threads=self.cfg.shard_io_threads,
            logger=self.logger,
            deadline_s=self.cfg.restore_deadline_s)

    def _restore_from_peers(self, state, sharding):
        """Diskless restore (ckpt/peerstore.py): when the adopted
        restart decision says ``source="peer"``, rebuild the state from
        the survivors' in-memory payloads plus the lost hosts' replicas
        — zero checkpoint reads. Any classified miss (replica missing,
        stale, or corrupt) logs an explicit ``peer_replica`` fallback
        record and returns None, so the caller runs the unchanged disk
        walk. None also when no peer-sourced decision is pending."""
        cluster = self.cluster
        if cluster is None or cluster.peer_store is None:
            return None
        pending = cluster.take_peer_restore()
        if pending is None:
            return None
        decision, world, lost = pending
        store = cluster.peer_store
        from dml_cnn_cifar10_tpu.ckpt.checkpoint import _logger_on_event
        on_event = _logger_on_event(self.logger)
        try:
            restored = store.restore(state, decision.restore_step,
                                     world, lost=lost,
                                     on_event=on_event)
        except peerstore_lib.ReplicaMiss as e:
            cluster.log("peer_replica", op="fallback",
                        step=decision.restore_step, owner=None,
                        bytes=None, secs=None, ok=False,
                        error=str(e)[:300], staleness=None)
            print(f"[ckpt] peer restore at step "
                  f"{decision.restore_step} not servable ({e}); "
                  f"falling back to the disk restore walk",
                  file=sys.stderr)
            return None
        if sharding is not None:
            restored = jax.device_put(restored, sharding)
        print(f"[ckpt] restored step {decision.restore_step} from peer "
              f"replicas (zero checkpoint reads)")
        return restored

    def _placed(self, batch: pipe.Batch):
        return mesh_lib.shard_batch(
            self.mesh, batch.images, batch.labels,
            spatial=mesh_lib.spatial_enabled(self.model_def, self.mesh))

    def evaluate(self, state, test_it: pipe.ShuffleBatchIterator) -> float:
        """Faithful: accuracy on ONE shuffled test batch
        (``cifar10cnn.py:202,238``); fixed: full-split sweep.

        On the resident path (set up by ``fit``) the whole test split
        lives in HBM and either mode is one dispatch + one fetch. The
        host-fed sweep uses fixed-shape padded batches (pad label -1 ⇒ 0
        correct) so every process issues the same number of collective
        eval steps — correct under any process/shard layout."""
        # (a model that states its own loss has no per-row count to sum
        # over a sweep: its evaluation is one batch's accuracy over tokens)
        if self.cfg.eval_full_test_set and self.model_def.loss is None:
            if self._resident_full_eval is not None:
                fn, total = self._resident_full_eval
                return int(jax.device_get(fn(state))) / max(total, 1)
            # Accumulate the correct-count ON DEVICE across the sweep and
            # fetch once: a per-batch int() fetch is a full host<->device
            # round trip x M batches per eval, and under multi-host it
            # serialized every process on every batch. The adds are async dispatches; the single
            # device_get at the end is the only drain — O(1) fetches
            # under any process count.
            correct = None
            for batch in test_it.full_sweep_padded():
                c = self.eval_step(state, *self._placed(batch))["correct"]
                correct = c if correct is None else correct + c
            if correct is None:
                return 0.0
            return int(jax.device_get(correct)) / max(
                test_it.total_records, 1)
        if self._resident_test_eval is not None:
            idx = self._resident_idx(test_it.next_index_chunk(1)[0])
            return float(jax.device_get(self._resident_test_eval(state,
                                                                 idx)))
        m = self.eval_step(state, *self._placed(next(test_it)))
        return float(m["accuracy"])

    def fit(self, total_steps: Optional[int] = None,
            state: Optional[step_lib.TrainState] = None) -> TrainResult:
        tracer = self._tracer
        tracer.reopen()
        with contextlib.ExitStack() as setup:
            # `_fit` closes the stack where set-up ends; here it only
            # closes the span of a set-up that raised.
            setup.enter_context(tracer.span("fit_setup"))
            try:
                return self._fit(tracer, setup, total_steps, state)
            finally:
                # Spans that finish from here on (the probe thread may
                # outlive a short fit) are logged by whoever finishes them.
                tracer.close(self.logger)

    def _fit(self, tracer, setup, total_steps, state) -> TrainResult:
        cfg = self.cfg
        total_steps = total_steps or cfg.total_steps
        state = state if state is not None else self.init_or_restore()
        start_step = int(jax.device_get(state.step))
        if self.steps_per_dispatch > 1 and \
                (total_steps - start_step) % self.steps_per_dispatch:
            # Covers fit(total_steps=...) overrides and resumes from
            # checkpoints written at non-multiple steps — the loop advances
            # k at a time and must land exactly on the stop step
            # (StopAtStepHook parity, cifar10cnn.py:219).
            raise ValueError(
                f"remaining steps {total_steps - start_step} (stop "
                f"{total_steps}, resume {start_step}) must be a multiple "
                f"of steps_per_dispatch={self.steps_per_dispatch}")

        with tracer.span("build_iterators"):
            num_shards = jax.process_count()
            shard = jax.process_index()
            per_process_batch = cfg.batch_size // num_shards
            # Resident-eval fns are fit-scoped: reset so a prior fit's
            # closures (bound to THAT run's iterators and HBM-pinned splits)
            # can't leak into this one or into standalone evaluate() calls.
            self._resident_full_eval = None
            self._resident_test_eval = None
            self._resident_acc_eval = None
            self._resident_idx = None
            train_data_cfg = cfg.data
            if (self.steps_per_dispatch > 1 and cfg.resident_data
                    and cfg.data.use_native_loader):
                # The HBM-resident path needs the index view only the
                # in-memory permutation iterator provides; the native C++
                # stream would silently force the ~90x-slower host-fed chunk
                # path. Resident wins: build the train iterator non-native.
                train_data_cfg = dataclasses.replace(cfg.data,
                                                     use_native_loader=False)
            train_it = pipe.input_pipeline(
                train_data_cfg, per_process_batch, train=True,
                seed=cfg.seed + shard, shard=shard, num_shards=num_shards)
            # Full-split byte size, computed PROCESS-UNIFORMLY: per-shard
            # nbytes differ when records don't divide evenly, and any
            # size-gated decision below must come out identical on every
            # process or the SPMD programs diverge and the job deadlocks.
            def full_split_bytes(it):
                per_record = int(np.prod(it.images.shape[1:])) \
                    * it.images.dtype.itemsize
                return it.total_records * per_record

            if (train_data_cfg is not cfg.data
                    and full_split_bytes(train_it)
                    > cfg.resident_data_max_bytes):
                # Dataset turned out to exceed the HBM-resident cap: losing
                # the native loader AND the resident path would be strictly
                # worse than doing nothing, so rebuild the native stream.
                train_data_cfg = cfg.data
                train_it = pipe.input_pipeline(
                    train_data_cfg, per_process_batch, train=True,
                    seed=cfg.seed + shard, shard=shard, num_shards=num_shards)
            test_it = pipe.input_pipeline(
                train_data_cfg, per_process_batch, train=False,
                seed=cfg.seed + shard, shard=shard, num_shards=num_shards)
            # Fresh-batch train accuracy (cifar10cnn.py:235) — an independent
            # stream over the same decoded arrays (no second decode).
            acc_it = train_it.clone(seed=cfg.seed + 7 + shard)
            k = self.steps_per_dispatch
            # The resident cap is judged on the FULL split — multi-host
            # replicates the whole dataset into every process's HBM (the
            # host ships only per-process index slices).
            resident = (k > 1 and cfg.resident_data
                        and getattr(train_it, "supports_index_stream", False)
                        and full_split_bytes(train_it)
                        <= cfg.resident_data_max_bytes)
            # Exact-resume data order: fast-forward the fresh streams to the
            # cumulative consumption recorded at the checkpoint being
            # resumed, so interrupted+resumed training is bit-identical to
            # an uninterrupted run (the reference's MTS restart replays the
            # stream from scratch — a documented improvement). Must happen
            # BEFORE the prefetch threads start drawing. Augmentation draws
            # are replayed only on paths whose ``_finish`` makes them: the
            # per-step train stream (k==1) and the host-fed acc stream.
            # Scope: params + stream position are exact at ANY resume step;
            # the metric/eval CADENCE is keyed to the LOCAL step (reference
            # parity, cifar10cnn.py:232), so resuming at a step that is not
            # a cadence multiple (possible only via wall-clock or preemption
            # saves) shifts WHEN eval batches are drawn relative to the
            # uninterrupted run.
            base_counts = {"train": 0, "acc": 0, "test": 0}
            exact_ok = all(getattr(it, "supports_skip", False)
                           for it in (train_it, acc_it, test_it))
            if start_step > 0 and exact_ok:
                prior = ckpt_lib.load_data_state(cfg.log_dir, start_step)
                if prior:
                    base_counts.update({name: int(prior.get(name, 0))
                                        for name in base_counts})
                    train_it.skip_batches(base_counts["train"], aug=(k == 1))
                    acc_it.skip_batches(base_counts["acc"], aug=not resident)
                    test_it.skip_batches(base_counts["test"])
            consumed = {"acc": 0, "test": 0}
        if resident:
            # HBM-resident data path: dataset lives on device, the host
            # ships only shuffled index arrays; gather+decode+K steps are
            # one dispatch (parallel/step.py:make_train_chunk_resident).
            # Multi-host: the FULL split replicates into every process's
            # HBM, each process keeps its disjoint strided index stream
            # (pipeline.py shards records as [shard::num_shards], so
            # local row i is full-split row shard + i*num_shards) and
            # contributes its slice of the global [K, B] index array —
            # the same ~16x win over host-fed chunks as single-host.
            with tracer.span("place_resident"):
                repl = mesh_lib.replicated(self.mesh)
                host_imgs, host_lbls = _full_split_arrays(
                    train_it, lambda: pipe.input_pipeline(
                        train_data_cfg, per_process_batch, train=True,
                        seed=cfg.seed))
                ds_images = mesh_lib.place_local(repl, host_imgs)
                ds_labels = mesh_lib.place_local(repl,
                                                 host_lbls.astype(np.int32))

            with tracer.span("build_step"):
                def to_global(idx):
                    if num_shards > 1:
                        return (shard + idx * num_shards).astype(np.int32)
                    return idx

                # Device-generated index stream: the training dispatch takes
                # ONLY the donated state — no host index generation, no H2D
                # upload, and exact resume is free (the stream position is
                # state.step). Requires the global row space: the full split
                # is replicated in HBM, and the stateless stream emits GLOBAL
                # rows directly (identical on every process by purity).
                dev_stream = cfg.data.device_index_stream
                if dev_stream:
                    # uint32 position domain — refuse runs that would wrap
                    # (data/device_stream.py module docstring).
                    from dml_cnn_cifar10_tpu.data import device_stream
                    device_stream.check_supported_range(cfg.total_steps,
                                                        cfg.batch_size)
                chunk_fn = step_lib.make_train_chunk_resident(
                    self.model_def, cfg.model, cfg.optim, self.mesh,
                    ds_images, ds_labels,
                    state_sharding=self.state_sharding, data_cfg=cfg.data,
                    index_stream=((cfg.data.seed, cfg.batch_size, k)
                                  if dev_stream else None),
                    health_metrics=cfg.health_metrics,
                    compile_cache=self.compile_cache,
                    rules=self.partition_rules)
                idx_sh = mesh_lib.batch_sharding(self.mesh, 2, leading_dims=1)
                # Eval also goes resident: boundary train-accuracy is
                # index-fed from the in-HBM train split, test eval is one
                # dispatch over the in-HBM test split — each boundary costs
                # ONE host↔device round trip instead of a decoded-batch H2D
                # + per-batch fetches.
                self._idx1_sharding = mesh_lib.batch_sharding(self.mesh, 1)
                self._resident_idx = lambda a: mesh_lib.place_local(
                    self._idx1_sharding, to_global(a))
                own_loss = self.model_def.loss is not None
                if not own_loss:
                    # (a model that states its own loss counts what it got
                    # right inside the step: no second forward pass)
                    self._resident_acc_eval = \
                        step_lib.make_batch_eval_resident(
                            self.model_def, cfg.model, self.mesh, ds_images,
                            ds_labels, cfg.data,
                            state_sharding=self.state_sharding,
                            compile_cache=self.compile_cache)
                if cfg.eval_full_test_set and not own_loss:
                    # Multi-host included (round 3): each process contributes
                    # its padded strided shard as its slice of the global
                    # [M, B, ...] arrays; the scan's replicated output is the
                    # GLOBAL correct count — one dispatch + one fetch per
                    # eval on every process (the host-fed fallback cost M
                    # per-batch H2D uploads per eval).
                    self._resident_full_eval = step_lib.make_eval_resident(
                        self.model_def, cfg.model, self.mesh,
                        test_it.images, test_it.labels, cfg.data,
                        state_sharding=self.state_sharding,
                        batch_size=per_process_batch,
                        num_shards=num_shards,
                        total_records=test_it.total_records,
                        expected_batches=test_it.num_padded_sweep_batches(),
                        compile_cache=self.compile_cache)
                else:
                    t_imgs, t_lbls = _full_split_arrays(
                        test_it, lambda: pipe.input_pipeline(
                            train_data_cfg, per_process_batch, train=False,
                            seed=cfg.seed))
                    t_images = mesh_lib.place_local(repl, t_imgs)
                    t_labels = mesh_lib.place_local(repl,
                                                    t_lbls.astype(np.int32))
                    self._resident_test_eval = \
                        step_lib.make_batch_eval_resident(
                            self.model_def, cfg.model, self.mesh, t_images,
                            t_labels, cfg.data,
                            state_sharding=self.state_sharding,
                            compile_cache=self.compile_cache,
                            scope="test_eval")

                if dev_stream:
                    def produce():
                        # The chunk generates its own indices in-graph; a
                        # dispatch has no data arguments at all.
                        return ()
                else:
                    def produce():
                        local = train_it.next_index_chunk(k)
                        return (mesh_lib.place_local(idx_sh,
                                                     to_global(local)),)

                prefetch = pipe.PrefetchIterator(
                    iter(produce, None), depth=cfg.data.prefetch, place=None)
                step_fn = chunk_fn
        elif k > 1:
            # Host-fed chunked path (multi-host, or dataset too big for
            # HBM): the host gathers raw uint8 bytes; decode/augment runs
            # on device inside the compiled chunk (ops/preprocess.py).
            spatial = mesh_lib.spatial_enabled(self.model_def, self.mesh)

            def produce():
                b = train_it.next_raw_chunk(k)
                return mesh_lib.shard_batch(self.mesh, b.images, b.labels,
                                            leading_dims=1, spatial=spatial)

            prefetch = pipe.PrefetchIterator(
                iter(produce, None), depth=cfg.data.prefetch, place=None)
            step_fn = self.train_chunk
        else:
            prefetch = pipe.PrefetchIterator(
                train_it, depth=cfg.data.prefetch, place=self._placed)
            step_fn = self.train_step

        # Set-up ends here, and the goodput clock starts: data, step and
        # resident arrays are built; what follows is the loop's own.
        setup.close()
        tracer.start()
        # Device-time attribution (utils/devprof.py): the always-on
        # step-time estimator rides the existing fused boundary fetch
        # (two clock reads, zero device traffic — the parity test pins
        # it), and --profile_at_steps arms a bounded jax.profiler
        # window whose trace is parsed host-side into `devtime` JSONL.
        dev_est = devprof_lib.DeviceStepEstimator()
        devwin = devprof_lib.ProfileWindow.from_config(cfg,
                                                       logger=self.logger)
        # True when `devwin` was popped from the flight recorder (an
        # alert-armed one-shot) rather than --profile_at_steps: those
        # retire once done so a later capture can arm a fresh window.
        flight_win = False
        # Online train-and-serve (--fleet_publish): every committed
        # checkpoint is published to the fleet's coordination dir so
        # live serve workers hot-swap to it between micro-batches. The
        # hook runs AFTER the integrity sidecar commits (it rides the
        # manager's on_committed seam, writer thread under async_save)
        # because the workers' swap gate requires a verifiable sidecar.
        on_committed = None
        if cfg.fleet.publish:
            from dml_cnn_cifar10_tpu.fleet.publisher import (
                fleet_coord_dir, publish_checkpoint)
            pub_dir = fleet_coord_dir(cfg)

            def on_committed(step, path, _dir=pub_dir):
                publish_checkpoint(_dir, path, step, logger=self.logger)
        # In-process publish (runtime/core.py): guarded_save below parks
        # a device-side copy of the serving weights for each due save;
        # the commit callback hands it to the hook so the publish honors
        # the same commit ordering the fleet publisher does (a failed or
        # skipped save never publishes). Entries are pruned on commit
        # and bounded, so at most a few snapshots are ever live.
        publish_pending: dict = {}
        if self._publish_hook is not None:
            _chained = on_committed

            def on_committed(step, path, _chained=_chained):
                if _chained is not None:
                    _chained(step, path)
                parked = publish_pending.pop(step, None)
                if parked is not None:
                    self._publish_hook(step, path, parked[0], parked[1])
        ckpt_mgr = ckpt_lib.CheckpointManager(
            cfg.log_dir, cfg.checkpoint_every, keep=cfg.keep_checkpoints,
            async_save=cfg.async_checkpoint,
            every_secs=cfg.checkpoint_every_secs, fmt=cfg.ckpt_format,
            logger=self.logger, on_committed=on_committed,
            shard_io_threads=cfg.shard_io_threads)
        train_loss, test_accuracy = [], []
        last_metrics = None
        # on_nonfinite="skip" keeps a device-side snapshot of the last
        # known-finite state, refreshed at every finite metrics boundary;
        # a detection restores it (discarding every update since) and
        # training continues forward. A real buffer copy: step buffers
        # are donated, so holding a reference alone would dangle.
        keep_snapshot = cfg.check_numerics and cfg.on_nonfinite == "skip"
        snapshot = _copy_state(state) if keep_snapshot else None
        skips = {"n": 0}

        def _nonfinite(loss, step):
            """Apply the on_nonfinite policy to a detected non-finite
            loss. halt — and an exhausted skip budget — raises via
            ``_numerics_halt``; rollback logs the classified fault and
            raises for the supervisor; skip returns a fresh copy of the
            snapshot with the step counter advanced to ``step`` (the
            updates are discarded but the steps still happened — data
            consumption, cadences, and checkpoint naming key on it)."""
            if cfg.on_nonfinite == "rollback":
                self.logger.log("fault", step=step, fault="nonfinite",
                                injected=False)
                raise FloatingPointError(
                    f"non-finite train loss ({loss}) at step {step}; "
                    f"raising for supervisor rollback "
                    f"(on_nonfinite=rollback)")
            if cfg.on_nonfinite == "skip" and snapshot is not None \
                    and skips["n"] < cfg.recovery_retries:
                skips["n"] += 1
                self.logger.log("fault", step=step, fault="nonfinite",
                                injected=False)
                self.logger.log("recovery", step=step, fault="nonfinite",
                                action="skip", attempt=skips["n"])
                print(f"[recover] non-finite loss at step {step}: "
                      f"discarding updates since the last finite "
                      f"boundary (skip {skips['n']}/"
                      f"{cfg.recovery_retries})")
                restored = _copy_state(snapshot)
                opt = dict(restored.opt)
                opt["step"] = restored.opt["step"] * 0 + step
                return restored._replace(opt=opt)
            _numerics_halt(loss, step)

        def guarded_save(save_state, step, force=False):
            """ckpt_mgr.maybe_save, but under check_numerics no save may
            persist a non-finite state: the loss of the LAST dispatch is
            fetched (one round trip, only when a save is actually due)
            and a poisoned state follows the on_nonfinite policy —
            halt/rollback raise instead of overwriting the last good
            checkpoint; skip discards the poisoned update and skips this
            save (the next due boundary checkpoints the restored
            state)."""
            nonlocal state, last_metrics
            if not ckpt_mgr.due(step, force):
                # Early out BEFORE opening the checkpoint span: due() is
                # the manager's own save predicate, so a skipped boundary
                # records no span and the telemetry stream only carries
                # checkpoints that actually spent wall-clock.
                return False
            if cfg.check_numerics and last_metrics is not None:
                loss = float(jax.device_get(last_metrics["loss"]))
                if not np.isfinite(loss):
                    state = _nonfinite(loss, step)
                    last_metrics = None
                    return False
            # Sidecar pairing the checkpoint with the streams' cumulative
            # consumption (counts identical on every process under SPMD
            # lockstep). The manager's writer commits it AFTER the
            # checkpoint bytes land — chief-only, ordered even when
            # async — so the pair can never be half-written.
            data_state = {
                "train": base_counts["train"] + (step - start_step),
                "acc": base_counts["acc"] + consumed["acc"],
                "test": base_counts["test"] + consumed["test"],
            } if exact_ok else None
            if self._publish_hook is not None and ckpt_mgr.is_chief:
                # Park the serving weights (EMA when armed — the same
                # selection --mode serve/export restore) BEFORE the save:
                # under async_save the commit callback runs on the writer
                # thread after further steps may have donated the live
                # buffers. jnp.copy is device-side — no fetch.
                pub_params = save_state.opt.get("ema", save_state.params)
                pub_mstate = save_state.opt.get(
                    "ema_mstate", save_state.model_state) \
                    if self.model_def.has_state else None
                publish_pending[step] = (_copy_state(pub_params),
                                         _copy_state(pub_mstate))
                while len(publish_pending) > 4:
                    # A skipped/failed save never commits: drop the
                    # oldest parked snapshot instead of accreting them.
                    publish_pending.pop(min(publish_pending))
            if self.cluster is not None:
                self.cluster.set_phase("checkpoint")
            with tracer.span("checkpoint", cat="checkpoint"):
                saved = ckpt_mgr.maybe_save(save_state, step, force=force,
                                            data_state=data_state)
            if saved and self.cluster is not None:
                store = self.cluster.peer_store
                if store is not None and store.enabled:
                    # Peer redundancy (ckpt/peerstore.py): mirror this
                    # boundary's shard payload to the ring successor.
                    # Collect happens here on the step thread (donated
                    # buffers are not touched off-thread); only the
                    # file push runs in the store's background worker.
                    store.push_state_async(step, save_state)
            return saved

        def _numerics_halt(loss, step):
            self.logger.log("numerics_halt", step=step)
            raise FloatingPointError(
                f"non-finite train loss ({loss}) at step {step}; "
                f"halting without checkpointing the poisoned state "
                f"(check_numerics=True)")

        # FLOPs per dispatch (XLA cost analysis of the compiled step).
        # The AOT lower().compile() the probe needs does NOT share the
        # call-path executable cache — it recompiles (seconds for the
        # chunked step) — so it runs ONCE on a background thread,
        # launched right after the first dispatch; metrics boundaries
        # read the cell non-blockingly and omit the perf keys until it
        # lands ({} = pending, {"flops": 0.0} = probe failed).
        step_abs = None
        flops_cell = {}
        # Exposed for tests/diagnostics: the probe thread posts its result
        # here after fit() may already have returned.
        self._flops_cell = flops_cell
        probe_thread = None
        run_t0 = None  # post-compile wall anchor for the run-average rate
        # Drain-anchored throughput for the metrics stream (see
        # DrainMeter: async dispatch makes host intervals meaningless).
        meter = DrainMeter(cfg.batch_size)

        print("Starting Training")  # parity: cifar10cnn.py:225
        i = 0  # local step, like the reference's `i` (cifar10cnn.py:224)
        global_step = start_step
        stop = False
        # Dispatches between preemption allgathers: ~preempt_sync_every
        # STEPS regardless of chunk size (at least every dispatch).
        sync_stride = max(1, cfg.preempt_sync_every // k)
        n_dispatch = 0
        try:
            # A step-gated capture window owns the profiler when armed;
            # whole-run capture into --profile_dir remains the default.
            with PreemptionGuard() as preempt, profile_trace(
                    cfg.profile_dir if devwin is None else None):
                while global_step < total_steps and not stop:
                    drained = False
                    if devwin is None and cfg.profile_dir is None \
                            and self.flightrec is not None:
                        # An alert capture arms a one-shot post-mortem
                        # window; adopting it as `devwin` lets the
                        # existing stop/close seams drive it. Skipped
                        # whenever --profile_dir or --profile_at_steps
                        # already owns the profiler.
                        devwin = self.flightrec.pop_devprof_window(
                            global_step, logger=self.logger)
                        flight_win = devwin is not None
                    if devwin is not None:
                        devwin.maybe_start(global_step)
                    if self.autopilot is not None:
                        # Autopilot restart seam: a remediation action
                        # that changed the step geometry (shrink) asks
                        # for a restart here, BEFORE the cluster beat
                        # and the data draw — the supervisor restores
                        # the newest checkpoint and rebuilds the step
                        # through the compile cache with the new config.
                        reason = self.autopilot.poll_restart()
                        if reason is not None:
                            from dml_cnn_cifar10_tpu.autopilot.engine \
                                import RemediationRestartError
                            raise RemediationRestartError(reason)
                    if self.cluster is not None:
                        # Dispatch-seam liveness (parallel/cluster.py):
                        # publish a beat, check for eviction, arm the
                        # collective watchdog. Raises PeerLostError when
                        # a peer's heartbeats went stale — determinism
                        # instead of blocking in XLA.
                        self.cluster.begin_step(global_step)
                    if self.faults is not None:
                        # Deterministic fault injection at the host seam
                        # (utils/faults.py): may poison the state, corrupt
                        # the latest checkpoint on disk, deliver SIGTERM,
                        # raise an injected data stall, or fire a cluster
                        # fault (stalled beats / abrupt death / wedged
                        # collective) against the armed watchdog.
                        state = self.faults.step_hook(
                            global_step, state, cfg.log_dir, self.logger,
                            cluster=self.cluster)
                    if self.cluster is not None:
                        # Lockstep simulation barrier (no-op outside the
                        # CPU sim): wait for every live peer to reach
                        # this step, the software stand-in for the XLA
                        # collective a real pod would block in.
                        self.cluster.sync(global_step)
                    first = probe_thread is None
                    with tracer.span("data_wait", cat="data"):
                        try:
                            batch = next(prefetch)
                        except pipe.DataPipelineError:
                            raise
                        except Exception as e:
                            # Classify the data seam: anything that dies
                            # while drawing input is a pipeline failure
                            # the supervisor may restart from the last
                            # checkpoint, not a model bug.
                            raise pipe.DataPipelineError(
                                f"input pipeline failed at step "
                                f"{global_step}: {e!r}") from e
                    if step_abs is None:
                        step_abs = abstractify((state, *batch))
                    # First call traces + compiles before it enqueues
                    # (goodput cat "compile"); steady-state dispatches are
                    # async enqueue — traced but uncategorized, i.e. part
                    # of the productive-train remainder. With the compile
                    # cache armed, the cache's own obtain-time events
                    # carry the compile attribution (via
                    # _note_compile_event) — the span stays uncategorized
                    # so the seconds aren't counted twice.
                    with tracer.span("compile_first_dispatch" if first
                                     else "dispatch",
                                     cat="compile" if first
                                     and self.compile_cache is None
                                     else None):
                        state, metrics = step_fn(state, *batch)
                    if self.cluster is not None:
                        # The dispatch came back: disarm the watchdog.
                        # Boundary work (eval/checkpoint) runs unarmed —
                        # the background publisher keeps this process
                        # looking alive to its peers throughout.
                        self.cluster.end_step(global_step + k)

                    if probe_thread is None:
                        # First dispatch returned ⇒ trace+compile are done
                        # and device execution is only now starting: anchor
                        # the drain meter here so the FIRST boundary
                        # reports a real post-compile rate instead of 0.0.
                        meter.mark(global_step)
                        dev_est.mark(global_step)
                        run_t0 = time.perf_counter()
                        # Where a profiler capture of this run goes, the
                        # instruction-to-layer maps go beside it.
                        scopemap_dir = devwin.out_dir if devwin is not None \
                            else cfg.profile_dir

                        def _probe(fn=step_fn, abs_args=step_abs,
                                   step0=global_step):
                            with tracer.span("flops_probe"):
                                with tracer.span("probe_compile_dispatch"):
                                    f, compiled = compiled_with_flops(
                                        fn, abs_args,
                                        exact_metadata=tracer.enabled)
                                    f = f or 0.0
                                analytic = self.model_def.step_flops
                                if f and analytic is not None:
                                    # Counted from the shapes: XLA counts a
                                    # loop's body once and a kernel not at
                                    # all (models/registry.py).
                                    f = analytic(
                                        cfg.model, cfg.data, cfg.batch_size
                                        // self.mesh.shape.get("data", 1))
                                    flops_cell["stack"] = "from_shapes"
                                elif f and k > 1:
                                    # Verify, don't assume, that this backend
                                    # counts the K-step scan body ONCE: probe
                                    # the scan-free per-step fn too; a
                                    # chunk/step flops ratio near K means the
                                    # scan was unrolled or counted
                                    # per-iteration — scale back by K.
                                    d = cfg.data
                                    img = jax.ShapeDtypeStruct(
                                        (cfg.batch_size, d.crop_height,
                                         d.crop_width, d.num_channels),
                                        jnp.float32)
                                    lab = jax.ShapeDtypeStruct(
                                        (cfg.batch_size,), jnp.int32)
                                    with tracer.span("probe_compile_step"):
                                        f1 = compiled_flops(
                                            self.train_step,
                                            (abs_args[0], img, lab)) or 0.0
                                    if f1 and f >= (1 + k) / 2 * f1:
                                        flops_cell["assume"] = "per_iteration"
                                        f = f / k
                                    elif f1:
                                        flops_cell["assume"] = "scan_once"
                                # Models that scan their LAYER stack (ViT)
                                # also get their scan body counted once —
                                # ~1/depth of the real FLOPs (round-2
                                # verdict weak #4). The model's stack_probe
                                # measures one block standalone: bf_counted
                                # (as the step runs it — Pallas attention is
                                # an opaque custom call counted as 0) and
                                # bf_true (dense-equivalent, fully counted);
                                # correct_stack_flops swaps counted for true
                                # at full depth. Only on pure-data-parallel
                                # meshes: under seq/model/pipe partitioning
                                # the unsharded block probe doesn't match
                                # the per-chip share, so the figure stays
                                # uncorrected and is LABELED as such. The
                                # block probe runs at the PER-CHIP
                                # microbatch (batch / grad_accum / data
                                # axis) to match f's per-device accounting.
                                sp = getattr(self.model_def, "stack_probe",
                                             None)
                                if f and sp is not None:
                                    mesh_shape = dict(self.mesh.shape) \
                                        if self.mesh is not None else {}
                                    ndata = mesh_shape.get("data", 1)
                                    pure_dp = all(
                                        v == 1 for a, v in mesh_shape.items()
                                        if a != "data")
                                    if not pure_dp:
                                        flops_cell["stack"] = (
                                            "uncorrected_model_parallel")
                                    else:
                                        micro = max(1, cfg.batch_size // max(
                                            1, cfg.optim.grad_accum) // ndata)
                                        try:
                                            depth, bfc, bft = sp(
                                                cfg.model, cfg.data, micro)
                                        except Exception:
                                            depth, bfc, bft = 0, None, None
                                        f, flops_cell["stack"] = \
                                            correct_stack_flops(f, depth,
                                                                bfc, bft)
                                        if flops_cell["stack"] == \
                                                "probe_failed":
                                            # Don't publish a known ~1/depth
                                            # undercount as TFLOP/s.
                                            f = 0.0
                                if tracer.enabled:
                                    # From the compile just made (no third
                                    # one), BEFORE the figure is posted:
                                    # whoever waits for the probe finds the
                                    # maps built.
                                    with tracer.span("probe_scope_map"):
                                        self._register_scope_maps(
                                            compiled, abs_args[0],
                                            scopemap_dir, step0)
                                flops_cell["flops"] = f

                        probe_thread = threading.Thread(
                            target=_probe, daemon=True, name="flops-probe")
                        probe_thread.start()
                    last_metrics = metrics
                    global_step += k

                    if (i + k) % cfg.output_every == 0:
                        with tracer.span("boundary_acc_dispatch"):
                            # Fresh-batch train accuracy
                            # (cifar10cnn.py:235), then ONE fused
                            # device->host fetch for loss+accuracy.
                            if self.model_def.loss is not None:
                                # the step's own count of the last batch
                                acc_arr = metrics["accuracy"]
                            elif self._resident_acc_eval is not None:
                                aidx = self._resident_idx(
                                    acc_it.next_index_chunk(1)[0])
                                acc_arr = self._resident_acc_eval(state, aidx)
                            else:
                                acc_arr = self.eval_step(
                                    state, *self._placed(next(acc_it))
                                )["accuracy"]
                            consumed["acc"] += 1
                            # Router health for MoE models (ops/moe.py stats
                            # via parallel/step.py) and the optional
                            # training-health scalars (grad/param norms,
                            # update ratio — health_metrics=True) ride the
                            # SAME fused fetch as loss/accuracy: everything
                            # concatenates into one 1-D f32 array -> one
                            # device->host round trip per boundary (a second
                            # fetch would drain the device queue twice).
                            fused_keys = sorted(
                                mk for mk in metrics
                                if mk.startswith(("moe_", "attn_",
                                                  "health_")))
                            parts = [jnp.reshape(metrics["loss"], (1,)),
                                     jnp.reshape(
                                         jnp.asarray(acc_arr, jnp.float32),
                                         (1,))]
                            parts += [jnp.reshape(metrics[mk], (-1,)).astype(
                                          jnp.float32) for mk in fused_keys]
                        # The fused fetch is a true drain: the host blocks
                        # on device compute, so the span is device-busy
                        # time — traced, but counted as productive. The
                        # two clock reads around it feed the device
                        # step-time estimator (no extra fetches).
                        t_drain0 = time.perf_counter()
                        with tracer.span("boundary_drain"):
                            fused = jax.device_get(
                                jnp.concatenate(parts))
                        t_drain1 = time.perf_counter()
                        with tracer.span("boundary_log"):
                            device_step_ms, drain_wait_ms = dev_est.boundary(
                                global_step, t_drain0, t_drain1)
                            rate = meter.rate(global_step)
                            drained = True
                            loss, acc = float(fused[0]), float(fused[1])
                            train_loss.append(loss)
                            perf = {}
                            off = 2
                            for mk in fused_keys:
                                nleaf = int(np.prod(metrics[mk].shape)) \
                                    if metrics[mk].shape else 1
                                mv = fused[off:off + nleaf]
                                off += nleaf
                                perf[mk] = (round(float(mv[0]), 5)
                                            if nleaf == 1
                                            else [round(float(x), 5)
                                                  for x in mv])
                            flops_probe = flops_cell.get("flops")
                            if flops_probe and rate > 0:
                                # steps/sec x flops/step. XLA cost analysis
                                # reports the PER-DEVICE share of the
                                # partitioned program (already per-chip, no
                                # device_count divide). Whether it counted
                                # the K-step scan body once was VERIFIED by
                                # the probe's chunk-vs-step cross-check
                                # (flops_scan in the metrics records which
                                # case held); grad-accum microbatches scale
                                # back in. Models that scan their layer
                                # stack (ViT) are corrected to full depth
                                # via stack_probe (flops_stack label);
                                # exact for the CNN.
                                tf = (flops_probe
                                      * max(1, cfg.optim.grad_accum)
                                      * (rate / cfg.batch_size) / 1e12)
                                perf["tflops_per_sec_per_chip"] = round(tf, 3)
                                if cfg.peak_tflops:
                                    perf["mfu"] = round(
                                        tf / cfg.peak_tflops, 4)
                            if "assume" in flops_cell:
                                # Logged once, OUTSIDE the rate guard (like
                                # flops_stack below): a 0-rate boundary must
                                # defer the TFLOP/s figure, not silently
                                # swallow the scan-accounting label.
                                perf["flops_scan"] = flops_cell.pop("assume")
                            if "stack" in flops_cell:
                                # Logged once, OUTSIDE the flops>0 guard: the
                                # layer-stack accounting case
                                # (scan_once_x<depth> = corrected;
                                # probe_failed = TFLOP/s withheld;
                                # uncorrected_model_parallel = raw figure,
                                # trust accordingly).
                                perf["flops_stack"] = flops_cell.pop("stack")
                            self.logger.train_print(global_step, i + k - 1,
                                                    acc)
                            # optimizer_ms: per-step device time inside the
                            # step's named_scope("optimizer"), measured by
                            # the last --profile_at_steps window (null until
                            # one completes) — the kernel/sharding win is
                            # attributed, not inferred.
                            self.logger.log("train", step=global_step,
                                            loss=loss, train_accuracy=acc,
                                            images_per_sec=rate,
                                            lr=_current_lr(cfg, global_step),
                                            device_step_ms=device_step_ms,
                                            drain_wait_ms=drain_wait_ms,
                                            optimizer_ms=(
                                                devwin.optimizer_step_ms
                                                if devwin is not None
                                                else None),
                                            **perf)
                            telemetry_lib.flush_boundary(tracer, self.logger,
                                                         global_step,
                                                         alerts=self.alerts)
                        if cfg.check_numerics:
                            # Loss is a replicated metric, so every
                            # process takes the same branch on the same
                            # boundary — no peer hangs.
                            if not np.isfinite(loss):
                                state = _nonfinite(loss, global_step)
                                last_metrics = None
                            elif keep_snapshot:
                                snapshot = _copy_state(state)
                    if (i + k) % cfg.eval_every == 0:
                        if self.cluster is not None:
                            self.cluster.set_phase("eval")
                        with tracer.span("eval", cat="eval"):
                            ta = self.evaluate(state, test_it)
                        if not cfg.eval_full_test_set:
                            # Full sweeps are sequential slices (no
                            # stream draws); single-batch eval consumes
                            # one shuffled test batch.
                            consumed["test"] += 1
                        test_accuracy.append(ta)
                        self.logger.eval_print(ta)
                        self.logger.log("eval", step=global_step,
                                        test_accuracy=ta)
                        drained = True
                    if guarded_save(state, global_step):
                        drained = True
                    i += k
                    n_dispatch += 1
                    # Preemption: a single process reacts immediately; a
                    # multi-host job must AGREE first — under synchronous SPMD
                    # no process may leave the step loop alone (its peers would
                    # hang in the next collective), so the flag is allgathered
                    # at a shared dispatch boundary and every process exits on
                    # the same iteration.
                    if num_shards == 1:
                        stop = preempt.requested
                        # Wall-clock checkpoint cadence (MTS parity: the
                        # reference's MonitoredTrainingSession saved every
                        # 600 s by default, cifar10cnn.py:222).
                        if ckpt_mgr.time_due():
                            if guarded_save(state, global_step, force=True):
                                drained = True
                    elif n_dispatch % sync_stride == 0:
                        from jax.experimental import multihost_utils
                        # One DCN allgather carries both flags: no process may
                        # leave the loop OR enter the collective checkpoint
                        # fetch alone.
                        with tracer.span("preempt_allgather", cat="sync"):
                            flags = multihost_utils.process_allgather(
                                np.asarray([preempt.requested,
                                            ckpt_mgr.time_due()]))
                        stop = bool(np.asarray(flags)[..., 0].any())
                        if bool(np.asarray(flags)[..., 1].any()):
                            if guarded_save(state, global_step, force=True):
                                drained = True
                    if drained:
                        # End-of-iteration mark: the next rate window
                        # starts AFTER this iteration's eval/checkpoint
                        # work, so only training dispatches are timed.
                        meter.mark(global_step)
                        dev_est.mark(global_step)
                    if devwin is not None:
                        # The capture stops only at a drained boundary
                        # at/after its stop step — quiesced devices, no
                        # truncated in-flight dispatches.
                        devwin.maybe_stop(global_step, drained=drained)
                        if flight_win and devwin.state == "done":
                            devwin = None
                            flight_win = False

                # Final save covers both normal completion and preemption: the
                # in-flight step finished, so the checkpoint loses zero work.
                # It runs INSIDE the guard so a second signal during the
                # write (Ctrl-C twice, pool re-sending SIGTERM) can't kill the
                # process before the atomic rename lands.
                # Run-average throughput over the post-compile window,
                # drain-anchored: fetch one scalar of the LAST dispatch
                # (waits for everything before it) and read the clock
                # BEFORE the final checkpoint save — a host-interval
                # enqueue rate would be garbage on the chunked path, and
                # including the final save would charge checkpoint IO
                # against training throughput.
                avg_rate = 0.0
                if run_t0 is not None and global_step > start_step:
                    jax.device_get(last_metrics["loss"])
                    avg_rate = ((global_step - start_step) * cfg.batch_size
                                / max(time.perf_counter() - run_t0, 1e-9))
                # A preempted NON-CHIEF host does not attempt the drain
                # save: the chief owns the checkpoint decision, and a
                # non-chief writing its own view of step N is how
                # restore races start. It emits a peer_lost-style
                # notice and exits cleanly instead. Gated to the
                # process-local case (jax.process_count() == 1 — the
                # cluster-sim / independent-world layout): in a real
                # jax.distributed world the save is a COLLECTIVE fetch
                # the allgathered stop makes every process enter
                # together, and skipping it on one would hang the rest.
                nonchief_preempt = (stop and num_shards == 1
                                    and not multihost.is_chief(
                                        cfg.parallel))
                if nonchief_preempt:
                    self.logger.log(
                        "peer_lost", step=global_step,
                        process_id=cfg.parallel.process_id,
                        reason="preempt_nonchief_exit")
                    print(f"[preempt] signal {preempt.signum} on "
                          f"non-chief process "
                          f"{cfg.parallel.process_id}: exiting cleanly "
                          f"without saving (chief owns the checkpoint)")
                else:
                    guarded_save(state, global_step, force=True)
                if stop and not nonchief_preempt:
                    print(f"[preempt] signal {preempt.signum}: checkpointed at "
                          f"step {global_step}, exiting cleanly")
                if stop:
                    self.logger.log("preempt", step=global_step,
                                    signum=preempt.signum)
                self.logger.log("done", step=global_step,
                                images_per_sec=avg_rate)
                # Run-end telemetry: the spans finished since the last
                # boundary (final eval/checkpoint included) plus the
                # cumulative goodput breakdown, marked final so
                # tools/telemetry_report.py can anchor on it.
                telemetry_lib.flush_boundary(tracer, self.logger,
                                             global_step, final=True,
                                             alerts=self.alerts)
        finally:
            # Crash paths clean up too: the async checkpoint writer must
            # drain (surfacing any background write error alongside the
            # original exception), the prefetch thread must stop, and
            # tensorboardX's daemon writer dies unflushed at interpreter
            # exit — an OOM/NaN abort is exactly when the last scalars
            # matter.
            with tracer.span("fit_teardown"):
                ckpt_mgr.close()
                prefetch.close()
                # A capture window the run ended (or crashed) inside still
                # stops, parses, and emits its devtime records: the runs that
                # die mid-window are exactly the ones worth attributing.
                if devwin is not None:
                    devwin.close(global_step)
                # A supervisor-owned monitor must keep its threads (and
                # epoch/world state) across fit attempts; only a monitor
                # this Trainer built for itself dies with the fit.
                if self._owns_cluster and self.cluster is not None:
                    self.cluster.close()
            self.logger.flush()
        # Release the fit-scoped resident closures — their partials pin
        # the train/test splits in HBM.
        self._resident_full_eval = None
        self._resident_test_eval = None
        self._resident_acc_eval = None
        return TrainResult(global_step, train_loss, test_accuracy,
                           avg_rate, state, preempted=stop)


def _copy_state(state):
    """Independent buffer copy of a train state (same shardings): the
    on_nonfinite="skip" snapshot must survive the donation of every
    subsequent step's buffers, so a reference is not enough."""
    return jax.tree.map(
        lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x, state)


def _full_split_arrays(it, reload_fn):
    """``(images, labels)`` of the FULL split backing a possibly-sharded
    iterator. A sharded iterator holds strided views
    (``pipeline.py``: ``arr[shard::num_shards]``) whose ``.base`` IS the
    full decoded split in original order — reuse it instead of decoding
    the files a second time (and pinning a second full-split copy in
    host RAM); fall back to a fresh unsharded load if the view structure
    ever stops matching."""
    if it.num_shards == 1:
        return it.images, it.labels
    base_i, base_l = it.images.base, it.labels.base
    n = it.total_records
    if (isinstance(base_i, np.ndarray) and isinstance(base_l, np.ndarray)
            and base_i.shape == (n, *it.images.shape[1:])
            and base_l.shape[:1] == (n,)):
        return base_i, base_l
    full = reload_fn()
    return full.images, full.labels


def _current_lr(cfg: TrainConfig, step: int) -> float:
    """Host-math mirror of ``optim.learning_rate`` for the metrics log —
    a device dispatch + fetch here would cost a full link round trip per
    boundary. ``test_train_math.py`` pins it equal to the jnp version."""
    import math
    o = cfg.optim
    if o.schedule == "exponential":
        e = 0.0 if o.dead_lr_decay else step / o.decay_every
        if o.staircase:
            e = math.floor(e)
        lr = o.learning_rate * o.lr_decay ** e
    elif o.schedule == "cosine":
        horizon = max(o.cosine_decay_steps - o.warmup_steps, 1)
        prog = min(max((step - o.warmup_steps) / horizon, 0.0), 1.0)
        lr = o.learning_rate * 0.5 * (1.0 + math.cos(math.pi * prog))
    else:
        lr = o.learning_rate
    if o.warmup_steps > 0:
        lr *= min((step + 1.0) / o.warmup_steps, 1.0)
    return lr



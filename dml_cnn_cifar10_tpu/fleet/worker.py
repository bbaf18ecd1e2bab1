"""One serve replica of the fleet: engine + batcher + HTTP, plus the
three fleet duties a lone ``--mode serve`` process doesn't have:

1. **Advertise** — publish heartbeats to the fleet dir
   (``HeartbeatStore`` beats with ``extra = {replica_id, version,
   queue_depth, port}``; ``step`` is the completed-request counter).
   Phase ``warmup`` until the HTTP socket is up and every bucket is
   compiled, then ``serve`` — the router only routes to ``serve``.
2. **Hot-swap** — poll the published-version file
   (``fleet/publisher.py``); when ``seq`` advances, restore exactly the
   published checkpoint (integrity-verified,
   ``ckpt.restore_checkpoint_at``) and
   :meth:`~dml_cnn_cifar10_tpu.serve.engine.ServingEngine.try_swap` it
   in between micro-batches. A candidate that fails restore or the
   engine's shape/dtype contract is rejected (``swap_rejected`` JSONL)
   and the old version keeps serving.
3. **Die loudly or drain cleanly** — SIGTERM takes the same
   PreemptionGuard drain as ``--mode serve`` (the autoscaler retires
   replicas with SIGTERM); the ``--worker_fault`` drill hook arms a
   ``utils/faults.py`` kind (``host_lost`` = ``os._exit``, no cleanup)
   after N batch dispatches so the router's evict/re-route path is
   testable on CPU in tier-1.

Spawned by the fleet controller as ``python -m
dml_cnn_cifar10_tpu.fleet.worker <config.json> <replica_id> [fault]``;
its telemetry stream is ``<fleet_dir>/telemetry/replica_<id>.jsonl``
(serve windows, compile events, swap events) — the same files the
autoscaler reads its signals from.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from http.server import ThreadingHTTPServer
from typing import Optional

from dml_cnn_cifar10_tpu.fleet import publisher as publisher_lib
from dml_cnn_cifar10_tpu.parallel.cluster import HeartbeatStore
from dml_cnn_cifar10_tpu.serve.batcher import MicroBatcher
from dml_cnn_cifar10_tpu.serve.cache import ResponseCache
from dml_cnn_cifar10_tpu.serve.metrics import ServeMetrics
from dml_cnn_cifar10_tpu.serve.server import _make_handler, _MetricsFlusher


def replica_jsonl_path(fleet_dir: str, replica_id: int) -> str:
    return os.path.join(fleet_dir, "telemetry",
                        f"replica_{replica_id}.jsonl")


class _FaultingEngine:
    """Engine proxy arming one ``utils/faults.py`` kind at the Nth
    TRAFFIC dispatch (warmup forwards go through the real engine and
    don't count). The fleet analogue of the trainer's ``--fault_spec``
    seam — how tier-1 kills a worker mid-load without mocking."""

    def __init__(self, engine, kind: str, at_n: int, on_stall=None):
        self._engine = engine
        self._kind = kind
        self._at_n = int(at_n)
        self._n = 0
        self._fired = False
        self._on_stall = on_stall

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def forward_timed_versioned(self, batch):
        self._n += 1
        if not self._fired and self._n >= self._at_n:
            self._fired = True
            from dml_cnn_cifar10_tpu.utils.faults import EXIT_HOST_LOST
            print(f"[fleet] replica fault {self._kind} at dispatch "
                  f"{self._n}", flush=True)
            if self._kind == "host_lost":
                os._exit(EXIT_HOST_LOST)
            elif self._kind == "heartbeat_stall" \
                    and self._on_stall is not None:
                self._on_stall()
        return self._engine.forward_timed_versioned(batch)


def _parse_fault(fault: Optional[str]):
    """``"kind@n"`` with kind in {host_lost, heartbeat_stall}."""
    if not fault:
        return None
    kind, sep, n = fault.partition("@")
    if not sep or kind not in ("host_lost", "heartbeat_stall"):
        raise ValueError(f"bad worker fault {fault!r}: want "
                         f"host_lost@N or heartbeat_stall@N")
    return kind, int(n)


class _SwapWatcher(threading.Thread):
    """Poll the published-version file; restore + try_swap on advance.

    The restore target is the worker's own TrainState (structure from
    its first restore), so a published checkpoint from a DIFFERENT
    model config fails restore — which is handled exactly like an
    engine-contract mismatch: ``swap_rejected``, keep serving.

    A record carrying ``quantize="int8"`` is adopted through the quant
    publish gate instead (``quant/convert.gate_and_swap``): recalibrate
    for the restored weights, score int8 vs float top-1 on the holdout,
    and swap only on pass — a failing candidate emits
    ``quant_rejected`` and the current version keeps serving."""

    def __init__(self, fleet_dir: str, engine, trainer, state,
                 poll_s: float, last_seq: int, logger=None,
                 quant_ctx=None):
        super().__init__(name="fleet-swap-watcher", daemon=True)
        self.fleet_dir = fleet_dir
        self.engine = engine
        self.trainer = trainer
        self.state = state
        self.poll_s = poll_s
        self.last_seq = last_seq
        self.logger = logger
        self.quant_ctx = quant_ctx
        self._stop = threading.Event()

    def stop(self) -> None:
        self._stop.set()

    def check_once(self) -> bool:
        """One poll; True when a swap was installed."""
        rec = publisher_lib.read_published(self.fleet_dir)
        if rec is None or rec.seq <= self.last_seq:
            return False
        # Whatever happens below, this seq is handled: a bad candidate
        # must not be retried every poll_s forever.
        self.last_seq = rec.seq
        from dml_cnn_cifar10_tpu.ckpt import checkpoint as ckpt_lib
        try:
            new_state = ckpt_lib.restore_checkpoint_at(rec.path,
                                                       self.state)
        except Exception as e:
            if self.logger is not None:
                self.logger.log("swap_rejected",
                                replica_id=self.engine.replica_id,
                                version=rec.version,
                                reason=f"restore failed: {e}")
            print(f"[fleet] REJECTED published version {rec.version}: "
                  f"restore failed ({e})")
            return False
        self.state = new_state
        params = new_state.opt.get("ema", new_state.params)
        mstate = new_state.opt.get("ema_mstate", new_state.model_state) \
            if self.trainer.model_def.has_state else None
        if getattr(rec, "quantize", None) == "int8":
            if self.quant_ctx is None:
                if self.logger is not None:
                    self.logger.log("swap_rejected",
                                    replica_id=self.engine.replica_id,
                                    version=rec.version,
                                    reason="quantized publish but worker "
                                           "has no int8 program "
                                           "(--serve_quantize unset)")
                print(f"[fleet] REJECTED published version "
                      f"{rec.version}: worker has no int8 program")
                return False
            from dml_cnn_cifar10_tpu.quant.convert import gate_and_swap
            ok, _ = gate_and_swap(self.engine, self.quant_ctx, params,
                                  str(rec.step), logger=self.logger)
            return ok
        ok, _ = self.engine.try_swap(params, mstate, version=rec.version)
        return ok

    def run(self) -> None:
        while not self._stop.wait(self.poll_s):
            try:
                self.check_once()
            except Exception as e:
                print(f"[fleet] swap watcher error: {e!r}")


class _BeatPublisher(threading.Thread):
    """Advertise this replica: liveness + placement signals per beat."""

    def __init__(self, store: HeartbeatStore, batcher, engine,
                 interval_s: float, port_ref: dict, phase_ref: dict,
                 cell: str = "default"):
        super().__init__(name="fleet-beat-publisher", daemon=True)
        self.store = store
        self.batcher = batcher
        self.engine = engine
        self.interval_s = interval_s
        self.port_ref = port_ref
        self.phase_ref = phase_ref
        self.cell = cell
        self._stop = threading.Event()
        self._stalled = False

    def stall(self) -> None:
        """Fault hook: stop beating while serving continues — from the
        router's side, indistinguishable from a dead worker."""
        self._stalled = True

    def stop(self) -> None:
        self._stop.set()

    def beat_once(self) -> None:
        if self._stalled:
            return
        self.store.publish(
            self.batcher.metrics.cumulative()["completed"],
            self.phase_ref["phase"],
            extra={"replica_id": self.store.process_id,
                   "version": self.engine.version,
                   "queue_depth": self.batcher.queue_depth(),
                   # Device-time attribution for the fleet: the router/
                   # autoscaler (and trace_aggregate's request-flow
                   # view) can tell a slow DEVICE from a deep queue.
                   "device_ms": self.batcher.metrics.recent_device_ms(),
                   # Failure domain (--cell): the router prefers a
                   # request's target cell and logs the crossing when
                   # it must fail over out of it.
                   "cell": self.cell,
                   "port": self.port_ref.get("port")})

    def run(self) -> None:
        self.beat_once()
        while not self._stop.wait(self.interval_s):
            self.beat_once()


def main_worker(cfg, replica_id: int, fault: Optional[str] = None,
                ready_event: Optional[threading.Event] = None,
                stop_event: Optional[threading.Event] = None) -> int:
    """Blocking worker loop (the fleet's ``main_serve`` analogue)."""
    from dml_cnn_cifar10_tpu.utils.logging import MetricsLogger
    from dml_cnn_cifar10_tpu.utils.preemption import PreemptionGuard

    fleet_dir = publisher_lib.fleet_coord_dir(cfg)
    jsonl = replica_jsonl_path(fleet_dir, replica_id)
    os.makedirs(os.path.dirname(jsonl), exist_ok=True)
    # The replica's whole stream — serve windows, compile events, swap
    # events, and anything the Trainer-based restore logs — goes to one
    # per-replica file; the autoscaler and telemetry_report read these.
    cfg.metrics_jsonl = jsonl
    logger = MetricsLogger(jsonl_path=jsonl, task_index=replica_id)
    # Per-replica streaming alerts (shed / p99-vs-SLO / custom rules):
    # same engine the lone --mode serve path arms, emitting into this
    # replica's stream — which the controller's signal aggregation and
    # the live monitor already tail.
    from dml_cnn_cifar10_tpu.utils import alerts as alerts_lib
    from dml_cnn_cifar10_tpu.utils.flightrec import FlightRecorder
    # Flight recorder first (observers run in attach order — the record
    # that trips an alert must be ringed before the capture fires); the
    # engine doesn't exist yet, so context goes through a holder.
    holder: dict = {}
    flightrec = FlightRecorder.from_config(
        cfg, context_fn=lambda: {
            "active_version": getattr(holder.get("engine"), "version",
                                      None),
            "replica_id": replica_id},
        logger=logger)
    if flightrec is not None:
        logger.add_observer(flightrec.observer())
    alert_engine = alerts_lib.AlertEngine.from_config(cfg)
    if alert_engine is not None:
        logger.add_observer(alert_engine.observer(logger))

    # Engine over the PUBLISHED version when there is one (every
    # replica of a fleet must serve the same weights regardless of
    # spawn order), else the latest checkpoint — structure restored
    # through the Trainer exactly like --mode serve, so fleet outputs
    # pin bit-equal to the single-process path.
    import jax

    from dml_cnn_cifar10_tpu.ckpt import checkpoint as ckpt_lib
    from dml_cnn_cifar10_tpu.serve.engine import ServingEngine
    from dml_cnn_cifar10_tpu.train.loop import Trainer

    trainer = Trainer(cfg, task_index=replica_id)
    state = trainer.init_or_restore()
    published = publisher_lib.read_published(fleet_dir)
    last_seq = 0
    if published is not None:
        if int(jax.device_get(state.step)) != published.step:
            state = ckpt_lib.restore_checkpoint_at(published.path, state)
        last_seq = published.seq
    version = str(int(jax.device_get(state.step)))
    params = state.opt.get("ema", state.params)
    mstate = state.opt.get("ema_mstate", state.model_state) \
        if trainer.model_def.has_state else None
    engine = ServingEngine.from_params(
        trainer.model_def, cfg.model, cfg.data, params, mstate,
        compile_cache=trainer.compile_cache, logger=logger,
        version=version, replica_id=replica_id)
    holder["engine"] = engine

    # Quantized serving (docs/QUANT.md): the engine stays FLOAT-first —
    # it is built over the float weights, then armed with the int8
    # program so try_swap can route either tree shape. Adoption follows
    # the PUBLISHED record: a replica joining a fleet whose current
    # version is quantized gates + swaps before going routable (every
    # replica serves the same variant regardless of spawn order); with
    # nothing quantized published yet it serves float and the watcher
    # gates the first quantized publish like any other. A failed gate
    # means float keeps serving and the version string says so — that
    # is the contract.
    quant_ctx = None
    if cfg.serve.quantize == "int8":
        from dml_cnn_cifar10_tpu.quant.convert import (QuantContext,
                                                       gate_and_swap)
        quant_ctx = QuantContext.build(trainer.model_def, cfg.model,
                                       cfg.data, cfg.serve)
        engine.attach_program(
            "int8", quant_ctx.quant_fn,
            (quant_ctx.quantize(params), None),
            warm_buckets=cfg.serve.buckets)
        if published is not None and \
                getattr(published, "quantize", None) == "int8":
            gate_and_swap(engine, quant_ctx, params, version,
                          logger=logger)

    # Advertise on the fleet's coordination transport. NET mode talks
    # to the controller-hosted CoordServer (parallel/net.py) — bounded
    # timeouts, classified errors, the chaos partition seam; a beat the
    # transport loses is just a beat the router never sees, the same
    # silence a crashed worker produces. FILE mode stays the n=1/test
    # fallback.
    if getattr(cfg.parallel, "cluster_transport", "file") == "net":
        from dml_cnn_cifar10_tpu.parallel import net as net_lib
        net_client = net_lib.CoordClient(
            fleet_dir, replica_id,
            timeout_s=cfg.parallel.net_timeout_s,
            retries=cfg.parallel.net_retries, log_fn=logger.log)
        store = net_lib.NetHeartbeatStore(fleet_dir, replica_id,
                                          net_client, log_fn=logger.log)
    else:
        store = HeartbeatStore(fleet_dir, process_id=replica_id,
                               log_fn=logger.log)
    # Failure-domain assignment is positional — replica i lands in cell
    # i % len(cells) — so a fleet config names its cells once and every
    # spawn (autoscaler included) is deterministically placed.
    cells = [c.strip() for c in (cfg.fleet.cell or "").split(",")
             if c.strip()] or ["default"]
    cell = cells[replica_id % len(cells)]
    phase_ref = {"phase": "warmup"}
    port_ref: dict = {}
    parsed_fault = _parse_fault(fault)

    serve_cfg = cfg.serve
    metrics = ServeMetrics()
    beats = None
    front = engine
    if parsed_fault is not None:
        front = _FaultingEngine(engine, parsed_fault[0], parsed_fault[1],
                                on_stall=lambda: beats.stall())
    batcher = MicroBatcher(
        front, buckets=serve_cfg.buckets,
        max_queue_depth=serve_cfg.max_queue_depth,
        batch_window_s=serve_cfg.batch_window_ms / 1e3,
        default_deadline_s=None if serve_cfg.deadline_ms is None
        else serve_cfg.deadline_ms / 1e3,
        metrics=metrics, logger=logger)
    beats = _BeatPublisher(store, batcher, engine,
                           cfg.fleet.heartbeat_interval_s, port_ref,
                           phase_ref, cell=cell)
    beats.start()

    response_cache = (ResponseCache(serve_cfg.cache_size)
                      if serve_cfg.cache_size > 0 else None)
    server = ThreadingHTTPServer(
        ("", serve_cfg.port),
        _make_handler(batcher, metrics, replica_id=replica_id,
                      hop="worker", logger=logger,
                      sample_rate=serve_cfg.trace_sample_rate,
                      cache=response_cache))
    port_ref["port"] = server.server_address[1]
    watcher = _SwapWatcher(fleet_dir, engine, trainer, state,
                           cfg.fleet.swap_poll_s, last_seq,
                           logger=logger, quant_ctx=quant_ctx)
    flusher = _MetricsFlusher(metrics, logger, serve_cfg.metrics_every_s,
                              alerts=alert_engine)
    accept = threading.Thread(target=server.serve_forever,
                              name="fleet-worker-accept", daemon=True)
    drained = True
    try:
        with PreemptionGuard() as guard:
            accept.start()
            watcher.start()
            flusher.start()
            phase_ref["phase"] = "serve"
            beats.beat_once()   # don't wait one interval to go routable
            print(f"[fleet] replica {replica_id} serving version "
                  f"{engine.version} on :{port_ref['port']} "
                  f"(compile_s={batcher.compile_secs})", flush=True)
            if ready_event is not None:
                ready_event.set()
            try:
                while not guard.requested and (
                        stop_event is None or not stop_event.is_set()):
                    time.sleep(0.05)
                why = (f"signal {guard.signum}" if guard.requested
                       else "stop requested")
            except KeyboardInterrupt:
                why = "keyboard interrupt"
            phase_ref["phase"] = "drain"
            beats.beat_once()
            print(f"[fleet] replica {replica_id} {why}: draining "
                  f"(deadline {serve_cfg.drain_deadline_s:.1f}s)")
            server.shutdown()
            accept.join()
            drained = batcher.drain(timeout=serve_cfg.drain_deadline_s)
    finally:
        server.server_close()
        watcher.stop()
        flusher.stop()
        beats.stop()
        if batcher._worker.is_alive():
            batcher.close()
        phase_ref["phase"] = "stopped"
        beats.beat_once()
        metrics.emit(logger, final=True)
        logger.flush()
        logger.close()
    print(f"[fleet] replica {replica_id} exiting cleanly "
          f"({'drained' if drained else 'drain deadline hit'})")
    return 0


def main_from_argv(argv) -> int:
    """``worker.py <config.json> <replica_id> [fault]`` — the spawn
    contract of the fleet controller's worker pool (a JSON config file,
    not a re-marshalled CLI, so workers can't drift from the fleet's
    flags)."""
    if len(argv) < 2:
        print("usage: python -m dml_cnn_cifar10_tpu.fleet.worker "
              "<config.json> <replica_id> [fault_kind@n]",
              file=sys.stderr)
        return 2
    from dml_cnn_cifar10_tpu.config import config_from_dict
    with open(argv[0]) as f:
        cfg = config_from_dict(json.load(f))
    fault = argv[2] if len(argv) > 2 and argv[2] else None
    return main_worker(cfg, int(argv[1]), fault=fault)


if __name__ == "__main__":
    sys.exit(main_from_argv(sys.argv[1:]))

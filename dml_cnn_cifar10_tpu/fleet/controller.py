"""``--mode fleet``: the process topology, owned end to end.

One controller process runs the router (in-process threads), the
checkpoint publisher, and the autoscaler loop, and owns a pool of serve
worker SUBPROCESSES::

      trainer ──ckpt──▶ log_dir ──▶ DirectoryPublisher ─▶ published.json
                                                              │ poll
        client ─▶ Router (:fleet_port) ──proxy──▶ worker 0 ◀──┤ swap
                    ▲  beats (fleet_dir)          worker 1 ◀──┘
                    └──────────────────────────── worker N

Workers are real processes, not threads, deliberately: a replica must
be killable (the failure unit), retirable (SIGTERM → drain), and
spawnable (the scale unit) without touching the others — the same
reason the cluster layer's simulation runs one process per host. Each
worker gets the fleet's exact config as a JSON file
(``config_to_dict``), binds an ephemeral port, and announces itself by
heartbeat; nothing here tracks ports.

The autoscaler loop closes the control loop: aggregate the replicas'
serve JSONL windows + heartbeat queue depths → ``decide`` (pure,
``fleet/autoscaler.py``) → spawn or retire, within
``--fleet_min/max_replicas``, one action per cooldown. Every decision
that acts logs a ``scale`` JSONL record.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Dict, Optional

from dml_cnn_cifar10_tpu.fleet import autoscaler as autoscaler_lib
from dml_cnn_cifar10_tpu.fleet.publisher import (DirectoryPublisher,
                                                 fleet_coord_dir)
from dml_cnn_cifar10_tpu.fleet.router import Router


class WorkerPool:
    """Spawn/retire/reap the worker subprocesses. Replica ids are
    never reused — eviction state, heartbeat files, and telemetry
    streams all key on them."""

    def __init__(self, config_path: str, fleet_dir: str,
                 worker_fault: Optional[str] = None):
        self.config_path = config_path
        self.fleet_dir = fleet_dir
        self.worker_fault = worker_fault   # "<rid>:<kind>@<n>" drill hook
        self.procs: Dict[int, subprocess.Popen] = {}
        self.retiring: Dict[int, subprocess.Popen] = {}
        self.next_id = 0

    def _fault_for(self, replica_id: int) -> Optional[str]:
        if not self.worker_fault:
            return None
        rid, sep, spec = self.worker_fault.partition(":")
        if sep and rid.isdigit() and int(rid) == replica_id:
            return spec
        return None

    def spawn(self) -> int:
        replica_id = self.next_id
        self.next_id += 1
        argv = [sys.executable, "-m", "dml_cnn_cifar10_tpu.fleet.worker",
                self.config_path, str(replica_id)]
        fault = self._fault_for(replica_id)
        if fault:
            argv.append(fault)
        log_path = os.path.join(self.fleet_dir, "telemetry",
                                f"replica_{replica_id}.log")
        os.makedirs(os.path.dirname(log_path), exist_ok=True)
        # Workers inherit the environment (JAX_PLATFORMS included);
        # their stdout/stderr go to a per-replica log, not the router's
        # console.
        env = dict(os.environ)
        import dml_cnn_cifar10_tpu
        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(dml_cnn_cifar10_tpu.__file__)))
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get(
            "PYTHONPATH", "")
        with open(log_path, "ab") as logf:
            self.procs[replica_id] = subprocess.Popen(
                argv, stdout=logf, stderr=subprocess.STDOUT, env=env)
        print(f"[fleet] spawned replica {replica_id} "
              f"(pid {self.procs[replica_id].pid})")
        return replica_id

    def retire(self, replica_id: int) -> None:
        """Graceful retirement: SIGTERM → the worker's PreemptionGuard
        drain. The process is reaped (not waited on) by the next
        :meth:`reap` pass so retirement never blocks the control
        loop."""
        proc = self.procs.pop(replica_id, None)
        if proc is None:
            return
        self.retiring[replica_id] = proc
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
        print(f"[fleet] retiring replica {replica_id} (SIGTERM)")

    def reap(self) -> Dict[int, int]:
        """Collect exits; returns {replica_id: returncode} of newly
        dead workers still counted as active (crashes — retirements
        exit through ``retiring`` silently)."""
        dead = {}
        for rid, proc in list(self.procs.items()):
            rc = proc.poll()
            if rc is not None:
                dead[rid] = rc
                del self.procs[rid]
        for rid, proc in list(self.retiring.items()):
            if proc.poll() is not None:
                del self.retiring[rid]
        return dead

    def active_ids(self):
        return sorted(self.procs)

    def terminate_all(self, timeout_s: float = 10.0) -> None:
        for rid in list(self.procs):
            self.retire(rid)
        deadline = time.time() + timeout_s
        for rid, proc in list(self.retiring.items()):
            try:
                proc.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()


class FleetController:
    """Router + publisher + pool + the autoscaler control loop."""

    def __init__(self, cfg, logger=None):
        self.cfg = cfg
        self.fleet_dir = fleet_coord_dir(cfg)
        os.makedirs(self.fleet_dir, exist_ok=True)
        self.logger = logger
        # Streaming alerts over the controller's own stream (fleet
        # windows, scale events, evictions via peer_lost) — and the
        # autoscaler's extra input: active load-shaped alerts push
        # scale-up, ANY active alert vetoes scale-down. Evaluated once
        # per control tick, the fleet's metrics boundary.
        from dml_cnn_cifar10_tpu.utils import alerts as alerts_lib
        self.alerts = alerts_lib.AlertEngine.from_config(cfg)
        if self.alerts is not None and logger is not None:
            logger.add_observer(self.alerts.observer(logger))
        # Alert-driven remediation (--autopilot; autopilot/engine.py):
        # a qualifying SLO/shed alert requests an immediate scale-up —
        # served at the NEXT tick ahead of the autoscaler's own cadence
        # and cooldown (the autoscaler would get there too, one
        # autoscale_every_s later; the autopilot buys back that lag and
        # leaves the remediation lineage in the JSONL stream).
        from dml_cnn_cifar10_tpu.autopilot.engine import AutopilotEngine
        self._scale_up_requested: Optional[str] = None
        self.autopilot = AutopilotEngine.from_config(
            cfg, logger=logger)
        if self.autopilot is not None:
            self.autopilot.bind("scale_up", self._request_scale_up)
            if self.alerts is not None:
                self.autopilot.attach(self.alerts)
        # NET coordination transport (--cluster_transport net): the
        # controller hosts the fleet's coordination service over the
        # fleet dir; workers beat through CoordClient. The router keeps
        # reading the SAME directory straight off disk (it is
        # co-process with the server), so discovery needs no extra hop.
        self.net_server = None
        if getattr(cfg.parallel, "cluster_transport", "file") == "net":
            from dml_cnn_cifar10_tpu.parallel import net as net_lib
            self.net_server = net_lib.CoordServer(self.fleet_dir)
        self.router = Router(
            self.fleet_dir,
            dead_after_s=cfg.fleet.replica_dead_after_s,
            route_retries=cfg.fleet.route_retries,
            route_timeout_s=cfg.fleet.route_timeout_s,
            route_backoff_s=cfg.fleet.route_backoff_s,
            logger=logger,
            trace_sample_rate=cfg.serve.trace_sample_rate)
        config_path = os.path.join(self.fleet_dir, "worker_config.json")
        from dml_cnn_cifar10_tpu.config import config_to_dict
        worker_cfg = config_to_dict(cfg)
        # Workers must never fight over one HTTP port or one JSONL
        # stream: ephemeral ports, per-replica telemetry (worker.py
        # derives the path from fleet dir + replica id).
        worker_cfg["serve"]["port"] = 0
        worker_cfg["metrics_jsonl"] = None
        worker_cfg["fleet"]["dir"] = self.fleet_dir
        tmp = config_path + f".tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(worker_cfg, f, indent=1)
        os.replace(tmp, config_path)
        self.pool = WorkerPool(config_path, self.fleet_dir,
                               worker_fault=cfg.fleet.worker_fault)
        self.publisher = DirectoryPublisher(
            cfg.log_dir, self.fleet_dir,
            poll_s=cfg.fleet.publish_poll_s, logger=logger,
            quantize=cfg.serve.quantize)
        self._cooldown_until = 0.0
        self._last_decide = 0.0
        self._last_fleet_emit = time.time()

    def _request_scale_up(self, rule_name: str) -> None:
        """Autopilot scale_up seam: remember the request; :meth:`tick`
        serves it ahead of the autoscaler cadence."""
        self._scale_up_requested = rule_name

    # -- the control loop body (one tick, also driven by tests) --------

    def signals(self) -> autoscaler_lib.FleetSignals:
        live = self.router.live()
        live_ids = {v.replica_id for v in live}
        starting = len([rid for rid in self.pool.active_ids()
                        if rid not in live_ids])
        return autoscaler_lib.aggregate_signals(
            live, starting, os.path.join(self.fleet_dir, "telemetry"))

    def tick(self) -> None:
        """Reap crashes, then (cooldown permitting) one scale action."""
        dead = self.pool.reap()
        for rid, rc in dead.items():
            # A crashed worker stops beating and the router evicts it
            # on staleness; evicting here too closes the gap between
            # process exit and beat expiry.
            self.router.evict(rid, f"replica_evicted_exit_{rc}")
        now = time.time()
        if now - self._last_fleet_emit >= self.cfg.fleet.metrics_every_s:
            self._last_fleet_emit = now
            self.router.emit()
            if self.alerts is not None:
                self.alerts.evaluate(
                    emit=self.logger.log if self.logger is not None
                    else None)
        requested, self._scale_up_requested = \
            self._scale_up_requested, None
        if requested is not None \
                and len(self.pool.active_ids()) \
                < self.cfg.fleet.max_replicas:
            # Autopilot remediation: spawn now, ahead of the decide
            # cadence; the scale record keeps the autoscaler's shape
            # with an autopilot-attributed reason.
            self.pool.spawn()
            self._cooldown_until = now + self.cfg.fleet.scale_cooldown_s
            if self.logger is not None:
                self.logger.log("scale", action="up",
                                reason=f"autopilot:{requested}",
                                replicas=len(self.pool.active_ids()))
            print(f"[fleet] scale up (autopilot:{requested}): "
                  f"{len(self.pool.active_ids())} worker(s)")
            return
        if now < self._cooldown_until \
                or now - self._last_decide < self.cfg.fleet.autoscale_every_s:
            return
        self._last_decide = now
        sig = self.signals()
        decision = autoscaler_lib.decide(
            sig, self.cfg.fleet.min_replicas,
            self.cfg.fleet.max_replicas,
            slo_ms=self.cfg.serve.slo_ms,
            scale_up_queue_depth=self.cfg.fleet.scale_up_queue_depth,
            alerts_active=(self.alerts.active_names()
                           if self.alerts is not None else ()))
        if decision.action == "hold":
            return
        if not self.cfg.fleet.autoscale and decision.reason != "below_min":
            # Autoscaling off still self-heals: a fleet below its floor
            # is a missing replica, not a capacity opinion.
            return
        if decision.action == "up":
            self.pool.spawn()
        elif decision.action == "down":
            victim = max((v.replica_id for v in self.router.live()),
                         default=None)
            if victim is None:
                return
            self.router.drain_replica(victim)
            self.pool.retire(victim)
        self._cooldown_until = now + self.cfg.fleet.scale_cooldown_s
        if self.logger is not None:
            self.logger.log(
                "scale", action=decision.action, reason=decision.reason,
                replicas=len(self.pool.active_ids()))
        print(f"[fleet] scale {decision.action} ({decision.reason}): "
              f"{len(self.pool.active_ids())} worker(s)")

    # -- lifecycle ------------------------------------------------------

    def start(self) -> int:
        """Spawn the floor, start publisher + router; returns the
        router's bound port."""
        self.publisher.scan_once()   # publish what already exists
        self.publisher.start()
        for _ in range(self.cfg.fleet.min_replicas):
            self.pool.spawn()
        server = self.router.serve(self.cfg.fleet.port)
        return server.server_address[1]

    def shutdown(self) -> None:
        self.publisher.stop()
        self.router.emit(final=True)
        self.router.shutdown()
        self.pool.terminate_all()
        # Last: workers drain first so their final beats don't land on
        # a closed coordination service.
        if self.net_server is not None:
            self.net_server.stop()


def main_fleet(cfg, ready_event: Optional[threading.Event] = None,
               stop_event: Optional[threading.Event] = None) -> int:
    """Blocking fleet loop with graceful SIGTERM/SIGINT shutdown:
    retire every worker (their own drains bound the wait), final
    ``fleet_done`` record, exit 0."""
    from dml_cnn_cifar10_tpu.utils.logging import MetricsLogger
    from dml_cnn_cifar10_tpu.utils.preemption import PreemptionGuard

    logger = MetricsLogger(jsonl_path=cfg.metrics_jsonl)
    controller = FleetController(cfg, logger=logger)
    port = controller.start()
    print(f"[fleet] router listening on :{port} "
          f"(POST /predict, GET /stats, GET /healthz); "
          f"{cfg.fleet.min_replicas} worker(s) warming up; "
          f"fleet dir {controller.fleet_dir}")
    try:
        with PreemptionGuard() as guard:
            if ready_event is not None:
                ready_event.set()
            try:
                while not guard.requested and (
                        stop_event is None or not stop_event.is_set()):
                    controller.tick()
                    time.sleep(0.1)
                why = (f"signal {guard.signum}" if guard.requested
                       else "stop requested")
            except KeyboardInterrupt:
                why = "keyboard interrupt"
            print(f"[fleet] {why}: retiring workers")
    finally:
        controller.shutdown()
        logger.flush()
        logger.close()
    print("[fleet] exiting cleanly")
    return 0

#!/usr/bin/env python
"""Lint a metrics JSONL stream against the documented schema.

The JSONL stream (``--metrics_jsonl``) is the contract every downstream
consumer — ``tools/telemetry_report.py``, ``tools/convergence_report.py``,
ad-hoc pandas — parses. This lint enforces the contract documented in
``docs/OBSERVABILITY.md``: every line is strict JSON (no NaN/Infinity
tokens — the writer maps non-finite floats to null), every record carries
the base keys, and each known ``kind`` carries its required keys.

Unknown kinds are tolerated by default (a stream from a NEWER build must
stay lintable by an older tool) but rejected under ``--strict``: a new
record kind must be added to ``KIND_KEYS`` here AND to the schema table
in the doc, which is exactly the drift strict mode exists to catch — a
typo'd kind never lints again. The tier-1 suite runs strict everywhere.

Usage: ``python tools/check_jsonl_schema.py [--strict] run.jsonl
[more.jsonl ...]`` (exit 1 on any violation). ``tests/test_telemetry.py``
runs it over a real training run's stream as part of the tier-1 suite.
"""

from __future__ import annotations

import json
import sys
from typing import Iterable, List

# Keys every record must carry (utils/logging.py writes them).
BASE_KEYS = ("kind", "t", "task")

# Required keys per record kind. Values may be null (the writer maps
# NaN/Inf to null) but the KEY must be present.
KIND_KEYS = {
    # `device_step_ms`/`drain_wait_ms` are the always-on device
    # step-time estimate riding the fused boundary fetch
    # (utils/devprof.py; null before the first complete window).
    # `optimizer_ms` is the per-step device time inside the step's
    # jax.named_scope("optimizer"), from the last --profile_at_steps
    # capture window (null until one completes). Optional keys ride
    # beside these and are not checked: `moe_*` (an expert decoder's
    # counters, `moe_aux_loss` with a balance loss), `attn_*`, `health_*`.
    "train": ("step", "loss", "train_accuracy", "images_per_sec", "lr",
              "device_step_ms", "drain_wait_ms", "optimizer_ms"),
    "eval": ("step", "test_accuracy"),
    # `cat` rides categorized spans, `thread` those of another thread
    # than the loop's (the FLOP probe, a collection on a worker thread).
    "span": ("step", "name", "start_s", "dur_s", "depth"),
    "goodput": ("step", "total_s", "train_frac", "compile_frac",
                "data_frac", "eval_frac", "checkpoint_frac", "sync_frac"),
    "hbm": ("step", "available", "devices", "bytes_in_use", "peak_bytes",
            "bytes_limit"),
    "done": ("step", "images_per_sec"),
    "preempt": ("step", "signum"),
    "numerics_halt": ("step",),
    # Resilience layer (train/supervisor.py, utils/faults.py,
    # ckpt/checkpoint.py; docs/RESILIENCE.md). `fault` records both
    # injections (injected=true) and detections (injected=false);
    # `recovery` records the action taken (skip/restart/recovered);
    # `rollback` the supervisor's restore-point + LR decision;
    # `ckpt_fallback` a checkpoint skipped by the newest-verifiable
    # restore walk; `ckpt_prune_error` a retention prune that failed.
    "fault": ("step", "fault", "injected"),
    "recovery": ("step", "fault", "action", "attempt"),
    "rollback": ("step", "restore_step", "attempt", "lr"),
    "ckpt_fallback": ("step", "path", "error", "walk_ms"),
    "ckpt_prune_error": ("step", "path", "error"),
    # Cluster-resilience layer (parallel/cluster.py;
    # docs/RESILIENCE.md multi-host section). `heartbeat` is the
    # rate-limited JSONL mirror of the beat store; `straggler` names a
    # peer beating but behind at an overrun dispatch seam; `peer_lost`
    # records a stale-heartbeat death declaration, a watchdog abort, an
    # eviction fence, or a non-chief preemption exit (`reason` says
    # which); `elastic_restart` is the adopted coordinated-restart
    # decision (shrunken world, restore step, epoch).
    "heartbeat": ("step", "process_id", "phase", "wallclock"),
    "straggler": ("step", "process_id", "behind_steps", "beat_age_s"),
    "peer_lost": ("step", "process_id", "reason"),
    "elastic_restart": ("step", "restore_step", "world_size", "epoch",
                        "attempt", "source"),
    # Elastic scale-UP (--elastic_expand). `host_rejoin` is a rejoin
    # announcement — logged by the returning host when it starts
    # beating with phase "rejoin", and by the chief when its scan
    # detects one; `elastic_expand` is the adopted coordinated-expand
    # decision (grown world, restore step, epoch) — the scale-UP twin
    # of `elastic_restart`.
    "host_rejoin": ("step", "process_id", "epoch"),
    "elastic_expand": ("step", "restore_step", "world_size", "epoch",
                       "attempt", "source"),
    # A corrupt restart-decision file classified by the hardened
    # RestartCoordinator.read (undecodable payload or sha256-sidecar
    # mismatch): the decision reads as absent, the poll self-heals, and
    # this record is the evidence (rate-limited per payload digest).
    "decision_corrupt": ("path", "error"),
    # Chaos campaign driver (tools/chaos.py; docs/RESILIENCE.md chaos
    # section). `chaos` is one seeded schedule's verdict (`spec` is the
    # ready-to-paste --fault_spec, `invariant` the first violated
    # invariant or null, and on failure `reproducer` carries the shrunk
    # minimal spec); `chaos_done` the campaign summary (faults_by_kind
    # counts every fault the schedules injected, slowest_recovery_s the
    # worst fault→recovery latency observed across all runs).
    "chaos": ("seed", "scenario", "spec", "ok", "invariant", "secs"),
    "chaos_done": ("schedules", "passed", "failed", "faults_by_kind",
                   "slowest_recovery_s"),
    # Sharded-checkpoint fast-resume (ckpt/sharded.py). One record per
    # shard file written (`op: save` — verify null, the digest is being
    # created) or read (`op: restore` — verify true/false/null, null =
    # pre-integrity shard without a sidecar); `op: legacy_glob` flags a
    # manifest without `shard_files` restored via filename glob (bytes/
    # secs/verify null). `source` says where the bytes went/came from:
    # "disk" (the checkpoint dir) or "peer" (the peer-replica store —
    # a diskless restore shows ONLY source=peer records).
    "shard_io": ("op", "shard", "bytes", "secs", "verify", "source"),
    # Peer-redundancy layer (ckpt/peerstore.py; docs/RESILIENCE.md
    # diskless-recovery section). One record per replica operation:
    # `op` is push (a boundary payload committed to the ring-successor
    # store), verify (a replica read's sidecar check), reconstruct (a
    # lost host's shards rebuilt from its replica), decide (the chief's
    # source choice — `staleness` is beat-vs-replica step lag), or
    # fallback (a peer restore classified a miss and degraded to the
    # disk walk). `owner` is the payload's owning process id (null for
    # decide/fallback), `ok` the operation verdict, `error` the
    # classified reason when not ok.
    "peer_replica": ("op", "step", "owner", "bytes", "secs", "ok",
                     "error", "staleness"),
    # Compilation cache (compilecache/; docs/COMPILECACHE.md). One
    # record per compile-seam lookup: `key` is the program fingerprint
    # (null when no cache is configured but the seam still reports its
    # compile, e.g. serve warmup), `phase` names the seam (train_step /
    # train_chunk / train_chunk_resident / init / eval_* /
    # serve_warmup / analysis), `hit` whether an executable was reused,
    # `compile_s` the obtain time (trace + load or compile), `source`
    # one of memory | executable | stablehlo | miss | corrupt | error |
    # uncached.
    "compile": ("key", "phase", "hit", "compile_s", "source"),
    # Device-time attribution (utils/devprof.py; docs/OBSERVABILITY.md
    # device-time section). One record per trace lane of a
    # --profile_at_steps capture window: bucket totals in milliseconds
    # (compute / collective / infeed), the overlapping named-scope
    # total `optimizer_ms` (the weight-update tail), the lane's wall
    # window, and the top-k op table as a nested list of
    # {name, bucket, dur_ms, calls, frac}.
    # Instruction-to-layer map of one compiled program
    # (utils/devprof.scope_map), announced once per telemetry fit and
    # program; `path` is null where no profiler capture directory
    # exists to write scopemap_<module>.json beside.
    "scopemap": ("step", "module", "instructions", "mapped", "mixed",
                 "recompute", "path"),
    "devtime": ("step", "device", "total_ms", "compute_ms",
                "collective_ms", "infeed_ms", "optimizer_ms",
                "window_ms", "top_ops"),
    # Streaming alert engine (utils/alerts.py; docs/OBSERVABILITY.md
    # Alerting section). `alert` fires when a rule's condition holds
    # (threshold on consecutive records / rate over a trailing
    # step-or-second window / record absence); `alert_resolved` pairs
    # it when the signal recovers. Emission is rate-limited per rule,
    # and a suppressed re-fire suppresses its resolution too, so the
    # emitted records are strictly paired. `window` is the rule's
    # window descriptor ("2 consecutive" / "50 steps" / "15s"),
    # `value` the reading that crossed (or recovered past) the line.
    # `id` is the firing's identity ("<rule>#<N>", monotonic per
    # engine): stamped on both records of an emitted pair, and the join
    # key remediation records point back at.
    "alert": ("rule", "severity", "window", "value", "id"),
    "alert_resolved": ("rule", "severity", "window", "value", "id"),
    # Autopilot remediation (autopilot/engine.py; docs/AUTOPILOT.md).
    # One record per qualifying alert firing per matching policy:
    # `alert_id` joins the firing `alert` record, `action` is the
    # policy's remediation, `status` one of applied | noop | failed |
    # suppressed_cooldown | suppressed_budget, `postmortem` the
    # flight-recorder bundle captured for the same firing (null when
    # the recorder is unarmed), `detail` the action's own summary,
    # `step` the global step snapshot at firing time.
    "remediation": ("policy", "rule", "alert_id", "action", "status",
                    "postmortem", "detail", "step"),
    # Serving runtime (serve/metrics.py; docs/SERVING.md). Percentile
    # values are null until the window has completions.
    "serve": ("requests", "completed", "shed_queue", "shed_deadline",
              "cache_hit", "qps", "p50_ms", "p95_ms", "p99_ms",
              "batch_fill", "window_s"),
    "serve_done": ("requests", "completed", "shed_queue",
                   "shed_deadline", "cache_hit", "qps", "p50_ms",
                   "p95_ms", "p99_ms", "batch_fill", "shed_fraction",
                   "total_s"),
    # Quantized serving (quant/; docs/QUANT.md). `calibration` is one
    # record per calibrated tensor (weights per-channel, activations
    # per-tensor; channels=0 marks a per-tensor scale); `quant_rejected`
    # is the accuracy-delta publish gate firing — the int8 candidate's
    # holdout top-1 trailed float by more than max_delta, so the
    # previous version keeps serving (the quantized `swap_rejected`).
    "calibration": ("tensor", "amax", "scale", "channels", "batches"),
    "quant_rejected": ("replica_id", "version", "float_top1",
                       "quant_top1", "delta", "max_delta", "reason"),
    # Serving fleet (fleet/; docs/SERVING.md fleet section). `fleet` is
    # the router's periodic window (replica membership + routing
    # counters; `fleet_done` the final cumulative one); `swap` a
    # worker's successful checkpoint hot-swap and `swap_rejected` a
    # candidate refused (contract mismatch / failed restore — the old
    # version keeps serving); `scale` an autoscaler action (up/down
    # with its decision-table reason); `fleet_publish` a checkpoint
    # version committed for the fleet to serve.
    "fleet": ("replicas", "live", "routed", "rerouted", "evictions",
              "shed", "version_mix", "window_s"),
    "fleet_done": ("replicas", "live", "routed", "rerouted",
                   "evictions", "shed", "version_mix", "window_s"),
    "swap": ("replica_id", "version", "from_version", "swap_ms"),
    "swap_rejected": ("replica_id", "version", "reason"),
    "scale": ("action", "reason", "replicas"),
    "fleet_publish": ("seq", "version", "step", "path"),
    # Distributed request tracing (utils/reqtrace.py;
    # docs/OBSERVABILITY.md Request-tracing section). One span per hop
    # a sampled-or-forced request crossed: `trace_id` is the join key
    # across process streams, `hop` the stage (client / router / server
    # / worker / batcher / engine / batch), `dur_ms` the hop's own
    # latency contribution, `wallclock` unix seconds at hop start (what
    # places the span on the merged timeline). Hop-specific context
    # (batch_id, version, shed, attempt, replica_id) rides as extra
    # keys.
    "rspan": ("trace_id", "hop", "dur_ms", "wallclock"),
    # Flight recorder (utils/flightrec.py). One record per post-mortem
    # bundle captured on an alert firing: the rule that fired, the
    # bundle directory, and how many ring records it snapshotted.
    "postmortem": ("rule", "dir", "records"),
    # Unified multi-job runtime (runtime/; docs/RUNTIME.md). `job` is a
    # job lifecycle transition (state: pending / running / done /
    # failed; alert-born jobs also carry `trigger=<rule>`); `job_done`
    # the completion summary (`ok` + wall seconds, `error` when not
    # ok); `publish` one committed checkpoint's weights installed into
    # the in-process serving engine via the locked pointer swap —
    # `source` is "live_params" (device buffers, zero checkpoint
    # reads), `swapped` whether the engine accepted the candidate, and
    # the extra `job`/`seq` keys stamp the alert→job→publish lineage.
    "job": ("job", "jtype", "state"),
    "job_done": ("job", "jtype", "ok", "secs"),
    "publish": ("step", "version", "source", "latency_ms", "swapped"),
    # Net coordination transport (parallel/net.py): one rate-limited
    # record per (operation, error) transition — `op` the client call
    # (publish/read/scan/record/...), `ok` whether it resolved; failed
    # ops carry the classified `error` reason (timeout, unreachable,
    # http_<code>, proto) plus attempts/ms, the partition-timeline
    # input for telemetry_report's network-health section.
    "net": ("op", "ok"),
    # Cross-cell failover: the router had to place a request tagged
    # `from_cell` (X-DML-Cell) onto a replica in `to_cell` because the
    # target cell had no live replica; always trace-forced.
    "cell_route": ("from_cell", "to_cell", "replica_id"),
    # A torn/undecodable heartbeat found mid-scan (HeartbeatStore
    # .read_all / the net scan): classified and skipped, never raised —
    # discovery keeps working through one corrupt beat file.
    "beat_decode_error": ("path", "error"),
}


def _reject_constant(name: str):
    raise ValueError(f"non-strict JSON constant {name}")


def check_lines(lines: Iterable[str], source: str = "<stream>",
                strict: bool = False) -> List[str]:
    """Validate JSONL lines; returns a list of human-readable errors.
    ``strict`` additionally rejects unknown kinds (see module
    docstring)."""
    errors = []
    for ln, line in enumerate(lines, 1):
        line = line.strip()
        if not line:
            continue
        where = f"{source}:{ln}"
        try:
            rec = json.loads(line, parse_constant=_reject_constant)
        except ValueError as e:
            errors.append(f"{where}: invalid strict JSON ({e})")
            continue
        if not isinstance(rec, dict):
            errors.append(f"{where}: record is not a JSON object")
            continue
        missing = [k for k in BASE_KEYS if k not in rec]
        if missing:
            errors.append(f"{where}: missing base keys {missing}")
        kind = rec.get("kind")
        if kind not in KIND_KEYS:
            if strict:
                errors.append(
                    f"{where}: unknown kind {kind!r} (add it to "
                    f"tools/check_jsonl_schema.py and "
                    f"docs/OBSERVABILITY.md)")
            continue
        missing = [k for k in KIND_KEYS[kind] if k not in rec]
        if missing:
            errors.append(f"{where}: kind {kind!r} missing keys {missing}")
        for k, v in rec.items():
            # json.loads only yields inf/nan via the constants rejected
            # above, but a float check keeps the rule explicit.
            if isinstance(v, float) and v != v:
                errors.append(f"{where}: key {k!r} is NaN")
    return errors


def check_file(path: str, strict: bool = False) -> List[str]:
    with open(path) as f:
        return check_lines(f, source=path, strict=strict)


def list_kinds() -> List[str]:
    """Every kind the lint knows, sorted — the machine-readable side of
    the drift contract with docs/OBSERVABILITY.md's kinds table
    (``tests/test_telemetry.py`` asserts the two match both ways)."""
    return sorted(KIND_KEYS)


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv == ["--list-kinds"]:
        for kind in list_kinds():
            print(kind)
        return 0
    strict = False
    while "--strict" in argv:
        argv.remove("--strict")
        strict = True
    if not argv:
        print(__doc__.strip().splitlines()[0])
        print("usage: check_jsonl_schema.py [--strict] [--list-kinds] "
              "FILE.jsonl [...]")
        return 2
    failed = False
    for path in argv:
        errs = check_file(path, strict=strict)
        for e in errs:
            print(e)
        if errs:
            failed = True
        else:
            print(f"{path}: OK")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""Summarize a run's metrics JSONL into a goodput / run-health table.

Reads the stream written by ``--metrics_jsonl`` (schema:
``docs/OBSERVABILITY.md``) and answers "where did the wall-clock go?" and
"was this run healthy?" without loading a trace UI:

- goodput breakdown from the final ``goodput`` record (falling back to
  re-aggregating ``span`` records when a run died before the final
  flush),
- throughput from the ``train`` / ``done`` records (the drain-anchored
  figures BENCH_*.json quotes — see docs/OBSERVABILITY.md for how the
  two relate),
- training health (grad/param norm, update ratio) when the run compiled
  them in (``--health_metrics``),
- device-time attribution: the per-boundary ``device_step_ms`` /
  ``drain_wait_ms`` split (host-bound vs device-bound) from the train
  rows, and the per-op ``devtime`` table a ``--profile_at_steps``
  capture window emitted (utils/devprof.py),
- HBM peak from the ``hbm`` snapshots.

Usage: ``python tools/telemetry_report.py run.jsonl [more.jsonl ...]``
``--format json`` emits the same summary as one machine-readable JSON
document (``summarize_json``) for the perf gate / CI; the text renderer
stays the default. ``--follow`` switches to an incremental tail mode
that re-renders the summary as the stream grows (shared tailing helper
with ``tools/live_monitor.py``), exiting when the run's final record
lands. An alerts section reports what fired/resolved while the run was
live (``utils/alerts.py``) and which rules were still firing at stream
end.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dml_cnn_cifar10_tpu.utils.telemetry import (GOODPUT_CATEGORIES,  # noqa: E402
                                                 percentile)


def load_records(path: str) -> List[dict]:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def _last(records: List[dict], kind: str) -> Optional[dict]:
    for rec in reversed(records):
        if rec.get("kind") == kind:
            return rec
    return None


def _goodput_from_spans(records: List[dict]) -> Optional[dict]:
    """Rebuild the cumulative breakdown from raw span records — the
    fallback when a run died before its final goodput flush. Wall-clock
    total comes from the last record's ``t`` offset."""
    spans = [r for r in records if r.get("kind") == "span"]
    if not spans:
        return None
    total = max((r.get("t") or 0.0) for r in records)
    if total <= 0:
        return None
    secs = dict.fromkeys(GOODPUT_CATEGORIES, 0.0)
    for s in spans:
        cat = s.get("cat")
        if cat in secs and s.get("depth") == 0:
            secs[cat] += s.get("dur_s") or 0.0
    out = {"total_s": total}
    for cat, v in secs.items():
        out[f"{cat}_frac"] = v / total
    out["train_frac"] = max(0.0, 1.0 - sum(secs.values()) / total)
    return out


def _slowest_boundary(records: List[dict]) -> Optional[dict]:
    """The longest boundary interval of a telemetry stream against the
    median one, and the spans inside it against what they take in a
    median interval: which span holds the excess of a stalled interval
    (a ``gc_gen2``, a ``dispatch`` that blocked, ``boundary_log``, or
    ``boundary_drain`` itself — then the host was waiting and the cause
    lies below the program). An interval runs from the end of one
    ``boundary_drain`` to the end of the next, on the tracer's clock; a
    span belongs to the interval it ends in. None with fewer than three
    intervals. A stream of several ``fit``s (their clocks restart) is
    read fit by fit."""
    spans = [r for r in records if r.get("kind") == "span"
             and isinstance(r.get("start_s"), (int, float))
             and isinstance(r.get("dur_s"), (int, float))]
    # Spans are recorded as they finish, so on one tracer's clock their
    # ends never go back: an end that does is a new fit's (its clock
    # restarted).
    fits: List[List[dict]] = [[]]
    newest = None
    for r in spans:
        end = r["start_s"] + r["dur_s"]
        if newest is not None and end < newest - 0.5:
            fits.append([])
            newest = None
        newest = end if newest is None else max(newest, end)
        fits[-1].append(r)
    intervals = []
    for fit in fits:
        ends = [(r["start_s"] + r["dur_s"], r.get("step")) for r in fit
                if r.get("name") == "boundary_drain"
                and not r.get("thread")]
        for (t0, _), (t1, step) in zip(ends, ends[1:]):
            inside: dict = {}
            for r in fit:
                end = r["start_s"] + r["dur_s"]
                if t0 < end <= t1:
                    inside[r["name"]] = inside.get(r["name"], 0.0) \
                        + r["dur_s"]
            intervals.append({"step": step, "secs": t1 - t0,
                              "spans": inside})
    if len(intervals) < 3:
        return None
    median = percentile([iv["secs"] for iv in intervals], 50)
    worst = max(intervals, key=lambda iv: iv["secs"])
    names = set(worst["spans"])
    rows = []
    for name in names:
        typical = percentile([iv["spans"].get(name, 0.0)
                              for iv in intervals], 50)
        rows.append({"name": name,
                     "secs": round(worst["spans"][name], 6),
                     "median_secs": round(typical, 6),
                     "excess_secs": round(worst["spans"][name] - typical,
                                          6)})
    rows.sort(key=lambda r: -r["excess_secs"])
    return {"intervals": len(intervals), "step": worst["step"],
            "secs": round(worst["secs"], 6),
            "median_secs": round(median, 6),
            "ratio": round(worst["secs"] / median, 4) if median else None,
            "spans": rows}


def _device_split(trains: List[dict]) -> Optional[dict]:
    """Boundary-estimator aggregate over the train rows: p50
    ``device_step_ms`` / ``drain_wait_ms`` and the implied device-busy
    fraction of the step window (device wall per step vs total wall per
    step from ``images_per_sec``). None when no row carries the keys."""
    dev = [r["device_step_ms"] for r in trains
           if isinstance(r.get("device_step_ms"), (int, float))]
    if not dev:
        return None
    drain = [r["drain_wait_ms"] for r in trains
             if isinstance(r.get("drain_wait_ms"), (int, float))]
    out = {
        "boundaries": len(dev),
        "device_step_ms_p50": round(percentile(dev, 50), 4),
        "device_step_ms_p99": round(percentile(dev, 99), 4),
        "drain_wait_ms_p50": round(percentile(drain, 50), 3)
        if drain else None,
        "device_busy_frac": None,
    }
    # Host-idle share of each boundary window: drain_wait is the time
    # the host spent BLOCKED on the device at the fused fetch, and
    # device_step_ms x (steps between consecutive train rows) is the
    # window's wall (the estimator divides that wall by the same step
    # count). A share near 1 means the host idles on the device
    # (device-bound: the step itself must get faster); near 0 means the
    # device idles on the host (host-bound: feed it better).
    fracs = []
    for prev, cur in zip(trains, trains[1:]):
        d, w = cur.get("device_step_ms"), cur.get("drain_wait_ms")
        if not (isinstance(d, (int, float))
                and isinstance(w, (int, float))
                and isinstance(cur.get("step"), int)
                and isinstance(prev.get("step"), int)):
            continue
        steps = cur["step"] - prev["step"]
        if steps > 0 and d > 0:
            fracs.append(min(w / (d * steps), 1.0))
    if fracs:
        out["device_busy_frac"] = round(sum(fracs) / len(fracs), 4)
    return out


def _chaos_totals(records: List[dict]) -> Optional[dict]:
    """Sum every ``chaos_done`` record in the stream into one summary —
    a mixed campaign (`--scenario mixed`) writes one per scenario, and
    the section should report the whole campaign, not the last leg."""
    dones = [r for r in records if r.get("kind") == "chaos_done"]
    if not dones:
        return None
    by_kind: dict = {}
    for r in dones:
        for k, v in (r.get("faults_by_kind") or {}).items():
            by_kind[k] = by_kind.get(k, 0) + v
    return {
        "schedules": sum(r.get("schedules") or 0 for r in dones),
        "passed": sum(r.get("passed") or 0 for r in dones),
        "failed": sum(r.get("failed") or 0 for r in dones),
        "faults_by_kind": by_kind,
        "slowest_recovery_s": max(
            (r.get("slowest_recovery_s") or 0.0) for r in dones),
    }


def _hop_breakdown(records: List[dict]) -> Optional[dict]:
    """Per-hop request-latency breakdown from the ``rspan`` records
    (utils/reqtrace.py): span/trace counts, p50/p99 per hop, and a
    slowest-trace exemplar table (total = the sum of the trace's hop
    durations; its trace_id is directly findable in the merged Perfetto
    output). ``batch`` spans carry a batch_id as their trace_id and are
    counted as a hop but excluded from the per-trace totals."""
    spans = [r for r in records if r.get("kind") == "rspan"
             and isinstance(r.get("dur_ms"), (int, float))]
    if not spans:
        return None
    by_hop: dict = {}
    by_trace: dict = {}
    for r in spans:
        hop = r.get("hop") or "?"
        by_hop.setdefault(hop, []).append(r["dur_ms"])
        if hop != "batch" and r.get("trace_id"):
            ent = by_trace.setdefault(str(r["trace_id"]),
                                      {"total_ms": 0.0, "hops": {},
                                       "version": None})
            ent["hops"][hop] = round(
                ent["hops"].get(hop, 0.0) + r["dur_ms"], 3)
            ent["total_ms"] = round(ent["total_ms"] + r["dur_ms"], 3)
            if r.get("version") is not None:
                ent["version"] = r["version"]
    hops = [{"hop": hop, "spans": len(durs),
             "p50_ms": round(percentile(durs, 50), 3),
             "p99_ms": round(percentile(durs, 99), 3)}
            for hop, durs in sorted(by_hop.items())]
    slowest = [{"trace_id": tid, **ent}
               for tid, ent in sorted(by_trace.items(),
                                      key=lambda kv: -kv[1]["total_ms"])
               [:5]]
    return {"spans": len(spans), "traces": len(by_trace),
            "hops": hops, "slowest": slowest}


def _peer_summary(records: List[dict]) -> Optional[dict]:
    """Diskless-recovery rollup from ``peer_replica`` records plus the
    ``source`` field on adopted elastic restart/expand decisions
    (ckpt/peerstore.py). None when the stream carries neither — the
    report stays byte-identical for pre-redundancy streams."""
    peer_recs = [r for r in records if r.get("kind") == "peer_replica"]
    transitions = [r for r in records
                   if r.get("kind") in ("elastic_restart",
                                        "elastic_expand")]
    sourced = [r for r in transitions if r.get("source") is not None]
    if not peer_recs and not sourced:
        return None
    recon = [r for r in peer_recs if r.get("op") == "reconstruct"
             and r.get("secs") is not None]
    recon_s = [float(r["secs"]) for r in recon]
    decides = [r for r in peer_recs if r.get("op") == "decide"
               and r.get("ok") and r.get("staleness") is not None]
    out = {
        "peer_restores": sum(1 for r in sourced
                             if r.get("source") == "peer"),
        "disk_restores": sum(1 for r in transitions
                             if (r.get("source") or "disk") == "disk"),
        "pushes": sum(1 for r in peer_recs
                      if r.get("op") == "push" and r.get("ok")),
        "push_failures": sum(1 for r in peer_recs
                             if r.get("op") == "push"
                             and r.get("ok") is False),
        "fallbacks": sum(1 for r in peer_recs
                         if r.get("op") == "fallback"),
        "reconstructs": len(recon),
        "reconstruct_mean_s": round(sum(recon_s) / len(recon_s), 6)
        if recon_s else None,
        "reconstruct_max_s": round(max(recon_s), 6) if recon_s else None,
        # Staleness the chief saw at its LAST decide seam: how many
        # steps the beats were ahead of the replica set it restored.
        "decide_staleness": decides[-1].get("staleness")
        if decides else None,
    }
    return out


def _autopilot_summary(records: List[dict]) -> Optional[dict]:
    """Alert → remediation → outcome lineage from the ``remediation``
    records (autopilot/engine.py; docs/AUTOPILOT.md): per-policy action
    counts split by status (applied / noop / failed and the explicit
    cooldown/budget suppressions), plus each firing's full arc — the
    alert id it answered, the action taken, and whether that alert
    later resolved. None when the stream carries no remediation
    records — the report stays byte-identical for pre-autopilot
    streams."""
    rems = [r for r in records if r.get("kind") == "remediation"]
    if not rems:
        return None
    resolved_ids = {r.get("id") for r in records
                    if r.get("kind") == "alert_resolved"
                    and r.get("id")}
    by_policy: dict = {}
    counts: dict = {}
    for r in rems:
        st = r.get("status") or "?"
        counts[st] = counts.get(st, 0) + 1
        e = by_policy.setdefault(str(r.get("policy")),
                                 {"action": r.get("action"),
                                  "statuses": {}})
        e["statuses"][st] = e["statuses"].get(st, 0) + 1
    lineage = [{
        "alert_id": r.get("alert_id"), "rule": r.get("rule"),
        "step": r.get("step"), "policy": r.get("policy"),
        "action": r.get("action"), "status": r.get("status"),
        "detail": r.get("detail"), "postmortem": r.get("postmortem"),
        "outcome": (("resolved" if r.get("alert_id") in resolved_ids
                     else "unresolved at stream end")
                    if r.get("alert_id") else None),
    } for r in rems]
    return {"remediations": len(rems), "statuses": counts,
            "by_policy": by_policy, "lineage": lineage}


def _jobs_summary(records: List[dict]) -> Optional[dict]:
    """Unified-runtime rollup (``--mode run``; runtime/, docs/RUNTIME.md)
    from the ``job`` / ``job_done`` / ``publish`` records: per-job state
    timeline, completion verdicts, publish latency, and the
    alert→job→publish lineage for trigger-born jobs. None when the
    stream carries none of the three kinds — the report stays
    byte-identical for pre-runtime streams."""
    job_recs = [r for r in records if r.get("kind") == "job"]
    dones = [r for r in records if r.get("kind") == "job_done"]
    pubs = [r for r in records if r.get("kind") == "publish"]
    if not job_recs and not dones and not pubs:
        return None
    by_job: dict = {}

    def ent(name):
        return by_job.setdefault(str(name), {
            "jtype": None, "timeline": [], "trigger": None,
            "ok": None, "secs": None, "error": None, "publishes": 0,
            "versions": []})

    for r in job_recs:
        e = ent(r.get("job"))
        e["jtype"] = r.get("jtype") or e["jtype"]
        e["timeline"].append({"state": r.get("state"), "t": r.get("t")})
        if r.get("trigger"):
            e["trigger"] = r["trigger"]
    for r in dones:
        e = ent(r.get("job"))
        e["jtype"] = r.get("jtype") or e["jtype"]
        e["ok"], e["secs"] = r.get("ok"), r.get("secs")
        if r.get("error"):
            e["error"] = r["error"]
    for r in pubs:
        if r.get("job") is not None and str(r["job"]) in by_job:
            e = by_job[str(r["job"])]
            e["publishes"] += 1
            e["versions"].append(r.get("version"))
    latencies = [r["latency_ms"] for r in pubs
                 if isinstance(r.get("latency_ms"), (int, float))]
    publish = None
    if pubs:
        publish = {
            "publishes": len(pubs),
            "swapped": sum(1 for r in pubs if r.get("swapped")),
            "latency_ms_mean": round(sum(latencies) / len(latencies), 3)
            if latencies else None,
            "latency_ms_max": round(max(latencies), 3)
            if latencies else None,
            "last_version": pubs[-1].get("version"),
            "last_step": pubs[-1].get("step"),
        }
    # Trigger lineage: an alert-born job carries trigger=<rule> on its
    # `job` records and stamps job=<name> on the publishes it commits —
    # the full alert → job → publish arc, read straight off the stream.
    lineage = [{"rule": e["trigger"], "job": name,
                "versions": e["versions"]}
               for name, e in sorted(by_job.items()) if e["trigger"]]
    return {"jobs": by_job, "publish": publish, "lineage": lineage}


def _net_summary(records: List[dict]) -> Optional[dict]:
    """Network-health rollup (parallel/net.py, utils/netfaults.py):
    per-operation and per-link ok/fail counters with classified error
    reasons off the rate-limited ``net`` records, the injected
    partition timeline off ``fault`` records with a ``net_*`` kind,
    cross-cell failover counts off ``cell_route``, and classified torn
    beats off ``beat_decode_error``. None when the stream carries none
    of them — file-transport streams render byte-identical."""
    nets = [r for r in records if r.get("kind") == "net"]
    net_faults = [r for r in records if r.get("kind") == "fault"
                  and str(r.get("fault") or "").startswith("net_")]
    routes = [r for r in records if r.get("kind") == "cell_route"]
    torn = [r for r in records
            if r.get("kind") == "beat_decode_error"]
    if not nets and not net_faults and not routes and not torn:
        return None
    ops: dict = {}
    errors: dict = {}
    links: dict = {}
    for r in nets:
        op = ops.setdefault(str(r.get("op")), {"ok": 0, "failed": 0})
        link = links.setdefault(r.get("task"),
                                {"ok": 0, "failed": 0, "max_ms": 0.0})
        bucket = "ok" if r.get("ok") else "failed"
        op[bucket] += 1
        link[bucket] += 1
        if isinstance(r.get("ms"), (int, float)):
            link["max_ms"] = round(max(link["max_ms"], r["ms"]), 1)
        if not r.get("ok"):
            err = str(r.get("error"))
            errors[err] = errors.get(err, 0) + 1
    crossings: dict = {}
    for r in routes:
        key = f"{r.get('from_cell')}->{r.get('to_cell')}"
        crossings[key] = crossings.get(key, 0) + 1
    return {
        "ops": ops,
        "errors": errors,
        "links": {str(t): v for t, v in sorted(
            links.items(), key=lambda kv: str(kv[0]))},
        "partitions": [
            {"fault": r.get("fault"), "step": r.get("step"),
             "task": r.get("task"), "isolate": r.get("isolate"),
             "duration_s": r.get("duration_s")} for r in net_faults],
        "cell_routes": {"count": len(routes), "crossings": crossings},
        "beat_decode_errors": len(torn),
    }


def _fmt_bytes(n: Optional[int]) -> str:
    if not n:
        return "-"
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024:
            return f"{n:.1f} {unit}"
        n /= 1024
    return f"{n:.1f} PiB"


def summarize(path: str) -> str:
    return summarize_records(load_records(path), path)


def _quant_summary(records: List[dict]) -> Optional[dict]:
    """Quantized-serving rollout view (docs/QUANT.md): calibration
    coverage (how many tensors, over how many batches, at what scales),
    publish-gate outcomes (a ``swap`` to a ``+int8`` version is an
    accept; ``quant_rejected`` is the gate holding the line), and how
    much traffic each variant actually answered — shared by the text
    and ``--format json`` paths. None when the stream has no
    quantization activity at all."""
    def _is_q(v) -> bool:
        return str(v).endswith("+int8")

    calibs = [r for r in records if r.get("kind") == "calibration"]
    rejects = [r for r in records if r.get("kind") == "quant_rejected"]
    accepts = [r for r in records if r.get("kind") == "swap"
               and _is_q(r.get("version"))]
    if not (calibs or rejects or accepts):
        return None
    out: dict = {}
    if calibs:
        acts = [r for r in calibs
                if str(r.get("tensor", "")).startswith("act/")]
        scales = [r.get("scale") for r in calibs
                  if isinstance(r.get("scale"), (int, float))]
        out["calibration"] = {
            "records": len(calibs),
            "weight_tensors": len(calibs) - len(acts),
            "act_tensors": len(acts),
            "batches": max((r.get("batches") or 0 for r in calibs),
                           default=0),
            "scale_min": min(scales) if scales else None,
            "scale_max": max(scales) if scales else None,
        }
    out["publishes"] = {"accepted": len(accepts),
                        "rejected": len(rejects)}
    if rejects:
        out["rejections"] = [
            {"version": r.get("version"),
             "replica_id": r.get("replica_id"),
             "delta": r.get("delta"), "max_delta": r.get("max_delta")}
            for r in rejects]
    # Traffic split: the fleet's cumulative version mix when the run
    # flushed one, summed windows otherwise (same fallback the fleet
    # health section uses).
    fleet_done = _last(records, "fleet_done")
    if fleet_done:
        mix = dict(fleet_done.get("version_mix") or {})
    else:
        mix = {}
        for r in records:
            if r.get("kind") == "fleet":
                for v, n in (r.get("version_mix") or {}).items():
                    mix[v] = mix.get(v, 0) + n
    if mix:
        out["traffic"] = {
            "by_version": mix,
            "int8": sum(n for v, n in mix.items() if _is_q(v)),
            "float": sum(n for v, n in mix.items() if not _is_q(v)),
        }
    return out


def summarize_records(records: List[dict], header: str) -> str:
    """The report body over an in-memory record list — the seam
    ``--follow`` re-renders from as the stream grows (no re-reading
    the whole file per refresh) and ``summarize`` wraps for the
    one-shot path."""
    lines = [f"== {header} =="]
    if not records:
        return "\n".join(lines + ["  (no records)"])

    done = _last(records, "done")
    trains = [r for r in records if r.get("kind") == "train"]
    if done or trains:
        step = (done or trains[-1]).get("step")
        lines.append(f"  steps: {step}")
    if done and done.get("images_per_sec"):
        lines.append(
            f"  run-average throughput: {done['images_per_sec']:.1f} "
            f"images/sec (drain-anchored, post-compile)")

    # Compile cost (compilecache/, docs/COMPILECACHE.md): where the
    # startup/restart compile seconds went and how much the cache saved
    # — the detail behind the goodput `compile` fraction below.
    compiles = [r for r in records if r.get("kind") == "compile"]
    if compiles:
        hits = [r for r in compiles if r.get("hit")]
        misses = [r for r in compiles if not r.get("hit")]
        total_s = sum(r.get("compile_s") or 0.0 for r in compiles)
        miss_s = sum(r.get("compile_s") or 0.0 for r in misses)
        lines.append(
            f"  compile cost: {len(compiles)} seam lookup(s), "
            f"{len(hits)} hit / {len(misses)} miss, {total_s:.2f} s "
            f"total ({miss_s:.2f} s compiling)")
        by_phase = {}
        for r in compiles:
            ph = by_phase.setdefault(r.get("phase") or "?",
                                     {"n": 0, "hits": 0, "s": 0.0})
            ph["n"] += 1
            ph["hits"] += 1 if r.get("hit") else 0
            ph["s"] += r.get("compile_s") or 0.0
        for phase in sorted(by_phase):
            d = by_phase[phase]
            lines.append(f"    {phase:<22} {d['n']:>3} lookup(s)  "
                         f"{d['hits']:>3} hit  {d['s']:8.2f} s")
        corrupt = sum(1 for r in compiles if r.get("source") == "corrupt")
        if corrupt:
            lines.append(f"    [{corrupt} corrupt cache entr"
                         f"{'y' if corrupt == 1 else 'ies'} dropped and "
                         f"recompiled (fail-open)]")

    gp = _last(records, "goodput") or _goodput_from_spans(records)
    if gp:
        total = gp.get("total_s") or 0.0
        lines.append(f"  goodput over {total:.2f} s wall-clock:")
        cats = ["train"] + list(GOODPUT_CATEGORIES)
        for cat in cats:
            frac = gp.get(f"{cat}_frac")
            if frac is None:
                continue
            lines.append(f"    {cat:<11} {100 * frac:6.2f} %"
                         f"  {frac * total:8.2f} s")
        covered = sum(gp.get(f"{c}_frac") or 0.0 for c in cats)
        lines.append(f"    {'(sum)':<11} {100 * covered:6.2f} %")
        if gp.get("dropped_spans"):
            lines.append(f"    [{gp['dropped_spans']} spans dropped by "
                         f"the ring buffer]")
    else:
        lines.append("  no goodput/span records (run without --telemetry)")
    slow = _slowest_boundary(records)
    if slow:
        lines.append(
            f"  slowest boundary interval: {slow['secs']:.4f} s ending at "
            f"step {slow['step']} (median {slow['median_secs']:.4f} s over "
            f"{slow['intervals']} intervals, x{slow['ratio']})")
        lines.append(f"    {'span':<24} {'in it':>10} {'median':>10} "
                     f"{'excess':>10}")
        for row in slow["spans"][:8]:
            lines.append(f"    {row['name']:<24} {row['secs']:>9.4f}s "
                         f"{row['median_secs']:>9.4f}s "
                         f"{row['excess_secs']:>+9.4f}s")

    health = [r for r in trains if "health_grad_norm" in r]
    if health:
        first, last = health[0], health[-1]
        gmax = max((r.get("health_grad_norm") or 0.0) for r in health)
        lines.append("  training health (first -> last boundary):")
        for key, label in (("health_grad_norm", "grad norm"),
                           ("health_param_norm", "param norm"),
                           ("health_update_ratio", "update ratio")):
            lines.append(f"    {label:<13} {first.get(key)} -> "
                         f"{last.get(key)}")
        lines.append(f"    max grad norm {gmax}")
    # Device-time split (utils/devprof.py): the always-on boundary
    # estimator answers device-bound vs host-bound; the devtime table
    # (a --profile_at_steps capture) answers WHICH ops own the device.
    dev_split = _device_split(trains)
    if dev_split:
        lines.append(
            f"  device step time (boundary estimator, "
            f"{dev_split['boundaries']} boundaries):")
        lines.append(
            f"    device_step p50 {dev_split['device_step_ms_p50']} ms, "
            f"drain-wait p50 {dev_split['drain_wait_ms_p50']} ms per "
            f"boundary")
        if dev_split.get("device_busy_frac") is not None:
            lines.append(
                f"    device-busy ~{100 * dev_split['device_busy_frac']:.0f} "
                f"% of the step window "
                f"({'device' if dev_split['device_busy_frac'] > 0.5 else 'host'}-bound)")
    devtimes = [r for r in records if r.get("kind") == "devtime"]
    if devtimes:
        lines.append("  device-time attribution (--profile_at_steps):")
        newest_step = max(r.get("step") or 0 for r in devtimes)
        for r in devtimes:
            if (r.get("step") or 0) != newest_step:
                continue
            lines.append(
                f"    {r.get('device')}: {r.get('total_ms')} ms "
                f"attributed (compute {r.get('compute_ms')} / "
                f"collective {r.get('collective_ms')} / infeed "
                f"{r.get('infeed_ms')}) over a {r.get('window_ms')} ms "
                f"window")
            for op in (r.get("top_ops") or [])[:5]:
                lines.append(
                    f"      {op.get('name', '?')[:44]:<44} "
                    f"{op.get('dur_ms', 0):>9.2f} ms "
                    f"{100 * (op.get('frac') or 0):5.1f}%  "
                    f"[{op.get('bucket')}] x{op.get('calls')}")
    serve = _last(records, "serve_done")
    if serve is None:
        # A server that died before the final flush still has windows.
        windows = [r for r in records if r.get("kind") == "serve"]
        if windows:
            serve = windows[-1]
    if serve:
        span = serve.get("total_s") or serve.get("window_s") or 0.0
        lines.append(f"  serving over {span:.2f} s "
                     f"({'final' if serve['kind'] == 'serve_done' else 'last window'}):")
        lines.append(
            f"    {serve.get('completed')}/{serve.get('requests')} "
            f"completed at {serve.get('qps')} qps; shed "
            f"{serve.get('shed_queue')} queue-full + "
            f"{serve.get('shed_deadline')} deadline")
        if serve.get("p50_ms") is not None:
            lines.append(
                f"    latency p50/p95/p99: {serve.get('p50_ms')} / "
                f"{serve.get('p95_ms')} / {serve.get('p99_ms')} ms "
                f"(queue-wait p50 {serve.get('queue_wait_p50_ms')} ms, "
                f"device p50 {serve.get('device_p50_ms')} ms)")
        if serve.get("batch_fill") is not None:
            lines.append(
                f"    {serve.get('batches')} batches, mean fill "
                f"{100 * serve['batch_fill']:.1f} %")
        warm = [r for r in compiles if r.get("phase") == "serve_warmup"]
        if warm:
            whits = sum(1 for r in warm if r.get("hit"))
            wtotal = sum(r.get("compile_s") or 0.0 for r in warm)
            lines.append(
                f"    warmup: {len(warm)} bucket(s) ready in "
                f"{wtotal:.2f} s total ({whits} cache hit(s), "
                f"{len(warm) - whits} compile(s))")
    # Request tracing (utils/reqtrace.py; docs/OBSERVABILITY.md
    # Request-tracing section): which hop ate a slow request's latency,
    # from this stream's rspan records.
    hopbd = _hop_breakdown(records)
    if hopbd:
        lines.append(
            f"  request tracing: {hopbd['spans']} span(s) across "
            f"{hopbd['traces']} trace(s)")
        for h in hopbd["hops"]:
            lines.append(
                f"    {h['hop']:<10} {h['spans']:>5} span(s)  "
                f"p50 {h['p50_ms']:>9.3f} ms  p99 {h['p99_ms']:>9.3f} ms")
        if hopbd["slowest"]:
            lines.append("    slowest traces (sum of hop durations):")
            for t in hopbd["slowest"]:
                per = ", ".join(f"{hop} {ms}"
                                for hop, ms in sorted(t["hops"].items()))
                ver = f" v{t['version']}" if t.get("version") else ""
                lines.append(
                    f"      {t['trace_id']}: {t['total_ms']:.3f} ms"
                    f"{ver} ({per})")
    # Fleet health (fleet/; docs/SERVING.md fleet section): replica
    # count over time, routing/eviction counters, hot-swap latency, and
    # what the autoscaler decided — the stream-side answer to "did the
    # fleet layer keep the rollout invisible to clients".
    fleets = [r for r in records if r.get("kind") == "fleet"]
    fleet_done = _last(records, "fleet_done")
    swaps = [r for r in records if r.get("kind") == "swap"]
    swap_rejects = [r for r in records
                    if r.get("kind") == "swap_rejected"]
    scales = [r for r in records if r.get("kind") == "scale"]
    publishes = [r for r in records if r.get("kind") == "fleet_publish"]
    if fleets or fleet_done or swaps or swap_rejects or scales \
            or publishes:
        lines.append("  fleet health:")
        if fleets or fleet_done:
            series = fleets or [fleet_done]
            live_series = [r.get("live") or 0 for r in series]
            last = series[-1]
            lines.append(
                f"    replicas over {len(series)} window(s): live "
                f"min {min(live_series)} / max {max(live_series)}, "
                f"final {last.get('live')}/{last.get('replicas')}")
            # Totals from the cumulative final record when the run
            # flushed one; summed per-window deltas otherwise (a
            # router that died mid-run).
            total = fleet_done or {
                k: sum(r.get(k) or 0 for r in fleets)
                for k in ("routed", "rerouted", "evictions", "shed")}
            lines.append(
                f"    routed {total.get('routed')} request(s), "
                f"{total.get('rerouted')} re-routed, "
                f"{total.get('evictions')} eviction(s), "
                f"{total.get('shed')} shed")
            if fleet_done:
                mix = dict(fleet_done.get("version_mix") or {})
            else:
                mix = {}
                for r in fleets:
                    for v, n in (r.get("version_mix") or {}).items():
                        mix[v] = mix.get(v, 0) + n
            if mix:
                per = ", ".join(f"v{v}: {n}"
                                for v, n in sorted(mix.items()))
                lines.append(f"    version mix: {per}")
        for r in publishes:
            lines.append(f"    published version {r.get('version')} "
                         f"(seq {r.get('seq')})")
        if swaps:
            ms = [r.get("swap_ms") or 0.0 for r in swaps]
            lines.append(
                f"    {len(swaps)} hot-swap(s), swap latency mean "
                f"{sum(ms) / len(ms):.1f} / max {max(ms):.1f} ms")
            for r in swaps:
                lines.append(
                    f"      replica {r.get('replica_id')}: "
                    f"{r.get('from_version')} -> {r.get('version')}")
        for r in swap_rejects:
            lines.append(
                f"    swap REJECTED on replica {r.get('replica_id')} "
                f"(version {r.get('version')}): {r.get('reason')}")
        for r in scales:
            lines.append(
                f"    autoscale {r.get('action')} "
                f"({r.get('reason')}) -> {r.get('replicas')} worker(s)")
        # Per-replica device time, from the newest fleet window that
        # carries the beats' advertised device_ms: a replica whose
        # device_ms is ~uniform with its peers but whose queue is deep
        # is overloaded (scale up); one whose device_ms is the outlier
        # is a slow DEVICE (drain + replace) — visible here without
        # raw beat-file spelunking.
        dev_rows = [r for r in fleets + ([fleet_done] if fleet_done
                                         else [])
                    if r.get("device_ms")]
        if dev_rows:
            per = ", ".join(
                f"r{rid}: {ms} ms" for rid, ms in
                sorted(dev_rows[-1]["device_ms"].items()))
            lines.append(f"    per-replica device_ms (beats, last "
                         f"window): {per}")
    # Quantized serving (quant/; docs/QUANT.md): calibration coverage,
    # what the publish-time accuracy gate decided, and the float/int8
    # traffic split — the stream-side answer to "is the fleet actually
    # serving the quantized variant, and did anything get rejected on
    # the way there".
    quant = _quant_summary(records)
    if quant:
        lines.append("  quantization (int8 serving):")
        cal = quant.get("calibration")
        if cal:
            rng = ""
            if cal["scale_min"] is not None:
                rng = (f", scales [{cal['scale_min']:.3g}, "
                       f"{cal['scale_max']:.3g}]")
            lines.append(
                f"    calibration: {cal['weight_tensors']} weight / "
                f"{cal['act_tensors']} activation tensor record(s) "
                f"over {cal['batches']} batch(es){rng}")
        pub = quant["publishes"]
        lines.append(f"    publish gate: {pub['accepted']} accepted, "
                     f"{pub['rejected']} rejected")
        for r in quant.get("rejections", []):
            lines.append(
                f"      REJECTED {r['version']} on replica "
                f"{r['replica_id']}: top-1 delta {r['delta']:+.4f} > "
                f"max {r['max_delta']:.4f}")
        tr = quant.get("traffic")
        if tr:
            lines.append(
                f"    traffic mix: {tr['int8']} int8 / {tr['float']} "
                f"float response(s)")
    # Alerting (utils/alerts.py; docs/OBSERVABILITY.md Alerting
    # section): what fired while the run was live, what resolved, and
    # what was STILL firing when the stream ended — the post-hoc view
    # of the live alert state.
    alert_recs = [r for r in records if r.get("kind") == "alert"]
    resolved_recs = [r for r in records
                     if r.get("kind") == "alert_resolved"]
    if alert_recs or resolved_recs:
        lines.append(f"  alerts: {len(alert_recs)} fired, "
                     f"{len(resolved_recs)} resolved")
        # Sequential pairing (fire/resolve/fire again = active): the
        # rules still firing are the ones whose LAST event is a fire.
        still_active = {}
        for r in records:
            if r.get("kind") == "alert":
                still_active[r.get("rule")] = r
            elif r.get("kind") == "alert_resolved":
                still_active.pop(r.get("rule"), None)
        for r in alert_recs:
            state = "STILL ACTIVE at stream end" \
                if still_active.get(r.get("rule")) is r else "resolved"
            lines.append(
                f"    [{r.get('severity')}] {r.get('rule')} fired at "
                f"t={r.get('t')}s (value {r.get('value')}, window "
                f"{r.get('window')}) — {state}")
    # Autopilot (--autopilot; autopilot/engine.py, docs/AUTOPILOT.md):
    # the alert → remediation → outcome lineage — which policy answered
    # each firing, what it did, whether the alert then resolved, and
    # how many firings the cooldown/budget gates suppressed.
    ap = _autopilot_summary(records)
    if ap:
        st = ap["statuses"]
        lines.append(
            f"  autopilot: {ap['remediations']} remediation(s) — "
            f"{st.get('applied', 0)} applied, "
            f"{st.get('noop', 0)} noop, {st.get('failed', 0)} failed, "
            f"{st.get('suppressed_cooldown', 0)} cooldown-suppressed, "
            f"{st.get('suppressed_budget', 0)} budget-suppressed")
        for name, e in sorted(ap["by_policy"].items()):
            per = ", ".join(f"{s}: {n}"
                            for s, n in sorted(e["statuses"].items()))
            lines.append(f"    policy {name} ({e['action']}): {per}")
        for arc in ap["lineage"]:
            pm = f", postmortem {arc['postmortem']}" \
                if arc.get("postmortem") else ""
            det = f" ({arc['detail']})" if arc.get("detail") else ""
            lines.append(
                f"    {arc['alert_id']} [{arc['rule']}] -> "
                f"{arc['policy']}/{arc['action']}: {arc['status']}"
                f"{det} — alert {arc['outcome']}{pm}")
    # Unified runtime (--mode run; runtime/, docs/RUNTIME.md): the job
    # lifecycle timeline, the in-process publish latency, and the
    # alert→job→publish lineage for any trigger-born fine-tunes.
    jobs = _jobs_summary(records)
    if jobs:
        lines.append("  runtime jobs:")
        for name, e in sorted(jobs["jobs"].items()):
            arc = " -> ".join(t["state"] for t in e["timeline"]) \
                or "(no transitions)"
            tail = ""
            if e["secs"] is not None:
                verdict = "ok" if e["ok"] else "FAILED"
                tail = f" ({verdict} in {e['secs']} s)"
            trig = f" [trigger: {e['trigger']}]" if e["trigger"] else ""
            npub = (f", {e['publishes']} publish(es)"
                    if e["publishes"] else "")
            lines.append(f"    {name} ({e['jtype']}): {arc}"
                         f"{tail}{trig}{npub}")
            if e["error"]:
                lines.append(f"      error: {e['error']}")
        pub = jobs["publish"]
        if pub:
            lines.append(
                f"    publishes: {pub['publishes']} "
                f"({pub['swapped']} swapped), latency mean "
                f"{pub['latency_ms_mean']} / max {pub['latency_ms_max']} "
                f"ms, last version {pub['last_version']} "
                f"(step {pub['last_step']})")
        for arc in jobs["lineage"]:
            vers = ", ".join(str(v) for v in arc["versions"]) or "none"
            lines.append(
                f"    lineage: alert {arc['rule']!r} -> {arc['job']} -> "
                f"published version(s) {vers}")
    # Resilience events (docs/RESILIENCE.md): how many faults the run
    # absorbed, and what the recovery path did about them.
    faults = [r for r in records if r.get("kind") == "fault"]
    recoveries = [r for r in records if r.get("kind") == "recovery"]
    fallbacks = [r for r in records if r.get("kind") == "ckpt_fallback"]
    prune_errs = [r for r in records
                  if r.get("kind") == "ckpt_prune_error"]
    if faults or recoveries or fallbacks or prune_errs:
        injected = sum(1 for r in faults if r.get("injected"))
        lines.append(
            f"  resilience: {len(faults)} fault(s) "
            f"({injected} injected), {len(recoveries)} recovery "
            f"action(s), {len(fallbacks)} checkpoint fallback(s)")
        for r in recoveries:
            lines.append(
                f"    step {r.get('step')}: {r.get('fault')} -> "
                f"{r.get('action')} (attempt {r.get('attempt')})")
        rb = _last(records, "rollback")
        if rb:
            lines.append(
                f"    last rollback restored step "
                f"{rb.get('restore_step')} at lr {rb.get('lr')}")
        if prune_errs:
            lines.append(
                f"    [{len(prune_errs)} checkpoint prune failure(s) — "
                f"old checkpoints may be accumulating]")
    # Restore source (ckpt/peerstore.py, docs/RESILIENCE.md diskless-
    # recovery section): which elastic restarts skipped checkpoint I/O
    # entirely (source=peer), how long lost-shard reconstruction took,
    # and how stale the replica set was at each decide seam.
    peer = _peer_summary(records)
    if peer:
        lines.append(
            f"  restore source: {peer['peer_restores']} peer / "
            f"{peer['disk_restores']} disk elastic restore(s), "
            f"{peer['pushes']} replica push(es), "
            f"{peer['fallbacks']} peer->disk fallback(s)")
        if peer.get("reconstructs"):
            lines.append(
                f"    lost-shard reconstructs: {peer['reconstructs']} "
                f"(mean {peer.get('reconstruct_mean_s')}s, max "
                f"{peer.get('reconstruct_max_s')}s)")
        if peer.get("decide_staleness") is not None:
            lines.append(
                f"    replica staleness at decide: "
                f"{peer['decide_staleness']} step(s) behind the beats")
    # Chaos campaign (tools/chaos.py; docs/RESILIENCE.md): schedules
    # run, the fault mix they injected, which invariants failed (with
    # the shrunk reproducer specs), and the slowest observed
    # fault→recovery latency.
    chaos_runs = [r for r in records if r.get("kind") == "chaos"]
    chaos_done = _chaos_totals(records)
    if chaos_runs or chaos_done:
        lines.append("  chaos campaign:")
        n = chaos_done.get("schedules") if chaos_done else len(chaos_runs)
        passed = chaos_done.get("passed") if chaos_done \
            else sum(1 for r in chaos_runs if r.get("ok"))
        failed = chaos_done.get("failed") if chaos_done \
            else sum(1 for r in chaos_runs if not r.get("ok"))
        lines.append(f"    {n} schedule(s) run: {passed} passed, "
                     f"{failed} failed")
        by_kind = (chaos_done or {}).get("faults_by_kind") or {}
        if by_kind:
            per = ", ".join(f"{k}: {v}"
                            for k, v in sorted(by_kind.items()))
            lines.append(f"    faults injected by kind: {per}")
        for r in chaos_runs:
            if r.get("ok"):
                continue
            lines.append(
                f"    FAILED seed {r.get('seed')} "
                f"[{r.get('scenario')}] \"{r.get('spec')}\": "
                f"{r.get('invariant')}")
            if r.get("reproducer"):
                lines.append(
                    f"      minimal reproducer: --fault_spec "
                    f"\"{r.get('reproducer')}\"")
        slow = (chaos_done or {}).get("slowest_recovery_s")
        if slow is not None:
            lines.append(f"    slowest recovery: {slow:.2f} s "
                         f"(fault record -> recovery record)")
    # Corrupt restart-decision reads (parallel/cluster.py sidecar
    # check): each one was classified and read as absent, never
    # adopted — but a recurring one means the shared filesystem is
    # serving garbage.
    dcorr = [r for r in records if r.get("kind") == "decision_corrupt"]
    if dcorr:
        lines.append(f"  decision-file corruption: {len(dcorr)} "
                     f"classified corrupt read(s)")
        for r in dcorr[:3]:
            lines.append(f"    {r.get('path')}: {r.get('error')}")
    # Cluster health (parallel/cluster.py): beat cadence per process,
    # straggler pressure, peer deaths, elastic restarts AND expands —
    # the stream-side answer to "did the cluster layer earn its keep".
    beats = [r for r in records if r.get("kind") == "heartbeat"]
    stragglers = [r for r in records if r.get("kind") == "straggler"]
    losses = [r for r in records if r.get("kind") == "peer_lost"]
    restarts = [r for r in records if r.get("kind") == "elastic_restart"]
    expands = [r for r in records if r.get("kind") == "elastic_expand"]
    rejoins = [r for r in records if r.get("kind") == "host_rejoin"]
    if beats or stragglers or losses or restarts or expands or rejoins:
        lines.append("  cluster health:")
        by_pid = {}
        for r in beats:
            by_pid.setdefault(r.get("process_id"), []).append(
                r.get("t") or 0.0)
        for pid in sorted(by_pid, key=lambda p: (p is None, p)):
            ts = by_pid[pid]
            gap = max((b - a for a, b in zip(ts, ts[1:])), default=0.0)
            lines.append(
                f"    process {pid}: {len(ts)} heartbeat(s), max gap "
                f"{gap:.2f} s")
        if stragglers:
            counts = {}
            for r in stragglers:
                counts[r.get("process_id")] = \
                    counts.get(r.get("process_id"), 0) + 1
            worst = max(r.get("behind_steps") or 0 for r in stragglers)
            per = ", ".join(f"proc {p}: {n}"
                            for p, n in sorted(counts.items(),
                                               key=lambda kv: str(kv[0])))
            lines.append(f"    stragglers: {len(stragglers)} event(s) "
                         f"({per}); worst lag {worst} step(s)")
        for r in losses:
            lines.append(
                f"    peer_lost: process {r.get('process_id')} at step "
                f"{r.get('step')} ({r.get('reason')})")
        for r in rejoins:
            lines.append(
                f"    host_rejoin: process {r.get('process_id')} "
                f"announced at step {r.get('step')} "
                f"(epoch {r.get('epoch')})")
        for r in restarts:
            lines.append(
                f"    elastic restart epoch {r.get('epoch')}: world "
                f"size {r.get('world_size')}, restored step "
                f"{r.get('restore_step')}")
        for r in expands:
            lines.append(
                f"    elastic expand epoch {r.get('epoch')}: world "
                f"size {r.get('world_size')} "
                f"(joined {r.get('joined')}), restored step "
                f"{r.get('restore_step')}")
        transitions = sorted(restarts + expands,
                             key=lambda r: (r.get("epoch") or 0))
        if transitions:
            # The world-size timeline in one line: every adopted
            # shrink/expand decision in epoch order.
            arc = " -> ".join(
                f"{r.get('world_size')}"
                f"[{'expand' if r.get('kind') == 'elastic_expand' else 'shrink'}"
                f"@{r.get('step')}]" for r in transitions)
            lines.append(f"    world-size timeline: {arc}")
    # Network health (parallel/net.py `net` records + injected net_*
    # faults + cell_route crossings): what the coordination transport
    # saw per link, and where the chaos partitions landed.
    net = _net_summary(records)
    if net:
        lines.append("  network health:")
        if net["ops"]:
            per = ", ".join(
                f"{op} {v['ok']} ok / {v['failed']} failed"
                for op, v in sorted(net["ops"].items()))
            lines.append(f"    transport ops: {per}")
        if net["errors"]:
            per = ", ".join(f"{e}: {n}" for e, n in
                            sorted(net["errors"].items()))
            lines.append(f"    classified errors: {per}")
        for task, v in net["links"].items():
            lines.append(
                f"    link proc {task}: {v['ok']} ok / "
                f"{v['failed']} failed, slowest {v['max_ms']:.1f} ms")
        for p in net["partitions"]:
            lines.append(
                f"    injected {p['fault']} at step {p['step']} "
                f"(proc {p['task']}, isolate {p['isolate']}, "
                f"duration {p['duration_s']} s)")
        if net["cell_routes"]["count"]:
            per = ", ".join(
                f"{k}: {n}" for k, n in
                sorted(net["cell_routes"]["crossings"].items()))
            lines.append(
                f"    cross-cell failovers: "
                f"{net['cell_routes']['count']} ({per})")
        if net["beat_decode_errors"]:
            lines.append(
                f"    torn beats classified: "
                f"{net['beat_decode_errors']} (beat_decode_error)")
    # Sharded fast-resume breakdown (ckpt/sharded.py `shard_io` rows):
    # how many shard files moved, how many bytes, and the slowest shard
    # — the wall-clock of a concurrent phase is its slowest member.
    sios = [r for r in records if r.get("kind") == "shard_io"]
    if sios:
        lines.append("  shard io:")
        for op in ("save", "restore"):
            rows = [r for r in sios if r.get("op") == op]
            if not rows:
                continue
            nbytes = sum(r.get("bytes") or 0 for r in rows)
            secs = [r.get("secs") or 0.0 for r in rows]
            fails = sum(1 for r in rows if r.get("verify") is False)
            lines.append(
                f"    {op}: {len(rows)} shard(s), {_fmt_bytes(nbytes)}, "
                f"{sum(secs):.3f} s io (slowest {max(secs):.3f} s), "
                f"{fails} verify failure(s)")
        legacy = [r for r in sios if r.get("op") == "legacy_glob"]
        for r in legacy:
            lines.append(
                f"    [legacy manifest without shard_files restored "
                f"via glob: {r.get('shard')}]")
    hbm = _last(records, "hbm")
    if hbm:
        if hbm.get("available"):
            lines.append(
                f"  HBM ({hbm.get('devices')} local devices): "
                f"{_fmt_bytes(hbm.get('bytes_in_use'))} in use, "
                f"peak {_fmt_bytes(hbm.get('peak_bytes'))}, "
                f"limit {_fmt_bytes(hbm.get('bytes_limit'))}")
        else:
            lines.append("  HBM: backend reports no memory stats")
    return "\n".join(lines)


def summarize_json(path: str) -> dict:
    """Machine-readable summary of one stream — the ``--format json``
    payload the perf gate / CI consumes. Same sections as the text
    renderer (which stays the default), plainly keyed."""
    records = load_records(path)
    out: dict = {"path": path, "records": len(records)}
    done = _last(records, "done")
    trains = [r for r in records if r.get("kind") == "train"]
    if done or trains:
        out["steps"] = (done or trains[-1]).get("step")
    if done:
        out["images_per_sec"] = done.get("images_per_sec")
    gp = _last(records, "goodput") or _goodput_from_spans(records)
    if gp:
        out["goodput"] = {k: v for k, v in gp.items()
                          if k not in ("kind", "t", "task")}
    slow = _slowest_boundary(records)
    if slow:
        out["slowest_boundary"] = slow
    compiles = [r for r in records if r.get("kind") == "compile"]
    if compiles:
        misses = [r for r in compiles if not r.get("hit")]
        out["compile"] = {
            "lookups": len(compiles),
            "hits": len(compiles) - len(misses),
            "misses": len(misses),
            "total_s": round(sum(r.get("compile_s") or 0.0
                                 for r in compiles), 3),
            "miss_s": round(sum(r.get("compile_s") or 0.0
                                for r in misses), 3),
        }
    health = [r for r in trains if "health_grad_norm" in r]
    if health:
        out["health"] = {
            "first_grad_norm": health[0].get("health_grad_norm"),
            "last_grad_norm": health[-1].get("health_grad_norm"),
            "max_grad_norm": max((r.get("health_grad_norm") or 0.0)
                                 for r in health),
            "last_update_ratio": health[-1].get("health_update_ratio"),
        }
    dev_split = _device_split(trains)
    if dev_split:
        out["device_split"] = dev_split
    devtimes = [r for r in records if r.get("kind") == "devtime"]
    if devtimes:
        out["devtime"] = [
            {k: v for k, v in r.items() if k not in ("kind", "t", "task")}
            for r in devtimes]
    serve = _last(records, "serve_done") or _last(records, "serve")
    if serve:
        out["serve"] = {k: v for k, v in serve.items()
                        if k not in ("kind", "t", "task")}
    hopbd = _hop_breakdown(records)
    if hopbd:
        out["request_tracing"] = hopbd
    fleet_done = _last(records, "fleet_done") \
        or _last(records, "fleet")
    if fleet_done:
        out["fleet"] = {k: v for k, v in fleet_done.items()
                        if k not in ("kind", "t", "task")}
        out["fleet"]["swaps"] = sum(1 for r in records
                                    if r.get("kind") == "swap")
        out["fleet"]["scales"] = sum(1 for r in records
                                     if r.get("kind") == "scale")
    quant = _quant_summary(records)
    if quant:
        out["quant"] = quant
    chaos_runs = [r for r in records if r.get("kind") == "chaos"]
    chaos_done = _chaos_totals(records)
    if chaos_runs or chaos_done:
        out["chaos"] = {
            "schedules": (chaos_done or {}).get("schedules",
                                                len(chaos_runs)),
            "passed": (chaos_done or {}).get(
                "passed", sum(1 for r in chaos_runs if r.get("ok"))),
            "failed": (chaos_done or {}).get(
                "failed",
                sum(1 for r in chaos_runs if not r.get("ok"))),
            "faults_by_kind": (chaos_done or {}).get("faults_by_kind"),
            "slowest_recovery_s": (chaos_done or {}).get(
                "slowest_recovery_s"),
            "failures": [
                {"seed": r.get("seed"), "spec": r.get("spec"),
                 "invariant": r.get("invariant"),
                 "reproducer": r.get("reproducer")}
                for r in chaos_runs if not r.get("ok")],
        }
    alert_recs = [r for r in records if r.get("kind") == "alert"]
    resolved_recs = [r for r in records
                     if r.get("kind") == "alert_resolved"]
    if alert_recs or resolved_recs:
        still_active = {}
        for r in records:
            if r.get("kind") == "alert":
                still_active[r.get("rule")] = r
            elif r.get("kind") == "alert_resolved":
                still_active.pop(r.get("rule"), None)
        out["alerts"] = {
            "fired": len(alert_recs),
            "resolved": len(resolved_recs),
            "active": [
                {"rule": r.get("rule"), "severity": r.get("severity"),
                 "value": r.get("value"), "window": r.get("window")}
                for r in still_active.values()],
        }
    ap = _autopilot_summary(records)
    if ap:
        out["autopilot"] = ap
    jobs = _jobs_summary(records)
    if jobs:
        out["jobs"] = jobs
    faults = [r for r in records if r.get("kind") == "fault"]
    recoveries = [r for r in records if r.get("kind") == "recovery"]
    if faults or recoveries:
        out["resilience"] = {
            "faults": len(faults),
            "injected": sum(1 for r in faults if r.get("injected")),
            "recoveries": len(recoveries),
            "ckpt_fallbacks": sum(1 for r in records
                                  if r.get("kind") == "ckpt_fallback"),
        }
    peer = _peer_summary(records)
    if peer:
        out.setdefault("resilience", {})["restore_source"] = peer
    beats = [r for r in records if r.get("kind") == "heartbeat"]
    losses = [r for r in records if r.get("kind") == "peer_lost"]
    transitions = [r for r in records
                   if r.get("kind") in ("elastic_restart",
                                        "elastic_expand")]
    if beats or losses or transitions:
        out["cluster"] = {
            "heartbeats": len(beats),
            "stragglers": sum(1 for r in records
                              if r.get("kind") == "straggler"),
            "peer_losses": [{"process_id": r.get("process_id"),
                             "step": r.get("step"),
                             "reason": r.get("reason")} for r in losses],
            "world_size_timeline": [
                {"kind": r["kind"], "epoch": r.get("epoch"),
                 "world_size": r.get("world_size"),
                 "step": r.get("step")}
                for r in sorted(transitions,
                                key=lambda r: (r.get("epoch") or 0))],
        }
    net = _net_summary(records)
    if net:
        out["network"] = net
    hbm = _last(records, "hbm")
    if hbm and hbm.get("available"):
        out["hbm"] = {k: hbm.get(k) for k in
                      ("devices", "bytes_in_use", "peak_bytes",
                       "bytes_limit")}
    return out


def follow(paths: List[str], refresh_s: float = 2.0,
           max_refreshes: Optional[int] = None, clear: bool = True,
           out=None) -> int:
    """Incremental tail mode (``--follow``): re-render the summary as
    the JSONL streams grow, sharing the live monitor's tailing helper
    (``tools/live_monitor.py``). Exits when every stream has flushed
    its final record (``done``/``serve_done``/``fleet_done``), on
    Ctrl-C, or after ``max_refreshes`` (test/batch bound)."""
    from tools.live_monitor import FINAL_KINDS, JsonlTail
    out = sys.stdout if out is None else out
    tails = {p: JsonlTail(p) for p in paths}
    records = {p: [] for p in paths}
    n = 0
    while True:
        for p, tail in tails.items():
            records[p].extend(tail.poll())
        if clear and n > 0 and out is sys.stdout:
            out.write("\x1b[2J\x1b[H")
        for p in paths:
            print(summarize_records(records[p],
                                    f"{p} (following)"), file=out)
        n += 1
        finished = all(
            any(r.get("kind") in FINAL_KINDS for r in records[p])
            for p in paths) and paths
        if finished or (max_refreshes is not None
                        and n >= max_refreshes):
            return 0
        try:
            time.sleep(refresh_s)
        except KeyboardInterrupt:
            return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    fmt = "text"
    usage = ("usage: telemetry_report.py [--format text|json] "
             "[--follow [--refresh S]] run.jsonl [more.jsonl ...]")
    if "--format" in argv:
        i = argv.index("--format")
        try:
            fmt = argv[i + 1]
        except IndexError:
            fmt = ""
        del argv[i:i + 2]
        if fmt not in ("text", "json"):
            print(usage)
            return 2
    follow_mode = "--follow" in argv
    if follow_mode:
        argv.remove("--follow")
    refresh_s = 2.0
    if "--refresh" in argv:
        i = argv.index("--refresh")
        try:
            refresh_s = float(argv[i + 1])
        except (IndexError, ValueError):
            print(usage)
            return 2
        del argv[i:i + 2]
    if not argv:
        print(usage)
        return 2
    if follow_mode:
        if fmt != "text":
            print("--follow renders text only")
            return 2
        return follow(argv, refresh_s=refresh_s)
    if fmt == "json":
        docs = [summarize_json(path) for path in argv]
        print(json.dumps(docs[0] if len(docs) == 1
                         else {"reports": docs}))
        return 0
    for path in argv:
        print(summarize(path))
    return 0


if __name__ == "__main__":
    sys.exit(main())

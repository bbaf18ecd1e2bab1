#!/usr/bin/env python
"""Merge the per-process JSONL streams of a
multi-host run into ONE run-level timeline.

Cluster simulations (``parallel/cluster.py``), real multi-host jobs, and
serving fleets (``fleet/``) each scatter one ``--metrics_jsonl`` stream
per process, every
stream with its OWN clock zero (``t`` is seconds since that logger
started). Post-mortems of cross-host behavior — who stalled, who
restarted whom, how far the hosts' steps skewed — need those streams on
one clock. This tool:

- recovers a per-stream unix offset from the ``heartbeat`` records'
  ``wallclock`` field (median of ``wallclock − t``), falling back to
  the ``serve``/``fleet`` window records' wallclock anchors for serving
  processes, which publish no heartbeats; streams with neither stay
  unaligned and are flagged,
- merges records onto one timeline keyed by ``(task, step)``, with a
  per-host step-skew table (first-seen wall-clock spread of each step
  observed on ≥ 2 aligned hosts) and a straggler bar view,
- collects the run's notable events (faults, peer losses, elastic
  restarts/expands, rejoins, autoscales, swaps) in aligned order,
- summarizes fleet request flow (serve windows per replica, router
  routing/eviction counters),
- optionally writes ONE merged Perfetto/Chrome trace (``--out``):
  host-loop span lanes per process (rebuilt from ``span`` records),
  instant events for the notable kinds, counter tracks for
  ``images_per_sec`` / ``device_step_ms``, request-tracing hop lanes
  rebuilt from ``rspan`` records with one Chrome flow arrow per
  ``trace_id`` linking a request's hops across processes.

Usage:
  python tools/trace_aggregate.py logs_0/m.jsonl logs_1/m.jsonl \\
      [--out merged_trace.json] [--format text|json]

``tests/test_cluster.py`` runs this over the 2-process lockstep sim's
streams in tier-1 and pins that the merged per-host step counts match
the individual streams exactly.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
from typing import Dict, List, Optional

#: Record kinds surfaced on the merged event timeline.
EVENT_KINDS = ("fault", "recovery", "rollback", "peer_lost",
               "elastic_restart", "elastic_expand", "host_rejoin",
               "preempt", "numerics_halt", "scale", "swap",
               "swap_rejected", "ckpt_fallback")


def load_stream(path: str) -> List[dict]:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                try:
                    out.append(json.loads(line))
                except ValueError:
                    continue
    return out


def _median(vals):
    vals = sorted(vals)
    if not vals:
        return None
    mid = len(vals) // 2
    return vals[mid] if len(vals) % 2 else (vals[mid - 1] + vals[mid]) / 2


#: Kinds whose ``wallclock`` anchors clock alignment, in preference
#: order: heartbeats when the stream has them (training / cluster),
#: else the serve/fleet window records — serving processes publish no
#: heartbeats, and without this fallback every fleet stream was flagged
#: unalignable.
ANCHOR_KINDS = (("heartbeat",), ("serve", "serve_done",
                                 "fleet", "fleet_done"))


def clock_offset(records: List[dict]) -> Optional[float]:
    """Unix seconds at this stream's ``t == 0``, recovered from the
    ``wallclock`` anchors of heartbeat records — or, for serve/fleet
    streams that have none, of their periodic window records. None when
    no anchor kind carries a wallclock."""
    for kinds in ANCHOR_KINDS:
        deltas = [r["wallclock"] - r["t"] for r in records
                  if r.get("kind") in kinds
                  and isinstance(r.get("wallclock"), (int, float))
                  and isinstance(r.get("t"), (int, float))]
        if deltas:
            return _median(deltas)
    return None


def summarize_host(path: str, records: List[dict]) -> dict:
    tasks = [r.get("task") for r in records if r.get("task") is not None]
    task = collections.Counter(tasks).most_common(1)[0][0] if tasks else 0
    kinds = collections.Counter(r.get("kind") for r in records)
    train_steps = [r.get("step") for r in records
                   if r.get("kind") == "train"]
    steps = [r.get("step") for r in records
             if isinstance(r.get("step"), int)]
    return {
        "path": path,
        "task": task,
        "records": len(records),
        "kinds": dict(kinds),
        "offset_unix": clock_offset(records),
        "train_rows": len(train_steps),
        "train_steps": train_steps,
        "last_step": max(steps) if steps else None,
        "heartbeats": kinds.get("heartbeat", 0),
    }


def aggregate(paths: List[str]) -> dict:
    """Merge streams → hosts summary, (task, step) timeline, step-skew
    table, aligned event list, fleet flow. Pure data (JSON-ready)."""
    streams = {p: load_stream(p) for p in paths}
    hosts = [summarize_host(p, recs) for p, recs in streams.items()]
    offsets = {h["path"]: h["offset_unix"] for h in hosts}
    aligned = [h for h in hosts if h["offset_unix"] is not None]
    # Wall zero: earliest aligned stream start (unaligned streams are
    # placed at 0 and flagged by offset_unix == null).
    wall0 = min((h["offset_unix"] for h in aligned), default=0.0)

    def wall(path, t):
        off = offsets.get(path)
        return round(((off - wall0) if off is not None else 0.0)
                     + (t or 0.0), 4)

    # Timeline keyed by (task, step): first-seen wall + the kinds each
    # host reported at that step. JSON has no tuple keys → nested dict.
    timeline: Dict[int, Dict[int, dict]] = {}
    first_seen: Dict[int, Dict[int, float]] = {}
    events = []
    for path, recs in streams.items():
        for r in recs:
            step = r.get("step")
            kind = r.get("kind")
            task = r.get("task", 0)
            w = wall(path, r.get("t"))
            if isinstance(step, int):
                ent = timeline.setdefault(task, {}).setdefault(
                    step, {"kinds": [], "wall_s": w})
                ent["kinds"].append(kind)
                ent["wall_s"] = min(ent["wall_s"], w)
                fs = first_seen.setdefault(step, {})
                if offsets.get(path) is not None:
                    fs[task] = min(fs.get(task, w), w)
            if kind in EVENT_KINDS:
                ev = {"task": task, "kind": kind, "step": step,
                      "wall_s": w}
                for key in ("fault", "reason", "action", "process_id",
                            "epoch", "world_size", "restore_step",
                            "replica_id", "version"):
                    if key in r:
                        ev[key] = r[key]
                events.append(ev)
    events.sort(key=lambda e: e["wall_s"])

    # Step skew: wall spread of each step seen on >= 2 ALIGNED hosts.
    per_step = []
    for step in sorted(first_seen):
        seen = first_seen[step]
        if len(seen) < 2:
            continue
        lo, hi = min(seen.values()), max(seen.values())
        per_step.append({"step": step, "hosts": len(seen),
                         "spread_s": round(hi - lo, 4),
                         "laggard": max(seen, key=seen.get)})
    skew = {
        "steps_compared": len(per_step),
        "max_spread_s": max((s["spread_s"] for s in per_step),
                            default=None),
        "mean_spread_s": round(sum(s["spread_s"] for s in per_step)
                               / len(per_step), 4) if per_step else None,
        "per_step": per_step,
    }
    # Straggler attribution: how often each task was the last to reach
    # a shared step.
    lag_counts = collections.Counter(s["laggard"] for s in per_step)
    skew["laggard_counts"] = dict(lag_counts)

    # Fleet request flow, when any stream carries the serving kinds.
    fleet: dict = {}
    serve_windows = {h["task"]: h["kinds"].get("serve", 0)
                     for h in hosts if h["kinds"].get("serve")}
    if serve_windows:
        fleet["serve_windows"] = serve_windows
    routed = rerouted = evictions = 0
    fleet_rows = 0
    for recs in streams.values():
        for r in recs:
            if r.get("kind") in ("fleet", "fleet_done"):
                fleet_rows += 1
                routed += r.get("routed") or 0
                rerouted += r.get("rerouted") or 0
                evictions += r.get("evictions") or 0
    if fleet_rows:
        fleet.update({"routed": routed, "rerouted": rerouted,
                      "evictions": evictions})

    return {"hosts": hosts, "timeline": timeline, "skew": skew,
            "events": events, "fleet": fleet,
            "aligned_hosts": len(aligned), "wall0_unix": wall0 or None}


# ---------------------------------------------------------------------------
# merged Perfetto trace
# ---------------------------------------------------------------------------

def _span_epoch_t(records: List[dict]) -> Optional[float]:
    """Estimate the SpanTracer epoch in stream-``t`` coordinates: every
    span record is flushed at/after its finish, so ``t − (start+dur)``
    upper-bounds nothing and lower-bounds the epoch — the minimum over
    spans converges on it."""
    cands = [r["t"] - (r["start_s"] + r["dur_s"]) for r in records
             if r.get("kind") == "span"
             and isinstance(r.get("t"), (int, float))
             and isinstance(r.get("start_s"), (int, float))
             and isinstance(r.get("dur_s"), (int, float))]
    return min(cands) if cands else None


def build_merged_trace(paths: List[str]) -> dict:
    """One Chrome/Perfetto document: per-process lanes rebuilt from the
    JSONL streams."""
    streams = {p: load_stream(p) for p in paths}
    offsets = {p: clock_offset(recs) for p, recs in streams.items()}
    known = [v for v in offsets.values() if v is not None]
    # rspan records carry ABSOLUTE wallclocks, so a stream that is
    # otherwise unalignable still places its request spans correctly —
    # include them when choosing the merged clock's zero.
    rspan_walls = [r["wallclock"] for recs in streams.values()
                   for r in recs
                   if r.get("kind") == "rspan"
                   and isinstance(r.get("wallclock"), (int, float))]
    wall0 = min(known + ([min(rspan_walls)] if rspan_walls else []),
                default=0.0)
    #: request-tracing lanes, one tid per hop, in causal order.
    hop_tid = {"client": 10, "router": 11, "server": 12, "worker": 12,
               "batcher": 13, "engine": 14, "batch": 15}
    flows: Dict[str, List[dict]] = {}
    events = []
    for path, recs in streams.items():
        tasks = [r.get("task") for r in recs if r.get("task") is not None]
        task = collections.Counter(tasks).most_common(1)[0][0] \
            if tasks else 0
        base_s = (offsets[path] - wall0) if offsets[path] is not None \
            else 0.0
        events.append({"ph": "M", "name": "process_name", "pid": task,
                       "args": {"name": f"task {task} ({os.path.basename(os.path.dirname(path)) or path})"}})
        epoch_t = _span_epoch_t(recs)
        for r in recs:
            kind = r.get("kind")
            ts_us = (base_s + (r.get("t") or 0.0)) * 1e6
            if kind == "span" and epoch_t is not None:
                events.append({
                    "ph": "X",
                    "name": r.get("name") or "span",
                    "pid": task, "tid": r.get("depth", 0),
                    "ts": round((base_s + epoch_t + r["start_s"]) * 1e6,
                                1),
                    "dur": round(r["dur_s"] * 1e6, 1),
                    **({"cat": r["cat"]} if r.get("cat") else {}),
                })
            elif kind == "train":
                for key in ("images_per_sec", "device_step_ms"):
                    if isinstance(r.get(key), (int, float)):
                        events.append({"ph": "C", "name": key,
                                       "pid": task, "tid": 0,
                                       "ts": round(ts_us, 1),
                                       "args": {key: r[key]}})
            elif kind == "rspan" \
                    and isinstance(r.get("wallclock"), (int, float)):
                # One hop of one traced request: placed by its ABSOLUTE
                # wallclock (no stream offset needed), one lane per
                # hop. The span is also registered under its trace_id
                # so the flow pass below can causally link the hops.
                hop = r.get("hop") or "hop"
                span = {
                    "ph": "X",
                    "name": f"{hop} {str(r.get('trace_id'))[:8]}",
                    "cat": "rspan",
                    "pid": task, "tid": hop_tid.get(hop, 19),
                    "ts": round((r["wallclock"] - wall0) * 1e6, 1),
                    "dur": round((r.get("dur_ms") or 0.0) * 1e3, 1),
                    "args": {k: v for k, v in r.items()
                             if k in ("trace_id", "hop", "dur_ms",
                                      "batch_id", "version", "shed",
                                      "attempt", "status", "replica_id",
                                      "error")},
                }
                events.append(span)
                if r.get("trace_id"):
                    flows.setdefault(str(r["trace_id"]), []).append(span)
            elif kind in EVENT_KINDS:
                events.append({"ph": "i", "s": "p",
                               "name": f"{kind}"
                               + (f"@{r['step']}"
                                  if isinstance(r.get("step"), int)
                                  else ""),
                               "pid": task, "tid": 0,
                               "ts": round(ts_us, 1)})
    # Causal links: one Chrome flow per trace_id, connecting its hop
    # spans in wallclock order (s → t... → f). Single-span traces (and
    # batch spans, whose batch_id is its own trace_id) need no arrow —
    # their membership is already in args.
    for flow_id, (trace_id, spans) in enumerate(sorted(flows.items()), 1):
        if len(spans) < 2:
            continue
        spans.sort(key=lambda s: s["ts"])
        for i, span in enumerate(spans):
            ph = "s" if i == 0 else ("f" if i == len(spans) - 1 else "t")
            ev = {"ph": ph, "name": "request", "cat": "rspan",
                  "id": flow_id, "pid": span["pid"], "tid": span["tid"],
                  "ts": round(span["ts"] + min(span["dur"], 1.0), 1)}
            if ph == "f":
                ev["bp"] = "e"
            events.append(ev)
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"wall0_unix_s": wall0 or None,
                          "sources": list(paths)}}


# ---------------------------------------------------------------------------
# report rendering
# ---------------------------------------------------------------------------

def render(agg: dict) -> str:
    lines = ["== run-wide aggregation =="]
    for h in agg["hosts"]:
        off = ("aligned" if h["offset_unix"] is not None
               else "UNALIGNED (no wallclock anchors)")
        lines.append(
            f"  task {h['task']}: {h['records']} record(s), "
            f"{h['train_rows']} train row(s), last step "
            f"{h['last_step']}, {h['heartbeats']} heartbeat(s) [{off}]")
    skew = agg["skew"]
    if skew["steps_compared"]:
        lines.append(
            f"  step skew over {skew['steps_compared']} shared "
            f"step(s): max {skew['max_spread_s']:.3f} s, mean "
            f"{skew['mean_spread_s']:.3f} s")
        counts = skew.get("laggard_counts") or {}
        worst = max(counts.values(), default=0)
        for task in sorted(counts):
            n = counts[task]
            bar = "#" * max(1, round(20 * n / worst)) if worst else ""
            lines.append(f"    task {task} last to arrive {n:>4}x {bar}")
    elif agg["aligned_hosts"] < 2:
        lines.append("  step skew: n/a (< 2 clock-aligned hosts)")
    if agg["events"]:
        lines.append(f"  events ({len(agg['events'])}):")
        for e in agg["events"][:40]:
            detail = {k: v for k, v in e.items()
                      if k not in ("task", "kind", "step", "wall_s")}
            extra = f" {detail}" if detail else ""
            lines.append(
                f"    +{e['wall_s']:9.3f}s task {e['task']} "
                f"{e['kind']}@{e['step']}{extra}")
        if len(agg["events"]) > 40:
            lines.append(f"    ... {len(agg['events']) - 40} more")
    if agg["fleet"]:
        f = agg["fleet"]
        if "serve_windows" in f:
            per = ", ".join(f"replica {t}: {n}"
                            for t, n in sorted(f["serve_windows"].items()))
            lines.append(f"  fleet serve windows: {per}")
        if "routed" in f:
            lines.append(
                f"  fleet request flow: {f['routed']} routed, "
                f"{f['rerouted']} re-routed, {f['evictions']} "
                f"eviction(s)")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="merge per-process metrics JSONL streams into "
                    "one run-level timeline")
    p.add_argument("streams", nargs="+", help="metrics JSONL files")
    p.add_argument("--out", default=None,
                   help="write the merged Perfetto/Chrome trace here")
    p.add_argument("--format", choices=("text", "json"), default="text")
    args = p.parse_args(argv)
    agg = aggregate(args.streams)
    if args.format == "json":
        print(json.dumps(agg))
    else:
        print(render(agg))
    if args.out:
        doc = build_merged_trace(args.streams)
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(doc, f)
        print(f"merged trace ({len(doc['traceEvents'])} events) -> "
              f"{args.out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())

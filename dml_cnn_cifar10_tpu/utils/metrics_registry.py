"""Live metrics: a process-local registry + Prometheus-text export.

Every observability surface before this module was post-hoc: the JSONL
stream, the reports, the trace tools all read files after the run. The
reference stack got live supervision for free from
``tf.train.MonitoredTrainingSession``'s hook machinery; this is the
SPMD-era equivalent — a thread-safe registry of counters / gauges /
histograms that any process type (trainer, serve worker, fleet router)
can expose over HTTP in the standard text exposition format, scrapable
by Prometheus or by ``tools/live_monitor.py`` while the run is live.

Design rules:

- **No new instrumentation.** The numbers already exist — the JSONL
  records carry them. :func:`observe_record` is the one translation
  table from record kinds to metrics, and ``MetricsLogger`` calls it
  for every record it writes (``utils/logging.py``), so every seam
  that logs is already exporting. Direct registry calls exist only
  where a number never enters the stream (per-peer beat staleness in
  ``parallel/cluster.py``, the serving latency histogram in
  ``serve/metrics.py``).
- **Zero device traffic.** Everything here is host-side dict work; the
  ``test_telemetry`` fetch-parity assert pins that arming the registry
  adds no ``jax.device_get`` calls.
- **Process-local.** One registry per process (:func:`default_registry`)
  — the fleet's workers each export their own; aggregation is the
  scraper's job (that is the Prometheus model, and what the live
  monitor does).

Export surfaces: ``GET /metrics`` on the serve server and the fleet
router (next to their ``/healthz``), and :func:`ensure_stats_server` —
the lightweight stats-HTTP thread the trainer starts behind
``--stats_port`` (0 = off; the trainer has no other HTTP surface).

:func:`parse_prometheus_text` is the inverse of :meth:`render` —
shared by the live monitor's scraper and the exposition-format lint in
``tests/test_alerts.py`` (render → parse → same numbers).
"""

from __future__ import annotations

import json
import threading
from typing import Dict, List, Optional, Sequence, Tuple

#: Default histogram buckets (milliseconds-flavored: the one histogram
#: fed today is the serving latency).
DEFAULT_BUCKETS = (1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                   500.0, 1000.0, 2500.0)


def _fmt(v: float) -> str:
    """Prometheus-text float: integers render bare, specials by name."""
    if v != v:
        return "NaN"
    if v in (float("inf"), float("-inf")):
        return "+Inf" if v > 0 else "-Inf"
    if float(v).is_integer() and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


def _label_str(names: Sequence[str], values: Sequence[str]) -> str:
    if not names:
        return ""
    inner = ",".join(
        f'{n}="{str(v).replace(chr(92), chr(92) * 2).replace(chr(34), chr(92) + chr(34))}"'
        for n, v in zip(names, values))
    return "{" + inner + "}"


class _Metric:
    """One named metric family: help text, type, per-label-set values."""

    def __init__(self, name: str, help_text: str, mtype: str,
                 labelnames: Sequence[str] = ()):
        self.name = name
        self.help = help_text
        self.type = mtype
        self.labelnames = tuple(labelnames)
        self._lock = threading.Lock()
        self._values: Dict[Tuple[str, ...], float] = {}

    def _key(self, labels: Dict[str, str]) -> Tuple[str, ...]:
        if set(labels) != set(self.labelnames):
            raise ValueError(
                f"metric {self.name} wants labels {self.labelnames}, "
                f"got {sorted(labels)}")
        return tuple(str(labels[n]) for n in self.labelnames)

    def values(self) -> Dict[Tuple[str, ...], float]:
        with self._lock:
            return dict(self._values)


class Counter(_Metric):
    """Monotone counter. ``inc`` by a non-negative delta."""

    def __init__(self, name, help_text, labelnames=()):
        super().__init__(name, help_text, "counter", labelnames)

    def inc(self, delta: float = 1.0, **labels) -> None:
        if delta < 0:
            return  # counters never go down; a bad delta is dropped
        key = self._key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + delta


class Gauge(_Metric):
    """Point-in-time value. ``set`` wins, ``inc``/``dec`` adjust."""

    def __init__(self, name, help_text, labelnames=()):
        super().__init__(name, help_text, "gauge", labelnames)

    def set(self, value, **labels) -> None:
        if value is None:
            return  # null-valued JSONL fields simply don't update
        key = self._key(labels)
        with self._lock:
            self._values[key] = float(value)

    def inc(self, delta: float = 1.0, **labels) -> None:
        key = self._key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + delta

    def remove(self, **labels) -> None:
        key = self._key(labels)
        with self._lock:
            self._values.pop(key, None)


class Histogram(_Metric):
    """Cumulative-bucket histogram (the Prometheus shape: every bucket
    counts observations ≤ its bound, plus ``+Inf``/sum/count series)."""

    def __init__(self, name, help_text, labelnames=(),
                 buckets: Sequence[float] = DEFAULT_BUCKETS):
        super().__init__(name, help_text, "histogram", labelnames)
        self.buckets = tuple(sorted(buckets))
        self._counts: Dict[Tuple[str, ...], List[int]] = {}
        self._sums: Dict[Tuple[str, ...], float] = {}
        self._totals: Dict[Tuple[str, ...], int] = {}

    def observe(self, value: float, **labels) -> None:
        key = self._key(labels)
        with self._lock:
            counts = self._counts.setdefault(key,
                                             [0] * len(self.buckets))
            for i, bound in enumerate(self.buckets):
                if value <= bound:
                    counts[i] += 1
            self._sums[key] = self._sums.get(key, 0.0) + float(value)
            self._totals[key] = self._totals.get(key, 0) + 1

    def snapshot(self) -> Dict[Tuple[str, ...], dict]:
        with self._lock:
            return {key: {"buckets": list(self._counts[key]),
                          "sum": self._sums[key],
                          "count": self._totals[key]}
                    for key in self._counts}


class MetricsRegistry:
    """Thread-safe named-metric registry; ``render()`` is the
    ``/metrics`` payload. Registration is idempotent by name (the same
    seam may re-register across supervisor restart attempts)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: Dict[str, _Metric] = {}

    def _register(self, cls, name, help_text, labelnames, **kw):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = cls(name, help_text, labelnames=labelnames, **kw)
                self._metrics[name] = m
            elif not isinstance(m, cls) \
                    or m.labelnames != tuple(labelnames):
                raise ValueError(
                    f"metric {name} re-registered with a different "
                    f"type/labels ({m.type}{m.labelnames})")
            return m

    def counter(self, name, help_text="", labelnames=()) -> Counter:
        return self._register(Counter, name, help_text, labelnames)

    def gauge(self, name, help_text="", labelnames=()) -> Gauge:
        return self._register(Gauge, name, help_text, labelnames)

    def histogram(self, name, help_text="", labelnames=(),
                  buckets=DEFAULT_BUCKETS) -> Histogram:
        return self._register(Histogram, name, help_text, labelnames,
                              buckets=buckets)

    def get(self, name: str) -> Optional[_Metric]:
        with self._lock:
            return self._metrics.get(name)

    def snapshot(self) -> Dict[str, Dict[Tuple[str, ...], float]]:
        """Plain-dict view of every scalar series (histograms excluded)
        — what tests and the live monitor's in-process path read."""
        with self._lock:
            metrics = list(self._metrics.values())
        return {m.name: m.values() for m in metrics
                if not isinstance(m, Histogram)}

    def render(self) -> str:
        """The standard text exposition format (version 0.0.4): HELP +
        TYPE comments, one ``name{labels} value`` line per series."""
        with self._lock:
            metrics = sorted(self._metrics.values(),
                             key=lambda m: m.name)
        lines: List[str] = []
        for m in metrics:
            lines.append(f"# HELP {m.name} {m.help}")
            lines.append(f"# TYPE {m.name} {m.type}")
            if isinstance(m, Histogram):
                for key, snap in sorted(m.snapshot().items()):
                    for bound, n in zip(m.buckets, snap["buckets"]):
                        lines.append(
                            m.name + "_bucket"
                            + _label_str(tuple(m.labelnames) + ("le",),
                                         key + (_fmt(bound),))
                            + f" {n}")
                    lines.append(
                        m.name + "_bucket"
                        + _label_str(tuple(m.labelnames) + ("le",),
                                     key + ("+Inf",))
                        + f" {snap['count']}")
                    lines.append(m.name + "_sum"
                                 + _label_str(m.labelnames, key)
                                 + f" {_fmt(snap['sum'])}")
                    lines.append(m.name + "_count"
                                 + _label_str(m.labelnames, key)
                                 + f" {snap['count']}")
                continue
            for key, value in sorted(m.values().items()):
                lines.append(m.name + _label_str(m.labelnames, key)
                             + f" {_fmt(value)}")
        return "\n".join(lines) + "\n"


def parse_prometheus_text(text: str) -> Dict[str, dict]:
    """Parse the text exposition format back into
    ``{name: {"type": ..., "help": ..., "samples":
    {(("label","value"),...): float}}}`` — the scrape half of the live
    monitor, and the round-trip check the exposition lint runs.
    Raises ``ValueError`` on a malformed line (the lint's teeth)."""
    out: Dict[str, dict] = {}
    for ln, raw in enumerate(text.splitlines(), 1):
        line = raw.strip()
        if not line:
            continue
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            _, verb, rest = line.split(" ", 2)
            name, _, payload = rest.partition(" ")
            fam = out.setdefault(name, {"type": None, "help": None,
                                        "samples": {}})
            fam["help" if verb == "HELP" else "type"] = payload
            continue
        if line.startswith("#"):
            continue
        # sample line: name{l="v",...} value   (labels optional)
        brace = line.find("{")
        if brace >= 0:
            close = line.rfind("}")
            if close < brace:
                raise ValueError(f"line {ln}: unbalanced braces: {raw!r}")
            name = line[:brace]
            label_body = line[brace + 1:close]
            value_s = line[close + 1:].strip()
            labels = []
            if label_body:
                # Split on commas OUTSIDE quotes, then unescape each
                # label value (the renderer escapes \ and ").
                part = ""
                in_quote = False
                parts = []
                for ch in label_body:
                    if ch == '"' and not part.endswith("\\"):
                        in_quote = not in_quote
                    if ch == "," and not in_quote:
                        parts.append(part)
                        part = ""
                    else:
                        part += ch
                if part:
                    parts.append(part)
                for p in parts:
                    k, eq, v = p.partition("=")
                    if not eq or not (v.startswith('"')
                                      and v.endswith('"')):
                        raise ValueError(
                            f"line {ln}: bad label {p!r} in {raw!r}")
                    labels.append(
                        (k, v[1:-1].replace('\\"', '"')
                            .replace("\\\\", "\\")))
        else:
            name, _, value_s = line.partition(" ")
            labels = []
            value_s = value_s.strip()
        if not name or not value_s:
            raise ValueError(f"line {ln}: malformed sample: {raw!r}")
        try:
            value = float(value_s.replace("+Inf", "inf")
                          .replace("-Inf", "-inf"))
        except ValueError:
            raise ValueError(f"line {ln}: bad value {value_s!r}")
        fam = out.setdefault(name.rstrip(), {"type": None, "help": None,
                                             "samples": {}})
        fam["samples"][tuple(labels)] = value
    return out


# ---------------------------------------------------------------------------
# the process-default registry + the JSONL-kind translation table
# ---------------------------------------------------------------------------

_DEFAULT = MetricsRegistry()


def default_registry() -> MetricsRegistry:
    """The process-local registry every export surface renders."""
    return _DEFAULT


def observe_record(kind: str, fields: dict,
                   registry: Optional[MetricsRegistry] = None) -> None:
    """Translate one JSONL record into registry updates — the single
    table that turns the existing telemetry stream into live metrics.
    Called by ``MetricsLogger.log`` for every record it writes, so any
    seam that logs is already exporting; unknown kinds are ignored.
    Fail-open: a malformed record must not take down the logger."""
    reg = registry if registry is not None else _DEFAULT
    try:
        _observe_record(kind, fields, reg)
    except Exception:
        pass


def _observe_record(kind: str, f: dict, reg: MetricsRegistry) -> None:
    if kind == "train":
        reg.gauge("dml_train_step",
                  "Global training step at the last metrics boundary"
                  ).set(f.get("step"))
        reg.gauge("dml_train_loss", "Training loss at the last boundary"
                  ).set(f.get("loss"))
        reg.gauge("dml_train_images_per_sec",
                  "Drain-anchored training throughput"
                  ).set(f.get("images_per_sec"))
        reg.gauge("dml_device_step_ms",
                  "Estimated device time per training step"
                  ).set(f.get("device_step_ms"))
        reg.gauge("dml_drain_wait_ms",
                  "Host time blocked in the fused boundary fetch"
                  ).set(f.get("drain_wait_ms"))
        reg.counter("dml_train_boundaries_total",
                    "Metrics boundaries flushed").inc()
        # one chip's share of an expert layer (ops/moe.routed_experts)
        for key, help_text in (
                ("moe_rows_here_frac",
                 "Expert slots routed to experts held here, over tokens x "
                 "experts a token, mean over the expert layers"),
                ("moe_load_max_over_mean",
                 "Rows of the fullest expert held here over the mean's"),
                ("moe_buffer_rounds",
                 "Times an expert layer filled its buffer of rows and "
                 "summed it by token, mean over the expert layers (1: "
                 "the load was within the buffer)"),
                ("moe_aux_loss",
                 "Sequence-wise balance loss of an expert layer (alpha "
                 "times the mean over sequences of sum_i f_i P_i), mean "
                 "over the expert layers"),
                # a stack with window layers (models/hybrid_decoder.py)
                ("attn_window_blocks_frac",
                 "Block pairs the window layers' flash schedule visits "
                 "over those the causal schedule visits at the same "
                 "sizes")):
            if f.get(key) is not None:
                reg.gauge("dml_" + key, help_text).set(f[key])
    elif kind == "goodput":
        g = reg.gauge("dml_goodput_fraction",
                      "Cumulative goodput fraction by category",
                      labelnames=("category",))
        for key, value in f.items():
            if key.endswith("_frac"):
                g.set(value, category=key[:-len("_frac")])
        reg.gauge("dml_goodput_total_seconds",
                  "Wall-clock seconds since the tracer epoch"
                  ).set(f.get("total_s"))
    elif kind == "span":
        # Where the host's time goes, live: every finished span of the
        # telemetry stream (utils/telemetry.py), by name.
        name = str(f.get("name"))
        reg.counter("dml_span_seconds_total",
                    "Seconds spent in finished host spans, by span name",
                    labelnames=("name",)
                    ).inc(f.get("dur_s") or 0.0, name=name)
        reg.counter("dml_spans_total", "Finished host spans, by span name",
                    labelnames=("name",)).inc(1, name=name)
    elif kind == "hbm":
        if f.get("available"):
            reg.gauge("dml_hbm_bytes_in_use",
                      "Device memory in use, summed over local devices"
                      ).set(f.get("bytes_in_use"))
            reg.gauge("dml_hbm_bytes_limit",
                      "Device memory limit, summed over local devices"
                      ).set(f.get("bytes_limit"))
            reg.gauge("dml_hbm_peak_bytes",
                      "Peak device memory, summed over local devices"
                      ).set(f.get("peak_bytes"))
    elif kind == "eval":
        reg.gauge("dml_eval_accuracy", "Last eval accuracy"
                  ).set(f.get("test_accuracy"))
    elif kind == "fault":
        reg.counter("dml_faults_total", "Fault records by class",
                    labelnames=("fault",)
                    ).inc(1, fault=str(f.get("fault")))
    elif kind == "recovery":
        reg.counter("dml_recoveries_total", "Recovery actions by kind",
                    labelnames=("action",)
                    ).inc(1, action=str(f.get("action")))
    elif kind == "compile":
        reg.counter("dml_compile_lookups_total",
                    "Compile-seam lookups by hit/miss",
                    labelnames=("hit",)
                    ).inc(1, hit="true" if f.get("hit") else "false")
        reg.counter("dml_compile_seconds_total",
                    "Seconds spent obtaining compiled programs"
                    ).inc(f.get("compile_s") or 0.0)
    elif kind == "heartbeat":
        reg.gauge("dml_heartbeat_step",
                  "Step carried by this process's latest beat"
                  ).set(f.get("step"))
    elif kind == "serve":
        reg.gauge("dml_serve_qps", "Completed requests/s, last window"
                  ).set(f.get("qps"))
        reg.gauge("dml_serve_p50_ms", "Latency p50, last window"
                  ).set(f.get("p50_ms"))
        reg.gauge("dml_serve_p99_ms", "Latency p99, last window"
                  ).set(f.get("p99_ms"))
        reg.gauge("dml_serve_batch_fill",
                  "Mean batch fill fraction, last window"
                  ).set(f.get("batch_fill"))
        reg.counter("dml_serve_requests_total", "Requests submitted"
                    ).inc(f.get("requests") or 0)
        reg.counter("dml_serve_completed_total", "Requests completed"
                    ).inc(f.get("completed") or 0)
        shed = reg.counter("dml_serve_shed_total",
                           "Requests shed by admission control",
                           labelnames=("reason",))
        shed.inc(f.get("shed_queue") or 0, reason="queue_full")
        shed.inc(f.get("shed_deadline") or 0, reason="deadline")
        reg.counter("dml_serve_cache_hits_total",
                    "Requests answered by the response cache "
                    "(bypassed the batcher)"
                    ).inc(f.get("cache_hit") or 0)
    elif kind == "fleet":
        reg.gauge("dml_fleet_live_replicas",
                  "Replicas in the routing rotation").set(f.get("live"))
        reg.gauge("dml_fleet_replicas",
                  "Replicas known to the router").set(f.get("replicas"))
        reg.counter("dml_fleet_routed_total", "Requests routed"
                    ).inc(f.get("routed") or 0)
        reg.counter("dml_fleet_rerouted_total",
                    "Requests re-routed after a replica failure"
                    ).inc(f.get("rerouted") or 0)
        reg.counter("dml_fleet_evictions_total", "Replica evictions"
                    ).inc(f.get("evictions") or 0)
        reg.counter("dml_fleet_shed_total", "Requests shed by the router"
                    ).inc(f.get("shed") or 0)
    elif kind == "scale":
        reg.counter("dml_fleet_scale_total", "Autoscaler actions",
                    labelnames=("action",)
                    ).inc(1, action=str(f.get("action")))
    elif kind in ("elastic_restart", "elastic_expand"):
        reg.gauge("dml_cluster_world_size",
                  "World size adopted by the last restart decision"
                  ).set(f.get("world_size"))
        reg.gauge("dml_cluster_epoch", "Adopted coordination epoch"
                  ).set(f.get("epoch"))
    elif kind == "alert":
        reg.gauge("dml_alert_active", "1 while the alert rule is firing",
                  labelnames=("rule", "severity")
                  ).set(1, rule=str(f.get("rule")),
                        severity=str(f.get("severity")))
        reg.counter("dml_alerts_total", "Alert firings by rule",
                    labelnames=("rule",)).inc(1, rule=str(f.get("rule")))
    elif kind == "alert_resolved":
        reg.gauge("dml_alert_active", "1 while the alert rule is firing",
                  labelnames=("rule", "severity")
                  ).set(0, rule=str(f.get("rule")),
                        severity=str(f.get("severity")))
    elif kind == "job":
        reg.counter("dml_job_transitions_total",
                    "Runtime job state transitions by type and state",
                    labelnames=("jtype", "state")
                    ).inc(1, jtype=str(f.get("jtype")),
                          state=str(f.get("state")))
    elif kind == "job_done":
        reg.counter("dml_jobs_done_total",
                    "Runtime jobs finished, by type and verdict",
                    labelnames=("jtype", "ok")
                    ).inc(1, jtype=str(f.get("jtype")),
                          ok="true" if f.get("ok") else "false")
        reg.gauge("dml_job_seconds",
                  "Wall seconds of the last finished job of each type",
                  labelnames=("jtype",)
                  ).set(f.get("secs"), jtype=str(f.get("jtype")))
    elif kind == "publish":
        reg.counter("dml_publishes_total",
                    "Checkpoint weights published into the in-process "
                    "serving engine, by swap verdict",
                    labelnames=("swapped",)
                    ).inc(1, swapped="true" if f.get("swapped")
                          else "false")
        reg.gauge("dml_publish_latency_ms",
                  "Latency of the last publish (copy-install swap)"
                  ).set(f.get("latency_ms"))
        reg.gauge("dml_published_step",
                  "Training step of the last published version"
                  ).set(f.get("step"))


# ---------------------------------------------------------------------------
# the stats HTTP thread (--stats_port) — trainer-side export surface
# ---------------------------------------------------------------------------

class StatsServer:
    """``GET /metrics`` (text exposition) + ``GET /healthz`` on a
    daemon accept thread — the trainer's only HTTP surface, so it stays
    deliberately tiny (same stdlib transport as ``serve/server.py``)."""

    def __init__(self, registry: MetricsRegistry, port: int,
                 host: str = ""):
        from http.server import (BaseHTTPRequestHandler,
                                 ThreadingHTTPServer)
        reg = registry

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _reply(self, code, body: bytes, ctype: str) -> None:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/metrics":
                    self._reply(200, reg.render().encode(),
                                "text/plain; version=0.0.4")
                elif self.path == "/healthz":
                    self._reply(200, json.dumps({"ok": True}).encode(),
                                "application/json")
                else:
                    self._reply(404, b'{"error": "no route"}',
                                "application/json")

        self._server = ThreadingHTTPServer((host, port), Handler)
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="stats-http",
            daemon=True)
        self._thread.start()

    def close(self) -> None:
        self._server.shutdown()
        self._server.server_close()


_STATS_LOCK = threading.Lock()
_STATS_SERVER: Optional[StatsServer] = None


def ensure_stats_server(port: Optional[int],
                        registry: Optional[MetricsRegistry] = None
                        ) -> Optional[StatsServer]:
    """Start (once per process) the stats HTTP thread when ``port`` is
    truthy; idempotent so supervisor restart attempts re-entering
    ``Trainer.__init__`` reuse the bound socket instead of fighting
    over it. ``0``/``None`` = off (the default). Fail-open: a bind
    failure prints a notice and returns None — live export must never
    kill training."""
    global _STATS_SERVER
    if not port:
        return None
    with _STATS_LOCK:
        if _STATS_SERVER is not None:
            return _STATS_SERVER
        try:
            _STATS_SERVER = StatsServer(
                registry if registry is not None else _DEFAULT, port)
        except OSError as e:
            import sys
            print(f"[stats] could not bind --stats_port {port}: {e}; "
                  f"live metrics export disabled", file=sys.stderr)
            return None
        print(f"[stats] GET /metrics on :{_STATS_SERVER.port}")
        return _STATS_SERVER


def stop_stats_server() -> None:
    """Close and forget the process stats server (tests; a long-lived
    driver embedding several runs in one process)."""
    global _STATS_SERVER
    with _STATS_LOCK:
        if _STATS_SERVER is not None:
            _STATS_SERVER.close()
            _STATS_SERVER = None

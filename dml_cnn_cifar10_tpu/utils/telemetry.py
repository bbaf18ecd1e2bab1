"""Run-health telemetry: host-loop span tracing, goodput accounting, and
device/HBM health snapshots.

The metrics stream (``utils/logging.py``) records WHAT happened at each
boundary; this layer records WHERE THE WALL-CLOCK WENT and WHETHER THE RUN
IS HEALTHY — the two questions a long multi-host job must answer without a
profiler attached. Three coordinated pieces:

- :class:`SpanTracer`: a ring-buffered context-manager tracer, ONE span
  stream over a ``Trainer``'s life: its making (``trainer_init``),
  ``init_or_restore``, and the whole of every ``fit``: set-up
  (``fit_setup`` and its children), the loop's phases
  (compile/first-dispatch, data wait, dispatch enqueue, the boundary's
  accuracy dispatch / drain / logging, eval, checkpoint, preemption
  allgather), the FLOP-probe thread, the
  collections of Python's garbage collector that can stall a loop
  (``gc_gen<n>``), teardown.
  Depth is kept per thread; a record names its thread where that is not
  the loop's. Near-zero overhead when disabled — ``span()`` returns a
  shared no-op context manager, no allocation, no clock read, no
  profiler annotation, no collector hook. Finished spans export two
  ways: JSONL ``span`` records through the existing ``MetricsLogger``
  (:func:`flush_boundary`; microsecond resolution) and a
  ``jax.profiler.TraceAnnotation`` while the span is open (the host
  phases appear in any profiler capture, on the profiler's clock).
- Goodput accounting: top-level spans carry a category
  (``compile`` / ``data`` / ``eval`` / ``checkpoint`` / ``sync``);
  :meth:`SpanTracer.goodput` reports the fraction of wall-clock since
  :meth:`SpanTracer.start` (the loop's entry) spent in each, with
  productive training as the remainder — so the categories sum to 1.0 by
  construction. Host-loop caveat: on the
  async-dispatch paths a host-side data wait can overlap device compute,
  so ``data_frac`` is an upper bound on true device starvation.
- :func:`hbm_stats`: per-process device-memory snapshot via
  ``device.memory_stats()`` (sum of bytes in use / peak / limit over local
  devices) — a host-side runtime call, NOT a device fetch, so logging it
  at boundaries adds no round trip. Backends without memory stats (CPU)
  report ``available=False`` rather than omitting the record.

Training-health scalars (grad norm, param norm, update ratio) are NOT
computed here — they are compiled into the step (``parallel/step.py``,
``health_metrics=True``) and ride the loop's single fused boundary fetch
(one device-to-host fetch per boundary, no per-step host round trip;
``train/loop.py``).
"""

from __future__ import annotations

import collections
import gc
import threading
import time
from typing import Optional

# Category order pins the goodput report layout (train first, then the
# overheads in rough size order for a typical run).
GOODPUT_CATEGORIES = ("compile", "data", "eval", "checkpoint", "sync")

# A collection of generation 0 or 1 shorter than this leaves no span: a
# fit makes a thousand of them (90 us each) while it traces, compiles and
# parses, and the boundary that flushed their records held the device idle
# for 12 ms (PERF.md, PR 24). A full collection always leaves one.
GC_SPAN_MIN_S = 1e-3


class _NullSpan:
    """Shared no-op context manager — the disabled-tracer fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def _trace_annotation(name: str):
    """The profiler's own host event for a span: in any capture
    (``--profile_dir``, ``--profile_at_steps``, a harness's) the phase is
    an event of the host plane on the profiler's clock, above the device
    operations it caused. A flag check when no capture is running."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class _Span:
    __slots__ = ("_tracer", "name", "cat", "t0", "_annotation")

    def __init__(self, tracer: "SpanTracer", name: str, cat: Optional[str]):
        self._tracer = tracer
        self.name = name
        self.cat = cat

    def __enter__(self):
        self._annotation = _trace_annotation(self.name)
        self._annotation.__enter__()
        self.t0 = time.perf_counter()
        self._tracer._local.depth = self._tracer._depth + 1
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        tr = self._tracer
        depth = tr._local.depth = tr._depth - 1
        self._annotation.__exit__(*exc)
        tr._record(self.name, self.cat, self.t0, t1 - self.t0, depth)
        return False


class SpanTracer:
    """Ring-buffered host span tracer + goodput aggregator.

    ``with tracer.span("eval", cat="eval"): ...`` records one finished
    span. Only DEPTH-0 spans with a category count toward goodput —
    nested sub-spans are trace detail, not wall-clock attribution (a
    category on a nested span would double-count its parent's time).
    ``drain()`` hands out (and forgets) the spans finished
    since the last drain so boundary flushes are incremental. Overflow is
    counted (``dropped``), never silent.

    One tracer covers a ``Trainer``'s life — its making, every ``fit``
    (:meth:`reopen` .. :meth:`close`) and what runs between them — with
    its background threads: depth is kept per thread (a span of the
    FLOP-probe thread never shifts the loop's nesting), and a record names
    its thread where that is not the one the tracer was made on. Two
    clocks: span starts are relative to ``_epoch`` (the tracer's creation,
    so set-up is inside it), the goodput clock runs from :meth:`start`
    (where a fit's set-up ends).
    """

    def __init__(self, enabled: bool = True, max_spans: int = 65536):
        self.enabled = enabled
        self.max_spans = max_spans
        self.dropped = 0
        self._local = threading.local()
        self._home = threading.get_ident()
        # (name, cat, start_s, dur_s, depth, thread), for the JSONL flush.
        self._pending = collections.deque(maxlen=max_spans)
        self._cat_secs = dict.fromkeys(GOODPUT_CATEGORIES, 0.0)
        self._epoch = self._goodput_epoch = time.perf_counter()
        # (logger, step) once the owning fit has made its last flush:
        # a span that finishes later is logged by whoever finishes it.
        self._sink = None
        self.flushed_step = 0    # the step of the newest boundary flush

    @property
    def _depth(self) -> int:
        """Open spans on the calling thread."""
        return getattr(self._local, "depth", 0)

    def start(self) -> None:
        """Start the goodput clock and its attributed seconds anew (call
        at loop entry, pre-compile). Span starts stay relative to the
        tracer's creation."""
        self._goodput_epoch = time.perf_counter()
        self._cat_secs = dict.fromkeys(GOODPUT_CATEGORIES, 0.0)

    def span(self, name: str, cat: Optional[str] = None):
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, cat)

    def _record(self, name, cat, t0, dur, depth) -> None:
        thread = None if threading.get_ident() == self._home \
            else threading.current_thread().name
        rec = (name, cat, t0 - self._epoch, dur, depth, thread)
        if depth == 0 and cat is not None:
            self._cat_secs[cat] = self._cat_secs.get(cat, 0.0) + dur
        sink = self._sink
        if sink is None:
            if len(self._pending) == self.max_spans:
                self.dropped += 1
            self._pending.append(rec)
        else:
            _log_span(sink[0], sink[1], rec)

    def add_secs(self, cat: str, secs: float) -> None:
        """Attribute externally-measured seconds to a goodput category
        without a span — the compile cache reports its obtain time
        (trace + executable load-or-compile) here, so startup/restart
        compile cost lands in the `compile` fraction instead of the
        train-as-remainder bucket even when it happens outside any
        categorized span (eval-seam first compiles, warm-start loads).
        Caveat: seconds added while a categorized span is ALSO open are
        counted in both categories; ``goodput()`` clamps the sum to 1.0,
        so the overlap only softens the remainder, never inflates it."""
        if not self.enabled or secs <= 0:
            return
        self._cat_secs[cat] = self._cat_secs.get(cat, 0.0) + secs

    def reopen(self) -> None:
        """A ``fit`` begins (the first, or the next after a
        :meth:`close`): finished spans wait for its boundary flushes again,
        those from before it among them, and the collector is watched."""
        self._sink = None
        self.watch_gc()

    def watch_gc(self) -> None:
        """Record one span ``gc_gen<n>`` per collection of Python's
        garbage collector (every full one; of generations 0 and 1 those
        of :data:`GC_SPAN_MIN_S` or more), start to stop, on the thread
        it runs on and at that thread's depth, until :meth:`close`.
        Nothing is registered on a disabled tracer."""
        if self.enabled and self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._local.gc_t0 = time.perf_counter()
            return
        t0 = getattr(self._local, "gc_t0", None)
        if t0 is not None:
            self._local.gc_t0 = None
            dur = time.perf_counter() - t0
            gen = info.get("generation")
            if gen == 2 or dur >= GC_SPAN_MIN_S:
                self._record(f"gc_gen{gen}", None, t0, dur, self._depth)

    def close(self, logger=None) -> None:
        """The owning ``fit`` is over: stop watching the collector, log
        what finished since its last flush (under that flush's step), and
        from here on let whoever finishes a span log it (the probe thread
        outlives a short fit)."""
        if not self.enabled:
            return
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        if logger is not None:
            self._sink = (logger, self.flushed_step)
            for rec in self.drain():
                _log_span(logger, self.flushed_step, rec)

    def drain(self) -> list:
        """Spans finished since the last drain (and forget them)."""
        out = []
        while True:
            try:
                out.append(self._pending.popleft())
            except IndexError:
                return out

    def goodput(self, now: Optional[float] = None) -> dict:
        """Cumulative goodput breakdown since :meth:`start`.

        ``{total_s, train_frac, <cat>_frac...}`` — ``train_frac`` is the
        unattributed remainder (dispatch enqueue, boundary drain, host
        logging all count as productive: during them the device is
        executing training steps), so the fractions sum to 1.0 exactly.
        """
        total = max((now if now is not None else time.perf_counter())
                    - self._goodput_epoch, 1e-9)
        out = {"total_s": round(total, 4)}
        attributed = 0.0
        for cat in sorted(self._cat_secs):
            secs = min(self._cat_secs[cat], total - attributed)
            attributed += secs
            out[f"{cat}_frac"] = round(secs / total, 6)
        out["train_frac"] = round((total - attributed) / total, 6)
        return out


def _log_span(logger, step: int, rec) -> None:
    """One ``span`` record, times to the microsecond: the idle gaps they
    are joined with are microseconds long."""
    name, cat, start, dur, depth, thread = rec
    logger.log("span", step=step, name=name, start_s=round(start, 6),
               dur_s=round(dur, 6), depth=depth,
               **({"cat": cat} if cat else {}),
               **({"thread": thread} if thread else {}))


def percentile(values, q: float):
    """Linearly-interpolated percentile (numpy's default method) of an
    UNSORTED sequence; ``None`` on empty input. Kept dependency-free so
    the serving hot path and ``tools/loadgen.py`` share one definition
    without importing numpy for a handful of floats."""
    if not values:
        return None
    vs = sorted(values)
    if len(vs) == 1:
        return vs[0]
    rank = (len(vs) - 1) * (q / 100.0)
    lo = int(rank)
    hi = min(lo + 1, len(vs) - 1)
    frac = rank - lo
    return vs[lo] * (1.0 - frac) + vs[hi] * frac


def latency_summary(seconds, prefix: str = "") -> dict:
    """p50/p95/p99/mean/max of a latency sample, in MILLISECONDS (the
    serving-convention unit; train-side spans stay in seconds). Keys are
    ``{prefix}p50_ms`` etc.; all ``None`` when the sample is empty so
    JSONL records keep their required keys (null-valued, per the schema
    contract in tools/check_jsonl_schema.py)."""
    if not seconds:
        return {f"{prefix}{k}": None
                for k in ("p50_ms", "p95_ms", "p99_ms", "mean_ms", "max_ms")}
    return {
        f"{prefix}p50_ms": round(percentile(seconds, 50) * 1e3, 3),
        f"{prefix}p95_ms": round(percentile(seconds, 95) * 1e3, 3),
        f"{prefix}p99_ms": round(percentile(seconds, 99) * 1e3, 3),
        f"{prefix}mean_ms": round(sum(seconds) / len(seconds) * 1e3, 3),
        f"{prefix}max_ms": round(max(seconds) * 1e3, 3),
    }


def hbm_stats() -> dict:
    """Per-process device-memory snapshot, summed over local devices.

    A host-side runtime query (no device round trip). Fields are 0 with
    ``available=False`` on backends whose ``memory_stats()`` is missing
    or empty (CPU), so the ``hbm`` record is emitted unconditionally and
    downstream tooling need not special-case the backend.
    """
    import jax

    in_use = peak = limit = 0
    ndev = 0
    for d in jax.local_devices():
        try:
            s = d.memory_stats()
        except Exception:
            s = None
        if not s:
            continue
        ndev += 1
        in_use += int(s.get("bytes_in_use", 0))
        peak += int(s.get("peak_bytes_in_use", s.get("bytes_in_use", 0)))
        limit += int(s.get("bytes_limit", 0))
    return {"available": ndev > 0, "devices": ndev,
            "bytes_in_use": in_use, "peak_bytes": peak,
            "bytes_limit": limit}


def flush_boundary(tracer: SpanTracer, logger, step: int,
                   final: bool = False, alerts=None) -> None:
    """Emit the boundary telemetry records through ``MetricsLogger``:
    every span finished since the last flush, the cumulative goodput
    breakdown, and an HBM snapshot. Pure host work — zero device fetches
    (the one-fused-fetch-per-boundary rule of ``train/loop.py``).

    ``alerts`` (an :class:`~dml_cnn_cifar10_tpu.utils.alerts.AlertEngine`)
    gets its time-window pass here — the record-driven rules already saw
    every record above via the logger's observer hook; this is where
    absence rules (heartbeat staleness) and rate-window resolutions are
    adjudicated, so alerting runs exactly at the cadence the stream
    already flushes. The engine may run even when the tracer is off —
    `train`/`fault` records still flow without ``--telemetry``."""
    if tracer.enabled:
        tracer.flushed_step = step
        for rec in tracer.drain():
            _log_span(logger, step, rec)
        gp = tracer.goodput()
        if tracer.dropped:
            gp["dropped_spans"] = tracer.dropped
        if final:
            gp["final"] = 1
        logger.log("goodput", step=step, **gp)
        logger.log("hbm", step=step, **hbm_stats())
    if alerts is not None:
        alerts.evaluate(emit=logger.log, step=step)

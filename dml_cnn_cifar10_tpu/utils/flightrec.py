"""Alert-triggered flight recorder: a bounded in-memory ring of the
last N metrics records per process, snapshotted to an atomic
post-mortem bundle the moment a streaming alert FIRES.

The ring is fed from the existing :meth:`MetricsLogger.add_observer`
hook — the same seam the alert engine rides — so arming it adds zero
instrumentation and zero device fetches (the fetch-parity pin in
``tests/test_telemetry.py`` stays green). The recorder must be attached
BEFORE the alert engine's observer: observers run in attach order, so
the record that trips a rule lands in the ring first, and the engine's
nested ``alert`` emission (observed here as just another record) then
triggers the capture with the full causal prefix already ringed.

Capture semantics map 1:1 onto the alert engine's emission contract
(``utils/alerts.py``): an ``alert`` record exists exactly when a firing
EMITS, so one bundle per firing falls out naturally — suppressed
re-fires inside the rate-limit window emit nothing and capture nothing,
and ``alert_resolved`` is a different kind and never captures.

A bundle is one directory (written to a temp path, then atomically
renamed into ``postmortem_dir``) holding::

    ring.jsonl     the ring at capture time (kind + wallclock + fields)
    alert.json     the triggering alert record + capture wallclock
    config.json    the run's full config tree (when one was given)
    env.json       python/jax/platform versions, pid, selected env vars
    context.json   live process context (active serving version, ...)

Training captures additionally ARM a one-shot ``utils/devprof.py``
window: the trainer's loop pops it at the next dispatch seam
(:meth:`FlightRecorder.pop_devprof_window`) so the bundle gains a
device-time attribution of the steps right after the fault — but only
when no whole-run ``--profile_dir`` capture owns the profiler.
``tools/postmortem.py`` renders a bundle into a human timeline.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Callable, Optional

#: Devprof window length (steps) armed after a training capture.
DEVPROF_STEPS = 2


def _jsonable(v):
    """Best-effort plain-JSON coercion for ring/context payloads."""
    try:
        json.dumps(v, allow_nan=False)
        return v
    except (TypeError, ValueError):
        return repr(v)


class FlightRecorder:
    """Ring buffer + alert-triggered atomic bundle writer.

    ``size`` bounds the ring; ``postmortem_dir`` is where bundles land;
    ``config`` (a TrainConfig) and ``context_fn`` (zero-arg callable
    returning live process context, e.g. the serving engine's active
    version) enrich the bundle; ``logger`` receives one ``postmortem``
    JSONL record per capture so the stream itself says a bundle exists.
    """

    def __init__(self, size: int = 256,
                 postmortem_dir: Optional[str] = None,
                 config=None,
                 context_fn: Optional[Callable[[], dict]] = None,
                 logger=None):
        self.size = max(1, int(size))
        self.postmortem_dir = postmortem_dir
        self.config = config
        self.context_fn = context_fn
        self.logger = logger
        self._ring = collections.deque(maxlen=self.size)
        self._lock = threading.Lock()
        self._seq = 0
        self._capturing = False
        self._devprof_bundle: Optional[str] = None
        #: bundle directories written, in capture order (tests + tools).
        self.bundles = []

    @classmethod
    def from_config(cls, cfg, context_fn=None,
                    logger=None) -> Optional["FlightRecorder"]:
        """Armed only when ``--postmortem_dir`` is set — the disarmed
        path costs nothing (no observer, no ring)."""
        pm_dir = getattr(cfg, "postmortem_dir", None)
        if not pm_dir:
            return None
        return cls(size=getattr(cfg, "flightrec_size", 256),
                   postmortem_dir=pm_dir, config=cfg,
                   context_fn=context_fn, logger=logger)

    def observer(self):
        """The ``MetricsLogger.add_observer`` adapter. Attach BEFORE
        the alert engine's observer (see module docstring)."""
        return self.observe

    # -- the ring -------------------------------------------------------

    def observe(self, kind: str, fields: dict) -> None:
        with self._lock:
            if self._capturing:
                # The capture's own `postmortem` emission re-enters
                # here; ring it after the flag clears, never recurse.
                return
            self._ring.append({"kind": kind,
                               "wallclock": round(time.time(), 6),
                               **{k: _jsonable(v)
                                  for k, v in fields.items()}})
            if kind != "alert":
                return
            self._capturing = True
            ring_snapshot = list(self._ring)
            self._seq += 1
            seq = self._seq
        try:
            self._capture(dict(fields), ring_snapshot, seq)
        except Exception as e:  # fail-open: never take down the host
            print(f"[flightrec] capture failed: {e!r}", flush=True)
        finally:
            with self._lock:
                self._capturing = False

    def snapshot(self) -> list:
        with self._lock:
            return list(self._ring)

    # -- capture --------------------------------------------------------

    def _capture(self, alert_fields: dict, ring: list, seq: int) -> None:
        rule = str(alert_fields.get("rule") or "alert")
        safe_rule = "".join(c if c.isalnum() or c in "-_" else "_"
                            for c in rule) or "alert"
        final = os.path.join(self.postmortem_dir,
                             f"{safe_rule}_{seq:03d}")
        tmp = f"{final}.tmp{os.getpid()}"
        os.makedirs(tmp, exist_ok=True)
        with open(os.path.join(tmp, "ring.jsonl"), "w") as f:
            for rec in ring:
                f.write(json.dumps(rec) + "\n")
        with open(os.path.join(tmp, "alert.json"), "w") as f:
            json.dump({**{k: _jsonable(v)
                          for k, v in alert_fields.items()},
                       "captured_wallclock": round(time.time(), 6)},
                      f, indent=2)
        if self.config is not None:
            from dml_cnn_cifar10_tpu.config import config_to_dict
            with open(os.path.join(tmp, "config.json"), "w") as f:
                json.dump(config_to_dict(self.config), f, indent=2)
        with open(os.path.join(tmp, "env.json"), "w") as f:
            json.dump(self._env(), f, indent=2)
        context = {}
        if self.context_fn is not None:
            try:
                context = {k: _jsonable(v)
                           for k, v in (self.context_fn() or {}).items()}
            except Exception as e:
                context = {"error": repr(e)}
        with open(os.path.join(tmp, "context.json"), "w") as f:
            json.dump(context, f, indent=2)
        # Atomic publish: a reader never sees a half-written bundle.
        os.rename(tmp, final)
        self.bundles.append(final)
        # Arm the one-shot devprof window for the NEXT dispatch seam
        # (training only; the serving hosts have no step loop to pop it
        # and simply never do).
        self._devprof_bundle = final
        if self.logger is not None:
            self.logger.log("postmortem", rule=rule, dir=final,
                            records=len(ring))
        print(f"[flightrec] alert {rule!r} captured post-mortem bundle "
              f"-> {final} ({len(ring)} ring record(s))", flush=True)

    @staticmethod
    def _env() -> dict:
        import platform
        import sys
        env = {"python": sys.version.split()[0],
               "platform": platform.platform(),
               "pid": os.getpid(),
               "env": {k: os.environ[k] for k in
                       ("JAX_PLATFORMS", "XLA_FLAGS")
                       if k in os.environ}}
        try:
            import jax
            env["jax"] = jax.__version__
        except Exception:
            pass
        return env

    # -- devprof arming -------------------------------------------------

    def pop_devprof_window(self, step: int, logger=None):
        """One-shot: after a capture, return a ProfileWindow starting
        at ``step`` writing under ``<bundle>/devprof``; None when no
        capture is pending. The trainer pops this at its dispatch seam
        (only when no ``--profile_dir`` run-wide capture owns the
        profiler)."""
        with self._lock:
            bundle = self._devprof_bundle
            self._devprof_bundle = None
        if bundle is None:
            return None
        from dml_cnn_cifar10_tpu.utils.devprof import ProfileWindow
        return ProfileWindow(step, DEVPROF_STEPS,
                             os.path.join(bundle, "devprof"),
                             logger=logger)

"""Drives the system under test: ``Trainer.fit`` on a ``TrainConfig``
built by the program's own flag parser, the path a user of
``python cifar10cnn.py`` runs.

One ``Trainer`` is built. A first ``fit`` of one dispatch (K steps, from
the benchmark's weights) compiles the cell's shapes and yields the state
that ``correct`` is decided on. The same trainer and that same state go
on in a second ``fit``, and the window is a stretch of it between two
metrics boundaries, each of which ends in the loop's fused
``device_get``: all steps between them over all seconds between them.
The boundaries are seen from outside through the logger's observer hook.
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

import numpy as np


class WindowClosed(BaseException):
    """Raised from the observer to end the measured ``fit``. Not an
    ``Exception``: the logger swallows those from its observers."""


def build_train_config(flags: dict):
    """The program's ``TrainConfig`` from flags, as ``cli/main.py`` does."""
    from dml_cnn_cifar10_tpu.cli.main import build_parser, config_from_args
    argv: List[str] = []
    for k, v in flags.items():
        argv += [f"--{k}", str(v).lower() if isinstance(v, bool) else str(v)]
    return config_from_args(build_parser().parse_args(argv))


class Boundary(NamedTuple):
    t: float        # perf_counter just after the boundary's drain
    step: int
    loss: float


class Span(NamedTuple):
    name: str
    start: float    # perf_counter
    dur: float
    depth: int


class Window(NamedTuple):
    t0: float
    t1: float
    step0: int
    step1: int
    boundaries: List[Boundary]   # those inside the window, both ends
    spans: List[Span]            # the program's spans that ended in it
    trace_dir: Optional[str]
    marker_perf_ns: Optional[int]   # perf_counter_ns at the trace marker


TRACE_MARKER = "bench_window_start"


class WindowObserver:
    """Sees every record the trainer logs. Opens the window at the first
    boundary at which ``ready()`` holds, closes it at the first boundary
    ``seconds`` later (traced: after ``trace_boundaries`` boundaries) and
    ends the ``fit``."""

    def __init__(self, seconds: float, ready, span_epoch,
                 trace_dir: Optional[str] = None, trace_boundaries: int = 0,
                 telemetry: bool = False):
        self.seconds = seconds
        self.ready = ready
        self.span_epoch = span_epoch
        self.trace_dir = trace_dir
        self.trace_boundaries = trace_boundaries
        self.telemetry = telemetry
        self.boundaries: List[Boundary] = []
        self.spans: List[Span] = []
        self.open_at: Optional[int] = None     # index into boundaries
        self.t0 = self.t1 = None
        self.marker_perf_ns = None
        self._closing = False
        self.tracing = False

    def __call__(self, kind: str, fields: dict) -> None:
        if kind == "train":
            self._boundary(Boundary(time.perf_counter(), int(fields["step"]),
                                    float("nan") if fields["loss"] is None
                                    else float(fields["loss"])))
        elif kind == "span" and self.open_at is not None:
            epoch = self.span_epoch()
            self.spans.append(Span(fields["name"], epoch + fields["start_s"],
                                   fields["dur_s"], fields["depth"]))
        elif kind == "hbm" and self._closing:
            raise WindowClosed()

    def _boundary(self, b: Boundary) -> None:
        self.boundaries.append(b)
        if self.open_at is None:
            if not self.ready():
                return
            self.open_at = len(self.boundaries) - 1
            if self.trace_dir is not None:
                import jax
                jax.profiler.start_trace(self.trace_dir)
                self.tracing = True
                with jax.profiler.TraceAnnotation(TRACE_MARKER):
                    self.marker_perf_ns = time.perf_counter_ns()
            # the clock starts after the profiler has started
            self.t0 = time.perf_counter()
            self.boundaries[-1] = b._replace(t=self.t0)
            return
        n = len(self.boundaries) - 1 - self.open_at
        done = n >= self.trace_boundaries if self.trace_dir is not None \
            else b.t - self.t0 >= self.seconds
        if done:
            self.t1 = b.t
            self.stop_trace()
            if not self.telemetry:
                raise WindowClosed()
            self._closing = True   # the boundary's spans are flushed next

    def stop_trace(self) -> None:
        if self.tracing:
            import jax
            self.tracing = False
            jax.profiler.stop_trace()

    def window(self) -> Window:
        if self.t1 is None:
            raise RuntimeError("the fit ended before the window closed")
        inside = self.boundaries[self.open_at:]
        inside = [b for b in inside if b.t <= self.t1]
        spans = [s for s in self.spans
                 if s.start + s.dur <= self.t1 + 1e-3 and s.start >= self.t0]
        return Window(self.t0, self.t1, inside[0].step, inside[-1].step,
                      inside, spans, self.trace_dir, self.marker_perf_ns)


class FirstDispatch(NamedTuple):
    """What the first K steps made of the benchmark's weights, on the host."""

    loss: Optional[float]      # None where the dispatch ended on no boundary
    params: Any
    model_state: Any
    opt: Dict[str, Any]        # every tree of the optimizer's state, by name


def in_the_programs_place(ref) -> FirstDispatch:
    """A reference's ``ChunkResult`` where the program's first dispatch
    goes: a control, a planted fault."""
    return FirstDispatch(float(ref.losses[-1]), ref.params, ref.model_state,
                         ref.opt)


def _to_host(tree):
    import jax
    return jax.tree.map(np.asarray, jax.device_get(tree))


class Program(NamedTuple):
    """The one object that set-up builds and the window drives."""

    trainer: Any
    logger: Any
    cfg: Any
    state: Any                 # the state after the first dispatch
    first: FirstDispatch


def start_program(flags: dict, devices, make_params, telemetry: bool = False,
                  fault=None) -> Program:
    """Set-up and the first dispatch. ``make_params(abstract, sharding)``
    gives the starting weights. ``fault(trainer)`` may break the timed
    path underneath (tests and the reading of limits only)."""
    import jax

    from dml_cnn_cifar10_tpu.compilecache import arm_native_cache
    from dml_cnn_cifar10_tpu.parallel import mesh as mesh_lib
    from dml_cnn_cifar10_tpu.train.loop import Trainer
    from dml_cnn_cifar10_tpu.utils.logging import MetricsLogger

    arm_native_cache()
    cfg = build_train_config({**flags, "telemetry": telemetry})
    mesh = mesh_lib.build_mesh(cfg.parallel, devices=devices)
    logger = MetricsLogger(None)
    trainer = Trainer(cfg, mesh=mesh, logger=logger)
    if fault is not None:
        fault(trainer)

    state = trainer.init_or_restore()
    sharding = trainer.state_sharding.params \
        if trainer.state_sharding is not None else mesh_lib.replicated(mesh)
    state = state._replace(params=make_params(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                     state.params), sharding))

    threads_before = set(threading.enumerate())
    with contextlib.redirect_stdout(sys.stderr):
        warm = trainer.fit(total_steps=cfg.steps_per_dispatch, state=state)
    first = FirstDispatch(
        loss=float(warm.train_loss[0]) if warm.train_loss else None,
        params=_to_host(warm.state.params),
        model_state=_to_host(warm.state.model_state),
        opt={name: _to_host(tree) for name, tree in warm.state.opt.items()
             if name != "step"})
    # the warm-up's background work (the program's FLOP probe compiles the
    # step a second time on a thread) must not run into the window
    for t in set(threading.enumerate()) - threads_before:
        t.join(timeout=900)
    return Program(trainer, logger, cfg, warm.state, first)


def measure_window(program: Program, seconds: float,
                   trace_dir: Optional[str] = None,
                   trace_boundaries: int = 0) -> Window:
    """The same trainer and state go on in a second ``fit``; the window is
    a stretch of it."""
    trainer = program.trainer

    def probe_landed() -> bool:
        # the measured fit starts the program's FLOP probe again; the
        # window opens at the first boundary after it has posted its result
        return "flops" in getattr(trainer, "_flops_cell", {"flops": 0})

    obs = WindowObserver(seconds, probe_landed,
                         lambda: trainer._tracer._epoch, trace_dir,
                         trace_boundaries, program.cfg.telemetry)
    program.logger.add_observer(obs)
    try:
        with contextlib.redirect_stdout(sys.stderr):
            trainer.fit(total_steps=program.cfg.total_steps,
                        state=program.state)
    except WindowClosed:
        pass
    finally:
        obs.stop_trace()
    return obs.window()


def memory_peak_bytes(devices) -> int:
    """The peak on the fullest chip, from the runtime's counters, read
    once the window has closed. The TPU runtime counts live buffers
    (``bytes_in_use``) and the scratch it reserves for a running program
    (``bytes_reserved``) apart, and keeps a peak of each: the peak is the
    buffers live now (weights, optimizer state, resident records) beside
    the largest scratch a program took, or the buffers' own peak where
    that was higher."""
    def one(d) -> int:
        m = d.memory_stats() or {}
        return max(int(m.get("peak_bytes_in_use", 0)),
                   int(m.get("bytes_in_use", 0))
                   + int(m.get("peak_bytes_reserved", 0)))
    return max(one(d) for d in devices)

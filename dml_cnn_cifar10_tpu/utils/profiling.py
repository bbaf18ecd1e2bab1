"""Profiling hooks: throughput metering, FLOPs probes, XLA trace capture.

The reference has no profiling at all (SURVEY §5). Here: the drain-anchored
throughput meter feeding images/sec into the metrics stream, the XLA
cost-analysis FLOPs probes behind the TFLOP/s / MFU metrics, and an optional
``jax.profiler`` trace for TensorBoard/Perfetto. Host-loop phase timing
lives in ``utils/telemetry.py`` (``SpanTracer``), which subsumed the old
``StepTimer`` (a rolling host-interval step timer the trainer never used —
host intervals measure enqueue rate, not execution, exactly the hazard
``DrainMeter`` exists to avoid).
"""

from __future__ import annotations

import contextlib
import time
from typing import Optional


class DrainMeter:
    """Drain-anchored throughput meter.

    Dispatches are async: host loop intervals measure ENQUEUE rate, not
    execution.
    Every device fetch is a true drain, so the exact training rate is
    (steps between drains) / (wall time between drains) — provided the
    window holds only training dispatches. Protocol: call :meth:`rate`
    right after a boundary's metric fetch, and :meth:`mark` at the END
    of any iteration that drained (metrics fetch, eval sweep, checkpoint
    fetch), so eval/checkpoint work never pollutes the next window.
    """

    def __init__(self, images_per_step: float):
        self.images_per_step = images_per_step
        self._mark: Optional[tuple] = None

    def rate(self, step: int) -> float:
        """images/sec since the previous mark; 0.0 before the first."""
        if self._mark is None:
            return 0.0
        prev_step, prev_t = self._mark
        dt = time.perf_counter() - prev_t
        if dt <= 0 or step <= prev_step:
            return 0.0
        return (step - prev_step) * self.images_per_step / dt

    def mark(self, step: int) -> None:
        self._mark = (step, time.perf_counter())


def abstractify(tree):
    """Pytree of arrays → ``ShapeDtypeStruct``s (sharding preserved) —
    the avals needed to look a compiled executable up via ``lower``."""
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=getattr(x, "sharding",
                                                        None)), tree)


@contextlib.contextmanager
def metadata_keyed_compiles(on: bool = True):
    """Within it, this thread's compiles look jax's persistent cache up
    under a key that includes the program's metadata. By default jax
    strips it from the key, so an executable loaded from the cache may
    carry the name scopes of whoever compiled it first (an older
    checkout's, which had none): fine to run, wrong to read
    ``utils/devprof.scope_map`` from. Thread-local; a jax without the
    option compiles as ever."""
    state = None
    if on:
        try:
            from jax._src import config as jax_config
            state = getattr(jax_config,
                            "compilation_cache_include_metadata_in_key",
                            None)
        except ImportError:
            pass
    if state is None:
        yield
        return
    with state(True):
        yield


def compiled_with_flops(jitted_fn, abstract_args,
                        exact_metadata: bool = False):
    """``(flops, compiled)`` of one dispatch: XLA's cost-analysis FLOPs
    and the compiled object they were read from (``None`` each where
    there is none), so that a caller who also wants the executable's text
    (``utils/devprof.scope_map``) does not compile a second time; such a
    caller asks for ``exact_metadata`` (:func:`metadata_keyed_compiles`).

    A cache-wrapped function (``compilecache.CachedFunction``, or the
    resident-chunk partial's shim) serves the figure from the persistent
    compile cache — the already-obtained executable's analysis or the
    entry's recorded one — with NO recompile, and hands on the executable
    it obtained. The bare AOT fallback ``lower().compile()`` keeps its own
    executable cache and recompiles (hundreds of ms to seconds for a real
    train step) even when the call path already compiled, so the driver
    runs this on a background thread, never inline in the step loop. On
    a TPU a failure to lower, compile or analyse is a real error and is
    raised; off it (the CPU backend's analysis has another shape and the
    figure is never published) it is ``(None, None)``."""
    from dml_cnn_cifar10_tpu.utils import platform as platform_lib

    try:
        cached = getattr(jitted_fn, "cached_flops", None)
        flops = cached(abstract_args) if cached is not None else None
        if flops and flops > 0:
            holder = getattr(jitted_fn, "cached", None) or jitted_fn
            compiled = getattr(holder, "compiled", None)
        else:
            with metadata_keyed_compiles(exact_metadata):
                compiled = jitted_fn.lower(*abstract_args).compile()
            flops = compiled.cost_analysis().get("flops", 0.0)
        return (float(flops) if flops and flops > 0 else None), compiled
    except Exception:
        if platform_lib.on_tpu():
            raise
        return None, None


def compiled_flops(jitted_fn, abstract_args) -> Optional[float]:
    """FLOPs of one dispatch from XLA's cost analysis; None when the
    backend doesn't report flops (:func:`compiled_with_flops`)."""
    return compiled_with_flops(jitted_fn, abstract_args)[0]


def correct_stack_flops(f: float, depth: int, bf_counted: Optional[float],
                        bf_true: Optional[float]):
    """Fix a step's cost-analysis FLOPs for a lax.scan-ned layer stack →
    ``(corrected_flops, label)``.

    XLA counts a scan body once, so a depth-D stacked model reports
    ~1/D of its stack FLOPs; Pallas kernels are opaque custom calls
    counted as 0. Given one block's standalone measurements —
    ``bf_counted`` (as the step runs it) and ``bf_true``
    (dense-equivalent, fully counted) — swap the counted contribution
    for the true cost at full depth. A scan-once count contains the body
    ~once (``f ≈ overhead + bf_counted``); an unrolled / per-iteration
    count contains it ~``depth`` times (``f ≥ depth·bf_counted``). The
    midpoint ``(1+depth)/2 · bf_counted`` separates the two regimes even
    when non-stack step FLOPs (embed/head/optimizer) exceed one block's
    counted FLOPs — the old fixed ``2·bf_counted`` threshold mislabeled
    such steps per-iteration (round-3 advisor finding). Returns the input
    unchanged with label ``probe_failed`` when the block numbers are
    unusable — the caller must then NOT publish the (known ~1/depth
    wrong) figure as honest.
    """
    if not (depth and depth > 1 and bf_counted and bf_true):
        return f, "probe_failed"
    if f < (1 + depth) / 2 * bf_counted:
        return f - bf_counted + depth * bf_true, f"scan_once_x{depth}"
    return f + depth * (bf_true - bf_counted), "per_iteration"


@contextlib.contextmanager
def profile_trace(log_dir: Optional[str]):
    """Capture an XLA profiler trace into ``log_dir`` when set."""
    if not log_dir:
        yield
        return
    import jax
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()

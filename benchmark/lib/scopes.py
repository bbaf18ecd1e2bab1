"""Joins what the benchmark observes with what the program names: the
trace's operations with the program's instruction-to-layer maps
(``dml_cnn_cifar10_tpu.utils.devprof.scope_maps()``, built once a ``fit``
from the compiled dispatch), and the host's phases with the program's
span counters (``dml_span_seconds_total{name}`` of its metrics registry).

Device figures keep ``xplane``'s rules: ONE device's union of leaf
intervals, the mean over the device planes, never a sum of nested events.
A program that has no such map or counter (the parent of the PR that
added them, a run without telemetry) gives ``None``, never 0.
"""

from __future__ import annotations

import re
import sys
from typing import Dict, List, NamedTuple, Optional, Tuple

from benchmark.lib import xplane

KINDS = ("conv", "pool", "norm_act", "dense", "decode", "optimizer")
_WHILE = re.compile(r"=\s.*\swhile\(|^%?while[.\d]*\s")


def program_maps() -> Optional[dict]:
    """``{module: {instruction: entry}}`` of this process, or nothing."""
    try:
        from dml_cnn_cifar10_tpu.utils import devprof
    except ImportError:
        return None
    get = getattr(devprof, "scope_maps", None)
    return (get() if get is not None else None) or None


def resolve(name: str, in_while: bool, maps: dict):
    """The entry of an event's instruction. A name that several modules
    hold (the dispatch and the boundary's accuracy program number their
    fusions alike) is told apart by whether the event lies inside a
    ``while`` of the dispatch; what is still ambiguous and disagrees on
    kind or pass has no entry."""
    found = [m[name] for m in maps.values() if name in m]
    if len(found) > 1:
        found = [e for e in found if e.in_loop == in_while] or found
        if len({(e.kind, e.pass_) for e in found}) > 1:
            return None
    return found[0] if found else None


class PlaneSplit(NamedTuple):
    """Leaf intervals of one device plane's ``XLA Ops`` line."""

    by_kind: Dict[str, List[Tuple[float, float]]]
    by_pass: Dict[str, List[Tuple[float, float]]]
    mixed: List[Tuple[float, float]]     # fusions of several layers
    inherited: List[Tuple[float, float]]  # copies named by their consumer
    left: Dict[str, float]               # ns by name, no layer kind


def split_plane(plane: xplane.DevicePlane, maps: dict) -> PlaneSplit:
    ops = sorted((o for o in plane.ops if o.line == "XLA Ops"),
                 key=lambda o: (o.start, -(o.end - o.start)))
    by_kind: Dict[str, list] = {}
    by_pass: Dict[str, list] = {}
    mixed: list = []
    inherited: list = []
    left: Dict[str, float] = {}
    stack: List[list] = []      # [op, is a while, holds another event]

    def close(item):
        op, _, parent = item
        if parent:
            return
        entry = resolve(op.name, any(w for _, w, _ in stack), maps)
        if entry is not None:
            by_kind.setdefault(entry.kind, []).append((op.start, op.end))
            by_pass.setdefault(entry.pass_, []).append((op.start, op.end))
            if entry.mixed:
                mixed.append((op.start, op.end))
            if entry.inherited:
                inherited.append((op.start, op.end))
        if entry is None or entry.kind not in KINDS:
            left[op.name] = left.get(op.name, 0.0) + op.end - op.start

    for op in ops:
        # what does not span this event is over, or overlaps it without
        # holding it (a kernel that starts inside its neighbour)
        while stack and stack[-1][0].end < op.end:
            close(stack.pop())
        if stack:
            stack[-1][2] = True
        stack.append([op, bool(_WHILE.search(op.text)), False])
    while stack:
        close(stack.pop())
    return PlaneSplit(by_kind, by_pass, mixed, inherited, left)


_LAST: list = [None, None]     # the trace split last, and its splits


def splits(trace: xplane.Trace) -> Optional[List[PlaneSplit]]:
    """One split a device plane, made once a trace."""
    maps = program_maps()
    if trace is None or maps is None:
        return None
    if _LAST[0] is not trace:
        _LAST[:] = [trace, [split_plane(p, maps) for p in trace.planes]]
        _report(trace, _LAST[1])
    return _LAST[1]


def _report(trace: xplane.Trace, sp: List[PlaneSplit], top: int = 8) -> None:
    """One line to standard error, once a trace: the share of busy time in
    fusions of several layers, the share in copies that are counted with
    the layer of their consumer (a guess of the map's), and the leaves no
    layer kind covers."""
    busy = trace.busy_s() * 1e9
    left = sorted(sp[0].left.items(), key=lambda kv: -kv[1])[:top]
    print(f"scopes mixed_pct={100 * xplane.length(sp[0].mixed) / busy:.2f} "
          f"inherited_pct={100 * xplane.length(sp[0].inherited) / busy:.2f} "
          "left " + " ".join(f"{n}={100 * ns / busy:.2f}%" for n, ns in left),
          file=sys.stderr)


def _mean_s(parts: List[float]) -> float:
    return sum(parts) / len(parts) / 1e9


def kind_ms_per_step(ctx, kind: str) -> Optional[float]:
    """Device milliseconds a step in instructions of ``kind``, both
    passes; nothing where no plane holds such an instruction."""
    sp = splits(ctx["trace"])
    if sp is None or not any(s.by_kind.get(kind) for s in sp):
        return None
    return 1e3 * _mean_s([xplane.length(s.by_kind.get(kind, ()))
                          for s in sp]) / ctx["steps"]


def pass_pct(ctx, pass_: str) -> Optional[float]:
    """Share of the device's busy time in instructions of ``pass_``."""
    sp = splits(ctx["trace"])
    if sp is None or not any(s.by_pass.get(pass_) for s in sp):
        return None
    return 100.0 * _mean_s([xplane.length(s.by_pass.get(pass_, ()))
                            for s in sp]) / ctx["trace"].busy_s()


def mixed_pct(ctx) -> Optional[float]:
    """Share of the device's busy time in fusions whose working
    instructions come from several layers. Such a fusion goes whole to
    its root's layer, so this much of the per-kind split is the
    compiler's fusion and not the layers'. Nothing where the maps flag no
    instruction of the trace."""
    sp = splits(ctx["trace"])
    if sp is None or not any(s.mixed for s in sp):
        return None
    return 100.0 * _mean_s([xplane.length(s.mixed) for s in sp]) \
        / ctx["trace"].busy_s()


def unattributed_pct(ctx) -> Optional[float]:
    """Share of the device's busy time that no instruction with a layer
    kind covers: kind ``none``, names the maps do not hold or cannot tell
    apart, a ``while``'s own time. The tracing's error bar; below 0 the
    kinds cover more than the device was busy, and the join is at fault."""
    sp = splits(ctx["trace"])
    if sp is None:
        return None
    named = _mean_s([xplane.length(
        iv for k in KINDS for iv in s.by_kind.get(k, ())) for s in sp])
    return 100.0 * (1.0 - named / ctx["trace"].busy_s())


def _span_values(metric: str, name: str) -> Optional[float]:
    try:
        from dml_cnn_cifar10_tpu.utils import metrics_registry
    except ImportError:
        return None
    family = metrics_registry.default_registry().get(metric)
    return None if family is None else family.values().get((name,))


def span_counter(name: str) -> Optional[Tuple[float, float]]:
    """``(seconds, count)`` of the program's finished spans of one name,
    over the whole process (both ``fit``s, whatever ended before the
    window opened included), from its registry's two span counters."""
    n = _span_values("dml_spans_total", name)
    if not n:
        return None
    return _span_values("dml_span_seconds_total", name) or 0.0, n


def span_seconds(name: str) -> Optional[float]:
    found = span_counter(name)
    return None if found is None else found[0]

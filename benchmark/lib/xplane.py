"""From a profiler trace to device times. Reads the ``.xplane.pb`` that
``jax.profiler`` writes with ``jax.profiler.ProfileData`` and nothing else.

What a TPU trace looks like (looked at by hand, PR 23): one plane per
chip, named ``/device:TPU:<n>``, with the lines ``Steps``, ``XLA
Modules``, ``XLA Ops`` and ``Async XLA Ops``. An event of ``XLA Ops`` is
one HLO instruction; a ``while`` and the instructions of its body are all
events of that line, so events nest, and durations must not be summed.
Every figure here is per device: the union of intervals on ONE plane.
Figures "of the trace" are the mean over the device planes.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Callable, Dict, Iterable, List, NamedTuple, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OP_LINES = ("XLA Ops", "Async XLA Ops")
COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)(-start|-done)?\b")


class TraceError(RuntimeError):
    """The trace lacks what a figure needs: no device plane, no operation
    line, a scope that no event carries. Never read as 0."""


class NotInTrace(TraceError):
    """The planes are there, and no event of one is what was asked for (a
    scope's kernels, a collective). The reducer never reads that as 0; a
    metric's reader may read it as "nothing to read" and return nothing,
    as when a later PR takes the kernel off the path."""


class Op(NamedTuple):
    start: float     # ns
    end: float       # ns
    name: str        # the instruction's name, without its operands
    text: str        # all the event says: HLO text and stats, for scopes
    line: str


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                   float]]:
    """Merged, sorted intervals: overlapping and nested ones count once."""
    out: List[List[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in union(intervals))


def subtract(a: Iterable[Tuple[float, float]],
             b: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The parts of ``a`` that no interval of ``b`` covers."""
    cover = union(b)
    out = []
    for s, e in union(a):
        cur = s
        for cs, ce in cover:
            if ce <= cur:
                continue
            if cs >= e:
                break
            if cs > cur:
                out.append((cur, cs))
            cur = max(cur, ce)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def short_name(event_name: str) -> str:
    """``%fusion.12 = f32[..] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def self_times(ops: List[Op]) -> Dict[str, float]:
    """Seconds by instruction name, an instruction's time less that of the
    events inside it (a ``while`` less its body)."""
    out: Dict[str, float] = {}
    stack: List[Tuple[Op, List[Tuple[float, float]]]] = []

    def close(item):
        op, inside = item
        out[op.name] = out.get(op.name, 0.0) \
            + (op.end - op.start - length(inside)) / 1e9

    for op in sorted(ops, key=lambda o: (o.start, -(o.end - o.start))):
        while stack and stack[-1][0].end <= op.start:
            close(stack.pop())
        for holder, inside in stack:
            inside.append((op.start, min(op.end, holder.end)))
        stack.append((op, []))
    while stack:
        close(stack.pop())
    return out


class DevicePlane(NamedTuple):
    name: str
    ops: List[Op]

    def busy(self) -> List[Tuple[float, float]]:
        return union((o.start, o.end) for o in self.ops)

    def matching(self, pred: Callable[[Op], bool]) -> List[Op]:
        return [o for o in self.ops if pred(o)]


class Trace(NamedTuple):
    planes: List[DevicePlane]
    marker_ns: float = None   # trace time of the harness's host marker

    def _mean(self, per_plane: Callable[[DevicePlane], float]) -> float:
        return sum(per_plane(p) for p in self.planes) / len(self.planes)

    def busy_s(self) -> float:
        """Seconds in which an operation ran on a device."""
        return self._mean(lambda p: length(p.busy()) / 1e9)

    def scope_s(self, scope: str) -> float:
        """Seconds of the operations of ``scope``: instructions that take
        the scope's name (``optimizer.12``: a kernel called under
        ``jax.named_scope`` is named after it), or that carry it in a
        name-scope path among their stats. Loud when no event of a plane
        does."""
        pat = re.compile(r"(^|[/\"\s(=])" + re.escape(scope) + r"([/\"\s)]|$)")
        named = re.compile(r"^" + re.escape(scope) + r"(\.\d+)?$")

        def one(p: DevicePlane) -> float:
            found = p.matching(lambda o: bool(named.match(o.name)
                                              or pat.search(o.text)))
            if not found:
                raise NotInTrace(f"no operation under scope {scope!r} on "
                                 f"{p.name}")
            leaves = _leaves(found)
            return length((o.start, o.end) for o in leaves) / 1e9
        return self._mean(one)

    def exposed_collective_s(self) -> float:
        """Seconds in which a collective ran on a device and no other
        operation did; loud when a plane has no collective."""
        def one(p: DevicePlane) -> float:
            coll = p.matching(lambda o: bool(COLLECTIVE.search(o.name)))
            if not coll:
                raise NotInTrace(f"no collective operation on {p.name}")
            rest = _leaves(p.matching(
                lambda o: not COLLECTIVE.search(o.name)))
            return sum(e - s for s, e in subtract(
                ((o.start, o.end) for o in coll),
                ((o.start, o.end) for o in rest))) / 1e9
        return self._mean(one)


def _leaves(ops: List[Op]) -> List[Op]:
    """Events that hold no other event of the list: a ``while`` or a
    ``call`` spans its body and would cover everything."""
    out = []
    ordered = sorted(ops, key=lambda o: (o.start, -(o.end - o.start)))
    for i, op in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is not None and nxt.start < op.end and nxt.end <= op.end \
                and (nxt.start, nxt.end) != (op.start, op.end):
            continue
        out.append(op)
    return out


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a directory (or the file itself)."""
    if os.path.isfile(trace_dir):
        return trace_dir
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def from_profile(profile, marker: str = None) -> Trace:
    """``profile`` is a ``jax.profiler.ProfileData``."""
    planes, marker_ns = [], None
    for plane in profile.planes:
        if marker is not None and plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == marker:
                        marker_ns = e.start_ns
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops = []
        for line in plane.lines:
            if line.name not in OP_LINES:
                continue
            for e in line.events:
                text = e.name + " " + " ".join(
                    f"{k}={v}" for k, v in e.stats)
                ops.append(Op(e.start_ns, e.start_ns + e.duration_ns,
                              short_name(e.name), text, line.name))
        if not ops:
            raise TraceError(f"device plane {plane.name} has no event on "
                             f"the lines {OP_LINES}")
        planes.append(DevicePlane(plane.name, ops))
    if not planes:
        raise TraceError("the trace has no device plane")
    return Trace(sorted(planes, key=lambda p: p.name), marker_ns)


def load(trace_dir: str, marker: str = None) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(find_xplane(trace_dir)),
                        marker)


def breakdown(trace: Trace, window=None, top: int = 10) -> dict:
    """The device operations that took most time on the first chip, by the
    names the trace prints, and its longest idle gaps by the program span
    the host was in (``window`` carries the spans and the host clock of
    the trace marker)."""
    plane = trace.planes[0]
    ops = sorted(self_times([o for o in plane.ops if o.line == "XLA Ops"]
                            ).items(), key=lambda kv: -kv[1])[:top]
    busy = plane.busy()
    gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    named: Dict[str, float] = {}
    for s, e in gaps:
        label = "unattributed"
        if window is not None and trace.marker_ns is not None \
                and window.marker_perf_ns is not None:
            mid = ((s + e) / 2 - trace.marker_ns
                   + window.marker_perf_ns) / 1e9
            inside = [sp for sp in window.spans
                      if sp.start <= mid <= sp.start + sp.dur]
            if inside:
                label = max(inside, key=lambda sp: sp.depth).name
            else:
                label = "between_spans"
        named[label] = named.get(label, 0.0) + (e - s) / 1e9
    idle = sorted(named.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in idle]}

"""Device time by any property of the program's instruction-to-layer
entries (``dml_cnn_cifar10_tpu.utils.devprof.ScopeEntry``): the pass
``recompute``, a sublayer's ``part``, the ``inherited`` flag, whatever a
predicate on ``(instruction name, entry)`` keeps.

The join itself is ``scopes.split_plane``'s and stays there: this module
hands it the program's maps with each entry's ``kind`` replaced by a label
(kept or not; for the table, the entry's own kind, pass and part with the
instruction's name) and reads the leaf intervals back under that label.
So every figure keeps ``xplane``'s rules: ONE device's union of leaf
intervals, the mean over the device planes, never a sum of nested events.

A program that has no map, or whose entries have no ``part`` (the parent
of the PR that added it), gives ``None``, never 0.

Once a trace, to standard error: milliseconds a step by kind, pass and
part (rows of 0.5 ms or more) and the 20 largest instructions with their
scope, kind, pass and part, which is what a builder needs to put a step's
time down to its layers.
"""

from __future__ import annotations

import sys
from typing import Callable, Dict, List, Optional

from benchmark.lib import scopes, xplane

ROW_MS = 0.5
TOP = 20


def program_maps() -> Optional[dict]:
    """The program's maps where their entries tell a part, else nothing."""
    maps = scopes.program_maps()
    if maps is None or not all(hasattr(e, "part") for entries
                               in maps.values() for e in entries.values()):
        return None
    return maps


def split_by(trace: xplane.Trace, maps: dict, label: Callable
             ) -> List[scopes.PlaneSplit]:
    """``scopes.split_plane`` of every device plane on maps whose entries'
    ``kind`` is ``label(name, entry)``: ``by_kind`` holds a plane's leaf
    intervals by label."""
    relabelled = {module: {name: e._replace(kind=label(name, e))
                           for name, e in entries.items()}
                  for module, entries in maps.items()}
    return [scopes.split_plane(p, relabelled) for p in trace.planes]


_LAST: list = [None]     # the trace whose table was printed last


def _kept_s(ctx, keep: Callable) -> Optional[float]:
    """Seconds of the traced window, the mean over the device planes, in
    the instructions that ``keep(name, entry)`` holds for; nothing where
    no plane has one."""
    trace, maps = ctx["trace"], program_maps()
    if trace is None or maps is None:
        return None
    if _LAST[0] is not trace:
        _LAST[0] = trace
        _report(ctx, maps)
    kept = [s.by_kind.get(True, ()) for s in split_by(
        trace, maps, lambda name, e: bool(keep(name, e)))]
    if not any(kept):
        return None
    return sum(xplane.length(iv) for iv in kept) / len(kept) / 1e9


def ms_per_step(ctx, keep: Callable) -> Optional[float]:
    """Device milliseconds a step in the instructions kept."""
    s = _kept_s(ctx, keep)
    return None if s is None else 1e3 * s / ctx["steps"]


def pct_of_busy(ctx, keep: Callable) -> Optional[float]:
    """Share of the device's busy time in the instructions kept."""
    s = _kept_s(ctx, keep)
    return None if s is None else 100.0 * s / ctx["trace"].busy_s()


ATTENTION = ("attention", "window_attention")
PROJECTIONS = ("qkv", "out")


def attention_projection(name: str, e) -> bool:
    """The four projections of an attention sublayer, all passes."""
    return e.kind in ATTENTION and e.part in PROJECTIONS


def attention_glue(name: str, e) -> bool:
    """What surrounds the projections and the flash kernels' launches
    (instructions named ``flash_*``) in an attention sublayer: rotary, the
    per-head norms, relayouts, the log-sum-exp tiles, copies."""
    return e.kind in ATTENTION and e.part not in PROJECTIONS \
        and not name.startswith("flash_")


def _report(ctx, maps: dict) -> None:
    """The table, from the first device plane."""
    by = split_by(ctx["trace"], maps, lambda name, e: (
        e.kind, e.pass_, e.part, name, e.scope))[0].by_kind
    scale = 1e-6 / ctx["steps"]
    rows: Dict[tuple, float] = {}
    for key, intervals in by.items():
        rows[key[:3]] = rows.get(key[:3], 0.0) + xplane.length(intervals)
    out = [f"scope_parts ms a step by kind, pass, part (rows of {ROW_MS} "
           f"ms or more; {ctx['steps']} steps, first plane)"]
    for (kind, pass_, part), ns in sorted(rows.items(),
                                          key=lambda kv: -kv[1]):
        if ns * scale >= ROW_MS:
            out.append(f"  {ns * scale:10.3f}  {kind:<17}{pass_:<10}{part}")
    out.append(f"scope_parts the {TOP} largest instructions")
    largest = sorted(by.items(), key=lambda kv: -xplane.length(kv[1]))[:TOP]
    for (kind, pass_, part, name, scope), intervals in largest:
        out.append(f"  {xplane.length(intervals) * scale:10.3f}  "
                   f"{name:<42}{kind:<17}{pass_:<10}{part or '-':<18}"
                   f"{scope}")
    # what the table lacks of the plane's busy time: a `while`'s own time,
    # async copies beside no leaf, and leaves without an entry (a name no
    # map holds, or one that two modules hold and tell differently; a kind
    # of `scopes.KINDS` for every entry leaves those that differ by pass
    # or are unknown in `left`, by name)
    plane = ctx["trace"].planes[0]
    lacks = xplane.length(plane.busy()) - sum(rows.values())
    left = split_by(ctx["trace"], maps,
                    lambda name, e: scopes.KINDS[0])[0].left
    out.append(f"scope_parts not in the table {lacks * scale:.3f} ms a step;"
               " leaves without an entry: " + " ".join(
                   f"{n}={ns * scale:.3f}" for n, ns in sorted(
                       left.items(), key=lambda kv: -kv[1])[:8]))
    print("\n".join(out), file=sys.stderr)

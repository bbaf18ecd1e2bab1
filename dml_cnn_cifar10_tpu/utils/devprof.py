"""Device-time performance attribution: programmatic profiler capture
windows and a zero-fetch device step-time estimator.

The PR-1 telemetry layer (``utils/telemetry.py``) times the HOST loop —
it can say the run spent 95% of wall-clock "training" and still not know
where the device spent that time. This module closes that gap from two directions, both honoring the loop's round-trip budget
(zero extra device fetches — ``tests/test_telemetry.py`` pins it):

- :class:`ProfileWindow` — ``--profile_at_steps N:K`` arms a
  programmatic ``jax.profiler`` capture from global step N for K steps,
  written under ``--profile_dir`` (default ``<log_dir>/devprof``). On
  stop, the captured Chrome trace is parsed HOST-SIDE into a per-lane
  device-time table — top-k ops and compute / collective / infeed
  buckets — and emitted as ``devtime`` JSONL records that
  ``tools/telemetry_report.py`` renders. No trace UI required to answer
  "which op owns the step".
- :class:`DeviceStepEstimator` — an always-on per-boundary estimate of
  the device-side step time, measured as the block-until-ready delta at
  the loop's EXISTING fused metrics fetch (the fetch drains everything
  dispatched since the last boundary, so ``drain_end − window_start``
  bounds the device's busy window; divided by the steps in the window
  it is the per-step device time). ``train`` rows gain
  ``device_step_ms`` + ``drain_wait_ms``: a ``drain_wait_ms`` near the
  full window means the host idled on the device (device-bound — the
  step itself must get faster); near zero means the device idled on the
  host (host-bound — feed it better). Two ``perf_counter`` reads per
  boundary, no device traffic.

Bucket semantics (op names, lowercased): ``collective`` matches the
cross-device primitives (all-reduce / all-gather / reduce-scatter /
all-to-all / collective-permute / send / recv), ``infeed`` matches data
movement (in/outfeed, copies, transfers), everything else is
``compute``. On backends whose profiler emits no per-op device lanes
(CPU: host-side runtime events only) the parser falls back to the host
lanes so the record shape — and the tier-1 tests — stay identical; the
table then attributes runtime phases rather than XLA ops.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import sys
import time
from typing import List, NamedTuple, Optional, Tuple

#: Device-time buckets, in report order.
DEVTIME_BUCKETS = ("compute", "collective", "infeed")

#: named_scope phases attributed as their own (overlapping) totals, in
#: addition to the exclusive buckets above: the train step wraps its
#: grad and update phases in jax.named_scope("fwd_bwd"/"optimizer")
#: (parallel/step.py), and the scope name survives into the emitted op
#: names / metadata — so `optimizer_ms` is MEASURED attribution of the
#: weight-update tail (the ZeRO-1 / fused-kernel target), not inference.
SCOPE_RE = re.compile(r"optimizer")

_COLLECTIVE_RE = re.compile(
    r"all[-_]?reduce|all[-_]?gather|reduce[-_]?scatter|all[-_]?to[-_]?all"
    r"|collective[-_]?permute|collective|ppermute|psum|\bsend\b|\brecv\b")
_INFEED_RE = re.compile(
    r"infeed|outfeed|\bcopy\b|copy[-_]?start|copy[-_]?done|transfer"
    r"|memcpy|h2d|d2h|host[-_]?to[-_]?device|device[-_]?to[-_]?host")


def classify_op(name: str) -> str:
    """Bucket an op/event name: ``collective`` | ``infeed`` | ``compute``."""
    low = name.lower()
    if _COLLECTIVE_RE.search(low):
        return "collective"
    if _INFEED_RE.search(low):
        return "infeed"
    return "compute"


def parse_profile_at_steps(spec: Optional[str]):
    """``"N:K"`` → ``(start_step, n_steps)``; None/empty → None.

    Validated loudly: a typo'd capture spec silently profiling nothing
    would be the worst kind of observability bug.
    """
    if not spec:
        return None
    parts = spec.split(":")
    try:
        if len(parts) != 2:
            raise ValueError
        start, n = int(parts[0]), int(parts[1])
    except ValueError:
        raise ValueError(
            f"--profile_at_steps must be START:COUNT (e.g. 100:20), got "
            f"{spec!r}")
    if start < 0 or n < 1:
        raise ValueError(
            f"--profile_at_steps needs START >= 0 and COUNT >= 1, got "
            f"{spec!r}")
    return start, n


def parse_trace_doc(doc: dict, top_k: int = 12) -> List[dict]:
    """Chrome-trace dict → per-lane device-time records (no I/O).

    Lane selection prefers the profiler's device lanes (process names
    containing ``/device:``); absent those (CPU backend) it falls back
    to host lanes, then to any lane with complete events. Durations are
    summed per op name within a lane — nested host events double-count
    their parents, which is why device lanes (flat per-op rows) are
    preferred when present.
    """
    events = doc.get("traceEvents") or []
    pid_names = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pid_names[e.get("pid")] = (e.get("args") or {}).get("name", "")
    xs = [e for e in events
          if e.get("ph") == "X" and e.get("dur") is not None]
    if not xs:
        return []
    pids_with_x = {e.get("pid") for e in xs}
    device_pids = {p for p in pids_with_x
                   if "/device:" in (pid_names.get(p) or "")}
    host_pids = {p for p in pids_with_x
                 if "/host:" in (pid_names.get(p) or "")}
    lanes = device_pids or host_pids or pids_with_x
    out = []
    for pid in sorted(lanes, key=lambda p: (str(pid_names.get(p, "")), p)):
        evs = [e for e in xs if e.get("pid") == pid]
        if not evs:
            continue
        by_op = {}
        optimizer_us = 0.0
        t_lo = min(e["ts"] for e in evs)
        t_hi = max(e["ts"] + e["dur"] for e in evs)
        for e in evs:
            agg = by_op.setdefault(e.get("name") or "?", [0.0, 0])
            agg[0] += e["dur"]          # microseconds
            agg[1] += 1
            # Scope attribution: the named_scope prefix may live in the
            # event name OR in the profiler's metadata args (long_name /
            # tf_op carry the full HLO op_name on XLA device lanes).
            args = e.get("args") or {}
            text = " ".join((e.get("name") or "",
                             str(args.get("name", "")),
                             str(args.get("long_name", "")),
                             str(args.get("tf_op", "")))).lower()
            if SCOPE_RE.search(text):
                optimizer_us += e["dur"]
        buckets = dict.fromkeys(DEVTIME_BUCKETS, 0.0)
        total_us = 0.0
        for name, (dur_us, _calls) in by_op.items():
            buckets[classify_op(name)] += dur_us
            total_us += dur_us
        top = sorted(by_op.items(), key=lambda kv: -kv[1][0])[:top_k]
        out.append({
            "device": pid_names.get(pid) or f"pid:{pid}",
            "total_ms": round(total_us / 1e3, 3),
            "compute_ms": round(buckets["compute"] / 1e3, 3),
            "collective_ms": round(buckets["collective"] / 1e3, 3),
            "infeed_ms": round(buckets["infeed"] / 1e3, 3),
            # OVERLAPPING scope total (a subset of the buckets above,
            # not a fourth one): device time inside the step's
            # jax.named_scope("optimizer") — the weight-update tail.
            "optimizer_ms": round(optimizer_us / 1e3, 3),
            "window_ms": round((t_hi - t_lo) / 1e3, 3),
            "top_ops": [
                {"name": name, "bucket": classify_op(name),
                 "dur_ms": round(dur_us / 1e3, 3), "calls": calls,
                 "frac": round(dur_us / total_us, 4) if total_us else 0.0}
                for name, (dur_us, calls) in top],
        })
    return out


def parse_profile_dir(profile_dir: str, top_k: int = 12) -> List[dict]:
    """Parse the NEWEST capture session under a ``jax.profiler`` output
    dir (``<dir>/plugins/profile/<timestamp>/*.trace.json[.gz]``) into
    per-lane records; ``[]`` when nothing parseable is there."""
    sessions = sorted(glob.glob(
        os.path.join(profile_dir, "plugins", "profile", "*")))
    if not sessions:
        return []
    lanes: List[dict] = []
    paths = (glob.glob(os.path.join(sessions[-1], "*.trace.json.gz"))
             + glob.glob(os.path.join(sessions[-1], "*.trace.json")))
    for path in sorted(paths):
        try:
            if path.endswith(".gz"):
                with gzip.open(path, "rt") as f:
                    doc = json.load(f)
            else:
                with open(path) as f:
                    doc = json.load(f)
            lanes.extend(parse_trace_doc(doc, top_k=top_k))
        except (OSError, ValueError):
            continue
    return lanes


class ProfileWindow:
    """Step-gated ``jax.profiler`` capture + host-side trace parsing.

    The driver calls :meth:`maybe_start` at each dispatch seam (arms at
    the first seam at/after ``start_step``) and :meth:`maybe_stop` at
    each iteration end with the boundary's ``drained`` flag — the stop
    waits for a DRAINED boundary at/after ``start+n_steps`` so the
    captured window closes on quiesced devices instead of truncating
    in-flight dispatches. :meth:`close` (the loop's ``finally``) stops a
    window the run ended inside of. Fail-open throughout: a profiler or
    parse error prints one warning and the training run continues.
    """

    def __init__(self, start_step: int, n_steps: int, out_dir: str,
                 logger=None, top_k: int = 12):
        self.start_step = start_step
        self.n_steps = n_steps
        self.out_dir = out_dir
        self.logger = logger
        self.top_k = top_k
        self.state = "pending"            # pending -> active -> done
        self._armed_at = start_step       # actual arm step once active
        # Per-step optimizer device time from the parsed window (mean
        # over lanes of optimizer_ms / steps-in-window); None until a
        # window completes. Train rows after the window carry it as
        # `optimizer_ms` — measured attribution of the update tail.
        self.optimizer_step_ms: Optional[float] = None

    @classmethod
    def from_config(cls, cfg, logger=None) -> Optional["ProfileWindow"]:
        """Build the capture window the config asked for (None = flag
        off). Composes with ``--profile_dir``: the window writes there
        when set (so the host-loop Chrome trace, the XLA trace, and the
        parsed ``devtime`` table all describe the same run), else under
        ``<log_dir>/devprof``."""
        spec = parse_profile_at_steps(
            getattr(cfg, "profile_at_steps", None))
        if spec is None:
            return None
        out_dir = cfg.profile_dir or os.path.join(cfg.log_dir, "devprof")
        return cls(spec[0], spec[1], out_dir, logger=logger)

    def maybe_start(self, step: int) -> None:
        if self.state != "pending" or step < self.start_step:
            return
        self.state = "active"
        self._armed_at = step
        try:
            import jax
            jax.profiler.start_trace(self.out_dir)
        except Exception as e:              # fail-open
            print(f"[devprof] profiler start failed at step {step}: "
                  f"{e!r}", file=sys.stderr)
            self.state = "done"

    def maybe_stop(self, step: int, drained: bool = True) -> None:
        if self.state != "active" or not drained \
                or step < self.start_step + self.n_steps:
            return
        self._finish(step)

    def close(self, step: int) -> None:
        """End-of-run stop for a window the run finished inside."""
        if self.state == "active":
            self._finish(step)

    def _finish(self, step: int) -> None:
        self.state = "done"
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception as e:
            print(f"[devprof] profiler stop failed at step {step}: {e!r}",
                  file=sys.stderr)
            return
        try:
            lanes = parse_profile_dir(self.out_dir, top_k=self.top_k)
        except Exception as e:
            print(f"[devprof] trace parse failed: {e!r}", file=sys.stderr)
            return
        if not lanes:
            print(f"[devprof] no parseable trace under {self.out_dir}",
                  file=sys.stderr)
            return
        steps = max(1, step - self._armed_at)
        self.optimizer_step_ms = round(
            sum(ln.get("optimizer_ms") or 0.0 for ln in lanes)
            / len(lanes) / steps, 4)
        for lane in lanes:
            if self.logger is not None:
                self.logger.log("devtime", step=step, **lane)
            top = lane["top_ops"][0] if lane["top_ops"] else None
            head = (f"; top op {top['name']} {top['dur_ms']:.1f} ms "
                    f"({100 * top['frac']:.1f}%)") if top else ""
            print(f"[devprof] {lane['device']}: {lane['total_ms']:.1f} ms "
                  f"attributed over steps {self._armed_at}..{step} "
                  f"(compute {lane['compute_ms']:.1f} / collective "
                  f"{lane['collective_ms']:.1f} / infeed "
                  f"{lane['infeed_ms']:.1f}){head}")


class DeviceStepEstimator:
    """Per-boundary device step-time estimate from the fused fetch.

    Protocol mirrors ``DrainMeter`` (utils/profiling.py): ``mark(step)``
    at the end of any iteration that drained (and once after the first
    dispatch returns), then at a metrics boundary wrap the existing
    fused ``device_get`` with two clock reads and call :meth:`boundary`.
    The window ``[mark, drain_end]`` contains every training dispatch
    since the mark plus the drain itself; the device executes that
    window's steps back-to-back (modulo input starvation), so
    ``(drain_end − mark) / steps`` estimates the per-step device time
    and ``drain_end − drain_start`` is the host's blocked share (host
    idle ⇔ device busy). An upper bound when the device starves — the
    profiler window (:class:`ProfileWindow`) adjudicates that case.
    """

    __slots__ = ("_mark",)

    def __init__(self):
        self._mark = None

    def mark(self, step: int, now: Optional[float] = None) -> None:
        self._mark = (step, time.perf_counter() if now is None else now)

    def boundary(self, step: int, drain_start: float, drain_end: float):
        """→ ``(device_step_ms, drain_wait_ms)``; the first is ``None``
        before any mark (schema keys stay present, null-valued)."""
        drain_ms = round(max(drain_end - drain_start, 0.0) * 1e3, 3)
        if self._mark is None:
            return None, drain_ms
        mark_step, mark_t = self._mark
        steps = step - mark_step
        if steps <= 0:
            return None, drain_ms
        return round((drain_end - mark_t) / steps * 1e3, 4), drain_ms


# ---------------------------------------------------------------------------
# Instruction -> layer: the map from a compiled executable's optimized HLO
# ---------------------------------------------------------------------------
#
# A device trace names an event after its HLO instruction (`fusion.393`,
# `select-and-scatter.23`) and carries no name-scope path. The executable
# does: every instruction's `metadata={op_name=...}` holds the path of
# `jax.named_scope`s it was traced under, with the autodiff pass around
# each component (`.../fwd_bwd/transpose(jvp(conv1))/conv_general_dilated`).
# `scope_map` reads that once per compiled program; the join with a trace
# is by instruction name (`benchmark/lib/scopes.py`, or by hand with the
# `scopemap_<module>.json` written beside a capture).

#: Layer kinds, by the LAST scope component that a row knows.
LAYER_KINDS = (
    ("conv", re.compile(r"^(conv\d*|shortcut)$")),
    ("pool", re.compile(r"^pool\d*$")),
    ("norm_act", re.compile(r"^(bn\d*|add)$")),
    ("dense", re.compile(r"^(fc\d*|logits|loss)$")),
    ("decode", re.compile(r"^(decode|index|gather)$")),
    ("optimizer", re.compile(r"^optimizer$")),
    # a decoder over tokens (models/looped_decoder.py): the innermost
    # scope that names a kind decides, so `attn/qkv` is attention and
    # `exit/norm` is a norm
    ("attention", re.compile(r"^attn$")),
    ("mlp", re.compile(r"^mlp$")),
    ("norm", re.compile(r"^(attn_norm|attn_post_norm|mlp_norm|"
                        r"mlp_post_norm|norm|op_norm|ffn_norm|"
                        r"final_norm)$")),
    ("embed", re.compile(r"^embed$")),
    ("exit_head", re.compile(r"^(exit|head|gate)$")),
    # a decoder whose layers differ by a list (models/hybrid_decoder.py):
    # the gated short convolution with its two projections (not `conv`:
    # that is the image models'), the router with the ordering and
    # gathering of rows and the adding back, the experts' grouped products
    ("short_conv", re.compile(r"^short_conv$")),
    ("route", re.compile(r"^(route|dispatch|combine)$")),
    ("expert", re.compile(r"^experts$")),
    # attention under a sliding window, where a stack has both kinds (its
    # norm stays `norm`; the sublayer's scopes are `attn`'s)
    ("window_attention", re.compile(r"^attn_window$")),
    # multi-head latent attention (its parts `q`, `kv_down`, `kv_norm`,
    # `kv_up`, `rotary`, `flash`, `out`) and the experts every token takes
    ("latent_attention", re.compile(r"^mla$")),
    ("shared_expert", re.compile(r"^shared$")),
)
PASSES = ("forward", "recompute", "backward", "update", "other")

_HLO_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_HLO_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_HLO_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_HLO_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
_HLO_CALLED = re.compile(
    r"\b(calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)")
_HLO_CALLED_LIST = re.compile(
    r"\b(branch_computations|called_computations)=\{([^}]*)\}")
_HLO_REF = re.compile(r"%([\w.\-]+)")
_HLO_SAYS = re.compile(r'\b(?:custom_call_target="[^"]*"'
                       r'|frontend_attributes=\{[^}]*\})')
_TRANSFORM = re.compile(r"^(jvp|transpose|vmap|remat|checkpoint|"
                        r"custom_jvp|custom_vjp|shard_map)\((.*)\)$")
_CALL = re.compile(r"^(jit|pjit|closed_call|core_call|custom_jvp_call|"
                   r"custom_vjp_call)(\(.*\))?$")
#: What `jax.checkpoint` puts around the forward it forms again in the
#: backward pass (jax 0.9.0); the transposed products beside it sit under
#: `checkpoint` alone.
_REMATTED = "rematted_computation"
_PLUMBING = frozenset(("while", "body", "cond", "body_fun", "cond_fun",
                       "branch", "scan", "checkpoint", _REMATTED))
#: Instructions that move or name data and never decide what a fusion
#: costs: left out when a fusion's layers are counted.
_NO_WORK = frozenset((
    "parameter", "constant", "broadcast", "bitcast", "tuple",
    "get-tuple-element", "iota", "reshape", "copy", "convert", "transpose"))


class ScopeEntry(NamedTuple):
    """Where one instruction of an executable came from."""

    scope: str       # the named scopes, outermost first, joined by "/"
    kind: str        # a row of LAYER_KINDS, or "none"
    pass_: str       # one of PASSES
    mixed: bool      # a fusion whose instructions come from several layers
    in_loop: bool    # an instruction of a `while` body or condition
    inherited: bool = False   # an unnamed copy, counted with its consumer
    part: str = ""   # the scope after the one that decided the kind


def _split_path(op_name: str) -> List[str]:
    """``a/jvp(b/c)/d`` -> ``[a, jvp(b/c), d]``: slashes at depth 0."""
    parts, depth, cur = [], 0, []
    for ch in op_name:
        if ch == "/" and depth == 0:
            parts.append("".join(cur))
            cur = []
            continue
        depth += ch == "("
        depth -= ch == ")"
        cur.append(ch)
    parts.append("".join(cur))
    return [p for p in parts if p]


def parse_op_name(op_name: str):
    """An instruction's ``op_name`` -> ``(scope, kind, pass, part)``.

    ``part`` is the scope that follows the one that decided the kind
    (``layer2/attn_window/rotary`` is kind ``window_attention``, part
    ``rotary``; ``conv1`` has none). The pass is ``recompute`` where the
    path lies under a ``transpose(`` and holds ``rematted_computation``: a
    forward that ``jax.checkpoint`` forms again in the backward pass (the
    optimized HLO of the three decoders' dispatches, compiled for a v5e,
    names every product of a recomputed forward so, and the fusions around
    them). What a ``custom_vjp`` forms again by its own backward rule (the
    blockwise loss's logits, the experts' products in their written-out
    backward loop) is under no ``jax.checkpoint`` and stays ``backward``."""
    # XLA joins the names of instructions it merged with ";".
    comps = _split_path(op_name.split(";", 1)[0])
    backward = any("transpose(" in c for c in comps)
    relu = any("jit(relu)" in c for c in comps)
    if comps and not _TRANSFORM.match(comps[-1]):
        comps = comps[:-1]       # the primitive's own name
    scopes: List[str] = []
    rematted = False
    for c in comps:
        m = _TRANSFORM.match(c)
        while m:
            c = m.group(2)
            m = _TRANSFORM.match(c)
        for piece in _split_path(c):
            rematted = rematted or piece == _REMATTED
            if not (_CALL.match(piece) or piece in _PLUMBING):
                scopes.append(piece)
    kind, part = "none", ""
    for i in reversed(range(len(scopes))):
        kind = next((k for k, pat in LAYER_KINDS if pat.match(scopes[i])),
                    "none")
        if kind != "none":
            part = scopes[i + 1] if i + 1 < len(scopes) else ""
            break
    if kind == "none" and relu:
        kind = "norm_act"        # a ReLU under no layer's scope
    if backward:
        pass_ = "recompute" if rematted else "backward"
    elif "fwd_bwd" in scopes:
        pass_ = "forward"
    elif "optimizer" in scopes:
        pass_ = "update"
    else:
        pass_ = "other"
    return "/".join(scopes), kind, pass_, part


class _Instr(NamedTuple):
    name: str
    opcode: str
    root: bool
    op_name: str
    called: Tuple[Tuple[str, str], ...]    # (attribute, computation)
    refs: Tuple[str, ...]                  # every %name the line mentions
    says: str = ""     # a custom call's target and frontend attributes


def _parse_hlo(text: str):
    """``(module name, entry computation, {computation: [_Instr]})``."""
    module, entry, comps, cur = "", None, {}, None
    for line in text.splitlines():
        if cur is None:
            if line.startswith("HloModule "):
                module = line.split()[1].rstrip(",")
                continue
            m = _HLO_COMPUTATION.match(line)
            if m:
                cur = comps.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _HLO_INSTRUCTION.match(line)
        if not m:
            continue
        rest = m.group(3)
        op = _HLO_OPCODE.search(" " + rest)
        name = _HLO_OP_NAME.search(rest)
        called = [(a, c) for a, c in _HLO_CALLED.findall(rest)]
        for attr, names in _HLO_CALLED_LIST.findall(rest):
            called += [(attr, c.strip().lstrip("%"))
                       for c in names.split(",") if c.strip()]
        opcode = op.group(1) if op else ""
        cur.append(_Instr(m.group(2), opcode,
                          bool(m.group(1)), name.group(1) if name else "",
                          tuple(called), tuple(_HLO_REF.findall(rest)),
                          " ".join(_HLO_SAYS.findall(rest))
                          if opcode == "custom-call" else ""))
    return module, entry, comps


#: Copies the compiler puts in (a prefetch into the fast memory space, a
#: layout change) carry no metadata; their time belongs to the layer that
#: consumes them. What such a copy may be followed through to its user:
_COPIES = frozenset(("copy", "copy-start", "copy-done", "slice-start",
                     "slice-done"))
_THROUGH = _COPIES | frozenset(("bitcast", "reshape", "tuple",
                                "get-tuple-element", "custom-call"))

#: Kernels the compiler makes of an operation and names anew: the custom
#: call's metadata holds the new name and no scope, no source line, and its
#: target is every Mosaic kernel's. Their kind, by what the instruction
#: still says of itself (its name, its ``op_name``, its frontend
#: attributes): ``lax.ragged_dot`` becomes ``ragged-dot-none.<n>`` with a
#: ``ragged_dot_tiling`` and the scalar-core ``ragged-dot-metadata`` that
#: feeds it, and ``ops/layers.grouped_matmul`` (the experts' grouped
#: products) is its one caller.
_RENAMED_KERNELS = ((re.compile(r"ragged[-_]dot"), "expert"),)

#: Attributes whose computation runs as instructions of its own (events
#: of the trace), against `calls=` of a fusion and the scalar `to_apply`
#: of a reduce, which run inside their instruction.
_RUNS = {"while": ("body", "condition"), "call": ("to_apply",),
         "conditional": ("true_computation", "false_computation",
                         "branch_computations"),
         "async-start": ("calls", "called_computations")}


def scope_map_of_text(text: str):
    """``(module name, {instruction name: ScopeEntry})`` for every
    instruction that can be an event of a trace's ``XLA Ops`` line: those
    of the entry computation, of ``while`` bodies and conditions, of called
    computations and of conditional branches. A fusion goes by its own
    metadata and, where that names no layer, by its root's (then by the
    working instruction nearest the root that names one); one whose
    working instructions come from more than one layer is ``mixed``. A
    kernel the compiler named anew (``_RENAMED_KERNELS``) has the kind of
    what it is and the pass its operands and users tell. A copy without
    metadata (the compiler's prefetches and layout changes) takes the
    layer of the instruction that consumes it: ``inherited``, a guess."""
    module, entry, comps = _parse_hlo(text)

    def layers_in(comp: str, found: list) -> None:
        """``(scope, kind, pass, part)`` of the working instructions of a
        fused computation that name a layer, nested fusions included, in
        the order of the text (operands before their users)."""
        for ins in comps.get(comp, ()):
            if ins.opcode == "fusion":
                for attr, c in ins.called:
                    if attr == "calls":
                        layers_in(c, found)
            elif ins.opcode not in _NO_WORK and ins.op_name:
                parsed = parse_op_name(ins.op_name)
                if parsed[1] != "none":
                    found.append(parsed)

    def root_op_name(comp: str) -> str:
        for ins in comps.get(comp, ()):
            if ins.root:
                if ins.op_name or ins.opcode != "fusion":
                    return ins.op_name
                return next((root_op_name(c) for a, c in ins.called
                             if a == "calls"), "")
        return ""

    def reached(start: str, edges: dict, opcode: dict, tells) -> list:
        """The entries that ``tells`` holds for, nearest first, along
        ``edges`` (users or operands) from ``start`` through unnamed
        movers, a few hops within the same computation."""
        told, front, seen = [], [start], {start}
        for _ in range(4):
            nxt = [n for f in front for n in edges.get(f, ())
                   if n in out and n not in seen]
            seen.update(nxt)
            says = [n for n in nxt if tells(out[n])]
            told += [out[n] for n in says]
            front = [n for n in nxt if n not in says
                     and out[n].kind == "none" and opcode.get(n) in _THROUGH]
            if not front:
                break
        return told

    def settle(comp: str, renamed: list) -> None:
        """The passes of this computation's renamed kernels, then its
        copies. A kernel's pass is what its operands and users tell: one
        that a forward instruction reads is forward, one made from a
        backward (or a recomputed) value is that, and where all that
        tell anything agree it is theirs; else ``other``. An unnamed copy
        takes the layer of the first named instruction that consumes it."""
        users, operands, opcode = {}, {}, {}
        for ins in comps.get(comp, ()):
            opcode[ins.name] = ins.opcode
            operands[ins.name] = ins.refs
            for ref in ins.refs:
                users.setdefault(ref, []).append(ins.name)

        def a_pass(e):
            return e.pass_ != "other"

        for name in renamed * 2:     # a kernel may feed another
            made = {e.pass_ for e in reached(name, operands, opcode, a_pass)}
            read = {e.pass_ for e in reached(name, users, opcode, a_pass)}
            told = made | read
            out[name] = out[name]._replace(pass_=(
                "backward" if "backward" in made
                else "recompute" if "recompute" in made
                else "forward" if "forward" in read
                else told.pop() if len(told) == 1 else "other"))
        for ins in comps.get(comp, ()):
            if ins.opcode in _COPIES and out[ins.name].kind == "none":
                named = reached(ins.name, users, opcode,
                                lambda e: e.kind != "none")
                if named:
                    out[ins.name] = named[0]._replace(mixed=False,
                                                      inherited=True)

    out: dict = {}
    todo, done = [(entry, False)], set()
    while todo:
        comp, in_loop = todo.pop()
        if comp is None or comp in done:
            continue
        done.add(comp)
        renamed = []
        for ins in comps.get(comp, ()):
            scope, kind, pass_, part = parse_op_name(ins.op_name)
            mixed = False
            if ins.opcode == "fusion":
                fused = [c for a, c in ins.called if a == "calls"]
                if kind == "none" and fused:
                    scope, kind, pass_, part = parse_op_name(
                        root_op_name(fused[0]) or ins.op_name)
                found: list = []
                for c in fused:
                    layers_in(c, found)
                if kind == "none" and found:
                    # a fusion the compiler made (a packed ReLU mask) whose
                    # root carries no name: the layer nearest the root
                    scope, kind, pass_, part = found[-1]
                mixed = len({f[0] for f in found}) > 1
            elif ins.opcode == "custom-call" and kind == "none":
                said = f"{ins.name} {ins.op_name} {ins.says}"
                kind = next((k for pat, k in _RENAMED_KERNELS
                             if pat.search(said)), "none")
                if kind != "none":
                    renamed.append(ins.name)
            out[ins.name] = ScopeEntry(scope, kind, pass_, mixed, in_loop,
                                       part=part)
            for attr, c in ins.called:
                if attr in _RUNS.get(ins.opcode, ()):
                    todo.append((c, in_loop or ins.opcode == "while"))
        settle(comp, renamed)
    return module, out


def scope_map(compiled) -> dict:
    """``{instruction name: ScopeEntry}`` of a compiled executable
    (``jax.stages.Compiled``, or anything with ``as_text()``)."""
    return scope_map_of_text(compiled.as_text())[1]


_SCOPE_MAPS: dict = {}


def scope_maps() -> dict:
    """The maps this process has built, by HLO module name
    (``jit_chunk_dev``, ``jit_ev``): what a trace of this process is
    joined with."""
    return dict(_SCOPE_MAPS)


def clear_scope_maps() -> None:
    _SCOPE_MAPS.clear()


def register_scope_map(compiled, out_dir: Optional[str] = None,
                       logger=None, step: int = 0) -> Optional[str]:
    """Build the map of ``compiled``, keep it under its module's name,
    write ``scopemap_<module>.json`` under ``out_dir`` (beside a profiler
    capture) where one is given, and announce it with one ``scopemap``
    record. Returns the module's name."""
    module, entries = scope_map_of_text(compiled.as_text())
    if not entries:
        return None
    _SCOPE_MAPS[module] = entries
    path = None
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, f"scopemap_{module}.json")
        with open(path, "w") as f:
            json.dump({"module": module, "instructions": {
                name: {"scope": e.scope, "kind": e.kind, "pass": e.pass_,
                       "part": e.part, "mixed": e.mixed,
                       "in_loop": e.in_loop, "inherited": e.inherited}
                for name, e in entries.items()}}, f)
    if logger is not None:
        logger.log("scopemap", step=step, module=module,
                   instructions=len(entries),
                   mapped=sum(e.kind != "none" for e in entries.values()),
                   mixed=sum(e.mixed for e in entries.values()),
                   recompute=sum(e.pass_ == "recompute"
                                 for e in entries.values()), path=path)
    return module

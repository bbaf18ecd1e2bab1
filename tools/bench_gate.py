#!/usr/bin/env python
"""Perf-regression gate: compare a fresh ``bench.py`` report against a
trajectory of earlier reports and exit nonzero on regression.

Every metric ``bench.py`` reports is compared, per row, against the
median of the trajectory with a per-metric tolerance, and any breach is
a nonzero exit (wire it after the bench in CI / the release checklist).
The repo keeps no trajectory of its own (the driver's record is
``PERF_LEDGER.jsonl``); name the reports to compare against:

  python bench.py > bench.json                       # on the chip
  python tools/bench_gate.py bench.json --baselines 'earlier/bench_*.json'

Checks (a metric absent from either side is skipped, never failed —
older rounds predate ``compile_s``/``step_ms_*``):

- headline ``value`` and per-row ``images_per_sec_per_chip``: candidate
  must be ≥ (1 − ``--tol-throughput``) × trajectory median,
- per-row ``mfu``: ≥ (1 − ``--tol-mfu``) × median,
- per-row ``compile_s``: ≤ max(median, 1 s) × ``--tol-compile`` (the
  floor keeps warm-cache jitter from flagging 0.2 s vs 0.05 s),
- per-row ``spread_pct``: ≤ ``--max-spread`` (absolute — a noisy
  measurement invalidates every other comparison),
- per-row ``step_ms_p99``: ≤ (1 + ``--tol-tail``) × median (the tail
  regression the mean hides; see bench.py's sampling-pass caveat).

Medians, not bests: one lucky round must not ratchet the bar to a level
the hardware only sometimes reaches (run-to-run spread on the current
chip: not measured). ``--self-check`` runs a built-in decision table over
synthetic reports (tier-1 wired) so the gate's own logic is pinned.

Baseline files may be raw bench output or a driver wrapper around it
(``{"parsed": {...}}``); both shapes load.
"""

from __future__ import annotations

import argparse
import glob
import json
import sys
from typing import List, Optional


#: Benchmark rows a report may carry (bench.py main()).
ROW_KEYS = ("fp32", "bf16", "fp32_k320", "fp32_hostidx", "fp32_zero1",
            "int8_serve")

#: Default tolerances — one place, shared by the CLI and --self-check.
DEFAULTS = {
    "tol_throughput": 0.05,
    "tol_mfu": 0.07,
    "tol_compile": 2.0,
    "max_spread": 10.0,
    "tol_tail": 0.5,
    "min_int8_speedup": 1.5,
}

#: Per-row tolerance overrides, layered over DEFAULTS (and over any CLI
#: override). fp32_zero1 carries the ZeRO-1 reduce-scatter/all-gather
#: pair whose cost varies with interconnect weather more than the plain
#: all-reduce's — slightly wider floors keep the gate honest without
#: letting a real regression through. int8_serve times single-batch
#: serving dispatches (~ms each), jitterier than the amortized 100-step
#: train chunks. (Absent-metric skipping still applies: rounds before
#: a row existed simply don't gate it.)
ROW_TOLERANCES = {
    "fp32_zero1": {"tol_throughput": 0.08, "tol_mfu": 0.10},
    "int8_serve": {"tol_throughput": 0.10, "max_spread": 15.0},
}


def load_report(path: str) -> dict:
    """Load a bench report: raw ``bench.py`` stdout JSON, or a driver
    wrapper around it (its ``parsed`` field)."""
    with open(path) as f:
        doc = json.load(f)
    if "parsed" in doc and isinstance(doc["parsed"], dict):
        doc = doc["parsed"]
    if doc.get("metric") != "train_throughput":
        raise ValueError(f"{path}: not a bench report "
                         f"(metric={doc.get('metric')!r})")
    return doc


def _median(vals: List[float]) -> Optional[float]:
    vals = sorted(v for v in vals if isinstance(v, (int, float)))
    if not vals:
        return None
    mid = len(vals) // 2
    return vals[mid] if len(vals) % 2 else (vals[mid - 1] + vals[mid]) / 2


def _get(report: dict, row: Optional[str], key: str):
    src = report if row is None else report.get(row)
    if not isinstance(src, dict):
        return None
    v = src.get(key)
    return v if isinstance(v, (int, float)) else None


def gate(candidate: dict, baselines: List[dict], **tol) -> List[dict]:
    """Run every applicable check → list of
    ``{check, row, candidate, baseline, limit, ok}`` dicts (the JSON the
    CI consumer reads; ``main`` renders them as a table)."""
    t = dict(DEFAULTS)
    t.update({k: v for k, v in tol.items() if v is not None})
    checks = []

    def add(check, row, cand, base, limit, ok):
        checks.append({"check": check, "row": row or "headline",
                       "candidate": cand, "baseline": base,
                       "limit": round(limit, 4), "ok": bool(ok)})

    def floor_check(check, row, key, tol_frac):
        cand = _get(candidate, row, key)
        med = _median([_get(b, row, key) for b in baselines])
        if cand is None or med is None:
            return
        limit = med * (1.0 - tol_frac)
        add(check, row, cand, med, limit, cand >= limit)

    # Headline throughput, then per-row metrics (per-row tolerance
    # entries in ROW_TOLERANCES layer over the CLI/default ones).
    floor_check("throughput", None, "value", t["tol_throughput"])
    for row in ROW_KEYS:
        if not isinstance(candidate.get(row), dict):
            continue
        tr = {**t, **ROW_TOLERANCES.get(row, {})}
        floor_check("throughput", row, "images_per_sec_per_chip",
                    tr["tol_throughput"])
        floor_check("mfu", row, "mfu", tr["tol_mfu"])
        cand = _get(candidate, row, "compile_s")
        med = _median([_get(b, row, "compile_s") for b in baselines])
        if cand is not None and med is not None:
            limit = max(med, 1.0) * tr["tol_compile"]
            add("compile_s", row, cand, med, limit, cand <= limit)
        spread = _get(candidate, row, "spread_pct")
        if spread is not None:
            add("spread", row, spread, None, tr["max_spread"],
                spread <= tr["max_spread"])
        cand = _get(candidate, row, "step_ms_p99")
        med = _median([_get(b, row, "step_ms_p99") for b in baselines])
        if cand is not None and med is not None:
            limit = med * (1.0 + tr["tol_tail"])
            add("step_tail_p99", row, cand, med, limit, cand <= limit)
    # Quantized-serving speedup floor (docs/QUANT.md): int8 must beat
    # the bf16 serving path by min_int8_speedup — an absolute contract,
    # not a trajectory comparison, because the whole point of shipping
    # the path is the speedup. TPU rows only: XLA's CPU int8 lowering
    # has no MXU advantage, so CPU rows (where the gate MACHINERY is
    # verified in tier-1) are recorded but not floored.
    row = candidate.get("int8_serve")
    if isinstance(row, dict):
        tr = {**t, **ROW_TOLERANCES.get("int8_serve", {})}
        sp = row.get("speedup_vs_bf16")
        if isinstance(sp, (int, float)) and row.get("backend") == "tpu":
            add("int8_speedup", "int8_serve", sp, None,
                tr["min_int8_speedup"], sp >= tr["min_int8_speedup"])
    return checks


def render(checks: List[dict]) -> str:
    lines = [f"{'check':<14} {'row':<13} {'candidate':>12} "
             f"{'baseline':>12} {'limit':>12}  verdict"]
    for c in checks:
        base = "-" if c["baseline"] is None else f"{c['baseline']:.4g}"
        lines.append(
            f"{c['check']:<14} {c['row']:<13} {c['candidate']:>12.4g} "
            f"{base:>12} {c['limit']:>12.4g}  "
            f"{'ok' if c['ok'] else 'REGRESSION'}")
    bad = sum(1 for c in checks if not c["ok"])
    lines.append(f"{len(checks)} check(s), {bad} regression(s): "
                 f"{'FAIL' if bad else 'PASS'}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# --self-check: the decision table that pins the gate's own logic
# ---------------------------------------------------------------------------

def _synth(ips=1000.0, mfu=0.30, compile_s=20.0, spread=2.0,
           p99=1.2, int8=None) -> dict:
    doc = {"metric": "train_throughput", "value": ips,
           "unit": "images/sec/chip",
           "fp32": {"images_per_sec_per_chip": ips, "mfu": mfu,
                    "compile_s": compile_s, "spread_pct": spread,
                    "step_ms_p50": 1.0, "step_ms_p99": p99}}
    if int8 is not None:   # (speedup_vs_bf16, backend)
        doc["int8_serve"] = {"images_per_sec_per_chip": 5000.0,
                             "speedup_vs_bf16": int8[0],
                             "backend": int8[1], "spread_pct": 2.0}
    return doc


#: (case name, candidate overrides, expected gate verdict).
SELF_CHECK_TABLE = (
    ("identical", {}, True),
    ("within_noise", {"ips": 980.0}, True),
    ("improvement", {"ips": 1200.0, "compile_s": 1.0}, True),
    ("throughput_-10%", {"ips": 900.0}, False),
    ("mfu_-10%", {"mfu": 0.27}, False),
    ("compile_3x", {"compile_s": 60.0}, False),
    ("spread_blowup", {"spread": 15.0}, False),
    ("tail_p99_2x", {"p99": 2.4}, False),
    ("warm_cache_compile_0", {"compile_s": 0.1}, True),
    # int8_serve speedup floor: absolute, TPU rows only (the row's own
    # backend key decides — a CPU row never trips it).
    ("int8_speedup_ok", {"int8": (1.8, "tpu")}, True),
    ("int8_speedup_low", {"int8": (1.2, "tpu")}, False),
    ("int8_cpu_not_floored", {"int8": (0.8, "cpu")}, True),
)


def self_check() -> int:
    """Run the decision table; nonzero when the gate's verdicts drift
    from the documented expectations."""
    baselines = [_synth(990.0), _synth(1000.0), _synth(1010.0)]
    failed = 0
    for name, overrides, expect_pass in SELF_CHECK_TABLE:
        checks = gate(_synth(**overrides), baselines)
        ok = all(c["ok"] for c in checks)
        verdict = "ok" if ok == expect_pass else "WRONG VERDICT"
        if ok != expect_pass:
            failed += 1
        print(f"  {name:<22} expected "
              f"{'pass' if expect_pass else 'fail'}, gate said "
              f"{'pass' if ok else 'fail'}: {verdict}")
    print(f"self-check: {len(SELF_CHECK_TABLE)} case(s), "
          f"{failed} wrong verdict(s)")
    return 1 if failed else 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="gate a bench.py report against a trajectory of "
                    "earlier reports (exit 1 on regression)")
    p.add_argument("candidate", nargs="?",
                   help="fresh report (bench.py stdout JSON or a "
                        "driver wrapper around it)")
    p.add_argument("--baselines",
                   help="glob of the baseline reports to gate against")
    p.add_argument("--tol-throughput", type=float, default=None,
                   help=f"max fractional throughput drop vs median "
                        f"(default {DEFAULTS['tol_throughput']})")
    p.add_argument("--tol-mfu", type=float, default=None,
                   help=f"max fractional MFU drop "
                        f"(default {DEFAULTS['tol_mfu']})")
    p.add_argument("--tol-compile", type=float, default=None,
                   help=f"max compile_s vs max(median, 1 s) "
                        f"(default {DEFAULTS['tol_compile']}x)")
    p.add_argument("--max-spread", type=float, default=None,
                   help=f"max spread_pct, absolute "
                        f"(default {DEFAULTS['max_spread']})")
    p.add_argument("--tol-tail", type=float, default=None,
                   help=f"max fractional step_ms_p99 growth "
                        f"(default {DEFAULTS['tol_tail']})")
    p.add_argument("--min-int8-speedup", type=float, default=None,
                   help=f"int8_serve speedup_vs_bf16 floor, TPU rows "
                        f"only (default {DEFAULTS['min_int8_speedup']})")
    p.add_argument("--format", choices=("text", "json"), default="text")
    p.add_argument("--self-check", action="store_true",
                   help="run the built-in synthetic decision table "
                        "instead of gating a report")
    args = p.parse_args(argv)

    if args.self_check:
        return self_check()
    if not args.candidate or not args.baselines:
        p.error("candidate report and --baselines required "
                "(or --self-check)")
    baseline_paths = sorted(glob.glob(args.baselines))
    baselines = []
    for path in baseline_paths:
        try:
            baselines.append(load_report(path))
        except (OSError, ValueError) as e:
            print(f"[gate] skipping baseline {path}: {e}",
                  file=sys.stderr)
    if not baselines:
        print(f"[gate] no usable baselines match {args.baselines!r}",
              file=sys.stderr)
        return 2
    candidate = load_report(args.candidate)
    checks = gate(candidate, baselines,
                  tol_throughput=args.tol_throughput,
                  tol_mfu=args.tol_mfu, tol_compile=args.tol_compile,
                  max_spread=args.max_spread, tol_tail=args.tol_tail,
                  min_int8_speedup=args.min_int8_speedup)
    bad = any(not c["ok"] for c in checks)
    if args.format == "json":
        print(json.dumps({"candidate": args.candidate,
                          "baselines": baseline_paths,
                          "checks": checks,
                          "pass": not bad}))
    else:
        print(f"candidate {args.candidate} vs {len(baselines)} "
              f"baseline(s)")
        print(render(checks))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

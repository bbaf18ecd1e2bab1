"""The benchmark's own check of its last line, called before printing and
by the tests on recorded lines."""

from __future__ import annotations

import json
import math
import re
from typing import Iterable, List

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SHARE = re.compile(r"(^|[._])(mfu|roofline|pct)($|[._])|_roofline$")


def _number(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) \
        and math.isfinite(x)


def problems(line: str, metrics: List[dict], chips: int, traced: bool,
             nothing_to_read: Iterable[str] = ()) -> List[str]:
    """What is wrong with a result line, given the metric entries of
    ``BENCHMARK.json`` that the cell has to report in this kind of run;
    empty when the line is sound. ``nothing_to_read`` names the per-layer
    metrics whose readers found nothing: they have to be absent, and at
    least one other has to be there."""
    if "\n" in line.strip():
        return ["the result is more than one line"]
    try:
        obj = json.loads(line)
    except ValueError as e:
        return [f"not JSON: {e}"]
    if not isinstance(obj, dict):
        return ["not a JSON object"]
    out = []
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        if key not in obj:
            out.append(f"key {key!r} is missing")
    if out:
        return out
    if not isinstance(obj["correct"], bool):
        out.append("correct is not true or false")
    for key in ("attempted", "failed"):
        if not isinstance(obj[key], int) or isinstance(obj[key], bool) \
                or obj[key] < 0:
            out.append(f"{key} is not a whole number")
    if isinstance(obj["attempted"], int) and obj["attempted"] < 1:
        out.append("nothing was attempted")

    got = obj["metrics"]
    if not isinstance(got, dict):
        return out + ["metrics is not an object"]
    silent = set(nothing_to_read)
    want = {m["name"]: m for m in metrics if m["name"] not in silent}
    if not want:
        out.append("no metric found anything to read")
    for name in want:
        if name not in got:
            out.append(f"metric {name!r} is missing")
    for name, m in got.items():
        if not NAME.match(name):
            out.append(f"metric name {name!r} has other characters")
        if name not in want:
            out.append(f"metric {name!r} is not one of this run's")
            continue
        if not isinstance(m, dict) or set(m) != {"value", "unit"}:
            out.append(f"metric {name!r} is not a value and a unit")
            continue
        if not _number(m["value"]):
            out.append(f"metric {name!r} has no finite value")
        elif m["value"] < 0:
            out.append(f"metric {name!r} is negative")
        elif m["unit"] == "%" and SHARE.search(name) and m["value"] > 100:
            out.append(f"share {name!r} reads {m['value']} > 100")
        if not isinstance(m["unit"], str) or not UNIT.match(m["unit"]):
            out.append(f"unit of {name!r} has other characters")
        elif m["unit"] != want[name]["unit"]:
            out.append(f"unit of {name!r} is {m['unit']!r}, not "
                       f"{want[name]['unit']!r}")

    dev = obj["device"]
    if not isinstance(dev, dict):
        return out + ["device is not an object"]
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        if key not in dev:
            out.append(f"device.{key} is missing")
    if dev.get("count") != chips:
        out.append(f"device.count is {dev.get('count')}, the cell has "
                   f"{chips}")
    if not _number(dev.get("memory_peak_bytes")) \
            or dev["memory_peak_bytes"] <= 0:
        out.append("device.memory_peak_bytes is not above 0")
    if traced:
        busy, window = dev.get("busy_s"), dev.get("window_s")
        if not _number(busy) or not _number(window):
            out.append("device.busy_s and device.window_s have to be numbers")
        elif not 0 < busy <= window:
            out.append(f"device.busy_s {busy} is not above 0 and at most "
                       f"window_s {window}")
        bd = obj.get("breakdown")
        if bd is not None:
            for key in ("device_ops", "idle_gaps"):
                rows = bd.get(key) if isinstance(bd, dict) else None
                if not isinstance(rows, list) or len(rows) > 10 or any(
                        not (isinstance(r, list) and len(r) == 2
                             and isinstance(r[0], str) and _number(r[1]))
                        for r in rows):
                    out.append(f"breakdown.{key} is not a list of at most "
                               f"10 [name, seconds]")
    if "compared" in obj and list(obj)[-1] != "compared":
        out.append("compared is not the last key")
    return out

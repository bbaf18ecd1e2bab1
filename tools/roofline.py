#!/usr/bin/env python
"""Per-op roofline table from an xplane trace.

For every XLA op (fusion/conv/custom-call) in the profiled program:
device time share, achieved TFLOP/s, HBM bytes, arithmetic intensity
(flops/byte), and the roofline verdict at the chip's ridge point —
``compute-bound`` when intensity clears peak_flops/peak_bw, else
``bandwidth-bound`` with the % of peak HBM bandwidth it actually
achieved. This is the evidence table the round-3 ResNet-50 verdict asked
for: whether the remaining conv+BN fusions sit against the bandwidth
roof rather than the MXU roof.

Usage:
    python tools/roofline.py /path/to/*.xplane.pb
        --device-kind "TPU v5 lite" [--top 25]

The peaks come from the one table, ``bench.PEAKS``, keyed by the
``device_kind`` of the chip that took the trace; an unknown kind is an
error.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load_ops(pb_path):
    from xprof.convert import raw_to_tool_data as rtd

    data, _ = rtd.xspace_to_tool_data([pb_path], "op_profile", {})
    tree = json.loads(data.decode() if isinstance(data, bytes) else data)
    ops = []

    def walk(node, depth=0):
        m = node.get("metrics", {})
        xla = node.get("xla") or {}
        # leaves: nodes with xla info and occurrences
        if xla and m.get("occurrences"):
            ops.append({
                "name": node.get("name", "?"),
                "category": xla.get("category", "?"),
                "time_ps": m.get("rawTime", 0),
                "flops": m.get("rawFlops", 0),
                # [HBM, on-chip read, on-chip write] in the converter's
                # rawBytesAccessedArray
                "hbm_bytes": (m.get("rawBytesAccessedArray") or [0])[0],
                "occ": m.get("occurrences", 0),
            })
        for ch in node.get("children", []):
            walk(ch, depth + 1)

    walk(tree.get("byProgram", {}))
    # The tree nests op groups; leaves repeat at several levels. Keep the
    # deepest unique (name, time) rows.
    seen = {}
    for o in ops:
        key = (o["name"], o["time_ps"])
        seen[key] = o
    return list(seen.values())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("xplane", help="xplane.pb path (or glob)")
    ap.add_argument("--device-kind", required=True,
                    help="jax.devices()[0].device_kind of the chip that "
                         "took the trace (a key of bench.PEAKS)")
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args()
    from bench import device_peaks
    peaks = device_peaks(args.device_kind)
    peak_tflops, peak_gbps = peaks["tflops"], peaks["hbm_gbps"]

    paths = sorted(glob.glob(args.xplane))
    if not paths:
        sys.exit(f"no xplane matches {args.xplane}")
    ops = load_ops(paths[0])
    total_ps = sum(o["time_ps"] for o in ops)
    ridge = peak_tflops * 1e12 / (peak_gbps * 1e9)  # flops/byte

    ops.sort(key=lambda o: -o["time_ps"])
    print(f"total device op time: {total_ps / 1e9:.2f} ms; ridge "
          f"intensity {ridge:.0f} flops/byte "
          f"({peak_tflops:.0f} TF/s / {peak_gbps:.0f} GB/s, "
          f"{args.device_kind})\n")
    print("| % time | op | TF/s | GB/s | flops/byte | bound | % of roof |")
    print("|---|---|---|---|---|---|---|")
    for o in ops[:args.top]:
        t = o["time_ps"] / 1e12
        if t == 0:
            continue
        tf = o["flops"] / t / 1e12
        gb = o["hbm_bytes"] / t / 1e9
        inten = o["flops"] / o["hbm_bytes"] if o["hbm_bytes"] else float(
            "inf")
        if inten >= ridge:
            bound, roof = "compute", tf / peak_tflops
        else:
            bound, roof = "bandwidth", gb / peak_gbps
        name = o["name"][:48]
        print(f"| {o['time_ps'] / total_ps * 100:5.1f} | {name} | "
              f"{tf:6.1f} | {gb:6.0f} | {inten:8.1f} | {bound} | "
              f"{roof * 100:5.1f}% |")


if __name__ == "__main__":
    main()

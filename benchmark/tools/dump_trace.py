"""Look at one profiler trace by hand: which planes are devices, which
lines they carry, how events are named and what stats they hold.

    python3 benchmark/tools/dump_trace.py <dir or .xplane.pb> [events per line]
"""

from __future__ import annotations

import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib.xplane import find_xplane  # noqa: E402


def main(argv) -> int:
    from jax.profiler import ProfileData
    per_line = int(argv[2]) if len(argv) > 2 else 4
    path = find_xplane(argv[1])
    print(f"trace {path} ({os.path.getsize(path)} bytes)")
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            names = collections.Counter(e.name for e in events)
            t0 = min(e.start_ns for e in events)
            t1 = max(e.start_ns + e.duration_ns for e in events)
            print(f"  line {line.name!r}: {len(events)} events, "
                  f"{len(names)} names, span {(t1 - t0) / 1e6:.3f} ms, "
                  f"first start_ns {t0}")
            for name, count in names.most_common(per_line):
                e = next(x for x in events if x.name == name)
                stats = {k: (str(v)[:160]) for k, v in e.stats}
                print(f"    {count:6d} x {name[:100]!r} dur_ns="
                      f"{e.duration_ns} stats={stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

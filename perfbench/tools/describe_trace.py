"""Look at a raw profiler trace by hand: planes, lines, how many events each
holds, the names that take most time, and the stats a few events carry.

    python3 perfbench/tools/describe_trace.py <dir with *.xplane.pb> [out.txt]
"""

from __future__ import annotations

import glob
import os
import sys


def describe(logdir: str, out) -> None:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True))
    print(f"{len(files)} xplane file(s) under {logdir}", file=out)
    for path in files:
        print(f"== {path} ({os.path.getsize(path) / 2**20:.1f} MiB)", file=out)
        data = ProfileData.from_file(path)
        for plane in data.planes:
            lines = list(plane.lines)
            print(f"plane {plane.name!r}: {len(lines)} lines", file=out)
            for ln in lines:
                events = list(ln.events)
                totals = {}
                for ev in events:
                    s, n = totals.get(ev.name, (0.0, 0))
                    totals[ev.name] = (s + ev.duration_ns, n + 1)
                print(f"  line {ln.name!r}: {len(events)} events, {len(totals)} names", file=out)
                top = sorted(totals.items(), key=lambda kv: -kv[1][0])[:25]
                for name, (ns, n) in top:
                    print(f"    {ns / 1e6:10.3f} ms  x{n:<6d} {name[:160]}", file=out)
                if plane.name.startswith("/device:") and events:
                    seen = set()
                    for ev in events:
                        if ev.name in seen or len(seen) >= 6:
                            continue
                        seen.add(ev.name)
                        stats = [(k, str(v)[:300]) for k, v in ev.stats]
                        print(f"    stats of {ev.name[:80]!r}: {stats}", file=out)


if __name__ == "__main__":
    target = open(sys.argv[2], "w", encoding="utf-8") if len(sys.argv) > 2 else sys.stdout
    describe(sys.argv[1], target)

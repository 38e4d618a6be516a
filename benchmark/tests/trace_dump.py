#!/usr/bin/env python3
"""Look at one trace by hand: planes, lines, event counts and the names that
take most time on each line. ``python3 benchmark/tests/trace_dump.py <dir|file>``"""

import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(path: str, top: int = 25) -> None:
    import jax

    from harness import trace_reduce

    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    print(path, os.path.getsize(path), "bytes")
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            total, count = collections.Counter(), collections.Counter()
            lo, hi = float("inf"), 0.0
            for e in line.events:
                total[e.name] += e.duration_ns
                count[e.name] += 1
                lo, hi = min(lo, e.start_ns), max(hi, e.start_ns + e.duration_ns)
            n = sum(count.values())
            if not n:
                continue
            print(f"  LINE {line.name!r}: {n} events, {lo * 1e-9:.4f}s..{hi * 1e-9:.4f}s")
            shown = top if plane.name.startswith("/device") else 8
            for name, ns in total.most_common(shown):
                print(f"    {ns * 1e-6:12.3f} ms  x{count[name]:<6d} {name[:110]}")


if __name__ == "__main__":
    main(sys.argv[1])

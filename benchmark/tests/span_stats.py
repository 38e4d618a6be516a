#!/usr/bin/env python3
"""Look at the host spans of one trace by hand: per ``atpu:`` and ``bench:``
span name its count, median, p95 and summed duration in ms. Comparing
``bench:engine_step`` between the traced runs of two trees gives what the
program's own spans cost while a session is on.
``python3 benchmark/tests/span_stats.py <dir|file> [...]``"""

import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(path: str) -> None:
    from harness import program_trace, stats, trace_reduce

    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    took = collections.defaultdict(list)
    for name, start, end, _ in program_trace.load(path)["spans"]:
        took[name].append((end - start) * 1e3)
    print(path)
    for name, ms in sorted(took.items()):
        print(f"  {name:28s} x{len(ms):<5d} median {stats.median(ms):9.4f} ms  "
              f"p95 {stats.percentile(ms, 95):9.4f} ms  sum {sum(ms):10.3f} ms")


if __name__ == "__main__":
    for arg in sys.argv[1:]:
        main(arg)

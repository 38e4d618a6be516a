#!/usr/bin/env python3
"""One CPU rehearsal of the mixed window / full attention sparse-expert cell at a
tiny size (``tiny_smallthinker``: the real ``serve_hybrid`` runner, reference,
weights and readers) through ``run.execute``, in a process of its own, in the
manner of ``rehearse_eva.py``. Never a measurement: the device is the CPU.

    python3 benchmark/tests/rehearse_smallthinker.py [--trace 1] [--fault no_band]

faults: ``smallthinker_faults.py``'s seven, and ``faults.py``'s ``wrong_token``
and ``one_token``; ``correct`` must come out false under each.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

import smallthinker_faults  # noqa: E402
import tiny_smallthinker  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=2**31 + 45)
    args = ap.parse_args()

    import run
    from harness import cell as cells

    if args.fault:
        smallthinker_faults.plant(args.fault)
    cell = tiny_smallthinker.serve_cell()
    with tempfile.TemporaryDirectory() as tmp:
        loaded = cells.load_cell(cell["name"], tiny_smallthinker.make_root(tmp, cell))
        result = run.execute(loaded, args.seed, args.seconds, bool(args.trace), T0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""One CPU rehearsal of the hybrid expert cell at a tiny size (``tiny_hybrid``:
the real runner, reference, weights and readers), in a process of its own, in
the manner of ``rehearse.py``. Never a measurement: the device is the CPU.

    python3 benchmark/tests/rehearse_hybrid.py [--trace 1] [--fault offset_off_by_one]

The fault is planted UNDER the timed path, as ``faults.py`` plants its own:
the program's expert layer is told its share starts one expert later than the
configuration (and the reference) say; ``correct`` must come out false.
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

import tiny_hybrid  # noqa: E402


def plant_offset_off_by_one() -> None:
    from accelerate_tpu.ops import moe

    real = moe.moe_ragged
    moe.moe_ragged = lambda *a, expert_offset=0, **kw: real(
        *a, expert_offset=expert_offset + 1, **kw)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--fault", choices=("offset_off_by_one",), default=None)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=2**31 + 11)
    args = ap.parse_args()

    import run
    from harness import cell as cells

    if args.fault:
        plant_offset_off_by_one()
    cell = tiny_hybrid.train_cell()
    with tempfile.TemporaryDirectory() as tmp:
        loaded = cells.load_cell(cell["name"], tiny_hybrid.make_root(tmp, cell))
        result = run.execute(loaded, args.seed, args.seconds, bool(args.trace), T0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

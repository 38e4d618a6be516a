#!/usr/bin/env python3
"""One CPU rehearsal of a runner at a tiny size, in a process of its own
(``JAX_PLATFORMS=cpu``, and ``JAX_NUM_CPU_DEVICES=4`` for the four-device
case). Skips the harness's look for a chip and drives the rest of a run; the
last line is the result object. Never a measurement: the device is the CPU.

    python3 benchmark/tests/rehearse.py <case> [--trace 1] [--fault <name>]

cases: train, train4 (four virtual devices), serve, sat (pre-seated).
faults (``faults.py``: the timed path broken underneath by patching the
program in place; ``correct`` must come out false): half_batch, frozen_state,
compile_in_window, lr_off_1pct (train); wrong_token, one_token, int8_kv (serve).
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

import tiny  # noqa: E402

EXTRA_METRIC = {  # a span-reading per-layer metric added as data alone
    "name": "reference_s.tiny", "unit": "s", "better": "lower",
    "source": "host_clock", "layer": "benchmark", "moves": "setup_s",
    "reader": "scalar", "args": {"key": "reference_s"},
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("case", choices=("train", "train4", "serve", "sat"))
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=2**31 + 11)
    args = ap.parse_args()
    cell = {
        "train": lambda: tiny.train_cell(),
        "train4": lambda: tiny.train_cell("tiny-train4", chips=4),
        "serve": lambda: tiny.serve_cell(),
        "sat": lambda: tiny.serve_cell("tiny-sat", preseat=4),
    }[args.case]()
    extra = dict(EXTRA_METRIC, workloads=[cell["name"]])

    import faults
    import run
    from harness import cell as cells

    if args.fault:
        faults.plant(args.fault)

    with tempfile.TemporaryDirectory() as tmp:
        root = tiny.make_root(tmp, [cell], [extra])
        loaded = cells.load_cell(cell["name"], root)
        result = run.execute(loaded, args.seed, args.seconds, bool(args.trace), T0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""The benchmark's one command: one cell, once, in one process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in BENCHMARK.json, builds the system under test from the
seed, warms up every shape the cell's traffic uses (set-up), measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints ONE JSON object as the last line of stdout. Needs the
chips the cell asks for: without them it exits 2 and prints no result. There
is no CPU switch; ``benchmark/tests`` drive :func:`execute` directly.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as Python gets

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import cell as cells  # noqa: E402


def execute(cell: dict, seed: int, seconds: float, trace: bool,
            t_start: float, say=print) -> dict:
    """Drive one run of ``cell`` on whatever devices JAX has and return the
    result object."""
    import jax

    # a cell picks its runner by its kind: harness/<kind>_runner.py
    runner = importlib.import_module(f"harness.{cell['spec']['kind']}_runner")
    record, tr = runner.run(cell, seed=seed, seconds=seconds, trace=trace,
                            t_start=t_start, say=say)
    group = "per_layer" if trace else "end_to_end"
    device = cells.device_info(jax)
    device["memory_peak_bytes"] = record["memory_peak_bytes"]
    record["device_kind"] = device["kind"]
    result = {
        "correct": bool(record["correct"]),
        "attempted": int(record["attempted"]),
        "failed": int(record["failed"]),
        "metrics": cells.evaluate(cell, group, record, tr),
        "device": device,
    }
    if trace and tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = tr["breakdown"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    try:
        import accelerate_tpu  # noqa: F401 - the system under test
    except ImportError as exc:
        print(f"benchmark: the program is not in this checkout ({exc}) - "
              "nothing ran", file=sys.stderr)
        return 2

    import jax

    device = cells.device_info(jax)
    if device["platform"] != "tpu" or device["count"] < cell["chips"]:
        print(f"benchmark: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX found {device} - nothing ran", file=sys.stderr)
        return 2
    tag = f"[{device['platform']} {device['kind']} x{device['count']}]"

    def say(msg: str) -> None:
        print(f"{tag} {msg}", flush=True)

    result = execute(cell, args.seed, args.seconds, bool(args.trace),
                     T_START, say)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

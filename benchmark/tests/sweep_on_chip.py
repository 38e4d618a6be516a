#!/usr/bin/env python3
"""Find a serve mix's knee once, by a sweep on the chip: run the cell at each
offered rate for a short window in ONE process and print what came out. The
knee is the highest rate at which the backlog at the window's close does not
grow with the rate and the drain is short. The benchmark itself never
searches: the rate it offers is the number written into the cell's file.

    python3 benchmark/tests/sweep_on_chip.py --workload <cell> --rates 1,1.5,2 --seconds 30
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    import jax
    import numpy as np

    from harness import cell as cells
    from harness import serve_runner

    base = cells.load_cell(args.workload)
    if cells.device_info(jax)["platform"] != "tpu":
        print("needs the chip", file=sys.stderr)
        return 2
    for rate in (float(r) for r in args.rates.split(",")):
        cell = copy.deepcopy(base)
        cell["spec"]["traffic"]["rate_per_s"] = rate
        rec, _ = serve_runner.run(cell, seed=args.seed, seconds=args.seconds,
                                  trace=False, t_start=time.perf_counter(),
                                  say=lambda m: None)
        ttft, itl = rec["ttft_ms"], rec["itl_ms"]
        print("SWEEP " + json.dumps({
            "rate_per_s": rate, "attempted": rec["attempted"],
            "backlog_at_close": rec["backlog_at_close"],
            "drained_s": round(rec["drained_s"], 2),
            "tokens_per_s": round(rec["tokens_in_window"] / args.seconds, 1),
            "ttft_p50_ms": round(float(np.median(ttft)), 1),
            "ttft_p90_ms": round(float(np.percentile(ttft, 90)), 1),
            "itl_p50_ms": round(float(np.median(itl)), 2),
            "itl_p95_ms": round(float(np.percentile(itl, 95)), 1),
            "decode_step_ms_p50": round(float(np.median(rec["decode_step_ms"])), 2),
            "prefill_call_ms_p50": round(float(np.median(rec["prefill_call_ms"])), 1),
            "mean_seated": round(rec["mean_seated"], 2),
            "correct": rec["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

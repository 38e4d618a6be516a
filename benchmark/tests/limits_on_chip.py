#!/usr/bin/env python3
"""Read, on the chip and at a cell's own size, the numbers every limit is set
from: the largest that sound runs of the program give over many seeds, the
smallest that the CONTROL gives, and what each planted fault reads.

    python3 benchmark/tests/limits_on_chip.py --workload <cell> \\
        --seeds 11,12,... --control 3 --faults one_token,int8_kv --fault-seeds 3 \\
        --seconds <short window> --out <summary.json> --dump <raw.npz>

Controls: the reference computed in int8 (the step below the configuration's
precision) put in the program's place, on the first ``--control`` seeds; and
the faults of ``faults.py`` planted under the timed path, each on the first
``--fault-seeds`` seeds — ``int8_kv`` is the program's own lower-precision
path switched on, ``lr_off_1pct`` / ``one_token`` the mildest wrong update and
wrong answer a limit is held against. One process for all seeds (set-up is
long). Prints one line per run and the summary; ``--dump`` keeps every
compared token's gap and margin (serve), so that a statistic can be chosen
from the readings, not before them. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import copy
import importlib
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)


class _Rows:
    def __init__(self):
        self.rows = []

    def limit(self, name, value, limit):
        self.rows.append((name, value))

    def say(self, msg):
        pass


def _values(rec) -> dict:
    vals = {name: float(value) for name, value, _limit, _ok in rec["checks"]
            if isinstance(value, float)}
    vals.update({k: float(v) for k, v in rec.get("gap_stats", {}).items()})
    return vals


def _line(tag, seed, vals, extra=""):
    print(f"{tag} seed={seed} {extra}"
          + " ".join(f"{k}={v:.6g}" for k, v in vals.items()), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--dump", default=None)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal of this script on tests/tiny.py's cell "
                         "of the workload's name (train, serve or sat)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import faults
    from harness import cell as cells
    from harness import common

    if args.tiny:
        import tempfile

        import tiny

        made = {"train": tiny.train_cell, "serve": tiny.serve_cell,
                "sat": lambda: tiny.serve_cell("tiny-sat", preseat=4)}[args.workload]()
        cell = cells.load_cell(made["name"], tiny.make_root(
            tempfile.mkdtemp(dir=os.environ.get("TMPDIR")), [made]))
    else:
        cell = cells.load_cell(args.workload)
        dev = cells.device_info(jax)
        if dev["platform"] != "tpu":
            print(f"needs the chip, found {dev}", file=sys.stderr)
            return 2
    kind = cell["spec"]["kind"]
    runner = importlib.import_module(f"harness.{kind}_runner")
    reference, _ = common.modules_of(cell["config"])
    readings: dict = {"sound": {}, "control_int8_reference": {}}
    raw: dict = {}

    def keep(tag, seed, vals, rec=None):
        for k, v in vals.items():
            readings.setdefault(tag, {}).setdefault(k, []).append(v)
        if rec is not None and "gaps" in rec:
            for part in ("gap", "margin"):
                arrs = rec["gaps"][part]
                raw[f"{tag}.{seed}.{part}"] = np.concatenate(arrs)
            raw[f"{tag}.{seed}.request"] = np.concatenate(
                [np.full(len(g), i) for i, g in enumerate(rec["gaps"]["gap"])])

    def drive(this_cell, seed):
        return runner.run(this_cell, seed=seed, seconds=args.seconds, trace=False,
                          t_start=time.perf_counter(), say=print)[0]

    fault_names = [f for f in args.faults.split(",") if f]
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        rec = drive(cell, seed)
        _line("SOUND", seed, _values(rec), f"correct={rec['correct']} ")
        keep("sound", seed, _values(rec), rec)
        if i < args.control:
            t = time.perf_counter()
            if kind == "train":
                spec = cell["spec"]
                rows = importlib.import_module("harness.traffic").token_rows(
                    spec["traffic"], seed, cell["config"]["vocab_size"])
                n = spec["rows_per_chip"] * cell["chips"]
                low = reference.train_reference(
                    cell["config"], spec["optimizer"], seed,
                    [rows[k * n:(k + 1) * n] for k in range(spec["checked_steps"])],
                    quant=True)
                chk = _Rows()
                runner.compare(low, rec["reference"], spec["limits"], chk)
                cvals = dict(chk.rows)
            else:
                seqs, plens = rec["reference_sample"]
                out = reference.served_token_gaps(
                    cell["config"], seed, seqs, plens,
                    jnp.dtype(cell["spec"]["weight_dtype"]), quant=True,
                    rows=int(cell["spec"]["reference_rows_per_block"]),
                    width=cell["spec"]["engine"]["max_seq_len"])
                cvals = {k: float(v) for k, v in
                         runner.gap_stats(out["control_gap"]).items()}
                raw[f"control_int8_reference.{seed}.gap"] = np.concatenate(
                    out["control_gap"])
                raw[f"control_int8_reference.{seed}.margin"] = np.concatenate(
                    out["margin"])
            _line("CONTROL int8_reference", seed, cvals,
                  f"({time.perf_counter() - t:.1f}s) ")
            keep("control_int8_reference", seed, cvals)
        if i < args.fault_seeds:
            for name in fault_names:
                broken = copy.deepcopy(cell)
                if name == "one_token":  # its request has to be in the sample
                    broken["spec"]["reference_sample"] = 10 ** 6
                undo = faults.plant(name)
                try:
                    frec = drive(broken, seed)
                    _line(f"FAULT {name}", seed, _values(frec),
                          f"correct={frec['correct']} ")
                    keep(f"fault_{name}", seed, _values(frec), frec)
                except Exception:  # a control that crashes has failed
                    print(f"FAULT {name} seed={seed} CRASHED\n"
                          + traceback.format_exc()[-3000:], flush=True)
                    readings.setdefault(f"fault_{name}", {}).setdefault(
                        "crashed", []).append(seed)
                finally:
                    undo()
    summary = {tag: {k: {"min": min(v), "max": max(v), "all": v}
                     for k, v in vals.items()} for tag, vals in readings.items()}
    print("SUMMARY " + json.dumps(
        {tag: {k: [v["min"], v["max"]] for k, v in vals.items()}
         for tag, vals in summary.items()}), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    if args.dump and raw:
        np.savez_compressed(args.dump, **raw)
    return 0


if __name__ == "__main__":
    sys.exit(main())

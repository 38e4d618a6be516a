"""A share of the roofline from the device trace: the least time the chip
could take for the NEEDED work of ``work`` (``package.module:function`` under
the benchmark, e.g. ``harness.flops_bytes:decode_step_work``), times
the executions of the program matching ``program`` that lie whole inside the
trace, over the device time of the operations matching ``ops`` inside those
executions (the whole program's device time when ``ops`` is absent)."""

import re

from harness import cell as cells
from harness import flops_bytes, peaks


def read(record, trace, cell, program, work, ops=None):
    if trace is None:
        return None
    need = cells.named(work)(cell["config"], record)
    least_s, bound = flops_bytes.roofline_seconds(
        need, peaks.peaks_for(record["device_kind"]))
    prog_rx = re.compile(program)
    ops_rx = re.compile(ops) if ops else None
    shares = []
    for dev in trace["trace"]["devices"].values():
        runs = [m for m in dev["modules"] if prog_rx.search(m[0])]
        if not runs:
            continue
        if ops_rx is None:
            took = sum(e - s for _, s, e in runs)
        else:
            hits = sorted((s, e) for n, s, e in dev["ops"] if ops_rx.search(n))
            took = 0.0
            for _, lo, hi in runs:
                took += sum(e - s for s, e in hits if s >= lo and e <= hi)
        if took > 0:
            shares.append(100.0 * least_s * len(runs) / took)
    if not shares:
        return None
    print(f"roofline {work}: bound by {bound}, least {least_s * 1e3:.3f} ms per "
          f"execution, shares per chip {[round(s, 2) for s in shares]}", flush=True)
    return sum(shares) / len(shares)

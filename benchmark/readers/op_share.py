"""Self time of the device operations inside executions of ``program`` whose
LABEL (``trace_reduce.op_label``: ``<name> <opcode> [<custom-call target>]``)
matches ``ops`` or whose scope path — as ``scope_share`` cleans it — matches
``scope``, as % of the time an operation ran inside those executions. For
work that XLA's own rewrites leave without the program's scope path: its
grouped-matmul kernels (``ragged-dot-none.14 custom-call tpu_custom_call``)
carry no path at all, while the elementwise work between them keeps
``moe/experts``. Nothing to read where no operation matches."""

import bisect
import re

from harness import program_trace


def read(record, trace, cell, program, ops, scope=None, model="CausalLM"):
    path = program_trace.path_of(cell) if trace is not None else None
    if path is None:
        return None
    prog_rx, ops_rx = re.compile(program), re.compile(ops)
    scope_rx = re.compile(scope) if scope else None
    hit = total = 0.0
    for dev in program_trace.load(path)["devices"].values():
        runs = sorted((s, e) for name, s, e in dev["modules"] if prog_rx.search(name))
        starts = [s for s, _ in runs]
        inside = []
        for op in dev["ops"]:
            k = bisect.bisect_right(starts, op[1]) - 1
            if k >= 0 and op[2] <= runs[k][1]:
                inside.append(op)
        for label, raw, self_s in program_trace.self_seconds(inside):
            total += self_s
            if ops_rx.search(label) or (scope_rx and scope_rx.search(
                    program_trace.scope_of(raw, model))):
                hit += self_s
    if hit <= 0 or total <= 0:
        return None
    return 100.0 * hit / total

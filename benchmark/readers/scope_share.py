"""Self time of the device operations inside executions of ``program`` whose
scope path matches ``scope``, as % of the time an operation ran inside those
executions. ``scope`` is a regular expression searched in the operation's
path as ``program_trace.scope_of`` leaves it — what the program named, down
to the primitive: ``layers/mlp/up_proj/dot_general``, ``optimizer/add`` —
with ``model`` the class name of the model, which is no name of a layer.
Once per trace and program it prints the UNSCOPED share — operations left
with nothing but their primitive, or for which XLA recorded no path — and the
three largest such paths, so that a PR that adds unnamed work is seen.
Nothing to read where no operation matches."""

import bisect
import functools
import re

from harness import program_trace


@functools.lru_cache(maxsize=8)
def by_scope(path: str, program: str, model: str):
    """``({cleaned scope path: self seconds}, total seconds)`` over every
    chip's executions of ``program``; printed once."""
    rx = re.compile(program)
    seconds, unscoped, total = {}, {}, 0.0
    for dev in program_trace.load(path)["devices"].values():
        runs = sorted((s, e) for name, s, e in dev["modules"] if rx.search(name))
        starts = [s for s, _ in runs]
        inside = []
        for op in dev["ops"]:
            k = bisect.bisect_right(starts, op[1]) - 1
            if k >= 0 and op[2] <= runs[k][1]:
                inside.append(op)
        for label, raw, self_s in program_trace.self_seconds(inside):
            cleaned = program_trace.scope_of(raw, model)
            seconds[cleaned] = seconds.get(cleaned, 0.0) + self_s
            total += self_s
            if program_trace.is_unscoped(cleaned):
                key = raw or f"(no path) {label}"
                unscoped[key] = unscoped.get(key, 0.0) + self_s
    if total > 0:
        top = sorted(unscoped.items(), key=lambda kv: -kv[1])[:3]
        print(f"scope_share {program}: unscoped "
              f"{100.0 * sum(unscoped.values()) / total:.3f} % of {total:.4f}s "
              "device time; largest unscoped: "
              + ", ".join(f"{k} {100.0 * v / total:.3f} %" for k, v in top),
              flush=True)
    return seconds, total


def read(record, trace, cell, program, scope, model="CausalLM"):
    path = program_trace.path_of(cell) if trace is not None else None
    if path is None:
        return None
    seconds, total = by_scope(path, program, model)
    rx = re.compile(scope)
    hit = [secs for cleaned, secs in seconds.items() if rx.search(cleaned)]
    if not hit or total <= 0:
        return None
    return 100.0 * sum(hit) / total

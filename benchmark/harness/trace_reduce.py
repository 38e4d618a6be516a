"""From a profiler trace to numbers: device busy and idle time, kernel and
program time, collectives not hidden behind compute, and what the host was
doing in the idle gaps. Reads ``.xplane.pb`` with ``jax.profiler.ProfileData``
alone; the interval arithmetic is ``stats.py``'s. Checked against the
recorded trace in ``benchmark/tests``.

A device plane is named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one
event per executed HLO operation and ``XLA Modules`` one per executed program.
The benchmark's own host spans are ``jax.profiler.TraceAnnotation``s whose
names start with ``bench:``; they sit on the host plane on the same clock.
"""

from __future__ import annotations

import glob
import os
import re

from . import stats

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
CONTAINERS = ("while", "conditional", "call")
HOST_PREFIX = "bench:"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"all-reduce-scatter|send|recv)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_label(text: str) -> str:
    """An operation's event name is its whole HLO line. Reduce it to
    ``<name> <opcode> [<custom-call target>]``, e.g. ``attn.40 custom-call
    tpu_custom_call`` or ``fusion.430 fusion``: what the readers match."""
    short, sep, rest = text.partition(" = ")
    if not sep:
        return text.lstrip("%")
    if rest.startswith("("):  # a tuple shape: skip to its closing bracket
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        rest = rest[i + 1:]
    else:
        rest = rest.partition(" ")[2]
    opcode = rest.lstrip().partition("(")[0].strip()
    target = _TARGET.search(text) if opcode == "custom-call" else None
    label = f"{short.lstrip('%')} {opcode}"
    return f"{label} {target.group(1)}" if target else label


def is_collective(label: str) -> bool:
    name, _, opcode = label.partition(" ")
    return bool(COLLECTIVE.match(name) or COLLECTIVE.match(opcode))


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> dict:
    """The trace as plain lists of (name, start_s, end_s): per device its
    operations and programs, and the benchmark's host spans."""
    import jax

    if path.endswith(".textproto"):  # a hand-written trace, for the tests
        with open(path) as f:
            data = jax.profiler.ProfileData.from_text_proto(f.read())
    else:
        data = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(
                int(m.group(1)), {"ops": [], "async": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", ASYNC_LINE: "async",
                       MODULES_LINE: "modules"}.get(line.name)
                if key:
                    name = (lambda t: t) if key == "modules" else op_label
                    dev[key] += [(name(e.name), e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9)
                                 for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                         for e in line.events if e.name.startswith(HOST_PREFIX)]
    return {"devices": devices, "host": host}


def _span(trace: dict):
    starts = [e[1] for d in trace["devices"].values() for e in d["ops"]]
    ends = [e[2] for d in trace["devices"].values() for e in d["ops"]]
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def busy(trace: dict) -> dict:
    """Seconds with an operation running, per chip and averaged, over the
    traced window (first device operation's start to the last's end)."""
    lo, hi = _span(trace)
    per_chip = {
        dev: stats.total(stats.merge_intervals([e[1:] for e in d["ops"]]))
        for dev, d in trace["devices"].items()
    }
    return {"window_s": hi - lo, "per_chip": per_chip,
            "busy_s": sum(per_chip.values()) / len(per_chip)}


def matching_time(events, pattern: str) -> tuple[float, int]:
    """Summed duration and count of the events whose name matches."""
    rx = re.compile(pattern)
    hits = [e for e in events if rx.search(e[0])]
    return sum(e[2] - e[1] for e in hits), len(hits)


def exposed_collective(trace: dict) -> dict:
    """Per chip: the union of collective operations' intervals (the
    synchronous ones, the asynchronous ones from start to done, and the waits
    in their ``-done``) that no compute operation covers, as seconds; and the
    mean over chips. Containers (``while``) are not compute: their bodies are."""
    out = {}
    for dev, d in trace["devices"].items():
        coll = stats.merge_intervals(
            [e[1:] for e in d["ops"] + d["async"] if is_collective(e[0])])
        comp = stats.merge_intervals(
            [e[1:] for e in d["ops"] if not is_collective(e[0])
             and e[0].partition(" ")[2] not in CONTAINERS])
        out[dev] = stats.total(stats.subtract(coll, comp))
    return {"per_chip": out, "exposed_s": sum(out.values()) / max(1, len(out))}


def top_ops(trace: dict, n: int = 10) -> list:
    """The device operations that took most time on the busiest chip, by
    SELF time: an operation that wraps others (a ``while`` and its body) is
    charged only what its children leave."""
    per = busy(trace)["per_chip"]
    dev = max(per, key=per.get)
    by_name: dict = {}
    stack: list = []  # open events, innermost last: [name, end, self_s]

    def close():
        name, _, self_s = stack.pop()
        by_name[name] = by_name.get(name, 0.0) + self_s

    for name, s, e in sorted(trace["devices"][dev]["ops"],
                             key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            close()
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    while stack:
        close()
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs] for name, secs in ranked]


def idle_gaps(trace: dict, n: int = 10) -> list:
    """Idle time of the busiest chip by what the host was doing: each gap
    between device operations is shared out over the ``bench:`` spans that
    overlap it (innermost wins), the rest goes to ``(no span)``."""
    per = busy(trace)["per_chip"]
    dev = max(per, key=per.get)
    lo, hi = _span(trace)
    ran = stats.merge_intervals([e[1:] for e in trace["devices"][dev]["ops"]])
    gaps = stats.subtract([[lo, hi]], ran)
    # innermost span first: shorter spans shadow the longer ones around them
    spans = sorted(trace["host"], key=lambda e: e[2] - e[1])
    by_name: dict = {}
    for g in gaps:
        left = [g]
        for name, s, e in spans:
            if e <= g[0] or s >= g[1] or not left:
                continue
            took = stats.total(stats.clip(left, s, e))
            if took > 0:
                by_name[name] = by_name.get(name, 0.0) + took
                left = stats.subtract(left, [[s, e]])
        rest = stats.total(left)
        if rest > 0:
            by_name["(no span)"] = by_name.get("(no span)", 0.0) + rest
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs] for name, secs in ranked]


def reduce(path: str) -> dict:
    """What ``run.py`` puts on the result line, plus the loaded trace for
    the per-layer readers."""
    trace = load(path)
    b = busy(trace)
    return {
        "trace": trace,
        "busy_s": b["busy_s"], "window_s": b["window_s"],
        "per_chip_busy_s": b["per_chip"],
        "breakdown": {"device_ops": top_ops(trace), "idle_gaps": idle_gaps(trace)},
    }

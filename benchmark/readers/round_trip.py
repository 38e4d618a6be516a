"""One decode step split into device, launch-and-wake-up and host, in ms
(median over the traced decode-only steps), WITHOUT taking the device plane
and the host plane of the trace for one clock: the device plane gives
DURATIONS only, the host plane INSTANTS only, so a constant offset between
the two cancels.

Paired, in order and by the ``n`` stat: each ``atpu:serve.decode.dispatch``
span of ``program`` with an execution of that program on the busiest chip's
``XLA Modules`` line and with the ``atpu:serve.decode.wait`` span of the same
``n``. The pairing is aligned at the trace's first dispatch span: among the
executions that begin after that span began, less a millisecond of slack for
the planes' offset, the last one that has ended when its wait returns (an
engine that decodes ahead has executions in flight, dispatched before the
trace began, when its first span opens: they pair with nothing). Per pair k:

* ``device``      the execution's duration;
* ``period``      dispatch k + 1's start - dispatch k's start (host clock);
* ``exposed``     period - device: what the step cost beyond its program;
* ``launch_wake`` (wait k's end - dispatch k's start) - device: the jitted
  call, the launch and the completion's way back to the host thread. Only
  where no other dispatch lies between the two (an engine that decodes
  ahead: nothing to read);
* ``host``        exposed - launch_wake for such an engine (the copy, emit,
  the caller's loop, schedule, inputs); for an engine that decodes ahead the
  sum of the step's host spans other than ``wait``.

A step whose ``atpu:serve.step`` holds an ``atpu:serve.prefill`` or
``atpu:serve.roll_over`` is left out, at either end of a period. Once per
trace and program the table is printed, with the largest period (a host stall
inside the traced seconds) and the interval of offsets between the planes
that causality allows: what may be added to the device plane's times so that
no execution begins before its call (lo = max of dispatch start - execution
start) or ends after its wait has returned (hi = min of wait end - execution
end). Nothing to read where the trace holds no dispatch span of the program
(a program that draws none) or no device plane."""

import bisect
import functools
import re

from harness import program_trace, stats

DISPATCH = "atpu:serve.decode.dispatch"
WAIT = "atpu:serve.decode.wait"
STEP = "atpu:serve.step"
NOT_DECODE_ONLY = ("atpu:serve.prefill", "atpu:serve.roll_over")
SLACK_S = 1e-3
PARTS = ("device", "period", "exposed", "launch_wake", "host")


def _executions(trace, program):
    """The program's executions on the busiest chip, in order."""
    ran = {dev: stats.total(stats.merge_intervals([op[1:3] for op in d["ops"]]))
           for dev, d in trace["devices"].items()}
    if not ran:
        return []
    rx = re.compile(program)
    return sorted((s, e) for name, s, e in
                  trace["devices"][max(ran, key=ran.get)]["modules"]
                  if rx.search(name))


def pairs(trace, program):
    """``[{"n", "dispatch": (start, end), "wait": (start, end) | None,
    "execution": (start, end)}]`` in order, for the dispatches whose ``n``
    counts up by one from the trace's first."""
    calls = sorted((int(st["n"]), s, e) for name, s, e, st in trace["spans"]
                   if name == DISPATCH and st.get("program") == program)
    waits = {int(st["n"]): (s, e) for name, s, e, st in trace["spans"]
             if name == WAIT and st.get("program") == program}
    runs = _executions(trace, program)
    if not calls or not runs:
        return []
    n0, began = calls[0][0], calls[0][1]
    first = bisect.bisect_left(runs, (began - SLACK_S,))
    if n0 in waits:  # executions in flight before it: ended a step earlier
        done = bisect.bisect_right([e for _, e in runs], waits[n0][1] + SLACK_S)
        first = max(first, done - 1)
    out = []
    for i, (n, s, e) in enumerate(calls):
        if n != n0 + i or first + i >= len(runs):
            break
        out.append({"n": n, "dispatch": (s, e), "wait": waits.get(n),
                    "execution": runs[first + i]})
    return out


def _steps(trace):
    """``atpu:serve.step`` spans as ``(start, end, decode_only, host_s)``:
    ``host_s`` is the sum of the phases inside other than the wait."""
    inner = sorted((s, e, name) for name, s, e, _ in trace["spans"]
                   if name.startswith(program_trace.PROGRAM_PREFIX)
                   and name != STEP)
    starts = [s for s, _, _ in inner]
    out = []
    for name, s, e, _ in trace["spans"]:
        if name != STEP:
            continue
        held = [sp for sp in inner[bisect.bisect_left(starts, s):
                                   bisect.bisect_right(starts, e)]
                if sp[1] <= e]
        out.append((s, e, not any(sp[2] in NOT_DECODE_ONLY for sp in held),
                    sum(sp[1] - sp[0] for sp in held if sp[2] != WAIT)))
    return sorted(out)


@functools.lru_cache(maxsize=8)
def table(path: str, program: str):
    """``{part: median ms}`` for the trace at ``path``, printed once; a part
    with nothing to read is absent. None where nothing pairs."""
    trace = program_trace.load(path)
    found = pairs(trace, program)
    if not found:
        return None
    steps = _steps(trace)
    step_starts = [s for s, _, _, _ in steps]
    every_call = sorted(s for name, s, _, _ in trace["spans"] if name == DISPATCH)

    def step_of(t):
        i = bisect.bisect_right(step_starts, t) - 1
        return steps[i] if i >= 0 and t <= steps[i][1] else None

    rows = {part: [] for part in PARTS}
    lo, hi = float("-inf"), float("inf")
    for k, pair in enumerate(found):
        (d0, _), (x0, x1) = pair["dispatch"], pair["execution"]
        lo = max(lo, d0 - x0)
        if pair["wait"] is not None:
            hi = min(hi, pair["wait"][1] - x1)
        step = step_of(d0)
        # no wait in the trace: the trace's end may have cut the execution
        if pair["wait"] is None or step is None or not step[2]:
            continue
        device = x1 - x0
        rows["device"].append(device)
        alone = (bisect.bisect_left(every_call, pair["wait"][1])
                 == bisect.bisect_right(every_call, d0))
        launch_wake = pair["wait"][1] - d0 - device if alone else None
        if launch_wake is not None:
            rows["launch_wake"].append(launch_wake)
        after = step_of(found[k + 1]["dispatch"][0]) if k + 1 < len(found) else None
        if after is None or not after[2]:
            continue
        period = found[k + 1]["dispatch"][0] - d0
        rows["period"].append(period)
        rows["exposed"].append(period - device)
        rows["host"].append(step[3] if launch_wake is None
                            else period - device - launch_wake)
    out = {part: stats.median(v) * 1e3 for part, v in rows.items() if v}
    said = ", ".join(f"{part} {out[part]:.3f}" for part in PARTS if part in out)
    line = (f"round_trip {program}: {len(found)} pairs, {len(rows['device'])} "
            f"in decode-only steps; medians in ms: {said}")
    if rows["period"]:
        line += f"; largest period {max(rows['period']) * 1e3:.3f}"
    line += (f"; offsets between the planes that causality allows (added to "
             f"the device plane): [{lo * 1e3:.3f}, {hi * 1e3:.3f}] ms")
    off = lo if lo > 0 else hi if hi < 0 else 0.0
    if lo > hi:
        line += ": no offset satisfies every pair"
    elif off and "period" in out:
        line += (f": the planes disagree by at least {abs(off) * 1e3:.3f} ms, "
                 f"{100 * abs(off) * 1e3 / out['period']:.2f} points of the idle "
                 f"under inputs + dispatch against the idle under wait at "
                 f"this step length")
    print(line, flush=True)
    out["offsets_ms"] = (lo * 1e3, hi * 1e3)
    return out


def read(record, trace, cell, program, part):
    path = program_trace.path_of(cell) if trace is not None else None
    found = table(path, program) if path else None
    return None if found is None else found.get(part)

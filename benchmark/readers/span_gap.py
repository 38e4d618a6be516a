"""Idle time of the busiest chip (the gaps between its ``XLA Ops``, as
``trace_reduce.idle_gaps`` takes them) that falls under the program's host
spans named in ``spans``, as % of the traced window. A gap under nested spans
goes to the innermost, among ALL ``atpu:`` spans, so every gap is counted
once; ``spans: null`` reads what lies under no ``atpu:`` span (the caller's
loop). Once per trace the whole table is printed — gap by innermost span,
``atpu:serve.step`` alone being what lies inside a step and in no phase, and
``(no atpu: span)`` — whose rows sum to the idle share of the window.
Nothing to read where the program wrote no ``atpu:`` span."""

import functools

from harness import program_trace, stats

NO_SPAN = "(no atpu: span)"


def innermost(spans):
    """Nested ``(name, start, end)`` spans as disjoint ``[start, end, name]``
    segments, each named for the innermost span that covers it."""
    out, stack, cur = [], [], float("-inf")

    def close_until(t):
        nonlocal cur
        while stack and stack[-1][1] <= t:
            name, end = stack.pop()
            if end > cur:
                out.append([cur, end, name])
                cur = end

    for name, s, e in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        close_until(s)
        if stack and s > cur:
            out.append([cur, s, stack[-1][0]])
        cur = max(cur, s)
        stack.append((name, e))
    close_until(float("inf"))
    return out


@functools.lru_cache(maxsize=4)
def table(path: str):
    """``({span name: % of the window}, window_s)`` for the trace at ``path``,
    printed once; None when it holds no ``atpu:`` span or no device operation."""
    trace = program_trace.load(path)
    spans = [sp[:3] for sp in trace["spans"]
             if sp[0].startswith(program_trace.PROGRAM_PREFIX)]
    gaps, window_s = program_trace.busiest_gaps(trace)
    if not spans or window_s <= 0:
        return None
    by_name, covered, i = {}, 0.0, 0
    segments = innermost(spans)
    for lo, hi in gaps:  # both lists are sorted and disjoint: one sweep
        while i < len(segments) and segments[i][1] <= lo:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < hi:
            took = min(hi, segments[j][1]) - max(lo, segments[j][0])
            by_name[segments[j][2]] = by_name.get(segments[j][2], 0.0) + took
            covered += took
            j += 1
    by_name[NO_SPAN] = stats.total(gaps) - covered
    shares = {name: 100.0 * secs / window_s for name, secs in by_name.items()}
    rows = ", ".join(f"{name} {share:.3f}" for name, share in
                     sorted(shares.items(), key=lambda kv: -kv[1]))
    print(f"span_gap: idle % of the {window_s:.3f}s traced window by innermost "
          f"span: {rows}; sum {sum(shares.values()):.3f}", flush=True)
    return shares, window_s


def read(record, trace, cell, spans):
    path = program_trace.path_of(cell) if trace is not None else None
    found = table(path) if path else None
    if found is None:
        return None
    shares, _ = found
    if spans is None:
        return shares[NO_SPAN]
    names = [spans] if isinstance(spans, str) else spans
    return sum(shares.get(name, 0.0) for name in names)

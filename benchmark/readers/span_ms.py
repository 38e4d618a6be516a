"""Nearest-rank percentile ``q`` of the durations, in ms, of the program's
host spans named ``span`` in the run's trace (``atpu:serve.prefill``: one
``_prefill_slot``, dispatch to fetched token); nothing to read when the trace
holds none."""

from harness import program_trace, stats


def read(record, trace, cell, span, q):
    path = program_trace.path_of(cell) if trace is not None else None
    if path is None:
        return None
    took = [(e - s) * 1e3 for name, s, e, _ in program_trace.load(path)["spans"]
            if name == span]
    return stats.percentile(took, q) if took else None

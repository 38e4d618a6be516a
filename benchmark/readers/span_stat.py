"""A stat the program wrote on its host spans named ``span``, summed over the
spans inside the trace and, with ``over``, divided by the sum of that other
stat of the same spans; times ``scale``. Nothing to read where the trace
holds no such span with those stats."""

from harness import program_trace


def read(record, trace, cell, span, stat, over=None, scale=1.0):
    path = program_trace.path_of(cell) if trace is not None else None
    if path is None:
        return None
    need = [stat] + ([over] if over else [])
    seen = [st for name, _, _, st in program_trace.load(path)["spans"]
            if name == span and all(key in st for key in need)]
    if not seen:
        return None
    total = sum(float(st[stat]) for st in seen)
    if over is None:
        return scale * total
    below = sum(float(st[over]) for st in seen)
    return scale * total / below if below else None

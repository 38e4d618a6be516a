"""``roofline_scope``, for needed work that depends on what the traced calls
held: the mean of each stat named in ``stats`` over the program's host spans
named ``span`` inside the trace is handed to the ``work`` function in the run
record as ``traced_<stat>`` (as ``roofline_traced`` does for ``roofline``);
everything else is ``roofline_scope``'s. Nothing to read where the trace
holds no such span with those stats (a program that does not write them) or
no operation under the scope."""

from harness import program_trace
from readers import roofline_scope


def read(record, trace, cell, program, work, scope, span, stats,
         model="CausalLM"):
    path = program_trace.path_of(cell) if trace is not None else None
    if path is None:
        return None
    seen = [st for name, _, _, st in program_trace.load(path)["spans"]
            if name == span and all(key in st for key in stats)]
    if not seen:
        return None
    means = {f"traced_{key}": sum(float(st[key]) for st in seen) / len(seen)
             for key in stats}
    print(f"roofline_scope_traced {span}: {len(seen)} spans, "
          + ", ".join(f"{k} {v:.1f}" for k, v in means.items()), flush=True)
    return roofline_scope.read({**record, **means}, trace, cell, program, work,
                               scope, model)

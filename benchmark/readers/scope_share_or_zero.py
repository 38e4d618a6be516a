"""``scope_share`` for work that a traced window may not hold at all (a
filling window, every 2,048th byte of a slot): 0 where the programs matching
``program`` ran in the trace and no operation of theirs matches ``scope``;
nothing to read only where none of those programs ran."""

import re

from harness import program_trace
from readers import scope_share


def read(record, trace, cell, program, scope, model="CausalLM"):
    path = program_trace.path_of(cell) if trace is not None else None
    if path is None:
        return None
    seconds, total = scope_share.by_scope(path, program, model)
    if total <= 0:
        return None
    rx = re.compile(scope)
    return 100.0 * sum(s for cleaned, s in seconds.items() if rx.search(cleaned)) / total

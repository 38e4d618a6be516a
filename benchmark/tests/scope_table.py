#!/usr/bin/env python3
"""Where a traced program's device time goes, by the scope the program
named: ``scope_share.by_scope``'s whole table for each program, largest
first, from the trace a ``--trace 1`` run of the cell left under
``.bench_trace/``. By hand, after such a run, in the same checkout:

    python3 benchmark/tests/scope_table.py serve-gdn-moe-sat jit__decode jit__prefill
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(cell_name: str, *programs: str, top: int = 45) -> None:
    from harness import cell as cells
    from harness import program_trace
    from readers import scope_share

    path = program_trace.path_of(cells.load_cell(cell_name))
    for program in programs:
        seconds, total = scope_share.by_scope(path, program, "CausalLM")
        runs = sum(1 for dev in program_trace.load(path)["devices"].values()
                   for name, _s, _e in dev["modules"] if program in name)
        print(f"== {program}: {runs} executions, {total * 1e3:.1f} ms of device "
              f"self time, {total / max(runs, 1) * 1e3:.3f} ms an execution")
        for scope, secs in sorted(seconds.items(), key=lambda kv: -kv[1])[:top]:
            print(f"  {100 * secs / total:6.2f} %  {secs / max(runs, 1) * 1e3:9.3f} ms  {scope}")


if __name__ == "__main__":
    main(*sys.argv[1:])

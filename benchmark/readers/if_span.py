"""Another reader's answer, but only where the trace holds a program span
named ``needs``; nothing to read elsewhere. For a metric over a span whose
extent changed when ``needs`` appeared (``atpu:serve.decode.inputs`` ended at
the call's return, and ``.fetch`` held the wait, until the program drew
``.dispatch``): an older program's wider span must not read under the
narrower span's name, nor a share it never drew as 0."""

from harness import cell as cells
from harness import program_trace


def read(record, trace, cell, needs, reader, args):
    path = program_trace.path_of(cell) if trace is not None else None
    if path is None or not any(
            span[0] == needs for span in program_trace.load(path)["spans"]):
        return None
    return cells.named(f"readers.{reader}").read(record, trace, cell, **args)

"""Share of the traced window in which a collective ran on a chip and no
compute operation covered it, averaged over the chips."""

from harness import trace_reduce


def read(record, trace, cell):
    if trace is None or len(trace["trace"]["devices"]) < 2:
        return None
    exposed = trace_reduce.exposed_collective(trace["trace"])
    return 100.0 * exposed["exposed_s"] / trace["window_s"]

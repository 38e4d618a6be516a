"""The hand-written trace of ``synthetic_round_trip.xplane.textproto`` as a
cell's trace, with the device plane moved against the host plane: what
``test_round_trip.py`` and tier-1's ``tests/test_program_spans.py`` hold
``readers/round_trip.py`` to."""

import importlib
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROUND_TRIP = os.path.join(HERE, "synthetic_round_trip.xplane.textproto")
_DEVICE, _HOST = 'planes {\n  id: 1 name: "/device:TPU:0"', 'planes {\n  id: 2 name: "/host:CPU"'
_LINE_START_NS = 1_000_000
# what the trace's header reckons, medians in ms: engine A (one step at a
# time, program jit__decode) and engine B (one step ahead, jit__ahead)
ONE_AT_A_TIME = {"device": 10.15, "period": 11.6, "exposed": 1.4,
                 "launch_wake": 0.55, "host": 0.75}
AHEAD = {"device": 8.0, "period": 8.1, "exposed": 0.1, "launch_wake": None,
         "host": 0.75}


def text(device_shift_ms: float = 0.0, device_plane: bool = True) -> str:
    """The trace's text with every line of the device plane starting
    ``device_shift_ms`` later, or with no device plane at all."""
    with open(ROUND_TRIP) as f:
        whole = f.read()
    device, host = whole[whole.index(_DEVICE):whole.index(_HOST)], whole[whole.index(_HOST):]
    if not device_plane:
        return host
    moved = _LINE_START_NS + int(round(device_shift_ms * 1e6))
    return device.replace(f"timestamp_ns: {_LINE_START_NS}", f"timestamp_ns: {moved}") + host


def cell_over(root, trace_text: str, name: str = "a-cell") -> dict:
    """A cell under ``root`` whose run 'wrote' ``trace_text``, by
    ``common.Tracer``'s path rule, in the binary form the loader reads."""
    import jax

    where = os.path.join(str(root), f"root-{name}", ".bench_trace", name,
                         "plugins", "profile", "t")
    os.makedirs(where)
    with open(os.path.join(where, "host.xplane.pb"), "wb") as f:
        f.write(jax.profiler.ProfileData.text_proto_to_serialized_xspace(trace_text))
    return {"name": name,
            "bench_dir": os.path.join(str(root), f"root-{name}", "benchmark")}


def read(cell: dict, program: str):
    """``({part: ms | None}, (lo, hi))``: every part of ``readers/round_trip``
    for the cell's trace, and the offsets it found causality to allow."""
    reader = importlib.import_module("readers.round_trip")
    parts = {part: reader.read({}, {}, cell, program=program, part=part)
             for part in reader.PARTS}
    found = reader.table(reader.program_trace.path_of(cell), program)
    return parts, None if found is None else found["offsets_ms"]

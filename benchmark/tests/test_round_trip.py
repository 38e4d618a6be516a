"""``readers/round_trip.py`` on a hand-written trace whose every duration is
known: the five parts to the microsecond, and the SAME answers with the
device plane 0.8 ms late and 0.8 ms early, while ``span_gap``, which takes
the two planes for one clock, moves its idle between ``inputs`` and ``wait``.
By hand: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_round_trip.py``;
tier-1 runs the shifted-plane case from ``tests/test_program_spans.py``."""

import importlib
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import round_trip_traces as traces  # noqa: E402

round_trip = importlib.import_module("readers.round_trip")
span_gap = importlib.import_module("readers.span_gap")


def read(cell, program):
    return traces.read(cell, program)[0]


@pytest.mark.parametrize("shift_ms", [0.0, 0.8, -0.8])
def test_the_five_parts_to_the_microsecond_wherever_the_device_plane_lies(
        tmp_path, capsys, shift_ms):
    cell = traces.cell_over(tmp_path, traces.text(shift_ms))
    got = read(cell, "jit__decode")
    assert got == pytest.approx(traces.ONE_AT_A_TIME, abs=1e-3)
    said = capsys.readouterr().out
    assert said.count("round_trip jit__decode:") == 1  # once per trace
    assert "9 pairs, 8 in decode-only steps" in said
    assert "largest period 11.900" in said
    # what may be added to the device plane's times: what undoes the shift
    lo, hi = traces.read(cell, "jit__decode")[1]
    assert (lo, hi) == pytest.approx((-0.30 - shift_ms, 0.25 - shift_ms), abs=1e-6)
    assert lo <= -shift_ms <= hi
    assert ("the planes disagree by at least" in said) == (shift_ms != 0.0)


def test_an_engine_that_decodes_ahead(tmp_path, capsys):
    """Its execution in flight when the trace began pairs with nothing, though
    it began inside the slack; a dispatch lies between every dispatch and its
    wait, so there is no launch_wake to read."""
    for shift_ms in (0.0, 0.25, -0.25):
        cell = traces.cell_over(tmp_path, traces.text(shift_ms), f"ahead{shift_ms}")
        assert read(cell, "jit__ahead") == pytest.approx(traces.AHEAD, abs=1e-3)
    # the last dispatch has no wait in the trace: its execution may be cut
    assert "5 pairs, 4 in decode-only steps" in capsys.readouterr().out


def test_span_gap_moves_with_the_device_plane_and_round_trip_does_not(tmp_path):
    tables = {}
    for shift_ms in (0.0, 0.8, -0.8):
        cell = traces.cell_over(tmp_path, traces.text(shift_ms), f"gap{shift_ms}")
        shares = {name: span_gap.read({}, {}, cell, spans=f"atpu:serve.decode.{name}")
                  for name in ("inputs", "dispatch", "wait", "fetch")}
        tables[shift_ms] = (shares, read(cell, "jit__decode"))
    (base, parts), (late, parts_late), (early, parts_early) = (
        tables[0.0], tables[0.8], tables[-0.8])
    # equal to the nanosecond (the planes' times are floats from another origin)
    assert parts_late == pytest.approx(parts, abs=1e-6)
    assert parts_early == pytest.approx(parts, abs=1e-6)
    # the device plane late: the gap before an execution slides out of the
    # wait before it and the inputs into the dispatch and the wait behind it
    assert late["dispatch"] > base["dispatch"] and late["fetch"] < base["fetch"]
    # early: out of inputs and dispatch, into the wait before them
    assert early["inputs"] < base["inputs"] and early["dispatch"] < base["dispatch"]
    assert early["wait"] > base["wait"] + 0.5 * (base["inputs"] + base["dispatch"])


def test_nothing_to_read_without_a_dispatch_span_or_a_device_plane(tmp_path):
    with open(os.path.join(HERE, "synthetic_spans.xplane.textproto")) as f:
        parent = traces.cell_over(tmp_path, f.read(), "parent")
    hostonly = traces.cell_over(tmp_path, traces.text(device_plane=False), "host")
    for cell in (parent, hostonly):
        assert all(v is None for v in read(cell, "jit__decode").values())
    # no trace at all (an untraced run)
    assert round_trip.read({}, None, parent, program="jit__decode", part="host") is None


def test_a_narrowed_spans_metric_is_absent_for_a_program_that_draws_no_dispatch(
        tmp_path):
    """``if_span``: the older program's ``fetch`` span held the wait and its
    ``wait`` share was never drawn: neither reads under the new names."""
    if_span = importlib.import_module("readers.if_span")
    with open(os.path.join(HERE, "synthetic_spans.xplane.textproto")) as f:
        parent = traces.cell_over(tmp_path, f.read(), "parent")
    change = traces.cell_over(tmp_path, traces.text(), "change")
    copy = {"needs": "atpu:serve.decode.dispatch", "reader": "span_ms",
            "args": {"span": "atpu:serve.decode.fetch", "q": 50}}
    idle = {"needs": "atpu:serve.decode.dispatch", "reader": "span_gap",
            "args": {"spans": ["atpu:serve.decode.wait"]}}
    assert if_span.read({}, {}, parent, **copy) is None
    assert if_span.read({}, {}, parent, **idle) is None
    assert if_span.read({}, None, change, **copy) is None  # an untraced run
    assert if_span.read({}, {}, change, **copy) == pytest.approx(0.05, abs=1e-6)
    assert if_span.read({}, {}, change, **idle) > 1.0

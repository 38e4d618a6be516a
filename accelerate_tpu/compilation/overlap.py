"""Collective/compute overlap: XLA scheduler options + profile evidence.

Two halves of one story — make the compiler hide collective latency under
compute, then *prove* it did from the step profile:

* **Options** — :func:`overlap_options` returns the MaxText-style XLA
  flag set (async collective fusion + latency-hiding scheduler) for the
  ZeRO/FSDP data-parallel paths, and :func:`merge_compiler_options`
  threads it through the existing ``CompilePlugin.compiler_options``
  hook (PR 2) with user-set options always winning. On a non-TPU
  backend the option set is empty — the CPU test backend would reject
  TPU scheduler flags at compile time, so the fallback is a no-op, not
  an error.
* **Evidence** — :func:`collective_compute_overlap` walks a profile
  capture directory (PR 5 ``TraceCapture`` output), parses the
  ``*.xplane.pb`` device planes with a dependency-free protobuf
  wire-format reader (no tensorflow import), and reports what fraction
  of collective time (all-gather / reduce-scatter / all-reduce /
  all-to-all / collective-permute, including async ``-start``/``-done``
  pairs) ran concurrently with compute. :func:`overlap_from_spans` is
  the pure interval math, unit-testable without a TPU.

Everything here is best-effort: a missing/garbled profile yields
``None``, never an exception on the train loop.
"""

from __future__ import annotations

import os
import re
from typing import Any, Iterable, Optional

from ..logging import get_logger

logger = get_logger(__name__)

# MaxText/T5X-lineage flag set: async collective fusion lets the
# latency-hiding scheduler issue all-gather/reduce-scatter early and
# overlap the wait with compute; the data-parallel all-reduce opts
# apply the same treatment to the pure-DP grad sync.
DEFAULT_OVERLAP_OPTIONS: dict[str, Any] = {
    "xla_tpu_enable_async_collective_fusion": True,
    "xla_tpu_enable_async_collective_fusion_fuse_all_gather": True,
    "xla_tpu_enable_async_collective_fusion_multiple_steps": True,
    "xla_tpu_overlap_compute_collective_tc": True,
    "xla_enable_async_all_gather": True,
    "xla_tpu_enable_data_parallel_all_reduce_opt": True,
    "xla_tpu_data_parallel_opt_different_sized_ops": True,
}

# Added on hierarchical (multi-slice) meshes: DCN-crossing collectives
# are orders of magnitude slower than ICI ones, so the scheduler must
# rank them FIRST — issue the cross-slice all-reduce as early as its
# operands exist and hide the long DCN latency under the in-slice
# compute + ICI collectives that follow.
DCN_OVERLAP_OPTIONS: dict[str, Any] = {
    "xla_tpu_enable_async_collective_fusion_fuse_all_reduce": True,
    "xla_tpu_dcn_max_overlap_estimation": 32,
}

_COLLECTIVE_RE = re.compile(
    r"all[-_]gather|all[-_]reduce|reduce[-_]scatter|all[-_]to[-_]all"
    r"|collective[-_]permute|ragged[-_]all[-_]to[-_]all",
    re.IGNORECASE,
)


def is_collective_event(name: str) -> bool:
    """Does this HLO/trace event name denote a cross-device collective?"""
    return bool(_COLLECTIVE_RE.search(name or ""))


# --------------------------------------------------------------------- #
# options
# --------------------------------------------------------------------- #
def overlap_options(
    plugin: Any = None,
    mesh: Any = None,
    *,
    backend: Optional[str] = None,
) -> dict[str, Any]:
    """The XLA compiler options enabling collective/compute overlap for
    this (plugin, mesh) — ``{}`` whenever they would not apply.

    Empty on a non-TPU backend (the flags are TPU-scheduler knobs; the
    CPU no-op fallback keeps single-host tests and the multichip dryrun
    green) and when the sharding layout issues no per-step collectives
    worth hiding (see ``parallel.sharding.wants_collective_overlap``).
    """
    if backend is None:
        try:
            import jax

            backend = jax.default_backend()
        except Exception:
            return {}
    if backend != "tpu":
        return {}
    options = dict(DEFAULT_OVERLAP_OPTIONS)
    if plugin is not None and mesh is not None:
        from ..parallel.mesh import mesh_num_slices
        from ..parallel.sharding import wants_collective_overlap

        if not wants_collective_overlap(plugin, mesh):
            return {}
        if mesh_num_slices(mesh) > 1:
            options.update(DCN_OVERLAP_OPTIONS)
    return options


def merge_compiler_options(
    overlap: Optional[dict[str, Any]],
    user: Optional[dict[str, Any]],
) -> Optional[dict[str, Any]]:
    """Overlay the overlap flag set UNDER any user-provided
    ``CompilePlugin.compiler_options`` — an explicit user value for the
    same flag always wins. Returns None when both sides are empty (the
    plugin's "untouched" sentinel)."""
    if not overlap:
        return user
    merged = dict(overlap)
    if user:
        merged.update(user)
    return merged


# --------------------------------------------------------------------- #
# evidence: pure interval math
# --------------------------------------------------------------------- #
def _merge_intervals(
    intervals: list[tuple[int, int]],
) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], end))
        else:
            out.append((start, end))
    return out


def overlap_from_spans(spans: Iterable[dict]) -> Optional[dict[str, Any]]:
    """Collective/compute overlap from a flat span list.

    ``spans``: dicts with ``name``, ``start``, ``end`` (any consistent
    time unit; ``end > start``) and optionally an explicit ``kind``
    (``"collective"`` / ``"compute"``) overriding the name classifier.
    Async collectives traced as ``<op>-start`` / ``<op>-done`` pairs are
    folded into one interval spanning issue to completion.

    Returns ``{"overlap_pct", "collective_time", "compute_time",
    "overlapped_time"}`` with ``overlap_pct`` = share of total
    collective time covered by the union of compute spans, or None when
    no collective spans exist (nothing to measure).
    """
    collectives: list[tuple[int, int]] = []
    compute: list[tuple[int, int]] = []
    pending_start: dict[str, tuple[int, int]] = {}
    for span in spans:
        name = str(span.get("name", ""))
        start, end = span["start"], span["end"]
        if end <= start:
            continue
        kind = span.get("kind")
        if kind is None:
            kind = "collective" if is_collective_event(name) else "compute"
        if kind != "collective":
            compute.append((start, end))
            continue
        base = name
        if name.endswith("-start"):
            pending_start[name[: -len("-start")]] = (start, end)
            continue
        if name.endswith("-done"):
            base = name[: -len("-done")]
            issued = pending_start.pop(base, None)
            if issued is not None:
                collectives.append((issued[0], end))
                continue
        collectives.append((start, end))
    # unmatched -start events still count for their own duration
    collectives.extend(pending_start.values())
    if not collectives:
        return None
    collectives = _merge_intervals(collectives)
    compute = _merge_intervals(compute)
    total = sum(e - s for s, e in collectives)
    covered = 0
    ci = 0
    for s, e in collectives:
        while ci < len(compute) and compute[ci][1] <= s:
            ci += 1
        cj = ci
        while cj < len(compute) and compute[cj][0] < e:
            covered += min(e, compute[cj][1]) - max(s, compute[cj][0])
            cj += 1
    return {
        "overlap_pct": 100.0 * covered / total,
        "collective_time": total,
        "compute_time": sum(e - s for s, e in compute),
        "overlapped_time": covered,
    }


# --------------------------------------------------------------------- #
# evidence: .xplane.pb wire-format reader (no proto deps)
# --------------------------------------------------------------------- #
# Minimal protobuf wire walker for the XSpace schema (tsl xplane.proto):
#   XSpace   { repeated XPlane planes = 1; }
#   XPlane   { string name = 2; repeated XLine lines = 3;
#              map<int64, XEventMetadata> event_metadata = 4; }
#   XLine    { string name = 2; int64 timestamp_ns = 3;
#              repeated XEvent events = 4; }
#   XEvent   { int64 metadata_id = 1; int64 offset_ps = 2;
#              int64 duration_ps = 3; }
#   XEventMetadata { int64 id = 1; string name = 2; }
# Only these fields are read; everything else is skipped by wire type.


def _read_varint(buf: bytes, pos: int) -> tuple[int, int]:
    result = 0
    shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        result |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return result, pos
        shift += 7
        if shift > 70:
            raise ValueError("varint too long")


def _fields(buf: bytes):
    """Yield (field_number, wire_type, value) over one message body.
    value: int for varint/fixed, bytes for length-delimited."""
    pos = 0
    end = len(buf)
    while pos < end:
        key, pos = _read_varint(buf, pos)
        field, wire = key >> 3, key & 0x7
        if wire == 0:  # varint
            value, pos = _read_varint(buf, pos)
        elif wire == 1:  # fixed64
            value = int.from_bytes(buf[pos : pos + 8], "little")
            pos += 8
        elif wire == 2:  # length-delimited
            length, pos = _read_varint(buf, pos)
            value = buf[pos : pos + length]
            pos += length
        elif wire == 5:  # fixed32
            value = int.from_bytes(buf[pos : pos + 4], "little")
            pos += 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield field, wire, value


def _parse_event(buf: bytes) -> tuple[int, int, int]:
    metadata_id = offset_ps = duration_ps = 0
    for field, _, value in _fields(buf):
        if field == 1:
            metadata_id = value
        elif field == 2:
            offset_ps = value
        elif field == 3:
            duration_ps = value
    return metadata_id, offset_ps, duration_ps


def _parse_line(buf: bytes) -> dict:
    line = {"name": "", "timestamp_ns": 0, "events": []}
    for field, _, value in _fields(buf):
        if field == 2:
            line["name"] = value.decode("utf-8", "replace")
        elif field == 3:
            line["timestamp_ns"] = value
        elif field == 4:
            line["events"].append(_parse_event(value))
    return line


def _parse_event_metadata_entry(buf: bytes) -> tuple[int, str]:
    """One map<int64, XEventMetadata> entry -> (id, event name)."""
    key = 0
    name = ""
    for field, _, value in _fields(buf):
        if field == 1:
            key = value
        elif field == 2:  # XEventMetadata
            for f2, _, v2 in _fields(value):
                if f2 == 2:
                    name = v2.decode("utf-8", "replace")
    return key, name


def _parse_plane(buf: bytes) -> dict:
    plane = {"name": "", "lines": [], "event_names": {}}
    for field, _, value in _fields(buf):
        if field == 2:
            plane["name"] = value.decode("utf-8", "replace")
        elif field == 3:
            plane["lines"].append(_parse_line(value))
        elif field == 4:
            key, name = _parse_event_metadata_entry(value)
            plane["event_names"][key] = name
    return plane


def parse_xspace_planes(data: bytes) -> list[dict]:
    """Decode an XSpace blob -> list of plane dicts (name, lines with
    (metadata_id, offset_ps, duration_ps) events, metadata-id -> event
    name map). Raises ValueError on malformed input."""
    return [
        _parse_plane(value)
        for field, wire, value in _fields(data)
        if field == 1 and wire == 2
    ]


def spans_from_plane(plane: dict) -> list[dict]:
    """Flatten one device plane into :func:`overlap_from_spans` input,
    on the absolute picosecond timeline (line timestamp + offset)."""
    names = plane["event_names"]
    spans = []
    for line in plane["lines"]:
        base_ps = line["timestamp_ns"] * 1000
        for metadata_id, offset_ps, duration_ps in line["events"]:
            if duration_ps <= 0:
                continue
            start = base_ps + offset_ps
            spans.append(
                {
                    "name": names.get(metadata_id, ""),
                    "start": start,
                    "end": start + duration_ps,
                }
            )
    return spans


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


# --------------------------------------------------------------------- #
# evidence: op-level self-time breakdown
# --------------------------------------------------------------------- #
def self_times_from_plane(plane: dict) -> dict[str, tuple[int, int]]:
    """Per-op-name **self time** (nested children subtracted) from one
    plane -> ``{name: (self_ps, count)}``.

    Trace lines nest: a fusion event contains the sub-op events it
    fused, so summing raw durations double-counts every level. Within
    each line, events are walked in ``(start, -end)`` order with a stack
    of open intervals; an event fully inside the stack top is its child,
    and a parent's self time is its duration minus the directly-enclosed
    child durations.
    """
    names = plane["event_names"]
    totals: dict[str, list[int]] = {}
    for line in plane["lines"]:
        events = []
        for metadata_id, offset_ps, duration_ps in line["events"]:
            if duration_ps <= 0:
                continue
            events.append(
                (offset_ps, offset_ps + duration_ps,
                 names.get(metadata_id, ""))
            )
        events.sort(key=lambda e: (e[0], -e[1]))
        # stack entries: [end_ps, duration_ps, child_ps, name]
        stack: list[list] = []
        def _pop():
            end, dur, child, name = stack.pop()
            slot = totals.setdefault(name, [0, 0])
            slot[0] += max(dur - child, 0)
            slot[1] += 1
            if stack:
                stack[-1][2] += dur
        for start, end, name in events:
            while stack and stack[-1][0] <= start:
                _pop()
            stack.append([end, end - start, 0, name])
        while stack:
            _pop()
    return {name: (ps, n) for name, (ps, n) in totals.items()}


def top_ops_from_plane(plane: dict, k: int = 5) -> list[dict]:
    """Top-``k`` ops by self time in one plane, as JSON-ready dicts
    ``{"op", "self_time_ms", "count"}`` sorted descending."""
    ranked = sorted(
        self_times_from_plane(plane).items(),
        key=lambda kv: -kv[1][0],
    )[: max(k, 0)]
    return [
        {
            "op": name,
            "self_time_ms": round(ps / 1e9, 6),
            "count": count,
        }
        for name, (ps, count) in ranked
        if ps > 0
    ]


def top_self_time_ops(trace_dir: str, k: int = 5) -> Optional[list[dict]]:
    """Best-effort top-``k`` op breakdown for one capture directory.

    Walks ``trace_dir`` for ``*.xplane.pb`` dumps, aggregates self time
    per op name across every accelerator device plane (falling back to
    host/CPU planes when no device plane exists — the CPU test backend
    still produces a meaningful breakdown), and returns the ranked list
    or None when nothing parses. Never raises.
    """
    try:
        paths = []
        for root, _, files in os.walk(trace_dir):
            paths.extend(
                os.path.join(root, f)
                for f in files
                if f.endswith(".xplane.pb")
            )
        device_totals: dict[str, list[int]] = {}
        host_totals: dict[str, list[int]] = {}
        for path in sorted(paths):
            try:
                with open(path, "rb") as fh:
                    planes = parse_xspace_planes(fh.read())
            except (OSError, ValueError, IndexError) as exc:
                logger.debug(f"skipping unparseable xplane {path}: {exc}")
                continue
            for plane in planes:
                totals = (
                    device_totals
                    if _is_device_plane(plane["name"])
                    else host_totals
                )
                for name, (ps, count) in self_times_from_plane(
                    plane
                ).items():
                    slot = totals.setdefault(name, [0, 0])
                    slot[0] += ps
                    slot[1] += count
        totals = device_totals or host_totals
        if not totals:
            return None
        ranked = sorted(totals.items(), key=lambda kv: -kv[1][0])
        out = [
            {
                "op": name,
                "self_time_ms": round(ps / 1e9, 6),
                "count": count,
            }
            for name, (ps, count) in ranked[: max(k, 0)]
            if ps > 0
        ]
        return out or None
    except Exception as exc:  # diagnostics never take down training
        logger.debug(f"top_self_time_ops({trace_dir}) failed: {exc}")
        return None


def collective_compute_overlap(trace_dir: str) -> Optional[dict[str, Any]]:
    """Best-effort overlap report for one profile capture directory.

    Walks ``trace_dir`` for ``*.xplane.pb`` dumps (the layout
    ``jax.profiler.start_trace`` writes), folds every accelerator device
    plane's spans, and returns the :func:`overlap_from_spans` report
    plus ``{"source": path, "devices": n}`` — or None when there is no
    parseable device plane with collective events (always the case on
    CPU). Never raises.
    """
    try:
        paths = []
        for root, _, files in os.walk(trace_dir):
            paths.extend(
                os.path.join(root, f)
                for f in files
                if f.endswith(".xplane.pb")
            )
        for path in sorted(paths):
            try:
                with open(path, "rb") as fh:
                    planes = parse_xspace_planes(fh.read())
            except (OSError, ValueError, IndexError) as exc:
                logger.debug(f"skipping unparseable xplane {path}: {exc}")
                continue
            spans: list[dict] = []
            devices = 0
            for plane in planes:
                if not _is_device_plane(plane["name"]):
                    continue
                devices += 1
                spans.extend(spans_from_plane(plane))
            report = overlap_from_spans(spans) if spans else None
            if report is not None:
                report["source"] = path
                report["devices"] = devices
                return report
        return None
    except Exception as exc:  # diagnostics never take down training
        logger.debug(f"collective_compute_overlap({trace_dir}) failed: {exc}")
        return None


def assert_overlap(
    trace_dir: str, min_pct: float = 10.0
) -> dict[str, Any]:
    """The multichip profile assertion: parse ``trace_dir`` and require
    ``overlap_pct >= min_pct``. Raises AssertionError with the report
    (or the absence of one) spelled out — dryrun harness hook."""
    report = collective_compute_overlap(trace_dir)
    assert report is not None, (
        f"no collective events found in any device plane under {trace_dir}"
    )
    assert report["overlap_pct"] >= min_pct, (
        f"collective/compute overlap {report['overlap_pct']:.1f}% < "
        f"{min_pct:.1f}% (report: {report})"
    )
    return report

"""What the PROGRAM wrote into the run's profiler trace, beside what
``trace_reduce`` reads: its host spans (``jax.profiler.TraceAnnotation``s
named ``atpu:...``, and the benchmark's own ``bench:...``) WITH their stats,
and per device operation the scope path the program gave it
(``jax.named_scope``, Flax module names, a ``pallas_call``'s ``name=``).

The scope path is the ``tf_op`` stat of an operation's EVENT METADATA, e.g.
``jit(_step)/transpose(jvp(loss))/CausalLM/layers/while/body/closed_call/
checkpoint/layers/mlp/up_proj/dot_general:``. ``jax.profiler.ProfileData``
shows an event's own stats but not its metadata's, so this module parses the
``.xplane.pb`` itself with ``google.protobuf`` (imported lazily, here only)
against the few fields of ``xplane.proto`` it reads, declared below: no
TensorFlow import, the same on the chip machine and in the CPU tests.
Times are seconds on the trace's one clock, counted from its earliest line
(``trace_reduce`` counts from the epoch; differences are the same).
"""

from __future__ import annotations

import functools
import os
import re

from . import stats, trace_reduce

SPAN_PREFIXES = ("atpu:", "bench:")
PROGRAM_PREFIX = "atpu:"
# path components that are transformation or control-flow wrappers, not names
# the program chose; ``jit(f)`` is dropped whole, ``jvp(x)`` and the like keep x
WRAPPERS = frozenset({
    "pjit", "shard_map", "while", "body", "cond", "closed_call", "checkpoint",
    "rematted_computation"})
_CALL = re.compile(r"^(\w*)\((.*)\)$")


@functools.cache
def _xspace():
    """The message class for an ``XSpace``, from a descriptor of the fields
    read here (numbers and types as in tsl's ``xplane.proto``; every other
    field is skipped by the parser)."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    fdp = descriptor_pb2.FieldDescriptorProto
    int64, string, message = fdp.TYPE_INT64, fdp.TYPE_STRING, fdp.TYPE_MESSAGE
    pkg = "atpu_bench_xplane"
    file = descriptor_pb2.FileDescriptorProto(
        name=f"{pkg}.proto", package=pkg, syntax="proto3")

    def add(name, *fields, oneof=None):
        msg = file.message_type.add(name=name)
        if oneof:
            msg.oneof_decl.add(name=oneof)
        for fname, number, ftype, *more in fields:
            f = msg.field.add(name=fname, number=number, type=ftype,
                              label=fdp.LABEL_OPTIONAL)
            for m in more:
                if m == "repeated":
                    f.label = fdp.LABEL_REPEATED
                elif m == "oneof":
                    f.oneof_index = 0
                else:
                    f.type_name = f".{pkg}.{m}"

    add("XStat", ("metadata_id", 1, int64),
        ("double_value", 2, fdp.TYPE_DOUBLE, "oneof"),
        ("uint64_value", 3, fdp.TYPE_UINT64, "oneof"),
        ("int64_value", 4, int64, "oneof"),
        ("str_value", 5, string, "oneof"),
        ("bytes_value", 6, fdp.TYPE_BYTES, "oneof"),
        ("ref_value", 7, fdp.TYPE_UINT64, "oneof"), oneof="value")
    add("XEvent", ("metadata_id", 1, int64), ("offset_ps", 2, int64),
        ("duration_ps", 3, int64), ("stats", 4, message, "repeated", "XStat"))
    add("XLine", ("name", 2, string), ("timestamp_ns", 3, int64),
        ("events", 4, message, "repeated", "XEvent"))
    add("XEventMetadata", ("name", 2, string),
        ("stats", 5, message, "repeated", "XStat"))
    add("XStatMetadata", ("name", 2, string))
    add("EventMetadataEntry", ("key", 1, int64),
        ("value", 2, message, "XEventMetadata"))
    add("StatMetadataEntry", ("key", 1, int64),
        ("value", 2, message, "XStatMetadata"))
    add("XPlane", ("name", 2, string), ("lines", 3, message, "repeated", "XLine"),
        ("event_metadata", 4, message, "repeated", "EventMetadataEntry"),
        ("stat_metadata", 5, message, "repeated", "StatMetadataEntry"))
    add("XSpace", ("planes", 1, message, "repeated", "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(file)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{pkg}.XSpace"))


def _stats(xstats, stat_names) -> dict:
    out = {}
    for st in xstats:
        kind = st.WhichOneof("value")
        if kind is None:
            continue
        value = getattr(st, kind)
        if kind == "ref_value":  # a string kept once, among the stat names
            value = stat_names.get(value, "")
        out[stat_names.get(st.metadata_id, str(st.metadata_id))] = value
    return out


@functools.lru_cache(maxsize=4)
def load(path: str) -> dict:
    """``{"spans": [(name, start_s, end_s, stats)], "devices": {n: {"ops":
    [(label, start_s, end_s, scope path)], "modules": [(name, start_s,
    end_s)]}}}``; loaded once per path. An operation's label is
    ``trace_reduce.op_label``'s, its scope path is "" where XLA recorded none."""
    space = _xspace()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    spans, devices = [], {}
    # from the earliest line, so that a float holds a picosecond offset
    t0_ns = min((line.timestamp_ns for plane in space.planes
                 for line in plane.lines if line.events), default=0)
    for plane in space.planes:
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}

        def timed(line):
            base = (line.timestamp_ns - t0_ns) * 1e-9
            for ev in line.events:
                start = base + ev.offset_ps * 1e-12
                yield ev, start, start + ev.duration_ps * 1e-12

        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), {"ops": [], "modules": []})
            scope_of = {
                key: _stats(md.stats, stat_names).get("tf_op", "").partition(":")[0]
                for key, md in meta.items()}
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    dev["ops"] += [
                        (trace_reduce.op_label(meta[ev.metadata_id].name), s, e,
                         scope_of[ev.metadata_id]) for ev, s, e in timed(line)]
                elif line.name == trace_reduce.MODULES_LINE:
                    dev["modules"] += [(meta[ev.metadata_id].name, s, e)
                                       for ev, s, e in timed(line)]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev, s, e in timed(line):
                    name = meta[ev.metadata_id].name
                    if name.startswith(SPAN_PREFIXES):
                        spans.append((name, s, e, _stats(ev.stats, stat_names)))
    return {"spans": spans, "devices": devices}


def path_of(cell: dict):
    """Where this run of ``cell`` wrote its trace, by ``common.Tracer``'s
    path rule (``<checkout>/.bench_trace/<cell>``); None when there is none."""
    trace_dir = os.path.join(os.path.dirname(cell["bench_dir"]), ".bench_trace",
                             cell["name"])
    try:
        return trace_reduce.find_xplane(trace_dir)
    except FileNotFoundError:
        return None


def self_seconds(ops) -> list:
    """Per operation ``(label, scope path, self seconds)``: an operation that
    wraps others (a ``while`` and its body) is charged only what its children
    leave, as ``trace_reduce.top_ops`` reckons it."""
    out = []
    stack: list = []  # open events, innermost last: [label, path, end, self_s]

    def close():
        label, path, _, self_s = stack.pop()
        out.append((label, path, self_s))

    for label, s, e, path in sorted(ops, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            close()
        if stack:
            stack[-1][3] -= min(e, stack[-1][2]) - s
        stack.append([label, path, e, e - s])
    while stack:
        close()
    return out


def scope_of(path: str, model: str = "") -> str:
    """The scope path with what the program did not name taken out:
    ``jit(f)`` components, the transformation around a name (``jvp(loss)`` ->
    ``loss``), control-flow and call wrappers (``while``, ``body``,
    ``closed_call``, ``checkpoint``, ...) and the model's class name. The
    last component, the primitive, stays: ``layers/mlp/up_proj/dot_general``.
    An operation whose whole result is its primitive is UNSCOPED."""
    parts = path.split("/")
    kept = []
    for comp in parts[:-1]:
        m = _CALL.match(comp)
        while m:  # transpose(jvp(loss)) -> loss; jit(...) and vmap() -> nothing
            comp = "" if m.group(1) in ("jit", "pjit") else m.group(2)
            m = _CALL.match(comp)
        if comp and comp not in WRAPPERS and comp != model \
                and not comp.startswith("branch_"):
            kept.append(comp)
    return "/".join(kept + parts[-1:])


def is_unscoped(cleaned: str) -> bool:
    return "/" not in cleaned


def busiest_gaps(trace: dict):
    """``(gaps, window_s)`` of the busiest chip: the intervals between its
    operations over the traced window (first device operation's start to
    the last's end, over all chips), as ``trace_reduce.idle_gaps`` takes them."""
    every = [op for d in trace["devices"].values() for op in d["ops"]]
    if not every:
        return [], 0.0
    lo, hi = min(op[1] for op in every), max(op[2] for op in every)
    ran = {dev: stats.merge_intervals([op[1:3] for op in d["ops"]])
           for dev, d in trace["devices"].items()}
    dev = max(ran, key=lambda k: stats.total(ran[k]))
    return stats.subtract([[lo, hi]], ran[dev]), hi - lo

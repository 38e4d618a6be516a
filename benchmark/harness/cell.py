"""Everything the harness knows about a cell comes from data: the entry in
``BENCHMARK.json`` and the files it names. Nothing here knows a cell, a
configuration or a metric by name."""

from __future__ import annotations

import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def named(dotted: str):
    """What a data file names as ``package.module`` or ``package.module:attr``
    under the benchmark's directory: a metric's reader, a configuration's
    plain reference and weights, a roofline metric's needed-work function. A
    later PR brings a new module and names it; nothing here is edited."""
    module, _, attr = dotted.partition(":")
    mod = importlib.import_module(module)
    return getattr(mod, attr) if attr else mod


def load_cell(name: str, root: str = ROOT) -> dict:
    """One cell with its configuration, its traffic and its metrics."""
    bench = _read(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in bench['workloads']]}")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    bdir = os.path.join(root, bench["paths"][0])
    cell = dict(entry)
    cell["config_entry"] = conf
    cell["config"] = _read(os.path.join(root, conf["file"]))
    cell["spec"] = _read(os.path.join(bdir, "workloads", f"{name}.json"))
    cell["bench_dir"] = bdir

    def applies(metric):
        return "workloads" not in metric or name in metric["workloads"]

    for group in ("end_to_end", "per_layer"):
        cell[group] = []
        for metric in bench[group]:
            if applies(metric):
                spec = _read(os.path.join(bdir, "metrics", f"{metric['name']}.json"))
                cell[group].append({**metric, **spec})
    return cell


def evaluate(cell: dict, group: str, record: dict, trace) -> dict:
    """Each metric of the group through its reader; a reader that finds
    nothing to read returns None and the metric is left out of the line."""
    out = {}
    for metric in cell[group]:
        reader = named(f"readers.{metric['reader']}")
        value = reader.read(record, trace, cell, **metric.get("args", {}))
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}

"""Every name and unit in BENCHMARK.json and the files it names keeps to the
contract's alphabet, every file it needs is there, and every ``moves`` names
an end-to-end metric that each listed cell reports."""

import json
import os
import re

import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(path):
    with open(path) as f:
        return json.load(f)


def test_benchmark_json_and_its_files():
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    for c in configs.values():
        assert NAME.match(c["name"]) and c["file"].startswith("benchmark/")
        data = load(os.path.join(ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
        assert sorted(c["reduced"]) == sorted(data["reduced"])
        for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                    "num_key_value_heads", "head_dim", "vocab_size"):
            assert key not in c["reduced"], "a width may never be reduced"
    assert {w["config"] for w in cells.values()} == set(configs)
    four = [w for w in cells.values() if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    for w in cells.values():
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        spec = load(os.path.join(BENCH, "workloads", f"{w['name']}.json"))
        assert spec["configuration"] == w["config"] and spec["chips"] == w["chips"]
        assert os.path.isfile(os.path.join(BENCH, "harness", f"{spec['kind']}_runner.py"))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1

    def cells_of(metric):
        return set(metric.get("workloads", cells))

    for m in list(e2e.values()) + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert cells_of(m) <= set(cells)
        spec = load(os.path.join(BENCH, "metrics", f"{m['name']}.json"))
        assert spec["unit"] == m["unit"] and spec["better"] == m["better"]
        assert os.path.isfile(os.path.join(BENCH, "readers", f"{spec['reader']}.py"))
    for m in e2e.values():
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m
        assert len(m["layer"]) <= 200 and "\n" not in m["layer"]
        assert cells_of(m) <= cells_of(e2e[m["moves"]]), (
            f"{m['name']} lists a cell that does not report {m['moves']}")
    for name in cells:  # setup_s, one more end-to-end and one per-layer each
        assert sum(name in cells_of(m) for m in e2e.values()) >= 2
        assert any(name in cells_of(m) for m in bench["per_layer"])


def test_what_a_data_file_names_is_there():
    """A configuration's reference, weights and program fields, and a
    roofline metric's needed work, are found by the names the files give."""
    import dataclasses

    from accelerate_tpu.models import TransformerConfig
    from harness import cell as cells

    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    for fname in os.listdir(os.path.join(BENCH, "configs")):
        cfg = load(os.path.join(BENCH, "configs", fname))
        ref, weights = cells.named(cfg["reference"]), cells.named(cfg["weights"])
        for need in ("train_reference", "served_token_gaps", "leaf_norms",
                     "param_change_leaf_norms"):
            assert callable(getattr(ref, need)), (fname, need)
        for need in ("make_tree", "abstract_tree", "layer_slice", "base_key",
                     "spread_shardings"):
            assert callable(getattr(weights, need)), (fname, need)
        assert set(cfg["program_fields"]) <= fields
        assert all(key in cfg for key in cfg["program_fields"].values())
    for fname in os.listdir(os.path.join(BENCH, "metrics")):
        spec = load(os.path.join(BENCH, "metrics", fname))
        if "work" in spec.get("args", {}):
            assert callable(cells.named(spec["args"]["work"]))


def test_every_data_file_keeps_to_the_alphabet():
    for sub in ("configs", "workloads", "metrics"):
        for fname in os.listdir(os.path.join(BENCH, sub)):
            assert re.match(r"^[A-Za-z0-9_.\-]+\.json$", fname), fname
            data = load(os.path.join(BENCH, sub, fname))
            assert NAME.match(data["name"]) and data["name"] + ".json" == fname
            if "unit" in data:
                assert UNIT.match(data["unit"])

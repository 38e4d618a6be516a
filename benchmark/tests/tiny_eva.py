"""A tiny copy of the EvaByte configuration and its cell for the CPU tests and
the rehearsal, in the manner of ``tiny_hybrid.py``: the same keys as
``configs/evabyte-6.5b-serve-1chip.json`` at widths a test run can hold
(hidden 64, 4 heads of 16, chunks of 4 in windows of 16, 2 layers), added to a
copy of the benchmark as NEW files and entries only."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
TWIN = "serve-eva-longctx-sat"  # the committed cell whose metrics a tiny one reports
REAL = "evabyte-6.5b-serve-1chip"


def config(**over) -> dict:
    """The real file with its sizes made tiny; ``over`` replaces any key."""
    with open(os.path.join(BENCH, "configs", f"{REAL}.json")) as f:
        cfg = json.load(f)
    cfg.update(
        name="tiny-eva", source="benchmark/tests/tiny_eva.py", vocab_size=40,
        hidden_size=64, intermediate_size=160, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=4, head_dim=16,
        chunk_size=4, window_size=16, num_pred_heads=3,
        max_position_embeddings=128, reduced=[])
    cfg.update(over)
    return cfg


def serve_cell(name="tiny-eva-sat", dtype="float32") -> dict:
    """Prompts and answers that cross several windows of 16 in a context of
    128, four slots seated, blocks of 4: windows fill in every slot."""
    return {
        "name": name, "kind": "serve", "configuration": "tiny-eva", "chips": 1,
        "weight_dtype": dtype,
        "engine": {"max_slots": 4, "block_size": 4, "max_seq_len": 128},
        "traffic": {"rate_per_s": 8.0, "preseat": 4, "arrangement": 1,
                    "prompt": {"median": 30, "sigma": 0.6, "min": 6, "max": 64},
                    "output": {"median": 24, "sigma": 0.5, "min": 8, "max": 60}},
        "drain_limit_s": 60.0,
        # every finished request: a fault in one of them has to be seen
        "reference_sample": 64, "reference_rows_per_block": 8,
        # float32 on the CPU serves the reference's own best byte: sound reads 0
        "limits": {"served_token_mean_logit_gap": 1e-5,
                   "worst_request_mean_logit_gap": 2e-4, "widest_logit_gap": 1.5e-3},
        "trace_seconds": 0.5, "why": "CPU rehearsal",
    }


def make_root(tmp: str, cell: dict, cfg: dict | None = None) -> str:
    """Copy the benchmark into ``tmp`` and ADD the tiny configuration and
    cell; the cell reports what the committed cell ``TWIN`` reports."""
    shutil.copytree(BENCH, os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    b = os.path.join(tmp, "benchmark")
    with open(os.path.join(b, "configs", "tiny-eva.json"), "w") as f:
        json.dump(cfg or config(), f)
    with open(os.path.join(b, "workloads", f"{cell['name']}.json"), "w") as f:
        json.dump(cell, f)
    bench["configs"].append({
        "name": "tiny-eva", "source": "benchmark/tests/tiny_eva.py",
        "file": "benchmark/configs/tiny-eva.json", "reduced": [],
        "why": "CPU rehearsal"})
    bench["workloads"].append({
        "name": cell["name"], "config": "tiny-eva", "traffic": cell["name"],
        "chips": 1, "why": "rehearsal"})
    for group in ("end_to_end", "per_layer"):
        for metric in bench[group]:
            if TWIN in metric.get("workloads", ()):
                metric["workloads"].append(cell["name"])
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp

"""A tiny copy of the mixed window / full attention sparse-expert configuration
and its cell for the CPU tests and the rehearsal, in the manner of
``tiny_qwen3_next.py``: the same keys as
``configs/smallthinker-21b-a3b-serve-1chip.json`` at widths a test run can hold
(hidden 48; 4 query heads over 2 KV heads of 16; 8 experts of width 24, 3 a
token; a window of 8 positions in blocks of 4; two periods of full, sliding,
sliding, sliding), added to a copy of the benchmark as NEW files and entries
only."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
REAL = "smallthinker-21b-a3b-serve-1chip"
TWIN = "serve-swa-moe-mixed-sat"  # the committed cell whose metrics a tiny one reports


def real() -> dict:
    with open(os.path.join(BENCH, "configs", f"{REAL}.json")) as f:
        return json.load(f)


def config(periods: int = 2, **over) -> dict:
    """Whole periods of the published layout at tiny widths; ``over`` replaces
    any key."""
    cfg = dict(real())
    period = cfg["sliding_window_layout"][:4]
    cfg.update({
        "name": "tiny-smallthinker", "source": "benchmark/tests/tiny_smallthinker.py",
        "vocab_size": 96, "hidden_size": 48, "intermediate_size": 24,
        "moe_ffn_hidden_size": 24, "num_hidden_layers": 4 * periods,
        "sliding_window_layout": period * periods, "rope_layout": period * periods,
        "layer_types": ["sliding_attention" if w else "full_attention"
                        for w in period * periods],
        "sliding_window_size": 8,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "moe_num_primary_experts": 8, "moe_num_active_primary_experts": 3,
        "max_position_embeddings": 128, "reduced": [], "assumed": {},
        # the period as ONE scanned body (the committed file holds its eight
        # layers alone: ``scan_layers`` false there)
        "scan_layers": True,
    })
    cfg.update(over)
    return cfg


def serve_cell(name="tiny-swa-moe-mixed-sat", dtype="float32") -> dict:
    """Prompts below, across and beyond two windows of 8, in prefill buckets
    of 4-64; four slots seated; blocks of 4; answers that wrap a ring."""
    return {
        "name": name, "kind": "serve_hybrid", "configuration": "tiny-smallthinker",
        "chips": 1, "weight_dtype": dtype,
        "engine": {"max_slots": 4, "block_size": 4, "max_seq_len": 128},
        "traffic": {"rate_per_s": 6.0, "preseat": 4, "arrangement": 1,
                    "prompt": {"median": 12, "sigma": 1.0, "min": 3, "max": 64},
                    "output": {"median": 12, "sigma": 0.6, "min": 5, "max": 40}},
        "drain_limit_s": 90.0,
        # every finished request: a fault in one of them has to be seen
        "reference_sample": 64, "reference_rows_per_block": 1,
        # the first tokens the decode steps of the shorter requests served:
        # the prefill's write into the ring and the first reads out of it
        "handoff_sample": {"requests": 16, "decoded": 3, "width": 32},
        # float32 on the CPU serves the reference's own best token but for a
        # near-tied expert choice: sound reads 0 to 1e-5
        "limits": {"served_token_mean_logit_gap": 1e-4,
                   "worst_request_mean_logit_gap": 1e-3, "widest_logit_gap": 1e-2,
                   "first_decoded_mean_logit_gap": 1e-4},
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
    with open(os.path.join(b, "configs", "tiny-smallthinker.json"), "w") as f:
        json.dump(cfg or config(), f)
    with open(os.path.join(b, "workloads", f"{cell['name']}.json"), "w") as f:
        json.dump(cell, f)
    bench["configs"].append({
        "name": "tiny-smallthinker", "source": "benchmark/tests/tiny_smallthinker.py",
        "file": "benchmark/configs/tiny-smallthinker.json", "reduced": [],
        "why": "CPU rehearsal"})
    bench["workloads"].append({
        "name": cell["name"], "config": "tiny-smallthinker", "traffic": cell["name"],
        "chips": 1, "why": "rehearsal"})
    for group in ("end_to_end", "per_layer"):
        for metric in bench[group]:
            if TWIN in metric.get("workloads", ()):
                metric["workloads"].append(cell["name"])
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp

"""A tiny copy of the hybrid linear-attention / sparse-expert configuration and
its cell for the CPU tests and the rehearsal, in the manner of
``tiny_eva.py``: the same keys as
``configs/qwen3-next-80b-a3b-serve-1chip.json`` at widths a test run can hold
(hidden 48; 2 key heads and 4 value heads of 8 under a convolution of 4 taps;
4 query heads over 2 KV heads of 16, rope over the first 4; 4 of 8 experts
held, 3 a token), added to a copy of the benchmark as NEW files and entries
only."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
REAL = "qwen3-next-80b-a3b-serve-1chip"
TWIN = "serve-gdn-moe-sat"  # the committed cell whose metrics a tiny one reports


def real() -> dict:
    with open(os.path.join(BENCH, "configs", f"{REAL}.json")) as f:
        return json.load(f)


def config(layers: int = 4, num_experts: int = 4, expert_offset: int = 0,
           router_width: int = 8, **over) -> dict:
    """The cut's shape (half of the router's experts held, one whole period)
    at tiny widths; ``over`` replaces any key."""
    cfg = dict(real())
    interval = cfg["full_attention_interval"]
    cfg.update({
        "name": "tiny-qwen3-next", "source": "benchmark/tests/tiny_qwen3_next.py",
        "vocab_size": 96, "hidden_size": 48, "intermediate_size": 64,
        "moe_intermediate_size": 24, "shared_expert_intermediate_size": 40,
        "num_hidden_layers": layers,
        "layer_types": ["full_attention" if (i + 1) % interval == 0
                        else "linear_attention" for i in range(layers)],
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "linear_num_key_heads": 2, "linear_num_value_heads": 4,
        "linear_key_head_dim": 8, "linear_value_head_dim": 8,
        "num_experts": num_experts, "num_experts_per_tok": 3,
        "router_width": router_width, "expert_offset": expert_offset,
        "max_position_embeddings": 256, "reduced": [], "assumed": {},
    })
    cfg.update(over)
    return cfg


def serve_cell(name="tiny-gdn-moe-sat", dtype="float32") -> dict:
    """Prompts that do and do not fill whole chunks of 64, in prefill buckets
    of 8-128; four slots seated; blocks of 4."""
    return {
        "name": name, "kind": "serve_hybrid", "configuration": "tiny-qwen3-next",
        "chips": 1, "weight_dtype": dtype,
        "engine": {"max_slots": 4, "block_size": 4, "max_seq_len": 256},
        "traffic": {"rate_per_s": 6.0, "preseat": 4, "arrangement": 1,
                    "prompt": {"median": 40, "sigma": 0.7, "min": 6, "max": 128},
                    "output": {"median": 16, "sigma": 0.5, "min": 6, "max": 40}},
        "drain_limit_s": 90.0,
        # every finished request: a fault in one of them has to be seen
        "reference_sample": 64, "reference_rows_per_block": 1,
        # float32 on the CPU serves the reference's own best token but for a
        # near-tied expert choice: sound reads 0 to 1e-5
        # the first tokens the decode steps of the shorter requests served
        "handoff_sample": {"requests": 16, "decoded": 3, "width": 64},
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
    with open(os.path.join(b, "configs", "tiny-qwen3-next.json"), "w") as f:
        json.dump(cfg or config(), f)
    with open(os.path.join(b, "workloads", f"{cell['name']}.json"), "w") as f:
        json.dump(cell, f)
    bench["configs"].append({
        "name": "tiny-qwen3-next", "source": "benchmark/tests/tiny_qwen3_next.py",
        "file": "benchmark/configs/tiny-qwen3-next.json", "reduced": [],
        "why": "CPU rehearsal"})
    bench["workloads"].append({
        "name": cell["name"], "config": "tiny-qwen3-next", "traffic": cell["name"],
        "chips": 1, "why": "rehearsal"})
    for group in ("end_to_end", "per_layer"):
        for metric in bench[group]:
            if TWIN in metric.get("workloads", ()):
                metric["workloads"].append(cell["name"])
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp

"""A tiny copy of the hybrid expert configuration for the CPU tests and the
rehearsal of its cell, in the manner of ``tiny.py``: the same keys as
``configs/lfm2-8b-a1b-train-1chip.json`` at widths a test run can hold, added
to a copy of the benchmark as NEW files and entries only."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
TWIN = "train-moe-conv-1chip"  # the committed cell whose metrics a tiny one reports

PUBLISHED_LAYER_TYPES = [
    "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention",
    "conv", "conv", "conv", "full_attention", "conv", "conv", "conv",
    "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv",
    "full_attention", "conv", "conv"]


def config(layer_types=("conv", "full_attention", "conv", "conv", "conv"),
           num_dense_layers=1, num_experts=2, expert_offset=2, router_width=8,
           **over) -> dict:
    """The cut's shape (one dense layer, the first whole period; a quarter of
    the router's experts held) at tiny widths; ``over`` replaces any key."""
    with open(os.path.join(BENCH, "configs", "lfm2-8b-a1b-train-1chip.json")) as f:
        real = json.load(f)
    cfg = {
        "name": "tiny-hybrid", "source": "benchmark/tests/tiny_hybrid.py",
        "vocab_size": 512, "hidden_size": 64, "intermediate_size": 160,
        "moe_intermediate_size": 48, "num_hidden_layers": len(layer_types),
        "layer_types": list(layer_types), "num_dense_layers": num_dense_layers,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_experts": num_experts, "num_experts_per_tok": 4,
        "router_width": router_width, "expert_offset": expert_offset,
        "router_kind": "sigmoid", "norm_topk_prob": True, "use_expert_bias": True,
        "routed_scaling_factor": 1, "conv_L_cache": 3, "conv_bias": False,
        "qk_norm": True, "max_position_embeddings": 128, "norm_eps": 1e-5,
        "rope_theta": 1000000, "tie_word_embeddings": True,
        "reduced": [], "assumed": {},
        # what names the modules and the program's fields is the real file's
        "reference": real["reference"], "weights": real["weights"],
        "program_fields": real["program_fields"],
    }
    cfg.update(over)
    return cfg


OPT = {"lr": 3e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 1e-4,
       "max_grad_norm": 1.0}


def train_cell(name="tiny-hybrid-train") -> dict:
    return {
        "name": name, "kind": "train", "configuration": "tiny-hybrid",
        "chips": 1, "mixed_precision": "no", "compute_dtype": "float32",
        "remat": "dots_ragged", "rows_per_chip": 2, "checked_steps": 3,
        "optimizer": OPT,
        "traffic": {"rows": 64, "seq_len": 64, "zipf_a": 1.1, "bigram_p": 0.5},
        "reference_rows_per_block": 1,
        "limits": {"loss_gap": [1e-3, 1e-3, 1e-3],
                   "first_grad_worst_leaf_gap": 1e-3,
                   "param_change_worst_leaf_gap": 1e-3},
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
    with open(os.path.join(b, "configs", "tiny-hybrid.json"), "w") as f:
        json.dump(cfg or config(), f)
    with open(os.path.join(b, "workloads", f"{cell['name']}.json"), "w") as f:
        json.dump(cell, f)
    bench["configs"].append({
        "name": "tiny-hybrid", "source": "benchmark/tests/tiny_hybrid.py",
        "file": "benchmark/configs/tiny-hybrid.json", "reduced": [],
        "why": "CPU rehearsal"})
    bench["workloads"].append({
        "name": cell["name"], "config": "tiny-hybrid", "traffic": cell["name"],
        "chips": 1, "why": "rehearsal"})
    for group in ("end_to_end", "per_layer"):
        for metric in bench[group]:
            if TWIN in metric.get("workloads", ()):
                metric["workloads"].append(cell["name"])
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp

"""A tiny copy of the latent-attention / grouped-sparse-expert configuration
and its cell for the CPU tests and the rehearsal, in the manner of
``tiny_qwen3_next.py``: the same keys as
``configs/deepseek-v3-serve-1chip.json`` at widths a test run can hold that
keep every ratio (hidden 64; 4 heads of 16 + 8 over a latent of 24 beside 8
rotated, values 16 wide, queries through 32; 1 dense then 2 expert layers; 4 of
16 experts held — half of group 0 of 4 groups of which 2 are kept —, 3 a
token; YaRN at factor 4 over an original context of 32), added to a copy of
the benchmark as NEW files and entries only."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
REAL = "deepseek-v3-serve-1chip"
TWIN = "serve-mla-moe-longctx-sat"  # the committed cell whose metrics a tiny one reports


def real() -> dict:
    with open(os.path.join(BENCH, "configs", f"{REAL}.json")) as f:
        return json.load(f)


def config(layers: int = 3, dense: int = 1, n_routed_experts: int = 4,
           expert_offset: int = 0, router_width: int = 16, **over) -> dict:
    """The cut's shape at tiny widths; ``over`` replaces any key."""
    cfg = dict(real())
    cfg.update({
        "name": "tiny-deepseek-v3", "source": "benchmark/tests/tiny_deepseek_v3.py",
        "vocab_size": 96, "hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 24, "shared_expert_intermediate_size": 24,
        "num_hidden_layers": layers,
        "first_k_dense_replace": dense, "num_attention_heads": 4,
        "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 24,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "n_routed_experts": n_routed_experts, "num_experts_per_tok": 3,
        "n_group": 4, "topk_group": 2, "router_width": router_width,
        "expert_offset": expert_offset, "max_position_embeddings": 256,
        "rope_scaling": {"type": "yarn", "factor": 4.0, "beta_fast": 32,
                         "beta_slow": 1, "mscale": 1.0, "mscale_all_dim": 1.0,
                         "original_max_position_embeddings": 32},
        "reduced": [], "assumed": {},
    })
    cfg.update(over)
    return cfg


def serve_cell(name="tiny-mla-moe-sat", dtype="float32") -> dict:
    """Prompts in prefill buckets of 8-128; four slots seated; blocks of 8."""
    return {
        "name": name, "kind": "serve_hybrid", "configuration": "tiny-deepseek-v3",
        "chips": 1, "weight_dtype": dtype,
        "engine": {"max_slots": 4, "block_size": 8, "max_seq_len": 256},
        "traffic": {"rate_per_s": 6.0, "preseat": 4, "arrangement": 1,
                    "prompt": {"median": 40, "sigma": 0.7, "min": 6, "max": 128},
                    "output": {"median": 16, "sigma": 0.5, "min": 6, "max": 40}},
        "drain_limit_s": 90.0,
        # every finished request: a fault in one of them has to be seen
        "reference_sample": 64, "reference_rows_per_block": 1,
        # float32 on the CPU serves the reference's own best token but for a
        # near-tied expert choice: sound reads 0 to 1e-5
        "limits": {"served_token_mean_logit_gap": 1e-4,
                   "worst_request_mean_logit_gap": 1e-3, "widest_logit_gap": 1e-2},
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
    with open(os.path.join(b, "configs", "tiny-deepseek-v3.json"), "w") as f:
        json.dump(cfg or config(), f)
    with open(os.path.join(b, "workloads", f"{cell['name']}.json"), "w") as f:
        json.dump(cell, f)
    bench["configs"].append({
        "name": "tiny-deepseek-v3", "source": "benchmark/tests/tiny_deepseek_v3.py",
        "file": "benchmark/configs/tiny-deepseek-v3.json", "reduced": [],
        "why": "CPU rehearsal"})
    bench["workloads"].append({
        "name": cell["name"], "config": "tiny-deepseek-v3", "traffic": cell["name"],
        "chips": 1, "why": "rehearsal"})
    for group in ("end_to_end", "per_layer"):
        for metric in bench[group]:
            if TWIN in metric.get("workloads", ()):
                metric["workloads"].append(cell["name"])
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp

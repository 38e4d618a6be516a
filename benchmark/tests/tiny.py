"""A tiny copy of the benchmark for the CPU rehearsals: the committed tree
plus, added as NEW files and entries only, one tiny configuration, one cell of
each kind and one per-layer metric — which is also how a later PR adds them.
"""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

TINY_CONFIG = {
    "name": "tiny-dense", "source": "benchmark/tests/tiny.py",
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 160,
    "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "max_position_embeddings": 128, "rms_norm_eps": 1e-5,
    "rope_theta": 10000.0, "sliding_window": 48, "tie_word_embeddings": False,
    "reduced": [], "assumed": {}, "reference": "harness.reference",
    "weights": "harness.weights",
    "program_fields": {
        "vocab_size": "vocab_size", "hidden_size": "hidden_size",
        "intermediate_size": "intermediate_size", "num_layers": "num_hidden_layers",
        "num_heads": "num_attention_heads", "num_kv_heads": "num_key_value_heads",
        "head_dim": "head_dim", "sliding_window": "sliding_window",
        "rope_theta": "rope_theta", "rms_norm_eps": "rms_norm_eps",
        "tie_embeddings": "tie_word_embeddings"},
}
OPT = {"lr": 3e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 1e-4,
       "max_grad_norm": 1.0}


def train_cell(name="tiny-train", chips=1, compute="float32", precision="no"):
    return {
        "name": name, "kind": "train", "configuration": "tiny-dense",
        "chips": chips, "mixed_precision": precision, "compute_dtype": compute,
        "remat": None, "rows_per_chip": 2, "checked_steps": 3, "optimizer": OPT,
        "traffic": {"rows": 64, "seq_len": 64, "zipf_a": 1.1, "bigram_p": 0.5},
        "reference_rows_per_block": 1,
        "limits": {"loss_gap": [1e-3, 1e-3, 1e-3], "first_grad_worst_leaf_gap": 1e-3,
                   "param_change_worst_leaf_gap": 1e-3},
        "trace_seconds": 0.5, "why": "CPU rehearsal",
    }


def serve_cell(name="tiny-serve", preseat=0, dtype="float32"):
    mix = {"rate_per_s": 12.0,
           "prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 60},
           "output": {"median": 8, "sigma": 0.4, "min": 4, "max": 12}}
    if preseat:
        mix["preseat"] = preseat
    return {
        "name": name, "kind": "serve", "configuration": "tiny-dense", "chips": 1,
        "weight_dtype": dtype,
        "engine": {"max_slots": 4, "block_size": 8, "max_seq_len": 128},
        "traffic": mix, "drain_limit_s": 30.0,
        # every finished request: a fault in one of them has to be seen
        "reference_sample": 64, "reference_rows_per_block": 8,
        # float32 on the CPU serves the reference's own best token: sound reads 0
        "limits": {"served_token_mean_logit_gap": 1e-5,
                   "worst_request_mean_logit_gap": 2e-4, "widest_logit_gap": 1.5e-3},
        "trace_seconds": 0.5, "why": "CPU rehearsal",
    }


def make_root(tmp: str, cells: list[dict], extra_metrics: list[dict] = ()) -> str:
    """Copy the benchmark into ``tmp`` and ADD the tiny files and entries;
    no existing file under the copy is edited except BENCHMARK.json, which
    gains entries. Returns the root to pass to ``load_cell``."""
    shutil.copytree(BENCH, os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    b = os.path.join(tmp, "benchmark")
    with open(os.path.join(b, "configs", "tiny-dense.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    bench["configs"].append({
        "name": "tiny-dense", "source": "benchmark/tests/tiny.py",
        "file": "benchmark/configs/tiny-dense.json", "reduced": [],
        "why": "CPU rehearsal"})
    for cell in cells:
        with open(os.path.join(b, "workloads", f"{cell['name']}.json"), "w") as f:
            json.dump(cell, f)
        bench["workloads"].append({
            "name": cell["name"], "config": "tiny-dense",
            "traffic": cell["name"], "chips": cell["chips"], "why": "rehearsal"})
        for group in ("end_to_end", "per_layer"):
            for metric in bench[group]:
                if "workloads" not in metric:
                    continue
                # a tiny cell reports what the committed cell of its kind does
                twin = {"train": "train-dense-1chip",
                        "serve": ("serve-decode-sat" if cell["traffic"].get("preseat")
                                  else "serve-prefill-knee")}[cell["kind"]]
                if twin in metric["workloads"]:
                    metric["workloads"].append(cell["name"])
    for metric in extra_metrics:
        entry = {k: metric[k] for k in
                 ("name", "unit", "better", "source", "layer", "moves", "workloads")}
        bench["per_layer"].append(entry)
        with open(os.path.join(b, "metrics", f"{metric['name']}.json"), "w") as f:
            json.dump(metric, f)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp

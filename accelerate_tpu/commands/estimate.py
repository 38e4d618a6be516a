"""`accelerate-tpu estimate-memory` — model memory calculator.

Parity: reference ``commands/estimate.py`` (309 LoC): meta-device model from
a Hub config (``create_empty_model`` :63), training usage ≈ Adam 4x param
bytes (``estimate_training_usage`` :215), ascii table (:139). Here the
abstract init is ``jax.eval_shape`` (truly zero-alloc) and the training
column reflects this framework's actual layout: fp32 master + 2 AdamW
moments + bf16 compute cast (+ optional fp32 accum buffer).
"""

from __future__ import annotations

import argparse
import json
from typing import Optional

import numpy as np

# jax is imported inside the functions: the CLI module that also hosts
# `launch` (a parent of chip children) imports this parser


def _human(n: float) -> str:
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024:
            return f"{n:.2f} {unit}"
        n /= 1024
    return f"{n:.2f} PB"


def estimate_activation_bytes(
    cfg, batch_size: int, seq_len: int, remat: Optional[str], dtype: str
) -> dict:
    """Activation memory for one train step — the term users get wrong when
    budgeting HBM (the reference documents params-only as its assumption;
    here activations are first-class because remat changes them 10x).

    Model: per layer, the saved residuals depend on the remat policy —
    "full" keeps only each layer's input; "dots" (what the dense train cell
    runs) keeps matmul outputs (qkv/o projections, gate/up/down); None
    keeps those plus the elementwise intermediates. The lm-head logits (+fp32 softmax) are
    counted separately: at large vocab they dominate and remat cannot
    remove them.
    """
    import jax.numpy as jnp

    h, f, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    qkv = (cfg.num_heads + 2 * cfg.num_kv_heads) * cfg.head_dim
    itemsize = jnp.dtype(dtype).itemsize
    if remat == "full":
        per_layer = h
    elif remat == "dots":
        per_layer = 2 * h + qkv + 2 * f
    else:
        per_layer = 2 * h + qkv + 3 * f + 2 * h
    tokens = batch_size * seq_len
    layer_bytes = tokens * per_layer * L * itemsize
    # logits in compute dtype + the fp32 softmax/loss intermediates
    logits_bytes = tokens * cfg.vocab_size * (itemsize + 4)
    return {
        "activation_bytes": int(layer_bytes),
        "logits_bytes": int(logits_bytes),
    }


def estimate_from_config(preset_or_json: str, dtype: str = "bfloat16",
                         grad_accum: bool = False, batch_size: int = 8,
                         seq_len: int = 2048,
                         remat: Optional[str] = "dots") -> dict:
    import jax
    import jax.numpy as jnp

    from ..models import TransformerConfig, causal_model_for

    presets = {
        "tiny": TransformerConfig.tiny,
        "gpt2": TransformerConfig.gpt2,
        "llama3-8b": TransformerConfig.llama3_8b,
        "llama3-70b": TransformerConfig.llama3_70b,
        "qwen2-7b": TransformerConfig.qwen2_7b,
        "mixtral-8x7b": TransformerConfig.mixtral_8x7b,
    }
    if preset_or_json in presets:
        cfg = presets[preset_or_json]()
    elif preset_or_json.endswith(".json"):
        with open(preset_or_json) as f:
            raw = json.load(f)
        # accept HF transformers config field names too
        mapped = {
            "vocab_size": raw.get("vocab_size", 32000),
            "hidden_size": raw.get("hidden_size", 4096),
            "intermediate_size": raw.get("intermediate_size", 11008),
            "num_layers": raw.get("num_hidden_layers", raw.get("num_layers", 32)),
            "num_heads": raw.get("num_attention_heads", raw.get("num_heads", 32)),
            "num_kv_heads": raw.get("num_key_value_heads"),
            "max_seq_len": raw.get("max_position_embeddings", 4096),
        }
        cfg = TransformerConfig(**mapped)
    else:
        raise ValueError(
            f"unknown preset {preset_or_json!r}; options: {sorted(presets)} "
            "or a config.json path"
        )
    # arch-dispatched (gpt2 preset -> GPT2LM): the byte estimate must
    # count the parameters of the model that will actually run
    model = causal_model_for(cfg)
    abstract = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0),
    )
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(abstract))
    itemsize = jnp.dtype(dtype).itemsize
    inference = n_params * itemsize
    # training: fp32 master + 2 AdamW moments (fp32) + compute-dtype cast
    train = n_params * (4 + 8 + itemsize + (4 if grad_accum else 0))
    acts = estimate_activation_bytes(cfg, batch_size, seq_len, remat, dtype)
    return {
        "params": n_params,
        "largest_layer": max(
            int(np.prod(l.shape)) * itemsize for l in jax.tree.leaves(abstract)
        ),
        "inference_bytes": inference,
        "training_bytes": train,
        "training_total_bytes": (
            train + acts["activation_bytes"] + acts["logits_bytes"]
        ),
        **acts,
        "batch_size": batch_size,
        "seq_len": seq_len,
        "remat": remat,
        "dtype": dtype,
    }


def estimate_command(args) -> None:
    for dtype in args.dtypes:
        info = estimate_from_config(
            args.model_name, dtype, args.grad_accum,
            batch_size=args.batch_size, seq_len=args.seq_len,
            remat=None if args.remat == "none" else args.remat,
        )
        print(
            f"{args.model_name} [{dtype}]: {info['params'] / 1e9:.2f}B params | "
            f"inference {_human(info['inference_bytes'])} | "
            f"training state (AdamW) {_human(info['training_bytes'])} | "
            f"activations@B{args.batch_size}xS{args.seq_len} "
            f"{_human(info['activation_bytes'] + info['logits_bytes'])} "
            f"(remat={info['remat']}) | "
            f"training total {_human(info['training_total_bytes'])} | "
            f"largest layer {_human(info['largest_layer'])}"
        )


def estimate_command_parser(subparsers=None) -> argparse.ArgumentParser:
    if subparsers is not None:
        parser = subparsers.add_parser(
            "estimate-memory", help="Estimate model memory usage"
        )
    else:
        parser = argparse.ArgumentParser("accelerate-tpu estimate-memory")
    parser.add_argument("model_name", help="Preset name or config.json path")
    parser.add_argument("--dtypes", nargs="+", default=["bfloat16", "float32"])
    parser.add_argument("--grad_accum", action="store_true")
    parser.add_argument("--batch_size", type=int, default=8)
    parser.add_argument("--seq_len", type=int, default=2048)
    parser.add_argument("--remat", choices=["none", "dots", "full"],
                        default="dots",
                        help="Remat policy assumed for the activation term")
    if subparsers is not None:
        parser.set_defaults(func=estimate_command)
    return parser

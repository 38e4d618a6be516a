"""Needed work of the hybrid expert stack (``lfm2-*`` configurations), by
``flops_bytes.py``'s one rule: what the ALGORITHM needs from the
configuration's own shapes — every weight once, every live token once,
causal attention at half the square, one EXPECTED local choice a token
(``num_experts_per_tok`` x held / router width), recompute uncounted — never
what the program happens to move. ``(cfg, run record) -> {"flops", "bytes"}``,
named by the metric files as ``harness.lfm2_work:<function>``.
"""

from __future__ import annotations

from . import flops_bytes


def _per_token(cfg: dict) -> dict:
    """Matmul parameters a token meets, by part, summed over the layers."""
    h = cfg["hidden_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    conv = sum(t == "conv" for t in cfg["layer_types"])
    attn = len(cfg["layer_types"]) - conv
    dense = cfg["num_dense_layers"]
    moe = len(cfg["layer_types"]) - dense
    local = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["router_width"]
    return {
        "conv": conv * (h * 3 * h + h * h),
        "attn_proj": attn * (h * q + 2 * h * kv + q * h),
        "dense_ff": dense * 3 * h * cfg["intermediate_size"],
        "experts": moe * local * 3 * h * cfg["moe_intermediate_size"],
        "router": moe * h * cfg["router_width"],
        "head": h * cfg["vocab_size"],
        "attn_layers": attn, "conv_layers": conv, "moe_layers": moe,
    }


def params_held(cfg: dict) -> int:
    """Every parameter this chip holds (the state AdamW passes over)."""
    h, p = cfg["hidden_size"], _per_token(cfg)
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    experts = (p["moe_layers"] * cfg["num_experts"] * 3 * h
               * cfg["moe_intermediate_size"])
    small = (len(cfg["layer_types"]) * 2 * h + h  # norms
             + p["attn_layers"] * 2 * d + p["conv_layers"] * cfg["conv_L_cache"] * h
             + p["moe_layers"] * cfg["router_width"])
    head = 0 if cfg.get("tie_word_embeddings") else h * cfg["vocab_size"]
    return int(p["conv"] + p["attn_proj"] + p["dense_ff"] + experts + p["router"]
               + h * cfg["vocab_size"] + head + small)


def train_step_work(cfg: dict, rec: dict) -> dict:
    """One optimizer step on one chip: forward + backward (2 + 4 FLOPs per
    matmul parameter a token meets, causal attention's two matmuls forward
    and four backward, the convolution's taps), and the bytes of AdamW's
    one pass over the state: four fp32 trees read (parameters, gradient, two
    moments), three written."""
    p = _per_token(cfg)
    tokens = rec["tokens_per_step_per_chip"]
    h = cfg["hidden_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    matmul = sum(p[k] for k in ("conv", "attn_proj", "dense_ff", "experts",
                                "router", "head"))
    attn_fwd = (2 * 2 * cfg["num_attention_heads"] * d
                * flops_bytes.attended_keys(rec["seq_len"], None))
    taps_fwd = 2 * cfg["conv_L_cache"] * h + 2 * h  # the filter and two gates
    per_token = (6.0 * matmul + 3.0 * p["attn_layers"] * attn_fwd
                 + 3.0 * p["conv_layers"] * taps_fwd)
    return {"flops": per_token * tokens, "bytes": 7 * 4.0 * params_held(cfg)}


def moe_experts_work(cfg: dict, rec: dict) -> dict:
    """The grouped matmuls over the experts held, one step: forward, and
    backward to the rows and to the weights, of the EXPECTED live rows (one
    local choice a token at the published routing); the held experts'
    bf16 weights read in each of the three passes, the rows' activations
    (h in, 2 x f between, h out) once forward and once backward."""
    p = _per_token(cfg)
    tokens = rec["tokens_per_step_per_chip"]
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = tokens * cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["router_width"]
    weights = p["moe_layers"] * cfg["num_experts"] * 3 * h * f * 2
    acts = p["moe_layers"] * rows * (2 * h + 2 * f) * 2
    return {"flops": 6.0 * p["experts"] * tokens, "bytes": 3.0 * weights + 2.0 * acts}

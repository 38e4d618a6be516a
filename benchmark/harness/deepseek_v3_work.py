"""Needed work of the ``deepseek-v3-*`` configurations, by ``flops_bytes.py``'s
one rule: what the ALGORITHM needs from the configuration's PUBLISHED shapes —
every weight a step TOUCHES once, every live latent row once at ``kv_lora_rank
+ qk_rope_head_dim`` values whatever lanes the pool pads them to, a value
``v_head_dim`` wide whatever is multiplied — never what the program happens to
move. ``(cfg, run record) -> {"flops", "bytes"}``, named by the metric files
as ``harness.deepseek_v3_work:<function>``.

A decode step does not touch every expert: which it touches is the routing's,
so the count comes from what the program wrote into the trace (the
``experts_touched``, ``rows`` and ``seated`` stats of its
``atpu:serve.decode.fetch`` span, handed over by the ``roofline_traced``
reader as ``traced_<stat>``), never from an expectation. A prefill's tokens
are its span's ``tokens`` likewise.
"""

from __future__ import annotations

BF16, F32 = 2, 4


def parts(cfg: dict) -> dict:
    """Parameters by part, and the count of each kind of layer."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rot, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    dense = cfg["first_k_dense_replace"]
    return {
        "dense_layers": dense, "moe_layers": cfg["num_hidden_layers"] - dense,
        # q_a, q_b, kv_a, kv_b, o
        "mla": (h * ql + ql * heads * (nope + rot) + h * (kvl + rot)
                + kvl * heads * (nope + dv) + heads * dv * h),
        "mla_norms": ql + kvl,
        "dense_mlp": 3 * h * cfg["intermediate_size"],
        "router": h * cfg["router_width"],
        "router_bias": cfg["router_width"],
        "shared": 3 * h * cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        "expert": 3 * h * cfg["moe_intermediate_size"],
        "head": h * cfg["vocab_size"],
    }


def params_held(cfg: dict) -> int:
    """Every parameter this chip holds."""
    p, h = parts(cfg), cfg["hidden_size"]
    layers = p["dense_layers"] + p["moe_layers"]
    moe = (p["router"] + p["router_bias"] + p["shared"]
           + cfg["n_routed_experts"] * p["expert"])
    return int(layers * (p["mla"] + p["mla_norms"] + 2 * h)
               + p["dense_layers"] * p["dense_mlp"] + p["moe_layers"] * moe
               + 2 * p["head"] + h)


def latent_row_bytes(cfg: dict) -> int:
    """One position's latent row over the held layers, as published: ``[c_kv
    | k_rope]`` in bf16, nothing a head."""
    return (cfg["num_hidden_layers"]
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * BF16)


def _absorbed_flops_per_row(cfg: dict) -> float:
    """One live position of one layer under the absorbed form: every head's
    score over the latent row (``kv_lora_rank + qk_rope_head_dim``) and its
    weighted sum of the row's ``kv_lora_rank`` values."""
    return 2.0 * cfg["num_attention_heads"] * (
        2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def _local_choices(cfg: dict, seated: float) -> float:
    """Routed rows a layer that land on held experts, at the even share."""
    return (seated * cfg["num_experts_per_tok"]
            * cfg["n_routed_experts"] / cfg["router_width"])


def decode_step_work(cfg: dict, rec: dict) -> dict:
    """One decode step over ``traced_seated`` requests holding ``traced_rows``
    latent rows between them, whose routing touched
    ``traced_experts_touched`` held experts (summed over the expert layers):
    the touched experts, MLA's projections, the dense MLP, the routers
    (float32), the shared experts and the head once; every live latent row
    once; the absorbed attention's FLOPs. The embedding is a lookup of
    ``seated`` rows."""
    p = parts(cfg)
    layers = p["dense_layers"] + p["moe_layers"]
    seated, rows = rec["traced_seated"], rec["traced_rows"]
    touched = rec["traced_experts_touched"]
    weights = (BF16 * (layers * p["mla"] + p["dense_layers"] * p["dense_mlp"]
                       + p["moe_layers"] * p["shared"] + p["head"]
                       + touched * p["expert"])
               + F32 * p["moe_layers"] * p["router"])
    met = (layers * p["mla"] + p["dense_layers"] * p["dense_mlp"]
           + p["moe_layers"] * (p["router"] + p["shared"]) + p["head"])
    flops = (2.0 * met * seated
             + 2.0 * p["moe_layers"] * _local_choices(cfg, seated) * p["expert"]
             + rows * layers * _absorbed_flops_per_row(cfg))
    return {"flops": flops,
            "bytes": float(weights + rows * latent_row_bytes(cfg))}


def mla_decode_work(cfg: dict, rec: dict) -> dict:
    """The latent decode kernel of a decode step, every layer: each live
    latent row once (1,152 B), every head's absorbed query in (``kv_lora_rank
    + qk_rope_head_dim``) and latent-space output out (``kv_lora_rank``) a
    seated slot."""
    layers = cfg["num_hidden_layers"]
    heads, kvl, rot = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
                       cfg["qk_rope_head_dim"])
    rows, seated = rec["traced_rows"], rec["traced_seated"]
    in_out = seated * layers * heads * (2 * kvl + rot) * BF16
    return {"flops": rows * layers * _absorbed_flops_per_row(cfg),
            "bytes": float(rows * latent_row_bytes(cfg) + in_out)}


def mla_prefill_work(cfg: dict, rec: dict) -> dict:
    """The expanded attention of one prefill of ``traced_tokens`` real
    positions, every layer: the causal half-square at the score's width
    (``qk_nope_head_dim + qk_rope_head_dim``) and the value's
    (``v_head_dim``), every head; q, k, v read and the result written once a
    position and head."""
    heads = cfg["num_attention_heads"]
    score = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv, layers = cfg["v_head_dim"], cfg["num_hidden_layers"]
    t = rec["traced_tokens"]
    flops = 2.0 * layers * heads * (t * t / 2.0) * (score + dv)
    return {"flops": flops,
            "bytes": float(layers * t * heads * (2 * score + 2 * dv) * BF16)}


def moe_experts_decode_work(cfg: dict, rec: dict) -> dict:
    """The grouped matmuls of a decode step, every expert layer: each
    TOUCHED expert's three matrices read once (bf16); the rows' activations
    beside them (h in, 2 f between, h out a routed row) are counted."""
    p = parts(cfg)
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    local = _local_choices(cfg, rec["traced_seated"])
    acts = p["moe_layers"] * local * (2 * h + 3 * f) * BF16
    return {"flops": 2.0 * p["moe_layers"] * local * p["expert"],
            "bytes": float(rec["traced_experts_touched"] * p["expert"] * BF16 + acts)}

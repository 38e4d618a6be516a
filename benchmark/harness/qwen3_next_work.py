"""Needed work of the ``qwen3-next-*`` configurations, by ``flops_bytes.py``'s
one rule: what the ALGORITHM needs from the configuration's own shapes — every
weight a step TOUCHES once, the recurrent state of every seated slot read and
written once, every live K/V row once — never what the program happens to
move. ``(cfg, run record) -> {"flops", "bytes"}``, named by the metric files
as ``harness.qwen3_next_work:<function>``.

A decode step does not touch every expert: which it touches is the routing's,
so the count comes from what the program wrote into the trace (the
``experts_touched``, ``rows`` and ``seated`` stats of its
``atpu:serve.decode.fetch`` span, handed over by the ``roofline_traced`` /
``roofline_scope_traced`` readers as ``traced_<stat>``), never from an
expectation. A prefill's tokens are its span's ``tokens`` likewise.
"""

from __future__ import annotations

from .qwen3_next_weights import gdn_dims

BF16, F32 = 2, 4


def _parts(cfg: dict) -> dict:
    """Parameters by part; ``*_layers`` the count of each kind of layer."""
    h = cfg["hidden_size"]
    _, hv, _, dv, conv = gdn_dims(cfg)
    d = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    gdn = cfg["layer_types"].count("linear_attention")
    attn = cfg["layer_types"].count("full_attention")
    return {
        "gdn_layers": gdn, "attn_layers": attn, "moe_layers": gdn + attn,
        # in_proj_qkvz, in_proj_ba, out_proj; the taps beside them
        "gdn_proj": h * (conv + hv * dv) + h * 2 * hv + hv * dv * h,
        "gdn_small": cfg["linear_conv_kernel_dim"] * conv + 2 * hv + dv,
        "attn_proj": h * 2 * q + 2 * h * kv + q * h,
        "attn_small": 2 * d,
        "router": h * cfg["router_width"],
        "shared": 3 * h * cfg["shared_expert_intermediate_size"] + h,
        "expert": 3 * h * cfg["moe_intermediate_size"],
        "head": h * cfg["vocab_size"],
    }


def params_held(cfg: dict) -> int:
    """Every parameter this chip holds."""
    p, h = _parts(cfg), cfg["hidden_size"]
    layer_norms = 2 * h  # the mixer's and the experts'
    gdn = p["gdn_proj"] + p["gdn_small"]
    attn = p["attn_proj"] + p["attn_small"]
    ff = p["router"] + p["shared"] + cfg["num_experts"] * p["expert"]
    return int(p["gdn_layers"] * (gdn + ff + layer_norms)
               + p["attn_layers"] * (attn + ff + layer_norms)
               + 2 * p["head"] + h)


def state_bytes_per_slot(cfg: dict) -> int:
    """What a seat holds beside its K/V rows: each DeltaNet layer's float32
    state a value head and the convolution's last taps (compute dtype)."""
    _, hv, dk, dv, conv = gdn_dims(cfg)
    return _parts(cfg)["gdn_layers"] * (
        hv * dk * dv * F32 + (cfg["linear_conv_kernel_dim"] - 1) * conv * BF16)


def kv_row_bytes(cfg: dict) -> int:
    """One position's K and V over the attention layers."""
    return (_parts(cfg)["attn_layers"] * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * BF16)


def _gdn_step_flops(cfg: dict) -> float:
    """One position of one DeltaNet layer's recurrence: decay, read, write
    and query over a Dk x Dv state a value head."""
    _, hv, dk, dv, _ = gdn_dims(cfg)
    return hv * dk * dv * (1 + 2 + 2 + 2.0)


def decode_step_work(cfg: dict, rec: dict) -> dict:
    """One decode step over ``traced_seated`` requests holding ``traced_rows``
    K/V rows between them, whose routing touched ``traced_experts_touched``
    held experts (summed over the expert layers): the touched experts, the
    mixers, the routers (float32), the shared experts and the head once; the
    seated slots' recurrent state read and written once; every live K/V row
    once. The embedding is a lookup of ``seated`` rows."""
    p = _parts(cfg)
    seated, rows = rec["traced_seated"], rec["traced_rows"]
    touched = rec["traced_experts_touched"]
    weights = (BF16 * (p["gdn_layers"] * p["gdn_proj"] + p["attn_layers"] * p["attn_proj"]
                       + p["moe_layers"] * p["shared"] + p["head"]
                       + touched * p["expert"])
               + F32 * p["moe_layers"] * p["router"])
    state = 2.0 * seated * state_bytes_per_slot(cfg)
    met = (p["gdn_layers"] * p["gdn_proj"] + p["attn_layers"] * p["attn_proj"]
           + p["moe_layers"] * (p["router"] + p["shared"]
                                + cfg["num_experts_per_tok"] * p["expert"]
                                * cfg["num_experts"] / cfg["router_width"])
           + p["head"])
    attn = 2 * 2.0 * cfg["num_attention_heads"] * cfg["head_dim"] * rows * p["attn_layers"]
    flops = 2.0 * met * seated + attn + seated * p["gdn_layers"] * _gdn_step_flops(cfg)
    return {"flops": flops, "bytes": float(weights + state + rows * kv_row_bytes(cfg))}


def moe_experts_decode_work(cfg: dict, rec: dict) -> dict:
    """The grouped matmuls of a decode step, every expert layer: each
    TOUCHED expert's three matrices read once (bf16); the rows' activations
    beside them are three orders smaller and are counted (h in, 2 f between,
    h out a routed row)."""
    p = _parts(cfg)
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    local = (rec["traced_seated"] * cfg["num_experts_per_tok"]
             * cfg["num_experts"] / cfg["router_width"])  # routed rows a layer
    acts = p["moe_layers"] * local * (2 * h + 3 * f) * BF16
    return {"flops": 2.0 * p["moe_layers"] * local * p["expert"],
            "bytes": float(rec["traced_experts_touched"] * p["expert"] * BF16 + acts)}


def gdn_step_work(cfg: dict, rec: dict) -> dict:
    """The one-position recurrence of a decode step, every DeltaNet layer:
    the seated slots' float32 state read and written once; q, k, v, g, beta
    in and o out are four orders smaller and are counted."""
    _, hv, dk, dv, conv = gdn_dims(cfg)
    layers, seated = _parts(cfg)["gdn_layers"], rec["traced_seated"]
    state = 2.0 * seated * layers * hv * dk * dv * F32
    acts = seated * layers * (conv * BF16 + hv * (2 + dv) * F32)
    return {"flops": seated * layers * _gdn_step_flops(cfg),
            "bytes": float(state + acts)}


def gdn_scan_work(cfg: dict, rec: dict) -> dict:
    """The chunked recurrence of one prefill of ``traced_tokens`` real
    positions, every DeltaNet layer (the projections, the convolution and the
    gated norm are NOT the scan's). A chunk of C positions and a value head:
    K K^T and Q K^T (C x C x Dk each), the unit-triangular solve over the two
    right sides (C x C x (Dv + Dk), half the square), W S_0 and Q S_0 (C x Dk
    x Dv each), scores times D (C x C x Dv) and the state's update (C x Dk x
    Dv): 2 FLOPs a multiply-add. Bytes: q, k (float32 after the
    normalisation), v, g, beta read and o written once a position, the
    float32 state read and written once a chunk."""
    _, hv, dk, dv, _ = gdn_dims(cfg)
    c = cfg.get("gdn_chunk", 64)
    tokens, layers = rec["traced_tokens"], _parts(cfg)["gdn_layers"]
    per_pos = 2.0 * hv * (2 * c * dk + c * (dv + dk) / 2 + 3 * dk * dv + c * dv)
    acts = tokens * hv * (2 * dk + 2 * dv + 2) * F32
    states = tokens / c * hv * dk * dv * F32 * 2
    return {"flops": layers * per_pos * tokens, "bytes": float(layers * (acts + states))}

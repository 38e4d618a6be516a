"""Needed work of the ``smallthinker-*`` configurations, by ``flops_bytes.py``'s
one rule: what the ALGORITHM needs from the configuration's published shapes —
every weight a step TOUCHES once, every K/V row a layer NEEDS once: a full
layer the row of every position its slots stand at, a window layer the last
``sliding_window_size`` of them — never what the program happens to move.
``(cfg, run record) -> {"flops", "bytes"}``, named by the metric files as
``harness.smallthinker_work:<function>``.

A decode step does not touch every expert: which it touches is the routing's,
so the count comes from what the program wrote into the trace (the
``experts_touched``, ``rows``, ``window_rows`` and ``seated`` stats of its
``atpu:serve.decode.fetch`` span, handed over by the ``roofline_traced``
reader as ``traced_<stat>``), never from an expectation.
"""

from __future__ import annotations

BF16, F32 = 2, 4


def parts(cfg: dict) -> dict:
    """Parameters by part, and the count of each kind of layer."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    window = sum(map(bool, cfg["sliding_window_layout"]))
    return {
        "window_layers": window,
        "full_layers": cfg["num_hidden_layers"] - window,
        "attn": h * q + 2 * h * kv + q * h,  # q, k, v, o
        "router": h * cfg["moe_num_primary_experts"],
        "expert": 3 * h * cfg["moe_ffn_hidden_size"],
        "head": h * cfg["vocab_size"],
    }


def params_held(cfg: dict) -> int:
    """Every parameter this chip holds: the layers whole (attention, router,
    every expert, two norms), embedding, head and the final norm."""
    p, h = parts(cfg), cfg["hidden_size"]
    layer = (p["attn"] + p["router"]
             + cfg["moe_num_primary_experts"] * p["expert"] + 2 * h)
    return int(cfg["num_hidden_layers"] * layer + 2 * p["head"] + h)


def kv_row_bytes(cfg: dict) -> int:
    """One position's K and V in ONE layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BF16


def cache_bytes_per_slot(cfg: dict, positions: int) -> dict:
    """What a slot of ``positions`` positions holds: every row in the full
    layers, at most the window's in the window layers; and what it would hold
    if every layer kept every row."""
    p, row = parts(cfg), kv_row_bytes(cfg)
    ring = min(positions, cfg["sliding_window_size"])
    return {"full": p["full_layers"] * positions * row,
            "window": p["window_layers"] * ring * row,
            "uniform": cfg["num_hidden_layers"] * positions * row}


def _needed_rows(cfg: dict, rec: dict) -> float:
    """K/V rows a decode step needs over the layers: ``traced_rows`` in each
    full layer, ``traced_window_rows`` in each window layer."""
    p = parts(cfg)
    return (p["full_layers"] * rec["traced_rows"]
            + p["window_layers"] * rec["traced_window_rows"])


def decode_step_work(cfg: dict, rec: dict) -> dict:
    """One decode step over ``traced_seated`` requests whose routing touched
    ``traced_experts_touched`` experts (summed over the layers): the touched
    experts, the attention projections, the routers (float32) and the head
    once; every needed K/V row once. The embedding is a lookup of ``seated``
    rows."""
    p, layers = parts(cfg), cfg["num_hidden_layers"]
    seated = rec["traced_seated"]
    weights = (BF16 * (layers * p["attn"] + p["head"]
                       + rec["traced_experts_touched"] * p["expert"])
               + F32 * layers * p["router"])
    met = (layers * (p["attn"] + p["router"]
                     + cfg["moe_num_active_primary_experts"] * p["expert"])
           + p["head"])
    attn = 2 * 2.0 * cfg["num_attention_heads"] * cfg["head_dim"] * _needed_rows(cfg, rec)
    return {"flops": 2.0 * met * seated + attn,
            "bytes": float(weights + _needed_rows(cfg, rec) * kv_row_bytes(cfg))}


def moe_experts_decode_work(cfg: dict, rec: dict) -> dict:
    """The grouped matmuls of a decode step, every layer: each TOUCHED
    expert's three matrices read once (bf16); the rows' activations beside
    them are three orders smaller and are counted (h in, 2 f between, h out a
    routed row)."""
    p, layers = parts(cfg), cfg["num_hidden_layers"]
    h, f = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    routed = rec["traced_seated"] * cfg["moe_num_active_primary_experts"]
    acts = layers * routed * (2 * h + 3 * f) * BF16
    return {"flops": 2.0 * layers * routed * p["expert"],
            "bytes": float(rec["traced_experts_touched"] * p["expert"] * BF16 + acts)}

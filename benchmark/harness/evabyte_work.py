"""Needed work of the ``evabyte-*`` configurations, by ``flops_bytes.py``'s one
rule: what the ALGORITHM needs from the configuration's own shapes — every
weight once, every live cache ROW once — never what the program happens to
move. ``(cfg, run record) -> {"flops", "bytes"}``, named by the metric files
as ``harness.evabyte_work:<function>``.

A request at byte position n holds ``128 floor(n / 2048) + n mod 2048`` rows
(chunk summaries beside a window), not n: the rows come from what the program
wrote into the trace (the ``rows`` stat of its ``atpu:serve.decode.inputs``
span, handed over by the ``roofline_traced`` reader as ``traced_rows``), never
from ``mean_live_tokens``, which counts positions and would read over 100 %.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Parameters a served byte meets in a matrix multiplication: the four
    projections and the SwiGLU of every layer, and the FIRST prediction
    head's columns (the next byte's; the further heads serve nothing)."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    per_layer = h * q + 2 * h * kv + q * h + 3 * h * f
    return cfg["num_hidden_layers"] * per_layer + h * cfg["vocab_size"]


def row_bytes(cfg: dict, kv_bytes: int = 2) -> int:
    """One cache row (a position's K and V, or a chunk's summaries) over
    every layer."""
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"] * d * kv_bytes


def decode_step_work(cfg: dict, rec: dict) -> dict:
    """One decode step over ``traced_seated`` requests that hold
    ``traced_rows`` cache rows between them (the means over the traced
    steps): every matmul weight read once in bf16, every live row once; two
    matmuls a row and head in attention."""
    rows, seated = rec["traced_rows"], rec["traced_seated"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    attn = 2 * 2.0 * cfg["num_attention_heads"] * d * rows * cfg["num_hidden_layers"]
    return {"flops": 2.0 * matmul_params(cfg) * seated + attn,
            "bytes": float(2 * matmul_params(cfg) + rows * row_bytes(cfg))}

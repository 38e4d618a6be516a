"""Operations and bytes the ALGORITHM needs, from shapes alone.

One rule: these functions count needed work (every weight once, every live
token once, causal attention at half the square), never what the current
program happens to move or recompute. A share built on them therefore cannot
pass 100 %, and a later PR that removes waste shows as a higher share, not
as a stale count. ``cfg`` is a configuration file's dict of published keys.
"""

from __future__ import annotations


def _dims(cfg: dict):
    h = cfg["hidden_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    return (h, cfg["intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], d, cfg["vocab_size"],
            cfg["num_hidden_layers"])


def matmul_params(cfg: dict) -> int:
    """Parameters that sit in a matrix multiplication: the projections, the
    MLP and the head. The embedding is a lookup."""
    h, f, nh, nkv, d, v, layers = _dims(cfg)
    per_layer = h * nh * d + 2 * h * nkv * d + nh * d * h + 3 * h * f
    head = 0 if cfg.get("tie_word_embeddings") else h * v
    return layers * per_layer + (head or h * v)


def total_params(cfg: dict) -> int:
    h, f, nh, nkv, d, v, layers = _dims(cfg)
    embed = v * h
    head = 0 if cfg.get("tie_word_embeddings") else h * v
    return (layers * (h * nh * d + 2 * h * nkv * d + nh * d * h + 3 * h * f
                      + 2 * h) + embed + head + h)


def attended_keys(seq: int, window) -> float:
    """Mean number of keys a query of a causal sequence of ``seq`` attends,
    self included, under a sliding band of ``window``."""
    w = seq if not window else min(window, seq)
    # positions 0..w-1 see i+1 keys, the rest see w
    return (w * (w + 1) / 2 + (seq - w) * w) / seq


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward (2 + 4 FLOPs per matmul parameter) plus causal
    attention's two matmuls forward and four backward; recompute uncounted."""
    _, _, nh, _, d, _, layers = _dims(cfg)
    attn_fwd = 2 * 2 * nh * d * attended_keys(seq, cfg.get("sliding_window"))
    return 6.0 * matmul_params(cfg) + 3.0 * layers * attn_fwd


def flash_train_step(cfg: dict, batch: int, seq: int) -> dict:
    """Flash attention's needed work in one training step on one chip:
    forward (QK^T, PV) and backward (recomputed QK^T, dP, dQ, dK, dV) = 7
    causal matmuls of 2*S_eff*D FLOPs per query row and head; bytes are
    q, k, v, o once forward and q, k, v, o, do read plus dq, dk, dv written
    backward, in the compute type's 2 bytes."""
    _, _, nh, nkv, d, _, layers = _dims(cfg)
    keys = attended_keys(seq, cfg.get("sliding_window"))
    flops = 7 * 2.0 * batch * seq * nh * d * keys * layers
    q_bytes = batch * seq * nh * d * 2
    kv_bytes = batch * seq * nkv * d * 2
    fwd = 2 * q_bytes + 2 * kv_bytes
    bwd = 4 * q_bytes + 4 * kv_bytes
    return {"flops": flops, "bytes": float((fwd + bwd) * layers)}


def decode_step(cfg: dict, live_tokens: float, batch: float,
                weight_bytes: int = 2, kv_bytes: int = 2) -> dict:
    """One decode step over ``batch`` seated requests holding
    ``live_tokens`` of context between them: every matmul weight is read
    once, every live K and V row once."""
    _, _, nh, nkv, d, _, layers = _dims(cfg)
    w = matmul_params(cfg) * weight_bytes
    kv = live_tokens * layers * 2 * nkv * d * kv_bytes
    flops = 2.0 * matmul_params(cfg) * batch + 2 * 2.0 * nh * d * live_tokens * layers
    return {"flops": flops, "bytes": float(w + kv)}


def roofline_seconds(work: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_flops = work["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")


# Needed work as a roofline metric's file names it ("work":
# "harness.flops_bytes:<function>"): (cfg, run record) -> {"flops", "bytes"}.
# A new kind of needed work is a new function in a new module, named the
# same way; nothing here is edited.
def flash_train_step_work(cfg: dict, rec: dict) -> dict:
    return flash_train_step(cfg, rec["rows_per_chip"], rec["seq_len"])


def decode_step_work(cfg: dict, rec: dict) -> dict:
    return decode_step(cfg, rec["mean_live_tokens"], rec["mean_seated"])

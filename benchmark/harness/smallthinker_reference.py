"""The plain reference of the stack the ``smallthinker-*`` configurations
describe (``model_name`` ``smallthinker_21b_instruct``), in straightforward
``jax.numpy`` and float32 under ``jax.default_matmul_precision("highest")``:
no kernel, no cache, no ring, no batching tricks, and nothing imported from
the program. From the published ``config.json`` keys and the family's
description; each departure stands under ``assumed`` in the configuration
file.

For layer ``l`` with input ``x`` (the residual stream), ``eps``
``rms_norm_eps``, RMSNorm ``x / rms(x) * w`` with a plain weight:

    a    = RMSNorm_in(x)
    g    = a @ W_router                 float32, one output an expert: the
                                        router reads the ATTENTION's input
    top, sel = top_k(g, k);  w = softmax(top)
                                        == softmax over all, top k, divided by
                                        their sum (``norm_topk_prob``)
    q, k, v = a @ W_q, a @ W_k, a @ W_v no bias, no q/k norm
    if rope_layout[l]:  q, k = rope(q, pos), rope(k, pos)
                                        ``rope_theta``, whole head, half-split
    visible(i, j) = j <= i and (not sliding_window_layout[l]
                                or j > i - sliding_window_size)
    h    = x + softmax(q k^T / sqrt(head_dim) over visible) v @ W_o
    m    = RMSNorm_post(h)
    y    = h + sum_{e in sel} w_e * ((relu(m @ W_gate[e]) * (m @ W_up[e]))
                                     @ W_down[e])

then the final RMSNorm and an untied head. Every expert is held: nothing of
the routed sum is left out.

``quant`` switches the CONTROL on (``reference.mm``): every matrix
multiplication of a projection, an expert and the head takes operands rounded
to int8; the router and the softmaxes stay float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import reference as dense_reference
from . import smallthinker_weights as W
from .reference import HIGHEST, mm, rms_norm, rope


def attention(q, k, v, window, q_block=2048):
    """(B, S, H, D) x (B, S, Hkv, D): causal softmax, under the band where
    ``window`` (0: none). The scores of one head and one block of queries at
    a time: the same numbers as the whole (S x S) table, which 16,384
    positions cannot hold."""
    b, s, nh, d = q.shape
    nkv = k.shape[2]
    blk = min(q_block, s)
    assert s % blk == 0, (s, blk)
    cols = jnp.arange(s)

    def one(_, item):
        q_hb, kv, lo = item  # (B, blk, D), its KV head, its first row
        scores = jnp.einsum(
            "bqd,bkd->bqk", q_hb, k[:, :, kv], precision=HIGHEST) * d ** -0.5
        rows = (lo + jnp.arange(blk))[:, None]
        keep = cols[None, :] <= rows
        if window:
            keep = keep & (cols[None, :] > rows - window)
        probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), axis=-1)
        return None, jnp.einsum("bqk,bkd->bqd", probs, v[:, :, kv], precision=HIGHEST)

    n = s // blk
    q_blocks = jnp.moveaxis(q.reshape(b, n, blk, nh, d), (3, 1), (0, 1))
    _, out = jax.lax.scan(one, None, (
        q_blocks.reshape(nh * n, b, blk, d),
        jnp.repeat(jnp.arange(nh) // (nh // nkv), n),
        jnp.tile(jnp.arange(n) * blk, nh)))
    out = jnp.moveaxis(out.reshape(nh, n, b, blk, d), (0, 1), (3, 1))
    return out.reshape(b, s, nh, d)


def route(a, lw, cfg):
    """(sel, w): the k choices among the router's outputs and their weights."""
    probs = jax.nn.softmax(mm(a, lw["moe/router/kernel"]), axis=-1)
    w, sel = jax.lax.top_k(probs, cfg["moe_num_active_primary_experts"])
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return sel, w


def reglu(x, w_gate, w_up, w_down, quant=False):
    return mm(jax.nn.relu(mm(x, w_gate, quant)) * mm(x, w_up, quant), w_down, quant)


def experts_ff(m, a, lw, cfg, quant=False):
    """The routed sum: the experts one after the other, in their order, each
    masked by its weight (zero where it was not chosen); the choice is made on
    ``a``, the attention's input, the experts read ``m``."""
    sel, w = route(a, lw, cfg)

    def add_expert(out, expert):
        e, w_gate, w_up, w_down = expert
        w_e = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1)
        return out + w_e[..., None] * reglu(m, w_gate, w_up, w_down, quant), None

    return jax.lax.scan(add_expert, jnp.zeros_like(m), (
        jnp.arange(cfg["moe_num_primary_experts"]), lw["moe/gate_proj"],
        lw["moe/up_proj"], lw["moe/down_proj"]))[0]


def block(x, lw, cfg, kind, positions, quant=False):
    """One layer; ``lw`` is ``smallthinker_weights.layer_view``'s flat dict,
    ``kind`` its (operator, feed-forward, rope)."""
    b, s, _ = x.shape
    nh, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    a = rms_norm(x, lw["attn_norm/scale"], eps)
    q = mm(a, lw["attn/q_proj/kernel"], quant).reshape(b, s, nh, d)
    k = mm(a, lw["attn/k_proj/kernel"], quant).reshape(b, s, nkv, d)
    v = mm(a, lw["attn/v_proj/kernel"], quant).reshape(b, s, nkv, d)
    if kind[2]:
        q, k = (rope(t, positions, cfg["rope_theta"]) for t in (q, k))
    window = cfg["sliding_window_size"] if kind[0] == "sliding_attention" else 0
    out = attention(q, k, v, window).reshape(b, s, nh * d)
    h = x + mm(out, lw["attn/o_proj/kernel"], quant)
    m = rms_norm(h, lw["mlp_norm/scale"], eps)
    return h + experts_ff(m, a, lw, cfg, quant)


def forward(params, cfg, ids, quant=False):
    """Logits (B, S, V) from the program-shaped tree ``params``, walking the
    layers in a Python loop: the one full forward pass."""
    x = params["embed"]["embedding"].astype(jnp.float32)[ids]
    pos = jnp.broadcast_to(jnp.arange(ids.shape[1])[None], ids.shape)
    for l, kind in enumerate(W.layer_kinds(cfg)):
        x = block(x, W.layer_view(params, cfg, l), cfg, kind, pos, quant)
    x = rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return mm(x, params["lm_head"]["kernel"], quant)


leaf_norms = dense_reference.leaf_norms


def train_reference(*_args, **_kwargs):
    raise NotImplementedError(
        "no train cell takes this configuration: serving is where its layers "
        "hold caches of two sizes (ISSUE 45)")


# --------------------------------------------------------------------------- #
# serving: one pass over prompt + served tokens, one layer's weights at a time
# --------------------------------------------------------------------------- #
def served_token_gaps(cfg, seed, sequences, prompt_lens, weight_dtype,
                      quant=False, rows=1, width=None):
    """As ``qwen3_next_reference.served_token_gaps``: for each sequence
    (prompt followed by the tokens that were served), at each position that
    produced a served token, ``gap`` — how far that token's logit lies below
    the reference's best — and ``margin``; with ``quant`` also
    ``control_gap``. Layer l's weights are regenerated from the seed in
    ``weight_dtype`` and upcast, one layer at a time; the sequences go through
    one a program call, each padded to ``width`` — one number for all, or one
    a sequence (every operator is causal, so padding is inert). The head is
    read ONLY at the positions that produced a served token, so that ``width x
    vocab_size`` logits are never held."""
    import numpy as np

    if rows != 1:
        raise NotImplementedError("one sequence a call (reference_rows_per_block 1)")
    widths = (list(width) if isinstance(width, (list, tuple))
              else [max(max(len(s) for s in sequences), width or 0)] * len(sequences))
    assert all(len(s) <= w for s, w in zip(sequences, widths)), widths
    served = {w: 1 << (max(len(s) - p for s, p, w2 in zip(
        sequences, prompt_lens, widths) if w2 == w) - 1).bit_length()
        for w in set(widths)}
    blocks, at = [], []
    for s, p, w in zip(sequences, prompt_lens, widths):
        ids = np.zeros((1, w), np.int32)
        ids[0, :len(s)] = s
        blocks.append(jnp.asarray(ids))
        # positions p-1 .. len(s)-2 produced the served tokens s[p:]
        idx = np.full(served[w], p - 1, np.int32)
        idx[:len(s) - p] = np.arange(p - 1, len(s) - 1)
        at.append(jnp.asarray(idx))
    base = W.base_key(seed)
    embed = jax.jit(lambda table, ids: table.astype(jnp.float32)[ids])
    kinds = W.layer_kinds(cfg)

    def logits_of(q):
        top = W.top_leaves(base, cfg, weight_dtype)
        xs = [embed(top["embed"], ids) for ids in blocks]
        steps = {kind: jax.jit(lambda x, lw, kind=kind: block(
            x, lw, cfg, kind, jnp.arange(x.shape[1])[None], q))
            for kind in dict.fromkeys(kinds)}
        for l, kind in enumerate(kinds):
            lw = W.layer_slice(base, cfg, l, weight_dtype)
            xs = [steps[kind](x, lw) for x in xs]
            del lw
        head = jax.jit(lambda x, idx, scale, w: mm(
            rms_norm(x[0, idx], scale, cfg["rms_norm_eps"]), w, q))
        for x, idx in zip(xs, at):  # (served, vocab) at a time
            yield head(x, idx, top["final_norm"], top["lm_head"])

    @jax.jit
    def read(logits, ids, idx):
        top2 = jax.lax.top_k(logits, 2)[0]
        got = jnp.take_along_axis(logits, ids[0, idx + 1][:, None], axis=-1)[:, 0]
        return top2[:, 0] - got, top2[:, 0] - top2[:, 1]

    @jax.jit
    def read_control(logits, low):
        put_first = jnp.take_along_axis(logits, low[:, None], axis=-1)[:, 0]
        return jnp.max(logits, axis=-1) - put_first

    # the lower precision's pass first and whole — only the token it puts
    # first is kept —, so that one pass's activations are held at a time
    low = [jnp.argmax(x, axis=-1) for x in logits_of(True)] if quant else None
    out = {"gap": [], "margin": [], "control_gap": []}
    for i, logits in enumerate(logits_of(False)):
        n = len(sequences[i]) - prompt_lens[i]
        g, m = read(logits, blocks[i], at[i])
        out["gap"].append(np.asarray(g)[:n])
        out["margin"].append(np.asarray(m)[:n])
        if quant:
            out["control_gap"].append(np.asarray(read_control(logits, low[i]))[:n])
    return out


def param_change_leaf_norms(cfg, seed, params) -> dict:
    raise NotImplementedError("a serving configuration: no parameter changes")

"""The plain reference of the stack the ``deepseek-v3-*`` configurations
describe (``model_type`` ``deepseek_v3``), in straightforward ``jax.numpy`` and
float32 under ``jax.default_matmul_precision("highest")``: the EXPANDED form
of latent attention only (per-head keys and values from the latent, one full
causal pass over prompt + answer), no cache, no kernel, no batching, in blocks
of heads and of query rows so that it fits, and nothing imported from the
program. From the published ``config.json`` keys and the family's published
modeling code; each departure stands under ``assumed`` in the configuration
file.

A layer: ``h += MLA(norm(h))``; ``h += FF(norm(h))``; every norm ``x / rms(x)
* w`` (``rms_norm_eps``).

* MLA, token at position ``p``: ``c_q = norm(x W_DQ)`` (``q_lora_rank``); ``q =
  c_q W_UQ`` as ``num_attention_heads`` heads of ``[q_nope (qk_nope_head_dim)
  | q_rope (qk_rope_head_dim)]``, ``q_rope = rope(q_rope, p)``. ``[c_kv
  (kv_lora_rank) | k_r] = x W_DKV``; ``c_kv = norm(c_kv)``; ``k_rope =
  rope(k_r, p)``, ONE for all heads. ``[k_nope_h | v_h (v_head_dim)] = c_kv
  W_UKV^h``. ``score_h(t, s) = (q_nope_h(t) . k_nope_h(s) + q_rope_h(t) .
  k_rope(s)) * scale``, causal softmax, ``o_h = sum_s P v_h(s)``, ``out =
  concat_h(o_h) W_O``. ``scale = (qk_nope_head_dim + qk_rope_head_dim) ** -0.5
  * m ** 2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``; cos and sin are
  multiplied by ``m(mscale) / m(mscale_all_dim)``.
* YaRN over the ``d / 2`` frequencies ``f_i = theta ** (-2 i / d)`` of the
  ``d = qk_rope_head_dim`` rotated elements: ``low = floor(d ln(L / (2 pi
  beta_fast)) / (2 ln theta))``, ``high = ceil(d ln(L / (2 pi beta_slow)) / (2
  ln theta))`` (``L`` the original context), clipped to [0, d - 1]; ``ramp_i =
  clip((i - low) / (high - low), 0, 1)``; ``f'_i = (f_i / factor) ramp_i + f_i
  (1 - ramp_i)``. Rope pairs element i with i + d / 2 (rotate-half; the source
  interleaves pairs, a relabelling of columns under seeded weights).
* Experts: ``s = sigmoid(x W_r)`` over all ``router_width`` outputs; ``s' = s
  + b``; the outputs in ``n_group`` groups, a group's score the sum of its two
  largest ``s'``, the ``topk_group`` best groups kept; the
  ``num_experts_per_tok`` largest ``s'`` inside them chosen; weights ``s``
  (without ``b``) of the chosen over (their sum + 1e-20), times
  ``routed_scaling_factor``; ``FF(x) = sum_e w_e E_e(x) + Shared(x)``, every
  expert ``down(silu(gate x) * up x)``. A configuration that holds a share
  (``n_routed_experts`` of ``router_width`` from ``expert_offset``) computes
  the held experts' part of the routed sum — one expert after the other, over
  the rows that chose it — and the shared expert whole; what the absent
  experts would add is left out, here as in the program.
* The multi-token-prediction module is no part of next-token logits and is
  not held (``num_nextn_predict_layers`` 0).

``quant`` switches the CONTROL on (``reference.mm``): every matrix
multiplication of a projection, an expert and the head takes operands rounded
to int8; the router, the scores and the softmaxes stay float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import deepseek_v3_weights as W
from . import reference as dense_reference
from .reference import HIGHEST, mm, rms_norm

NORM_TOPK_EPS = 1e-20


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_frequencies(cfg: dict) -> np.ndarray:
    """The ``qk_rope_head_dim / 2`` frequencies under the configuration's
    YaRN scaling (plain ``theta ** (-2 i / d)`` where it names none)."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    sc = cfg.get("rope_scaling")
    if not sc:
        return f.astype(np.float32)
    old = float(sc["original_max_position_embeddings"])

    def index_of(turns):
        return d * math.log(old / (2 * math.pi * turns)) / (2 * math.log(theta))

    low = max(math.floor(index_of(sc["beta_fast"])), 0)
    high = min(math.ceil(index_of(sc["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return (f / sc["factor"] * ramp + f * (1.0 - ramp)).astype(np.float32)


def softmax_scale(cfg: dict) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    sc = cfg.get("rope_scaling")
    if sc and sc.get("mscale_all_dim"):
        scale *= yarn_mscale(sc["factor"], sc["mscale_all_dim"]) ** 2
    return scale


def rope(x, positions, cfg):
    """``x`` (B, S, H, d) turned by ``positions`` (B, S), element i paired
    with i + d / 2."""
    sc = cfg.get("rope_scaling")
    mult = 1.0
    if sc:
        mult = (yarn_mscale(sc["factor"], sc["mscale"])
                / yarn_mscale(sc["factor"], sc["mscale_all_dim"]))
    ang = positions[:, :, None, None].astype(jnp.float32) * rope_frequencies(cfg)
    cos, sin = jnp.cos(ang) * mult, jnp.sin(ang) * mult
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def latent_attention(y, lw, cfg, positions, quant=False, q_block=1024,
                     head_block=16):
    """The expanded form over ``y`` (1, S, hidden): every position's latent
    through ``kv_b_proj`` to per-head keys and values; ``head_block`` heads at
    a time, each query block against the keys up to its own end."""
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rot, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    b, s, _ = y.shape
    eps = cfg["rms_norm_eps"]
    c_q = rms_norm(mm(y, lw["attn/q_a_proj/kernel"], quant),
                   lw["attn/q_a_norm/scale"], eps)
    down = mm(y, lw["attn/kv_a_proj/kernel"], quant)
    c_kv = rms_norm(down[..., :rank], lw["attn/kv_a_norm/scale"], eps)
    k_rope = rope(down[..., None, rank:], positions, cfg)  # (b, s, 1, rot)
    hb = min(head_block, heads)
    qb = min(q_block, s)
    scale = softmax_scale(cfg)

    def group(w):  # (in, heads * d) -> (heads / hb, in, hb * d)
        return jnp.moveaxis(w.reshape(w.shape[0], heads // hb, -1), 1, 0)

    def of_heads(ws):
        w_q, w_kv = ws
        q = mm(c_q, w_q, quant).reshape(b, s, hb, nope + rot)
        q = jnp.concatenate(
            [q[..., :nope], rope(q[..., nope:], positions, cfg)], axis=-1)
        kv = mm(c_kv, w_kv, quant).reshape(b, s, hb, nope + dv)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, hb, rot))], axis=-1)
        v = kv[..., nope:]
        outs = []
        for lo in range(0, s, qb):
            hi = min(lo + qb, s)
            rows = q[:, lo:hi]
            if outs:  # one block's scores at a time: this one after the last
                rows, _ = jax.lax.optimization_barrier((rows, outs[-1]))
            sc = jnp.einsum("bqhd,bkhd->bhqk", rows, k[:, :hi],
                            precision=HIGHEST) * scale
            keep = (jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None])
            p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, v[:, :hi],
                                   precision=HIGHEST))
        return jnp.concatenate(outs, axis=1)  # (b, s, hb, dv)

    out = jax.lax.map(of_heads, (group(lw["attn/q_b_proj/kernel"].astype(jnp.float32)),
                                 group(lw["attn/kv_b_proj/kernel"].astype(jnp.float32))))
    out = jnp.moveaxis(out, 0, 2).reshape(b, s, heads * dv)
    return mm(out, lw["attn/o_proj/kernel"], quant)


def swiglu(x, w_gate, w_up, w_down, quant=False):
    return mm(jax.nn.silu(mm(x, w_gate, quant)) * mm(x, w_up, quant), w_down, quant)


def route(x, lw, cfg):
    """(chosen experts among the router's outputs, their weights): float32."""
    s = jax.nn.sigmoid(jnp.matmul(
        x, lw["moe/router/kernel"].astype(jnp.float32), precision=HIGHEST))
    biased = s + lw["moe/expert_bias"]
    groups = cfg["n_group"]
    grouped = biased.reshape(*biased.shape[:-1], groups, -1)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    _, best = jax.lax.top_k(group_score, cfg["topk_group"])
    kept = jnp.any(best[..., None] == jnp.arange(groups), axis=-2)
    limited = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(biased.shape)
    _, sel = jax.lax.top_k(limited, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, sel, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + NORM_TOPK_EPS)
    return sel, w * cfg["routed_scaling_factor"]


def _expert_rows(x, sel, w, e, kernels, rows: int, quant: bool):
    """Expert ``e``'s weighted output on the (at most ``rows``) rows of ``x``
    (T, h) that chose it, added where they lie; the others get zero."""
    mine = sel == e  # (T, K)
    weight = jnp.sum(jnp.where(mine, w, 0.0), axis=-1)
    idx = jnp.nonzero(jnp.any(mine, axis=-1), size=rows, fill_value=x.shape[0])[0]
    got = swiglu(jnp.take(x, idx, axis=0, mode="fill", fill_value=0.0),
                 *kernels, quant)
    got = got * jnp.take(weight, idx, mode="fill", fill_value=0.0)[:, None]
    return jnp.zeros_like(x).at[idx].add(got, mode="drop")


_expert_rows_jit = jax.jit(_expert_rows, static_argnames=("rows", "quant"))


def shared_ff(x, lw, quant=False):
    return swiglu(x, lw["moe/shared/gate_proj/kernel"], lw["moe/shared/up_proj/kernel"],
                  lw["moe/shared/down_proj/kernel"], quant)


def _mixed(x, lw, cfg, quant):
    """``h = x + MLA(norm(x))`` and the feed-forward's input ``norm(h)``."""
    eps = cfg["rms_norm_eps"]
    positions = jnp.arange(x.shape[1])[None]
    h = x + latent_attention(
        rms_norm(x, lw["attn_norm/scale"], eps), lw, cfg, positions, quant)
    return h, rms_norm(h, lw["mlp_norm/scale"], eps)


def steps_of(cfg: dict, quant: bool = False) -> dict:
    """A layer's jitted pieces, made once and used for every layer and
    sequence (the routed experts walk the host between them, so a layer is a
    few calls, not one)."""
    return {
        "mixed": jax.jit(lambda x, lw: _mixed(x, lw, cfg, quant)),
        "dense": jax.jit(lambda y, lw: swiglu(
            y, lw["mlp/gate_proj/kernel"], lw["mlp/up_proj/kernel"],
            lw["mlp/down_proj/kernel"], quant)),
        "route": jax.jit(lambda flat, lw: route(flat, lw, cfg)),
        "shared": jax.jit(lambda y, lw: shared_ff(y, lw, quant)),
    }


def routed_ff(x, lw, cfg, quant=False, steps=None):
    """The held experts' part of the routed sum over ``x`` (B, S, h): one
    expert after the other over the rows that chose it (their count read on
    the host, rounded up to a power of two: a few compiled shapes)."""
    flat = x.reshape(-1, x.shape[-1])
    sel, w = (steps or steps_of(cfg, quant))["route"](flat, lw)
    first = cfg["expert_offset"]
    held = np.bincount(np.asarray(sel).ravel(), minlength=cfg["router_width"])[
        first:first + cfg["n_routed_experts"]]
    out = jnp.zeros_like(flat)
    for e, count in enumerate(held):
        if count:
            kernels = tuple(lw[f"moe/{n}"][e] for n in ("gate_proj", "up_proj", "down_proj"))
            out = out + _expert_rows_jit(
                flat, sel, w, first + e, kernels,
                rows=max(8, 1 << (int(count) - 1).bit_length()), quant=quant)
    return out.reshape(x.shape)


def block(x, lw, cfg, kind, quant=False, steps=None):
    """One layer over ``x`` (1, S, hidden) at positions 0 .. S - 1."""
    steps = steps or steps_of(cfg, quant)
    with jax.default_matmul_precision(HIGHEST):
        h, y = steps["mixed"](x, lw)
        if kind == "mlp":
            return h + steps["dense"](y, lw)
        return h + routed_ff(y, lw, cfg, quant, steps) + steps["shared"](y, lw)


def forward(params, cfg, ids, quant=False):
    """Logits (B, S, vocab) of the whole pass, from a program-shaped tree,
    one sequence after the other."""
    steps = steps_of(cfg, quant)
    rows = []
    for row in ids:
        x = params["embed"]["embedding"].astype(jnp.float32)[row[None]]
        for l, kind in enumerate(W.layer_kinds(cfg)):
            x = block(x, W.layer_view(params, cfg, l), cfg, kind, quant, steps)
        x = rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
        rows.append(mm(x, params["lm_head"]["kernel"], quant)[0])
    return jnp.stack(rows)


leaf_norms = dense_reference.leaf_norms


def train_reference(*_args, **_kwargs):
    raise NotImplementedError(
        "no train cell takes this configuration: its fp32 optimiser state "
        "fits no one-chip cut, and serving is where the latent cache does its "
        "work (ISSUE 40)")


# --------------------------------------------------------------------------- #
# serving: one pass over prompt + served tokens, one layer's weights at a time
# --------------------------------------------------------------------------- #
def served_token_gaps(cfg, seed, sequences, prompt_lens, weight_dtype,
                      quant=False, rows=1, width=None):
    """As ``reference.served_token_gaps``: for each sequence (prompt followed
    by the tokens that were served), at each position that produced a served
    token, ``gap`` — how far that token's logit lies below the reference's
    best — and ``margin``; with ``quant`` also ``control_gap``. Layer l's
    weights are regenerated from the seed in ``weight_dtype`` and upcast, one
    layer at a time; the sequences go through one a program call, each padded
    to the power of two that holds it (at least 256; ``width`` is the most a
    sequence may be: every operator is causal, so padding is inert, and a few
    widths are a few compiled programs). The head is read ONLY at the
    positions that produced a served token."""
    if rows != 1:
        raise NotImplementedError("one sequence a call (reference_rows_per_block 1)")
    most = max(width) if isinstance(width, (list, tuple)) else width
    assert most is None or all(len(s) <= most for s in sequences), most
    widths = [max(256, 1 << (len(s) - 1).bit_length()) for s in sequences]
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
        steps = steps_of(cfg, q)
        for l, kind in enumerate(kinds):
            lw = W.layer_slice(base, cfg, l, weight_dtype)
            xs = [block(x, lw, cfg, kind, q, steps) for x in xs]
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


def param_change_leaf_norms(*_args, **_kwargs):
    raise NotImplementedError("no train cell takes this configuration")

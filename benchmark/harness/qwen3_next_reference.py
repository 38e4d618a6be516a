"""The plain reference of the stack the ``qwen3-next-*`` configurations
describe (``model_type`` ``qwen3_next``), in straightforward ``jax.numpy`` and
float32 under ``jax.default_matmul_precision("highest")``: no kernel, no
cache, no chunking, no batching tricks, and nothing imported from the program.
From the published ``config.json`` keys and the family's published modeling
code; each departure stands under ``assumed`` in the configuration file.

Layer ``i`` is full attention where ``layer_types[i]`` says so (every
``full_attention_interval``-th), else Gated DeltaNet; every layer is
``x + mixer(norm(x))`` then ``x + experts(norm(x))``; every such norm and the
final one is ``x / rms(x) * (1 + w)`` (``rms_norm_eps``).

* Gated attention: ``q_proj`` gives ``[q | gate]`` a head; ``q`` and ``k``
  through a per-head ``(1 + w)`` RMS norm; rope (half-split pairing) over the
  FIRST ``partial_rotary_factor * head_dim`` elements of a head, the rest
  pass; causal softmax at ``head_dim ** -0.5``; ``o_proj(attn * sigmoid(gate))``.
* Gated DeltaNet: ``[q | k | v | z] = in_proj_qkvz(x)``, ``[b | a] =
  in_proj_ba(x)``; ``[q | k | v]`` through a causal depthwise convolution of
  ``linear_conv_kernel_dim`` taps, no bias, then silu; ``beta = sigmoid(b)``;
  ``g = -exp(A_log) * softplus(a + dt_bias)``; ``q``, ``k`` L2-normalised a
  head (eps 1e-6), key head ``j // (Hv / Hk)`` serving value head ``j``, ``q``
  times ``Dk ** -0.5``. For each value head, ``S`` (Dk x Dv) zero before the
  first token, TOKEN BY TOKEN (:func:`delta_rule`, a ``lax.scan`` over
  positions): ``S <- exp(g_t) S``; ``d_t = beta_t (v_t - S^T k_t)``;
  ``S <- S + k_t d_t^T``; ``o_t = S^T q_t``. Then
  ``o_t / rms(o_t) * w * silu(z_t)`` a head (plain ``w``) and ``out_proj``.
* Experts: router ``hidden -> router_width``, softmax over all of them, top
  ``num_experts_per_tok``, the chosen weights divided by their sum
  (``norm_topk_prob``); experts ``down(silu(gate x) * up x)``; plus
  ``sigmoid(w_s . x) * shared(x)``. A configuration that holds a share
  (``num_experts`` of ``router_width`` from ``expert_offset``) computes the
  held experts' part of the routed sum, one expert after the other, and the
  shared expert whole — what the absent experts would add is left out, here as
  in the program.

``quant`` switches the CONTROL on (``reference.mm``): every matrix
multiplication of a projection, an expert and the head takes operands rounded
to int8; the router, the recurrence and the softmaxes stay float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import qwen3_next_weights as W
from . import reference as dense_reference
from .reference import HIGHEST, mm, rope

L2_EPS = 1e-6


def rms_norm(x, w, eps):
    """``x / rms(x) * (1 + w)``: the zero-centred norm."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def partial_rope(x, positions, theta, rotary):
    """Rope over the first ``rotary`` elements of a head (frequencies of a
    head that wide); the others pass."""
    return jnp.concatenate(
        [rope(x[..., :rotary], positions, theta), x[..., rotary:]], axis=-1)


def gated_attention(y, lw, cfg, positions, quant=False, q_block=2048):
    b, s, _ = y.shape
    nh, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    rotary = int(d * cfg["partial_rotary_factor"])
    qg = mm(y, lw["attn/q_proj/kernel"], quant).reshape(b, s, nh, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = mm(y, lw["attn/k_proj/kernel"], quant).reshape(b, s, nkv, d)
    v = mm(y, lw["attn/v_proj/kernel"], quant).reshape(b, s, nkv, d)
    q = partial_rope(rms_norm(q, lw["attn/q_norm/scale"], eps), positions, theta, rotary)
    k = partial_rope(rms_norm(k, lw["attn/k_norm/scale"], eps), positions, theta, rotary)
    # the scores of one head and one block of queries at a time: the same
    # numbers as the whole (S x S) table, which 16,384 positions cannot hold
    blk = min(q_block, s)
    assert s % blk == 0, (s, blk)
    cols = jnp.arange(s)

    def one(_, item):
        q_hb, kv, lo = item  # (B, blk, D), its KV head, its first row
        scores = jnp.einsum(
            "bqd,bkd->bqk", q_hb, k[:, :, kv], precision=HIGHEST) * d ** -0.5
        keep = cols[None, :] <= (lo + jnp.arange(blk))[:, None]
        probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), axis=-1)
        return None, jnp.einsum("bqk,bkd->bqd", probs, v[:, :, kv], precision=HIGHEST)

    n = s // blk
    q_blocks = jnp.moveaxis(q.reshape(b, n, blk, nh, d), (3, 1), (0, 1))
    _, out = jax.lax.scan(one, None, (
        q_blocks.reshape(nh * n, b, blk, d),
        jnp.repeat(jnp.arange(nh) // (nh // nkv), n),
        jnp.tile(jnp.arange(n) * blk, nh)))
    out = jnp.moveaxis(out.reshape(nh, n, b, blk, d), (0, 1), (3, 1))
    out = out.reshape(b, s, nh, d) * jax.nn.sigmoid(gate)
    return mm(out.reshape(b, s, nh * d), lw["attn/o_proj/kernel"], quant)


def causal_conv(x, kernel):
    """Depthwise, no bias: ``out_t = sum_j kernel[j] x[t - (taps - 1) + j]``;
    ``x`` (B, S, C), ``kernel`` (taps, C)."""
    taps = kernel.shape[0]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(kernel[j].astype(jnp.float32) * padded[:, j:j + x.shape[1]]
               for j in range(taps))


def l2_normalize(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, g, beta):
    """The gated delta rule, one position after the other. ``q``, ``k`` (B, S,
    H, Dk) — already unit length, one a VALUE head —, ``v`` (B, S, H, Dv),
    ``g``, ``beta`` (B, S, H). Returns ``(o (B, S, H, Dv), last state (B, H,
    Dk, Dv))``."""
    b, _, h, dk = q.shape
    state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)

    def step(s, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        s = s * jnp.exp(g_t)[..., None, None]
        read = jnp.einsum("bhkv,bhk->bhv", s, k_t, precision=HIGHEST)
        d_t = beta_t[..., None] * (v_t - read)
        s = s + k_t[..., :, None] * d_t[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=HIGHEST)

    state, out = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1), state


def gated_delta_net(y, lw, cfg, quant=False):
    b, s, _ = y.shape
    hk, hv, dk, dv, conv = W.gdn_dims(cfg)
    qkvz = mm(y, lw["gdn/in_proj_qkvz/kernel"], quant)
    ba = mm(y, lw["gdn/in_proj_ba/kernel"], quant)
    mixed, z = qkvz[..., :conv], qkvz[..., conv:]
    mixed = jax.nn.silu(causal_conv(mixed, lw["gdn/conv1d"]))
    q = mixed[..., :hk * dk].reshape(b, s, hk, dk)
    k = mixed[..., hk * dk:2 * hk * dk].reshape(b, s, hk, dk)
    v = mixed[..., 2 * hk * dk:].reshape(b, s, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(lw["gdn/A_log"]) * jax.nn.softplus(ba[..., hv:] + lw["gdn/dt_bias"])
    q = jnp.repeat(l2_normalize(q) * dk ** -0.5, hv // hk, axis=2)
    k = jnp.repeat(l2_normalize(k), hv // hk, axis=2)
    o, _ = delta_rule(q, k, v, g, beta)
    var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
    o = o * jax.lax.rsqrt(var + cfg["rms_norm_eps"]) * lw["gdn/norm"]
    o = o * jax.nn.silu(z.reshape(b, s, hv, dv))
    return mm(o.reshape(b, s, hv * dv), lw["gdn/out_proj/kernel"], quant)


def swiglu(x, w_gate, w_up, w_down, quant=False):
    return mm(jax.nn.silu(mm(x, w_gate, quant)) * mm(x, w_up, quant), w_down, quant)


def route(x, lw, cfg):
    """(sel, w): the k choices among the router's outputs and their weights."""
    probs = jax.nn.softmax(mm(x, lw["moe/router/kernel"]), axis=-1)
    w, sel = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return sel, w


def routed_ff(x, lw, cfg, quant=False):
    """The held experts' part of the routed result: the experts one after
    the other, in their order, each masked by its weight (zero where it was
    not chosen)."""
    sel, w = route(x, lw, cfg)

    def add_expert(out, expert):
        e, w_gate, w_up, w_down = expert
        w_e = jnp.sum(jnp.where(sel == cfg["expert_offset"] + e, w, 0.0), axis=-1)
        return out + w_e[..., None] * swiglu(x, w_gate, w_up, w_down, quant), None

    return jax.lax.scan(add_expert, jnp.zeros_like(x), (
        jnp.arange(cfg["num_experts"]), lw["moe/gate_proj"], lw["moe/up_proj"],
        lw["moe/down_proj"]))[0]


def shared_ff(x, lw, quant=False):
    out = swiglu(x, *(lw[f"moe/shared/{n}/kernel"]
                      for n in ("gate_proj", "up_proj", "down_proj")), quant)
    gate = jnp.einsum("bsh,h->bs", x, lw["moe/shared_gate"].astype(jnp.float32),
                      precision=HIGHEST)
    return jax.nn.sigmoid(gate)[..., None] * out


def experts_ff(x, lw, cfg, quant=False):
    return routed_ff(x, lw, cfg, quant) + shared_ff(x, lw, quant)


def block(x, lw, cfg, kind, positions, quant=False):
    """One layer; ``lw`` is ``qwen3_next_weights.layer_view``'s flat dict."""
    eps = cfg["rms_norm_eps"]
    if kind[0] == "linear_attention":
        x = x + gated_delta_net(rms_norm(x, lw["gdn_norm/scale"], eps), lw, cfg, quant)
    else:
        x = x + gated_attention(
            rms_norm(x, lw["attn_norm/scale"], eps), lw, cfg, positions, quant)
    return x + experts_ff(rms_norm(x, lw["mlp_norm/scale"], eps), lw, cfg, quant)


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
        "no train cell takes this configuration: serving is where its three "
        "caches (experts, recurrent state, KV) meet in one step (ISSUE 38)")


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
    to ``width`` — one number for all, or one a sequence (a few widths: one
    compiled program per width and kind of layer; every operator is causal,
    so padding is inert). The head is read ONLY at the positions that
    produced a served token (padded to the power of two of the longest answer
    among the sequences of that width), so that ``width x vocab_size`` logits
    are never held."""
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
    """Per leaf of ``params``, keyed as ``leaf_norms`` keys them: the norm of
    its change since the seed, the seed's value regenerated a layer at a time."""
    base, change = W.base_key(seed), {}
    for row in W.leaf_table(cfg):
        node = params
        for part in row["path"]:
            node = node[part]
        total = 0.0
        for i, layer in enumerate(row["layers"] or [None]):
            x = node[i] if row["stacked"] else node
            again = W.make_leaf(base, row, jnp.float32, cfg, layer)
            total = total + jnp.sum(jnp.square(x.astype(jnp.float32) - again))
        change["".join(f"['{p}']" for p in row["path"])] = float(jnp.sqrt(total))
    return change

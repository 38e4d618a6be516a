"""The plain reference of the stack the ``evabyte-*`` configurations describe
(EvaByte: a byte-level decoder whose every layer attends by EVA), in
straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no scan, and
nothing imported from the program.

The published ``config.json`` carries the sizes and none of the formulas. The
estimator is that of Zheng et al., "Efficient Attention via Control Variates"
(ICLR 2023), with ONE learned ``mu`` and ``phi`` a head in place of sampled
random features. The one form, the program's and this file's alike; each line
marked (+) is an assumption and stands under ``assumed`` in the configuration
file:

* ``x = x + Attn(norm(x))``, ``x = x + SwiGLU(norm(x))``;
  ``norm(x) = x / rms(x) * (1 + w)`` (``norm_add_unit_offset``), eps 1e-5;
  ``SwiGLU = down(silu(gate(x)) * up(x))``; no biases.
* ``q, k, v = rope(x Wq), rope(x Wk), x Wv`` per head, d = 128 (+: the file
  leaves head_dim out), rope over the whole head in the half-split
  convention (+: element i pairs with i + d/2) at theta 100,000, applied
  BEFORE anything below; ``s = d ** -0.5``.
* Chunk ``c`` holds positions ``16c .. 16c+15``. Its summary, per head ``h``
  with learned ``mu_h``, ``phi_h`` in R^d (``j`` over the chunk's positions):
  ``kt_c = sum_j softmax_j(s * mu_h . k_j) k_j`` (+) and
  ``vt_c = sum_j softmax_j(s * (phi_h . k_j - |k_j|^2 / 2)) v_j`` (+), the
  second being the paper's self-normalised positive random feature.
* Window of position ``i``: ``w(i) = floor(i / 2048)``. Query ``i`` sees
  exactly the tokens ``j <= i`` with ``w(j) = w(i)`` and the summaries of every
  chunk that lies in a window before ``w(i)``: ONE softmax over the scores
  ``s * q_i . k_j`` and ``s * q_i . kt_c``, values ``v_j`` and ``vt_c`` (+).
  Windows do not slide: at ``i = 2048 m`` the query sees itself and ``128 m``
  summaries.
* Head: final norm, ``lm_head`` 4096 -> ``num_pred_heads * vocab_size``,
  head-major (+): columns ``0 .. vocab_size - 1`` are the next byte's and are
  what serving samples (the published plain ``generate``); the further heads
  are computed by :func:`forward` and compared by nothing. Self-speculative
  multi-byte decoding is not written, here or in the program.

``quant`` switches the CONTROL on, as in ``reference.py``: every matrix
multiplication's operands (projections, MLP, head) rounded to symmetric int8.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import evabyte_weights as W
from . import reference as dense_reference
from .reference import HIGHEST, mm, rope  # noqa: F401


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def chunk_summaries(k, v, mu, phi, chunk):
    """``k``, ``v`` (S, H, D) with ``S`` whole chunks; ``mu``, ``phi`` (H, D).
    One ``(kt, vt)`` (S / chunk, H, D) a chunk."""
    s, h, d = k.shape
    scale = d ** -0.5
    kc = k.reshape(s // chunk, chunk, h, d)
    vc = v.reshape(s // chunk, chunk, h, d)
    a = jax.nn.softmax(
        scale * jnp.einsum("cjhd,hd->cjh", kc, mu, precision=HIGHEST), axis=1)
    kt = jnp.einsum("cjh,cjhd->chd", a, kc, precision=HIGHEST)
    logit = (jnp.einsum("cjhd,hd->cjh", kc, phi, precision=HIGHEST)
             - 0.5 * jnp.sum(kc * kc, axis=-1))
    b = jax.nn.softmax(scale * logit, axis=1)
    vt = jnp.einsum("cjh,cjhd->chd", b, vc, precision=HIGHEST)
    return kt, vt


def eva_attention(q, k, v, mu, phi, chunk, window):
    """One sequence, (S, H, D) each, any ``S``: a window at a time, so that
    the scores held at once are one window's (H, window, window + summaries).
    The summaries of a window are made once it is complete and seen by the
    windows after it."""
    s, _, d = q.shape
    scale = d ** -0.5
    outs = []
    kt = jnp.zeros((0,) + k.shape[1:], k.dtype)
    vt = jnp.zeros((0,) + v.shape[1:], v.dtype)
    for lo in range(0, s, window):
        hi = min(lo + window, s)
        qs, ks, vs = q[lo:hi], k[lo:hi], v[lo:hi]
        own = jnp.einsum("qhd,khd->hqk", qs, ks, precision=HIGHEST) * scale
        rows = jnp.arange(hi - lo)
        own = jnp.where(rows[None, :, None] >= rows[None, None, :], own, -jnp.inf)
        before = jnp.einsum("qhd,chd->hqc", qs, kt, precision=HIGHEST) * scale
        probs = jax.nn.softmax(jnp.concatenate([before, own], axis=-1), axis=-1)
        n = kt.shape[0]
        outs.append(
            jnp.einsum("hqc,chd->qhd", probs[..., :n], vt, precision=HIGHEST)
            + jnp.einsum("hqk,khd->qhd", probs[..., n:], vs, precision=HIGHEST))
        if hi - lo == window and hi < s:
            kt_w, vt_w = chunk_summaries(ks, vs, mu, phi, chunk)
            kt = jnp.concatenate([kt, kt_w])
            vt = jnp.concatenate([vt, vt_w])
    return jnp.concatenate(outs)


def block(x, lw, cfg, positions, quant=False):
    """One decoder layer; ``lw`` is evabyte_weights.layer_slice's flat dict."""
    b, s, _ = x.shape
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    if nh != nkv:
        raise NotImplementedError("EVA as written here has one K/V head a head")
    d = cfg.get("head_dim") or cfg["hidden_size"] // nh
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    y = rms_norm(x, lw["attn_norm"], eps)
    q = rope(mm(y, lw["attn/q_proj"], quant).reshape(b, s, nh, d), positions, theta)
    k = rope(mm(y, lw["attn/k_proj"], quant).reshape(b, s, nkv, d), positions, theta)
    v = mm(y, lw["attn/v_proj"], quant).reshape(b, s, nkv, d)
    mu, phi = (lw[name].astype(jnp.float32) for name in ("attn/mu", "attn/phi"))
    a = jnp.stack([
        eva_attention(q[i], k[i], v[i], mu, phi, cfg["chunk_size"],
                      cfg["window_size"])
        for i in range(b)]).reshape(b, s, nh * d)
    x = x + mm(a, lw["attn/o_proj"], quant)
    y = rms_norm(x, lw["mlp_norm"], eps)
    gate, up = mm(y, lw["mlp/gate_proj"], quant), mm(y, lw["mlp/up_proj"], quant)
    return x + mm(jax.nn.silu(gate) * up, lw["mlp/down_proj"], quant)


def layer_view(params, l: int) -> dict:
    """Layer ``l`` of a program-shaped tree (stacked layers), flat as
    ``evabyte_weights.layer_slice``."""
    lay = params["layers"]
    return {
        "attn_norm": lay["attn_norm"]["scale"][l],
        "mlp_norm": lay["mlp_norm"]["scale"][l],
        "attn/mu": lay["attn"]["mu"][l], "attn/phi": lay["attn"]["phi"][l],
        **{f"attn/{n}": lay["attn"][n]["kernel"][l]
           for n in ("q_proj", "k_proj", "v_proj", "o_proj")},
        **{f"mlp/{n}": lay["mlp"][n]["kernel"][l]
           for n in ("gate_proj", "up_proj", "down_proj")},
    }


def forward(params, cfg, ids, quant=False):
    """Logits (B, S, num_pred_heads, V) from the program-shaped tree
    ``params``, walking the layers in a Python loop; ``[..., 0, :]`` is the
    next byte's."""
    x = params["embed"]["embedding"].astype(jnp.float32)[ids]
    pos = jnp.broadcast_to(jnp.arange(ids.shape[1])[None], ids.shape)
    for l in range(cfg["num_hidden_layers"]):
        x = block(x, layer_view(params, l), cfg, pos, quant)
    x = rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    logits = mm(x, params["lm_head"]["kernel"], quant)
    return logits.reshape(*ids.shape, cfg["num_pred_heads"], cfg["vocab_size"])


def loss(params, cfg, ids, quant=False):
    """Mean next-byte cross-entropy over every position but the last."""
    logits = forward(params, cfg, ids, quant)[:, :-1, 0]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.mean(-jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0])


leaf_norms = dense_reference.leaf_norms


def train_reference(*_args, **_kwargs):
    raise NotImplementedError(
        "no train cell takes this configuration: at 16 B a parameter its 16 "
        "layers do not fit one chip, and serving is where the mechanism is "
        "the work (ISSUE 30)")


def param_change_leaf_norms(cfg, seed, params) -> dict:
    """Per leaf of ``params``, keyed as ``leaf_norms`` keys them: the norm of
    its change since the seed, the seed's value regenerated a layer at a time."""
    base, change = W.base_key(seed), {}
    table = W.leaf_table(cfg)

    def one(i, x, l):
        return jnp.sum(jnp.square(
            x.astype(jnp.float32) - W.make_leaf(base, table[i], jnp.float32, l)))

    for i, row in enumerate(table):
        node = params
        for part in row["path"]:
            node = node[part]
        total = (sum(one(i, node[l], l) for l in range(cfg["num_hidden_layers"]))
                 if row["stacked"] else one(i, node, None))
        change["".join(f"['{p}']" for p in row["path"])] = float(jnp.sqrt(total))
    return change


# --------------------------------------------------------------------------- #
# serving: one pass over prompt + served bytes, one layer's weights at a time
# --------------------------------------------------------------------------- #
def served_token_gaps(cfg, seed, sequences, prompt_lens, weight_dtype,
                      quant=False, rows=1, width=None):
    """As ``reference.served_token_gaps``, over the next byte's logits
    (columns ``0 .. vocab_size - 1`` of the head): for each sequence (prompt
    followed by the bytes that were served), at each position that produced a
    served byte, ``gap`` — how far that byte's logit lies below the
    reference's best — and ``margin``; with ``quant`` also ``control_gap``.
    Layer l's weights are regenerated from the seed in ``weight_dtype`` and
    upcast, one layer at a time; the sequences go through in blocks of
    ``rows``, each padded to ``width`` (one compiled program per cell: causal
    inside a window and summaries only of earlier windows, so padding is
    inert)."""
    import numpy as np

    width = max(max(len(s) for s in sequences), width or 0)
    blocks = []
    for lo in range(0, len(sequences), rows):
        ids = np.zeros((rows, width), np.int32)
        for i, s in enumerate(sequences[lo:lo + rows]):
            ids[i, :len(s)] = s
        blocks.append(jnp.asarray(ids))
    base = W.base_key(seed)
    pos = jnp.broadcast_to(jnp.arange(width)[None], (rows, width))
    embed = jax.jit(lambda table, ids: table.astype(jnp.float32)[ids])
    vocab = cfg["vocab_size"]

    def logits_of(q):
        top = W.top_leaves(base, cfg, weight_dtype)
        xs = [embed(top["embed"], ids) for ids in blocks]
        step = jax.jit(lambda x, lw: block(x, lw, cfg, pos, q))
        for l in range(cfg["num_hidden_layers"]):
            lw = W.layer_slice(base, cfg, l, weight_dtype)
            xs = [step(x, lw) for x in xs]
        head = jax.jit(lambda x, scale, w: mm(
            rms_norm(x, scale, cfg["rms_norm_eps"]), w[:, :vocab], q))
        for x in xs:  # one block's logits at a time: rows x width x vocab
            yield head(x, top["final_norm"], top["lm_head"])

    @jax.jit
    def read(logits, ids):
        top2 = jax.lax.top_k(logits, 2)[0]
        # the byte served after position p is ids[p + 1]
        served = jnp.take_along_axis(logits[:, :-1], ids[:, 1:, None], axis=-1)[..., 0]
        return top2[:, :-1, 0] - served, top2[:, :-1, 0] - top2[:, :-1, 1]

    @jax.jit
    def read_control(logits, low):
        put_first = jnp.take_along_axis(logits, low[..., None], axis=-1)[..., 0]
        return (jnp.max(logits, axis=-1) - put_first)[:, :-1]

    gap, margin, ctl = [], [], []
    # the lower precision's pass first and whole — only the byte it puts
    # first is kept —, so that one pass's activations are held at a time
    # (22 sequences of 16,384 positions are 5.6 GB a pass)
    low = [jnp.argmax(x, axis=-1) for x in logits_of(True)] if quant else None
    for b, (ids, logits) in enumerate(zip(blocks, logits_of(False))):
        g, m = read(logits, ids)
        gap.append(np.asarray(g))
        margin.append(np.asarray(m))
        if quant:
            ctl.append(np.asarray(read_control(logits, low[b])))
    out = {"gap": [], "margin": [], "control_gap": []}
    for i, (s, p) in enumerate(zip(sequences, prompt_lens)):
        # positions p-1 .. len(s)-2 produced the served bytes s[p:]
        b, r, span = i // rows, i % rows, slice(p - 1, len(s) - 1)
        out["gap"].append(gap[b][r, span])
        out["margin"].append(margin[b][r, span])
        if quant:
            out["control_gap"].append(ctl[b][r, span])
    return out

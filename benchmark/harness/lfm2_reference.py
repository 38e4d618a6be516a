"""The plain reference of the hybrid stack the ``lfm2-*`` configurations
describe (LFM2-8B-A1B, ``model_type`` ``lfm2_moe``), in straightforward
``jax.numpy`` and float32 under ``highest`` matmul precision: no kernel, no
cache, no scan over layers (one over a layer's held experts, so that their
body compiles once), nothing imported from the program.

    RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * w;   x0 = E[ids]
    h = x + Op(RMSNorm_op(x));   y = h + FF(RMSNorm_ffn(h))
    Op "conv":  (B, C, X) = split3(W_in x^);  z = B * X;
                c_t = sum_j k[j] * z_{t-(L-1)+j}, z_{<0} = 0;  W_out (C * c)
    Op "full_attention":  q, k, v = W_q x^, W_k x^, W_v x^;  q, k <- RMSNorm
                over head_dim, then rope (rotate-half, theta);  causal
                softmax(q k^T / sqrt(d)) v, each KV head serving H/Hkv heads
    FF dense:   W2 (silu(W1 x^) * W3 x^)
    FF experts: s = sigmoid(W_r x^);  sel = top-k of (s + expert_bias);
                w = s[sel];  w <- w / (sum w + 1e-6) (norm_topk_prob);
                w <- w * routed_scaling_factor;
                FF = sum over the k with sel_k HELD HERE of w_k Expert_{sel_k}(x^)
    logits = RMSNorm_final(x_L) E^T (tied);  loss = mean next-token CE

**The share.** The configuration holds experts ``[expert_offset,
expert_offset + num_experts)`` of a router ``router_width`` wide: the sum
runs over the choices that fall there, w is normalised over all k choices, a
token with no choice here gets 0 from FF. Every held expert is computed for
EVERY token and masked by its weight (zero where it was not chosen).

Departures from the published description: ``expert_bias`` is published as a
buffer that an unstated rule updates outside the optimizer; here it is a
parameter drawn from the seed whose gradient is exactly zero (top-k's indices
carry none) and which AdamW's weight decay shrinks, in the program and here
alike. ``tie_word_embeddings`` and ``head_dim`` = hidden / heads are the
family's conventions (``assumed`` in the configuration file).

So that one 4096-token row fits beside the training state, attention is
computed one KV head (with its query heads) at a time, each under
``jax.checkpoint``: blocks of the same arithmetic, nothing approximated.

``quant`` switches the CONTROL on, as in ``reference.py``: the operands of
every matrix multiplication with a weight are rounded to int8. The router
stays in float32 — an int8 deployment keeps it so, and a control that failed
by scrambled routing would prove nothing about the limits.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import lfm2_weights as W
from . import reference as dense_reference
from .reference import HIGHEST, mm, rms_norm, rope  # noqa: F401


def short_conv(x, lw, cfg, quant=False):
    """The gated short convolution; x: (B, S, h) normalised."""
    taps = cfg["conv_L_cache"]
    gate_b, gate_c, xs = jnp.split(mm(x, lw["conv/in_proj/kernel"], quant), 3, axis=-1)
    z = gate_b * xs
    s = z.shape[1]
    padded = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    kernel = lw["conv/conv1d/kernel"].astype(jnp.float32)
    c = sum(kernel[j] * padded[:, j:j + s] for j in range(taps))
    return mm(gate_c * c, lw["conv/out_proj/kernel"], quant)


@jax.checkpoint
def _attend_one_kv_head(q, k, v):
    """q: (B, S, G, D) — the G query heads of one KV head; k, v: (B, S, D)."""
    s, d = q.shape[1], q.shape[-1]
    scores = jnp.einsum("bqgd,bkd->bgqk", q, k, precision=HIGHEST) * d ** -0.5
    keep = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(keep[None, None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("bgqk,bkd->bqgd", probs, v, precision=HIGHEST)


def attention_op(x, lw, cfg, positions, quant=False):
    b, s, _ = x.shape
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // nh
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    q = mm(x, lw["attn/q_proj/kernel"], quant).reshape(b, s, nh, d)
    k = mm(x, lw["attn/k_proj/kernel"], quant).reshape(b, s, nkv, d)
    v = mm(x, lw["attn/v_proj/kernel"], quant).reshape(b, s, nkv, d)
    q = rope(rms_norm(q, lw["attn/q_norm/scale"], eps), positions, theta)
    k = rope(rms_norm(k, lw["attn/k_norm/scale"], eps), positions, theta)
    q = q.reshape(b, s, nkv, nh // nkv, d)
    out = jnp.stack([_attend_one_kv_head(q[:, :, g], k[:, :, g], v[:, :, g])
                     for g in range(nkv)], axis=2)
    return mm(out.reshape(b, s, nh * d), lw["attn/o_proj/kernel"], quant)


def swiglu(x, w1, w3, w2, quant=False):
    return mm(jax.nn.silu(mm(x, w1, quant)) * mm(x, w3, quant), w2, quant)


def route(x, lw, cfg):
    """(sel, w): the k choices among the router's outputs and their weights."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(mm(x, lw["moe/router/kernel"]))
    choice = s + lw["moe/expert_bias"] if cfg["use_expert_bias"] else s
    sel = jax.lax.top_k(choice, k)[1]
    w = jnp.take_along_axis(s, sel, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return sel, w * cfg["routed_scaling_factor"]


def experts_ff(x, lw, cfg, quant=False):
    """The held experts' part of the expert layer's result: the experts one
    after the other, in their order (a ``lax.scan`` over the stacked weights:
    one body to compile, the sums in the order a Python loop would make;
    each expert under ``jax.checkpoint`` as each attention head is, or the
    backward pass keeps every expert's activations: 14.5 GB at the cell's
    size against 7.7 so)."""
    sel, w = route(x, lw, cfg)

    def add_expert(out, expert):
        e, w1, w3, w2 = expert
        # this expert's weight per token: zero where it was not chosen
        w_e = jnp.sum(jnp.where(sel == cfg["expert_offset"] + e, w, 0.0), axis=-1)
        return out + w_e[..., None] * swiglu(x, w1, w3, w2, quant), None

    return jax.lax.scan(jax.checkpoint(add_expert), jnp.zeros_like(x), (
        jnp.arange(cfg["num_experts"]), lw["moe/gate_proj"], lw["moe/up_proj"],
        lw["moe/down_proj"]))[0]


def block(x, lw, cfg, kind, positions, quant=False):
    """One layer; ``lw`` is ``lfm2_weights.layer_view``'s flat dict."""
    op, ff = kind
    eps = cfg["norm_eps"]
    if op == "conv":
        h = x + short_conv(rms_norm(x, lw["conv_norm/scale"], eps), lw, cfg, quant)
    else:
        h = x + attention_op(rms_norm(x, lw["attn_norm/scale"], eps), lw, cfg,
                             positions, quant)
    y = rms_norm(h, lw["mlp_norm/scale"], eps)
    if ff == "mlp":
        return h + swiglu(y, lw["mlp/gate_proj/kernel"], lw["mlp/up_proj/kernel"],
                          lw["mlp/down_proj/kernel"], quant)
    return h + experts_ff(y, lw, cfg, quant)


def forward(params, cfg, ids, quant=False):
    """Logits (B, S, V) from the program-shaped tree ``params``, walking the
    layers in a Python loop."""
    table = params["embed"]["embedding"].astype(jnp.float32)
    x = table[ids]
    pos = jnp.broadcast_to(jnp.arange(ids.shape[1])[None], ids.shape)
    for l, kind in enumerate(W.layer_kinds(cfg)):
        x = block(x, W.layer_view(params, cfg, l), cfg, kind, pos, quant)
    x = rms_norm(x, params["final_norm"]["scale"], cfg["norm_eps"])
    head = table.T if cfg.get("tie_word_embeddings") else params["lm_head"]["kernel"]
    return mm(x, head, quant)


def loss(params, cfg, ids, quant=False):
    """Mean next-token cross-entropy over every position but the last."""
    logits = forward(params, cfg, ids, quant)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)


def train_reference(cfg, opt, seed, batches, quant=False, rows_per_block=1,
                    sharding=None):
    """Follow the first ``len(batches)`` optimizer steps from the seed, as
    ``reference.train_reference`` does for the dense decoder (same AdamW
    after a global-norm clip, same outputs): gradients of a step are the
    mean over row blocks, the second moment waits on the host between steps.
    """
    import numpy as np

    params = W.make_tree(cfg, seed, jnp.float32, out_shardings=sharding)
    lr, b1, b2 = opt["lr"], opt["b1"], opt["b2"]
    eps, wd, clip = opt["eps"], opt["weight_decay"], opt["max_grad_norm"]

    @functools.partial(jax.jit, donate_argnums=1)
    def acc_step(p, acc, ids):
        l, g = jax.value_and_grad(lambda p: loss(p, cfg, ids, quant))(p)
        return l, jax.tree.map(jnp.add, acc, g)

    @functools.partial(jax.jit, donate_argnums=0)
    def clip_grads(g, n_blocks):
        g = jax.tree.map(lambda x: x / n_blocks, g)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, clip / (norm + 1e-6))
        return jax.tree.map(lambda x: x * scale, g), norm

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3), static_argnums=4)
    def adam_leaf(p, g, m, v, t):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        return p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p), m, v

    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    mu = nu_host = None
    out = {"loss": [], "grad_norm": []}
    for t, ids in enumerate(batches, start=1):
        ids = jnp.asarray(ids)
        blocks = [ids[i:i + rows_per_block]
                  for i in range(0, ids.shape[0], rows_per_block)]
        total, grads = 0.0, zeros(params)
        for blk in blocks:
            l, grads = acc_step(params, grads, blk)
            total += float(l)
        out["loss"].append(total / len(blocks))
        grads, gnorm = clip_grads(grads, float(len(blocks)))
        out["grad_norm"].append(float(gnorm))
        if t == 1:
            out["first_grad_leaf_norms"] = {
                k: float(v) for k, v in leaf_norms(grads).items()}
        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_g = jax.tree.leaves(grads)
        flat_m = mu if mu is not None else [None] * len(flat_p)
        del params, grads
        last = t == len(batches)
        new_p, mu, new_v = [], [], []
        for i in range(len(flat_p)):
            m = flat_m[i] if flat_m[i] is not None else jnp.zeros_like(flat_p[i])
            v = (jnp.zeros_like(flat_p[i]) if nu_host is None
                 else jax.device_put(nu_host[i], flat_p[i].sharding))
            p, m, v = adam_leaf(flat_p[i], flat_g[i], m, v, t)
            flat_p[i] = flat_g[i] = flat_m[i] = None
            new_p.append(p)
            mu.append(None if last else m)
            new_v.append(None if last else np.asarray(v))
            del m, v
        nu_host = new_v
        params = jax.tree_util.tree_unflatten(treedef, new_p)
    del mu, nu_host
    out["param_change_leaf_norms"] = param_change_leaf_norms(cfg, seed, params)
    return out


def leaf_norms(tree) -> dict:
    """The norm of every leaf of a gradient, keyed as ``reference.leaf_norms``
    keys them, WITHOUT the router and the expert stacks of the first expert
    layer where that is a layer of its own (``layer_<i>``). Every occurrence
    of a frequent token reaches that layer with nearly the same input (only
    the embedding and the dense layers lie before it), so one near-tied
    top-k choice that falls differently in bfloat16 and in float32 moves
    thousands of rows at once: sound runs read 0.08-0.29 there and at most
    0.0151 on every other leaf, where the int8 control reads 0.12-0.14
    (PERF.md section 4). The later expert layers run the same code and stay
    in; ``param_change_leaf_norms`` keeps every leaf."""
    def has_experts(node):
        return hasattr(node, "keys") and (
            "moe" in node or any(has_experts(node[k]) for k in node))

    norms = dense_reference.leaf_norms(tree)
    # segments are named by the index of their first layer
    first = min((int(name.split("_")[1]), name) for name in tree
                if name.startswith("layer") and has_experts(tree[name]))[1]
    if first.startswith("layer_"):
        norms = {k: v for k, v in norms.items()
                 if not k.startswith(f"['{first}']['moe']")}
    return norms


def param_change_leaf_norms(cfg, seed, params) -> dict:
    """Per leaf of ``params`` (the program's or the reference's own), keyed
    as ``leaf_norms`` keys them: the norm of its change since the seed, the
    seed's value regenerated one layer at a time."""
    base, change = W.base_key(seed), {}

    # the key is an ARGUMENT: closed over, every seed would compile its own
    @functools.partial(jax.jit, static_argnums=(0,))
    def one(i, key, x, layer):
        row = table[i]
        return jnp.sum(jnp.square(
            x.astype(jnp.float32) - W.make_leaf(key, row, jnp.float32, cfg, layer)))

    table = W.leaf_table(cfg)
    for i, row in enumerate(table):
        node = params
        for part in row["path"]:
            node = node[part]
        if row["layers"] is None:
            total = one(i, base, node, 0)
        elif not row["stacked"]:
            total = one(i, base, node, row["layers"][0])
        else:
            total = sum(one(i, base, node[k], l) for k, l in enumerate(row["layers"]))
        change["".join(f"['{p}']" for p in row["path"])] = float(jnp.sqrt(total))
    return change


def served_token_gaps(*_args, **_kwargs):
    raise NotImplementedError(
        "no serve cell can take this stack yet: a convolution layer's state "
        "beside the KV cache is not in the serving engine (ROADMAP Reach A4)")

"""The plain reference of the dense decoder the configurations describe
(Mistral-7B-v0.1: RMSNorm, rotary GQA attention under a causal sliding band,
SwiGLU, untied head), in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no scan, no
batching tricks, and nothing imported from the program. Departures from the
published description: none; rotary pairs element i with i + D/2 (the
``rotate_half`` layout of the published checkpoints).

``quant`` switches the CONTROL on: every matrix multiplication's operands are
rounded to symmetric int8 (activations per row, weights per output column)
before a float32 product — the W8A8 step below bf16 that would tempt a later
PR. The benchmark's own runs never set it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import weights as W

HIGHEST = "highest"


def _fq(x, axis):
    """Fake symmetric int8: round to 127 levels of the absmax along axis."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s) * s


@jax.custom_vjp
def _mm_int8(x, w):
    return jnp.matmul(_fq(x, -1), _fq(w, 0), precision=HIGHEST)


def _mm_int8_fwd(x, w):
    return _mm_int8(x, w), (x, w)


def _mm_int8_bwd(res, dy):
    # the backward products take int8 operands too, as an int8 step would
    x, w = res
    dyq = _fq(dy, -1)
    dx = jnp.matmul(dyq, _fq(w, 0).T, precision=HIGHEST)
    x2 = _fq(x, -1).reshape(-1, x.shape[-1])
    dw = jnp.matmul(x2.T, dyq.reshape(-1, dy.shape[-1]), precision=HIGHEST)
    return dx, dw


_mm_int8.defvjp(_mm_int8_fwd, _mm_int8_bwd)


def mm(x, w, quant=False):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant:
        return _mm_int8(x, w)
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, positions, theta):
    """x: (B, S, H, D), positions: (B, S)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, :, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, window):
    """(B, S, H, D) x (B, S, Hkv, D): full causal softmax under the band."""
    b, s, h, d = q.shape
    g = h // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) * d ** -0.5
    rows = jnp.arange(s)[:, None]
    cols = jnp.arange(s)[None, :]
    keep = cols <= rows
    if window:
        keep = keep & (cols > rows - window)
    scores = jnp.where(keep[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST)


def block(x, lw, cfg, positions, quant=False):
    """One decoder layer; ``lw`` is weights.layer_slice's flat dict."""
    b, s, _ = x.shape
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // nh
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    y = rms_norm(x, lw["attn_norm"], eps)
    q = mm(y, lw["attn/q_proj"], quant).reshape(b, s, nh, d)
    k = mm(y, lw["attn/k_proj"], quant).reshape(b, s, nkv, d)
    v = mm(y, lw["attn/v_proj"], quant).reshape(b, s, nkv, d)
    q, k = rope(q, positions, theta), rope(k, positions, theta)
    a = attention(q, k, v, cfg.get("sliding_window")).reshape(b, s, nh * d)
    x = x + mm(a, lw["attn/o_proj"], quant)
    y = rms_norm(x, lw["mlp_norm"], eps)
    gate, up = mm(y, lw["mlp/gate_proj"], quant), mm(y, lw["mlp/up_proj"], quant)
    return x + mm(jax.nn.silu(gate) * up, lw["mlp/down_proj"], quant)


def forward(params, cfg, ids, quant=False):
    """Logits (B, S, V) from the program-shaped tree ``params`` (stacked
    layers), walking the layers in a Python loop."""
    x = params["embed"]["embedding"].astype(jnp.float32)[ids]
    pos = jnp.broadcast_to(jnp.arange(ids.shape[1])[None], ids.shape)
    lay = params["layers"]
    for l in range(cfg["num_hidden_layers"]):
        lw = {
            "attn_norm": lay["attn_norm"]["scale"][l],
            "mlp_norm": lay["mlp_norm"]["scale"][l],
            **{f"attn/{n}": lay["attn"][n]["kernel"][l]
               for n in ("q_proj", "k_proj", "v_proj", "o_proj")},
            **{f"mlp/{n}": lay["mlp"][n]["kernel"][l]
               for n in ("gate_proj", "up_proj", "down_proj")},
        }
        x = block(x, lw, cfg, pos, quant)
    x = rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return mm(x, params["lm_head"]["kernel"], quant)


def loss(params, cfg, ids, quant=False):
    """Mean next-token cross-entropy over every position but the last."""
    logits = forward(params, cfg, ids, quant)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)


# --------------------------------------------------------------------------- #
# training: three AdamW steps, rows one block at a time, mu on the device and
# nu on the host so that params + grads + one moment is all the chip holds
# --------------------------------------------------------------------------- #
def leaf_norms(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for p, x in flat}


def train_reference(cfg, opt, seed, batches, quant=False, rows_per_block=1,
                    sharding=None):
    """Follow the first ``len(batches)`` optimizer steps from the seed.

    ``opt``: lr, b1, b2, eps, weight_decay, max_grad_norm (optax.adamw after
    a global-norm clip of min(1, max/(norm + 1e-6))). Returns per step the
    loss, and per leaf the norm of the first clipped gradient and of
    params_after_last - params_at_seed. Gradients of a step are the mean
    over row blocks (the loss is a mean over equally long rows). The second
    moment waits on the host between steps: params, the gradient sum and the
    first moment are then all that the chip holds beside one block's
    activations.
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


def param_change_leaf_norms(cfg, seed, params) -> dict:
    """Per leaf of ``params`` (the program's or the reference's own), keyed
    as ``leaf_norms`` keys them: the norm of its change since the seed."""
    base, change = W.base_key(seed), {}
    for row in W.leaf_table(cfg):
        node = params
        for part in row["path"]:
            node = node[part]
        key = "".join(f"['{p}']" for p in row["path"])
        change[key] = float(param_change_norm(
            base, row, node, cfg["num_hidden_layers"]))
    return change


def param_change_norm(base, row, leaf, layers):
    """||leaf - its value at the seed||, the seed's value regenerated one
    layer at a time so that no second copy of a stacked leaf is held."""
    # the key is an ARGUMENT: closed over, it would be a constant of the
    # program, and every seed would compile its own
    @jax.jit
    def one(key, x, l):
        return jnp.sum(jnp.square(
            x.astype(jnp.float32) - W.make_leaf(key, row, jnp.float32, l)))

    if not row["stacked"]:
        return jnp.sqrt(one(base, leaf, 0))
    return jnp.sqrt(sum(one(base, leaf[l], l) for l in range(layers)))


# --------------------------------------------------------------------------- #
# serving: one pass over prompt + served tokens, one layer's weights at a time
# --------------------------------------------------------------------------- #
def served_token_gaps(cfg, seed, sequences, prompt_lens, weight_dtype,
                      quant=False, rows=8, width=None):
    """For each sequence (prompt followed by the tokens that were served),
    at each position that produced a served token: ``gap``, how far that
    token's logit lies below the reference's best, and ``margin``, how far
    the reference's second-best lies below its best (how near a tie the
    position was). Layer l's weights are regenerated from the seed in
    ``weight_dtype`` and upcast, one layer at a time; the sequences go
    through in blocks of ``rows``, each padded to ``width`` (one compiled
    program per cell: causal, so padding is inert).

    With ``quant`` the pass is repeated as the control's, and
    ``control_gap`` is the gap of the token the lower precision puts first,
    against the full-precision best. Returns a dict of lists of numpy arrays,
    one array per sequence.
    """
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

    def logits_of(q):
        top = W.top_leaves(base, cfg, weight_dtype)
        xs = [embed(top["embed"], ids) for ids in blocks]
        step = jax.jit(lambda x, lw: block(x, lw, cfg, pos, q))
        for l in range(cfg["num_hidden_layers"]):
            lw = W.layer_slice(base, cfg, l, weight_dtype)
            xs = [step(x, lw) for x in xs]
        head = jax.jit(lambda x, scale, w: mm(
            rms_norm(x, scale, cfg["rms_norm_eps"]), w, q))
        for x in xs:  # one block's logits at a time: rows x width x vocab
            yield head(x, top["final_norm"], top["lm_head"])

    @jax.jit
    def read(logits, ids):
        top2 = jax.lax.top_k(logits, 2)[0]
        # the token served after position p is ids[p + 1]
        served = jnp.take_along_axis(logits[:, :-1], ids[:, 1:, None], axis=-1)[..., 0]
        return top2[:, :-1, 0] - served, top2[:, :-1, 0] - top2[:, :-1, 1]

    @jax.jit
    def read_control(logits, low_logits):
        low = jnp.argmax(low_logits, axis=-1)
        put_first = jnp.take_along_axis(logits, low[..., None], axis=-1)[..., 0]
        return (jnp.max(logits, axis=-1) - put_first)[:, :-1]

    gap, margin, ctl = [], [], []
    low_blocks = logits_of(True) if quant else None
    for ids, logits in zip(blocks, logits_of(False)):
        g, m = read(logits, ids)
        gap.append(np.asarray(g))
        margin.append(np.asarray(m))
        if quant:
            ctl.append(np.asarray(read_control(logits, next(low_blocks))))
    out = {"gap": [], "margin": [], "control_gap": []}
    for i, (s, p) in enumerate(zip(sequences, prompt_lens)):
        # positions p-1 .. len(s)-2 produced the served tokens s[p:]
        b, r, span = i // rows, i % rows, slice(p - 1, len(s) - 1)
        out["gap"].append(gap[b][r, span])
        out["margin"].append(margin[b][r, span])
        if quant:
            out["control_gap"].append(ctl[b][r, span])
    return out

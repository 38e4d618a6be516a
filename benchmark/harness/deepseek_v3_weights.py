"""Seeded random weights of the stack the ``deepseek-v3-*`` configurations
describe (``model_type`` ``deepseek_v3``): every layer multi-head LATENT
attention (``q_a_proj`` -> ``q_a_norm`` -> ``q_b_proj``; ``kv_a_proj`` ->
``kv_a_norm`` -> ``kv_b_proj``, ONE leaf that holds every head's ``[k_nope |
v]`` columns; ``o_proj``), the first ``first_k_dense_replace`` layers a dense
SwiGLU, the others sigmoid-routed SwiGLU experts with a selection bias
(``moe/expert_bias``, the checkpoint's ``e_score_correction_bias``) and a
shared expert; plain RMSNorms; an untied head.

As ``qwen3_next_weights.py``: the benchmark makes the weights and the plain
reference regenerates them from the same keys; every (leaf, layer) has a key
of its own, fold_in(fold_in(base(seed), crc32(leaf name)), layer), and every
EXPERT one under that by its index among the router's published outputs, so
the experts ``[expert_offset, expert_offset + n_routed_experts)`` a share holds
are the arrays the uncut layer holds there, and the shares add up.

Distributions (``assumed`` in the configuration file): kernels normal
1/sqrt(fan_in) — ``o_proj`` a QUARTER of that: at the full scale attention's
output, an average over the values of thousands of positions whose inputs it
has itself made alike, is a tenth of the router's input in layer 1 and a fifth
in layer 4 COMMON to all tokens, the router has favourites that hang on the
seed, and the load on a share of half a group ranges over 0.7-1.3 x the even
share a layer (PERF.md, PR 40) —, embedding normal 0.02, every norm weight normal 0.1 around
ONE (a weight of exactly 1 would hide a program that left a norm out), the
selection bias normal 0.01 around zero (a bias of exactly 0 would hide a
program that weighted by the biased score; at 0.1 the bias DECIDES the choice —
the two largest sigmoid scores of a group of 32 lie ~0.05 apart — and the load
on a share of half a group then hangs on the seed: PERF.md, PR 40). The router, its bias and every
norm weight stay float32 whatever ``dtype``, as the program declares them.
The configuration states ``scan_layers`` false: every layer is a module of its
own (``layer_<i>``) and no leaf is stacked. This module imports nothing of the
program; ``tests/test_deepseek_v3.py`` holds the two trees against each other.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .lfm2_weights import _leaf_key, spread_shardings  # noqa: F401
from .weights import _nest, base_key  # noqa: F401

_FLOAT32 = ("unit_scale", "router", "bias")


def layer_kinds(cfg: dict) -> list[str]:
    """Per layer its feed-forward: "mlp" in the leading dense layers."""
    return ["mlp" if l < cfg["first_k_dense_replace"] else "moe"
            for l in range(cfg["num_hidden_layers"])]


def layer_leaves(cfg: dict, kind: str) -> list[tuple[tuple, tuple, str]]:
    """(name inside the layer, shape, how it is drawn) of one layer."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rot, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    rows = [(("attn_norm", "scale"), (h,), "unit_scale"),
            (("attn", "q_a_proj", "kernel"), (h, ql), "kernel"),
            (("attn", "q_a_norm", "scale"), (ql,), "unit_scale"),
            (("attn", "q_b_proj", "kernel"), (ql, heads * (nope + rot)), "kernel"),
            (("attn", "kv_a_proj", "kernel"), (h, kvl + rot), "kernel"),
            (("attn", "kv_a_norm", "scale"), (kvl,), "unit_scale"),
            (("attn", "kv_b_proj", "kernel"), (kvl, heads * (nope + dv)), "kernel"),
            (("attn", "o_proj", "kernel"), (heads * dv, h), "quarter_kernel"),
            (("mlp_norm", "scale"), (h,), "unit_scale")]
    if kind == "mlp":
        f = cfg["intermediate_size"]
        return rows + [(("mlp", name, "kernel"), shape, "kernel") for name, shape in (
            ("gate_proj", (h, f)), ("up_proj", (h, f)), ("down_proj", (f, h)))]
    f = cfg["moe_intermediate_size"]
    fs = f * cfg["n_shared_experts"]
    n, width = cfg["n_routed_experts"], cfg["router_width"]
    rows += [(("moe", "router", "kernel"), (h, width), "router"),
             (("moe", "expert_bias"), (width,), "bias")]
    rows += [(("moe", name), (n,) + shape, "experts") for name, shape in (
        ("gate_proj", (h, f)), ("up_proj", (h, f)), ("down_proj", (f, h)))]
    rows += [(("moe", "shared", name, "kernel"), shape, "kernel")
             for name, shape in (("gate_proj", (h, fs)), ("up_proj", (h, fs)),
                                 ("down_proj", (fs, h)))]
    return rows


def leaf_table(cfg: dict) -> list[dict]:
    """Every parameter, as the program's tree holds it (``lfm2_weights``'s
    rows: ``path``, ``name``, ``shape``, ``kind``, ``layers``, ``stacked``)."""
    if cfg.get("scan_layers", True):
        raise NotImplementedError("written for scan_layers false: layer_<i>")
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    rows = [dict(path=("embed", "embedding"), name=("embed", "embedding"),
                 shape=(v, h), kind="embed", layers=None, stacked=False),
            dict(path=("final_norm", "scale"), name=("final_norm", "scale"),
                 shape=(h,), kind="unit_scale", layers=None, stacked=False),
            dict(path=("lm_head", "kernel"), name=("lm_head", "kernel"),
                 shape=(h, v), kind="kernel", layers=None, stacked=False)]
    for l, kind in enumerate(layer_kinds(cfg)):
        for name, shape, how in layer_leaves(cfg, kind):
            rows.append(dict(path=(f"layer_{l}",) + name, name=name, shape=shape,
                             kind=how, layers=[l], stacked=False))
    return rows


def _leaf_dtype(kind: str, dtype):
    return jnp.float32 if kind in _FLOAT32 else dtype


def _draw(key, shape, kind, dtype, cfg):
    dtype = _leaf_dtype(kind, dtype)
    if kind == "unit_scale":
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if kind == "bias":
        return 0.01 * jax.random.normal(key, shape, jnp.float32)
    if kind == "experts":  # one key per expert, by its published index
        ids = cfg["expert_offset"] + jnp.arange(shape[0])
        return jax.vmap(lambda e: _draw(
            jax.random.fold_in(key, e), shape[1:], "kernel", dtype, cfg))(ids)
    std = 0.02 if kind == "embed" else shape[-2] ** -0.5  # fan-in
    if kind == "quarter_kernel":
        std *= 0.25
    return (std * jax.random.normal(key, shape, dtype)).astype(dtype)


def make_leaf(base, row: dict, dtype, cfg: dict, layer: int | None = None):
    """One leaf outside the layers, or ``layer``'s own."""
    key = _leaf_key(base, row["name"])
    if row["layers"] is not None:
        key = jax.random.fold_in(key, layer)
    return _draw(key, row["shape"], row["kind"], dtype, cfg)


def abstract_tree(cfg: dict, dtype, sharding=None):
    """``make_tree``'s shapes and types without the values."""
    return _nest({
        row["path"]: jax.ShapeDtypeStruct(
            tuple(row["shape"]), _leaf_dtype(row["kind"], dtype), sharding=sharding)
        for row in leaf_table(cfg)})


def make_tree(cfg: dict, seed: int, dtype, out_shardings=None):
    """The whole tree the program takes, in one jitted call from the seed."""
    table = leaf_table(cfg)

    def build(base):
        return _nest({row["path"]: make_leaf(
            base, row, dtype, cfg, row["layers"] and row["layers"][0])
            for row in table})

    return jax.jit(build, out_shardings=out_shardings)(base_key(seed))


def layer_view(params: dict, cfg: dict, layer: int) -> dict:
    """Layer ``layer``'s leaves out of a program-shaped tree, flat by their
    names inside the layer (``attn/kv_b_proj/kernel``): the reference's walk."""
    out = {}
    for row in leaf_table(cfg):
        if row["layers"] != [layer]:
            continue
        node = params
        for part in row["path"]:
            node = node[part]
        out["/".join(row["name"])] = node
    return out


def layer_slice(base, cfg: dict, layer: int, dtype) -> dict:
    """One layer's weights regenerated from the seed, flat as ``layer_view``."""
    return {"/".join(row["name"]): make_leaf(base, row, dtype, cfg, layer)
            for row in leaf_table(cfg) if row["layers"] == [layer]}


def top_leaves(base, cfg: dict, dtype) -> dict:
    return {row["path"][0]: make_leaf(base, row, dtype, cfg)
            for row in leaf_table(cfg) if row["layers"] is None}


# the leaves the runner shows to be the program's own: what fills the latent
# cache, what reads it back in both forms, and a layer's held experts
_PROBED = ("attn/kv_a_proj/kernel", "attn/kv_b_proj/kernel", "moe/down_proj")


def probe(params: dict, cfg: dict, seed: int, dtype) -> float:
    """The reference regenerates the weights from the seed: how far the
    program's tree lies from that on the LAST layer, leaf by probed leaf, as
    the largest error over the largest value. To a rounding: a fused draw may
    differ from a lone one in the last place."""
    base, worst = base_key(seed), 0.0
    last = cfg["num_hidden_layers"] - 1
    for row in leaf_table(cfg):
        if "/".join(row["name"]) not in _PROBED or row["layers"] != [last]:
            continue
        mine = params
        for part in row["path"]:
            mine = mine[part]
        mine = mine.astype(jnp.float32)
        again = make_leaf(base, row, dtype, cfg, last).astype(jnp.float32)
        worst = max(worst, float(
            jnp.max(jnp.abs(mine - again)) / jnp.max(jnp.abs(again))))
    return worst

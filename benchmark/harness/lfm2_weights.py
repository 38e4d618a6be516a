"""Seeded random weights of the hybrid stack the ``lfm2-*`` configurations
describe: layers whose operator is a gated short convolution or GQA
attention (``layer_types``), ``num_dense_layers`` leading SwiGLU layers and
sigmoid-routed expert layers after them, a tied head.

As ``weights.py`` does for the dense decoder, the benchmark makes the weights
and the plain reference regenerates them from the same keys. Every (leaf,
layer) has a key of its own, whatever the program's tree stacks together:
fold_in(fold_in(base(seed), crc32(leaf name)), layer), and every EXPERT one
under that, by its index among the router's published outputs — so the
experts ``[expert_offset, expert_offset + num_experts)`` a share holds are the
same arrays the uncut layer holds there, and the shares add up.

Distributions: normal 1/sqrt(fan_in) kernels (the convolution's fan-in is
its ``conv_L_cache`` taps), normal 0.02 embedding and ``expert_bias`` (the
published model keeps it as a buffer that a rule outside the optimizer
updates; here it is a seeded parameter, non-zero so that it moves choices),
unit norm scales. This module imports nothing of the program: ``segments``
restates the rule by which the program cuts a list of layer kinds into
scans and single layers, and ``tests/test_hybrid_moe.py`` holds the two
trees against each other.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from .weights import _nest, base_key  # noqa: F401 - base_key is part of the contract


def layer_kinds(cfg: dict) -> list[tuple[str, str]]:
    """Per layer (operator, feed-forward): ``layer_types`` and "mlp" for the
    leading dense layers, "moe" after them."""
    return [(op, "mlp" if l < cfg["num_dense_layers"] else "moe")
            for l, op in enumerate(cfg["layer_types"])]


def segments(kinds: list) -> list[tuple[int, tuple, int]]:
    """(first layer, period, repeats), left to right: at each position the
    period whose consecutive repeats cover most layers (the shorter on a
    tie), else one layer alone."""
    out, i, n = [], 0, len(kinds)
    while i < n:
        best = (1, 1)
        for p in range(1, (n - i) // 2 + 1):
            r = 1
            while kinds[i + r * p:i + (r + 1) * p] == kinds[i:i + p]:
                r += 1
            if r >= 2 and p * r > best[0] * best[1]:
                best = (p, r)
        out.append((i, tuple(kinds[i:i + best[0]]), best[1]))
        i += best[0] * best[1]
    return out


def layer_leaves(cfg: dict, kind: tuple[str, str]) -> list[tuple[tuple, tuple, str]]:
    """(name inside the layer, shape, how it is drawn) of one layer."""
    h = cfg["hidden_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    op, ff = kind
    rows = [(("mlp_norm", "scale"), (h,), "scale")]
    if op == "conv":
        rows += [
            (("conv_norm", "scale"), (h,), "scale"),
            (("conv", "in_proj", "kernel"), (h, 3 * h), "kernel"),
            (("conv", "conv1d", "kernel"), (cfg["conv_L_cache"], h), "kernel"),
            (("conv", "out_proj", "kernel"), (h, h), "kernel"),
        ]
    else:
        rows += [(("attn_norm", "scale"), (h,), "scale"),
                 (("attn", "q_norm", "scale"), (d,), "scale"),
                 (("attn", "k_norm", "scale"), (d,), "scale")]
        rows += [(("attn", name, "kernel"), shape, "kernel") for name, shape in (
            ("q_proj", (h, q)), ("k_proj", (h, kv)), ("v_proj", (h, kv)),
            ("o_proj", (q, h)))]
    if ff == "mlp":
        f = cfg["intermediate_size"]
        rows += [(("mlp", name, "kernel"), shape, "kernel") for name, shape in (
            ("gate_proj", (h, f)), ("up_proj", (h, f)), ("down_proj", (f, h)))]
    else:
        f, n, width = cfg["moe_intermediate_size"], cfg["num_experts"], cfg["router_width"]
        rows += [(("moe", "router", "kernel"), (h, width), "kernel"),
                 (("moe", "expert_bias"), (width,), "small")]
        rows += [(("moe", name), (n,) + shape, "experts") for name, shape in (
            ("gate_proj", (h, f)), ("up_proj", (h, f)), ("down_proj", (f, h)))]
    return rows


def leaf_table(cfg: dict) -> list[dict]:
    """Every parameter, as the program's tree holds it: ``path``, the
    ``shape`` of ONE layer's part, how it is drawn (``kind``), the leaf's
    ``name`` inside its layer (its key), and ``layers``: the layers stacked
    along the leading axis, in order; one layer and ``stacked`` False for a
    layer that stands alone; None for what lies outside the layers."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    rows = [dict(path=("embed", "embedding"), name=("embed", "embedding"),
                 shape=(v, h), kind="embed", layers=None, stacked=False),
            dict(path=("final_norm", "scale"), name=("final_norm", "scale"),
                 shape=(h,), kind="scale", layers=None, stacked=False)]
    if not cfg.get("tie_word_embeddings"):
        rows.append(dict(path=("lm_head", "kernel"), name=("lm_head", "kernel"),
                         shape=(h, v), kind="kernel", layers=None, stacked=False))
    for start, period, repeats in segments(layer_kinds(cfg)):
        for j, kind in enumerate(period):
            if repeats == 1:
                prefix = (f"layer_{start}",)
            else:
                prefix = (f"layers_{start}",) + ((f"b{j}",) if len(period) > 1 else ())
            layers = [start + r * len(period) + j for r in range(repeats)]
            for name, shape, how in layer_leaves(cfg, kind):
                rows.append(dict(path=prefix + name, name=name, shape=shape,
                                 kind=how, layers=layers, stacked=repeats > 1))
    return rows


def _leaf_key(base, name: tuple):
    return jax.random.fold_in(base, zlib.crc32("/".join(name).encode()) & 0x7FFFFFFF)


def _draw(key, shape, kind, dtype, cfg):
    if kind == "scale":
        return jnp.ones(shape, jnp.float32)
    if kind == "experts":  # one key per expert, by its published index
        ids = cfg["expert_offset"] + jnp.arange(shape[0])
        return jax.vmap(lambda e: _draw(
            jax.random.fold_in(key, e), shape[1:], "kernel", dtype, cfg))(ids)
    std = 0.02 if kind in ("embed", "small") else shape[-2] ** -0.5
    return (std * jax.random.normal(key, shape, dtype)).astype(dtype)


def make_leaf(base, row: dict, dtype, cfg: dict, layer: int | None = None):
    """One leaf outside the layers, or ``layer``'s part of one inside."""
    key = _leaf_key(base, row["name"])
    if row["layers"] is not None:
        key = jax.random.fold_in(key, layer)
    return _draw(key, row["shape"], row["kind"], dtype, cfg)


def _whole_leaf(base, row: dict, dtype, cfg: dict):
    if row["layers"] is None:
        return make_leaf(base, row, dtype, cfg)
    if not row["stacked"]:
        return make_leaf(base, row, dtype, cfg, row["layers"][0])
    return jax.vmap(lambda l: make_leaf(base, row, dtype, cfg, l))(
        jnp.asarray(row["layers"]))


def tree_shape(cfg: dict, row: dict) -> tuple:
    lead = (len(row["layers"]),) if row["stacked"] else ()
    return lead + tuple(row["shape"])


def _leaf_dtype(row: dict, dtype):
    return jnp.float32 if row["kind"] == "scale" else dtype


def abstract_tree(cfg: dict, dtype, sharding=None):
    """``make_tree``'s shapes and types without the values."""
    return _nest({
        row["path"]: jax.ShapeDtypeStruct(
            tree_shape(cfg, row), _leaf_dtype(row, dtype), sharding=sharding)
        for row in leaf_table(cfg)})


def make_tree(cfg: dict, seed: int, dtype, out_shardings=None):
    """The whole tree the program takes, in one jitted call from the seed."""
    table = leaf_table(cfg)

    def build(base):
        return _nest({row["path"]: _whole_leaf(base, row, dtype, cfg)
                      for row in table})

    return jax.jit(build, out_shardings=out_shardings)(base_key(seed))


def layer_view(params: dict, cfg: dict, layer: int) -> dict:
    """Layer ``layer``'s leaves out of a program-shaped tree, flat by their
    names inside the layer (``conv/in_proj/kernel``): the reference's walk."""
    out = {}
    for row in leaf_table(cfg):
        if row["layers"] is None or layer not in row["layers"]:
            continue
        node = params
        for part in row["path"]:
            node = node[part]
        out["/".join(row["name"])] = (
            node[row["layers"].index(layer)] if row["stacked"] else node)
    return out


def layer_slice(base, cfg: dict, layer: int, dtype) -> dict:
    """One layer's weights regenerated from the seed, flat as ``layer_view``."""
    return {"/".join(row["name"]): make_leaf(base, row, dtype, cfg, layer)
            for row in leaf_table(cfg)
            if row["layers"] is not None and layer in row["layers"]}


def spread_shardings(cfg: dict, devices) -> dict | None:
    """The configuration is one chip's share: the reference runs on one
    chip too. (More chips would split each leaf as ``weights.py`` does.)"""
    if len(devices) > 1:
        raise NotImplementedError(
            "this configuration is one chip's share of its deployment")
    return None

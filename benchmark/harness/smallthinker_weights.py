"""Seeded random weights of the stack the ``smallthinker-*`` configurations
describe (``model_name`` ``smallthinker_21b_instruct``): GQA attention layers —
full attention WITHOUT rope and sliding-window attention WITH rope, by the
published ``sliding_window_layout`` / ``rope_layout`` —, every one followed by
softmax-routed ReGLU experts whose router reads the attention's input; plain
RMSNorms; an untied head.

As ``qwen3_next_weights.py``: the benchmark makes the weights and the plain
reference regenerates them from the same keys; every (leaf, layer) has a key
of its own, fold_in(fold_in(base(seed), crc32(leaf name)), layer), and every
expert one under that by its index.

Distributions (``assumed`` in the configuration file): kernels normal
1/sqrt(fan_in), embedding normal 0.02, norm weights normal 0.1 around ONE
(the norm multiplies by plain ``w``; a weight of exactly 1 would hide a
program that left it out). **``q_proj`` and ``k_proj`` are drawn
sqrt(``qk_gain``) times wider**, so that a head's scores ``q . k /
sqrt(head_dim)`` have a standard deviation of ``qk_gain`` (3.5) where unit
kernels give 1: under N(0, 1) scores attention over thousands of keys is an
average of values and neither a missing band nor rope on the wrong layers
moves a logit (PERF.md, PR 38 and section 7 row 21 g); at 3.5 a softmax over
4,096 keys puts most of its weight on a few dozen, which keys they are turns
on the band and on what turned them, and every planted fault reads on the
CPU at the published widths (``benchmark/tests/smallthinker_faults.py``). The
router and every norm weight stay float32 whatever ``dtype``, as the program
declares them. Where the configuration states ``scan_layers`` false every
layer is a module of its own (``layer_<i>``) and no leaf is stacked; scanned,
the period (full, sliding, sliding, sliding) is one body of blocks ``b0`` ..
``b3``. This module imports nothing of the program: ``lfm2_weights.segments``
restates the rule by which the program cuts a list of layer kinds into scans
and single layers, and ``tests/test_smallthinker.py`` holds the two trees
against each other.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .lfm2_weights import (  # noqa: F401 - parts of the contract
    _leaf_key, segments, spread_shardings, tree_shape)
from .weights import _nest, base_key  # noqa: F401

_FLOAT32 = ("unit_scale", "router")  # never in the compute dtype


def layer_kinds(cfg: dict) -> list[tuple[str, str, bool]]:
    """Per layer (operator, feed-forward, rope): every layer has experts."""
    return [("sliding_attention" if w else "full_attention", "moe", bool(r))
            for w, r in zip(cfg["sliding_window_layout"], cfg["rope_layout"])]


def layer_leaves(cfg: dict) -> list[tuple[tuple, tuple, str]]:
    """(name inside the layer, shape, how it is drawn) of one layer: every
    kind of layer has the same leaves."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    f, n = cfg["moe_ffn_hidden_size"], cfg["moe_num_primary_experts"]
    rows = [(("attn_norm", "scale"), (h,), "unit_scale"),
            (("attn", "q_proj", "kernel"), (h, q), "qk_kernel"),
            (("attn", "k_proj", "kernel"), (h, kv), "qk_kernel"),
            (("attn", "v_proj", "kernel"), (h, kv), "kernel"),
            (("attn", "o_proj", "kernel"), (q, h), "kernel"),
            (("mlp_norm", "scale"), (h,), "unit_scale"),
            (("moe", "router", "kernel"), (h, n), "router")]
    rows += [(("moe", name), (n,) + shape, "experts") for name, shape in (
        ("gate_proj", (h, f)), ("up_proj", (h, f)), ("down_proj", (f, h)))]
    return rows


def leaf_table(cfg: dict) -> list[dict]:
    """Every parameter, as the program's tree holds it (``lfm2_weights``'s
    rows: ``path``, ``name``, one layer's ``shape``, ``kind``, ``layers``,
    ``stacked``)."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    rows = [dict(path=("embed", "embedding"), name=("embed", "embedding"),
                 shape=(v, h), kind="embed", layers=None, stacked=False),
            dict(path=("final_norm", "scale"), name=("final_norm", "scale"),
                 shape=(h,), kind="unit_scale", layers=None, stacked=False),
            dict(path=("lm_head", "kernel"), name=("lm_head", "kernel"),
                 shape=(h, v), kind="kernel", layers=None, stacked=False)]
    kinds = layer_kinds(cfg)
    # ``scan_layers`` false: every layer a module of its own, ``layer_<i>``
    plan = (segments(kinds) if cfg.get("scan_layers", True)
            else [(i, (kind,), 1) for i, kind in enumerate(kinds)])
    for start, period, repeats in plan:
        for j in range(len(period)):
            if repeats == 1:
                prefix = (f"layer_{start}",)
            else:
                prefix = (f"layers_{start}",) + ((f"b{j}",) if len(period) > 1 else ())
            layers = [start + r * len(period) + j for r in range(repeats)]
            for name, shape, how in layer_leaves(cfg):
                rows.append(dict(path=prefix + name, name=name, shape=shape,
                                 kind=how, layers=layers, stacked=repeats > 1))
    return rows


def _leaf_dtype(kind: str, dtype):
    return jnp.float32 if kind in _FLOAT32 else dtype


def _draw(key, shape, kind, dtype, cfg):
    dtype = _leaf_dtype(kind, dtype)
    if kind == "unit_scale":
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if kind == "experts":  # one key per expert, by its index
        return jax.vmap(lambda e: _draw(
            jax.random.fold_in(key, e), shape[1:], "kernel", dtype, cfg))(
                jnp.arange(shape[0]))
    if kind == "embed":
        std = 0.02
    else:  # kernel, router: fan-in is the second-last axis
        std = shape[-2] ** -0.5
    if kind == "qk_kernel":  # q . k / sqrt(d) then spreads by qk_gain
        std *= float(cfg.get("qk_gain", 1.0)) ** 0.5
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


def abstract_tree(cfg: dict, dtype, sharding=None):
    """``make_tree``'s shapes and types without the values."""
    return _nest({
        row["path"]: jax.ShapeDtypeStruct(
            tree_shape(cfg, row), _leaf_dtype(row["kind"], dtype),
            sharding=sharding)
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
    names inside the layer (``attn/q_proj/kernel``): the reference's walk."""
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


def top_leaves(base, cfg: dict, dtype) -> dict:
    return {row["path"][0]: make_leaf(base, row, dtype, cfg)
            for row in leaf_table(cfg) if row["layers"] is None}


# the leaves the runner shows to be the program's own: what feeds each size
# of cache (the last window layer's and the last full layer's keys) and one
# layer's experts
_PROBED = ("attn/k_proj/kernel", "moe/down_proj")


def probe(params: dict, cfg: dict, seed: int, dtype) -> float:
    """The reference regenerates the weights from the seed: how far the
    program's tree lies from that on the probed leaves of the last full layer
    and the last window layer, as the largest error over the largest value.
    To a rounding: a fused draw may differ from a lone one in the last place."""
    base, worst = base_key(seed), 0.0
    kinds = layer_kinds(cfg)
    last = {max(l for l, k in enumerate(kinds) if k[0] == op)
            for op in {k[0] for k in kinds}}
    for row in leaf_table(cfg):
        if "/".join(row["name"]) not in _PROBED:
            continue
        for layer in last & set(row["layers"]):
            mine = params
            for part in row["path"]:
                mine = mine[part]
            if row["stacked"]:
                mine = mine[row["layers"].index(layer)]
            again = make_leaf(base, row, dtype, cfg, layer).astype(jnp.float32)
            worst = max(worst, float(
                jnp.max(jnp.abs(mine.astype(jnp.float32) - again))
                / jnp.max(jnp.abs(again))))
    return worst

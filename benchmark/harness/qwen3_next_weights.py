"""Seeded random weights of the stack the ``qwen3-next-*`` configurations
describe (``model_type`` ``qwen3_next``): layers whose operator is Gated
DeltaNet (``layer_types`` "linear_attention") or gated GQA attention
("full_attention"), every one followed by softmax-routed SwiGLU experts with a
gated shared expert; zero-centred RMSNorms; an untied head.

As ``lfm2_weights.py``: the benchmark makes the weights and the plain
reference regenerates them from the same keys; every (leaf, layer) has a key
of its own, fold_in(fold_in(base(seed), crc32(leaf name)), layer), and every
EXPERT one under that by its index among the router's published outputs, so
the experts ``[expert_offset, expert_offset + num_experts)`` a share holds
are the arrays the uncut layer holds there, and the shares add up.

Column layout of the fused projections (``assumed`` in the configuration
file; a published checkpoint interleaves them by key head, which is a
permutation of columns under seeded weights): ``in_proj_qkvz`` is
``[q | k | v | z]`` and ``in_proj_ba`` is ``[b | a]``, each part
head-contiguous; ``q_proj`` holds ``[q | gate]`` a head.

Distributions (``assumed``): normal 1/sqrt(fan_in) kernels (the
convolution's fan-in is its taps; the shared expert's gate vector, a
``hidden -> 1`` projection, 1/sqrt(hidden)), normal 0.02 embedding; the
zero-centred norm weights (the block norms, the final norm, ``q_norm`` and
``k_norm``, all multiplying by ``1 + w``) normal 0.1 around ZERO, because a
weight of exactly 0 would hide a program that left the offset out; the
DeltaNet output norm (plain ``w``) normal 0.1 around ONE; ``A_log`` =
log U(0.25, 16) — the family's U(0, 16) floored away from 0, where log has no
value —, ``dt_bias`` 1: the family's modeling code's initialiser. Under it a
value head forgets within about three positions and an attention head
averages thousands of random values, so two faults read inside the sound
range; a draw under which both carry weight (``dt_bias`` from log-uniform
time steps in [0.001, 0.1], q/k norm weights around 0.75) separates every
fault and makes the cell's tokens/s follow the seed by 1.2 % (PERF.md, PR
38): not taken. The router, every norm weight, the shared expert's
gate vector, ``A_log`` and ``dt_bias`` stay float32 whatever ``dtype``, as
the program declares them. Where the configuration states ``scan_layers``
false every layer is a module of its own (``layer_<i>``) and no leaf is
stacked. This module imports nothing of the program:
``lfm2_weights.segments`` restates the rule by which the program cuts a list
of layer kinds into scans and single layers, and ``tests/test_qwen3_next.py``
holds the two trees against each other.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .lfm2_weights import (  # noqa: F401 - parts of the contract
    _leaf_key, segments, spread_shardings, tree_shape)
from .weights import _nest, base_key  # noqa: F401

# never in the compute dtype
_FLOAT32 = ("zero_scale", "unit_scale", "a_log", "ones", "router", "gate_vec")


def layer_kinds(cfg: dict) -> list[tuple[str, str]]:
    """Per layer (operator, feed-forward): every layer has experts."""
    return [(op, "moe") for op in cfg["layer_types"]]


def gdn_dims(cfg: dict) -> tuple[int, int, int, int, int]:
    """(key heads, value heads, key head dim, value head dim, width of
    [q | k | v], what the convolution runs over)."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return hk, hv, dk, dv, 2 * hk * dk + hv * dv


def layer_leaves(cfg: dict, kind: tuple[str, str]) -> list[tuple[tuple, tuple, str]]:
    """(name inside the layer, shape, how it is drawn) of one layer."""
    h = cfg["hidden_size"]
    op, _ = kind
    if op == "linear_attention":
        _, hv, _, dv, conv = gdn_dims(cfg)
        rows = [
            (("gdn_norm", "scale"), (h,), "zero_scale"),
            (("gdn", "in_proj_qkvz", "kernel"), (h, conv + hv * dv), "kernel"),
            (("gdn", "in_proj_ba", "kernel"), (h, 2 * hv), "kernel"),
            (("gdn", "conv1d"), (cfg["linear_conv_kernel_dim"], conv), "kernel"),
            (("gdn", "A_log"), (hv,), "a_log"),
            (("gdn", "dt_bias"), (hv,), "ones"),
            (("gdn", "norm"), (dv,), "unit_scale"),
            (("gdn", "out_proj", "kernel"), (hv * dv, h), "kernel"),
        ]
    else:
        d = cfg["head_dim"]
        q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
        rows = [(("attn_norm", "scale"), (h,), "zero_scale"),
                (("attn", "q_norm", "scale"), (d,), "zero_scale"),
                (("attn", "k_norm", "scale"), (d,), "zero_scale")]
        rows += [(("attn", name, "kernel"), shape, "kernel") for name, shape in (
            ("q_proj", (h, 2 * q)), ("k_proj", (h, kv)), ("v_proj", (h, kv)),
            ("o_proj", (q, h)))]
    f, fs = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    n, width = cfg["num_experts"], cfg["router_width"]
    rows += [(("mlp_norm", "scale"), (h,), "zero_scale"),
             (("moe", "router", "kernel"), (h, width), "router"),
             (("moe", "shared_gate"), (h,), "gate_vec")]
    rows += [(("moe", name), (n,) + shape, "experts") for name, shape in (
        ("gate_proj", (h, f)), ("up_proj", (h, f)), ("down_proj", (f, h)))]
    rows += [(("moe", "shared", name, "kernel"), shape, "kernel")
             for name, shape in (("gate_proj", (h, fs)), ("up_proj", (h, fs)),
                                 ("down_proj", (fs, h)))]
    return rows


def leaf_table(cfg: dict) -> list[dict]:
    """Every parameter, as the program's tree holds it (``lfm2_weights``'s
    rows: ``path``, ``name``, one layer's ``shape``, ``kind``, ``layers``,
    ``stacked``)."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    rows = [dict(path=("embed", "embedding"), name=("embed", "embedding"),
                 shape=(v, h), kind="embed", layers=None, stacked=False),
            dict(path=("final_norm", "scale"), name=("final_norm", "scale"),
                 shape=(h,), kind="zero_scale", layers=None, stacked=False),
            dict(path=("lm_head", "kernel"), name=("lm_head", "kernel"),
                 shape=(h, v), kind="kernel", layers=None, stacked=False)]
    kinds = layer_kinds(cfg)
    # ``scan_layers`` false: every layer a module of its own, ``layer_<i>``
    plan = (segments(kinds) if cfg.get("scan_layers", True)
            else [(i, (kind,), 1) for i, kind in enumerate(kinds)])
    for start, period, repeats in plan:
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


def _leaf_dtype(kind: str, dtype):
    return jnp.float32 if kind in _FLOAT32 else dtype


def _draw(key, shape, kind, dtype, cfg):
    dtype = _leaf_dtype(kind, dtype)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 0.25, 16.0))
    if kind in ("zero_scale", "unit_scale"):
        return (kind == "unit_scale") + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if kind == "experts":  # one key per expert, by its published index
        ids = cfg["expert_offset"] + jnp.arange(shape[0])
        return jax.vmap(lambda e: _draw(
            jax.random.fold_in(key, e), shape[1:], "kernel", dtype, cfg))(ids)
    if kind == "embed":
        std = 0.02
    elif kind == "gate_vec":
        std = shape[0] ** -0.5
    else:  # kernel, router: fan-in is the second-last axis
        std = shape[-2] ** -0.5
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
    names inside the layer (``gdn/in_proj_qkvz/kernel``): the reference's
    walk."""
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


# the leaves the runner shows to be the program's own: one of each kind of
# cache a layer feeds (state, pool) and one expert
_PROBED = ("gdn/out_proj/kernel", "attn/o_proj/kernel", "moe/down_proj")


def probe(params: dict, cfg: dict, seed: int, dtype) -> float:
    """The reference regenerates the weights from the seed: how far the
    program's tree lies from that on the last layer that holds each probed
    leaf (a DeltaNet mixer, an attention mixer, a layer's held experts), as
    the largest error over the largest value. To a rounding: a fused draw may
    differ from a lone one in the last place."""
    base, worst = base_key(seed), 0.0
    for row in leaf_table(cfg):
        if "/".join(row["name"]) not in _PROBED:
            continue
        mine = params
        for part in row["path"]:
            mine = mine[part]
        mine = (mine[-1] if row["stacked"] else mine).astype(jnp.float32)
        again = make_leaf(base, row, dtype, cfg, row["layers"][-1]).astype(jnp.float32)
        worst = max(worst, float(
            jnp.max(jnp.abs(mine - again)) / jnp.max(jnp.abs(again))))
    return worst

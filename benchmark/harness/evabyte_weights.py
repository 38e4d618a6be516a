"""Seeded random weights of the stack the ``evabyte-*`` configurations
describe: embed -> L x [norm, EVA attention, norm, SwiGLU] -> norm -> a head
of ``num_pred_heads * vocab_size`` columns, head-major.

As ``weights.py`` does for the dense decoder: the benchmark makes the weights,
the plain reference regenerates them leaf by leaf (one layer at a time) from
the same keys, fold_in(fold_in(base(seed), crc32(path)), layer), and this
module imports nothing of the program.

Distributions (the configuration file lists them under ``assumed``; the
published ``init_fn`` "v2" / ``init_std`` describe a training run's start, and
no checkpoint is read here): normal 1/sqrt(fan_in) kernels and normal 0.02
embedding, as ``weights.py``; norm weights normal 0.1 around ZERO, because the
norm multiplies by ``1 + w`` (``norm_add_unit_offset``) and a weight of
exactly 0 would hide a program that left the offset out; ``mu`` and ``phi``
unit normal — with unit-variance keys a chunk's softmax logits ``s * mu . k``
then spread by about 1 over its 16 positions, so the largest weight of a chunk
is near 0.3: neither flat (1/16) nor one-hot. Norm weights, ``mu`` and ``phi``
stay float32 whatever ``dtype``, as the program declares them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .weights import _leaf_key, _nest, base_key  # noqa: F401 - base_key is part of the contract

_FLOAT32_KINDS = ("scale", "vec")


def leaf_table(cfg: dict) -> list[dict]:
    """Every parameter: path in the program's tree, shape of ONE layer's
    slice, whether it is stacked over layers, how it is drawn."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    nkv = cfg["num_key_value_heads"]
    q, kv = cfg["num_attention_heads"] * d, nkv * d
    rows = [
        (("embed", "embedding"), (v, h), False, "embed"),
        (("final_norm", "scale"), (h,), False, "scale"),
        (("lm_head", "kernel"), (h, cfg["num_pred_heads"] * v), False, "kernel"),
        (("layers", "attn_norm", "scale"), (h,), True, "scale"),
        (("layers", "mlp_norm", "scale"), (h,), True, "scale"),
        (("layers", "attn", "mu"), (nkv, d), True, "vec"),
        (("layers", "attn", "phi"), (nkv, d), True, "vec"),
    ]
    for name, shape in (("q_proj", (h, q)), ("k_proj", (h, kv)),
                        ("v_proj", (h, kv)), ("o_proj", (q, h))):
        rows.append((("layers", "attn", name, "kernel"), shape, True, "kernel"))
    for name, shape in (("gate_proj", (h, f)), ("up_proj", (h, f)),
                        ("down_proj", (f, h))):
        rows.append((("layers", "mlp", name, "kernel"), shape, True, "kernel"))
    return [dict(path=p, shape=s, stacked=st, kind=k) for p, s, st, k in rows]


def _leaf_dtype(row: dict, dtype):
    return jnp.float32 if row["kind"] in _FLOAT32_KINDS else dtype


def _draw(key, row: dict, dtype):
    dtype = _leaf_dtype(row, dtype)
    std = {"scale": 0.1, "vec": 1.0, "embed": 0.02}.get(
        row["kind"]) or row["shape"][-2] ** -0.5
    return (std * jax.random.normal(key, row["shape"], dtype)).astype(dtype)


def make_leaf(base, row: dict, dtype, layer: int | None = None):
    """One unstacked leaf, or one layer's slice of a stacked one."""
    key = _leaf_key(base, row["path"])
    if row["stacked"]:
        key = jax.random.fold_in(key, layer)
    return _draw(key, row, dtype)


def make_stacked(base, row: dict, dtype, layers: int):
    keys = jax.vmap(lambda l: jax.random.fold_in(_leaf_key(base, row["path"]), l))(
        jnp.arange(layers))
    return jax.vmap(lambda k: _draw(k, row, dtype))(keys)


def tree_shape(cfg: dict, row: dict) -> tuple:
    lead = (cfg["num_hidden_layers"],) if row["stacked"] else ()
    return lead + tuple(row["shape"])


def abstract_tree(cfg: dict, dtype, sharding=None):
    """``make_tree``'s shapes and types without the values."""
    return _nest({
        row["path"]: jax.ShapeDtypeStruct(
            tree_shape(cfg, row), _leaf_dtype(row, dtype), sharding=sharding)
        for row in leaf_table(cfg)})


def make_tree(cfg: dict, seed: int, dtype, out_shardings=None):
    """The whole tree the program takes, in one jitted call from the seed."""
    layers = cfg["num_hidden_layers"]
    table = leaf_table(cfg)

    def build(base):
        return _nest({
            row["path"]: (make_stacked(base, row, dtype, layers)
                          if row["stacked"] else make_leaf(base, row, dtype))
            for row in table})

    return jax.jit(build, out_shardings=out_shardings)(base_key(seed))


def layer_slice(base, cfg: dict, layer: int, dtype) -> dict:
    """One layer's weights regenerated from the seed, flat by the path inside
    the layer: ``attn/q_proj``, ``attn/mu``, ``mlp/down_proj``, ``mlp_norm``."""
    return {
        "/".join(p for p in row["path"][1:] if p not in ("kernel", "scale")):
            make_leaf(base, row, dtype, layer)
        for row in leaf_table(cfg) if row["stacked"]}


def top_leaves(base, cfg: dict, dtype) -> dict:
    return {row["path"][0]: make_leaf(base, row, dtype)
            for row in leaf_table(cfg) if not row["stacked"]}


def spread_shardings(cfg: dict, devices) -> dict | None:
    """The configuration is one chip's stage of its deployment: the
    reference runs on one chip too."""
    if len(devices) > 1:
        raise NotImplementedError(
            "this configuration is one chip's pipeline stage of its deployment")
    return None

"""Seeded random weights, made on the device in one jitted call.

The benchmark, not the program, makes the weights: the runner hands the tree
to the system under test and the plain reference regenerates the same values
leaf by leaf (one layer at a time where memory is short) from the same keys,
so the reference takes nothing the program has made. Every (leaf, layer) has
a key of its own: fold_in(fold_in(base(seed), crc32(path)), layer).

Distributions are flax's: normal 1/sqrt(fan_in) kernels, normal 0.02
embedding, unit norm scales. This module imports nothing of the program.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def base_key(seed: int):
    """A key from any whole number up to 2**62 (the driver's seeds pass
    2**31, which PRNGKey alone does not take without x64)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed >> 31), seed & 0x7FFFFFFF)


def leaf_table(cfg: dict) -> list[dict]:
    """Every parameter of the dense decoder (embed -> L x [norm, GQA
    attention, norm, SwiGLU] -> norm -> head): path in the program's tree,
    shape of ONE layer's slice, whether it is stacked over layers, kind."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    rows = [
        (("embed", "embedding"), (v, h), False, "embed"),
        (("final_norm", "scale"), (h,), False, "scale"),
        (("lm_head", "kernel"), (h, v), False, "kernel"),
        (("layers", "attn_norm", "scale"), (h,), True, "scale"),
        (("layers", "mlp_norm", "scale"), (h,), True, "scale"),
    ]
    for name, shape in (("q_proj", (h, q)), ("k_proj", (h, kv)),
                        ("v_proj", (h, kv)), ("o_proj", (q, h))):
        rows.append((("layers", "attn", name, "kernel"), shape, True, "kernel"))
    for name, shape in (("gate_proj", (h, f)), ("up_proj", (h, f)),
                        ("down_proj", (f, h))):
        rows.append((("layers", "mlp", name, "kernel"), shape, True, "kernel"))
    if cfg.get("tie_word_embeddings"):
        rows = [r for r in rows if r[0] != ("lm_head", "kernel")]
    return [dict(path=p, shape=s, stacked=st, kind=k) for p, s, st, k in rows]


def _leaf_key(base, path: tuple):
    return jax.random.fold_in(base, zlib.crc32("/".join(path).encode()) & 0x7FFFFFFF)


def _draw(key, row: dict, dtype):
    if row["kind"] == "scale":
        return jnp.ones(row["shape"], jnp.float32)
    std = 0.02 if row["kind"] == "embed" else row["shape"][-2] ** -0.5
    return (std * jax.random.normal(key, row["shape"], dtype)).astype(dtype)


def make_leaf(base, row: dict, dtype, layer: int | None = None):
    """One unstacked leaf, or one layer's slice of a stacked one."""
    key = _leaf_key(base, row["path"])
    if row["stacked"]:
        key = jax.random.fold_in(key, layer)
    return _draw(key, row, dtype)


def make_stacked(base, row: dict, dtype, layers: int):
    """All layers of a stacked leaf: vmap over the layer keys gives the
    values of ``make_leaf(..., layer=l)`` for each l."""
    keys = jax.vmap(lambda l: jax.random.fold_in(_leaf_key(base, row["path"]), l))(
        jnp.arange(layers))
    return jax.vmap(lambda k: _draw(k, row, dtype))(keys)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = leaf
    return out


def tree_shape(cfg: dict, row: dict) -> tuple:
    """A leaf's shape in the program's tree (stacked leaves lead with L)."""
    lead = (cfg["num_hidden_layers"],) if row["stacked"] else ()
    return lead + tuple(row["shape"])


def abstract_tree(cfg: dict, dtype, sharding=None):
    """``make_tree``'s shapes and types without the values (norm scales are
    float32 whatever ``dtype``), for compiling ahead of time."""
    return _nest({
        row["path"]: jax.ShapeDtypeStruct(
            tree_shape(cfg, row),
            jnp.float32 if row["kind"] == "scale" else dtype, sharding=sharding)
        for row in leaf_table(cfg)})


def make_tree(cfg: dict, seed: int, dtype, out_shardings=None):
    """The whole tree the program takes (norm scales stay float32, as flax
    declares them), in one jitted call from the seed."""
    layers = cfg["num_hidden_layers"]
    table = leaf_table(cfg)

    def build(base):
        flat = {}
        for row in table:
            flat[row["path"]] = (
                make_stacked(base, row, dtype, layers) if row["stacked"]
                else make_leaf(base, row, dtype)
            )
        return _nest(flat)

    return jax.jit(build, out_shardings=out_shardings)(base_key(seed))


def layer_slice(base, cfg: dict, layer: int, dtype) -> dict:
    """One layer's weights, flat by the last two path parts, e.g.
    ``attn/q_proj``, ``mlp_norm``; for the reference's layer walk."""
    out = {}
    for row in leaf_table(cfg):
        if row["stacked"]:
            name = "/".join(p for p in row["path"][1:] if p not in ("kernel", "scale"))
            out[name] = make_leaf(base, row, dtype, layer)
    return out


def top_leaves(base, cfg: dict, dtype) -> dict:
    return {row["path"][0]: make_leaf(base, row, dtype)
            for row in leaf_table(cfg) if not row["stacked"]}


def spread_shardings(cfg: dict, devices) -> dict | None:
    """For more than one chip: each leaf split over all ``devices`` along its
    largest dimension that divides evenly (replicated where none does), as a
    tree shaped like ``make_tree``'s. The reference uses it so that a model
    one chip cannot hold is followed across the chips; None on one chip."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    if len(devices) < 2:
        return None
    mesh = Mesh(np.asarray(devices), ("all",))
    flat = {}
    for row in leaf_table(cfg):
        shape = tree_shape(cfg, row)
        dims = [i for i in sorted(range(len(shape)), key=lambda i: -shape[i])
                if shape[i] % len(devices) == 0 and shape[i] >= 1024]
        spec = [None] * len(shape)
        if dims:
            spec[dims[0]] = "all"
        flat[row["path"]] = NamedSharding(mesh, PartitionSpec(*spec))
    return _nest(flat)

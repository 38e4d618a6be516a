#!/usr/bin/env python3
"""Rehearsal 3 of the on-chip-measurement guide: compile the program's real
step at the real sizes for a DESCRIBED ``v5e:2x2`` — no chip, no chip time —
and print ``memory_analysis()``. A compile that passes is not a chip run; one
that the compiler refuses ("Ran out of memory in memory space hbm") does not
fit, and that refusal is what ``fits`` records.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_aot.py train-1chip
    JAX_PLATFORMS=cpu python3 benchmark/rehearse_aot.py train-4chip --depths 6,7,8,9,10
    JAX_PLATFORMS=cpu python3 benchmark/rehearse_aot.py serve --slots 16

``train-*`` lowers ``Accelerator.unified_step(...).jitted`` (the very jit the
warm-up compiles) over a mesh of described devices, with the parameter
shardings the program infers; ``train-4chip`` walks the depths and, with
``--write``, records the deepest that fits and the compiler's bytes in the
four-chip configuration file's ``aot_memory`` note. ``serve`` lowers the
engine's decode program and its widest prefill and reports the bytes beside
the weights, for the slot count in the serve cells' files.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    out = {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes", "alias_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes")}
    return out


def _config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def _spec(name: str) -> dict:
    with open(os.path.join(HERE, "workloads", f"{name}.json")) as f:
        return json.load(f)


def train_step_memory(cfg: dict, spec: dict, devices) -> dict:
    """Lower and compile the program's unified_step for ``devices``."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from accelerate_tpu import (
        Accelerator, AcceleratorState, GradientState, ParallelismPlugin)
    from accelerate_tpu.models import CausalLM
    from accelerate_tpu.parallel.sharding import infer_param_shardings
    from harness import common

    _, weights = common.modules_of(cfg)
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    acc = Accelerator(mixed_precision=spec["mixed_precision"],
                      parallelism_plugin=ParallelismPlugin(fsdp_size=-1))
    acc.reform_mesh(devices)
    mesh = acc.mesh
    seq, n = spec["traffic"]["seq_len"], len(devices)
    # auto-dispatch asks jax.default_backend(), which is the CPU here, and
    # would take xla_attention: name the kernel the chip's dispatch picks
    model = CausalLM(common.program_config(
        cfg, max_seq_len=seq, remat=spec["remat"], dtype=spec["compute_dtype"],
        attention_impl="flash"))
    params = weights.abstract_tree(cfg, jnp.float32)
    shardings = infer_param_shardings(params, mesh, acc.state.parallelism_plugin)
    acc._param_shardings = shardings
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        params, shardings)
    o = spec["optimizer"]
    tx = optax.adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                     weight_decay=o["weight_decay"])
    optimizer = acc.prepare_optimizer(tx)
    rep = NamedSharding(mesh, P())
    opt_state = optax.tree_utils.tree_map_params(
        tx, lambda leaf, sh: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=sh),
        jax.eval_shape(tx.init, params), shardings,
        transform_non_params=lambda leaf: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=rep))
    optimizer.opt_state = opt_state
    step = acc.unified_step(CausalLM.loss_fn(model), max_grad_norm=o["max_grad_norm"])
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
    carry = {"params": params, "opt_state": opt_state, "opt_step": scalar,
             "micro_step": scalar}
    data_axes = tuple(a for a in mesh.axis_names if mesh.shape[a] > 1) or None
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (spec["rows_per_chip"] * n, seq), jnp.int32,
        sharding=NamedSharding(mesh, P(data_axes)))}
    compiled = step.jitted.lower(carry, batch).compile()
    text = compiled.as_text()
    out = _mem(compiled)
    out["mosaic_calls"] = text.count('custom_call_target="tpu_custom_call"')
    out["collectives"] = {k: text.count(f" {k}(") + text.count(f" {k}-start(")
                          for k in ("all-gather", "all-reduce", "reduce-scatter",
                                    "collective-permute", "all-to-all")}
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("train-1chip", "train-4chip", "serve"))
    ap.add_argument("--depths", default="6,7,8,9,10")
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args()
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    if args.what == "train-1chip":
        out = train_step_memory(_config("mistral-7b-v0.1-train-1chip"),
                                _spec("train-dense-1chip"), list(topo.devices)[:1])
        print(json.dumps({"train-1chip": out}, indent=1))
        return 0
    if args.what == "train-4chip":
        cfg, spec = _config("mistral-7b-v0.1-train-4chip"), _spec("train-zero3-4chip")
        found = {}
        for depth in (int(d) for d in args.depths.split(",")):
            try:
                out = train_step_memory(dict(cfg, num_hidden_layers=depth), spec,
                                        list(topo.devices))
                out["fits"] = True  # the chip's compiler checks the 15.75 G itself
            except Exception as exc:  # the compiler refusing IS the answer
                msg = str(exc)
                at = msg.find("Ran out of memory")
                out = {"fits": False, "error": msg[max(at, 0):max(at, 0) + 200]}
            found[depth] = out
            print(json.dumps({depth: out}), flush=True)
        fitting = [d for d, o in found.items() if o["fits"]]
        print(f"deepest that fits per chip: {max(fitting) if fitting else None}")
        if args.write and fitting:
            path = os.path.join(HERE, "configs", "mistral-7b-v0.1-train-4chip.json")
            cfg["num_hidden_layers"] = max(fitting)
            cfg["aot_memory"] = {str(d): o for d, o in found.items()}
            with open(path, "w") as f:
                json.dump(cfg, f, indent=1)
        return 0
    return serve_memory(topo, args.slots)


def serve_memory(topo, slots: int) -> int:
    """The engine's decode program and its widest prefill, lowered for one
    described chip: bytes of arguments (weights + pool), outputs and temps."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from accelerate_tpu.models import CausalLM
    from accelerate_tpu.ops.attention import PagedKVState
    from harness import common

    cfg, spec = _config("mistral-7b-v0.1-serve-1chip"), _spec("serve-decode-sat")
    _, weights = common.modules_of(cfg)
    eng = spec["engine"]
    one = SingleDeviceSharding(list(topo.devices)[0])
    model = CausalLM(common.program_config(
        cfg, max_seq_len=eng["max_seq_len"], dtype=spec["weight_dtype"]))
    bs = eng["block_size"]
    table = -(-eng["max_seq_len"] // bs)
    blocks = slots * table + 1
    params = weights.abstract_tree(cfg, jnp.bfloat16, sharding=one)

    def state(b):
        return PagedKVState(
            block_table=jnp.zeros((b, table), jnp.int32), num_blocks=blocks,
            cache_len=jnp.zeros((b,), jnp.int32), lengths=jnp.ones((b,), jnp.int32),
            block_size=bs)

    cache = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
                           decode=True, paged=state(1)))["cache"]
    cache = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), cache)

    def call(params, cache, ids, tables, cache_lens, lengths):
        st = PagedKVState(block_table=tables, num_blocks=blocks,
                          cache_len=cache_lens, lengths=lengths, block_size=bs)
        logits, mutated = model.apply({"params": params, "cache": cache}, ids,
                                      decode=True, paged=st, mutable=["cache"])
        return mutated["cache"], jnp.argmax(logits[:, -1], axis=-1)

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    out = {}
    for name, b, s in (("decode", slots, 1), ("prefill_widest", 1, eng["max_seq_len"])):
        compiled = jax.jit(call).lower(
            params, cache, sds((b, s)), sds((b, table)), sds((b,)), sds((b,))).compile()
        out[name] = _mem(compiled)
    pool = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    out["pool_bytes"] = int(pool)
    out["fits"] = True  # both compiled: the compiler refuses what overflows
    print(json.dumps({f"serve-{slots}slots": out}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

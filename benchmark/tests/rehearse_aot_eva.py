#!/usr/bin/env python3
"""Rehearsal 3 of the on-chip-measurement guide for the EvaByte cell: compile
``ServingEngine``'s OWN (donating) programs at the cell's size — decode, each
prefill width, the roll-over — for a DESCRIBED ``v5e:2x2`` chip, no chip and
no chip time, and print ``memory_analysis()``. A compile that passes is not a
chip run; one the compiler refuses does not fit.

    JAX_PLATFORMS=cpu python3 benchmark/tests/rehearse_aot_eva.py [--slots 8] [--widths 16384]

The engine is built here on the CPU (its pool of zeros, 6.4 GB at 8 slots,
lives in host RAM) over a tree that holds ``layers/attn/{mu,phi}`` alone —
all the roll-over it warms reads — and its programs are lowered over abstract
weights placed on the described chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

CELL = "serve-eva-longctx-sat"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--widths", default="16384")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import rehearse_aot
    from accelerate_tpu import ServingEngine
    from accelerate_tpu.models import CausalLM
    from harness import cell as cells
    from harness import common

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(list(topo.devices)[0])
    cell = cells.load_cell(CELL)
    cfg, eng = cell["config"], cell["spec"]["engine"]
    _, weights = common.modules_of(cfg)
    model = CausalLM(common.program_config(
        cfg, max_seq_len=eng["max_seq_len"], dtype=cell["spec"]["weight_dtype"]))
    vec = (cfg["num_hidden_layers"], cfg["num_key_value_heads"], cfg["head_dim"])
    engine = ServingEngine(
        model, {"layers": {"attn": {"mu": jnp.zeros(vec), "phi": jnp.zeros(vec)}}},
        max_slots=args.slots or eng["max_slots"], block_size=eng["block_size"])

    def sds(x, dtype=None):
        return jax.ShapeDtypeStruct(
            jnp.shape(x), dtype or jnp.result_type(x), sharding=one)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)

    params = weights.abstract_tree(cfg, jnp.bfloat16, sharding=one)
    cache = jax.tree.map(sds, engine.cache)
    n, table, key = engine.max_slots, engine._max_table, sds(engine._key)
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"  # dispatch asks it: answer for the chip
    try:
        lowered = {"decode": engine._decode_fn.lower(
            params, cache, i32(n, 1), i32(n, table), i32(n), i32(n), f32(n), key,
            i32(n))}
        for width in (int(w) for w in args.widths.split(",")):
            lowered[f"prefill_{width}"] = engine._prefill_fn.lower(
                params, cache, i32(1, width), i32(1, table), i32(1), i32(1), key,
                f32(1))
        lowered["roll_over"] = engine._rollover_fn.lower(
            params, cache, i32(engine._eva.window_blocks),
            i32(engine._eva.summary_blocks))
    finally:
        jax.default_backend = real
    out = {"slots": n, "table_blocks": table, "pool_blocks": engine.num_blocks,
           "pool_bytes": int(engine.kv_pool_bytes)}
    for name, low in lowered.items():
        try:
            compiled = low.compile()
            out[name] = rehearse_aot._mem(compiled)
            out[name]["mosaic_calls"] = compiled.as_text().count(
                'custom_call_target="tpu_custom_call"')
        except Exception as exc:  # the compiler refusing IS the answer
            msg = str(exc)
            at = max(msg.find("Ran out of memory"), 0)
            out[name] = {"fits": False, "error": msg[at:at + 300]}
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Rehearsal 3 of the on-chip-measurement guide for the mixed window /
full attention sparse-expert cell: compile ``ServingEngine``'s OWN
(donating) programs at the cell's size — decode and each prefill width — for a
DESCRIBED ``v5e:2x2`` chip, no chip and no chip time, and print
``memory_analysis()``. A compile that passes is not a chip run; one the
compiler refuses does not fit.

    JAX_PLATFORMS=cpu python3 benchmark/tests/rehearse_aot_smallthinker.py [--slots 32] [--widths 16384]

The engine is built here on the CPU (its pool and state of zeros, 3.8 GB at 32
slots, live in host RAM) over an empty tree, and its programs are lowered over
abstract weights placed on the described chip.
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

CELL = "serve-swa-moe-mixed-sat"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--widths", default="16384")
    ap.add_argument("--write", action="store_true",
                    help="write the counts into the configuration file as aot_memory")
    ap.add_argument("--dump", default=None, help="a directory for the compiled texts")
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
    # one leaf on the CPU's one device: the engine reads where its weights
    # lie (``single_device``, which the paged decode kernel asks for)
    engine = ServingEngine(
        model, {"final_norm": {"scale": jnp.zeros((cfg["hidden_size"],))}},
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
            params, cache, i32(n, 1), i32(n, table), i32(n), i32(n), f32(n), key)}
        for width in (int(w) for w in args.widths.split(",")):
            lowered[f"prefill_{width}"] = engine._prefill_fn.lower(
                params, cache, i32(1, width), i32(1, table), i32(1), i32(1), key,
                f32(1), i32(1))
    finally:
        jax.default_backend = real
    out = {"slots": n, "table_blocks": table, "pool_blocks": engine.num_blocks,
           "pool_bytes": int(engine.kv_pool_bytes),
           "state_bytes": int(engine.state_bytes_per_slot * n),
           "trace_counts": engine.trace_counts()}
    for name, low in lowered.items():
        try:
            compiled = low.compile()
            out[name] = rehearse_aot._mem(compiled)
            text = compiled.as_text()
            out[name]["mosaic_calls"] = text.count(
                'custom_call_target="tpu_custom_call"')
            if args.dump:
                with open(os.path.join(args.dump, f"{name}.hlo.txt"), "w") as f:
                    f.write(text)
        except Exception as exc:  # the compiler refusing IS the answer
            msg = str(exc)
            at = max(msg.find("Ran out of memory"), 0)
            out[name] = {"fits": False, "error": msg[at:at + 300]}
    print(json.dumps(out, indent=1))
    if args.write:
        path = os.path.join(os.path.dirname(HERE), "configs", f"{cfg['name']}.json")
        with open(path) as f:
            conf = json.load(f)
        conf["aot_memory"] = {
            "how": "ServingEngine's own (donating) programs lowered for a "
                   f"described v5e:2x2 chip, {n} slots x {eng['max_seq_len']} "
                   f"positions, pool of {engine.num_blocks} blocks and rings of "
                   f"{engine._regime.ring} rows a slot (benchmark/tests/"
                   "rehearse_aot_smallthinker.py, PR 45; a count, not a chip run)",
            f"serve-{n}slots": out}
        with open(path, "w") as f:
            json.dump(conf, f, indent=1)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

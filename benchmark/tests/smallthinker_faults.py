#!/usr/bin/env python3
"""The faults the mixed window / full attention cell's limits are held
against, planted UNDER the timed path as ``faults.py`` plants its own (the
program patched in place, so that the runner carries no test hook; a run
driven over any of them has to come out ``correct`` false — or the cell's file
says in so many words that the fault is not separable at the published widths:
``limits_why``; the CPU tests at tiny widths fail every one).
``plant(name)`` patches and returns the call that undoes it; a name of
``faults.py`` is handed on to it.

no_band: a sliding layer's prefill (and the plain forward pass) attends every
earlier position — the band left out where the program could leave it out: a
decode step reads a ring, which holds no more than the band. rope_on_full:
the full-attention layers rotate q and k too. rope_off_window: the window
layers rotate nothing. router_post_attn: the router reads the feed-forward's
normed input, as every other expert model's does. silu_gate: ``silu`` for
``relu`` in the experts' gate. ring_not_written: a prefill leaves its slot's
rings as they were — the first decode steps read whatever the seat held
before. ring_one_block_short: a decode step reads one block less of its ring
than the band allows, from the ring's first wrap on.

    python3 benchmark/tests/smallthinker_faults.py --fault no_band \\
        --workload serve-swa-moe-mixed-sat --seed 5 --seconds 10 --trace 0

runs the benchmark's one command on the chip with the fault planted
(``rehearse_smallthinker.py --fault`` does the same on the CPU at the tiny
size).
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import faults  # noqa: E402

NAMES = ("no_band", "rope_on_full", "rope_off_window", "router_post_attn",
         "silu_gate", "ring_not_written", "ring_one_block_short")
_plant_base = faults.plant  # ``limits_smallthinker_on_chip`` rebinds faults.plant


def _built_with(over):
    """The program built from the configuration with ``over(cfg)`` in place
    of what the file states."""
    from harness import common

    real = common.program_config
    faults._set(common, "program_config",
                lambda cfg, **kw: real({**cfg, **over(cfg)}, **kw))


def plant(name: str):
    import jax.numpy as jnp

    from accelerate_tpu.models import transformer

    if name == "no_band":
        real = transformer.dot_product_attention
        faults._set(transformer, "dot_product_attention",
                    lambda *a, **kw: real(*a, **{**kw, "window": None}))
    elif name == "rope_on_full":
        _built_with(lambda cfg: {"rope_layout": [1] * len(cfg["rope_layout"])})
    elif name == "rope_off_window":
        _built_with(lambda cfg: {"rope_layout": [0] * len(cfg["rope_layout"])})
    elif name == "router_post_attn":
        _built_with(lambda cfg: {"router_pre_attention": False})
    elif name == "silu_gate":
        _built_with(lambda cfg: {"hidden_act": "silu"})
    elif name == "ring_not_written":
        real = transformer.paged_update

        def update(key_pool, value_pool, k, v, state, *a, ring=False, **kw):
            if ring and state.fresh:
                return key_pool, value_pool
            return real(key_pool, value_pool, k, v, state, *a, ring=ring, **kw)
        faults._set(transformer, "paged_update", update)
    elif name == "ring_one_block_short":
        real = transformer.paged_attention

        def read(q, key_pool, value_pool, state, *a, ring=False, **kw):
            if ring:
                state = state.replace(cache_len=jnp.minimum(
                    state.cache_len, state.ring - 1 - state.block_size))
            return real(q, key_pool, value_pool, state, *a, ring=ring, **kw)
        faults._set(transformer, "paged_attention", read)
    else:
        return _plant_base(name)
    return faults.undo


def main() -> int:
    sys.path.insert(0, os.path.dirname(HERE))
    sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
    argv = sys.argv[1:]
    name = argv[argv.index("--fault") + 1]
    del argv[argv.index("--fault"):argv.index("--fault") + 2]
    import run

    plant(name)
    print(f"fault {name} planted", flush=True)
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""The faults the EvaByte cell's limits are held against, planted UNDER the
timed path as ``faults.py`` plants its own (the program patched in place; a
run driven over any of them has to come out ``correct`` false):

* ``no_summaries``: the summaries are left out of the softmax — a prompt's
  later windows see their own tokens alone, and what they write into the pool
  (rows, and the summaries of what they computed) is wrong from layer 1 on;
* ``own_chunks_twice``: a window's queries see the summaries of their OWN
  window beside its exact tokens, so its chunks count twice;
* ``roll_over_unwritten``: a window that fills while its request decodes is
  never summarised — the blocks that should hold its summaries keep the rows
  they held, and every later byte of that request reads them as summaries.

The first two turn the lengths under which a prefill's queries see the call's
summaries (``ops/eva_attention._attend_lse``'s ``kv_lengths``, the one call
that hands it any); decode then reads what the faulty prefill wrote. The third
turns ``ops/eva_attention.eva_roll_over`` into a program that writes nothing:
only a request whose ANSWER crosses a window's end shows it, and the cell's
``reference_sample`` is sized so that every sample holds one
(``test_eva_cell.py::test_every_sample_holds_an_answer_that_crossed_a_window``).

``CONTROLS`` are no faults: ``bf16_stream`` is the program's own next lower
precision, the residual stream and the logits in bfloat16 where the
configuration states float32 (``fp32_skip_add``, ``fp32_logits``). What it
reads beside the sound runs is in ``PERF.md``, section 4.

    python3 benchmark/tests/eva_faults.py --fault no_summaries \\
        --workload serve-eva-longctx-sat --seed 5 --seconds 20 --trace 0

runs the benchmark's one command on the chip with the fault planted
(``rehearse_eva.py --fault`` does the same on the CPU at the tiny size).
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
FAULTS = ("no_summaries", "own_chunks_twice", "roll_over_unwritten")
CONTROLS = ("bf16_stream",)


def plant(name: str):
    """Patch the program; returns the call that undoes it."""
    import jax.numpy as jnp

    from accelerate_tpu.ops import eva_attention

    if name == "bf16_stream":
        from harness import common

        real = common.program_config
        common.program_config = lambda cfg, **kw: real(
            {**cfg, "fp32_skip_add": False, "fp32_logits": False}, **kw)
        return lambda: setattr(common, "program_config", real)
    if name == "roll_over_unwritten":
        real = eva_attention.eva_roll_over
        eva_attention.eva_roll_over = (
            lambda key_pool, value_pool, *a, **kw: (key_pool, value_pool))
        return lambda: setattr(eva_attention, "eva_roll_over", real)
    if name == "no_summaries":
        seen = lambda lens, most: jnp.zeros_like(lens)  # noqa: E731
    elif name == "own_chunks_twice":
        # window w sees w + 1 windows' summaries, capped at what the call
        # hands it (the last window's own are not among them)
        seen = lambda lens, most: jnp.minimum(  # noqa: E731
            lens + (lens[1] - lens[0]), most)
    else:
        raise KeyError(name)
    real = eva_attention._attend_lse

    def attend(q, k, v, *, kv_lengths=None, **kw):
        if kv_lengths is not None:  # the attention over the summaries
            kv_lengths = seen(kv_lengths, k.shape[1])
        return real(q, k, v, kv_lengths=kv_lengths, **kw)

    eva_attention._attend_lse = attend
    return lambda: setattr(eva_attention, "_attend_lse", real)


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

#!/usr/bin/env python3
"""The faults the hybrid linear-attention / sparse-expert cell's limits are
held against, planted UNDER the timed path as ``faults.py`` plants its own
(the program patched in place, so that the runner carries no test hook; a run
driven over any of them has to come out ``correct`` false — or the cell's file
says in so many words that the fault is not separable, as it does of
``rope_whole_head`` at the published widths under the committed initialiser:
``limits_why``; the CPU rehearsal at tiny widths fails all five, and
``no_state_handoff`` fails ``first_decoded_mean_logit_gap``, the number the
runner reads from the first tokens that more, shorter requests decoded).
``plant(name)``
patches and returns the call that undoes it; a name of ``faults.py`` is
handed on to it.

no_state_handoff: a prefill leaves a ZERO state in its slot — the state is not
handed from prefill to decode, and the first decoded position starts the
recurrence anew. no_exp_g: ``exp(g)`` left out of the gated delta rule, in the
chunked form and the one-position form alike: nothing is ever forgotten.
no_shared_gate: the shared expert's output is not multiplied by
``sigmoid(w_s . x)``. no_attn_gate: the attention output is not multiplied by
``sigmoid(gate)`` (the program is built without the gate and handed the ``q``
half of each head's ``q_proj`` columns). rope_whole_head: rope turns all 256
elements of a head where the configuration states the first 64.

    python3 benchmark/tests/qwen3_next_faults.py --fault no_exp_g \\
        --workload serve-gdn-moe-sat --seed 5 --seconds 10 --trace 0

runs the benchmark's one command on the chip with the fault planted
(``rehearse_qwen3_next.py --fault`` does the same on the CPU at the tiny
size).
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import faults  # noqa: E402

NAMES = ("no_state_handoff", "no_exp_g", "no_shared_gate", "no_attn_gate",
         "rope_whole_head")
_plant_base = faults.plant  # ``limits_qwen3_next_on_chip`` rebinds faults.plant


def _built_with(**over):
    """The program built from the configuration with ``over`` in place of
    what the file states."""
    from harness import common

    real = common.program_config
    faults._set(common, "program_config",
                lambda cfg, **kw: real({**cfg, **over}, **kw))


def plant(name: str):
    import jax.numpy as jnp

    from accelerate_tpu.ops import gated_delta

    if name == "no_state_handoff":
        real = gated_delta.gated_delta_chunked

        def chunked(*a, **kw):
            out, state = real(*a, **kw)
            return out, jnp.zeros_like(state)
        faults._set(gated_delta, "gated_delta_chunked", chunked)
    elif name == "no_exp_g":
        for fn in ("gated_delta_chunked", "gated_delta_step"):
            real = getattr(gated_delta, fn)
            faults._set(gated_delta, fn, lambda q, k, v, g, *a, _real=real, **kw:
                        _real(q, k, v, jnp.zeros_like(g), *a, **kw))
    elif name == "no_shared_gate":
        _built_with(shared_expert_gate=False)
    elif name == "rope_whole_head":
        _built_with(partial_rotary_factor=1.0)
    elif name == "no_attn_gate":
        from harness import qwen3_next_weights as W

        _built_with(attn_output_gate=False)
        real_tree = W.make_tree

        def make_tree(cfg, *a, **kw):
            tree = real_tree(cfg, *a, **kw)
            nh, d = cfg["num_attention_heads"], cfg["head_dim"]
            for row in W.leaf_table(cfg):
                if row["name"] != ("attn", "q_proj", "kernel"):
                    continue
                node = tree
                for part in row["path"][:-1]:
                    node = node[part]
                kernel = node["kernel"]  # (..., hidden, heads x [q | gate])
                node["kernel"] = kernel.reshape(
                    *kernel.shape[:-1], nh, 2 * d)[..., :d].reshape(
                        *kernel.shape[:-1], nh * d)
            return tree
        faults._set(W, "make_tree", make_tree)
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

#!/usr/bin/env python3
"""The faults the latent-attention / grouped-sparse-expert cell's limits are
held against, planted UNDER the timed path as ``faults.py`` plants its own
(the program patched in place, so that the runner carries no test hook; a run
driven over any of them has to come out ``correct`` false — or the cell's file
says in so many words that the fault is not separable at the published widths
under the committed initialiser: ``limits_why``; the CPU rehearsal at tiny
widths fails all six). ``plant(name)`` patches and returns the call that
undoes it; a name of ``faults.py`` is handed on to it.

no_k_rope: ``q_rope . k_rope`` left out of the score, in the expanded and the
absorbed form alike (every head's rotated query part is zero). no_mscale:
YaRN's ``m ** 2`` left out of the softmax scale. no_latent_norm: ``kv_a_norm``
left out — the latent is expanded, and CACHED, un-normed. no_group_limit: the
router chooses its top 8 of all 256 outputs (``n_group`` = ``topk_group`` =
1). no_shared_expert: the shared expert left out of every expert layer.
plain_rope: YaRN's ramp left out — every frequency as ``theta`` alone gives it
(``m ** 2`` stays in the scale).

    python3 benchmark/tests/deepseek_v3_faults.py --fault no_k_rope \\
        --workload serve-mla-moe-longctx-sat --seed 5 --seconds 10 --trace 0

runs the benchmark's one command on the chip with the fault planted
(``rehearse_deepseek_v3.py --fault`` does the same on the CPU at the tiny
size).
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import faults  # noqa: E402

NAMES = ("no_k_rope", "no_mscale", "no_latent_norm", "no_group_limit",
         "no_shared_expert", "plain_rope")
_plant_base = faults.plant  # ``limits_deepseek_v3_on_chip`` rebinds faults.plant


def plant(name: str):
    import jax.numpy as jnp

    from accelerate_tpu.models import transformer

    if name == "no_k_rope":
        real = transformer.rope

        # the rotated key part is ONE head a position, a query's is every head's
        faults._set(transformer, "rope", lambda x, *a, **kw: (
            jnp.zeros_like(x) if x.shape[2] > 1 else real(x, *a, **kw)))
    elif name == "no_mscale":
        faults._set(transformer, "yarn_mscale", lambda factor, mscale=1.0: 1.0)
    elif name == "no_latent_norm":
        real_norm = transformer.RMSNorm.__call__
        faults._set(transformer.RMSNorm, "__call__", lambda self, x: (
            x if self.name == "kv_a_norm" else real_norm(self, x)))
    elif name == "no_group_limit":
        from harness import common

        real_config = common.program_config
        faults._set(common, "program_config", lambda cfg, **kw: real_config(
            {**cfg, "n_group": 1, "topk_group": 1}, **kw))
    elif name == "no_shared_expert":
        real_mlp = transformer.MLP.__call__

        def call(self, x, *a, **kw):
            out = real_mlp(self, x, *a, **kw)
            return jnp.zeros_like(out) if self.name == "shared" else out
        faults._set(transformer.MLP, "__call__", call)
    elif name == "plain_rope":
        faults._set(transformer, "_scale_rope_freqs",
                    lambda freqs, scaling, theta=None: freqs)
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

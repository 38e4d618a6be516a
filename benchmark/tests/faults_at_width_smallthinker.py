#!/usr/bin/env python3
"""Before the first chip call: what each planted LAYER fault reads at the
PUBLISHED widths under the committed initialiser, on the CPU — one period's
first two layers (a full layer without rope, a window layer with rope: hidden
2560, 28 / 4 heads of 128, 64 experts of 768, window 4096), a vocabulary of
8,192 rows and one sequence of ``--positions`` (past the window), the
program's own forward pass in float32 sound and then with each fault of
``smallthinker_faults.py`` planted. Prints the standard deviation of a head's
scores in each layer and, a fault, the mean and the widest change of a logit
and the mean gap of the sound pass's best token under the faulty logits, over
the positions past the window. A count of what the initialiser lets a fault
move, never a time.

    JAX_PLATFORMS=cpu python3 benchmark/tests/faults_at_width_smallthinker.py [--qk-gain 1.0]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

import smallthinker_faults  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--positions", type=int, default=5120,
                    help="whole thousands of 1,024; the program's xla attention "
                         "holds ~16 B a score on the CPU: 12 GB at 5,120")
    ap.add_argument("--qk-gain", type=float, default=None)
    ap.add_argument("--seed", type=int, default=2**31 + 45)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.models import CausalLM
    from harness import cell as cells
    from harness import common
    from harness import smallthinker_reference as ref

    cfg = dict(cells.load_cell("serve-swa-moe-mixed-sat")["config"])
    cfg.update(num_hidden_layers=2, vocab_size=8192,
               sliding_window_layout=cfg["sliding_window_layout"][:2],
               rope_layout=cfg["rope_layout"][:2], layer_types=cfg["layer_types"][:2])
    if args.qk_gain is not None:
        cfg["qk_gain"] = args.qk_gain
    _, weights = common.modules_of(cfg)
    params = weights.make_tree(cfg, args.seed, jnp.float32)
    s, w = args.positions, cfg["sliding_window_size"]
    ids = np.random.default_rng(args.seed).integers(
        0, cfg["vocab_size"], (1, s)).astype(np.int32)

    def logits():
        model = CausalLM(common.program_config(cfg, max_seq_len=s, dtype="float32"))
        return np.asarray(jax.jit(lambda p, i: model.apply({"params": p}, i))(
            params, ids))[0, w:]

    # a head's scores as the reference computes them, layer by layer
    import functools

    ref.attention = functools.partial(ref.attention, q_block=1024)
    x = params["embed"]["embedding"][ids]
    pos = jnp.arange(s)[None]
    for l, kind in enumerate(weights.layer_kinds(cfg)):
        lw = weights.layer_view(params, cfg, l)
        a = ref.rms_norm(x, lw["attn_norm/scale"], cfg["rms_norm_eps"])
        q = (a @ lw["attn/q_proj/kernel"]).reshape(1, s, -1, 128)[0, -256:, 0]
        k = (a @ lw["attn/k_proj/kernel"]).reshape(1, s, -1, 128)[0, :, 0]
        print(f"SCORES layer {l} ({kind[0]}): std "
              f"{float(jnp.std(q @ k.T / 128 ** 0.5)):.3f}", flush=True)
        x = jax.jit(lambda x, lw, kind=kind: ref.block(x, lw, cfg, kind, pos))(x, lw)
    sound = logits()
    best = sound.argmax(-1)
    for name in smallthinker_faults.NAMES[:5]:
        undo = smallthinker_faults.plant(name)
        try:
            got = logits()
        finally:
            undo()
        gap = got.max(-1) - got[np.arange(len(best)), best]
        print("FAULT " + json.dumps({
            "fault": name, "positions_compared": len(best),
            "mean_abs_logit_change": round(float(np.abs(got - sound).mean()), 5),
            "widest_logit_change": round(float(np.abs(got - sound).max()), 4),
            "mean_gap_of_the_sound_best_token": round(float(gap.mean()), 5),
            "tokens_off_best": int((gap > 0).sum())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""``limits_on_chip.py`` for the hybrid linear-attention / sparse-expert cell:
the same script, arguments and output, with ``--faults`` taking the five names
of ``qwen3_next_faults.py`` beside those of ``faults.py``.

    python3 benchmark/tests/limits_qwen3_next_on_chip.py --workload \\
        serve-gdn-moe-sat --seeds 11,12,... --control 2 \\
        --faults no_state_handoff,no_exp_g,no_shared_gate,no_attn_gate,rope_whole_head \\
        --fault-seeds 2 --seconds 8 --out <summary.json> --dump <raw.npz>
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import faults  # noqa: E402
import limits_on_chip  # noqa: E402
import qwen3_next_faults  # noqa: E402

if __name__ == "__main__":
    faults.plant = qwen3_next_faults.plant
    sys.exit(limits_on_chip.main())

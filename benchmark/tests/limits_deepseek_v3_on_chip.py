#!/usr/bin/env python3
"""``limits_on_chip.py`` for the latent-attention / grouped-sparse-expert cell:
the same script, arguments and output, with ``--faults`` taking the six names
of ``deepseek_v3_faults.py`` beside those of ``faults.py``.

    python3 benchmark/tests/limits_deepseek_v3_on_chip.py --workload \\
        serve-mla-moe-longctx-sat --seeds 11,12,... --control 2 \\
        --faults no_k_rope,no_mscale,no_latent_norm,no_group_limit,no_shared_expert,plain_rope \\
        --fault-seeds 2 --seconds 8 --out <summary.json> --dump <raw.npz>
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import faults  # noqa: E402
import limits_on_chip  # noqa: E402
import deepseek_v3_faults  # noqa: E402

if __name__ == "__main__":
    faults.plant = deepseek_v3_faults.plant
    sys.exit(limits_on_chip.main())

#!/usr/bin/env python3
"""``limits_on_chip.py`` for the mixed window / full attention sparse-expert cell:
the same script, arguments and output, with ``--faults`` taking the seven names
of ``smallthinker_faults.py`` beside those of ``faults.py``.

    python3 benchmark/tests/limits_smallthinker_on_chip.py --workload \\
        serve-swa-moe-mixed-sat --seeds 11,12,... --control 2 \\
        --faults no_band,rope_on_full,rope_off_window,router_post_attn,silu_gate,ring_not_written,ring_one_block_short \\
        --fault-seeds 2 --seconds 8 --out <summary.json> --dump <raw.npz>
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import faults  # noqa: E402
import limits_on_chip  # noqa: E402
import smallthinker_faults  # noqa: E402

if __name__ == "__main__":
    faults.plant = smallthinker_faults.plant
    sys.exit(limits_on_chip.main())

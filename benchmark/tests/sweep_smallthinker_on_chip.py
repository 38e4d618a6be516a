#!/usr/bin/env python3
"""``sweep_on_chip.py`` for a cell of the ``serve_hybrid`` kind: the same
script, arguments and output, with the cell driven by its own runner (the
sweep names ``serve_runner.run``; the two runners share the loop and the
record).

    python3 benchmark/tests/sweep_smallthinker_on_chip.py --workload \\
        serve-swa-moe-mixed-sat --rates 2.5,3,3.5,4 --seconds 30
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import sweep_on_chip  # noqa: E402

if __name__ == "__main__":
    from harness import serve_hybrid_runner, serve_runner

    serve_runner.run = serve_hybrid_runner.run
    sys.exit(sweep_on_chip.main())

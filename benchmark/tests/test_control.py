"""The control, kept at a size a test run can hold: the reference computed in
int8 — the step below the precision the configurations state — and put in the
program's place must come out as NOT correct against the tiny cells' limits,
in training (loss, first gradient, parameter change) and in serving (the gap
of the token the lower precision puts first). On the chip, at the cells' own
sizes, ``limits_on_chip.py`` reads the same numbers."""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import tiny  # noqa: E402
from harness import common, reference, traffic, train_runner  # noqa: E402

SEEDS = [3, 2**31 + 17, 99]


@pytest.mark.parametrize("seed", SEEDS)
def test_int8_training_fails_the_comparison(seed):
    spec, cfg = tiny.train_cell(), tiny.TINY_CONFIG
    rows = traffic.token_rows(spec["traffic"], seed, cfg["vocab_size"])
    batches = [rows[2 * k:2 * k + 2] for k in range(3)]
    ref = reference.train_reference(cfg, spec["optimizer"], seed, batches)
    low = reference.train_reference(cfg, spec["optimizer"], seed, batches, quant=True)
    chk = common.Checks(lambda m: None)
    train_runner.compare(low, ref, spec["limits"], chk)
    assert not chk.ok
    failed = {name for name, _, _, ok in chk.rows if not ok}
    assert "first_grad_worst_leaf_gap" in failed, chk.rows
    same = common.Checks(lambda m: None)
    train_runner.compare(ref, ref, spec["limits"], same)
    assert same.ok


@pytest.mark.parametrize("seed", SEEDS)
def test_int8_serving_puts_another_token_first(seed):
    import jax.numpy as jnp

    cfg, spec = tiny.TINY_CONFIG, tiny.serve_cell()
    rng = np.random.default_rng(seed)
    # greedy continuations by the reference itself stand in for served tokens
    seqs = [list(rng.integers(0, cfg["vocab_size"], 40)) for _ in range(3)]
    out = reference.served_token_gaps(
        cfg, seed, seqs, [8, 8, 8], jnp.float32, quant=True, rows=2)
    gaps, ctl = out["gap"], out["control_gap"]
    assert len(gaps) == len(ctl) == 3 and all(len(g) == 32 for g in ctl)
    assert all((m >= 0).all() for m in out["margin"])
    from harness import serve_runner

    low = serve_runner.gap_stats(ctl)
    assert any(low[name] > limit for name, limit in spec["limits"].items())
    assert serve_runner.gap_stats(gaps)["n"] == 96

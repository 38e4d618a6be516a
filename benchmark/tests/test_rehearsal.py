"""CPU rehearsals of both runners, and the tests the contract asks for: the
timed path broken underneath (``faults.py`` patches the program in place) makes
``correct`` false, a compile inside the window makes it false, and a configuration, a cell and a per-layer metric are
added as new files alone. Run by hand, not tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def rehearse(case, *args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_NUM_CPU_DEVICES=str(devices))
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse.py"), case, *args],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


@pytest.mark.parametrize("case,devices", [
    ("train", 1), ("train4", 4), ("serve", 1), ("sat", 1)])
def test_sound_run_is_correct(case, devices):
    result, log = rehearse(case, devices=devices)
    assert result["correct"] is True, log
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert result["device"]["count"] == devices
    assert "setup_s" in result["metrics"] and len(result["metrics"]) >= 2


@pytest.mark.parametrize("case,fault,check", [
    ("train", "half_batch", "loss_gap_step1"),
    ("train", "frozen_state", "param_change_worst_leaf_gap"),
    ("train", "compile_in_window", "compiles_in_window"),
    ("train", "lr_off_1pct", "param_change_worst_leaf_gap"),
    ("serve", "wrong_token", "served_token_mean_logit_gap"),
    ("serve", "one_token", "widest_logit_gap"),
    ("sat", "one_token", "worst_request_mean_logit_gap"),
    ("serve", "int8_kv", "served_token_mean_logit_gap"),
])
def test_broken_timed_path_is_not_correct(case, fault, check):
    result, log = rehearse(case, "--fault", fault)
    assert result["correct"] is False
    failed = [l for l in log.splitlines() if l.startswith("check ") and "FAILED" in l]
    assert any(check in l for l in failed), log


def test_a_cell_a_config_and_a_metric_are_added_as_files_alone():
    # rehearse.py copies the committed benchmark, ADDS configs/tiny-dense.json,
    # workloads/tiny-serve.json and metrics/reference_s.tiny.json plus one
    # BENCHMARK.json entry each, and edits no file that was there
    result, log = rehearse("serve", "--trace", "1")
    assert result["correct"] is True, log
    assert "reference_s.tiny" in result["metrics"], result
    assert "prefill_call_ms.itl" in result["metrics"]

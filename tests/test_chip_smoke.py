"""``chip_smoke.py`` off the chip: the default invocation must refuse — fast,
non-zero, no result line — and the ``--cpu-dry-run`` rehearsal must walk all
three phases at tiny size without ever being able to print the pass line.
(The real run needs a TPU; ``CHANGES.md`` carries its output.)
"""

import json
import os
import subprocess
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*flags, timeout):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_NUM_CPU_DEVICES", None)
    return subprocess.run(
        [sys.executable, "chip_smoke.py", *flags], cwd=REPO_ROOT, env=env,
        capture_output=True, text=True, timeout=timeout,
    )


def _result_lines(stdout: str) -> list:
    out = []
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "ok" in obj:
            out.append(obj)
    return out


def test_default_invocation_fails_fast_without_a_chip():
    t0 = time.monotonic()
    proc = _run(timeout=120)
    assert proc.returncode != 0
    assert time.monotonic() - t0 < 60  # before any model is built
    assert proc.stdout.strip() == ""  # no phase ran, no result printed
    assert "not a TPU" in proc.stderr


def test_script_alone_without_the_package_fails(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO_ROOT, "chip_smoke.py"), tmp_path)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", "--cpu-dry-run"], cwd=tmp_path,
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == "" and "accelerate_tpu" in proc.stderr


def test_cpu_dry_run_rehearses_every_phase_but_never_passes():
    proc = _run("--cpu-dry-run", timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.splitlines()
    assert lines and all(l.startswith("[DRY RUN cpu x1]") for l in lines)
    for phase in ("kernel", "train", "hybrid", "serve", "eva"):
        assert any(f"{phase} phase PASSED" in l for l in lines), phase
    assert _result_lines(proc.stdout) == []
    assert "NOT a pass" in lines[-1]


def test_a_subset_of_phases_runs_alone_and_never_passes():
    proc = _run("--cpu-dry-run", "--phases", "hybrid", timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    passed = [l for l in proc.stdout.splitlines() if "phase PASSED" in l]
    assert len(passed) == 1 and "hybrid phase PASSED" in passed[0]
    assert _result_lines(proc.stdout) == []
    assert _run("--cpu-dry-run", "--phases", "nothing", timeout=60).returncode == 2

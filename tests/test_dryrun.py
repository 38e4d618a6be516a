"""The driver-facing multichip dryrun must stay clean: all phases
(dp/fsdp/ep/tp ragged + capacity, sp ring, pp, pp x sp, pp x ep) execute,
each proves itself against its trivial-mesh/sequential oracle
("oracle-match"), AND the SPMD partitioner emits zero
"Involuntary full rematerialization" warnings (VERDICT r2 weak #1 — each
such warning is a real per-step full reshard at scale).

Runs in a subprocess: the warnings are printed by XLA's C++ logging on
stderr, invisible to in-process capture.
"""

import subprocess
import sys

import pytest


@pytest.mark.slow
def test_dryrun_multichip_clean():
    code = (
        "import jax\n"
        "jax.config.update('jax_platforms','cpu')\n"
        "jax.config.update('jax_num_cpu_devices',8)\n"
        "import __graft_entry__\n"
        "__graft_entry__._dryrun_impl(8)\n"
    )
    import os

    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=1200,
        cwd=os.path.dirname(os.path.dirname(__file__)),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = proc.stdout + proc.stderr
    assert "dryrun multichip(8)" in out
    assert "dryrun sp phase" in out
    assert "dryrun pp phase" in out
    assert "dryrun pp x sp phase" in out
    assert "dryrun pp x ep phase" in out
    # self-certification: every phase proves itself against its
    # trivial-mesh/sequential oracle, not isfinite
    assert out.count("oracle-match") >= 7, out
    n_reshard = out.count("Involuntary full rematerialization")
    assert n_reshard == 0, (
        f"{n_reshard} involuntary reshard warnings in dryrun:\n"
        + "\n".join(
            l for l in out.splitlines() if "Involuntary" in l
        )[:2000]
    )

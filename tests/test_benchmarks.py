"""Deadline-aware benchmark subsystem tests.

Three layers:

* pure-logic fake-clock tests for the scheduler (budget allocation never
  exceeds the global window, skip-with-record, runtime re-clamp),
  estimates persistence, partial-snapshot round-trips and the registry;
* fake-launch runner tests (no subprocess, no wall time): streaming
  order, budget-kill partial harvest, crashes —
  including the fixed first_rec fallback;
* slow-marked end-to-end subprocess tests: a SIGKILLed child leaves a
  recoverable partial, and ``bench.py --fast --deadline 120`` produces a
  complete stream with the headline on the last line (the driver
  contract). ``make bench-fast-smoke`` runs these two.
"""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from accelerate_tpu.benchmarks import (
    BenchRunner,
    Deadline,
    DeadlineScheduler,
    Estimates,
    LaunchResult,
    PartialWriter,
    Variant,
    VariantRegistry,
    build_registry,
    partial_path,
    partial_record,
    read_partial,
)
from accelerate_tpu.benchmarks.registry import ENV_ITERS
from accelerate_tpu.benchmarks.scheduler import ENV_DEADLINE, skip_record

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, s: float) -> None:
        self.t += s


# --------------------------------------------------------------------- #
# Deadline
# --------------------------------------------------------------------- #
def test_deadline_unbounded_never_expires():
    clock = FakeClock()
    d = Deadline(None, clock=clock)
    clock.advance(1e9)
    assert d.remaining() == float("inf")
    assert not d.expired()
    assert d.fits(1e12)


def test_deadline_tracks_fake_clock():
    clock = FakeClock()
    d = Deadline(100.0, clock=clock)
    clock.advance(30.0)
    assert d.elapsed() == pytest.approx(30.0)
    assert d.remaining() == pytest.approx(70.0)
    assert d.fits(70.0) and not d.fits(70.1)
    clock.advance(70.0)
    assert d.expired()


def test_deadline_from_env(monkeypatch):
    monkeypatch.setenv(ENV_DEADLINE, "42.5")
    assert Deadline.from_env().seconds == pytest.approx(42.5)
    # an explicit override beats the env
    assert Deadline.from_env(10.0).seconds == pytest.approx(10.0)
    monkeypatch.delenv(ENV_DEADLINE)
    assert Deadline.from_env().seconds is None


def test_deadline_rejects_nonpositive():
    with pytest.raises(ValueError):
        Deadline(0)


# --------------------------------------------------------------------- #
# DeadlineScheduler.plan / grant
# --------------------------------------------------------------------- #
def _sched(deadline_s, clock, **kw):
    return DeadlineScheduler(Deadline(deadline_s, clock=clock), **kw)


def test_plan_budget_sum_never_exceeds_deadline():
    # the acceptance-criteria invariant, across deadline/estimate shapes
    cases = [
        (100.0, [10, 10, 10, 10]),
        (100.0, [30, 30, 30, 30]),
        (120.0, [40, 25, 20, 60, 5]),
        (60.0, [59, 59, 59]),
        (500.0, [600, 10, 10]),
    ]
    for deadline_s, ests in cases:
        sched = _sched(deadline_s, FakeClock())
        items = [(f"v{i}", float(e)) for i, e in enumerate(ests)]
        planned, skipped = sched.plan(items)
        total = sum(p.budget_s for p in planned)
        assert total <= deadline_s + 1e-9, (deadline_s, ests, total)
        # every item is accounted for: planned or an explicit skip record
        assert len(planned) + len(skipped) == len(items)


def test_plan_skips_with_record_when_estimate_exceeds_pool():
    sched = _sched(100.0, FakeClock(), slack=1.5, min_budget_s=10.0)
    planned, skipped = sched.plan([("a", 60.0), ("b", 60.0)])
    assert [p.name for p in planned] == ["a"]
    assert planned[0].budget_s == pytest.approx(90.0)  # 60 * 1.5
    (sk,) = skipped
    assert sk["variant"] == "b"
    assert sk["skipped"] == "deadline"
    assert sk["estimated_s"] == pytest.approx(60.0)
    assert sk["remaining_s"] == pytest.approx(10.0)  # pool after a's grant


def test_plan_unbounded_deadline_plans_everything():
    sched = _sched(None, FakeClock(), slack=1.5, min_budget_s=60.0)
    planned, skipped = sched.plan([("a", 10.0), ("b", 1000.0)])
    assert not skipped
    assert [p.name for p in planned] == ["a", "b"]
    assert planned[0].budget_s == pytest.approx(60.0)  # min floor
    assert planned[1].budget_s == pytest.approx(1500.0)


def test_plan_members_attach_to_planned_groups():
    sched = _sched(None, FakeClock())
    planned, _ = sched.plan(
        [("g1", 10.0)], members={"g1": ["dense", "accum"]}
    )
    assert planned[0].members == ("dense", "accum")


def test_grant_reclamps_and_donates_slack():
    clock = FakeClock()
    sched = _sched(100.0, clock, slack=1.5, min_budget_s=10.0)
    planned, _ = sched.plan([("a", 20.0), ("b", 20.0)])
    a, b = planned
    # a finished early: b's grant may absorb the unspent window beyond
    # its planned budget (no later reservations)
    clock.advance(5.0)
    granted = sched.grant(b, reserved_later_s=0.0)
    assert granted == pytest.approx(95.0)
    # with later work reserved, b keeps at least its planned budget but
    # does not eat the reservation
    granted = sched.grant(b, reserved_later_s=50.0)
    assert granted == pytest.approx(45.0)
    # the window collapsed below the estimate: explicit None -> skip
    clock.advance(80.0)
    assert sched.grant(b) is None


def test_grant_unbounded_returns_planned_budget():
    sched = _sched(None, FakeClock())
    planned, _ = sched.plan([("a", 20.0)])
    assert sched.grant(planned[0]) == pytest.approx(planned[0].budget_s)


# --------------------------------------------------------------------- #
# Estimates
# --------------------------------------------------------------------- #
def test_estimates_round_trip(tmp_path):
    path = str(tmp_path / "est.json")
    est = Estimates(path)
    assert est.estimate("dense", 600.0) == pytest.approx(600.0)  # default
    est.observe("dense", 123.4, step_time_s=0.5, compile_time_s=30.0)
    est.save()
    reloaded = Estimates(path).load()
    assert reloaded.estimate("dense", 600.0) == pytest.approx(123.4)
    assert reloaded.data["dense"]["step_time_s"] == pytest.approx(0.5)


def test_estimates_load_tolerates_garbage(tmp_path):
    path = tmp_path / "est.json"
    path.write_text("{not json")
    est = Estimates(str(path)).load()
    assert est.data == {}
    path.write_text('{"dense": 17, "ok": {"total_s": 5}}')
    est = Estimates(str(path)).load()
    assert "dense" not in est.data  # non-dict entry dropped
    assert est.estimate("ok", 1.0) == pytest.approx(5.0)


# --------------------------------------------------------------------- #
# Partial snapshots
# --------------------------------------------------------------------- #
def test_partial_writer_round_trip(tmp_path):
    path = partial_path(str(tmp_path), "dense")
    w = PartialWriter(path, "dense")
    w.update(phase="warmup_done", iters_measured=0)
    snap = read_partial(path)
    assert snap["phase"] == "warmup_done"
    # killed during warmup: nothing publishable
    assert partial_record(snap) is None

    w.update(phase="measuring", iters_measured=7, metric="m", value=42.0,
             unit="u", extra={"step_time_s": 0.1})
    rec = partial_record(read_partial(path), reason="budget")
    assert rec["partial"] is True
    assert rec["partial_reason"] == "budget"
    assert rec["iters_measured"] == 7
    assert rec["value"] == pytest.approx(42.0)
    assert rec["extra"]["step_time_s"] == pytest.approx(0.1)


def test_partial_writer_none_path_is_noop(tmp_path):
    w = PartialWriter(None, "dense")
    w.update(phase="measuring", iters_measured=3, value=1.0)  # must not raise


def test_partial_chunk_cadence(monkeypatch):
    assert PartialWriter(None, "v").chunk(20) == 5  # quarters
    assert PartialWriter(None, "v").chunk(3) == 1
    assert PartialWriter(None, "v", flush_every=2).chunk(20) == 2
    monkeypatch.setenv("ACCELERATE_TPU_BENCH_PARTIAL_EVERY", "3")
    assert PartialWriter(None, "v").chunk(20) == 3


def test_read_partial_missing(tmp_path):
    assert read_partial(str(tmp_path / "nope.json")) is None


# --------------------------------------------------------------------- #
# Registry
# --------------------------------------------------------------------- #
def test_cpu_registry_groups_and_fast_subset():
    reg = build_registry(on_tpu=False)
    assert reg.headline == "dense"
    groups = reg.groups()
    # dense group first (headline priority 0); INSIDE the group accum
    # runs first — the round's first variant eats every cold
    # persistent-cache compile, and that must not be the headline
    # (BENCH_r06: dense ate 61 misses while later variants saw hits)
    assert groups[0][0] == "dense"
    assert [v.name for v in groups[0][1]] == ["accum", "dense"]
    fast = reg.select(fast=True)
    assert set(fast.names) == {"dense", "accum", "overhead", "ckpt", "lora"}
    assert fast.headline == "dense"


def test_tpu_registry_structure():
    reg = build_registry(on_tpu=True)
    groups = dict(reg.groups())
    # the expected-OOM S=8192 xla point runs LAST in its group so a crash
    # cannot take down the measurable 4k point
    xla_group = [v.name for v in groups["longseq_xla"]]
    assert xla_group[-1] == "longseq_xla"
    assert reg.get("longseq_xla").expected_oom
    # decode_load is isolated: a slow/failed load never costs the decode
    # headline
    assert [v.name for v in groups["decode_load"]] == ["decode_load"]
    # group order starts at the headline
    assert reg.groups()[0][0] == "dense"


def test_registry_select_unknown_raises():
    reg = build_registry(on_tpu=False)
    with pytest.raises(KeyError):
        reg.select(names=["dense", "nope"])


def test_registry_iters_env_override_train_only(monkeypatch):
    monkeypatch.setenv(ENV_ITERS, "500")
    reg = build_registry(on_tpu=False)
    assert reg.get("dense").args[3] == 500
    assert reg.get("ckpt").args[3] != 500  # non-train kinds untouched


# --------------------------------------------------------------------- #
# BenchRunner with a fake launcher (no subprocess, no wall time)
# --------------------------------------------------------------------- #
def _v(name, prio, group, *, est=10.0, headline=False, kind="train",
       iters=5):
    return Variant(
        name=name, kind=kind, priority=prio, group=group,
        args=(None, 1, 8, iters, 1), headline=headline,
        default_estimate_s=est,
    )


def _rec(name, value=100.0, unit="tokens/s/chip", mfu=0.5, wall=5.0):
    return {
        "variant": name, "metric": f"m_{name}", "value": value,
        "unit": unit, "vs_baseline": 1.0,
        "extra": {"mfu": mfu, "variant_wall_s": wall, "step_time_s": 0.1},
    }


class FakeLaunch:
    """Scripted launcher: each call pops the next (stdout_records,
    LaunchResult-overrides) response; records every call."""

    def __init__(self, responses):
        self.responses = list(responses)
        self.calls = []

    def __call__(self, members, budget_s):
        self.calls.append((list(members), budget_s))
        recs, kw = self.responses.pop(0)
        stdout = "\n".join(json.dumps(r) for r in recs)
        return LaunchResult(
            kw.get("returncode", 0), stdout, kw.get("stderr", ""),
            timed_out=kw.get("timed_out", False),
        )


def _runner(variants, responses, *, deadline=None, clock=None,
            tmp_path=None, **kw):
    clock = clock or FakeClock()
    reg = VariantRegistry(variants)
    sched = DeadlineScheduler(Deadline(deadline, clock=clock),
                              min_budget_s=kw.pop("min_budget_s", 10.0))
    est = Estimates(str(tmp_path / "est.json") if tmp_path else "/dev/null")
    launch = FakeLaunch(responses)
    emitted = []
    logged = []
    runner = BenchRunner(
        reg, sched, est, launch,
        partial_dir=str(tmp_path) if tmp_path else None,
        emit=emitted.append, log=logged.append, **kw,
    )
    return runner, launch, emitted, logged


def test_runner_streams_provisional_and_prints_headline_last(tmp_path):
    variants = [
        _v("dense", 0, "dense", headline=True),
        _v("accum", 1, "dense"),
        _v("ckpt", 3, "ckpt", kind="ckpt"),
    ]
    responses = [
        ([_rec("dense"), _rec("accum")], {}),
        ([_rec("ckpt", unit="s")], {}),
    ]
    runner, launch, emitted, _ = _runner(variants, responses,
                                         tmp_path=tmp_path)
    assert runner.run() == 0
    # one child per group, dense group first
    assert launch.calls[0][0] == ["dense", "accum"]
    assert launch.calls[1][0] == ["ckpt"]
    lines = [json.loads(l) for l in emitted]
    # provisional lines stream as variants land, before the final block
    assert [l["variant"] for l in lines if l.get("provisional")] == [
        "dense", "accum", "ckpt",
    ]
    # the consolidated block re-prints finals with the headline LAST
    finals = [l for l in lines if not l.get("provisional")]
    assert finals[-1]["variant"] == "dense"
    assert all(not l.get("provisional") for l in finals)
    # measured wall costs became next round's estimates
    assert runner.estimates.estimate("dense", 0.0) == pytest.approx(5.0)


def test_runner_timeout_harvests_partial_record(tmp_path):
    variants = [_v("dense", 0, "dense", headline=True, iters=20)]
    w = PartialWriter(partial_path(str(tmp_path), "dense"), "dense")
    w.update(phase="measuring", iters_measured=11, metric="m", value=7.5,
             unit="u", extra={"step_time_s": 0.2})
    responses = [([], {"timed_out": True, "returncode": -9})]
    runner, _, emitted, logged = _runner(variants, responses,
                                         tmp_path=tmp_path)
    assert runner.run() == 0  # a partial headline still counts as signal
    rec = runner.results["dense"]
    assert rec["partial"] is True
    assert rec["partial_reason"] == "budget"
    assert rec["iters_measured"] == 11
    assert not runner.errors
    # the stream saw it (provisional) and the final block re-printed it
    lines = [json.loads(l) for l in emitted]
    assert any(l.get("partial") and l.get("provisional") for l in lines)
    assert json.loads(emitted[-1])["partial"] is True


def test_runner_timeout_without_partial_is_an_error(tmp_path):
    variants = [_v("dense", 0, "dense", headline=True)]
    responses = [([], {"timed_out": True, "returncode": -9})]
    runner, _, _, _ = _runner(variants, responses, tmp_path=tmp_path)
    assert runner.run() == 1  # no headline signal at all
    assert "timeout" in runner.errors["dense"]


def test_runner_plan_skip_emits_member_records(tmp_path):
    clock = FakeClock()
    variants = [
        _v("dense", 0, "dense", headline=True, est=30.0),
        _v("decode_load", 7, "decode_load", est=200.0, kind="decode_load"),
    ]
    responses = [([_rec("dense")], {})]
    runner, launch, emitted, _ = _runner(
        variants, responses, deadline=100.0, clock=clock, tmp_path=tmp_path,
    )
    assert runner.run() == 0
    # only the fitting group launched; the other left an explicit record
    assert len(launch.calls) == 1
    (sk,) = runner.skipped
    assert sk["variant"] == "decode_load"
    assert sk["skipped"] == "deadline"
    assert sk["estimated_s"] == pytest.approx(200.0)
    assert any(json.loads(l).get("skipped") for l in emitted)


def test_runner_grant_collapse_skips_at_runtime(tmp_path):
    # both groups fit the static plan, but group 1 overruns its budget so
    # badly the runtime grant for group 2 comes back None
    clock = FakeClock()
    variants = [
        _v("dense", 0, "dense", headline=True, est=30.0),
        _v("ckpt", 3, "ckpt", est=30.0, kind="ckpt"),
    ]

    class OverrunLaunch(FakeLaunch):
        def __call__(self, members, budget_s):
            clock.advance(95.0)  # eats nearly the whole window
            return super().__call__(members, budget_s)

    reg = VariantRegistry(variants)
    sched = DeadlineScheduler(Deadline(100.0, clock=clock), min_budget_s=10.0)
    emitted = []
    runner = BenchRunner(
        reg, sched, Estimates(str(tmp_path / "e.json")),
        OverrunLaunch([([_rec("dense")], {})]),
        partial_dir=str(tmp_path), emit=emitted.append,
        log=lambda s: None,
    )
    assert runner.run() == 0
    assert runner.skipped and runner.skipped[0]["variant"] == "ckpt"


def test_runner_slow_number_is_a_number_and_crash_is_an_error(tmp_path):
    # a local chip has no transients to wait out: one launch per group,
    # a 3%-MFU record is published as measured, a crash is an error
    variants = [
        _v("dense", 0, "dense", headline=True),
        _v("accum", 1, "accum"),
    ]
    responses = [
        ([_rec("dense", value=5.0, mfu=0.03)], {}),
        ([], {"returncode": 1, "stderr": "boom"}),
    ]
    runner, launch, _, logged = _runner(variants, responses,
                                        tmp_path=tmp_path)
    assert runner.run() == 0
    assert len(launch.calls) == 2  # one per group, nothing re-run
    rec = runner.results["dense"]
    assert rec["value"] == pytest.approx(5.0)
    assert not rec.get("partial") and "retried" not in rec["extra"]
    assert "boom" in runner.errors["accum"]


def test_runner_oom_is_not_retried(tmp_path):
    variants = [_v("longseq_xla", 6, "longseq_xla")]
    stderr = "... RESOURCE_EXHAUSTED: Out of memory allocating 9G ...\n"
    responses = [([], {"returncode": 1, "stderr": stderr})]
    runner, launch, _, _ = _runner(variants, responses, tmp_path=tmp_path)
    runner.run()
    assert len(launch.calls) == 1  # deterministic OOM: one attempt
    assert "RESOURCE_EXHAUSTED" in runner.errors["longseq_xla"]


def test_runner_child_budget_skip_passes_through(tmp_path):
    variants = [
        _v("dense", 0, "dense", headline=True),
        _v("accum", 1, "dense"),
    ]
    child_skip = skip_record("accum", 30.0, 5.0, reason="budget")
    responses = [([_rec("dense"), child_skip], {})]
    runner, _, emitted, _ = _runner(variants, responses, tmp_path=tmp_path)
    assert runner.run() == 0
    assert any(s["variant"] == "accum" and s["skipped"] == "budget"
               for s in runner.skipped)
    assert "accum" not in runner.errors


def test_runner_folds_longseq_helpers(tmp_path):
    variants = [
        _v("dense", 0, "dense", headline=True),
        _v("longseq", 3, "longseq"),
        _v("longseq4k", 4, "longseq"),
        _v("longseq_xla4k", 5, "longseq_xla"),
        _v("longseq_xla", 6, "longseq_xla"),
    ]

    def train_rec(name, step):
        r = _rec(name)
        r["extra"]["step_time_s"] = step
        return r

    responses = [
        ([train_rec("dense", 0.1)], {}),
        ([train_rec("longseq", 0.3), train_rec("longseq4k", 0.2)], {}),
        ([train_rec("longseq_xla4k", 0.5), train_rec("longseq_xla", 0.9)],
         {}),
    ]
    runner, _, emitted, _ = _runner(variants, responses, tmp_path=tmp_path)
    assert runner.run() == 0
    assert set(runner.results) == {"dense", "longseq"}
    extra = runner.results["longseq"]["extra"]
    assert extra["flash_speedup_vs_xla"] == pytest.approx(3.0)
    assert extra["flash_step_s_s4096"] == pytest.approx(0.2)
    assert extra["xla_step_s_s4096"] == pytest.approx(0.5)
    finals = [json.loads(l) for l in emitted if "provisional" not in l]
    assert json.loads(emitted[-1])["variant"] == "dense"


# --------------------------------------------------------------------- #
# No silent off-chip fallback (peaks table, backend probe, parent imports)
# --------------------------------------------------------------------- #
def test_unknown_device_kind_has_no_peak():
    from accelerate_tpu.benchmarks.measure import PEAK_FLOPS, _peak_flops
    from accelerate_tpu.profiling import ProgramRegistry

    class Device:
        platform = "tpu"

        def __init__(self, kind):
            self.device_kind = kind

    assert _peak_flops(Device("TPU v5 lite")) == 197e12
    with pytest.raises(ValueError, match="no published peak"):
        _peak_flops(Device("TPU v99"))
    with pytest.raises(ValueError, match="no published peak"):
        _peak_flops(Device("cpu"))
    assert not any("cpu" in kind.lower() for kind in PEAK_FLOPS)
    # the record-only roofline answers None on a kind with no peak (this
    # CPU backend) instead of inventing one
    reg = ProgramRegistry()
    reg.register_analysis("p", kind="train", flops=1e9, bytes_accessed=1e6)
    assert reg.roofline("p") is None


def test_bench_refuses_to_measure_off_chip(monkeypatch, capsys):
    from accelerate_tpu.benchmarks import cli

    monkeypatch.setattr(cli, "_detect_backend", lambda: "cpu")
    assert cli.main([]) == 2
    assert cli.main(["dense"]) == 2
    assert "refusing" in capsys.readouterr().err
    # --list is not a measurement; --fast is the CPU harness smoke
    assert cli.main(["--list", "--fast"]) == 0


def test_backend_probe_failure_is_fatal(monkeypatch):
    import subprocess

    from accelerate_tpu.benchmarks import cli

    class Probe:
        returncode, stdout, stderr = 1, "", "libtpu: no chip"

    monkeypatch.setattr(subprocess, "run", lambda *a, **k: Probe())
    with pytest.raises(SystemExit, match="backend probe failed"):
        cli._detect_backend()
    Probe.returncode = 0  # a probe that prints nothing is no answer either
    with pytest.raises(SystemExit, match="backend probe failed"):
        cli._detect_backend()


def test_parents_of_chip_children_never_import_jax():
    # a process that touched JAX holds the chip: the bench parent and
    # `accelerate-tpu launch` must be able to do their whole job without it
    code = (
        "import sys\n"
        "import accelerate_tpu.commands.accelerate_cli\n"
        "import accelerate_tpu.commands.launch\n"
        "from accelerate_tpu.benchmarks import cli\n"
        "from accelerate_tpu.benchmarks.scheduler import Estimates\n"
        "cli.build_registry(True); cli.build_registry(False)\n"
        "Estimates.default_path()\n"
        "bad = [m for m in sys.modules if m.split('.')[0] in ('jax', 'jaxlib', 'flax')]\n"
        "assert not bad, bad[:5]\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO_ROOT,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]


# --------------------------------------------------------------------- #
# Harness overhead (satellite: bounded diagnostics per-step cost)
# --------------------------------------------------------------------- #
def test_anomaly_sample_every_bounds_baseline_folds():
    from accelerate_tpu.diagnostics.anomaly import AnomalyDetector
    from accelerate_tpu.diagnostics.config import DiagnosticsConfig

    det = AnomalyDetector(DiagnosticsConfig(anomaly_sample_every=4))
    for i in range(32):
        det.observe({"kind": "step", "step": i, "step_time_s": 0.01,
                     "loss": 1.0}, {"loss": 1.0, "grad_norm": 1.0})
    # only every 4th record entered the windows
    assert len(det._windows["step_time_s"]) == 8
    assert len(det._windows["loss"]) == 8
    # NaN detection is exempt from sampling: fires on an off-sample step
    out = det.observe({"kind": "step", "step": 33, "step_time_s": 0.01,
                       "loss": float("nan")}, {"loss": float("nan")})
    assert out and out[0]["anomaly_type"] == "nan_grad"


def test_anomaly_sample_every_validation():
    from accelerate_tpu.diagnostics.config import DiagnosticsConfig

    with pytest.raises(ValueError):
        DiagnosticsConfig(anomaly_sample_every=0)


def test_harness_overhead_under_2pct():
    # the regression bound from the acceptance criteria: telemetry +
    # full diagnostics ON vs OFF on the same loop, median step delta
    # < 2% on CPU. Medians make this robust to scheduler jitter.
    from accelerate_tpu.benchmarks.measure import _run_overhead
    from accelerate_tpu.models import TransformerConfig

    rec = _run_overhead(TransformerConfig.tiny(), 8, 256, iters=30, warmup=5)
    assert rec["metric"] == "harness_overhead_pct"
    assert rec["value"] < 2.0, rec
    assert rec["extra"]["step_records_emitted_on"] > 0


# --------------------------------------------------------------------- #
# End-to-end subprocess tests (slow tier; `make bench-fast-smoke`)
# --------------------------------------------------------------------- #
def _child_env(extra=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONUNBUFFERED"] = "1"
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra or {})
    return env


@pytest.mark.slow
def test_sigkilled_child_leaves_recoverable_partial(tmp_path):
    """A child killed MID-MEASUREMENT (SIGKILL — no handlers, no atexit)
    must leave an fsync'd snapshot the parent can publish with
    iters_measured > 0."""
    partial_dir = str(tmp_path)
    proc = subprocess.Popen(
        [sys.executable, "-m", "accelerate_tpu.benchmarks",
         "--child", "dense", "--partial-dir", partial_dir],
        cwd=REPO_ROOT,
        env=_child_env({
            ENV_ITERS: "100000",  # stretch the measured loop
            "ACCELERATE_TPU_BENCH_PARTIAL_EVERY": "5",
            # a private compile cache: the child's pipes are not drained
            # here, and a warm XLA:CPU cache floods stderr on load
            "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla_cache"),
        }),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    path = partial_path(partial_dir, "dense")
    try:
        deadline = time.monotonic() + 180.0
        snap = None
        while time.monotonic() < deadline:
            snap = read_partial(path)
            if snap and snap.get("iters_measured", 0) > 0:
                break
            if proc.poll() is not None:
                pytest.fail(
                    "child exited before being killed: "
                    + proc.stderr.read().decode(errors="replace")[-2000:]
                )
            time.sleep(0.2)
        else:
            pytest.fail("no mid-measurement snapshot within 180s")
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    rec = partial_record(read_partial(path), reason="budget")
    assert rec is not None
    assert rec["partial"] is True
    assert rec["iters_measured"] > 0
    assert rec["value"] is not None


@pytest.mark.slow
def test_bench_fast_deadline_end_to_end(tmp_path):
    """Acceptance: `python bench.py --fast --deadline 120` on CPU exits 0
    within the deadline, the last stdout line is the parseable dense
    headline, and every fast variant is accounted for (final, partial,
    or an explicit skip)."""
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "bench.py", "--fast", "--deadline", "120"],
        cwd=REPO_ROOT,
        env=_child_env({
            # a private estimates/cache location: the test must not
            # inherit (or pollute) the operator's persisted estimates
            "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla_cache"),
        }),
        capture_output=True, text=True, timeout=150,
    )
    elapsed = time.monotonic() - t0
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert elapsed < 130.0
    lines = [json.loads(l) for l in proc.stdout.splitlines()
             if l.strip().startswith("{")]
    assert lines, proc.stdout
    last = lines[-1]
    # the driver contract: last line is the dense headline, not marked
    # provisional, carrying a real number
    assert last["variant"] == "dense"
    assert "provisional" not in last
    assert last["value"] > 0
    assert last["unit"] == "tokens/s/chip"
    # complete stream: every fast variant accounted for
    accounted = {l["variant"] for l in lines
                 if not l.get("provisional")}
    assert {"dense", "accum", "overhead", "ckpt"} <= accounted
    # the harness proves itself cheap every round
    overhead = next(l for l in lines if l["variant"] == "overhead"
                    and not l.get("provisional"))
    if not overhead.get("partial") and not overhead.get("skipped"):
        assert overhead["value"] < 2.0, overhead
    # estimates persisted next to the (private) cache dir for round n+1
    assert os.path.exists(str(tmp_path / "xla_cache") + ".estimates.json")

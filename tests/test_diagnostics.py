"""Diagnostics subsystem tests — goodput accounting, anomaly detection,
triggered trace capture, the flight recorder, and `accelerate-tpu
diagnose`. All CPU-runnable; the SIGKILL survivability test is
slow-marked (subprocess tier)."""

import json
import math
import os
import signal
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from accelerate_tpu import (
    Accelerator,
    DataLoader,
    DiagnosticsConfig,
    JSONLSink,
    PrometheusTextSink,
    StepTelemetry,
    TelemetryConfig,
)
from accelerate_tpu.diagnostics import (
    AnomalyDetector,
    DiagnosticsManager,
    FlightRecorder,
    GoodputAccounting,
    TraceCapture,
    build_report,
    format_report,
    list_dumps,
)


def _fresh_accelerator(**kwargs) -> Accelerator:
    from accelerate_tpu.state import AcceleratorState, GradientState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    return Accelerator(**kwargs)


def _step_record(step, step_time_s=0.1, **fields):
    return {
        "kind": "step",
        "label": "step",
        "step": step,
        "time_unix": time.time(),
        "step_time_s": step_time_s,
        "retraced": False,
        **fields,
    }


class _ProfilerStub:
    """Stand-in for jax.profiler start/stop (a real CPU trace session is
    slow and single-session-global; the capture logic is what's under
    test)."""

    def __init__(self, monkeypatch):
        self.starts: list[str] = []
        self.stops = 0
        monkeypatch.setattr(
            jax.profiler, "start_trace", lambda d, **kw: self.starts.append(d)
        )
        monkeypatch.setattr(
            jax.profiler, "stop_trace",
            lambda: setattr(self, "stops", self.stops + 1),
        )


# ---------------------------------------------------------------------- #
# goodput accounting
# ---------------------------------------------------------------------- #
def test_goodput_buckets_sum_to_wall_clock():
    """Acceptance: folding a synthetic record stream, the buckets sum to
    wall-clock exactly (idle is the remainder by construction)."""
    g = GoodputAccounting(window_s=60.0, now=0.0)
    now = 0.0
    for i in range(20):
        now += 0.5
        g.observe(
            _step_record(i, step_time_s=0.4, dataloader_wait_s=0.05), now=now
        )
    now += 3.0
    g.observe({"kind": "compile", "compile_time_s": 2.5}, now=now)
    now += 1.0
    g.observe({"kind": "checkpoint", "blocked_s": 0.7}, now=now)
    snap = g.snapshot(now=now)
    assert snap["wall_s"] == pytest.approx(14.0)
    assert sum(snap["buckets"].values()) == pytest.approx(snap["wall_s"], abs=1e-9)
    assert snap["buckets"]["productive"] == pytest.approx(20 * 0.4)
    assert snap["buckets"]["compile"] == pytest.approx(2.5)
    assert snap["buckets"]["dataloader"] == pytest.approx(20 * 0.05)
    assert snap["buckets"]["checkpoint"] == pytest.approx(0.7)
    assert snap["goodput_pct"] == pytest.approx(100.0 * 8.0 / 14.0)


def test_goodput_in_step_compile_is_badput_not_productive():
    g = GoodputAccounting(now=0.0)
    # a retrace step: 5s wall, 4.5s of it XLA compile
    g.observe(_step_record(0, step_time_s=5.0, compile_time_s=4.5), now=5.0)
    snap = g.snapshot(now=5.0)
    assert snap["buckets"]["productive"] == pytest.approx(0.5)
    assert snap["buckets"]["compile"] == pytest.approx(4.5)


def test_goodput_rolling_window_forgets_old_badput():
    g = GoodputAccounting(window_s=10.0, now=0.0)
    g.observe({"kind": "compile", "compile_time_s": 50.0}, now=1.0)  # old
    now = 100.0
    for i in range(8):
        now += 1.0
        g.observe(_step_record(i, step_time_s=1.0), now=now)
    snap = g.snapshot(now=now)
    # run-total goodput is dragged down by the compile...
    assert snap["goodput_pct"] < 10.0
    # ...but the rolling window only sees the recent productive steps
    assert snap["rolling_goodput_pct"] == pytest.approx(80.0)


def test_goodput_record_is_flat_and_sink_ready():
    g = GoodputAccounting(now=0.0)
    g.observe(_step_record(3, step_time_s=1.0), now=2.0)
    rec = g.record(step=3, now=4.0)
    assert rec["kind"] == "goodput"
    assert rec["wall_s"] == pytest.approx(4.0)
    assert rec["productive_s"] == pytest.approx(1.0)
    for bucket in ("compile", "dataloader", "checkpoint", "idle"):
        assert isinstance(rec[f"badput_{bucket}_s"], float)
    assert rec["badput_idle_s"] == pytest.approx(3.0)
    json.dumps(rec)  # flat and JSON-able for every sink


def test_goodput_rejects_unknown_bucket():
    with pytest.raises(ValueError):
        GoodputAccounting().add("naptime", 1.0)


# ---------------------------------------------------------------------- #
# anomaly detection
# ---------------------------------------------------------------------- #
def test_slow_step_fires_exactly_once_under_cooldown():
    """Acceptance: an injected slow step produces exactly one rate-limited
    anomaly record, even when the stall persists for several steps."""
    det = AnomalyDetector(DiagnosticsConfig(anomaly_min_samples=4))
    fired = []
    now = 0.0
    for i in range(10):
        now += 0.1
        fired += det.observe(_step_record(i, step_time_s=0.1), now=now)
    assert fired == []  # a steady baseline never alarms
    for i in range(10, 16):  # the straggler regime: every step 50x slower
        now += 5.0
        fired += det.observe(_step_record(i, step_time_s=5.0), now=now)
    assert len(fired) == 1
    rec = fired[0]
    assert rec["kind"] == "anomaly"
    assert rec["anomaly_type"] == "slow_step"
    assert rec["step"] == 10
    assert rec["value"] == pytest.approx(5.0)
    assert rec["baseline_median"] == pytest.approx(0.1)
    assert rec["record"]["step_time_s"] == pytest.approx(5.0)  # evidence attached
    # repeats were suppressed, and the NEXT fired record reports them
    assert det._suppressed["slow_step"] == 5
    assert det.counts["slow_step"] == 6


def test_suppressed_count_reported_on_next_fire():
    det = AnomalyDetector(
        DiagnosticsConfig(
            anomaly_min_samples=4, anomaly_cooldown_steps=3, anomaly_cooldown_s=0.0
        )
    )
    fired = []
    for i in range(20):
        scalars = {"loss": float("nan")} if i >= 10 else {"loss": 1.0}
        fired += det.observe(_step_record(i), scalars, now=float(i))
    # NaN at steps 10..19 with cooldown 3: fires at 10, 13, 16, 19
    assert [f["step"] for f in fired] == [10, 13, 16, 19]
    assert fired[0]["suppressed_since_last"] == 0
    assert fired[1]["suppressed_since_last"] == 2
    assert fired[-1]["total_of_type"] == 10


def test_nan_grad_fires_immediately_without_baseline():
    det = AnomalyDetector(DiagnosticsConfig())
    fired = det.observe(
        _step_record(0), {"loss": 1.0, "grad_norm": float("inf")}, now=0.0
    )
    assert len(fired) == 1
    assert fired[0]["anomaly_type"] == "nan_grad"
    assert fired[0]["fields"] == "grad_norm"


def test_grads_finite_zero_is_a_nan_signal():
    det = AnomalyDetector(DiagnosticsConfig())
    fired = det.observe(
        _step_record(0), {"loss": 1.0, "grads_finite": 0.0}, now=0.0
    )
    assert [f["anomaly_type"] for f in fired] == ["nan_grad"]


def test_loss_spike_fires_and_retraced_steps_never_slow_step():
    det = AnomalyDetector(DiagnosticsConfig(anomaly_min_samples=4))
    fired = []
    now = 0.0
    for i in range(8):
        now += 0.1
        fired += det.observe(_step_record(i), {"loss": 1.0}, now=now)
    # a retraced step is slow because it compiled — never a straggler alarm
    now += 60.0
    fired += det.observe(
        _step_record(8, step_time_s=60.0, retraced=True), {"loss": 1.0}, now=now
    )
    assert fired == []
    now += 0.1
    fired += det.observe(_step_record(9), {"loss": 500.0}, now=now)
    assert [f["anomaly_type"] for f in fired] == ["loss_spike"]
    assert fired[0]["value"] == pytest.approx(500.0)


def test_nan_grad_detected_through_collector_raw_scalars(tmp_path):
    """The collector strips non-finite grad_norm from the RECORD (invalid
    JSON) — detection must still see the raw value, and exactly one
    anomaly record must reach the stream."""
    tel = StepTelemetry(
        TelemetryConfig(
            heartbeat=False,
            diagnostics=DiagnosticsConfig(dir=None, goodput_interval=0),
        )
    )
    for i in range(5):
        tel.begin_step()
        tel.end_step(
            None, step=i,
            metrics={"loss": 1.0, "grad_norm": float("nan"), "is_sync_step": 1.0},
        )
    steps = [r for r in tel.records if r["kind"] == "step"]
    assert all("grad_norm" not in r for r in steps)  # stripped from records
    anomalies = [r for r in tel.records if r["kind"] == "anomaly"]
    assert len(anomalies) == 1  # rate-limited: a NaN storm is ONE record
    assert anomalies[0]["anomaly_type"] == "nan_grad"
    tel.close()


def test_anomaly_sample_every_bounds_baseline_folds():
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
    with pytest.raises(ValueError):
        DiagnosticsConfig(anomaly_sample_every=0)


# ---------------------------------------------------------------------- #
# triggered trace capture
# ---------------------------------------------------------------------- #
def test_capture_bounded_by_max_captures(tmp_path, monkeypatch):
    stub = _ProfilerStub(monkeypatch)
    cap = TraceCapture(
        DiagnosticsConfig(
            trace_dir=str(tmp_path), capture_steps=2, max_captures=2
        )
    )
    for step in range(20):
        cap.request("anomaly_slow_step")
        cap.on_step(step)
    assert len(cap.captures) == 2  # acceptance: at most K captures per run
    assert stub.starts == [c["dir"] for c in cap.captures]
    assert stub.stops == 2
    assert cap.exhausted and not cap.active
    for entry in cap.captures:
        assert os.path.isdir(entry["dir"])
        assert "anomaly_slow_step" in os.path.basename(entry["dir"])
    assert cap.request("more") is False


def test_capture_runs_for_capture_steps_then_stops(tmp_path, monkeypatch):
    stub = _ProfilerStub(monkeypatch)
    cap = TraceCapture(
        DiagnosticsConfig(trace_dir=str(tmp_path), capture_steps=3)
    )
    cap.request("x")
    started = cap.on_step(0)
    assert started is not None and cap.active
    cap.on_step(1)
    cap.on_step(2)
    assert cap.active and stub.stops == 0
    cap.on_step(3)  # 3 captured steps done
    assert not cap.active and stub.stops == 1


def test_capture_disabled_without_trace_dir(monkeypatch):
    stub = _ProfilerStub(monkeypatch)
    cap = TraceCapture(DiagnosticsConfig(trace_dir=None))
    assert cap.request("anomaly") is False
    cap.on_step(0)
    assert stub.starts == [] and cap.captures == []


def test_trigger_file_touch_starts_one_capture(tmp_path, monkeypatch):
    stub = _ProfilerStub(monkeypatch)
    trigger = tmp_path / "trace-now"
    cap = TraceCapture(
        DiagnosticsConfig(
            trace_dir=str(tmp_path / "traces"),
            capture_steps=1,
            trigger_file=str(trigger),
        )
    )
    cap.on_step(0)
    assert stub.starts == []  # no trigger yet
    trigger.write_text("go")
    cap.on_step(1)
    assert len(stub.starts) == 1
    assert "trigger_file" in stub.starts[0]
    cap.on_step(2)  # same mtime: consumed, not re-fired
    cap.on_step(3)
    assert len(stub.starts) == 1


def test_capture_start_failure_never_raises(tmp_path, monkeypatch):
    def _boom(dir, **kw):
        raise RuntimeError("profiler already active")

    monkeypatch.setattr(jax.profiler, "start_trace", _boom)
    cap = TraceCapture(DiagnosticsConfig(trace_dir=str(tmp_path)))
    cap.request("anomaly")
    assert cap.on_step(0) is None  # logged, not raised
    assert cap.captures == [] and not cap.active


# ---------------------------------------------------------------------- #
# flight recorder
# ---------------------------------------------------------------------- #
def test_flight_recorder_dump_atomic_with_ring_and_checkpoint(tmp_path):
    rec = FlightRecorder(
        DiagnosticsConfig(dir=str(tmp_path), ring_size=4, dump_interval_s=1e9),
        process_index=0,
    )
    for i in range(10):
        rec.observe(_step_record(i))
    rec.observe(
        {"kind": "checkpoint", "step": 8, "dir": "/ck/checkpoint_8",
         "time_unix": 123.0}
    )
    path = rec.dump("test")
    assert path == str(tmp_path / "flightrec-rank0.json")
    payload = json.loads(open(path).read())
    assert payload["kind"] == "flight_recorder"
    assert payload["reason"] == "test"
    assert payload["last_step"] == 9
    assert payload["last_checkpoint"]["dir"] == "/ck/checkpoint_8"
    assert payload["last_checkpoint"]["step"] == 8
    assert len(payload["records"]) == 4  # the ring, not the full history
    assert not [
        f for f in os.listdir(tmp_path) if ".tmp" in f
    ]  # tmp committed via os.replace


def test_flight_recorder_periodic_dump_from_observe(tmp_path):
    rec = FlightRecorder(
        DiagnosticsConfig(dir=str(tmp_path), dump_interval_s=0.0)
    )
    rec.observe(_step_record(1))
    dumps = list_dumps(str(tmp_path))
    assert dumps and dumps[rec.process_index]["reason"] == "periodic"


def test_flight_recorder_excepthook_dumps_then_chains(tmp_path):
    rec = FlightRecorder(DiagnosticsConfig(dir=str(tmp_path)), process_index=0)
    seen = []
    prev, sys.excepthook = sys.excepthook, lambda *a: seen.append(a)
    try:
        rec.install_excepthook()
        try:
            raise ValueError("boom")
        except ValueError:
            sys.excepthook(*sys.exc_info())
    finally:
        rec.uninstall_excepthook()
        sys.excepthook = prev
    assert len(seen) == 1  # the previous hook still ran
    payload = list_dumps(str(tmp_path))[0]
    assert payload["reason"] == "exception:ValueError"
    events = [e for e in payload["events"] if e["event"] == "exception"]
    assert "ValueError: boom" in events[0]["exception"]
    assert "boom" in events[0]["traceback"]


def test_list_dumps_skips_torn_files(tmp_path):
    (tmp_path / "flightrec-rank0.json").write_text('{"process_index": 0, "x"')
    (tmp_path / "flightrec-rank1.json").write_text(
        json.dumps({"process_index": 1, "last_step": 7})
    )
    dumps = list_dumps(str(tmp_path))
    assert list(dumps) == [1]


# ---------------------------------------------------------------------- #
# the manager: records -> anomalies -> capture -> goodput stream
# ---------------------------------------------------------------------- #
def test_manager_anomaly_triggers_bounded_captures(tmp_path, monkeypatch):
    stub = _ProfilerStub(monkeypatch)
    mgr = DiagnosticsManager(
        DiagnosticsConfig(
            dir=str(tmp_path / "diag"),
            trace_dir=str(tmp_path / "traces"),
            capture_steps=1,
            max_captures=2,
            anomaly_cooldown_steps=0,
            anomaly_cooldown_s=0.0,
            goodput_interval=0,
            install_excepthook=False,
        ),
        process_index=0,
    )
    for i in range(6):  # every step has a NaN loss -> 6 anomalies fire
        out = mgr.observe(_step_record(i), {"loss": float("nan")})
        assert [r["kind"] for r in out] == ["anomaly"]
    assert len(stub.starts) == 2  # but captures stay bounded at K
    assert mgr.capture.exhausted
    events = [e["event"] for e in mgr.recorder.events]
    assert events.count("anomaly") == 6
    assert events.count("trace_capture") == 2
    mgr.close()


def test_manager_emits_goodput_records_on_interval():
    mgr = DiagnosticsManager(
        DiagnosticsConfig(goodput_interval=3, anomaly=False)
    )
    kinds = []
    for i in range(9):
        kinds += [r["kind"] for r in mgr.observe(_step_record(i))]
    assert kinds == ["goodput", "goodput", "goodput"]
    # derived records re-enter observe once and derive nothing further
    assert mgr.observe({"kind": "goodput", "wall_s": 1.0}) == []


def test_manager_record_wait_feeds_goodput_and_stall_events(tmp_path):
    mgr = DiagnosticsManager(
        DiagnosticsConfig(
            dir=str(tmp_path), dataloader_stall_event_s=1.0,
            install_excepthook=False,
        )
    )
    mgr.record_wait(0.2, source="shard")   # routine wait: bucket only
    mgr.record_wait(2.5, source="shard")   # stall: bucket + event + dump
    assert mgr.goodput.totals["dataloader"] == pytest.approx(2.7)
    stalls = [e for e in mgr.recorder.events if e["event"] == "dataloader_stall"]
    assert len(stalls) == 1 and stalls[0]["seconds"] == pytest.approx(2.5)
    mgr.close()


def test_manager_on_stall_dumps(tmp_path):
    mgr = DiagnosticsManager(
        DiagnosticsConfig(dir=str(tmp_path), install_excepthook=False)
    )
    mgr.on_stall(
        type("FakeMonitor", (), {"last_step": 41, "stall_timeout_s": 300.0})()
    )
    payload = list_dumps(str(tmp_path))[mgr.recorder.process_index]
    assert payload["reason"] == "heartbeat_stall"
    assert payload["events"][-1]["last_step"] == 41
    mgr.close()


# ---------------------------------------------------------------------- #
# sinks (satellites)
# ---------------------------------------------------------------------- #
def test_prometheus_sink_escapes_label_values(tmp_path):
    path = tmp_path / "metrics.prom"
    sink = PrometheusTextSink(str(path))
    sink.emit(
        {"kind": "step", "label": 'train"fn\\v1\nx', "step_time_s": 0.5}
    )
    text = path.read_text()
    assert 'label="train\\"fn\\\\v1\\nx"' in text
    assert "\nx" not in text.split("label=")[1].split(" ")[0]  # no raw newline


def test_prometheus_sink_exports_goodput_records(tmp_path):
    path = tmp_path / "metrics.prom"
    sink = PrometheusTextSink(str(path))
    sink.emit(
        {"kind": "goodput", "label": "goodput", "goodput_pct": 87.5,
         "badput_compile_s": 12.0}
    )
    text = path.read_text()
    assert "accelerate_tpu_goodput_pct" in text
    assert "87.5" in text
    assert "accelerate_tpu_badput_compile_s" in text


def test_jsonl_sink_close_flushes_durably(tmp_path):
    path = tmp_path / "t.jsonl"
    sink = JSONLSink(str(path))
    sink.emit({"kind": "step", "step": 1})
    sink.close()
    sink.close()  # idempotent
    assert json.loads(path.read_text().strip())["step"] == 1


# ---------------------------------------------------------------------- #
# PeakHostMemory deterministic stop (satellite)
# ---------------------------------------------------------------------- #
def test_peak_host_memory_stop_joins_thread_and_restarts():
    from accelerate_tpu.utils.profiling import PeakHostMemory

    tracker = PeakHostMemory()
    before = threading.active_count()
    for _ in range(3):  # repeated brackets on ONE tracker never stack threads
        tracker.start()
        thread = tracker._thread
        peak = tracker.stop()
        assert peak > 0
        assert not thread.is_alive()  # stop() joined, deterministically
        assert tracker._thread is None
    assert threading.active_count() == before
    assert tracker.stop() == peak  # idempotent


def test_peak_host_memory_double_start_raises():
    from accelerate_tpu.utils.profiling import PeakHostMemory

    tracker = PeakHostMemory()
    tracker.start()
    try:
        with pytest.raises(RuntimeError):
            tracker.start()
    finally:
        tracker.stop()


# ---------------------------------------------------------------------- #
# accelerator.profile() on CPU (satellite)
# ---------------------------------------------------------------------- #
def test_profile_creates_trace_dir_and_brackets_trace(tmp_path, monkeypatch):
    stub = _ProfilerStub(monkeypatch)
    acc = _fresh_accelerator()
    target = tmp_path / "trace"
    with acc.profile(str(target)) as handle:
        assert os.path.isdir(target)  # created before start_trace
        assert handle.dir == str(target)
        assert stub.starts == [str(target)]
        assert stub.stops == 0  # still tracing inside the context
    assert stub.stops == 1


def test_profile_skip_first_starts_lazily(tmp_path, monkeypatch):
    from accelerate_tpu.utils.profiling import ProfileKwargs

    stub = _ProfilerStub(monkeypatch)
    acc = _fresh_accelerator(
        profile_kwargs=ProfileKwargs(
            output_trace_dir=str(tmp_path), skip_first=2
        )
    )
    with acc.profile() as handle:
        assert stub.starts == []  # warmup steps stay un-profiled
        handle.step()
        assert stub.starts == []
        handle.step()  # skip_first reached: the trace starts here
        assert stub.starts == [str(tmp_path)]
        handle.step()
    assert stub.stops == 1


def test_profile_noop_without_dir_stays_noop(monkeypatch):
    stub = _ProfilerStub(monkeypatch)
    acc = _fresh_accelerator()
    with acc.profile() as handle:
        assert handle is None
    assert stub.starts == [] and stub.stops == 0


# ---------------------------------------------------------------------- #
# diagnose: aggregation + CLI
# ---------------------------------------------------------------------- #
def _write_rank(dir, rank, last_step, heartbeat_age_s, goodput=None,
                checkpoint=None, reason="periodic"):
    payload = {
        "kind": "flight_recorder", "schema": 1, "process_index": rank,
        "pid": 1000 + rank, "reason": reason, "time_unix": time.time(),
        "last_step": last_step, "last_checkpoint": checkpoint,
        "dumps": 3, "events": [], "records": [],
    }
    if goodput:
        payload["goodput"] = goodput
    with open(os.path.join(dir, f"flightrec-rank{rank}.json"), "w") as f:
        json.dump(payload, f)
    with open(os.path.join(dir, f"heartbeat-rank{rank}.json"), "w") as f:
        json.dump(
            {"process_index": rank, "pid": 1000 + rank, "step": last_step,
             "time_unix": time.time() - heartbeat_age_s, "stalled": False},
            f,
        )


def test_diagnose_names_straggler_checkpoint_and_badput(tmp_path):
    d = str(tmp_path)
    ckpt = {"dir": "/gcs/run/checkpoint_1000", "step": 1000, "time_unix": 5.0}
    snap = {
        "wall_s": 100.0, "goodput_pct": 80.0, "rolling_goodput_pct": 75.0,
        "buckets": {"productive": 80.0, "compile": 10.0, "dataloader": 4.0,
                    "checkpoint": 1.0, "idle": 5.0},
    }
    # rank 1 wedged at step 1180; ranks 0/2 advanced further, then stalled
    # behind it at the next collective (all heartbeats stale)
    _write_rank(d, 0, 1200, heartbeat_age_s=600, goodput=snap, checkpoint=ckpt)
    _write_rank(d, 1, 1180, heartbeat_age_s=640, goodput=snap, checkpoint=ckpt)
    _write_rank(d, 2, 1200, heartbeat_age_s=590, goodput=snap,
                checkpoint={"dir": "/gcs/run/checkpoint_900", "step": 900,
                            "time_unix": 4.0})
    report = build_report(d, stall_timeout_s=300.0)
    assert report["num_ranks"] == 3
    assert report["straggler"]["rank"] == 1  # lowest last_step = stopped first
    assert report["last_checkpoint"]["step"] == 1000  # newest across ranks
    assert report["goodput_pct"] == pytest.approx(80.0)
    assert report["badput_s"]["compile"] == pytest.approx(30.0)  # fleet sum

    text = format_report(report)
    assert "STRAGGLER: rank 1" in text
    assert "last step 1180" in text
    assert "checkpoint_1000" in text
    assert "80.0% productive" in text
    assert "compile" in text and "dataloader" in text


def test_diagnose_clean_shutdown_names_no_straggler(tmp_path):
    d = str(tmp_path)
    _write_rank(d, 0, 500, heartbeat_age_s=0, reason="shutdown")
    _write_rank(d, 1, 500, heartbeat_age_s=0, reason="shutdown")
    report = build_report(d, stall_timeout_s=300.0)
    assert report["straggler"] is None
    assert "No straggler" in format_report(report)


def test_diagnose_cli_empty_dir_exits_nonzero(tmp_path, capsys):
    from accelerate_tpu.commands.accelerate_cli import main

    with pytest.raises(SystemExit) as exc:
        main(["diagnose", str(tmp_path)])
    assert exc.value.code == 1
    assert "No flight-recorder dumps" in capsys.readouterr().err


def test_diagnose_cli_json_output(tmp_path, capsys):
    from accelerate_tpu.commands.accelerate_cli import main

    _write_rank(str(tmp_path), 0, 42, heartbeat_age_s=0)
    main(["diagnose", str(tmp_path), "--json"])
    report = json.loads(capsys.readouterr().out)
    assert report["num_dumps"] == 1
    assert report["ranks"]["0"]["last_step"] == 42


# ---------------------------------------------------------------------- #
# end to end through the Accelerator (the diag-smoke target)
# ---------------------------------------------------------------------- #
def test_accelerator_diagnostics_end_to_end(tmp_path, capsys):
    diag_dir = tmp_path / "diag"
    acc = _fresh_accelerator(
        # default anomaly_min_samples=8: the 4-step loop builds no
        # baseline, so only the injected NaN (needing none) can fire
        diagnostics=DiagnosticsConfig(dir=str(diag_dir), goodput_interval=2)
    )
    assert acc.telemetry.diagnostics is not None
    assert acc.telemetry.config.heartbeat_dir == str(diag_dir)  # one dir

    def loss_fn(params, batch):
        return jnp.mean((batch["x"] * params["w"]) ** 2)

    ds = [{"x": np.full((1,), float(i), np.float32)} for i in range(64)]
    loader = DataLoader(ds, batch_size=16, shuffle=False)
    params = {"w": jnp.asarray(1.0)}
    params, opt, prepared = acc.prepare(params, optax.sgd(0.1), loader)
    step_fn = acc.unified_step(loss_fn, opt)
    carry = acc.init_carry(params, opt)
    for batch in prepared:
        carry, _ = step_fn(carry, batch)

    # inject the two acceptance anomalies through the real collector
    acc.telemetry.begin_step()
    acc.telemetry.end_step(None, step=98, metrics={"loss": float("nan")})

    kinds = [r["kind"] for r in acc.telemetry.records]
    assert "goodput" in kinds  # emitted on the interval
    assert kinds.count("anomaly") == 1

    summary = acc.telemetry.summary()
    assert summary["goodput_pct"] is not None
    assert summary["anomalies"] == {"nan_grad": 1}

    acc.end_training()  # closes telemetry -> final "shutdown" dump
    dumps = list_dumps(str(diag_dir))
    assert dumps[0]["reason"] == "shutdown"
    assert dumps[0]["last_step"] == 98

    from accelerate_tpu.commands.accelerate_cli import main

    main(["diagnose", str(diag_dir)])
    out = capsys.readouterr().out
    assert "1 flight dump(s)" in out
    assert "nan_grad=1" in out
    assert "Goodput:" in out


# ---------------------------------------------------------------------- #
# SIGKILL survivability (acceptance; subprocess tier)
# ---------------------------------------------------------------------- #
_CHILD = r"""
import os, signal, sys
d = sys.argv[1]
from accelerate_tpu.telemetry import StepTelemetry, TelemetryConfig
from accelerate_tpu.diagnostics import DiagnosticsConfig

tel = StepTelemetry(TelemetryConfig(
    diagnostics=DiagnosticsConfig(dir=d, dump_interval_s=0.0),
    heartbeat_interval_s=0.01,
))
for i in range(6):
    tel.begin_step()
    tel.end_step(None, step=i)
tel.record_checkpoint(
    step=4, directory=os.path.join(d, "checkpoint_4"), mode="async",
    blocked_s=0.01, background_s=0.02, bytes_written=1024,
)
tel.begin_step()
tel.end_step(None, step=6)  # periodic dump now carries the checkpoint
open(os.path.join(d, "READY"), "w").write("ok")
os.kill(os.getpid(), signal.SIGKILL)  # no handler can run: the periodic
                                      # dump is the only evidence left
"""


@pytest.mark.slow
def test_sigkilled_run_leaves_dump_diagnose_names_it(tmp_path, capsys):
    d = str(tmp_path)
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, d],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        timeout=300,
    )
    assert proc.returncode == -signal.SIGKILL
    assert os.path.exists(os.path.join(d, "READY"))

    # the kill left a committed dump (tmp+rename: never torn)
    dumps = list_dumps(d)
    assert 0 in dumps
    assert dumps[0]["last_step"] == 6
    assert dumps[0]["last_checkpoint"]["step"] == 4

    # a healthy second rank reported later progress; rank 0's heartbeat
    # is now stale -> diagnose must name rank 0 as the one that stopped
    time.sleep(1.1)
    _write_rank(d, 1, 50, heartbeat_age_s=0)
    report = build_report(d, stall_timeout_s=1.0)
    assert report["straggler"]["rank"] == 0
    assert report["last_checkpoint"]["step"] == 4
    assert "checkpoint_4" in report["last_checkpoint"]["dir"]

    from accelerate_tpu.commands.accelerate_cli import main

    main(["diagnose", d, "--stall-timeout", "1.0"])
    out = capsys.readouterr().out
    assert "STRAGGLER: rank 0" in out
    assert "checkpoint_4" in out

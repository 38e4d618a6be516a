"""Profiling subsystem tests (SURVEY §5.1; reference
measures_util.py + ProfileKwargs handler shape)."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu import Accelerator
from accelerate_tpu.utils.profiling import (
    PeakHostMemory,
    ProfileKwargs,
    StepTimer,
    annotate,
    device_memory_stats,
    end_measure,
    host_memory_rss,
    profile,
    start_measure,
)


def test_measure_roundtrip():
    start = start_measure()
    x = jnp.ones((256, 256)) @ jnp.ones((256, 256))
    jax.block_until_ready(x)
    out = end_measure(start)
    assert out["time"] > 0
    assert "host" in out and "host-peak" in out
    assert "device:0" in out


def test_host_memory_rss_positive():
    assert host_memory_rss() > 1 << 20  # a Python process is >1MiB


def test_peak_host_memory_monitor():
    tracker = PeakHostMemory()
    tracker.start()
    blob = np.ones((4 << 20,), np.uint8)  # 4MiB spike
    peak = tracker.stop()
    assert peak >= host_memory_rss() - (64 << 20)
    del blob


def test_device_memory_stats_shape():
    stats = device_memory_stats()
    assert set(stats) == {"bytes_in_use", "peak_bytes_in_use", "bytes_limit"}


def test_step_timer_skips_compile():
    timer = StepTimer(skip=1)
    with timer:
        for i in range(4):
            y = jnp.sin(jnp.ones((64,)) * i).sum()
            timer.tick(y)
    s = timer.summary()
    assert s["steps"] == 3  # first (compile) tick excluded
    assert s["mean_s"] >= 0 and s["p90_s"] >= s["median_s"] >= 0


def test_profile_noop_without_dir():
    with profile() as p:
        assert p is None


def test_profile_writes_trace(tmp_path):
    target = str(tmp_path / "trace")
    with profile(target) as p:
        assert p.dir == target
        with annotate("matmul-region"):
            jax.block_until_ready(jnp.ones((64, 64)) @ jnp.ones((64, 64)))
    # xplane trace files land under plugins/profile/<ts>/
    found = glob.glob(os.path.join(target, "**", "*.xplane.pb"), recursive=True)
    assert found, os.listdir(target)


def test_profile_skip_first_defers_start(tmp_path):
    target = str(tmp_path / "trace")
    kw = ProfileKwargs(output_trace_dir=target, skip_first=2)
    with profile(kwargs=kw) as p:
        assert not p._started  # warmup: trace not yet running
        p.step()
        assert not p._started
        p.step()  # skip_first-th step: trace starts here
        assert p._started
        jax.block_until_ready(jnp.ones((32, 32)) @ jnp.ones((32, 32)))
    found = glob.glob(os.path.join(target, "**", "*.xplane.pb"), recursive=True)
    assert found, os.listdir(target)


def test_profile_user_error_propagates(tmp_path):
    """A TypeError inside the profiled region must propagate unchanged
    (review finding: the old fallback swallowed it and double-yielded)."""
    with pytest.raises(TypeError, match="user bug"):
        with profile(str(tmp_path / "t")):
            raise TypeError("user bug")


def test_accelerator_profile_context(tmp_path):
    acc = Accelerator(
        profile_kwargs=ProfileKwargs(output_trace_dir=str(tmp_path / "t"))
    )
    with acc.profile() as p:
        jax.block_until_ready(jnp.ones((32, 32)) @ jnp.ones((32, 32)))
    assert p.dir == str(tmp_path / "t")
    assert os.path.isdir(p.dir)


def test_accelerator_profile_noop_default():
    acc = Accelerator()
    with acc.profile() as p:
        pass
    assert p is None


# ===================================================================== #
# HBM & compute attribution plane: program registry, live-buffer census,
# OOM forensics, op-level step breakdown (ISSUE 15)
# ===================================================================== #
import json
import subprocess
import sys

import optax

from accelerate_tpu import DataLoader, TelemetryConfig
from accelerate_tpu.profiling import (
    BufferCensus,
    ProgramRegistry,
    get_program_registry,
    read_oom_report,
    write_oom_report,
)
from accelerate_tpu.profiling.oom import (
    is_resource_exhausted,
    parse_requested_bytes,
)


def _loss(params, batch):
    pred = batch["x"] * params["w"] + params["b"]
    return jnp.mean(pred**2)


def _train_setup(acc):
    ds = [{"x": np.full((2,), float(i), np.float32)} for i in range(24)]
    loader = DataLoader(ds, batch_size=8, shuffle=False)
    params = {"w": jnp.asarray(1.0), "b": jnp.asarray(0.5)}
    # adam, not sgd: real optimizer-state arrays for the census to claim
    params, opt, prepared = acc.prepare(params, optax.adam(0.1), loader)
    step = acc.unified_step(_loss, opt)
    carry = acc.init_carry(params, opt)
    return step, carry, prepared


# --------------------------------------------------------------------- #
# program registry
# --------------------------------------------------------------------- #
def test_register_compiled_extracts_real_cost_numbers():
    reg = ProgramRegistry()
    compiled = (
        jax.jit(lambda a, b: a @ b)
        .lower(
            jax.ShapeDtypeStruct((64, 64), jnp.float32),
            jax.ShapeDtypeStruct((64, 64), jnp.float32),
        )
        .compile()
    )
    rec = reg.register_compiled("toy_matmul", compiled, kind="train",
                                compile_seconds=0.25, note="unit")
    assert rec is reg.get("toy_matmul")
    assert rec.kind == "train"
    assert rec.compile_seconds == 0.25
    assert rec.meta["note"] == "unit"
    # XLA:CPU reports real numbers for both analyses on this program
    assert rec.argument_bytes == 2 * 64 * 64 * 4
    assert rec.flops > 0
    assert rec.bytes_accessed > 0
    assert rec.arithmetic_intensity > 0
    d = rec.as_dict()
    assert d["label"] == "toy_matmul" and d["flops"] == rec.flops


def test_registry_reregister_idempotent_and_top_programs_order():
    reg = ProgramRegistry()
    reg.register_analysis("small", kind="serve", temp_bytes=10)
    reg.register_analysis("big", kind="train", temp_bytes=1000)
    reg.register_analysis("mid", kind="serve", temp_bytes=100)
    # re-registering a label replaces, never duplicates
    reg.register_analysis("small", kind="serve", temp_bytes=20)
    assert len(reg) == 3
    top = reg.top_programs(2)
    assert [t["label"] for t in top] == ["big", "mid"]
    assert reg.temp_peak_bytes() == 1000  # MAX, not sum: serial execution


def test_ledger_sums_owned_plus_temp_peak_with_headroom():
    reg = ProgramRegistry()
    reg.register_analysis("a", kind="train", temp_bytes=300)
    reg.register_analysis("b", kind="serve", temp_bytes=700)
    led = reg.ledger(
        owner_bytes={"params": 1000, "kv_pool": 500},
        capacity_bytes=10_000,
    )
    assert led["owned_bytes"] == 1500
    assert led["program_temp_peak_bytes"] == 700
    assert led["budget_bytes"] == 1500 + 700
    assert led["capacity_bytes"] == 10_000
    assert led["headroom_bytes"] == 10_000 - 2200
    assert led["num_programs"] == 2
    assert led["owners"] == {"params": 1000, "kv_pool": 500}


def test_roofline_compute_vs_memory_bound_and_attribution_gap():
    reg = ProgramRegistry()
    # peak 100 FLOP/s, 10 B/s -> ridge intensity 10 FLOP/B
    reg.register_analysis("hot", kind="train", flops=1000.0,
                          bytes_accessed=10.0)  # intensity 100: compute
    reg.register_analysis("cold", kind="train", flops=10.0,
                          bytes_accessed=10.0)  # intensity 1: memory
    hot = reg.roofline("hot", peak_flops=100.0, peak_bytes_per_s=10.0)
    assert hot["bound"] == "compute"
    assert hot["peak_bound_mfu"] == 1.0
    cold = reg.roofline("cold", achieved_step_s=10.0,
                        peak_flops=100.0, peak_bytes_per_s=10.0)
    assert cold["bound"] == "memory"
    assert cold["peak_bound_mfu"] == pytest.approx(0.1)
    # memory-bound floor: 10 bytes / 10 B/s = 1s is the physics limit
    assert cold["peak_bound_step_s"] == pytest.approx(1.0)
    # achieved 10 FLOP in 10s on a 100 FLOP/s part = 1% MFU
    assert cold["achieved_mfu"] == pytest.approx(0.01)
    assert cold["attribution_gap"] == pytest.approx(0.1 - 0.01)


def test_roofline_unknown_label_or_missing_cost_is_none():
    reg = ProgramRegistry()
    reg.register_analysis("nocost", kind="train")  # CPU partial analysis
    assert reg.roofline("nope", peak_flops=1.0, peak_bytes_per_s=1.0) is None
    assert reg.roofline("nocost", peak_flops=1.0, peak_bytes_per_s=1.0) is None


def test_unknown_device_kind_has_no_peak():
    from accelerate_tpu.profiling.registry import DEVICE_PEAKS, device_peaks

    assert device_peaks("TPU v5 lite") == {
        "flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # a v5p reports itself as plain "TPU v5": not the v5e's row
    assert device_peaks("TPU v5")["flops_per_s"] == 459e12
    for kind in ("TPU v99", "cpu"):
        with pytest.raises(ValueError, match="no published peak"):
            device_peaks(kind)
    assert not any("cpu" in kind.lower() for kind in DEVICE_PEAKS)
    # the record-only roofline answers None on a kind with no peak (this
    # CPU backend) instead of inventing one
    reg = ProgramRegistry()
    reg.register_analysis("p", kind="train", flops=1e9, bytes_accessed=1e6)
    assert reg.roofline("p") is None


def test_the_programs_peaks_equal_the_benchmarks():
    """Two tables by design (the benchmark imports nothing but the system
    under test): for every kind both list, the same published numbers."""
    import importlib.util

    from accelerate_tpu.profiling.registry import DEVICE_PEAKS

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "harness", "peaks.py")
    spec = importlib.util.spec_from_file_location("_benchmark_peaks", path)
    peaks = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(peaks)
    both = sorted(set(peaks.PEAKS) & set(DEVICE_PEAKS))
    assert "TPU v5 lite" in both
    for kind in both:
        assert DEVICE_PEAKS[kind] == {
            "flops_per_s": peaks.PEAKS[kind]["bf16_flops_per_s"],
            "hbm_bytes_per_s": peaks.PEAKS[kind]["hbm_bytes_per_s"],
        }, kind


# --------------------------------------------------------------------- #
# live-buffer census
# --------------------------------------------------------------------- #
def test_census_owner_sum_invariant_and_single_counting():
    a = jnp.ones((128, 128), jnp.float32)  # 64 KiB
    b = jnp.ones((64,), jnp.float32)
    census = BufferCensus()
    census.set_owner("mine", lambda: {"w": a})
    census.set_owner("mine_too", lambda: [a, b])  # a already claimed
    out = census.sample()
    owners = out["census_owner_bytes"]
    assert owners["mine"] == a.nbytes
    # each live array is counted exactly once, first claimant wins
    assert owners["mine_too"] == b.nbytes
    assert (
        sum(owners.values()) + out["census_unowned_bytes"]
        == out["census_total_bytes"]
    )
    assert out["census_arrays"] >= 2
    assert out["host_rss_bytes"] > 1 << 20
    assert out["host_rss_peak_bytes"] >= out["host_rss_bytes"]
    assert census.last is out  # the crash handler's snapshot


def test_census_provider_exception_falls_to_unowned():
    x = jnp.ones((256,), jnp.float32)
    census = BufferCensus()

    def bad():
        raise RuntimeError("provider broke")

    census.set_owner("broken", bad)
    census.set_owner("constant", x)  # non-callable wrapped as constant
    out = census.sample()
    assert out["census_owner_bytes"]["broken"] == 0
    assert out["census_owner_bytes"]["constant"] == x.nbytes
    assert out["census_unowned_bytes"] >= 0  # never fatal, stays summable


def test_census_wall_clock_throttle_and_force():
    census = BufferCensus(min_interval_s=3600.0)
    assert census.maybe_sample() is not None  # first sample always lands
    assert census.maybe_sample() is None  # throttled for the next hour
    assert census.maybe_sample(force=True) is not None  # bypass


# --------------------------------------------------------------------- #
# OOM forensics
# --------------------------------------------------------------------- #
def test_parse_requested_bytes_units_and_max():
    assert parse_requested_bytes("failed to allocate 1024 bytes") == 1024
    assert parse_requested_bytes(
        "allocating 2.5KiB after reserving 1KiB"
    ) == 2560  # MAX across matches, not the first
    assert parse_requested_bytes(
        "trying to allocate 12.5GiB"
    ) == int(12.5 * (1 << 30))
    assert parse_requested_bytes("no numbers here") is None


def test_is_resource_exhausted_markers():
    assert is_resource_exhausted(RuntimeError("RESOURCE_EXHAUSTED: oom"))
    assert is_resource_exhausted(
        ValueError("XLA: Ran out of memory on device")
    )
    assert not is_resource_exhausted(TypeError("user bug"))


def test_oom_report_round_trip_with_ledger_census_pool(tmp_path):
    reg = ProgramRegistry()
    reg.register_analysis("decode", kind="serve", temp_bytes=512,
                          flops=10.0, bytes_accessed=5.0)
    census = {
        "census_total_bytes": 900,
        "census_unowned_bytes": 100,
        "census_owner_bytes": {"params": 500, "kv_pool": 300},
    }
    exc = RuntimeError(
        "RESOURCE_EXHAUSTED: failed to allocate 1048576 bytes"
    )
    path = write_oom_report(
        exc, context="unit", registry=reg, census=census,
        pool_stats={"num_blocks": 8}, directory=str(tmp_path),
        extra={"engine_steps": 3},
    )
    assert path == str(tmp_path / "oom-report.json")
    report = read_oom_report(str(tmp_path))
    assert report["kind"] == "oom_report"
    assert report["context"] == "unit"
    assert report["error_type"] == "RuntimeError"
    assert report["requested_bytes"] == 1048576
    assert report["ledger"]["owners"] == census["census_owner_bytes"]
    assert report["ledger"]["program_temp_peak_bytes"] == 512
    assert report["top_programs"][0]["label"] == "decode"
    assert report["census"] == census
    assert report["pool_stats"] == {"num_blocks": 8}
    assert report["extra"] == {"engine_steps": 3}
    # a file path is accepted too (diagnose hands either)
    assert read_oom_report(path)["context"] == "unit"
    assert read_oom_report(str(tmp_path / "missing")) is None


def test_oom_report_env_dir_override(tmp_path, monkeypatch):
    env_dir = tmp_path / "env_dir"
    monkeypatch.setenv("ACCELERATE_TPU_OOM_DIR", str(env_dir))
    path = write_oom_report(
        RuntimeError("RESOURCE_EXHAUSTED"), context="env",
        directory=str(tmp_path / "arg_dir"),
    )
    assert path == str(env_dir / "oom-report.json")
    assert read_oom_report(str(env_dir))["context"] == "env"


def test_oom_autopsy_survives_crashing_subprocess(tmp_path):
    """A RESOURCE_EXHAUSTED thrown inside the real train-step boundary
    must leave a parseable autopsy behind even though the process dies
    with a traceback — the report is written before the re-raise."""
    script = """
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import jax.numpy as jnp
import optax
from accelerate_tpu import Accelerator, DataLoader, TelemetryConfig

acc = Accelerator(telemetry=TelemetryConfig(census_interval=1,
                                            census_min_interval_s=0.0))
ds = [{"x": np.full((2,), float(i), np.float32)} for i in range(16)]
loader = DataLoader(ds, batch_size=8, shuffle=False)
params = {"w": jnp.asarray(1.0), "b": jnp.asarray(0.5)}
params, opt, prepared = acc.prepare(params, optax.sgd(0.1), loader)

def loss_fn(params, batch):
    raise RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "2147483648 bytes."
    )

step = acc.unified_step(loss_fn, opt)
carry = acc.init_carry(params, opt)
for batch in prepared:
    carry, _ = step(carry, batch)
"""
    env = dict(os.environ)
    env["ACCELERATE_TPU_OOM_DIR"] = str(tmp_path)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", script], env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0  # the crash still crashes
    assert "RESOURCE_EXHAUSTED" in proc.stderr
    report = read_oom_report(str(tmp_path))
    assert report is not None, proc.stderr[-2000:]
    assert report["context"].startswith("train_step")
    assert report["requested_bytes"] == 2147483648
    assert "ledger" in report and "top_programs" in report


# --------------------------------------------------------------------- #
# op-level step breakdown (xplane wire reader)
# --------------------------------------------------------------------- #
def _varint(n):
    out = b""
    while True:
        b7 = n & 0x7F
        n >>= 7
        out += bytes([b7 | (0x80 if n else 0)])
        if not n:
            return out


def _ld(field, payload):  # length-delimited field
    return _varint((field << 3) | 2) + _varint(len(payload)) + payload


def _vi(field, value):  # varint field
    return _varint(field << 3) + _varint(value)


def _meta_entry(mid, name):
    return _vi(1, mid) + _ld(2, _vi(1, mid) + _ld(2, name))


def _plane(name, events, metas):
    line = _ld(2, b"xla-ops") + _vi(3, 0)
    for mid, offset_ps, dur_ps in events:
        line += _ld(4, _vi(1, mid) + _vi(2, offset_ps) + _vi(3, dur_ps))
    plane = _ld(2, name) + _ld(3, line)
    for mid, mname in metas:
        plane += _ld(4, _meta_entry(mid, mname))
    return plane


def test_xplane_topk_self_time_subtracts_nested_children(tmp_path):
    from accelerate_tpu.compilation.overlap import (
        parse_xspace_planes,
        top_ops_from_plane,
    )

    metas = [(1, b"fusion.parent"), (2, b"sub.child"), (3, b"other.op")]
    # parent [0us,100us) encloses child [20us,50us): parent self = 70us
    us = 1_000_000  # ps per microsecond
    events = [(1, 0, 100 * us), (2, 20 * us, 30 * us), (3, 200 * us, 40 * us)]
    space = _ld(1, _plane(b"/device:TPU:0", events, metas))
    (plane,) = parse_xspace_planes(space)
    top = top_ops_from_plane(plane, k=2)
    assert [t["op"] for t in top] == ["fusion.parent", "other.op"]
    assert top[0]["self_time_ms"] == pytest.approx(0.070)
    assert top[1]["self_time_ms"] == pytest.approx(0.040)
    assert top[0]["count"] == 1


def test_top_self_time_ops_dir_walk_prefers_device_plane(tmp_path):
    from accelerate_tpu.compilation import top_self_time_ops

    host = _plane(b"/host:CPU", [(1, 0, 50)], [(1, b"host.noise")])
    dev = _plane(b"/device:TPU:0", [(1, 0, 80)], [(1, b"real.kernel")])
    (tmp_path / "t.xplane.pb").write_bytes(_ld(1, host) + _ld(1, dev))
    top = top_self_time_ops(str(tmp_path), k=5)
    assert [t["op"] for t in top] == ["real.kernel"]  # host plane dropped
    # host-only capture still yields a breakdown (the CPU test backend)
    host_only = tmp_path / "host_only"
    host_only.mkdir()
    (host_only / "h.xplane.pb").write_bytes(_ld(1, host))
    assert [t["op"] for t in top_self_time_ops(str(host_only))] == [
        "host.noise"
    ]


def test_top_self_time_ops_missing_or_empty_dir_is_none(tmp_path):
    from accelerate_tpu.compilation import top_self_time_ops

    assert top_self_time_ops(str(tmp_path / "nope")) is None
    (tmp_path / "garbage.xplane.pb").write_bytes(b"\xff\xff not a proto")
    assert top_self_time_ops(str(tmp_path)) is None  # never raises


# --------------------------------------------------------------------- #
# telemetry plumbing: sink gauges, unified record, leak rule
# --------------------------------------------------------------------- #
def test_prometheus_memory_gauges_with_label_escaping():
    from accelerate_tpu.telemetry import PrometheusTextSink

    sink = PrometheusTextSink(path=None)
    sink.emit({
        "kind": "memory", "label": "memory",
        "census_owner_bytes": {"params": 7.0, 'kv "pool"\n': 3.0},
        "census_unowned_bytes": 2,
        "census_total_bytes": 12,
        "hbm_bytes_in_use": 12,
    })
    text = sink.render()
    assert 'accelerate_tpu_hbm_bytes{owner="params"} 7.0' in text
    assert 'accelerate_tpu_hbm_bytes{owner="unowned"} 2.0' in text
    # Prometheus text exposition: " and newline escaped inside the label
    assert 'owner="kv \\"pool\\"\\n"' in text
    # the scalar fields ride as {prefix}_memory_* gauges
    assert "accelerate_tpu_memory_hbm_bytes_in_use" in text
    assert "accelerate_tpu_memory_census_total_bytes" in text


def test_collector_sample_memory_unifies_host_and_device(tmp_path):
    from accelerate_tpu.telemetry import StepTelemetry

    jsonl = tmp_path / "t.jsonl"
    tel = StepTelemetry(TelemetryConfig(
        jsonl_path=str(jsonl), census_min_interval_s=0.0,
    ))
    w = jnp.ones((64, 64), jnp.float32)
    tel.census.set_owner("weights", lambda: w)
    rec = tel.sample_memory(step=7, force=True)
    assert rec["kind"] == "memory"
    assert rec["step"] == 7
    assert rec["census_owner_bytes"]["weights"] == w.nbytes
    # one schema, host + device: the old PeakHostMemory RSS folded in
    assert rec["host_rss_bytes"] > 0
    assert "hbm_bytes_in_use" in rec and "hbm_bytes_limit" in rec
    tel.close()
    lines = [json.loads(l) for l in jsonl.read_text().splitlines()]
    assert any(l["kind"] == "memory" for l in lines)


def test_leak_rule_fires_on_monotone_unowned_growth():
    from accelerate_tpu.diagnostics import AnomalyDetector, DiagnosticsConfig

    det = AnomalyDetector(DiagnosticsConfig(
        leak_min_samples=3, leak_min_growth_bytes=1000,
    ))
    mk = lambda step, b: {  # noqa: E731
        "kind": "memory", "step": step, "census_unowned_bytes": b,
    }
    assert det.observe_memory(mk(1, 1000), now=1.0) == []
    assert det.observe_memory(mk(2, 3000), now=2.0) == []
    out = det.observe_memory(mk(3, 5000), now=3.0)
    assert len(out) == 1
    rec = out[0]
    assert rec["kind"] == "anomaly"
    assert rec["anomaly_type"] == "memory_leak"
    assert rec["growth_bytes"] == 4000
    assert rec["samples"] == 3


def test_leak_rule_flat_census_resets_the_trail():
    from accelerate_tpu.diagnostics import AnomalyDetector, DiagnosticsConfig

    det = AnomalyDetector(DiagnosticsConfig(
        leak_min_samples=3, leak_min_growth_bytes=1000,
    ))
    mk = lambda step, b: {  # noqa: E731
        "kind": "memory", "step": step, "census_unowned_bytes": b,
    }
    det.observe_memory(mk(1, 1000), now=1.0)
    det.observe_memory(mk(2, 3000), now=2.0)
    # one flat census resets the trail: a filling-then-stable pool is
    # NOT the leak shape
    assert det.observe_memory(mk(3, 3000), now=3.0) == []
    # three monotone samples but sub-threshold growth: still quiet
    assert det.observe_memory(mk(4, 3100), now=4.0) == []
    assert det.observe_memory(mk(5, 3200), now=5.0) == []
    assert det.observe_memory(mk(6, 9000), now=6.0) != []
    # owned growth and step records never reach the rule
    assert det.observe_memory({"kind": "step", "step": 7}, now=7.0) == []
    assert det.observe_memory({"kind": "memory", "step": 8}, now=8.0) == []


# --------------------------------------------------------------------- #
# integration: the plane attached to real train / serve programs
# --------------------------------------------------------------------- #
def test_warmup_registers_program_and_ledger_sums(tmp_path):
    """AOT warmup registers the real unified_step executable — the
    registry's ledger then sums owners + the program temp peak into one
    HBM budget."""
    acc = Accelerator(telemetry=TelemetryConfig(
        jsonl_path=str(tmp_path / "t.jsonl"),
    ))
    step, carry, prepared = _train_setup(acc)
    acc.warmup(step, carry, prepared)

    reg = get_program_registry()
    assert step.label in reg
    rec = reg.get(step.label)
    assert rec.kind == "train"
    assert rec.compile_seconds > 0
    assert rec.argument_bytes > 0  # XLA:CPU memory_analysis is real
    assert rec.meta.get("microbatches") == 1
    assert any(p["label"] == step.label for p in reg.top_programs(5))

    led = reg.ledger(owner_bytes={"params": 1 << 20, "opt_state": 1 << 19},
                     capacity_bytes=1 << 30)
    assert led["owned_bytes"] == (1 << 20) + (1 << 19)
    assert led["budget_bytes"] == (
        led["owned_bytes"] + led["program_temp_peak_bytes"]
    )
    assert led["headroom_bytes"] == (1 << 30) - led["budget_bytes"]
    assert led["num_programs"] == len(reg)
    acc.telemetry.close()


def test_census_owner_attribution_on_warmed_step(tmp_path):
    """With the census cadence on, a real warmed train loop emits
    kind="memory" records that attribute the live carry to the params /
    opt_state owners — and owners + unowned always sum to the total."""
    jsonl = tmp_path / "t.jsonl"
    acc = Accelerator(telemetry=TelemetryConfig(
        jsonl_path=str(jsonl), census_interval=1,
        census_min_interval_s=0.0,
    ))
    step, carry, prepared = _train_setup(acc)
    acc.warmup(step, carry, prepared)
    for batch in prepared:
        carry, metrics = step(carry, batch)
    assert np.isfinite(float(metrics["loss"]))
    acc.telemetry.close()

    lines = [json.loads(l) for l in jsonl.read_text().splitlines()]
    mems = [l for l in lines if l["kind"] == "memory"]
    assert len(mems) >= 3  # cadence 1: one census per step
    last = mems[-1]
    owners = last["census_owner_bytes"]
    # the donated carry is re-resolved through providers at sample time,
    # so attribution survives buffer replacement every step
    assert owners["params"] > 0
    assert owners["opt_state"] > 0
    assert (
        sum(owners.values()) + last["census_unowned_bytes"]
        == last["census_total_bytes"]
    )
    assert last["host_rss_bytes"] > 0
    assert "hbm_bytes_in_use" in last and "step" in last


def test_zero_retraces_after_warmup_with_plane_enabled(tmp_path):
    """The attribution plane is passive: census cadence + program
    registry on, the warmed step still never retraces (the zero-retrace
    contract the trace counters pin)."""
    acc = Accelerator(telemetry=TelemetryConfig(
        jsonl_path=str(tmp_path / "t.jsonl"), census_interval=1,
        census_min_interval_s=0.0,
    ))
    step, carry, prepared = _train_setup(acc)
    acc.warmup(step, carry, prepared)
    detector = acc.telemetry.detector(step.label)
    signatures_after_warmup = len(detector._seen)
    steps = 0
    for batch in prepared:
        carry, _ = step(carry, batch)
        steps += 1
    assert steps >= 3
    assert detector.retraces == 0
    assert len(detector._seen) == signatures_after_warmup
    assert step.label in get_program_registry()
    acc.telemetry.close()


@pytest.fixture(scope="module")
def tiny_serving_model():
    from accelerate_tpu.models import CausalLM, TransformerConfig

    cfg = TransformerConfig.tiny(max_seq_len=64)
    model = CausalLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return cfg, model, params


def test_engine_capture_programs_registers_without_new_traces(
    tiny_serving_model,
):
    """capture_programs AOT-compiles the engine's warmed programs into
    the registry (prefill buckets, the ONE decode program, COW, the key
    chain) without disturbing the zero-retrace trace counters."""
    from accelerate_tpu.serving import ServingEngine

    cfg, model, params = tiny_serving_model
    engine = ServingEngine(model, params, max_slots=2, block_size=8)
    engine.add_request([1, 2, 3], max_new_tokens=2)
    for _ in engine.stream():
        pass
    counts_before = dict(engine.trace_counts())
    assert counts_before["decode"] == 1

    reg = ProgramRegistry()
    labels = engine.capture_programs(reg)
    assert "serve_decode" in labels
    assert "serve_cow" in labels
    assert "serve_key_chain" in labels
    assert any(l.startswith("serve_prefill_b") for l in labels)
    # AOT lower/compile shares nothing with the jit call cache: the
    # engine's retrace counters must be bit-identical afterwards
    assert dict(engine.trace_counts()) == counts_before
    dec = reg.get("serve_decode")
    assert dec is not None and dec.kind == "serve"
    assert dec.argument_bytes > 0
    pre = next(l for l in labels if l.startswith("serve_prefill_b"))
    assert reg.get(pre).meta["bucket"] >= 4
    # the Compiled artifacts are memoized: a second capture reuses every
    # one of them (zero fresh compiles) and the counters still hold
    compiles_after_first = engine.capture_compile_count
    assert compiles_after_first == len(labels)
    labels2 = engine.capture_programs(reg)
    assert labels2 == labels
    assert engine.capture_compile_count == compiles_after_first
    assert dict(engine.trace_counts()) == counts_before
    # ... and the auditor reuses the same capture-time artifacts too:
    # auditing adds no compiles and leaves the trace counters untouched
    audits = engine.audit_programs(reg, emit=False)
    assert set(audits) == set(labels)
    assert engine.capture_compile_count == compiles_after_first
    assert dict(engine.trace_counts()) == counts_before


# --------------------------------------------------------------------- #
# diagnose: the autopsy + census + top-ops sections
# --------------------------------------------------------------------- #
def test_diagnose_reports_memory_top_ops_and_oom_autopsy(tmp_path):
    import time

    from accelerate_tpu.diagnostics import build_report, format_report

    d = str(tmp_path)
    mem_rec = {
        "kind": "memory", "step": 5,
        "census_total_bytes": 1000, "census_unowned_bytes": 100,
        "census_owner_bytes": {"params": 600, "kv_pool": 300},
        "census_arrays": 12, "hbm_bytes_in_use": 1000,
        "host_rss_bytes": 5 << 20,
    }
    step_rec = {
        "kind": "step", "step": 6,
        "top_ops": [
            {"op": "fusion.1", "self_time_ms": 1.5, "count": 3},
            {"op": "all-reduce.2", "self_time_ms": 0.5, "count": 1},
        ],
        "top_ops_capture_dir": "/tmp/cap0",
    }
    payload = {
        "kind": "flight_recorder", "schema": 1, "process_index": 0,
        "pid": 1234, "reason": "periodic", "time_unix": time.time(),
        "last_step": 6, "last_checkpoint": None, "dumps": 1,
        "events": [], "records": [mem_rec, step_rec],
    }
    with open(os.path.join(d, "flightrec-rank0.json"), "w") as f:
        json.dump(payload, f)
    reg = ProgramRegistry()
    reg.register_analysis("serve_decode", kind="serve", temp_bytes=2048)
    write_oom_report(
        RuntimeError("RESOURCE_EXHAUSTED: could not allocate 4096 bytes"),
        context="serving_step", registry=reg,
        census=mem_rec, pool_stats={"num_blocks": 4}, directory=d,
    )

    report = build_report(d, stall_timeout_s=300.0)
    assert report["memory"][0]["census_owner_bytes"]["params"] == 600
    assert report["memory"][0]["step"] == 5
    assert report["top_ops"]["rank"] == 0
    assert report["top_ops"]["ops"][0]["op"] == "fusion.1"
    assert report["oom_report"]["context"] == "serving_step"
    assert report["oom_report"]["requested_bytes"] == 4096

    text = format_report(report)
    assert "Memory (latest census per rank)" in text
    assert "params" in text
    assert "fusion.1" in text
    assert "OOM AUTOPSY (serving_step)" in text
    assert "serve_decode" in text

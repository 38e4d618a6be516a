"""Compilation subsystem tests: persistent-cache activation and round-trip
hits, AOT warmup through the real ``unified_step`` path (zero retraces on
the first real batch), compile-cost attribution, and one wired-consumer
test per ``CompilePlugin`` knob (``cache_dir``, ``static_argnames``,
``compiler_options``). All CPU-runnable on the virtual 8-device backend.

The persistent-cache tests mutate process-wide jax config (the conftest
installs its own cache for the whole suite) — every mutation goes through
``restore_cache_config`` so later tests see the conftest settings again.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from accelerate_tpu import Accelerator, DataLoader, TelemetryConfig
from accelerate_tpu.compilation import (
    activate_persistent_cache,
    batch_spec_of,
    get_compile_monitor,
    persistent_cache_dir,
    persistent_cache_entries,
    resolve_cache_dir,
    spec_like,
)
from accelerate_tpu.compilation import cache as cache_mod
from accelerate_tpu.utils.dataclasses import CompilePlugin


def _fresh_accelerator(**kwargs) -> Accelerator:
    from accelerate_tpu.state import AcceleratorState, GradientState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    return Accelerator(**kwargs)


def loss_fn(params, batch):
    pred = batch["x"] * params["w"] + params["b"]
    return jnp.mean(pred**2)


_CACHE_FLAGS = (
    "jax_enable_compilation_cache",
    "jax_compilation_cache_dir",
    "jax_persistent_cache_min_compile_time_secs",
    "jax_persistent_cache_min_entry_size_bytes",
    "jax_persistent_cache_enable_xla_caches",
    "jax_explain_cache_misses",
)


@pytest.fixture
def restore_cache_config():
    """Snapshot the jax cache config (set process-wide by conftest) and
    restore it after the test, so per-test cache dirs can't leak into the
    rest of the suite."""
    saved = {}
    for name in _CACHE_FLAGS:
        try:
            saved[name] = getattr(jax.config, name)
        except AttributeError:
            pass
    saved_active = cache_mod._active_dir
    yield
    for name, value in saved.items():
        try:
            jax.config.update(name, value)
        except Exception:
            pass
    cache_mod._active_dir = saved_active
    try:
        from jax.experimental.compilation_cache import compilation_cache as cc

        cc.reset_cache()
    except Exception:
        pass


# ---------------------------------------------------------------------- #
# CompilePlugin.cache_dir -> persistent cache activation (wired consumer)
# ---------------------------------------------------------------------- #
def test_cache_dir_activates_and_writes_entries(tmp_path, restore_cache_config):
    target = tmp_path / "xla_cache"
    plugin = CompilePlugin(
        cache_dir=str(target),
        cache_min_compile_time_secs=0.0,
        cache_min_entry_size_bytes=-1,
        cache_enable_xla_caches="all",
    )
    resolved = activate_persistent_cache(plugin)
    assert resolved == os.path.abspath(str(target))
    assert persistent_cache_dir() == resolved
    assert os.path.isdir(resolved)
    # activation is idempotent: same dir again is a no-op, not a reset
    assert activate_persistent_cache(plugin) == resolved

    jax.jit(lambda x: x * 2.0 + 1.0)(jnp.arange(8.0)).block_until_ready()
    assert persistent_cache_entries(resolved) > 0


# ---------------------------------------------------------------------- #
# the one rule for where the cache lives (compilation/cache.py)
# ---------------------------------------------------------------------- #
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_resolver_default_is_the_fixed_in_checkout_path(monkeypatch):
    monkeypatch.delenv(cache_mod.ENV_JAX_CACHE_DIR, raising=False)
    want = os.path.join(REPO_ROOT, ".jax_compile_cache")
    assert resolve_cache_dir() == resolve_cache_dir(CompilePlugin()) == want
    # ... and is what a plain Accelerator() leaves active (conftest went
    # through the same resolver, so nothing switches or resets)
    acc = _fresh_accelerator()
    assert acc.state.compile_cache_dir == want
    assert jax.config.jax_compilation_cache_dir == want
    # an explicit plugin dir still wins while the variable is unset
    assert resolve_cache_dir(CompilePlugin(cache_dir="/explicit")) == "/explicit"


def test_resolver_env_wins_over_plugin_with_one_log_line(monkeypatch, caplog):
    monkeypatch.setenv(cache_mod.ENV_JAX_CACHE_DIR, "/from/env")
    monkeypatch.setattr(cache_mod, "_warned_ignored", False)
    plugin = CompilePlugin(cache_dir="/explicit")
    with caplog.at_level("WARNING"):
        assert resolve_cache_dir(plugin) == "/from/env"
        assert resolve_cache_dir(plugin) == "/from/env"
    assert sum("is ignored" in r.getMessage() for r in caplog.records) == 1
    assert resolve_cache_dir() == "/from/env"


def test_env_cache_dir_is_the_only_one_any_entry_point_sets(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, Accelerator() and
    ServingEngine() both leave jax pointing at it."""
    import subprocess
    import sys

    code = (
        "import jax\n"
        "from accelerate_tpu import Accelerator, ServingEngine\n"
        "from accelerate_tpu.models import CausalLM, TransformerConfig\n"
        "from accelerate_tpu.utils.dataclasses import CompilePlugin\n"
        "seen = []\n"
        "Accelerator(compile_plugin=CompilePlugin(cache_dir='/explicit'))\n"
        "seen.append(jax.config.jax_compilation_cache_dir)\n"
        "model = CausalLM(TransformerConfig.tiny(num_layers=1))\n"
        "params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))\n"
        "ServingEngine(model, params, max_slots=1, block_size=8)\n"
        "seen.append(jax.config.jax_compilation_cache_dir)\n"
        "print('SEEN', *seen)\n"
    )
    target = str(tmp_path / "x")
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=REPO_ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             cache_mod.ENV_JAX_CACHE_DIR: target},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("SEEN")][-1]
    assert line.split()[1:] == [target] * 2


def test_no_cache_path_is_built_from_tempfile():
    """The cache must not move between runs: no path made from tempfile
    anywhere under the package names the compile cache, and the deleted
    package-specific variable is gone from the tree."""
    import re

    gone = "ACCELERATE_TPU_" + "COMPILE_CACHE"
    tmp = re.compile(r"gettempdir|mkdtemp|mkstemp|NamedTemporary")
    offenders = []
    for root in ("accelerate_tpu", "tests", "examples"):
        for dirpath, _dirs, files in os.walk(os.path.join(REPO_ROOT, root)):
            for name in files:
                if not name.endswith(".py"):
                    continue
                path = os.path.join(dirpath, name)
                with open(path) as f:
                    lines = f.read().splitlines()
                for i, line in enumerate(lines):
                    window = " ".join(lines[max(0, i - 1):i + 2])
                    if gone in line or (
                        root == "accelerate_tpu" and tmp.search(line)
                        and "cache" in window.lower()
                    ):
                        offenders.append(f"{path}:{i + 1}: {line.strip()}")
    assert not offenders, offenders


def test_state_activates_cache_from_plugin(tmp_path, restore_cache_config):
    acc = _fresh_accelerator(
        compile_plugin=CompilePlugin(
            cache_dir=str(tmp_path / "state_cache"),
            cache_min_compile_time_secs=0.0,
            cache_min_entry_size_bytes=-1,
        )
    )
    assert acc.state.compile_cache_dir == os.path.abspath(
        str(tmp_path / "state_cache")
    )
    assert persistent_cache_dir() == acc.state.compile_cache_dir


# ---------------------------------------------------------------------- #
# persistent-cache round trip: a second jit of the same program is a HIT
# ---------------------------------------------------------------------- #
def test_persistent_cache_round_trip_records_hit(tmp_path, restore_cache_config):
    mon = get_compile_monitor()
    activate_persistent_cache(
        CompilePlugin(
            cache_dir=str(tmp_path),
            cache_min_compile_time_secs=0.0,
            cache_min_entry_size_bytes=-1,
            cache_enable_xla_caches="all",
        )
    )

    def make():  # fresh jit wrapper each time: same program, no jit cache
        return jax.jit(lambda x: jnp.sin(x) * 3.0 + jnp.cos(x))

    before = mon.snapshot()
    make()(jnp.arange(16.0)).block_until_ready()
    first = mon.delta(before)
    assert first.get("persistent_cache_misses", 0) >= 1

    before = mon.snapshot()
    make()(jnp.arange(16.0)).block_until_ready()
    second = mon.delta(before)
    assert second.get("persistent_cache_hits", 0) >= 1
    assert second.get("persistent_cache_misses", 0) == 0
    # a hit deserializes instead of compiling (a few ms of auxiliary
    # backend work can still accrue — don't assert exactly zero)
    assert second.get("cache_retrieval_s", 0.0) > 0.0


def test_compile_monitor_attributes_by_label():
    mon = get_compile_monitor()
    before = mon.snapshot()
    with mon.label("probe-label"):
        jax.jit(lambda x: x @ x.T)(
            jnp.arange(12.0).reshape(3, 4)
        ).block_until_ready()
    delta = mon.delta(before)
    assert delta.get("trace_time_s", 0.0) > 0.0
    stats = mon.stats_for("probe-label")
    assert stats.get("trace_time_s", 0.0) > 0.0


# ---------------------------------------------------------------------- #
# CompilePlugin.static_argnames -> unified_step jit (wired consumer)
# ---------------------------------------------------------------------- #
def _loss_with_flag(params, batch, use_l2=False):
    pred = batch["x"] * params["w"] + params["b"]
    if use_l2:  # python-level branch: only a STATIC kwarg can reach here
        return jnp.mean(pred**2)
    return jnp.mean(jnp.abs(pred))


def test_static_argnames_wired_into_unified_step():
    acc = _fresh_accelerator(
        compile_plugin=CompilePlugin(static_argnames=("use_l2",))
    )
    params = {"w": jnp.asarray(2.0), "b": jnp.asarray(0.1)}
    params, opt = acc.prepare(params, optax.sgd(0.0))
    step = acc.unified_step(_loss_with_flag, opt)
    carry = acc.init_carry(params, opt)
    batch = {"x": jnp.asarray(np.full((8,), 3.0, np.float32))}
    carry, m_l1 = step(carry, batch, use_l2=False)
    carry, m_l2 = step(carry, batch, use_l2=True)
    # the static flag selected two different programs with different math
    assert abs(float(m_l1["loss"]) - float(m_l2["loss"])) > 1.0


def test_kwarg_is_traced_without_static_argnames():
    acc = _fresh_accelerator()  # default plugin: no static names
    params = {"w": jnp.asarray(2.0), "b": jnp.asarray(0.1)}
    params, opt = acc.prepare(params, optax.sgd(0.0))
    step = acc.unified_step(_loss_with_flag, opt)
    carry = acc.init_carry(params, opt)
    batch = {"x": jnp.asarray(np.full((8,), 3.0, np.float32))}
    with pytest.raises(jax.errors.TracerBoolConversionError):
        step(carry, batch, use_l2=True)


def test_plugin_normalizes_string_static_argnames():
    assert CompilePlugin(static_argnames="flag").static_argnames == ("flag",)


# ---------------------------------------------------------------------- #
# CompilePlugin.compiler_options -> the jit itself (wired consumer)
# ---------------------------------------------------------------------- #
def test_compiler_options_reach_the_warmed_and_the_unwarmed_step():
    """The options sit on the jax.jit, so warmup's AOT compile and a plain
    first call build ONE program (one cache key): an option XLA does not
    know must stop both paths, not only warm()."""

    def build(opts):
        acc = _fresh_accelerator(
            compile_plugin=CompilePlugin(compiler_options=opts)
        )
        params = {"w": jnp.asarray(1.0), "b": jnp.asarray(0.5)}
        params, opt = acc.prepare(params, optax.sgd(0.1))
        step = acc.unified_step(loss_fn, opt)
        return acc, step, acc.init_carry(params, opt)

    batch = {"x": jnp.asarray(np.ones((8,), np.float32))}
    bogus = {"xla_no_such_option_for_this_test": True}
    acc, step, carry = build(bogus)
    with pytest.raises(Exception, match="No such compile option"):
        acc.warmup(step, carry, batch)
    acc, step, carry = build(bogus)
    with pytest.raises(Exception, match="No such compile option"):
        step(carry, batch)

    acc, step, carry = build({"xla_embed_ir_in_executable": True})
    acc.warmup(step, carry, batch)
    carry, metrics = step(carry, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert step.aot_fallbacks == 0 and step.compiled is not None


def test_aot_fallback_is_counted_and_lands_on_the_step_record():
    """A warmed executable that rejects a call (same avals, another
    sharding) still falls back to jit — but never silently: the step fn
    counts it and every later step record carries the count."""
    acc = _fresh_accelerator(telemetry=True)
    params = {"w": jnp.asarray(1.0), "b": jnp.asarray(0.5)}
    params, opt = acc.prepare(params, optax.sgd(0.1))
    step = acc.unified_step(loss_fn, opt)
    carry = acc.init_carry(params, opt)
    batch = {"x": jnp.asarray(np.ones((8,), np.float32))}
    acc.warmup(step, carry, batch)
    carry, _ = step(carry, batch)
    assert step.aot_fallbacks == 0
    assert acc.telemetry.records[-1]["aot_fallbacks"] == 0
    from jax.sharding import NamedSharding, PartitionSpec as P

    split = jax.device_put(batch, NamedSharding(acc.mesh, P("dp")))
    carry, metrics = step(carry, split)
    assert np.isfinite(float(metrics["loss"]))
    assert step.aot_fallbacks == 1
    assert acc.telemetry.records[-1]["aot_fallbacks"] == 1


# ---------------------------------------------------------------------- #
# AOT warmup: specs from the prepared dataloader, zero retraces, compile
# records through the telemetry sinks (the acceptance demo)
# ---------------------------------------------------------------------- #
def test_dataloader_batch_spec_matches_real_batch():
    acc = _fresh_accelerator()
    ds = [{"x": np.full((3,), float(i), np.float32)} for i in range(16)]
    prepared = acc.prepare(DataLoader(ds, batch_size=8, shuffle=False))
    spec = prepared.batch_spec()
    batch = next(iter(prepared))
    got = jax.tree.map(lambda s: (s.shape, jnp.dtype(s.dtype)), spec)
    want = jax.tree.map(lambda a: (a.shape, jnp.dtype(a.dtype)), batch)
    assert got == want


def test_spec_like_keeps_committed_sharding_only():
    committed = jax.device_put(jnp.arange(4.0), jax.devices()[0])
    uncommitted = jnp.arange(4.0)  # jit is free to place it; spec must be too
    specs = spec_like({"c": committed, "u": uncommitted, "n": np.zeros(2)})
    assert specs["c"].sharding == committed.sharding
    assert specs["u"].sharding is None
    assert specs["n"].shape == (2,)
    # batch_spec_of on a plain pytree falls through to spec_like
    assert batch_spec_of({"u": uncommitted})["u"].shape == (4,)


def test_warmup_then_first_step_never_retraces(tmp_path):
    jsonl = tmp_path / "telemetry.jsonl"
    acc = _fresh_accelerator(
        telemetry=TelemetryConfig(jsonl_path=str(jsonl))
    )
    ds = [{"x": np.full((2,), float(i), np.float32)} for i in range(32)]
    loader = DataLoader(ds, batch_size=8, shuffle=False)
    params = {"w": jnp.asarray(1.0), "b": jnp.asarray(0.5)}
    params, opt, prepared = acc.prepare(params, optax.sgd(0.1), loader)
    step = acc.unified_step(loss_fn, opt)
    carry = acc.init_carry(params, opt)

    record = acc.warmup(step, carry, prepared)
    assert record["label"] == step.label
    assert record["compile_time_s"] > 0
    assert record["persistent_cache_hits"] >= 0
    assert record["persistent_cache_misses"] >= 0

    detector = acc.telemetry.detector(step.label)
    signatures_after_warmup = len(detector._seen)
    steps = 0
    for batch in prepared:
        carry, metrics = step(carry, batch)
        steps += 1
    assert steps >= 3
    assert np.isfinite(float(metrics["loss"]))
    # the warmed signature covered every real call: no retrace, and the
    # first real batch added NO new signature (true AOT dispatch)
    assert detector.retraces == 0
    assert len(detector._seen) == signatures_after_warmup

    lines = [json.loads(l) for l in jsonl.read_text().splitlines()]
    compile_recs = [l for l in lines if l["kind"] == "compile"]
    assert len(compile_recs) == 1
    assert compile_recs[0]["source"] == "warmup"
    assert compile_recs[0]["label"] == step.label
    assert compile_recs[0]["compile_time_s"] > 0
    assert "persistent_cache_hits" in compile_recs[0]
    assert "persistent_cache_misses" in compile_recs[0]
    step_recs = [l for l in lines if l["kind"] == "step"]
    assert len(step_recs) == steps
    # no step paid compile cost: retraced stays False and the compile
    # fields never appear on a step record
    for rec in step_recs:
        assert rec["retraced"] is False
        assert "compile_time_s" not in rec


def test_warmup_auto_audits_compiled_collectives(tmp_path):
    # the sharding X-ray runs at warmup by default: the train step's
    # compiled HLO is inventoried structurally (no string matching on
    # HLO text) and checked against the layout's expected-collective
    # contract — on the 8-way dp mesh the grad sync is explained, so
    # the audit is clean, and the verdict rides the telemetry stream
    from accelerate_tpu.profiling import (
        get_program_registry,
        reset_program_registry,
    )

    reset_program_registry()
    jsonl = tmp_path / "telemetry.jsonl"
    acc = _fresh_accelerator(
        telemetry=TelemetryConfig(jsonl_path=str(jsonl))
    )
    ds = [{"x": np.full((2,), float(i), np.float32)} for i in range(32)]
    loader = DataLoader(ds, batch_size=8, shuffle=False)
    params = {"w": jnp.asarray(1.0), "b": jnp.asarray(0.5)}
    params, opt, prepared = acc.prepare(params, optax.sgd(0.1), loader)
    step = acc.unified_step(loss_fn, opt)
    carry = acc.init_carry(params, opt)
    acc.warmup(step, carry, prepared)

    audit = get_program_registry().get_audit(step.label)
    assert audit is not None
    assert audit.contract is not None
    assert audit.contract.origin.startswith("train:")
    # every collective the compiler emitted is explained by the layout
    assert audit.violations == []
    assert audit.clean
    for op in audit.collectives:
        assert audit.contract.permits(op.kind)
        assert op.fabric in ("ici", "dcn")
    # the verdict landed in the telemetry stream as a kind="audit" record
    lines = [json.loads(l) for l in jsonl.read_text().splitlines()]
    audit_recs = [l for l in lines if l["kind"] == "audit"]
    assert len(audit_recs) == 1
    assert audit_recs[0]["program"] == step.label
    assert audit_recs[0]["clean"] is True
    assert audit_recs[0]["violations"] == []
    reset_program_registry()


def test_warmup_matches_unwarmed_numerics():
    ds = [{"x": np.full((2,), float(i), np.float32)} for i in range(32)]

    def run(warm: bool):
        acc = _fresh_accelerator()
        loader = DataLoader(ds, batch_size=8, shuffle=False)
        # fresh param leaves per run: the donated carry consumes them
        params = {"w": jnp.asarray(1.0), "b": jnp.asarray(0.5)}
        p, opt, prepared = acc.prepare(params, optax.sgd(0.1), loader)
        step = acc.unified_step(loss_fn, opt)
        carry = acc.init_carry(p, opt)
        if warm:
            acc.warmup(step, carry, prepared)
        losses = []
        for batch in prepared:
            carry, metrics = step(carry, batch)
            losses.append(float(metrics["loss"]))
        return losses

    np.testing.assert_allclose(run(True), run(False), rtol=1e-6)


def test_warmup_rejects_bare_callables():
    acc = _fresh_accelerator()
    with pytest.raises(TypeError, match="unified_step"):
        acc.warmup(lambda c, b: (c, {}), {}, {})


# --------------------------------------------------------------------- #
# collective/compute overlap (compilation/overlap.py)
# --------------------------------------------------------------------- #
def test_overlap_options_cpu_noop_tpu_default():
    from accelerate_tpu.compilation.overlap import (
        DEFAULT_OVERLAP_OPTIONS,
        overlap_options,
    )

    # CPU backend would reject the TPU scheduler flags: must be empty
    assert overlap_options(backend="cpu") == {}
    opts = overlap_options(backend="tpu")
    assert opts == DEFAULT_OVERLAP_OPTIONS
    assert opts is not DEFAULT_OVERLAP_OPTIONS  # caller-owned copy


def test_merge_compiler_options_user_wins():
    from accelerate_tpu.compilation.overlap import merge_compiler_options

    assert merge_compiler_options(None, None) is None
    assert merge_compiler_options({}, None) is None
    user = {"xla_enable_async_all_gather": False, "xla_custom": 1}
    merged = merge_compiler_options(
        {"xla_enable_async_all_gather": True, "xla_tpu_flag": True}, user
    )
    assert merged["xla_enable_async_all_gather"] is False  # user wins
    assert merged["xla_tpu_flag"] is True
    assert merged["xla_custom"] == 1
    # no overlap flags -> user dict passes through untouched
    assert merge_compiler_options(None, user) is user


def test_wants_collective_overlap_gates_on_layout():
    from accelerate_tpu.parallel.sharding import (
        MESH_AXIS_DATA,
        MESH_AXIS_FSDP,
        ShardingStrategy,
        wants_collective_overlap,
    )

    class _Mesh:
        def __init__(self, data, fsdp):
            self.shape = {MESH_AXIS_DATA: data, MESH_AXIS_FSDP: fsdp}

    class _Plugin:
        def __init__(self, strategy):
            self.sharding_strategy = strategy

    sharded = _Plugin(ShardingStrategy.FULL_SHARD)
    assert wants_collective_overlap(None, _Mesh(2, 4)) is False
    assert wants_collective_overlap(sharded, None) is False
    assert (
        wants_collective_overlap(_Plugin(ShardingStrategy.NO_SHARD), _Mesh(2, 4))
        is False
    )
    # single-device mesh: nothing to hide
    assert wants_collective_overlap(sharded, _Mesh(1, 1)) is False
    assert wants_collective_overlap(sharded, _Mesh(2, 4)) is True
    assert wants_collective_overlap(sharded, _Mesh(1, 8)) is True


def test_overlap_from_spans_interval_math():
    from accelerate_tpu.compilation.overlap import overlap_from_spans

    # all-gather [0,10) with compute covering [0,6): 60% overlap; the
    # async pair all-reduce-start [20,21) / -done [28,30) folds into one
    # [20,30) interval, covered by compute [25,30): 5 of 10.
    report = overlap_from_spans(
        [
            {"name": "fusion.1", "start": 0, "end": 6},
            {"name": "all-gather.7", "start": 0, "end": 10},
            {"name": "all-reduce.3-start", "start": 20, "end": 21},
            {"name": "all-reduce.3-done", "start": 28, "end": 30},
            {"name": "fusion.2", "start": 25, "end": 30},
        ]
    )
    assert report["collective_time"] == 20
    assert report["overlapped_time"] == 11
    np.testing.assert_allclose(report["overlap_pct"], 55.0)
    # no collectives -> nothing to measure
    assert overlap_from_spans([{"name": "fusion", "start": 0, "end": 5}]) is None


def test_xplane_wire_parser_round_trip():
    from accelerate_tpu.compilation.overlap import (
        parse_xspace_planes,
        spans_from_plane,
    )

    def varint(n):
        out = b""
        while True:
            b7 = n & 0x7F
            n >>= 7
            out += bytes([b7 | (0x80 if n else 0)])
            if not n:
                return out

    def ld(field, payload):  # length-delimited field
        return varint((field << 3) | 2) + varint(len(payload)) + payload

    def vi(field, value):  # varint field
        return varint(field << 3) + varint(value)

    event = vi(1, 7) + vi(2, 100) + vi(3, 50)  # metadata_id/offset/duration
    line = ld(2, b"xla-ops") + vi(3, 2) + ld(4, event)  # ts 2 ns
    # map<int64, XEventMetadata> entry: key 7 -> {id: 7, name: ...}
    entry = vi(1, 7) + ld(2, vi(1, 7) + ld(2, b"all-reduce.1"))
    plane = ld(2, b"/device:TPU:0") + ld(3, line) + ld(4, entry)
    space = ld(1, plane)

    planes = parse_xspace_planes(space)
    assert len(planes) == 1
    assert planes[0]["name"] == "/device:TPU:0"
    assert planes[0]["event_names"] == {7: "all-reduce.1"}
    spans = spans_from_plane(planes[0])
    # absolute ps timeline: 2 ns * 1000 + offset 100
    assert spans == [{"name": "all-reduce.1", "start": 2100, "end": 2150}]


def test_accelerator_cpu_overlap_is_noop(restore_cache_config):
    """The Accelerator threads overlap options through compiler_options
    at init; on CPU the option set is empty so the plugin sentinel stays
    None — even when the user forces overlap_collectives=True."""
    acc = _fresh_accelerator(
        compile_plugin=CompilePlugin(overlap_collectives=True)
    )
    assert acc.compile_plugin.compiler_options is None
    acc2 = _fresh_accelerator(compile_plugin=CompilePlugin())
    assert acc2.compile_plugin.compiler_options is None

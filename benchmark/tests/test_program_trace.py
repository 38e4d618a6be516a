"""The readers of what the PROGRAM writes into a trace — host spans with
their stats, scope paths of device operations — on a hand-written trace whose
answers are known and on traces recorded on the chip: the parent's (flax names
only: a fifth of the step under no scope) and this tree's."""

import importlib
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import program_trace, trace_reduce  # noqa: E402

SPANS = os.path.join(HERE, "synthetic_spans.xplane.textproto")
RECORDED = os.path.join(HERE, "recorded_train_1chip.xplane.pb")
RECORDED_SCOPED = os.path.join(HERE, "recorded_train_1chip_scoped.xplane.pb")


def reader(name):
    return importlib.import_module(f"readers.{name}")


def cell_over(tmp_path, trace_file, name="a-cell"):
    """A cell whose run 'wrote' ``trace_file``, by ``common.Tracer``'s path
    rule (a hand-written text trace is put there in the binary form)."""
    root = tmp_path / f"root-{name}"
    where = root / ".bench_trace" / name / "plugins" / "profile" / "t"
    where.mkdir(parents=True)
    if trace_file.endswith(".textproto"):
        import jax

        with open(trace_file) as f:
            raw = jax.profiler.ProfileData.text_proto_to_serialized_xspace(f.read())
        (where / "host.xplane.pb").write_bytes(raw)
    else:
        os.symlink(trace_file, where / "host.xplane.pb")
    return {"name": name, "bench_dir": str(root / "benchmark")}


def spans_trace(tmp_path) -> str:
    """The hand-written trace in the binary form the loader reads."""
    return program_trace.path_of(cell_over(tmp_path, SPANS, "loaded"))


def test_spans_and_scope_paths_are_loaded_with_their_stats(tmp_path):
    trace = program_trace.load(spans_trace(tmp_path))
    by_name = {}
    for name, s, e, st in trace["spans"]:
        by_name.setdefault(name, []).append((round(s * 1e3, 6), round(e * 1e3, 6), st))
    assert set(by_name) == {
        "atpu:serve.step", "atpu:serve.schedule", "atpu:serve.prefill",
        "atpu:serve.decode.inputs", "atpu:serve.decode.fetch", "atpu:serve.emit",
        "bench:engine_step"}
    assert by_name["atpu:serve.step"] == [(1.0, 8.0, {"step": 7}), (10.0, 13.0, {"step": 8})]
    # a string stat kept by reference, and one kept in place
    assert [st for _, _, st in by_name["atpu:serve.prefill"]] == [
        {"request_id": "req-9", "bucket": 8, "cached": 0},
        {"request_id": "req-10", "bucket": 16, "cached": 0}]
    ops = trace["devices"][0]["ops"]
    assert ("fusion.12 fusion", pytest.approx(4e-3), pytest.approx(5e-3),
            "jit(_decode)/sample/argmax") in ops
    assert [op[3] for op in ops if op[0].startswith("copy.4")] == [""]
    assert [m[0] for m in trace["devices"][0]["modules"]] == [
        "jit__decode(11)", "jit__prefill(12)"]


@pytest.mark.parametrize("path, cleaned", [
    ("jit(_step)/transpose(jvp(loss))/CausalLM/layers/while/body/closed_call/"
     "checkpoint/rematted_computation/layers/mlp/up_proj/dot_general",
     "loss/layers/layers/mlp/up_proj/dot_general"),
    ("jit(_step)/jvp(loss)/jit(log_softmax)/sub", "loss/sub"),
    ("jit(_step)/optimizer/add", "optimizer/add"),
    ("jit(_step)/jvp(CausalLM)/while", "while"),
    ("jit(_step)/add", "add"),
    ("jit(_decode)/sample/vmap(jit(_gumbel))/jit(_uniform)/vmap()/while/body/add",
     "sample/add"),
    ("jit(_decode)/CausalLM/layers/cond/branch_1_fun/mul", "layers/mul"),
    ("carry['params']['lm_head']['kernel']", "carry['params']['lm_head']['kernel']"),
    ("", ""),
])
def test_scope_of_keeps_what_the_program_named(path, cleaned):
    assert program_trace.scope_of(path, "CausalLM") == cleaned
    assert program_trace.is_unscoped(cleaned) == ("/" not in cleaned)


def test_span_gap_shares_every_gap_once(tmp_path, capsys):
    cell = cell_over(tmp_path, SPANS)
    gap = reader("span_gap")
    gap.table.cache_clear()

    def read(spans):
        return gap.read({}, {"any": "trace"}, cell, spans=spans)

    pct = 100.0 / 13.0  # of the 13 ms window, per ms
    assert read(["atpu:serve.schedule"]) == pytest.approx(0.5 * pct)
    # a gap split between the inner span and the outer one around it
    assert read(["atpu:serve.prefill"]) == pytest.approx(1.75 * pct)
    assert read("atpu:serve.step") == pytest.approx(1.25 * pct)
    assert read(["atpu:serve.decode.inputs"]) == pytest.approx(0.25 * pct)
    assert read(["atpu:serve.decode.fetch", "atpu:serve.emit"]) == pytest.approx(1.5 * pct)
    # under no atpu: span, though the benchmark's own span covers half of it
    assert read(None) == pytest.approx(1.0 * pct)
    shares, window_s = gap.table(program_trace.path_of(cell))
    red = trace_reduce.reduce(SPANS)
    assert window_s == pytest.approx(red["window_s"])
    assert sum(shares.values()) == pytest.approx(
        100.0 * (1.0 - red["busy_s"] / red["window_s"]))
    assert capsys.readouterr().out.count("span_gap:") == 1  # printed once
    assert gap.read({}, None, cell, spans=None) is None  # a --trace 0 run


def test_span_ms_is_a_percentile_of_the_spans_durations(tmp_path):
    cell = cell_over(tmp_path, SPANS)
    ms = reader("span_ms")
    assert ms.read({}, {}, cell, span="atpu:serve.prefill", q=50) == pytest.approx(1.0)
    assert ms.read({}, {}, cell, span="atpu:serve.prefill", q=100) == pytest.approx(1.25)
    assert ms.read({}, {}, cell, span="atpu:serve.nothing", q=50) is None


def test_scope_share_on_the_hand_written_trace(tmp_path, capsys):
    cell = cell_over(tmp_path, SPANS)
    share = reader("scope_share")
    share.by_scope.cache_clear()

    def read(program, scope):
        return share.read({}, {}, cell, program=program, scope=scope)

    # jit__decode: dot 2 ms, while's own 1 ms, sample 1, add 0.5, no path 0.25
    assert read("jit__decode", "(^|/)paged_attention/") == pytest.approx(100 * 2 / 4.75)
    assert read("jit__decode", "^layers/[^/]+$") == pytest.approx(100 * 1 / 4.75)
    assert read("jit__decode", "^sample/") == pytest.approx(100 * 1 / 4.75)
    assert read("jit__decode", "(^|/)layers/mlp") is None  # nothing matches
    assert read("jit__prefill", "(^|/)layers/mlp") == pytest.approx(100.0)
    out = capsys.readouterr().out
    assert "scope_share jit__decode: unscoped 15.789 %" in out
    assert "jit(_decode)/add 10.526 %, (no path) copy.4 copy 5.263 %" in out


def test_a_trace_without_program_spans_reads_nothing(tmp_path):
    """The parent's trace: ``bench:`` spans and flax names only. The span
    readers find nothing and do not raise; the scopes flax gave still read."""
    cell = cell_over(tmp_path, RECORDED, "parent-cell")
    assert reader("span_gap").read({}, {}, cell, spans=None) is None
    assert reader("span_gap").read({}, {}, cell, spans=["atpu:serve.emit"]) is None
    assert reader("span_ms").read({}, {}, cell, span="atpu:serve.prefill", q=50) is None
    share = reader("scope_share")
    assert share.read({}, {}, cell, program="jit__step",
                      scope="^(optimizer|clip|cast|accumulate)/") is None
    none = {"name": "no-run", "bench_dir": str(tmp_path / "nowhere" / "benchmark")}
    assert share.read({}, {}, none, program="jit__step", scope="layers") is None


def test_scope_share_on_the_recorded_parent_trace(tmp_path, capsys):
    cell = cell_over(tmp_path, RECORDED, "recorded")
    share = reader("scope_share")
    share.by_scope.cache_clear()

    def read(scope):
        return share.read({}, {}, cell, program="jit__step", scope=scope)

    assert read("(^|/)layers/mlp") == pytest.approx(47.6, abs=0.5)
    assert read("(^|/)layers/attn") == pytest.approx(18.7, abs=0.5)
    assert read("(^|/)lm_head/") == pytest.approx(11.6, abs=0.5)
    assert read("(^|/)embed/") == pytest.approx(0.9, abs=0.2)
    seconds, total = share.by_scope(program_trace.path_of(cell), "jit__step", "CausalLM")
    unscoped = sum(v for k, v in seconds.items() if program_trace.is_unscoped(k))
    assert 100 * unscoped / total == pytest.approx(21.0, abs=1.0)
    assert total == pytest.approx(trace_reduce.reduce(RECORDED)["busy_s"], rel=1e-4)
    assert "largest unscoped: jit(_step)/add 11.297 %" in capsys.readouterr().out


@pytest.mark.skipif(not os.path.isfile(RECORDED_SCOPED), reason="not recorded yet")
def test_scope_share_on_this_trees_recorded_trace(tmp_path):
    """Recorded on the chip with the scopes of ``unified_step`` and the
    ``layers`` scope in place: nearly nothing is left unnamed."""
    cell = cell_over(tmp_path, RECORDED_SCOPED, "scoped")
    share = reader("scope_share")
    seconds, total = share.by_scope(program_trace.path_of(cell), "jit__step", "CausalLM")
    unscoped = {k: v for k, v in seconds.items() if program_trace.is_unscoped(k)}
    assert 100 * sum(unscoped.values()) / total < 4.0
    assert 100 * max(unscoped.values()) / total < 1.0

    def read(scope):
        return share.read({}, {}, cell, program="jit__step", scope=scope)

    four = [read("(^|/)layers/mlp"), read("(^|/)layers/attn"),
            read("(^|/)lm_head/|^loss/[^/]+$"),
            read("^(optimizer|clip|cast|accumulate)/")]
    assert all(v is not None and 0 < v < 100 for v in four)
    assert 90 < sum(four) <= 100
    labels = {op[0] for op in program_trace.load(RECORDED_SCOPED)["devices"][0]["ops"]}
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert any(l.startswith(kernel) and l.endswith("tpu_custom_call")
                   for l in labels), kernel

"""What the program names in a profiler trace: the host phase spans of
``ServingEngine.step`` (``atpu:serve.*``, ``utils.profiling.annotate``) and
the scopes and kernel names on its device operations.

One profiler session for the whole module: five tiny engines (plain, which
decodes one step ahead by its own choice; chunked prefill; speculative; told to
decode ahead; told to take one step at a time) are stepped inside it and every
span test reads the one ``.xplane.pb`` it left. Spans exist only while a
session does; names on device operations are metadata and change no program.
The benchmark's reader of the decode round trip (``benchmark/readers/
round_trip.py``) is held here too, on its hand-written trace with the device
plane moved against the host plane: ``benchmark/tests`` run in no gate.
"""

import collections
import glob
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "tests"))

import round_trip_traces  # noqa: E402
from harness import program_trace  # noqa: E402
from readers import round_trip  # noqa: E402

from accelerate_tpu.models import CausalLM, TransformerConfig  # noqa: E402
from accelerate_tpu.serving import ServingEngine, SpecConfig  # noqa: E402
from accelerate_tpu.utils.profiling import annotate  # noqa: E402

INPUTS, DISPATCH, WAIT, FETCH = (
    "atpu:serve.decode.inputs", "atpu:serve.decode.dispatch",
    "atpu:serve.decode.wait", "atpu:serve.decode.fetch")
PHASES = ("atpu:serve.schedule", "atpu:serve.prefill", INPUTS, DISPATCH, WAIT,
          FETCH, "atpu:serve.emit")
STEP = "atpu:serve.step"
ENGINES = ("plain", "chunked", "spec", "ahead", "one")
AHEAD = ("plain", "ahead")  # by default (a dense engine with no feature), or told
Span = collections.namedtuple("Span", "name start end stats")


def _drive(eng, prompts, max_new_tokens=4):
    ids = [eng.add_request(p, max_new_tokens=max_new_tokens) for p in prompts]
    steps = 0
    while eng.has_work:
        eng.step()
        steps += 1
    return ids, [eng.result(i) for i in ids], steps


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    """Engines stepped before and inside ONE profiler session, and the
    ``atpu:`` spans the session recorded."""
    cfg = TransformerConfig.tiny(max_seq_len=64)
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
        "params"]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 9, 14, 20, 6, 11)]  # more requests than slots
    # a repetitive prompt, so that the n-gram proposer drafts
    echo = [np.tile(np.arange(3, 7, dtype=np.int32), 5)]

    def engine(**kw):
        return ServingEngine(model, params, max_slots=4, num_blocks=48,
                             block_size=8, **kw)

    quiet = engine()  # never sees a session
    _, quiet_tokens, _ = _drive(quiet, prompts)
    plain, chunked = engine(), engine(prefill_chunk_tokens=8)
    spec, ahead = engine(spec_decode=SpecConfig(k=2)), engine(decode_ahead=True)
    one = engine(decode_ahead=False)
    assert [e.decode_ahead for e in (plain, chunked, spec, ahead, one)] == [
        True, False, False, True, False]
    work = {"plain": (plain, prompts, 4), "chunked": (chunked, prompts, 4),
            "spec": (spec, echo + prompts[:1], 6), "ahead": (ahead, prompts, 4),
            "one": (one, prompts, 4)}
    # every engine does its work once outside the session: these steps must
    # leave no span, and they trace every program the session's steps run
    run = {}
    for name, (eng, asks, new) in work.items():
        run[name] = {"engine": eng, "steps_before": _drive(eng, asks, new)[2],
                     "dispatched_before": dict(eng._dispatched),
                     "traced_before": eng.trace_counts()}
    trace_dir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(trace_dir)):
        for name, (eng, asks, new) in work.items():
            ids, tokens, steps = _drive(eng, asks, new)
            run[name].update(ids=ids, tokens=tokens, steps=steps)
    (path,) = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("atpu:"):
                    spans.append(Span(ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      dict(ev.stats)))
    spans.sort(key=lambda s: (s.start, -s.end))
    # the engines ran one after the other: split the steps among them
    steps = [s for s in spans if s.name == STEP]
    at = 0
    for name in ENGINES:
        mine = steps[at:at + run[name]["steps"]]
        at += run[name]["steps"]
        run[name]["by_step"] = [
            (st, [s for s in spans if s.name != STEP
                  and st.start <= s.start and s.end <= st.end])
            for st in mine]
    return {"run": run, "spans": spans, "steps": steps, "path": path,
            "quiet_tokens": quiet_tokens, "prompts": prompts}


def test_every_step_of_the_session_left_one_step_span(session):
    run = session["run"]
    assert len(session["steps"]) == sum(r["steps"] for r in run.values())
    assert {s.name for s in session["spans"]} <= set(PHASES) | {STEP}
    for r in run.values():
        # the step stat is the engine's own count, and the steps taken before
        # the session left nothing: the first span is the next step
        counts = [st.stats["step"] for st, _ in r["by_step"]]
        assert counts == list(range(r["steps_before"],
                                    r["steps_before"] + r["steps"]))


ONE_STEP = [INPUTS, DISPATCH, WAIT, FETCH]
# one step ahead: the first step dispatches two, the last none
AHEAD_STEPS = ([], [INPUTS, DISPATCH] + ONE_STEP, ONE_STEP, [WAIT, FETCH])
DECODES = {
    "plain": AHEAD_STEPS, "chunked": ([], ONE_STEP), "one": ([], ONE_STEP),
    # a round in which nothing was drafted decodes plainly, after its own
    # inputs span (the COW and the proposer's work)
    "spec": ([], ONE_STEP, [INPUTS] + ONE_STEP),
    "ahead": AHEAD_STEPS,
}


def _decode_spans(session, which):
    """Per step of ``which`` that decoded: its decode spans, in order."""
    out = []
    for _, inner in session["run"][which]["by_step"]:
        decode = [s for s in inner if s.name in ONE_STEP]
        if decode:
            out.append(decode)
    return out


@pytest.mark.parametrize("which", ENGINES)
def test_a_step_holds_exactly_the_phases_nested_and_in_order(session, which):
    for st, inner in session["run"][which]["by_step"]:
        names = [s.name for s in inner]
        assert names[0] == "atpu:serve.schedule" and names[-1] == "atpu:serve.emit"
        assert names.count("atpu:serve.schedule") == 1
        assert names.count("atpu:serve.emit") == 1
        middle = names[1:-1]
        prefills = [n for n in middle if n == "atpu:serve.prefill"]
        assert middle[:len(prefills)] == prefills  # prefills, then the decode
        decode = middle[len(prefills):]
        # inputs, dispatch, wait, fetch, in that order
        assert decode in DECODES[which], decode
        # the phases lie inside the step, one after the other: none overlaps
        assert st.start <= inner[0].start and inner[-1].end <= st.end
        for a, b in zip(inner, inner[1:]):
            assert a.end <= b.start
        if which in ("plain", "one"):
            assert len(prefills) == inner[0].stats["admitted"]
        seated = [s for s in inner if s.name == INPUTS]
        assert all(1 <= s.stats["seated"] <= 4 for s in seated)
    emitted = sum(inner[-1].stats["tokens"]
                  for _, inner in session["run"][which]["by_step"])
    assert emitted == sum(len(t) for t in session["run"][which]["tokens"])
    seen = {tuple(s.name for s in d) for d in _decode_spans(session, which)}
    assert seen == {tuple(d) for d in DECODES[which] if d}  # every form ran


@pytest.mark.parametrize("which", ENGINES)
def test_dispatches_count_up_from_the_engines_count_before_the_session(
        session, which):
    r = session["run"][which]
    by_program = collections.defaultdict(list)
    for decode in _decode_spans(session, which):
        for s in decode:
            if s.name == DISPATCH:
                by_program[s.stats["program"]].append(s.stats["n"])
    assert set(by_program) == ({"jit__decode", "jit__verify"} if which == "spec"
                               else {"jit__decode"})
    for program, ns in by_program.items():
        first = r["dispatched_before"][program]
        assert ns == list(range(first, first + len(ns)))
        assert r["engine"]._dispatched[program] == first + len(ns)
    # the session's steps ran the programs the engine had: nothing retraced
    assert r["engine"].trace_counts() == r["traced_before"]


@pytest.mark.parametrize("which", ENGINES)
def test_every_wait_names_a_dispatch_that_began_before_it(session, which):
    spans = [s for decode in _decode_spans(session, which) for s in decode]
    calls = {(s.stats["program"], s.stats["n"]): s
             for s in spans if s.name == DISPATCH}
    waits = [s for s in spans if s.name == WAIT]
    assert len(waits) == len(calls) > 3  # each dispatch is waited for, once
    for i, w in enumerate(spans):
        if w.name != WAIT:
            continue
        call = calls.pop((w.stats["program"], w.stats["n"]))
        assert call.end <= w.start
        assert spans[i + 1].name == FETCH  # the copy of an array that is ready
        if which not in AHEAD:
            assert spans[i - 1] is call  # nothing between the call and its wait
            continue
        # one step ahead: where a next step was dispatched at all, its
        # dispatch began before the wait for this one
        nxt = calls.get((w.stats["program"], w.stats["n"] + 1))
        in_step = [s for s in spans if s.name == DISPATCH
                   and call.end <= s.start and s.end <= w.start]
        assert in_step == ([nxt] if nxt is not None and nxt.start < w.start
                           else [])
    assert not calls
    if which in AHEAD:
        ahead_of = [s for d in _decode_spans(session, which)
                    if [x.name for x in d] == ONE_STEP for s in d]
        assert ahead_of and all(  # the steady step: dispatch n + 1, wait n
            d.stats["n"] == w.stats["n"] + 1
            for d, w in zip(ahead_of[1::4], ahead_of[2::4]))


def test_a_speculative_round_names_the_program_it_dispatched(session):
    rounds = _decode_spans(session, "spec")
    said = collections.Counter()
    for decode in rounds:
        (call,) = [s for s in decode if s.name == DISPATCH]
        (wait,) = [s for s in decode if s.name == WAIT]
        fell_back = [s.name for s in decode].count(INPUTS) == 2
        assert call.stats["program"] == wait.stats["program"] == (
            "jit__decode" if fell_back else "jit__verify")
        said[call.stats["program"]] += 1
    assert said["jit__verify"] and said["jit__decode"]


@pytest.mark.parametrize("which", ENGINES)
def test_what_a_step_reads_still_sits_on_its_inputs_span(session, which):
    """``rows`` / ``positions`` / ``seated`` (``decode_roofline.eva``,
    ``cache_rows_per_token.eva``) stay on the span of the step's inputs."""
    for decode in _decode_spans(session, which):
        for i, s in enumerate(decode):
            if s.name == DISPATCH:
                assert set(s.stats) == {"program", "n"}
                fed = decode[i - 1]
                assert fed.name == INPUTS
                if s.stats["program"] == "jit__decode":
                    # one row a position on these models
                    assert fed.stats["rows"] == fed.stats["positions"] > 0
                    assert 1 <= fed.stats["seated"] <= 4
            elif s.name == WAIT:
                assert set(s.stats) == {"program", "n"}
            elif s.name == FETCH:
                assert not s.stats


def test_the_benchmarks_loader_reads_the_new_stats_as_the_reader_needs_them(
        session):
    spans = program_trace.load(session["path"])["spans"]
    calls = [st for name, _, _, st in spans if name == DISPATCH]
    assert len(calls) == sum(
        s.name == DISPATCH for s in session["spans"])
    assert {st["program"] for st in calls} == {"jit__decode", "jit__verify"}
    assert all(isinstance(st["n"], int) for st in calls)
    # a CPU trace holds no device plane: nothing to read, and no error
    assert round_trip.table(session["path"], "jit__decode") is None


@pytest.mark.parametrize("shift_ms", [0.8, -0.8])
def test_the_round_trip_reader_does_not_need_the_two_planes_to_agree(
        tmp_path, capsys, shift_ms):
    """The same five parts with the device plane 0.8 ms late or early, and
    an interval of offsets that holds what undoes the shift."""
    def read(shift):
        return round_trip_traces.read(round_trip_traces.cell_over(
            tmp_path, round_trip_traces.text(shift), f"shift{shift}"),
            "jit__decode")

    parts, (lo, hi) = read(0.0)
    assert parts == pytest.approx(round_trip_traces.ONE_AT_A_TIME, abs=1e-3)
    assert lo <= 0.0 <= hi
    moved, (lo, hi) = read(shift_ms)
    assert moved == pytest.approx(parts, abs=1e-6)
    assert lo <= -shift_ms <= hi and not lo <= 0.0 <= hi
    assert "the planes disagree by at least 0.5" in capsys.readouterr().out


NEW_METRICS = (
    "step_exposed_ms.tput", "step_exposed_ms.itl", "launch_wake_ms.tput",
    "launch_wake_ms.itl", "step_host_ms.tput", "decode_inputs_ms.tput",
    "decode_dispatch_ms.tput", "decode_fetch_copy_ms.tput",
    "idle_dispatch_share.tput", "idle_wait_share.tput")


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_new_metric_is_absent_for_a_program_that_draws_no_dispatch_span(
        tmp_path, metric):
    """Each metric file through its reader, as ``harness/cell.evaluate``
    does: a number on the hand-written trace of a program that draws the
    four decode spans, NOTHING (not 0, and not the wider span's duration)
    on the one of a program that draws ``inputs`` and ``fetch`` alone."""
    import json

    from harness import cell as cells

    with open(os.path.join(ROOT, "benchmark", "metrics", f"{metric}.json")) as f:
        spec = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "tests",
                           "synthetic_spans.xplane.textproto")) as f:
        older = round_trip_traces.cell_over(tmp_path, f.read(), "older")
    newer = round_trip_traces.cell_over(tmp_path, round_trip_traces.text(), "newer")
    reader = cells.named(f"readers.{spec['reader']}")
    assert reader.read({}, {}, older, **spec["args"]) is None
    assert reader.read({}, {}, newer, **spec["args"]) > 0.0
    entry = next(m for m in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))[
        "per_layer"] if m["name"] == metric)
    assert (entry["unit"], entry["layer"], entry["moves"]) == (
        spec["unit"], spec["layer"], spec["moves"])


def test_an_engine_with_no_collector_builds_no_gauge_record(session):
    class Collector:
        def __init__(self):
            self.gauges = []

        def record_serve_gauge(self, **fields):
            self.gauges.append(fields)

    plain = session["run"]["plain"]["engine"]
    model, params = plain.model, plain.params

    def serve(telemetry):
        eng = ServingEngine(model, params, max_slots=4, num_blocks=48,
                            block_size=8, telemetry=telemetry)
        built = []
        fields = eng._gauge_fields
        eng._gauge_fields = lambda: built.append(fields()) or built[-1]
        _, tokens, steps = _drive(eng, session["prompts"][:3])
        return tokens, steps, built

    tokens, steps, built = serve(None)
    assert built == [] and steps >= 4
    collector = Collector()
    again, steps_again, built = serve(collector)
    assert again == tokens and steps_again == steps
    # one record a step (gauge_interval 1), each what _gauge_fields gave
    assert collector.gauges == built and len(built) == steps
    assert [g["engine_steps"] for g in built] == list(range(1, steps + 1))
    # as before, the share of its steps that were decoded ahead, and what a
    # seat holds beside its K/V rows (0 for a stack without recurrent layers),
    # and what a position's latent row holds (0: per-head K and V), and what
    # of its prefill buckets held a token
    assert len(built[0]) == 40 and built[0]["slots_active"] == 3
    assert 0.5 < built[0]["prefill_real_token_share"] <= 1.0
    assert built[0]["state_bytes_per_slot"] == 0 == built[0]["latent_row_bytes"]
    assert built[0]["decode_ahead_share"] == 0.0 < built[-1]["decode_ahead_share"]


def test_one_prefill_span_per_admitted_request_joins_its_request_span(session):
    r = session["run"]["plain"]
    prefills = [s for _, inner in r["by_step"] for s in inner
                if s.name == "atpu:serve.prefill"]
    assert sorted(s.stats["request_id"] for s in prefills) == sorted(r["ids"])
    lengths = dict(zip(r["ids"], map(len, session["prompts"])))
    closed = {sp.request_id: sp for sp in r["engine"].span_log.closed}
    for s in prefills:
        rid = s.stats["request_id"]
        want = 1 << (lengths[rid] - 1).bit_length()
        assert s.stats["bucket"] == want and s.stats["cached"] == 0
        # the lifecycle record of the same request: it was prefilled once
        assert closed[rid].prefill_start_t is not None


def test_chunked_prefill_draws_one_prefill_span_per_chunk(session):
    r = session["run"]["chunked"]
    by_request = collections.defaultdict(list)
    for _, inner in r["by_step"]:
        for s in inner:
            if s.name == "atpu:serve.prefill":
                by_request[s.stats["request_id"]].append(s.stats)
    assert sorted(by_request) == sorted(r["ids"])
    for rid, n in zip(r["ids"], map(len, session["prompts"])):
        # the step's budget of 8 tokens is shared among the seats, so a
        # chunk may be shorter: each starts where the one before ended
        starts = [c["cached"] for c in by_request[rid]]
        assert len(starts) >= -(-n // 8) and starts[0] == 0
        assert starts == sorted(set(starts)) and starts[-1] < n


def test_tokens_are_the_same_with_and_without_a_session(session):
    assert session["run"]["plain"]["tokens"] == session["quiet_tokens"]
    assert session["run"]["chunked"]["tokens"] == session["quiet_tokens"]
    assert session["run"]["ahead"]["tokens"] == session["quiet_tokens"]
    assert session["run"]["one"]["tokens"] == session["quiet_tokens"]


def test_annotate_is_inert_without_a_session():
    with annotate("atpu:test.nothing", n=1) as span:
        assert not span.is_enabled()
        span.set_metadata(m=2)  # accepted, kept nowhere


def test_decode_still_traces_once(session):
    for r in session["run"].values():
        assert r["engine"].trace_counts()["decode"] == 1


# ---------------------------------------------------------------------- #
# names on device operations
# ---------------------------------------------------------------------- #
SCOPES = {"loss", "cast", "clip", "optimizer", "accumulate",
          "paged_attention", "kv_write", "sample"}
_CALL = re.compile(r"^\w*\((.*)\)$")


def _named(op_name: str, model: str) -> bool:
    """Whether the path lies under the model's scope or one of SCOPES."""
    for comp in op_name.split("/")[:-1]:
        m = _CALL.match(comp)
        while m:
            comp, m = m.group(1), _CALL.match(m.group(1))
        # ``layers``: rope tables hoisted out of the differentiated function
        # keep the block's flax names but lose the model's
        if comp in (model, "layers") or comp in SCOPES:
            return True
    return False


def _op_names(lowered, program: str):
    """The ``op_name`` of every operation of the compiled program that JAX
    emitted under the program's name (XLA's own reducer bodies and the
    parameters carry a bare or an argument name)."""
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    return [n for n in names if n.startswith(f"jit({program})/")]


def _train_step():
    from accelerate_tpu import Accelerator

    cfg = TransformerConfig.tiny(max_seq_len=32, remat="dots")
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
        "params"]
    acc = Accelerator(mixed_precision="bf16")
    params, opt = acc.prepare(params, optax.adamw(1e-3))
    step = acc.unified_step(CausalLM.loss_fn(model), max_grad_norm=1.0)
    carry = acc.init_carry(params, opt)
    batch = {"input_ids": jnp.zeros((8, 32), jnp.int32)}
    # the step's own counters (micro_step, opt_step, is_sync): scalars
    handful = {"add", "ge", "convert_element_type", "select_n"}
    return step.jitted.lower(carry, batch), "_step", handful, {
        "loss", "cast", "clip", "optimizer", "accumulate"}


def _decode_program():
    cfg = TransformerConfig.tiny(max_seq_len=64)
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
        "params"]
    eng = ServingEngine(model, params, max_slots=4, num_blocks=40, block_size=8)
    n = eng.max_slots
    lowered = eng._decode_fn.lower(
        eng.params, eng.cache, jnp.zeros((n, 1), jnp.int32),
        eng._tables_device(), jnp.zeros(n, jnp.int32), jnp.ones(n, jnp.int32),
        eng.sampling.temperatures(), eng._split_key())
    return lowered, "_decode", set(), {"paged_attention", "kv_write", "sample"}


@pytest.mark.parametrize("build", [_train_step, _decode_program])
def test_every_operation_lies_under_a_scope(build):
    lowered, program, handful, must_see = build()
    names = _op_names(lowered, program)
    assert len(names) > 100
    stray = collections.Counter(
        n for n in names if not _named(n, "CausalLM"))
    assert {n.rsplit("/", 1)[-1] for n in stray} <= handful, stray
    assert all(n.count("/") == 1 or "jit(_where)" in n for n in stray), stray
    seen = {s for s in SCOPES if any(f"/{s}/" in n or f"({s})/" in n for n in names)}
    assert must_see <= seen
    # the scan's own copies and slices count to the layer stack
    assert any(re.search(r"CausalLM/layers/while/body/dynamic_(update_)?slice$", n)
               for n in names)


def test_the_flash_pallas_calls_carry_their_names():
    from accelerate_tpu.ops import flash_attention as fa

    q = jnp.zeros((1, 256, 4, 64), jnp.float32)
    kv = jnp.zeros((1, 256, 2, 64), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True, block_q=128,
                                          block_k=128))

    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn.params["name"])
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, kv, kv).jaxpr)
    assert found == ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]


def test_a_cached_program_keeps_its_own_scope_names(tmp_path):
    """JAX's default cache key leaves metadata out: a program that differs
    from a cached one only in a scope's name would load the other's
    executable, and a profile would show the other's names. The repo's cache
    rule keys on the names, and on no source line."""
    from accelerate_tpu.compilation import (
        activate_persistent_cache,
        get_compile_monitor,
    )
    from accelerate_tpu.utils.dataclasses import CompilePlugin

    mon = get_compile_monitor()
    activate_persistent_cache(CompilePlugin(
        cache_min_compile_time_secs=0.0, cache_min_entry_size_bytes=-1,
        cache_enable_xla_caches="all"))
    salt = float(np.random.default_rng().integers(1 << 30))  # no stale entry

    def build(scope):
        def program(x):
            with jax.named_scope(scope):
                return jnp.sin(x) * salt
        return jax.jit(program)

    def compiled(scope):
        before = mon.snapshot()
        text = build(scope).lower(jnp.arange(8.0)).compile().as_text()
        return mon.delta(before), text

    first, _ = compiled("alpha")
    assert first.get("persistent_cache_misses", 0) >= 1
    again, text = compiled("alpha")  # traced from another line: the same entry
    assert again.get("persistent_cache_hits", 0) >= 1
    assert again.get("persistent_cache_misses", 0) == 0
    other, text = compiled("beta")
    assert other.get("persistent_cache_misses", 0) >= 1
    assert "program)/beta/sin" in text and "alpha" not in text

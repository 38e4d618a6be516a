"""What the program names in a profiler trace: the host phase spans of
``ServingEngine.step`` (``atpu:serve.*``, ``utils.profiling.annotate``) and
the scopes and kernel names on its device operations.

One profiler session for the whole module: three tiny engines (plain, chunked
prefill, speculative) are stepped inside it and every span test reads the one
``.xplane.pb`` it left. Spans exist only while a session does; names on
device operations are metadata and change no program.
"""

import collections
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from accelerate_tpu.models import CausalLM, TransformerConfig
from accelerate_tpu.serving import ServingEngine, SpecConfig
from accelerate_tpu.utils.profiling import annotate

PHASES = ("atpu:serve.schedule", "atpu:serve.prefill",
          "atpu:serve.decode.inputs", "atpu:serve.decode.fetch",
          "atpu:serve.emit")
STEP = "atpu:serve.step"
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
    spec = engine(spec_decode=SpecConfig(k=2))
    # warm every engine outside the session: these steps must leave no span
    before = {}
    for name, eng, warm in (("plain", plain, prompts[:2]),
                            ("chunked", chunked, prompts[:2]),
                            ("spec", spec, echo)):
        before[name] = _drive(eng, warm)[2]
    trace_dir = tmp_path_factory.mktemp("trace")
    run = {}
    with jax.profiler.trace(str(trace_dir)):
        for name, eng, work in (("plain", plain, prompts),
                                ("chunked", chunked, prompts),
                                ("spec", spec, echo + prompts[:1])):
            ids, tokens, steps = _drive(eng, work, max_new_tokens=6
                                        if name == "spec" else 4)
            run[name] = {"engine": eng, "ids": ids, "tokens": tokens,
                         "steps": steps, "steps_before": before[name]}
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
    # the three engines ran one after the other: split the steps among them
    steps = [s for s in spans if s.name == STEP]
    at = 0
    for name in ("plain", "chunked", "spec"):
        mine = steps[at:at + run[name]["steps"]]
        at += run[name]["steps"]
        run[name]["by_step"] = [
            (st, [s for s in spans if s.name != STEP
                  and st.start <= s.start and s.end <= st.end])
            for st in mine]
    return {"run": run, "spans": spans, "steps": steps,
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


@pytest.mark.parametrize("which", ["plain", "chunked", "spec"])
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
        assert decode in ([], ["atpu:serve.decode.inputs", "atpu:serve.decode.fetch"],
                          # a round in which nothing was drafted decodes plainly
                          ["atpu:serve.decode.inputs", "atpu:serve.decode.inputs",
                           "atpu:serve.decode.fetch"])
        # the phases lie inside the step, one after the other: none overlaps
        for a, b in zip(inner, inner[1:]):
            assert a.end <= b.start
        if which == "plain":
            assert len(prefills) == inner[0].stats["admitted"]
        if decode:
            assert decode[0] == "atpu:serve.decode.inputs"
            seated = [s for s in inner if s.name == "atpu:serve.decode.inputs"]
            assert all(1 <= s.stats["seated"] <= 4 for s in seated)
    emitted = sum(inner[-1].stats["tokens"]
                  for _, inner in session["run"][which]["by_step"])
    assert emitted == sum(len(t) for t in session["run"][which]["tokens"])


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

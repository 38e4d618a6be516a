"""``ServingEngine(decode_ahead=True)``: decode step k + 1 goes to the device
before step k's tokens are fetched, fed from step k's output on the device.

Held here: greedy bytes are those of the engine that takes one step at a time
(dense and eva, with admissions while a step is in flight, answers that end by
their count, by an eos, and across a window's end); the step ahead really is
in flight when ``step()`` returns; one decode program and one feed program
whatever the source of a step's tokens; the pool comes back whole; and every
feature the lookahead is not written for is refused beside it, by name.

The default (``decode_ahead=None``): ahead wherever the engine runs plain decode,
whatever the model; one step at a time where it was built with a feature; and a
default engine that a warm toggle asks for a feature LANDS (the step in flight
is fetched, none follows, nothing compiles) where one told to decode ahead
refuses. ``decode_ahead_share`` says which of the two an engine does.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "tests"))

import tiny_eva  # noqa: E402
from harness import common  # noqa: E402
from harness import evabyte_weights as W  # noqa: E402

from accelerate_tpu.compilation import get_compile_monitor  # noqa: E402
from accelerate_tpu.models import CausalLM, TransformerConfig  # noqa: E402
from accelerate_tpu.router import FleetRouter, InProcessReplica  # noqa: E402
from accelerate_tpu.serving import ServingEngine, SpecConfig  # noqa: E402

EVA = tiny_eva.config()


@pytest.fixture(scope="module")
def dense():
    model = CausalLM(TransformerConfig.tiny(max_seq_len=64))
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params, 8, model.config.vocab_size


@pytest.fixture(scope="module")
def eva():
    model = CausalLM(common.program_config(
        EVA, max_seq_len=EVA["max_position_embeddings"]))
    return model, W.make_tree(EVA, 2**31 + 5, jnp.float32), 4, EVA["vocab_size"]


def _ids(n, seed, vocab):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def _serve(which, asks, late=(), eos=None, **kw):
    """Serve ``asks`` [(prompt bytes, new bytes)], the ``late`` ones submitted
    three steps in, while decode steps are in flight. Returns the engine, each
    request's bytes and how many steps it took."""
    model, params, block, vocab = which
    eng = ServingEngine(model, params, max_slots=3, block_size=block, **kw)
    rids, steps = [], 0
    for i, (p, new) in enumerate(asks):
        rids.append(eng.add_request(_ids(p, 20 + i, vocab), max_new_tokens=new,
                                    eos_token_id=eos))
    while eng.has_work or late:
        if steps == 3:
            for i, (p, new) in enumerate(late):
                rids.append(eng.add_request(
                    _ids(p, 50 + i, vocab), max_new_tokens=new, eos_token_id=eos))
            late = ()
        eng.step()
        steps += 1
    return eng, [eng.result(r) for r in rids], steps


# more requests than slots; eva: prompts on a window (16, 32) and on a chunk
# (20), answers that cross up to four windows of 16
ASKS = [(16, 40), (7, 30), (32, 5), (21, 12), (20, 9)]
LATE = [(5, 20), (33, 2), (9, 1)]


@pytest.mark.parametrize("which", ["dense", "eva"])
def test_greedy_bytes_are_those_of_one_step_at_a_time(which, request):
    which = request.getfixturevalue(which)
    asks = [(p, min(new, 64 - p)) for p, new in ASKS] if which[2] == 8 else ASKS
    plain, want, plain_steps = _serve(which, asks, LATE, decode_ahead=False)
    eng, got, steps = _serve(which, asks, LATE, decode_ahead=True)
    assert plain._feed_fn is None and plain._ahead is None
    assert got == want and all(len(t) == new for t, (_, new) in zip(
        got, asks + LATE))
    # a slot sits out the dispatch after its prefill and after a roll-over:
    # a few steps more, never fewer
    assert plain_steps <= steps <= plain_steps + len(asks + LATE) + 11
    assert eng._ahead is None and eng.pool.stats()["allocated"] == 0
    counts = eng.trace_counts()
    assert counts["decode"] == 1 == plain.trace_counts()["decode"]
    # one executable each, whether a step's tokens came from the host, from
    # the step before, or from both
    assert eng._decode_fn._cache_size() == 1 and eng._feed_fn._cache_size() == 1


def _adapters(model):
    from accelerate_tpu.adapters import AdapterRegistry

    return AdapterRegistry(model.config, capacity=1, max_rank=2,
                           target_modules=("q_proj", "v_proj"))


# what the lookahead is not written for: (its name in the refusal, how an
# engine is built with it)
FEATURES = [
    ("prefix_cache", lambda m: {"prefix_cache": True}),
    ("spec_decode", lambda m: {"spec_decode": SpecConfig(k=2)}),
    ("prefill_chunk_tokens", lambda m: {"prefill_chunk_tokens": 16}),
    ("preemption", lambda m: {"preemption": True}),
    ("adapters", lambda m: {"adapters": _adapters(m)}),
    ("role 'prefill'", lambda m: {"role": "prefill"}),
    ("role 'decode'", lambda m: {"role": "decode"}),
]


@pytest.mark.parametrize("which", ["dense", "eva"])
def test_the_default_is_on_wherever_the_engine_runs_plain_decode(which, request):
    model, params, block, _ = request.getfixturevalue(which)
    eng = ServingEngine(model, params, block_size=block)
    assert eng.decode_ahead is True and eng._feed_fn is not None
    assert eng.decode_ahead_share == 0.0  # nothing fetched yet


@pytest.mark.parametrize("feature,kwargs", FEATURES, ids=[f for f, _ in FEATURES])
def test_the_default_is_off_beside_a_feature(dense, feature, kwargs):
    """Left to choose, an engine built with a feature takes one step at a
    time, as before: no feed program, nothing ever in flight."""
    model, params, block, vocab = dense
    eng = ServingEngine(model, params, block_size=block, **kwargs(model))
    assert eng.decode_ahead is False and eng._feed_fn is None
    if feature.startswith("role"):
        return  # half an engine: the hand-off tests serve through a pair
    eng.add_request(_ids(9, 1, vocab), max_new_tokens=4)
    eng.add_request(_ids(5, 2, vocab), max_new_tokens=3)
    while eng.has_work:
        eng.step()
        assert eng._ahead is None
    assert eng.decode_ahead_share == 0.0 and eng._fetched >= 3


def test_the_next_step_is_on_the_device_when_step_returns(eva):
    model, params, block, vocab = eva
    eng = ServingEngine(model, params, max_slots=2, block_size=block)
    eng.add_request(_ids(6, 1, vocab), max_new_tokens=12)
    eng.add_request(_ids(9, 2, vocab), max_new_tokens=3)
    fetched = []
    while eng.has_work:
        events = eng.step()
        ahead = eng._ahead
        fetched.append((len(events), None if ahead is None
                        else [s.index for s, _ in ahead[1]]))
    # step 1: two prefills (a first byte each) and a decode step; the second
    # request's count ends it at its third byte, so it is in no step
    # dispatched after its second; slot 0 stands at 6 + 10 = 16, a window's
    # end, after its eleventh byte: it sits that dispatch out and rolls over
    assert fetched[0] == (4, [0, 1])
    assert fetched[1] == (2, [0])
    # ... one byte a step, and a last step that only retires the request
    assert [n for n, _ in fetched[2:]] == [1] * (len(fetched) - 3) + [0]
    # nothing is in flight after the step of the eleventh byte (the
    # roll-over) nor after the twelfth (the count)
    assert [i for i, (_, a) in enumerate(fetched) if a is None] == [9, 10, 11]
    assert eng._gauge_fields()["window_rollovers_total"] == 1
    assert len(eng.result(eng._result_order[-1])) == 12


def test_an_eos_ends_a_request_that_was_already_decoded_ahead(dense):
    """The step in flight decoded a row for it: written into its own blocks,
    never read; its seat goes to the next request and the bytes of both are
    those of the plain engine."""
    _, want, _ = _serve(dense, [(8, 20), (5, 20), (6, 20)], [(7, 10)],
                        decode_ahead=False)
    # an eos that really ends one early: a byte a plain answer holds midway
    eos = want[1][len(want[1]) // 2]
    _, want, _ = _serve(dense, [(8, 20), (5, 20), (6, 20)], [(7, 10)], eos=eos,
                        decode_ahead=False)
    eng, got, _ = _serve(dense, [(8, 20), (5, 20), (6, 20)], [(7, 10)], eos=eos,
                         decode_ahead=True)
    assert got == want and any(len(t) < 20 for t in got[:3])
    assert eng.pool.stats()["allocated"] == 0 and eng._ahead is None


def test_nothing_compiles_once_both_sources_of_tokens_have_run(eva):
    model, params, block, vocab = eva
    eng = ServingEngine(model, params, max_slots=3, block_size=block)
    # as the benchmark's runner warms up: two new bytes a prompt, so every
    # decode step of the warm-up takes its tokens from the host
    for width in (8, 16, 32, 64):
        eng.add_request(_ids(width - 2, width, vocab), max_new_tokens=2)
    while eng.has_work:
        eng.step()
    monitor = get_compile_monitor()
    before, traced = monitor.snapshot(), eng.trace_counts()
    for i, (p, new) in enumerate(ASKS):
        eng.add_request(_ids(p, 40 + i, vocab), max_new_tokens=new)
    while eng.has_work:
        eng.step()
    delta = monitor.delta(before)
    assert eng.trace_counts() == traced
    assert common.compiles_in(delta) == 0 and delta["compile_time_s"] == 0


@pytest.mark.parametrize("feature,kwargs", FEATURES, ids=[f for f, _ in FEATURES])
def test_what_it_is_not_written_for_is_refused_beside_it(dense, feature, kwargs):
    model, params, block, _ = dense
    with pytest.raises(NotImplementedError) as err:
        ServingEngine(model, params, block_size=block, decode_ahead=True,
                      **kwargs(model))
    assert feature in str(err.value) and "decode_ahead" in str(err.value)
    # and stands where it is off
    ServingEngine(model, params, block_size=block, decode_ahead=False,
                  **kwargs(model))


def test_the_same_is_refused_on_a_warm_engine(dense):
    model, params, block, _ = dense
    eng = ServingEngine(model, params, block_size=block, decode_ahead=True)
    for name, call in (
        ("prefix_cache", lambda: eng.set_prefix_cache(True)),
        ("spec_decode", lambda: eng.set_speculation(SpecConfig(k=2))),
        ("role 'decode'", lambda: eng.set_role("decode")),
        ("hand-off", lambda: eng.acquire(None)),
    ):
        with pytest.raises(NotImplementedError, match=name) as err:
            call()
        assert "decode_ahead" in str(err.value)
    eng.set_prefix_cache(False)
    eng.set_speculation(None)
    eng.set_role("colocated")


# ---------------------------------------------------------------------- #
# the default dense engine: what the eva cell never exercised
# ---------------------------------------------------------------------- #
def _bytes_with_late_prefills(dense, **kw):
    asks = [(p, min(new, 64 - p)) for p, new in ASKS]
    eng, got, _ = _serve(dense, asks, LATE, **kw)
    return eng, got


def _bytes_with_a_late_eos(dense, **kw):
    asks = [(8, 20), (5, 20), (6, 20)]
    _, plain, _ = _serve(dense, asks, [(7, 10)], decode_ahead=False)
    eos = plain[1][len(plain[1]) // 2]  # a byte a plain answer holds midway
    eng, got, _ = _serve(dense, asks, [(7, 10)], eos=eos, **kw)
    assert any(len(t) < 20 for t in got[:3])
    return eng, got


def _bytes_of_one_and_two(dense, **kw):
    # answers of one byte (the prefill's own) and of two (the prefill's and
    # one decoded, which ends it: in no step dispatched after that), more of
    # them than seats, beside a long one that keeps steps in flight
    asks = [(9, 24), (5, 1), (7, 2), (12, 2), (3, 1)]
    eng, got, _ = _serve(dense, asks, [(6, 1), (8, 2), (4, 2)], **kw)
    assert [len(t) for t in got] == [24, 1, 2, 2, 1, 1, 2, 2]
    return eng, got


def _bytes_of_generate(dense, **kw):
    model, params, block, vocab = dense
    eng = ServingEngine(model, params, max_slots=3, block_size=block, **kw)
    rows = np.stack([_ids(10, 70 + i, vocab) for i in range(5)])  # > seats
    out = np.asarray(eng.generate(rows, max_new_tokens=7))
    assert out.shape == (5, 17) and (out[:, :10] == rows).all()
    # and with an eos that ends some rows early: padded with it
    eos = int(out[2, 13])
    padded = np.asarray(eng.generate(rows, max_new_tokens=7, eos_token_id=eos))
    assert (padded[2, 14:] == eos).all()
    return eng, [out.tolist(), padded.tolist()]


def _bytes_over_a_mesh(dense, **kw):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    model, params, block, vocab = dense
    mesh = Mesh(np.array(jax.devices()[:2]), ("fsdp",))
    spread = jax.device_put(params, NamedSharding(mesh, P()))
    eng, got = _bytes_with_late_prefills((model, spread, block, vocab), **kw)
    assert eng._device is None  # no one device: placement is the mesh's
    return eng, got


def _bytes_behind_a_router(dense, **kw):
    model, params, block, vocab = dense
    engines = [ServingEngine(model, params, max_slots=2, block_size=block, **kw)
               for _ in range(2)]
    router = FleetRouter([InProcessReplica(f"r{i}", e)
                          for i, e in enumerate(engines)], policy="round_robin")
    rids = [router.add_request(_ids(p, 90 + i, vocab).tolist(), max_new_tokens=new)
            for i, (p, new) in enumerate([(9, 12), (5, 1), (14, 7), (6, 2),
                                          (11, 9), (4, 5)])]
    for _ in range(200):
        if not router.has_work:
            break
        router.step()
    assert not router.has_work
    assert all(e.pool.stats()["allocated"] == 0 for e in engines)
    return engines[0], [router.result(r) for r in rids]


MIXES = {
    "a_prefill_joins_mid_flight": _bytes_with_late_prefills,
    "an_eos_one_step_late": _bytes_with_a_late_eos,
    "answers_of_one_and_two": _bytes_of_one_and_two,
    "generate": _bytes_of_generate,
    "weights_over_a_mesh": _bytes_over_a_mesh,
    "replicas_of_a_router": _bytes_behind_a_router,
}


@pytest.mark.parametrize("mix", list(MIXES))
def test_a_default_dense_engine_serves_the_bytes_of_one_step_at_a_time(dense, mix):
    plain, want = MIXES[mix](dense, decode_ahead=False)
    eng, got = MIXES[mix](dense)
    assert plain.decode_ahead is False and plain._fetched_ahead == 0
    assert eng.decode_ahead is True and eng._fetched_ahead > 0
    assert got == want
    assert eng._ahead is None and eng.pool.stats()["allocated"] == 0
    # one executable each, on one device and over a mesh, whether a step's
    # tokens came from the host, from the step before, or from both
    assert eng.trace_counts()["decode"] == 1
    assert eng._decode_fn._cache_size() == 1 and eng._feed_fn._cache_size() == 1


# ---------------------------------------------------------------------- #
# landing: a default engine that a warm toggle asks for a feature
# ---------------------------------------------------------------------- #
# every prefill width the traffic after it runs, cold (32) and behind a
# cached prefix (2, 4, 8)
WARM = [(20, 14), (3, 16), (12, 2), (7, 12), (2, 3)]
# shared first blocks (a prefix cache finds them), and echoes (an n-gram
# proposer drafts from them)
SHARED = np.tile(np.arange(3, 11, dtype=np.int32), 3)


def _after(vocab):
    return [(np.concatenate([SHARED[:16], _ids(n, 80 + n, vocab)]), new)
            for n, new in ((3, 9), (5, 12), (2, 1), (4, 7), (3, 10), (6, 5),
                           (2, 8), (5, 3))]


def _serve_after(eng, vocab, on_the_way=None):
    """Warm ``eng`` up, then serve ``_after``; ``on_the_way`` is called once,
    between two steps, while the first of them are decoding."""
    for i, (p, new) in enumerate(WARM):
        eng.add_request(_ids(p, 60 + i, vocab), max_new_tokens=new)
    while eng.has_work:
        eng.step()
    rids = [eng.add_request(p, max_new_tokens=new) for p, new in _after(vocab)]
    eng.step()
    eng.step()
    if on_the_way is not None:
        on_the_way(eng)
    while eng.has_work:
        eng.step()
    return [eng.result(r) for r in rids]


@pytest.mark.parametrize("feature,built,toggle", [
    ("prefix_cache", {"prefix_cache": True},
     lambda eng: eng.set_prefix_cache(True)),
    ("spec_decode", {"spec_decode": SpecConfig(k=2)},
     lambda eng: eng.set_speculation(SpecConfig(k=2))),
])
def test_a_default_engine_lands_on_a_warm_toggle(dense, feature, built, toggle):
    """... and serves the greedy bytes of an engine built with the feature
    (and of one that takes a step at a time), compiling nothing it had."""
    model, params, block, vocab = dense
    want = _serve_after(ServingEngine(
        model, params, max_slots=3, block_size=block, **built), vocab)
    assert want == _serve_after(ServingEngine(
        model, params, max_slots=3, block_size=block, decode_ahead=False), vocab)
    eng = ServingEngine(model, params, max_slots=3, block_size=block)
    seen = {}

    def land(eng):
        assert eng.decode_ahead and eng._ahead is not None  # a step in flight
        assert eng.decode_ahead_share > 0.8
        seen["monitor"] = get_compile_monitor().snapshot()
        seen["traced"] = eng.trace_counts()
        seen["prefill"] = eng._prefill_fn._cache_size()
        toggle(eng)
        # landed: that step is still to be fetched, as it is ...
        assert eng.decode_ahead is False and eng._ahead is not None
        assert eng.decode_ahead_share == 0.0
        events = eng.step()
        # ... and none went behind it
        assert eng._ahead is None and events

    got = _serve_after(eng, vocab, land)
    assert got == want
    assert eng.pool.stats()["allocated"] == 0
    # the programs it had are the programs it has: the feed goes on handing
    # the decode program its tokens, so that one sees ONE kind of argument
    traced = eng.trace_counts()
    assert all(traced[k] == seen["traced"][k] for k in (
        "prefill", "decode", "decode_attn_kernel", "qkv_in_place"))
    assert eng._decode_fn._cache_size() == 1 and eng._feed_fn._cache_size() == 1
    assert eng._prefill_fn._cache_size() == seen["prefill"]
    if feature == "prefix_cache":
        delta = get_compile_monitor().delta(seen["monitor"])
        assert common.compiles_in(delta) == 0 and delta["compile_time_s"] == 0
        assert eng.prefix_cache.stats()["hits"] >= 2  # and the feature acts
    else:
        assert traced["verify"] == 1 and eng._spec_rounds_total > 0
    # the share of a landed engine: one step was in flight, the rest were not
    assert 0.0 < eng.decode_ahead_share < 0.2
    # turning the feature off again does not take off
    eng.set_prefix_cache(False)
    eng.set_speculation(None)
    assert eng.decode_ahead is False


def test_a_default_engine_lands_on_a_hand_off_and_on_a_role(dense):
    from accelerate_tpu.serving import TransferPlane

    model, params, block, vocab = dense
    asks = [(_ids(12, 31, vocab), 9), (_ids(16, 32, vocab), 6)]
    own = (_ids(7, 33, vocab), 14)

    def colocated(**kw):
        eng = ServingEngine(model, params, max_slots=3, block_size=block, **kw)
        rids = [eng.add_request(p, max_new_tokens=n, request_id=f"r{i}")
                for i, (p, n) in enumerate([own] + asks)]
        while eng.has_work:
            eng.step()
        return [eng.result(r) for r in rids]

    want = colocated(decode_ahead=False)
    assert colocated() == want
    plane = TransferPlane("inprocess")
    pre = ServingEngine(model, params, max_slots=2, block_size=block,
                        role="prefill", transfer_plane=plane)
    dec = ServingEngine(model, params, max_slots=3, block_size=block,
                        transfer_plane=plane)
    assert pre.decode_ahead is False and dec.decode_ahead is True
    dec.add_request(own[0], max_new_tokens=own[1], request_id="r0")
    dec.step()
    dec.step()
    assert dec._ahead is not None
    for i, (p, n) in enumerate(asks):
        pre.add_request(p, max_new_tokens=n, request_id=f"r{i + 1}")
    while pre.has_work or dec.has_work:
        pre.step()
        for m in pre.pop_manifests():
            assert dec.acquire(m)["seated"]
            assert dec.decode_ahead is False  # landed, by the first of them
        dec.step()
    assert [dec.result(f"r{i}") for i in range(3)] == want
    assert dec.trace_counts()["prefill"] == 1 and dec.trace_counts()["decode"] == 1
    assert dec._decode_fn._cache_size() == 1
    # a role: engines primed colocated (they decoded ahead), then assigned
    primed = []
    for role in ("prefill", "decode"):
        eng = ServingEngine(model, params, max_slots=3, block_size=block,
                            transfer_plane=plane)
        eng.add_request(own[0], max_new_tokens=own[1], request_id="r0")
        while eng.has_work:
            eng.step()
        assert eng.result("r0") == want[0] and eng.decode_ahead_share > 0.8
        eng.set_role(role)
        assert eng.decode_ahead is False and eng.decode_ahead_share == 0.0
        primed.append(eng)
    pre, dec = primed
    for i, (p, n) in enumerate(asks):
        pre.add_request(p, max_new_tokens=n, request_id=f"r{i + 1}")
    while pre.has_work or dec.has_work:
        pre.step()
        for m in pre.pop_manifests():
            dec.acquire(m)
        dec.step()
        assert pre._ahead is None and dec._ahead is None
    assert [dec.result(f"r{i}") for i in range(3)] == want
    assert dec._decode_fn._cache_size() == 1 and dec._feed_fn._cache_size() == 1


# ---------------------------------------------------------------------- #
# the engage counter
# ---------------------------------------------------------------------- #
def test_the_share_of_steps_decoded_ahead(dense):
    class Collector:
        def __init__(self):
            self.gauges = []

        def record_serve_gauge(self, **fields):
            self.gauges.append(fields)

    model, params, block, vocab = dense
    collector = Collector()
    eng = ServingEngine(model, params, max_slots=3, block_size=block,
                        telemetry=collector)
    for i in range(3):  # every seat taken, and held: a saturated engine
        eng.add_request(_ids(6 + i, i, vocab), max_new_tokens=30)
    eng.step()
    # the first step dispatched, waited for and fetched its own decode step
    assert (eng._fetched, eng._fetched_ahead) == (1, 0)
    assert eng.decode_ahead_share == 0.0
    for _ in range(12):
        eng.step()
        assert eng._ahead is not None
    # every step since was on the device before its step() began
    assert (eng._fetched, eng._fetched_ahead) == (13, 12)
    assert eng.decode_ahead_share == 12 / 13
    # in the gauges record, beside pool_alias_bytes, a record a step
    shares = [g["decode_ahead_share"] for g in collector.gauges]
    assert shares == [n / (n + 1) for n in range(13)]
    assert list(collector.gauges[-1])[-2:] == [
        "pool_alias_bytes", "decode_ahead_share"]
    # and with no telemetry attached it reads the same
    assert eng._gauge_fields()["decode_ahead_share"] == eng.decode_ahead_share
    eng.set_prefix_cache(True)
    assert eng.decode_ahead_share == 0.0  # landed: counted anew
    eng.step()  # the step that was in flight
    while eng.has_work:
        eng.step()
    assert (eng._fetched, eng._fetched_ahead) == (30 - 1 - 13, 1)
    one = ServingEngine(model, params, max_slots=3, block_size=block,
                        decode_ahead=False)
    one.add_request(_ids(6, 0, vocab), max_new_tokens=8)
    while one.has_work:
        one.step()
    assert one.decode_ahead_share == 0.0 and one._fetched == 7

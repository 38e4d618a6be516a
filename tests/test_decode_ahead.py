"""``ServingEngine(decode_ahead=True)``: decode step k + 1 goes to the device
before step k's tokens are fetched, fed from step k's output on the device.

Held here: greedy bytes are those of the engine that takes one step at a time
(dense and eva, with admissions while a step is in flight, answers that end by
their count, by an eos, and across a window's end); the step ahead really is
in flight when ``step()`` returns; one decode program and one feed program
whatever the source of a step's tokens; the pool comes back whole; and every
feature the lookahead is not written for is refused beside it, by name.
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


def test_the_default_is_on_for_eva_and_off_elsewhere(dense, eva):
    assert ServingEngine(eva[0], eva[1], block_size=4).decode_ahead is True
    assert ServingEngine(dense[0], dense[1], block_size=8).decode_ahead is False


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


@pytest.mark.parametrize("feature,kwargs", [
    ("prefix_cache", {"prefix_cache": True}),
    ("spec_decode", {"spec_decode": SpecConfig(k=2)}),
    ("prefill_chunk_tokens", {"prefill_chunk_tokens": 16}),
    ("preemption", {"preemption": True}),
    ("role 'prefill'", {"role": "prefill"}),
    ("role 'decode'", {"role": "decode"}),
])
def test_what_it_is_not_written_for_is_refused_beside_it(dense, feature, kwargs):
    model, params, block, _ = dense
    with pytest.raises(NotImplementedError) as err:
        ServingEngine(model, params, block_size=block, decode_ahead=True, **kwargs)
    assert feature in str(err.value) and "decode_ahead" in str(err.value)
    # and stands where it is off
    ServingEngine(model, params, block_size=block, **kwargs)


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

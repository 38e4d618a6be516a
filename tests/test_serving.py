"""Serving engine tests: paged KV cache + continuous batching.

Three layers, matching the subsystem's split: the host-side block
allocator (pure policy, no jax), the paged attention math (must equal
the dense cache path — paging is layout, not math), and the engine's
step loop (admit/evict scheduling, EOS slot refill, and the
zero-retrace-after-warmup contract the trace counters pin).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import CausalLM, TransformerConfig
from accelerate_tpu.models.generation import generate
from accelerate_tpu.ops.attention import (
    PagedKVState,
    paged_attention,
    paged_update,
    xla_attention,
)
from accelerate_tpu.serving import (
    BlockPool,
    ContinuousScheduler,
    Request,
    ServingEngine,
)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = TransformerConfig.tiny(max_seq_len=64)
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]
    return cfg, model, params


# ---------------------------------------------------------------------- #
# block pool
# ---------------------------------------------------------------------- #
def test_block_pool_never_hands_out_garbage_block():
    pool = BlockPool(num_blocks=8, block_size=4)
    blocks = pool.allocate(7)  # everything allocatable
    assert 0 not in blocks
    assert sorted(blocks) == list(range(1, 8))
    assert pool.num_free == 0


def test_block_pool_alloc_free_roundtrip_and_reuse():
    pool = BlockPool(num_blocks=10, block_size=4)
    a = pool.allocate(3)
    b = pool.allocate(2)
    assert pool.num_allocated == 5 and pool.num_free == 4
    pool.free(a)
    # freed blocks are immediately reusable; the pool never leaks
    c = pool.allocate(4)
    assert set(c) & set(a)  # reuse really happened
    assert pool.num_allocated == 6
    pool.free(b)
    pool.free(c)
    assert pool.num_free == 9 and pool.num_allocated == 0
    assert pool.stats()["utilization"] == 0.0


def test_block_pool_fragmentation_is_free():
    """Block indirection means non-contiguous free blocks are as good as
    contiguous ones: free every other allocation and a full-size request
    still fits."""
    pool = BlockPool(num_blocks=17, block_size=4)
    held = [pool.allocate(2) for _ in range(8)]
    for blocks in held[::2]:
        pool.free(blocks)
    assert pool.num_free == 8
    assert pool.can_allocate(8)
    scattered = pool.allocate(8)  # interleaved ids, not a contiguous run
    assert len(set(scattered)) == 8
    assert pool.num_free == 0


def test_block_pool_rejects_double_free_and_exhaustion():
    pool = BlockPool(num_blocks=4, block_size=2)
    blocks = pool.allocate(2)
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.allocate(2)
    pool.free(blocks)
    with pytest.raises(ValueError, match="not allocated"):
        pool.free(blocks)
    with pytest.raises(ValueError, match="num_blocks"):
        BlockPool(num_blocks=1, block_size=2)


def test_blocks_for_tokens_sizing_formula():
    pool = BlockPool(num_blocks=8, block_size=16)
    assert pool.blocks_for_tokens(0) == 0
    assert pool.blocks_for_tokens(1) == 1
    assert pool.blocks_for_tokens(16) == 1
    assert pool.blocks_for_tokens(17) == 2
    assert pool.blocks_for_tokens(33) == 3


# ---------------------------------------------------------------------- #
# paged attention numerics
# ---------------------------------------------------------------------- #
def test_paged_attention_matches_dense_attention():
    """Writing K/V through the block table and attending through the
    gathered pool must reproduce plain causal attention bit-for-near-bit:
    paging is an addressing scheme, not an approximation."""
    rng = np.random.default_rng(0)
    heads, head_dim, block_size, num_blocks = 4, 16, 8, 12
    seq = 21  # deliberately not a multiple of block_size
    max_table = 4
    q = jnp.asarray(rng.standard_normal((1, seq, heads, head_dim)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, seq, heads, head_dim)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, seq, heads, head_dim)), jnp.float32)

    key_pool = jnp.zeros((num_blocks, block_size, heads, head_dim), jnp.float32)
    value_pool = jnp.zeros_like(key_pool)
    state = PagedKVState(
        block_table=jnp.asarray([[5, 2, 9, 7]], jnp.int32),  # scattered
        cache_len=jnp.zeros((1,), jnp.int32),
        lengths=jnp.asarray([seq], jnp.int32),
        num_blocks=num_blocks,
        block_size=block_size,
    )
    key_pool, value_pool = paged_update(key_pool, value_pool, k, v, state)
    paged = paged_attention(q, key_pool, value_pool, state)

    dense = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(paged[:, :seq]), np.asarray(dense), rtol=2e-5, atol=2e-5
    )


def test_paged_update_routes_padding_to_garbage_block():
    """Rows past ``lengths`` (bucket padding) must land in block 0 and
    leave every real block untouched."""
    heads, head_dim, block_size, num_blocks = 2, 4, 4, 6
    key_pool = jnp.zeros((num_blocks, block_size, heads, head_dim), jnp.float32)
    value_pool = jnp.zeros_like(key_pool)
    k = jnp.ones((1, 8, heads, head_dim), jnp.float32)
    state = PagedKVState(
        block_table=jnp.asarray([[3, 0, 0]], jnp.int32),
        cache_len=jnp.zeros((1,), jnp.int32),
        lengths=jnp.asarray([3], jnp.int32),  # only 3 of the 8 rows valid
        num_blocks=num_blocks,
        block_size=block_size,
    )
    key_pool, _ = paged_update(key_pool, value_pool, k, k, state)
    out = np.asarray(key_pool)
    assert out[3, :3].sum() > 0          # the 3 valid rows landed
    assert out[3, 3:].sum() == 0          # nothing past the valid length
    assert out[[1, 2, 4, 5]].sum() == 0   # no other block touched
    # garbage block absorbed the padding writes — that is its job
    assert out[0].sum() > 0


def test_paged_generate_matches_dense_generate(tiny_model):
    """Engine greedy decode == the dense-cache ``generate`` path, token
    for token, across mixed prompt lengths and slot churn."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(0)
    engine = ServingEngine(model, params, max_slots=2, block_size=8)
    for p_len in (3, 8, 13):
        prompt = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (2, p_len)), jnp.int32
        )
        want = generate(model, params, prompt, max_new_tokens=6)
        got = engine.generate(prompt, max_new_tokens=6)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------- #
# scheduler (fake clock)
# ---------------------------------------------------------------------- #
class FakeClock:
    def __init__(self):
        self.t = 0.0

    def tick(self, dt: float = 1.0) -> None:
        self.t += dt

    def __call__(self) -> float:
        return self.t


def test_scheduler_admits_in_fifo_order_within_capacity():
    clock = FakeClock()
    pool = BlockPool(num_blocks=9, block_size=4)  # 8 allocatable
    sched = ContinuousScheduler(max_slots=2, pool=pool, now=clock)
    ids = [
        sched.submit(Request(prompt=[1] * 4, max_new_tokens=4))
        for _ in range(3)
    ]
    clock.tick()
    admitted = sched.admit()  # 2 slots, 2 blocks each -> first two fit
    assert [s.request.request_id for s in admitted] == ids[:2]
    assert all(s.admit_time == 1.0 for s in admitted)
    assert all(s.request.submit_time == 0.0 for s in admitted)
    assert len(sched.queue) == 1
    assert sched.admit() == []  # no free seat for the third
    # retire one: its seat AND blocks refill the head of the queue
    clock.tick()
    sched.release(admitted[0])
    refill = sched.admit()
    assert [s.request.request_id for s in refill] == [ids[2]]
    assert refill[0].admit_time == 2.0


def test_scheduler_head_of_queue_blocks_until_pool_can_fund_it():
    """Strict FIFO: a big head request that doesn't fit must wait for
    blocks, and must NOT be overtaken by a small later request."""
    clock = FakeClock()
    pool = BlockPool(num_blocks=7, block_size=4)  # 6 allocatable
    sched = ContinuousScheduler(max_slots=3, pool=pool, now=clock)
    big = sched.submit(Request(prompt=[1] * 16, max_new_tokens=4))  # 5 blocks
    (slot,) = sched.admit()
    assert slot.request.request_id == big
    big2 = sched.submit(Request(prompt=[1] * 8, max_new_tokens=4))  # 3 blocks
    small = sched.submit(Request(prompt=[1] * 2, max_new_tokens=2))  # 1 block
    assert sched.admit() == []  # 1 block free < 3: head stalls, small waits
    sched.release(slot)
    admitted = sched.admit()  # both fit now, in order
    assert [s.request.request_id for s in admitted] == [big2, small]


def test_scheduler_rejects_request_larger_than_pool():
    pool = BlockPool(num_blocks=4, block_size=4)  # 12 tokens max
    sched = ContinuousScheduler(max_slots=1, pool=pool)
    with pytest.raises(ValueError, match="allocatable blocks"):
        sched.submit(Request(prompt=[1] * 16, max_new_tokens=8))


def test_engine_queue_and_latency_accounting_with_fake_clock(tiny_model):
    """With max_slots=1 the second request waits a full generation in the
    queue; the injectable clock makes queue_s/e2e_s exact."""
    cfg, model, params = tiny_model
    clock = FakeClock()
    engine = ServingEngine(
        model, params, max_slots=1, block_size=8, now=clock
    )
    r1 = engine.add_request([1, 2, 3], max_new_tokens=3)
    r2 = engine.add_request([4, 5], max_new_tokens=2)
    while engine.has_work:
        engine.step()
        clock.tick()
    recs = {r["request_id"]: r for r in engine.stats.requests}
    assert recs[r1]["queue_s"] == 0.0
    # r1 holds the only slot for its whole generation; r2's queue time is
    # the ticks that elapsed before its admission
    assert recs[r2]["queue_s"] > 0.0
    assert recs[r2]["e2e_s"] >= recs[r2]["queue_s"]
    assert recs[r1]["new_tokens"] == 3 and recs[r2]["new_tokens"] == 2


# ---------------------------------------------------------------------- #
# engine: EOS refill + zero retrace
# ---------------------------------------------------------------------- #
def test_eos_slot_refill_completes_all_requests(tiny_model):
    """EOS-finished slots must free mid-flight and their seats refill
    from the queue: more requests than slots all complete, short ones
    never wait out a long neighbour's budget."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(2)
    engine = ServingEngine(model, params, max_slots=2, block_size=8)
    # discover what greedy emits first for this prompt, use it as EOS so
    # the request finishes on its first decode step
    probe = rng.integers(0, cfg.vocab_size, (4,)).tolist()
    eid = engine.add_request(probe, max_new_tokens=2)
    for _ in engine.stream():
        pass
    eos = engine.result(eid)[1]

    ids = []
    budgets = {}
    for i in range(5):
        prompt = rng.integers(0, cfg.vocab_size, (3 + i,)).tolist()
        n = 8 if i % 2 else 3
        rid = engine.add_request(
            probe if i == 2 else prompt, max_new_tokens=n,
            eos_token_id=eos if i == 2 else None,
        )
        ids.append(rid)
        budgets[rid] = n
    events = list(engine.stream())
    for rid in ids:
        out = engine.result(rid)
        assert out is not None
        if rid == ids[2]:
            assert out[-1] == eos and len(out) <= budgets[rid]
        else:
            assert len(out) == budgets[rid]
    assert sum(e.done for e in events) == 5
    # every seat emptied, every block returned
    assert engine.pool.stats()["allocated"] == 0
    assert not engine.scheduler.has_work


def test_engine_lives_where_its_weights_do(tiny_model):
    """A fleet puts one engine per chip: the KV pool and every per-step
    host put must land on the device that holds the engine's weights —
    never first on device 0. Same tokens as an engine on device 0."""
    cfg, model, params = tiny_model
    home = jax.devices()[3]
    there = ServingEngine(model, jax.device_put(params, home),
                          max_slots=2, block_size=8)
    here = ServingEngine(model, params, max_slots=2, block_size=8)
    on = lambda tree: {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}
    assert on(there.cache) == {home}  # allocated there, not moved there
    outs = []
    for eng in (there, here):
        rid = eng.add_request([5, 9, 2, 7, 1], max_new_tokens=3)
        for _ in eng.stream():
            pass
        outs.append(eng.result(rid))
    assert outs[0] == outs[1] and len(outs[0]) == 3
    assert on(there.cache) == {home} and on(there.params) == {home}
    assert on(here.cache) == {jax.devices()[0]}


def test_zero_decode_retrace_after_warmup(tiny_model):
    """The decode step must compile exactly ONCE: admissions, evictions,
    mixed depths and temperatures are all traced data. Prefill stays
    within the power-of-two bucket budget."""
    import math

    cfg, model, params = tiny_model
    rng = np.random.default_rng(3)
    engine = ServingEngine(model, params, max_slots=3, block_size=8)
    # warmup: one short request compiles one bucket + the decode step
    engine.add_request([1, 2, 3], max_new_tokens=2)
    for _ in engine.stream():
        pass
    assert engine.trace_counts()["decode"] == 1
    # storm: mixed lengths, budgets, temperatures, churn through slots
    for i in range(8):
        prompt = rng.integers(0, cfg.vocab_size, (2 + 3 * i % 17,)).tolist()
        engine.add_request(
            prompt, max_new_tokens=1 + i % 5, temperature=0.5 * (i % 2)
        )
    for _ in engine.stream():
        pass
    counts = engine.trace_counts()
    assert counts["decode"] == 1, "decode step retraced after warmup"
    assert counts["prefill"] <= int(math.log2(cfg.max_seq_len))

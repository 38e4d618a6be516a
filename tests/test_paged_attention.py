"""Decode-time paged attention reads each live K/V byte once (PR 25).

Two forms of one algorithm, one oracle each:

* ``xla_attention`` contracts per KV-head group wherever a query block
  meets a longer cache — held against a reference written HERE with the
  GQA repeat materialised, values and gradients;
* the Pallas ``paged_decode`` kernel walks live blocks only — held
  against the gather form of ``paged_attention`` over ragged lengths,
  scattered and shared tables, an idle slot, windows and soft-capping;

then the engine: the kernel and the gather form give the same greedy
tokens, the trace counts say which ran, and ``live_block_share`` is the
hand count. Kernels run under ``kernel_interpret_mode()`` (CPU); the one
Mosaic compile at the benchmark's widths needs no chip either.
"""

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from accelerate_tpu.models import CausalLM, TransformerConfig
from accelerate_tpu.ops.attention import (
    PagedKVState,
    decode_kernel_eligible,
    paged_attention,
    xla_attention,
)
from accelerate_tpu.ops.flash_attention import kernel_interpret_mode
from accelerate_tpu.ops import paged_attention as paged_attention_kernel
from accelerate_tpu.serving import ServingEngine


# ---------------------------------------------------------------------- #
# (a) the grouped contraction against the materialised repeat
# ---------------------------------------------------------------------- #
def _repeated_reference(q, k, v, mask=None, causal=False, kv_lengths=None,
                        window=None, softcap=None):
    """Attention with K and V repeated to H heads first: what
    ``xla_attention`` computed until PR 25."""
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s_q, s_kv = q.shape[1], k.shape[1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    logits = logits * q.shape[-1] ** -0.5
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    keep = jnp.ones((1, 1, s_q, s_kv), bool)
    rows = jnp.arange(s_q)[:, None] + (s_kv - s_q)
    cols = jnp.arange(s_kv)[None, :]
    if causal:
        keep = keep & (cols <= rows)
    if window is not None:
        keep = keep & (cols > rows - window)
    if kv_lengths is not None:
        keep = keep & (cols[None, None] < kv_lengths[:, None, None, None])
    if mask is not None:
        keep = keep & mask
    logits = jnp.where(keep, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


_MODES = {
    "causal": dict(causal=True),
    "dense_mask": dict(mask="per_head"),
    "kv_lengths": dict(kv_lengths=True),
    "static_window": dict(causal=True, window=3),
    "softcap": dict(causal=True, softcap=2.0),
}


# a query block against a longer cache takes the grouped contraction;
# self-attention (S == Skv) keeps the repeat: both are held to the oracle
@pytest.mark.parametrize("s_q", [5, 7], ids=["cache", "self"])
@pytest.mark.parametrize("mode", sorted(_MODES))
@pytest.mark.parametrize("group", [1, 4, 8])
def test_grouped_xla_attention_matches_materialised_repeat(group, mode, s_q):
    rng = np.random.default_rng(group)
    b, s_kv, h_kv, d = 2, 7, 2, 8
    h = h_kv * group
    q = jnp.asarray(rng.standard_normal((b, s_q, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s_kv, h_kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s_kv, h_kv, d)), jnp.float32)
    kw = dict(_MODES[mode])
    if kw.get("mask"):
        # a different pattern per QUERY head: the split over (Hkv, G)
        # must keep head j's own mask
        kw["mask"] = jnp.asarray(rng.random((b, h, s_q, s_kv)) > 0.3)
        kw["mask"] = kw["mask"].at[..., 0].set(True)
    if kw.get("kv_lengths"):
        kw["kv_lengths"] = jnp.asarray([s_kv, 3], jnp.int32)

    def loss(f):
        return lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v, **kw)))

    got = xla_attention(q, k, v, **kw)
    want = _repeated_reference(q, k, v, **kw)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)
    got_g = jax.grad(loss(xla_attention), argnums=(0, 1, 2))(q, k, v)
    want_g = jax.grad(loss(_repeated_reference), argnums=(0, 1, 2))(q, k, v)
    for a, e in zip(got_g, want_g):
        np.testing.assert_allclose(a, e, atol=5e-6, rtol=5e-6)


def test_grouped_xla_attention_broadcasts_bias_and_low_rank_masks():
    rng = np.random.default_rng(0)
    b, s, s_kv, h, h_kv, d = 2, 4, 6, 8, 2, 8
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s_kv, h_kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s_kv, h_kv, d)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal((1, h, s, s_kv)), jnp.float32)
    mask2d = jnp.tril(jnp.ones((s, s_kv), bool), k=s_kv - s)
    got = xla_attention(q, k, v, mask=mask2d, bias=bias)
    rep = lambda x: jnp.repeat(x, h // h_kv, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, rep(k)) * d ** -0.5 + bias
    logits = jnp.where(mask2d, logits, jnp.finfo(jnp.float32).min)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), rep(v))
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)


# ---------------------------------------------------------------------- #
# (b) the kernel against the gather form
# ---------------------------------------------------------------------- #
BS, MAX_BLOCKS, HKV, D = 8, 6, 2, 128
FULL = BS * MAX_BLOCKS - 1
# 0, 1, block_size - 1, block_size, mid-block, the full table
RAGGED = (0, 1, BS - 1, BS, 2 * BS + 3, FULL)


@pytest.fixture
def two_block_chunks(monkeypatch):
    """Two blocks a chunk instead of a table's worth: the six-block table
    is then three steps of the walk, so the online softmax carries across
    chunks, slots end mid-chunk and the copies run ahead ACROSS slots."""
    monkeypatch.setattr(paged_attention_kernel, "CHUNK_ROWS", 2 * BS * HKV)


def _pools(rng, num_blocks, dtype):
    shape = (num_blocks, BS, HKV, D)
    return (jnp.asarray(rng.standard_normal(shape), dtype),
            jnp.asarray(rng.standard_normal(shape), dtype))


def _state(table, cache_len, lengths, num_blocks, single_device):
    return PagedKVState(
        block_table=jnp.asarray(table, jnp.int32),
        cache_len=jnp.asarray(cache_len, jnp.int32),
        lengths=jnp.asarray(lengths, jnp.int32),
        num_blocks=num_blocks, block_size=BS, single_device=single_device,
    )


def _scattered_table(rng, cache_len, num_blocks):
    """Each slot's live blocks drawn from a shuffled pool, in no order;
    dead tail entries point at garbage block 0, as the engine's do."""
    ids = list(rng.permutation(np.arange(1, num_blocks)))
    table = np.zeros((len(cache_len), MAX_BLOCKS), np.int32)
    for b, n in enumerate(cache_len):
        for t in range(n // BS + 1):
            table[b, t] = ids.pop()
    return table


def _both_forms(q, kp, vp, table, cache_len, lengths, **kw):
    """(kernel, gather) through ``paged_attention``'s own routing: the two
    states differ in the one static field the engine sets."""
    n = kp.shape[0]
    with kernel_interpret_mode():
        kernel_state = _state(table, cache_len, lengths, n, True)
        assert decode_kernel_eligible(kernel_state, 1, kp)
        jaxpr = jax.make_jaxpr(
            lambda q: paged_attention(q, kp, vp, kernel_state, **kw))(q)
        assert "pallas_call" in str(jaxpr)
        got = jax.jit(
            lambda q, kp, vp: paged_attention(q, kp, vp, kernel_state, **kw)
        )(q, kp, vp)
        gather_state = _state(table, cache_len, lengths, n, False)
        assert not decode_kernel_eligible(gather_state, 1, kp)
        want = paged_attention(q, kp, vp, gather_state, **kw)
    return got, want


def _tolerance(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(
        atol=3e-6, rtol=3e-6)


_WINDOWS = {"none": None, "static": 11, "traced": "traced"}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
@pytest.mark.parametrize("softcap", [None, 4.0], ids=["nocap", "softcap"])
@pytest.mark.parametrize("window", sorted(_WINDOWS))
@pytest.mark.parametrize("group", [1, 4])
def test_decode_kernel_matches_gather_over_ragged_lengths(
        group, window, softcap, dtype, two_block_chunks):
    rng = np.random.default_rng(7)
    num_blocks = len(RAGGED) * MAX_BLOCKS + 1
    kp, vp = _pools(rng, num_blocks, dtype)
    q = jnp.asarray(
        rng.standard_normal((len(RAGGED), 1, HKV * group, D)), dtype)
    table = _scattered_table(rng, RAGGED, num_blocks)
    win = _WINDOWS[window]
    if win == "traced":
        # the per-layer Gemma-2 pattern: the band rides a scan as data
        def run(form_window):
            return _both_forms(q, kp, vp, table, RAGGED, [1] * len(RAGGED),
                               softcap=softcap, window=form_window)
        got, want = jax.jit(run)(jnp.asarray(5, jnp.int32))
    else:
        got, want = _both_forms(q, kp, vp, table, RAGGED, [1] * len(RAGGED),
                                softcap=softcap, window=win)
    assert got.shape == want.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tolerance(dtype))


@pytest.mark.parametrize("group", [1, 4])
def test_decode_kernel_shared_blocks_and_idle_slot(group, two_block_chunks):
    """Two slots read the SAME prefix blocks (prefix sharing before any
    copy-on-write) and diverge in their last block; a third slot is idle
    (``lengths == 0``, table all garbage): it costs one block and leaves
    the others alone."""
    rng = np.random.default_rng(11)
    num_blocks = 12
    kp, vp = _pools(rng, num_blocks, jnp.float32)
    q = jnp.asarray(rng.standard_normal((3, 1, HKV * group, D)), jnp.float32)
    table = np.zeros((3, MAX_BLOCKS), np.int32)
    table[0, :3] = [5, 9, 2]
    table[1, :3] = [5, 9, 7]
    cache_len, lengths = [2 * BS + 1, 2 * BS + 6, 0], [1, 1, 0]
    got, want = _both_forms(q, kp, vp, table, cache_len, lengths)
    np.testing.assert_allclose(got, want, atol=3e-6, rtol=3e-6)
    assert np.isfinite(np.asarray(got)).all()
    # the shared prefix is the same bytes for both: give slot 1 slot 0's
    # query and private block and it must give slot 0's answer
    table[1, 2], cache_len[1] = 2, cache_len[0]
    q = q.at[1].set(q[0])
    got, _ = _both_forms(q, kp, vp, table, cache_len, lengths)
    np.testing.assert_array_equal(got[0], got[1])


def test_decode_kernel_never_reads_beyond_the_live_length(two_block_chunks):
    """Poison every block the mask rule hides (NaN): dead table entries
    and the blocks below a sliding band. The walk must not touch them —
    a copy of one would put NaN x 0 into P @ V."""
    rng = np.random.default_rng(3)
    cache_len = [BS + 2, 4 * BS + 1]
    num_blocks = 2 * MAX_BLOCKS + 1
    kp, vp = _pools(rng, num_blocks, jnp.float32)
    q = jnp.asarray(rng.standard_normal((2, 1, HKV * 4, D)), jnp.float32)
    table = np.arange(1, num_blocks).reshape(2, MAX_BLOCKS)
    window = BS  # slot 1 sees positions 3*BS+2 .. 4*BS+1: blocks 3 and 4
    dead = [int(x) for x in table[0, 2:]] + [int(x) for x in table[1, 5:]]
    below_band = [int(x) for x in table[1, :3]]
    poison = jnp.asarray(dead + below_band)
    kp_bad = kp.at[poison].set(jnp.nan)
    vp_bad = vp.at[poison].set(jnp.nan)
    with kernel_interpret_mode():
        clean = paged_attention_kernel.paged_decode_attention(
            q, kp, vp, jnp.asarray(table, jnp.int32),
            jnp.asarray(cache_len, jnp.int32), window=window)
        got = paged_attention_kernel.paged_decode_attention(
            q, kp_bad, vp_bad, jnp.asarray(table, jnp.int32),
            jnp.asarray(cache_len, jnp.int32), window=window)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(got, clean)


_KERNEL_POOL = jax.ShapeDtypeStruct((8, BS, HKV, D), jnp.bfloat16)
# why -> (changes to the eligible state, q_len, pool, inside the interpreter)
_GATHER_CASES = {
    "prefill_shape": ({}, 4, _KERNEL_POOL, True),
    "int8_pool": ({"kv_dtype": "int8"}, 1, _KERNEL_POOL, True),
    "sharded_pool": ({"single_device": False}, 1, _KERNEL_POOL, True),
    "head_dim_64": (
        {}, 1, jax.ShapeDtypeStruct((8, BS, HKV, 64), jnp.bfloat16), True),
    "half_a_sublane_tile": (
        {}, 1, jax.ShapeDtypeStruct((8, BS, 1, D), jnp.bfloat16), True),
    "no_tpu_no_interpreter": ({}, 1, _KERNEL_POOL, False),
}


@pytest.mark.parametrize("why", sorted(_GATHER_CASES))
def test_everything_else_takes_the_gather_form(why):
    changes, q_len, pool, interpreted = _GATHER_CASES[why]
    eligible = _state(np.zeros((1, MAX_BLOCKS)), [0], [1], 8, True)
    with kernel_interpret_mode():
        assert decode_kernel_eligible(eligible, 1, _KERNEL_POOL)
    state = dataclasses.replace(eligible, **changes)
    with kernel_interpret_mode() if interpreted else contextlib.nullcontext():
        assert not decode_kernel_eligible(state, q_len, pool)


# ---------------------------------------------------------------------- #
# the Mosaic compile at the benchmark's widths, for a described chip
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


def test_decode_kernel_compiles_for_v5e_with_no_copy_of_the_pools(one_chip):
    """16 slots x 32/8 heads x D 128 over a 1025-block bf16 pool, as the
    serve cells run it: Mosaic accepts the kernel, and the pools reach it
    as bitcasts — no relayout, no temporaries."""
    from jax.experimental.compilation_cache import compilation_cache

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((1025, 16, 8, 128), jnp.bfloat16)
    args = (sds((16, 1, 32, 128), jnp.bfloat16), pool, pool,
            sds((16, 64), jnp.int32), sds((16,), jnp.int32))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(
            lambda *a: paged_attention_kernel.paged_decode_attention(
                *a, window=4096)
        ).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert " copy(" not in text and "transpose(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes == 0


# ---------------------------------------------------------------------- #
# (c) the engine: same tokens, and the counts say which form ran
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def head128_model():
    # head_dim 128: the smallest model whose decode is kernel-eligible
    cfg = TransformerConfig.tiny(
        hidden_size=256, num_heads=2, num_kv_heads=1, max_seq_len=128)
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
        "params"]
    return cfg, model, params


def _serve(model, params, cfg, prompts, new_tokens):
    engine = ServingEngine(model, params, max_slots=3, block_size=8)
    ids = [engine.add_request(p, max_new_tokens=new_tokens) for p in prompts]
    steps = 0
    with kernel_interpret_mode():
        while engine.has_work:
            engine.step()
            steps += 1
    return [engine.result(i) for i in ids], engine.trace_counts(), steps


def test_engine_kernel_and_gather_forms_serve_the_same_tokens(head128_model):
    cfg, model, params = head128_model
    rng = np.random.default_rng(5)
    prompts = [list(map(int, rng.integers(1, cfg.vocab_size, n)))
               for n in (5, 9, 17, 3)]
    on_one = jax.device_put(params, jax.devices()[0])
    got, counts, steps = _serve(model, on_one, cfg, prompts, 66)
    assert steps >= 64
    assert counts["decode"] == 1 and counts["decode_attn_kernel"] == 1

    # the same weights as a sharded engine holds them: over a mesh. The
    # engine cannot say "one device" any more, so the SAME interpreter
    # context lands on the gather form.
    mesh = Mesh(np.array(jax.devices()[:2]), ("fsdp",))
    spread = jax.device_put(params, NamedSharding(mesh, P()))
    want, counts, _ = _serve(model, spread, cfg, prompts, 66)
    assert counts["decode"] == 1 and counts["decode_attn_kernel"] == 0
    assert got == want
    assert all(len(tokens) == 66 for tokens in got)


# ---------------------------------------------------------------------- #
# (d) live_block_share is the hand count
# ---------------------------------------------------------------------- #
def test_live_block_share_is_the_hand_count(head128_model):
    cfg, model, params = head128_model
    engine = ServingEngine(model, params, max_slots=4, block_size=8)
    assert engine._gauge_fields()["live_block_share"] == 0.0
    for n in (3, 8, 21):
        engine.add_request(list(range(1, n + 1)), max_new_tokens=40)
    engine.step()  # three prefills + one decode step: cache_len = n + 1
    lens = sorted(s.cache_len for s in engine.scheduler.slots if s.busy)
    assert lens == [4, 9, 22]
    # blocks holding positions 0..cache_len: 1, 2 and 3 of 16 per slot
    max_blocks = cfg.max_seq_len // 8
    share = engine._gauge_fields()["live_block_share"]
    assert share == (1 + 2 + 3) / (4 * max_blocks)
    while engine.has_work:
        engine.step()
    assert engine._gauge_fields()["live_block_share"] == 0.0

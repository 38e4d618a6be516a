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


def _compiled_for_the_chip(lowered):
    """Compiled with the persistent cache off: an entry written for a
    described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_decode_kernel_compiles_for_v5e_with_no_copy_of_the_pools(one_chip):
    """16 slots x 32/8 heads x D 128 over a 1025-block bf16 pool, as the
    serve cells run it: Mosaic accepts the kernel, and the pools reach it
    as bitcasts — no relayout, no temporaries."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((1025, 16, 8, 128), jnp.bfloat16)
    args = (sds((16, 1, 32, 128), jnp.bfloat16), pool, pool,
            sds((16, 64), jnp.int32), sds((16,), jnp.int32))
    compiled = _compiled_for_the_chip(jax.jit(
        lambda *a: paged_attention_kernel.paged_decode_attention(
            *a, window=4096)
    ).lower(*args))
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert " copy(" not in text and "transpose(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes == 0


# a fresh prefill's write at the shapes of the three cells that make one:
# (KV heads, row width, slots, ring) over 16,384 positions a slot in blocks
# of 16 — SmallThinker's full and window layers, Qwen3-Next's attention
# layer, DeepSeek-V3's latent row
_FRESH_WRITES = {"full-4x128": (4, 128, 32, 0), "ring-4x128": (4, 128, 32, 4096),
                 "full-2x256": (2, 256, 64, 0), "latent-640": (0, 640, 32, 0)}


@pytest.mark.parametrize("case", sorted(_FRESH_WRITES))
def test_the_block_write_compiles_for_v5e_in_place_with_no_copy_of_a_pool(
        one_chip, case):
    """An 8,192-wide fresh call into DONATED pools of the cell's size: the
    compiled program aliases every pool to its output and holds under a
    hundredth of one pool in temporaries — no relayout of a pool for the
    write, which an update indexed past the major axis draws (``put``'s
    comment in ``paged_update``)."""
    from accelerate_tpu.ops.attention import (
        latent_update, paged_update, pool_heads_first)

    hkv, d, slots, ring = _FRESH_WRITES[case]
    bs, ctx, width = 16, 16384, 8192
    heads_first = bool(hkv) and pool_heads_first(hkv, d)
    blocks = slots * ((ring or ctx) // bs) + 1
    row = (hkv, d) if hkv else (d,)
    block = (hkv, bs, d) if heads_first else (bs, *row)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pools = [sds((blocks, *block), jnp.bfloat16)] * (2 if hkv else 1)
    rows = [sds((1, width, *row), jnp.bfloat16)] * len(pools)

    def write(*args):
        *arrays, table, lengths, slot = args
        state = PagedKVState(
            block_table=table, cache_len=jnp.zeros_like(lengths),
            lengths=lengths, num_blocks=slots * (ctx // bs) + 1, block_size=bs,
            fresh=True, heads_first=heads_first, ring=ring, num_slots=slots,
            slot=slot)
        if not hkv:
            return latent_update(*arrays, state)
        return paged_update(*arrays, state, ring=bool(ring))

    compiled = _compiled_for_the_chip(jax.jit(
        write, donate_argnums=tuple(range(len(pools)))).lower(
            *pools, *rows, sds((1, ctx // bs), jnp.int32), sds((1,), jnp.int32),
            sds((1,), jnp.int32)))
    memory = compiled.memory_analysis()
    pool_bytes = 2 * blocks * bs * (hkv or 1) * d
    assert memory.alias_size_in_bytes == len(pools) * pool_bytes
    assert memory.temp_size_in_bytes < pool_bytes // 100


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


# ---------------------------------------------------------------------- #
# (e) PR 27: the pools ride the layer loop as a carry, written in place
# ---------------------------------------------------------------------- #
def _layer_rows(stacked, layers):
    """A scanned stack's parameters as the unrolled stack holds them."""
    flat = {k: v for k, v in stacked.items() if k != "layers"}
    for i in range(layers):
        flat[f"layer_{i}"] = jax.tree.map(lambda x: x[i], stacked["layers"])
    return flat


_STACK_CASES = {
    # kv dtype, tokens of the second call, window
    "native-kernel": ("native", 1, None),
    "native-kernel-window": ("native", 1, 5),
    "native-gather": ("native", 3, None),
    "native-gather-window": ("native", 3, 5),
    "int8-decode": ("int8", 1, None),
    "int8-decode-window": ("int8", 1, 5),
    "int8-gather": ("int8", 3, None),
    "int8-gather-window": ("int8", 3, 5),
}


@pytest.mark.parametrize("case", sorted(_STACK_CASES))
def test_scanned_stack_carries_its_pools_and_equals_the_unrolled_stack(case):
    """Prefill, then a second call (one token: the kernel where the pools
    are native; three: the gather form), through ``CausalLM.apply`` over a
    3-layer SCANNED stack — whose pools are one (L, num_blocks, ...) leaf
    each, carried through the loop and addressed by layer — against the
    same weights UNROLLED, every layer a 4-D pool of its own: the same
    logits and the same final pools, to the last few bits (XLA:CPU rounds a
    one-token matmul inside a loop body and outside one 2e-6 apart; a row
    in the wrong layer or block is wrong by ~1). Slot 1 shares slot 0's
    first block (a cached prefix); slot 2 is idle."""
    kv, q_len, window = _STACK_CASES[case]
    layers, nb, bs = 3, 9, 4
    base = dict(hidden_size=256, num_heads=2, num_kv_heads=1, max_seq_len=16,
                num_layers=layers, sliding_window=window)
    scanned = CausalLM(TransformerConfig.tiny(**base))
    unrolled = CausalLM(TransformerConfig.tiny(scan_layers=False, **base))
    params = scanned.init(
        jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))["params"]

    def state(cache_len, lengths):
        return PagedKVState(
            block_table=jnp.asarray(
                [[1, 2, 3, 0], [1, 4, 5, 6], [0, 0, 0, 0]], jnp.int32),
            cache_len=jnp.asarray(cache_len, jnp.int32),
            lengths=jnp.asarray(lengths, jnp.int32),
            num_blocks=nb, block_size=bs, kv_dtype=kv, single_device=True)

    rng = np.random.default_rng(11)
    vocab = scanned.config.vocab_size
    first = rng.integers(1, vocab, (3, 8))
    first[1, :2] = rng.integers(1, vocab, 2)  # row 1 holds positions 4..9
    second = rng.integers(1, vocab, (3, q_len))
    calls = [  # (ids, cache_len, lengths)
        (first, [0, bs, 0], [8, 6, 0]),
        (second, [8, bs + 6, 0], [q_len, q_len, 0]),
    ]

    def run(model, weights):
        cache = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32), decode=True,
            paged=state([0], [1]))["cache"]
        apply = jax.jit(lambda c, ids, st: model.apply(
            {"params": weights, "cache": c}, ids, decode=True, paged=st,
            mutable=["cache"]))
        logits = []
        with kernel_interpret_mode():
            for ids, cache_len, lengths in calls:
                out, mutated = apply(cache, jnp.asarray(ids, jnp.int32),
                                     state(cache_len, lengths))
                cache = mutated["cache"]
                logits.append(out)
        return logits, cache

    got, stack = run(scanned, params)
    want, rows = run(unrolled, _layer_rows(params, layers))
    leaves = stack["layers"]["attn"]
    assert set(leaves) == {"key_pool", "value_pool"} | (
        {"key_scale", "value_scale"} if kv == "int8" else set())
    for name, leaf in leaves.items():
        assert leaf.shape[:2] == (layers, nb), (name, leaf.shape)
        per_layer = jnp.stack(
            [rows[f"layer_{i}"]["attn"][name] for i in range(layers)])
        # block 0 takes every masked write, several to a row: no order
        a = np.asarray(leaf[:, 1:], np.float32)
        b = np.asarray(per_layer[:, 1:], np.float32)
        # an int8 row may round to the next step where its scale's last bit differs
        np.testing.assert_allclose(
            a, b, rtol=2e-5, atol=1 if leaf.dtype == jnp.int8 else 2e-5)
        assert np.mean(a == b) > 0.9 and np.any(a != 0)
    # rows 0 and 1 alone: the idle slot's logits attend to the garbage block
    tol = 2e-2 if kv == "int8" else 1e-4
    for a, b in zip(got, want):
        np.testing.assert_allclose(a[:2], b[:2], rtol=tol, atol=tol)


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("what", ["update", "gather", "kernel", "int8"])
def test_the_paged_operations_take_a_stack_and_a_layer_like_a_pool(what, layer):
    """``paged_update``, ``paged_attention`` and ``paged_decode_attention``
    given (the stack of every layer's pools, a layer) do to that layer's
    part of the stack what they do to the layer's own 4-D pool, and leave
    every other layer's as it was."""
    from accelerate_tpu.ops.attention import paged_update

    rng = np.random.default_rng(7)
    layers, nb = 3, 7
    int8 = what == "int8"
    dtype = jnp.int8 if int8 else jnp.float32
    draw = (lambda s: rng.integers(-127, 128, s)) if int8 else rng.standard_normal
    ks, vs = (jnp.asarray(draw((layers, nb, BS, HKV, D)), dtype)
              for _ in range(2))
    scales = [jnp.asarray(rng.uniform(0.01, 0.1, (layers, nb, BS)), jnp.float32)
              for _ in range(2)] if int8 else [None, None]
    cache_len = [BS + 3, 2, 0]
    table = _scattered_table(rng, cache_len, nb)
    s = 1 if what == "kernel" else 2
    st = dataclasses.replace(
        _state(table, cache_len, [s, s, 0], nb, what == "kernel"),
        kv_dtype="int8" if int8 else "native")
    k, v = (jnp.asarray(rng.standard_normal((3, s, HKV, D)), jnp.float32)
            for _ in range(2))
    q = jnp.asarray(rng.standard_normal((3, s, 2 * HKV, D)), jnp.float32)
    at = lambda stack: None if stack is None else stack[layer]
    traced = jnp.asarray(layer, jnp.int32)

    def on_stack(ks, vs, k_scale, v_scale):
        new = paged_update(ks, vs, k, v, st, key_scale=k_scale,
                           value_scale=v_scale, layer=traced)
        return new, paged_attention(
            q, *new[:2], st, key_scale=(new[2:] or [None])[0],
            value_scale=(new[2:] or [None, None])[1], layer=traced)

    def on_pool(kp, vp, k_scale, v_scale):
        new = paged_update(kp, vp, k, v, st, key_scale=k_scale,
                           value_scale=v_scale)
        return new, paged_attention(
            q, *new[:2], st, key_scale=(new[2:] or [None])[0],
            value_scale=(new[2:] or [None, None])[1])

    with kernel_interpret_mode():
        assert decode_kernel_eligible(st, s, ks) == (what == "kernel")
        got_pools, got = jax.jit(on_stack)(ks, vs, *scales)
        want_pools, want = jax.jit(on_pool)(at(ks), at(vs), *map(at, scales))
    np.testing.assert_array_equal(got[:2], want[:2])
    for stack, before, pool in zip(got_pools, [ks, vs, *scales], want_pools):
        assert stack.shape == before.shape
        np.testing.assert_array_equal(stack[layer, 1:], pool[1:])
        others = [i for i in range(layers) if i != layer]
        np.testing.assert_array_equal(stack[others, 1:], before[others, 1:])
    if what == "kernel":
        direct = paged_attention_kernel.paged_decode_attention
        with kernel_interpret_mode():
            np.testing.assert_array_equal(
                direct(q, *got_pools, st.block_table, st.cache_len,
                       layer=traced)[:2],
                direct(q, *want_pools, st.block_table, st.cache_len)[:2])


# a fresh call's rows by the BLOCK (PR 46): three seats of eight blocks (a
# ring: two), the call fills the third and the first, the second is another
# request's. name -> (bucket, length of the first row of the call)
_WRITE_SLOTS, _WRITE_RING = 3, 2 * BS
_BUCKET = 8 * BS
_BLOCK_WRITES = {
    "full": {f"L={n}": (_BUCKET, n) for n in (
        1, BS - 1, BS, BS + 1, _BUCKET - BS + 1, _BUCKET)},
    "ring": {
        "L<ring": (_BUCKET, 5), "L==ring": (_BUCKET, _WRITE_RING),
        # the block of position L - 1 and that of position L - ring are ONE
        # ring block, filled from both
        "L>ring,shared-block": (_BUCKET, _WRITE_RING + BS + 3),
        "L=3ring+5": (_BUCKET, 3 * _WRITE_RING + 5),
        "bucket<ring": (BS, BS - 2),
    },
}
_BLOCK_WRITES["latent"] = _BLOCK_WRITES["full"]


@pytest.mark.parametrize("heads_first,kind,case", [
    pytest.param(heads, kind, case, id=f"{layout}-{kind}-{case}")
    for heads, layout in ((False, "token-major"), (True, "heads-first"))
    for kind in sorted(_BLOCK_WRITES) for case in _BLOCK_WRITES[kind]
    if not (heads and kind == "latent")])  # a latent row has no heads
@pytest.mark.parametrize("stacked", [False, True], ids=["own", "stack"])
def test_a_fresh_call_writes_by_the_block_what_the_row_scatter_writes(
        stacked, heads_first, kind, case):
    """``paged_update`` / ``latent_update`` told a call is ``fresh`` leave, on
    every row a read can reach (a slot's first ``L`` rows; a ring's first
    ``min(L, ring)``), the bits the row scatter leaves, and every block
    outside the call's own seats — the other request's, the garbage block,
    the other layers of a stack — as it was."""
    from accelerate_tpu.ops.attention import (
        block_write_eligible, latent_update, paged_update)

    s, first = _BLOCK_WRITES[kind][case]
    lengths = [first, max(1, s // 2 - 3)]
    rng = np.random.default_rng(46)
    per, ring = _BUCKET // BS, kind == "ring"
    held = _WRITE_RING // BS if ring else per
    seats = np.asarray([2, 0], np.int32)
    nb = _WRITE_SLOTS * per + 1
    # a full layer's table: the seats' blocks drawn in no order; a ring's
    # blocks are the seat's own run
    table = rng.permutation(np.arange(1, nb)).reshape(_WRITE_SLOTS, per)
    owned = (1 + np.arange(_WRITE_SLOTS * held).reshape(_WRITE_SLOTS, held)
             if ring else table)
    blocks = _WRITE_SLOTS * held + 1
    row = (D,) if kind == "latent" else (HKV, D)
    block = (HKV, BS, D) if heads_first else (BS, *row)
    layers, layer = 3, 1
    shape = ((layers,) if stacked else ()) + (blocks, *block)
    pools = [jnp.asarray(rng.standard_normal(shape), jnp.float32)
             for _ in range(1 if kind == "latent" else 2)]
    rows = [jnp.asarray(rng.standard_normal((2, s, *row)), jnp.float32)
            for _ in pools]

    def write(fresh):
        st = PagedKVState(
            block_table=jnp.asarray(table[seats], jnp.int32),
            cache_len=jnp.zeros(2, jnp.int32),
            lengths=jnp.asarray(lengths, jnp.int32), num_blocks=nb,
            block_size=BS, fresh=fresh, heads_first=heads_first,
            ring=_WRITE_RING, num_slots=_WRITE_SLOTS, slot=jnp.asarray(seats))
        assert block_write_eligible(st, s) == fresh
        at = jnp.asarray(layer, jnp.int32) if stacked else None
        if kind == "latent":
            return [latent_update(pools[0], rows[0], st, layer=at)]
        return list(paged_update(*pools, *rows, st, layer=at, ring=ring))

    for before, by_row, by_block in zip(pools, write(False), write(True)):
        assert by_block.shape == before.shape
        if stacked:
            others = np.asarray([i for i in range(layers) if i != layer])
            np.testing.assert_array_equal(by_block[others], before[others])
            before, by_row, by_block = (
                x[layer] for x in (before, by_row, by_block))
        if heads_first:
            before, by_row, by_block = (
                jnp.swapaxes(x, 1, 2) for x in (before, by_row, by_block))
        for seat, n in zip(seats, lengths):
            reach = min(n, _WRITE_RING) if ring else n
            np.testing.assert_array_equal(
                np.asarray(by_block)[owned[seat]].reshape(-1, *row)[:reach],
                np.asarray(by_row)[owned[seat]].reshape(-1, *row)[:reach])
        untouched = np.setdiff1d(np.arange(blocks), owned[seats].ravel())
        np.testing.assert_array_equal(
            np.asarray(by_block)[untouched], np.asarray(before)[untouched])


@pytest.mark.parametrize("ring", [False, True], ids=["full", "ring"])
@pytest.mark.parametrize("form", ["gather", "kernel"])
def test_a_decode_step_reads_nothing_of_a_prompts_padded_last_block(form, ring):
    """The block form writes the prompt's last, partial block WHOLE: its rows
    past the length hold the padded positions' K/V (here 1e4: a masked
    column's weight is an exact zero, so a finite value cannot show where no
    read takes it) inside the slot's own block. The decode steps that follow
    overwrite them in order and read none: their attention is the row
    scatter's, bit for bit."""
    from accelerate_tpu.ops.attention import paged_update

    rng = np.random.default_rng(47)
    s, length, window = 4 * BS, 2 * BS + 3, 2 * BS
    nb = 2 * MAX_BLOCKS + 1
    table = rng.permutation(np.arange(1, nb)).reshape(2, MAX_BLOCKS)[:1]
    blocks = 2 * (window // BS) + 1 if ring else nb
    pools = [jnp.zeros((blocks, HKV, BS, D), jnp.float32) for _ in range(2)]
    k, v = (jnp.asarray(rng.standard_normal((1, s, HKV, D)), jnp.float32)
            .at[:, length:].set(1e4) for _ in range(2))

    def state(fresh, cache_len, lengths):
        return PagedKVState(
            block_table=jnp.asarray(table, jnp.int32),
            cache_len=jnp.asarray([cache_len], jnp.int32),
            lengths=jnp.asarray([lengths], jnp.int32), num_blocks=nb,
            block_size=BS, fresh=fresh, heads_first=True, ring=window,
            num_slots=2, slot=jnp.asarray([1], jnp.int32),
            single_device=form == "kernel")

    def served(fresh):
        kp, vp = paged_update(*pools, k, v, state(fresh, 0, length), ring=ring)
        outs = []
        for step in range(BS):  # across the partial block's end
            st = state(False, length + step, 1)
            new = [jnp.asarray(rng.standard_normal((1, 1, HKV, D)), jnp.float32)
                   for _ in range(3)]
            kp, vp = paged_update(kp, vp, new[0], new[1], st, ring=ring)
            assert decode_kernel_eligible(st, 1, kp) == (form == "kernel")
            outs.append(paged_attention(
                jnp.repeat(new[2], 2, axis=2), kp, vp, st, ring=ring))
        return np.asarray(jnp.stack(outs))

    with (kernel_interpret_mode() if form == "kernel"
          else contextlib.nullcontext()):
        rng = np.random.default_rng(48)
        by_block = served(True)
        rng = np.random.default_rng(48)
        by_row = served(False)
    assert np.abs(by_block).max() < 10.0
    np.testing.assert_array_equal(by_block, by_row)


def _while_operands(cfg):
    """Operand counts of the ``while`` loops in a forward pass's lowered
    text: everything the layer scans thread through."""
    import re

    model = CausalLM(cfg)
    ids = jnp.zeros((2, 16), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids))["params"]
    text = jax.jit(lambda p, x: model.apply({"params": p}, x)).lower(
        params, ids).as_text()
    return [len(re.findall("%iterArg", line.split(") :")[0]))
            for line in text.splitlines() if "stablehlo.while" in line]


_TRAIN_LOOPS = {
    # counted on the parent of PR 27 (commit 40f032d): the loop counter,
    # the hidden states, the positions and one operand a parameter leaf
    "dense": (TransformerConfig.tiny(num_layers=3), [14]),
    "hybrid-moe": (TransformerConfig.tiny(
        num_layers=9, layer_types=("conv",) + 2 * (
            "full_attention", "conv", "conv", "conv"),
        num_dense_layers=1, num_experts=2, moe_router_width=8,
        moe_expert_offset=2, num_experts_per_tok=4, moe_router="sigmoid",
        moe_expert_bias=True, qk_norm=True, tie_embeddings=True), [48]),
}


@pytest.mark.parametrize("name", sorted(_TRAIN_LOOPS))
def test_a_stack_applied_without_decode_threads_nothing_new(name):
    """The carry and the layer index exist for live paged pools alone: a
    training or evaluation pass scans what it scanned before PR 27 (both
    train cells' lowered steps were byte-identical to the parent's)."""
    cfg, counted = _TRAIN_LOOPS[name]
    assert _while_operands(cfg) == counted


# the serve cells' engine: Mistral-7B widths, 24 layers, 16 slots x 1024
_CELL = dict(vocab_size=32000, hidden_size=4096, intermediate_size=14336,
             num_layers=24, num_heads=32, num_kv_heads=8, head_dim=128,
             sliding_window=4096, max_seq_len=1024, dtype="bfloat16")


# EvaByte at the widths of its serve cell (MHA: q, k and v kernels all
# 4096 x 4096), 16 of 32 layers, 8 slots a step
_EVA_CELL = dict(vocab_size=320, hidden_size=4096, intermediate_size=11008,
                 num_layers=16, num_heads=32, num_kv_heads=32, head_dim=128,
                 rope_theta=100000.0, norm_offset=True, num_pred_heads=8,
                 attention_class="eva", chunk_size=16, window_size=2048,
                 fp32_residual=True, fp32_logits=True, max_seq_len=16384,
                 dtype="bfloat16")


def _engine_on_the_v5e(cfg, one_chip, **engine_kw):
    """An engine at a serve cell's widths and what its programs are lowered
    over, all placed on the described chip: abstract bf16 ``params``, its
    ``cache``'s and its ``key``'s shapes, and ``spec(dtype, *shape)`` for
    the rest. The engine lives where its weights do, one (CPU) device here:
    it is handed the two leaves an eva engine's roll-over program reads when
    the engine is built, no more."""
    from types import SimpleNamespace

    model = CausalLM(cfg)
    vec = jnp.zeros((cfg.num_layers, cfg.num_kv_heads, cfg.head_dim))
    held = jax.device_put(
        {"layers": {"attn": {"mu": vec, "phi": vec}}}, jax.devices()[0])
    engine = ServingEngine(model, held, block_size=16, **engine_kw)

    def spec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def like(x):
        return spec(x.dtype, *x.shape)

    params = jax.tree.map(
        lambda x: spec(jnp.bfloat16, *x.shape),
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"])
    return SimpleNamespace(
        engine=engine, params=params, cache=jax.tree.map(like, engine.cache),
        key=like(engine._key), spec=spec)


def _decode_args(on):
    """``_decode_fn``'s arguments at the engine's slots (an eva engine takes
    the slots' positions after them)."""
    n, i32 = on.engine.max_slots, jnp.int32
    return (on.params, on.cache, on.spec(i32, n, 1),
            on.spec(i32, n, on.engine._max_table), on.spec(i32, n),
            on.spec(i32, n), on.spec(jnp.float32, n), on.key)


@pytest.mark.parametrize("program", ["decode", "prefill_widest"])
def test_the_engine_program_holds_one_pool_on_the_v5e(
        one_chip, program, monkeypatch):
    """The engine's own decode and widest-prefill programs at the serve
    cells' shapes (abstract weights, a 1025-block pool), compiled for the
    described chip: every byte of the pool comes back in the buffer it came
    in, and no operation is left whose result is one layer's pool."""
    import re

    on = _engine_on_the_v5e(TransformerConfig(**_CELL), one_chip, max_slots=16)
    engine, spec = on.engine, on.spec
    assert engine.num_blocks == 1025
    layer_pool = jax.tree.leaves(engine.cache)[0].shape[1:]
    assert layer_pool == (1025, 16, 8, 128)
    # dispatch asks the default backend, the CPU here: answer for the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if program == "decode":
        lowered = engine._decode_fn.lower(*_decode_args(on))
        assert engine.trace_counts()["decode_attn_kernel"] == 1
    else:
        i32 = jnp.int32
        lowered = engine._prefill_fn.lower(
            on.params, on.cache, spec(i32, 1, 1024),
            spec(i32, 1, engine._max_table), spec(i32, 1), spec(i32, 1),
            on.key, spec(jnp.float32, 1))
    monkeypatch.undo()
    assert engine.trace_counts()["kv_in_place"] == 1
    compiled = _compiled_for_the_chip(lowered)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == engine.kv_pool_bytes == 1612185600
    if program == "decode":  # under one layer's K pool (it was 471 MB)
        assert memory.temp_size_in_bytes < engine.kv_pool_bytes // 48
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == (
        program == "decode")
    a_layers_pool = re.compile(
        r"= bf16\[(1,)?1025,16,8,128\]\S* "
        r"(dynamic-slice|dynamic-update-slice|copy|fusion)\(")
    assert not [line for line in text.splitlines()
                if a_layers_pool.search(line)]
    assert not re.search(r"= bf16\[24,1025,16,8,128\]\S* copy\(", text)


@pytest.mark.parametrize("name", ["mistral", "eva"])
def test_a_decode_step_reads_the_qkv_kernels_where_they_lie_on_the_v5e(
        one_chip, name, monkeypatch):
    """PR 31. The engine's own ``jit__decode`` at a serve cell's widths,
    compiled for the described chip: no operation's result is ONE layer's
    q, k or v kernel (the parent sliced each out of the stacked parameter and
    copied it transposed, every layer of every step), and the three
    projections are fusions that take the stacked kernel itself, as o_proj's
    always did. The pool stays one buffer and the temporaries stay small.
    The eva engine steps its cell's 8 slots over a pool of ONE slot's blocks
    (0.8 GB of zeros here, not 6.2): at one slot XLA multiplies a vector,
    and never made the copies."""
    import re

    eva = name == "eva"
    cfg = TransformerConfig(**(_EVA_CELL if eva else _CELL))
    on = _engine_on_the_v5e(
        cfg, one_chip,
        **(dict(max_slots=8, num_blocks=185) if eva else dict(max_slots=16)))
    engine, args = on.engine, _decode_args(on)
    if eva:  # the positions the slots' rows stand for
        args += (on.spec(jnp.int32, engine.max_slots),)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lowered = engine._decode_fn.lower(*args)
    monkeypatch.undo()
    counts = engine.trace_counts()
    assert counts["qkv_in_place"] == counts["decode"] == 1
    compiled = _compiled_for_the_chip(lowered)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == engine.kv_pool_bytes
    assert memory.temp_size_in_bytes < 1 << 20
    text = compiled.as_text()
    one_layers_kernel = re.compile(
        r"= bf16\[1,4096,(4096|1024)\]\S* (copy|fusion)\(")
    assert not [line for line in text.splitlines()
                if one_layers_kernel.search(line)]
    defined = dict(re.findall(r"^\s*(?:ROOT )?(%\S+) = (\S+) ", text, re.M))
    stacked = re.compile(rf"bf16\[{cfg.num_layers},4096,(4096|1024)\]")
    for proj in ("q_proj", "k_proj", "v_proj"):
        fusions = [
            line for line in text.splitlines()
            if f"attn/{proj}/dot_general" in line and " fusion(" in line
            and "kind=kOutput" in line]
        assert len(fusions) == 1, (proj, fusions)
        operands = re.search(r" fusion\(([^)]*)\)", fusions[0]).group(1)
        assert [op for op in operands.split(", ")
                if stacked.match(defined.get(op, ""))], (proj, fusions[0])


# ---------------------------------------------------------------------- #
# (f) PR 27: every program that writes the pools is given them to keep
# ---------------------------------------------------------------------- #
def test_every_pool_writing_program_donates_and_the_old_pool_is_gone():
    """A decode step, a prefill, a copy-on-write, a swap-out and -in and a
    speculative verify each leave the pool they were called with deleted
    (the swap-out's gather reads and keeps it), the lowered text marks the
    cache arguments as donated, and the tokens are those of an engine that
    never preempts, shares or speculates."""
    from accelerate_tpu.serving import SpecConfig

    cfg = TransformerConfig.tiny(max_seq_len=64)
    model = CausalLM(cfg)
    params = jax.device_put(
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
            "params"], jax.devices()[0])
    rng = np.random.default_rng(3)
    shared = list(map(int, rng.integers(1, cfg.vocab_size, 16)))
    prompts = [shared, shared, shared[:8] + [5, 6, 7], [9, 8, 7, 6, 5] * 3]

    def serve(eng):
        ids = [eng.add_request(prompts[0], max_new_tokens=12)]
        eng.step()  # published: the same prompt again is a full hit, a COW
        ids.append(eng.add_request(prompts[1], max_new_tokens=12))
        eng.step()
        # both seats taken: the head of the queue evicts a lower priority
        ids += [eng.add_request(p, max_new_tokens=12, priority=1)
                for p in prompts[2:]]
        while eng.has_work:
            eng.step()
        return [eng.result(i) for i in ids]

    plain = ServingEngine(model, params, max_slots=2, block_size=8)
    want = serve(plain)
    eng = ServingEngine(model, params, max_slots=2, block_size=8,
                        prefix_cache=True, preemption=True,
                        spec_decode=SpecConfig(k=2))
    gone: dict = {}

    def spy(name, fn, argnum, keeps=False):
        def call(*args):
            pools = jax.tree.leaves(args[argnum])
            out = fn(*args)
            deleted = [leaf.is_deleted() for leaf in pools]
            gone.setdefault(name, []).append(
                not any(deleted) if keeps else all(deleted))
            return out
        call.lower = fn.lower
        return call

    eng._prefill_fn = spy("prefill", eng._prefill_fn, 1)
    eng._decode_fn = spy("decode", eng._decode_fn, 1)
    eng._cow_fn = spy("cow", eng._cow_fn, 0)
    make_verify, make_swap = eng._make_verify, eng._make_swap_fns
    eng._make_verify = lambda width: spy("verify", make_verify(width), 1)

    def swap_fns(width):
        gather, scatter = make_swap(width)
        return (spy("swap_out", gather, 0, keeps=True),
                spy("swap_in", scatter, 0))

    eng._make_swap_fns = swap_fns
    got = serve(eng)
    counts = eng.trace_counts()
    assert counts["kv_in_place"] == (
        counts["prefill"] + counts["decode"] + counts["verify"])
    seen = {name for name, calls in gone.items() if calls}
    assert seen >= {"prefill", "cow", "swap_out", "swap_in", "verify"}, seen
    assert all(all(calls) for calls in gone.values()), gone
    assert got == want and all(len(tokens) == 12 for tokens in got)
    # a plain decode step, and what the lowered programs say
    before = jax.tree.leaves(plain.cache)
    rid = plain.add_request(prompts[3], max_new_tokens=3)
    while plain.has_work:
        plain.step()
    assert all(leaf.is_deleted() for leaf in before) and plain.result(rid)
    n = plain.max_slots
    text = plain._decode_fn.lower(
        plain.params, plain.cache, jnp.zeros((n, 1), jnp.int32),
        plain._tables_device(), jnp.zeros(n, jnp.int32),
        jnp.ones(n, jnp.int32), plain.sampling.temperatures(),
        plain._split_key()).as_text()
    pools = len(jax.tree.leaves(plain.cache))
    assert text.count("tf.aliasing_output") + text.count(
        "jax.buffer_donor") == pools


def test_a_call_that_fails_holding_the_pool_stops_the_engine(
        tmp_path, monkeypatch):
    """A program that fails on the device has consumed the pools it was
    donated: the autopsy is written, the error goes up as it is, and the
    next ``step`` refuses to serve from deleted buffers rather than
    failing somewhere inside."""
    from accelerate_tpu.profiling.oom import read_oom_report

    monkeypatch.setenv("ACCELERATE_TPU_OOM_DIR", str(tmp_path))
    cfg = TransformerConfig.tiny(max_seq_len=64)
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
        "params"]
    eng = ServingEngine(model, params, max_slots=2, block_size=8)
    eng.add_request([1, 2, 3], max_new_tokens=8)
    eng.step()

    def fails_on_the_device(params, cache, *rest):
        for leaf in jax.tree.leaves(cache):
            leaf.delete()
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    eng._decode_fn = fails_on_the_device
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        eng.step()
    assert read_oom_report(str(tmp_path))["context"] == "serving_step"
    with pytest.raises(RuntimeError, match="KV pool went with a call"):
        eng.step()

"""EVA attention (``attention_class="eva"``: chunk summaries beside a window
of exact keys and values) through the normal path — the plain forward pass,
the paged cache under ``CausalLM.apply`` and ``ServingEngine`` — held against
the benchmark's plain float32 reference
(``benchmark/harness/evabyte_reference.py``) on seeded weights
(``evabyte_weights.py``), at widths the CPU can hold: hidden 64, 4 heads of
16, chunks of 4 in windows of 16, 2 layers (``benchmark/tests/tiny_eva.py``).

Tolerances. Program and reference are both float32 here and differ only in
the order of their sums: the full forward reads 2.5e-6 on logits of size ~4.
``TOL`` is 20 x that. Summaries rounded to bfloat16 (a relative 4e-3 on every
summary) read 1e-3 and more and fail it: ``test_bf16_summaries_fail...``.
"""

import hashlib
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "tests"))

import tiny_eva  # noqa: E402
from harness import common  # noqa: E402
from harness import evabyte_reference as ref  # noqa: E402
from harness import evabyte_weights as W  # noqa: E402

from accelerate_tpu.compilation import get_compile_monitor  # noqa: E402
from accelerate_tpu.models import CausalLM, TransformerConfig  # noqa: E402
from accelerate_tpu.models.generation import init_cache  # noqa: E402
from accelerate_tpu.models.transformer import eva_roll_over_cache  # noqa: E402
from accelerate_tpu.ops import eva_attention as eva  # noqa: E402
from accelerate_tpu.ops.attention import (  # noqa: E402
    PagedKVState, paged_attention, paged_update)
from accelerate_tpu.ops.flash_attention import kernel_interpret_mode  # noqa: E402
from accelerate_tpu.serving import ServingEngine, SpecConfig  # noqa: E402

SEED = 2**31 + 5
CFG = tiny_eva.config()
WINDOW, CHUNK, BLOCK = CFG["window_size"], CFG["chunk_size"], 4
TOL = 5e-5


def _model(**kw):
    return CausalLM(common.program_config(
        CFG, max_seq_len=CFG["max_position_embeddings"], **kw))


@pytest.fixture(scope="module")
def params():
    return W.make_tree(CFG, SEED, jnp.float32)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], n).astype(np.int32)


def _reference_logits(params, ids):
    """The next byte's logits at every position of one sequence."""
    return np.asarray(ref.forward(params, CFG, jnp.asarray(ids)[None])[0, :, 0])


# --------------------------------------------------------------------------- #
# the tree, the layout, the plain forward pass
# --------------------------------------------------------------------------- #
def test_seeded_tree_is_the_programs_tree():
    own = nn.unbox(jax.eval_shape(lambda: _model().init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"])
    made = W.abstract_tree(CFG, jnp.float32)
    assert jax.tree.structure(own) == jax.tree.structure(made)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), own) == jax.tree.map(
        lambda a: (a.shape, a.dtype), made)
    # stacked over the scan, mu and phi two more leaves of attn; the head
    # holds every prediction head
    assert own["layers"]["attn"]["mu"].shape == (2, 4, 16)
    assert own["lm_head"]["kernel"].shape == (64, 3 * 40)


def test_layout_counts_rows_blocks_and_peaks():
    lay = eva.EvaLayout(WINDOW, CHUNK, BLOCK)
    assert [int(lay.rows(n)) for n in (0, 7, 15, 16, 17, 32, 53)] == [
        0, 7, 15, 4, 5, 8, 4 * 3 + 5]
    assert [lay.blocks(n) for n in (0, 7, 16, 17, 53)] == [0, 2, 1, 2, 5]
    assert (lay.per_window, lay.summary_blocks, lay.window_blocks) == (4, 1, 4)
    # a request that never fills a window: its rows; one that does: the
    # earlier windows as summaries beside its last full window (whose
    # summaries take its own first blocks), or what it ends with if more
    assert lay.peak_blocks(15) == 4 and lay.peak_blocks(16) == 0 + 4
    assert lay.peak_blocks(128) == 7 * 1 + 4
    assert lay.peak_blocks(53, start=50) == lay.blocks(53) == 5
    assert lay.peak_blocks(53, start=40) == 2 * 1 + 4
    assert lay.peak_blocks(31) == 1 + 4 and lay.peak_blocks(31, start=16) == 5
    # the published sizes: 16,384 bytes are 2,944 rows, 184 blocks at the peak
    real = eva.EvaLayout(2048, 16, 16)
    assert int(real.rows(16383)) == 7 * 128 + 2047
    assert real.peak_blocks(16384) == 184 == real.blocks(16383)
    assert real.peak_blocks(6144 + 2040, start=6000) == 3 * 8 + 128
    assert np.array_equal(real.rows(np.array([2048, 4097])), [128, 257])
    with pytest.raises(ValueError, match="whole blocks"):
        eva.EvaLayout(16, 4, 8)  # 4 summaries do not fill a block of 8


def test_config_refuses_what_eva_cannot_be_combined_with():
    with pytest.raises(ValueError, match="whole chunks"):
        TransformerConfig.tiny(attention_class="eva", window_size=18, chunk_size=4)
    with pytest.raises(ValueError, match="sliding_window"):
        TransformerConfig.tiny(attention_class="eva", sliding_window=8)
    with pytest.raises(ValueError, match="unknown attention_class"):
        TransformerConfig.tiny(attention_class="linear")
    with pytest.raises(ValueError, match="untied"):
        TransformerConfig.tiny(num_pred_heads=2, tie_embeddings=True)
    with pytest.raises(ValueError, match="untied head"):
        TransformerConfig.tiny(fp32_logits=True, tie_embeddings=True)
    with pytest.raises(ValueError, match="fused_kernels"):
        TransformerConfig.tiny(fp32_residual=True, fused_kernels=True)


@pytest.mark.parametrize("length", [7, 16, 53, 64])
def test_full_forward_matches_the_reference(params, length):
    """Shorter than a window, exactly one, no multiple of chunk or window,
    whole windows."""
    ids = np.stack([_ids(length, 1), _ids(length, 2)])
    got = _model().apply({"params": params}, jnp.asarray(ids))
    assert got.shape == (2, length, CFG["vocab_size"])  # the next byte's head
    want = np.stack([_reference_logits(params, row) for row in ids])
    assert np.max(np.abs(np.asarray(got) - want)) < TOL


@pytest.mark.parametrize("stated", [True, False])
def test_the_stream_and_the_logits_are_float32_where_the_file_states_it(
        params, stated):
    """At the cell's compute dtype (bfloat16) the configuration's
    ``fp32_skip_add`` and ``fp32_logits`` reach the program through
    ``program_fields``: the layer loop carries a float32 stream and the head
    hands on float32 logits. With both off there is one dtype throughout —
    the program's next lower precision, which the cell's ``bf16_stream``
    control runs (``benchmark/tests/eva_faults.py``)."""
    cfg = CFG if stated else {**CFG, "fp32_skip_add": False, "fp32_logits": False}
    assert cfg["fp32_skip_add"] is cfg["fp32_logits"] is stated
    model = CausalLM(common.program_config(
        cfg, max_seq_len=CFG["max_position_embeddings"], dtype="bfloat16"))
    assert (model.config.fp32_residual, model.config.fp32_logits) == (stated,) * 2
    jaxpr = jax.make_jaxpr(lambda p, ids: model.apply({"params": p}, ids))(
        params, jnp.asarray(_ids(24))[None])
    (loop,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    want = jnp.float32 if stated else jnp.bfloat16
    assert [v.aval.dtype for v in loop.outvars] == [want]
    assert [a.dtype for a in jaxpr.out_avals] == [want]
    # and nothing but bfloat16 goes into a matmul either way
    dots = [e for e in loop.params["jaxpr"].jaxpr.eqns
            if e.primitive.name == "dot_general"]
    assert dots and all(
        v.aval.dtype == jnp.bfloat16 for e in dots for v in e.invars)


def test_bf16_summaries_fail_the_tolerance(params, monkeypatch):
    real = eva.chunk_summaries

    def rounded(k, v, mu, phi, **kw):
        kt, vt = real(k, v, mu, phi, **kw)
        return (kt.astype(jnp.bfloat16).astype(kt.dtype),
                vt.astype(jnp.bfloat16).astype(vt.dtype))

    monkeypatch.setattr(eva, "chunk_summaries", rounded)
    ids = _ids(53, 1)
    got = _model().apply({"params": params}, jnp.asarray(ids)[None])[0]
    assert np.max(np.abs(np.asarray(got) - _reference_logits(params, ids))) > 10 * TOL


def test_the_dense_decode_cache_and_masks_are_refused_by_name(params):
    model = _model()
    ids = jnp.asarray(_ids(8))[None]
    with pytest.raises(NotImplementedError, match="no mask"):
        model.apply({"params": params}, ids, mask=jnp.ones((1, 1, 8, 8), bool))
    with pytest.raises(NotImplementedError, match="dense decode cache"):
        model.init(jax.random.PRNGKey(0), ids, decode=True)  # creates the cache


# --------------------------------------------------------------------------- #
# prefill then decode through the paged pool, against ONE forward pass
# --------------------------------------------------------------------------- #
def _drive_through_the_pool(params, seqs, prompts):
    """Teacher-forced: prefill ``seqs[b][:prompts[b]]`` into slot b's blocks,
    then decode the rest in ONE batch, a window rolling over whenever a slot
    fills one — what ``ServingEngine`` does, by hand, so that the LOGITS of
    every call can be read. Yields ``(slot, position, logits)``."""
    model = _model()
    lay = eva.EvaLayout(WINDOW, CHUNK, BLOCK)
    slots = len(seqs)
    max_table = lay.peak_blocks(CFG["max_position_embeddings"])
    num_blocks = slots * max_table + 1
    free = list(range(num_blocks - 1, 0, -1))
    common_kw = dict(num_blocks=num_blocks, block_size=BLOCK)
    cache = init_cache(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
        decode=True, paged=PagedKVState(
            block_table=jnp.zeros((1, max_table), jnp.int32),
            cache_len=jnp.zeros((1,), jnp.int32),
            lengths=jnp.ones((1,), jnp.int32), **common_kw))

    @jax.jit
    def prefill(cache, ids, table, length):
        state = PagedKVState(block_table=table, cache_len=jnp.zeros_like(length),
                             lengths=length, **common_kw)
        logits, mutated = model.apply(
            {"params": params, "cache": cache}, ids, decode=True, paged=state,
            mutable=["cache"])
        return mutated["cache"], logits

    @jax.jit
    def decode(cache, tokens, tables, rows, positions, lengths):
        state = PagedKVState(block_table=tables, cache_len=rows, lengths=lengths,
                             positions=positions, **common_kw)
        logits, mutated = model.apply(
            {"params": params, "cache": cache}, tokens, decode=True, paged=state,
            mutable=["cache"])
        return mutated["cache"], logits[:, 0]

    roll = jax.jit(lambda cache, src, dst: eva_roll_over_cache(
        model.config, params, cache, src, dst))
    blocks = [[] for _ in seqs]
    at = list(prompts)
    for b, (seq, p) in enumerate(zip(seqs, prompts)):
        bucket = 1 << (p - 1).bit_length()
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :p] = seq[:p]
        blocks[b] = [free.pop() for _ in range(lay.blocks(p))]
        table = np.zeros((1, max_table), np.int32)
        table[0, :len(blocks[b])] = blocks[b]
        cache, logits = prefill(cache, jnp.asarray(ids), jnp.asarray(table),
                                jnp.asarray([p], jnp.int32))
        yield b, p - 1, np.asarray(logits[0, p - 1])
    done = [p // WINDOW for p in prompts]  # windows whose summaries stand
    while any(at[b] < len(seqs[b]) for b in range(slots)):
        live = [b for b in range(slots) if at[b] < len(seqs[b])]
        tokens = np.zeros((slots, 1), np.int32)
        tables = np.zeros((slots, max_table), np.int32)
        rows, positions, lengths = (np.zeros(slots, np.int32) for _ in range(3))
        for b in live:
            rows[b], positions[b], lengths[b] = lay.rows(at[b]), at[b], 1
            while len(blocks[b]) * BLOCK < rows[b] + 1:
                blocks[b].append(free.pop())
            assert len(blocks[b]) <= max_table
            tokens[b, 0] = seqs[b][at[b]]
            tables[b, :len(blocks[b])] = blocks[b]
        cache, logits = decode(cache, *map(jnp.asarray, (
            tokens, tables, rows, positions, lengths)))
        for b in live:
            yield b, at[b], np.asarray(logits[b])
            at[b] += 1
            if at[b] % WINDOW == 0 and at[b] < len(seqs[b]):
                # as the engine: the summaries over the window's own first
                # blocks, the table left as it is
                first = done[b] * lay.summary_blocks
                src = blocks[b][first:first + lay.window_blocks]
                assert len(src) == lay.window_blocks
                cache = roll(cache, jnp.asarray(src, jnp.int32),
                             jnp.asarray(src[:lay.summary_blocks], jnp.int32))
                done[b] += 1


def test_prefill_then_decode_through_the_pool_is_one_forward_pass(params):
    """Logits, not tokens; slots of different lengths in one batch; 5, 2 and
    2 roll-overs; a prompt that ends exactly on a window (32), one exactly on
    a chunk (20), one in the middle of a chunk (7)."""
    prompts, totals = (7, 32, 20), (90, 70, 55)
    seqs = [_ids(n, 10 + i) for i, n in enumerate(totals)]
    want = [_reference_logits(params, seq) for seq in seqs]
    seen = [0] * len(seqs)
    for b, position, logits in _drive_through_the_pool(params, seqs, prompts):
        gap = float(np.max(np.abs(logits - want[b][position])))
        assert gap < TOL, (b, position, gap)
        seen[b] += 1
    # the prompt's last position, then every later one
    assert seen == [t - p + 1 for t, p in zip(totals, prompts)]


def test_the_kernel_forms_agree_with_the_xla_forms():
    """The forms the chip runs — the flash forward kernel with its
    log-sum-exp inside and across windows, the paged decode kernel walking
    summaries and window rows at a ROW count that is not the position — in
    interpret mode, against the XLA forms, at head_dim 128."""
    rng = np.random.default_rng(3)
    b, s, h, d, window, chunk, bs = 1, 384, 2, 128, 128, 8, 16
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
               for _ in range(3))
    mu, phi = (jnp.asarray(rng.normal(size=(h, d)), jnp.float32) for _ in range(2))
    plain, kt, vt = eva.eva_attention(q, k, v, mu, phi, chunk=chunk, window=window)
    with kernel_interpret_mode():
        assert eva.flash_eligible(window, window, d)
        kernel, kt2, vt2 = eva.eva_attention(
            q, k, v, mu, phi, chunk=chunk, window=window, kernel=True)
    assert np.max(np.abs(np.asarray(kernel) - np.asarray(plain))) < 2e-5
    assert np.array_equal(np.asarray(kt), np.asarray(kt2))
    # decode at position 300: two windows of 16 summaries, then 44 rows
    lay = eva.EvaLayout(window, chunk, bs)
    n = 300
    rows = int(lay.rows(n))
    assert rows == 32 + 44
    table = jnp.arange(1, 9, dtype=jnp.int32)[None]
    pools = [jnp.zeros((9, bs, h, d), jnp.float32) for _ in range(2)]
    held_k = jnp.concatenate([kt[:, :32], k[:, 256:300]], axis=1)
    held_v = jnp.concatenate([vt[:, :32], v[:, 256:300]], axis=1)
    state = PagedKVState(
        block_table=table, cache_len=jnp.zeros((1,), jnp.int32),
        lengths=jnp.asarray([rows], jnp.int32), num_blocks=9, block_size=bs,
        single_device=True)
    pools = paged_update(*pools, held_k, held_v, state)
    step = state.replace(cache_len=jnp.asarray([rows], jnp.int32),
                         lengths=jnp.ones((1,), jnp.int32))
    q1, k1, v1 = (x[:, :1] for x in (q, k, v))
    pools = paged_update(*pools, k1, v1, step)
    gathered = paged_attention(q1, *pools, step)
    with kernel_interpret_mode():
        walked = paged_attention(q1, *pools, step)
    assert np.max(np.abs(np.asarray(walked) - np.asarray(gathered))) < 2e-5


# --------------------------------------------------------------------------- #
# the serving engine
# --------------------------------------------------------------------------- #
def _engine(params, **kw):
    return ServingEngine(_model(), params, max_slots=3, block_size=BLOCK, **kw)


def _served_gap(params, prompt, tokens):
    """How far each served byte's logit lies below the reference's best."""
    seq = np.concatenate([prompt, tokens]).astype(np.int32)
    logits = _reference_logits(params, seq)[len(prompt) - 1:len(seq) - 1]
    return float(np.max(logits.max(-1) - logits[np.arange(len(tokens)), tokens]))


def test_engine_holds_rows_and_blocks_by_the_layout_and_gives_all_back(params):
    eng = _engine(params)
    lay = eng._eva
    assert eng._max_table == 11 and eng.num_blocks == 3 * 11 + 1
    assert eng.kv_bytes_per_token == 2 * 2 * 4 * 16 * 4  # K, V x layers x row
    # more requests than slots; prompts on a window (16, 32), on a chunk (20),
    # past a window (21, 50); answers that cross up to four windows
    asks = [(16, 40), (7, 30), (32, 5), (21, 50), (50, 70), (20, 9)]
    sent = []
    for i, (p, new) in enumerate(asks):
        prompt = _ids(p, 20 + i)
        sent.append((eng.add_request(prompt, max_new_tokens=new), prompt, new))
    steps = shrunk = 0
    while eng.has_work:
        eng.step()
        steps += 1
        held = 0
        for slot in eng.scheduler.slots:
            if slot.busy and not slot.done:
                n = slot.cache_len
                assert eng._regime.rows(n) == lay.rows(n) == (
                    lay.per_window * (n // WINDOW) + n % WINDOW)
                assert slot.windows_done == n // WINDOW
                # it holds what is still to come needs at most: the whole
                # from admission, less once its last window has filled
                total = len(slot.request.prompt) + slot.request.max_new_tokens
                assert len(slot.blocks) == lay.peak_blocks(
                    total, start=max(len(slot.request.prompt),
                                     slot.windows_done * WINDOW))
                assert lay.blocks(n + 1) <= len(slot.blocks), (n, slot.blocks)
                shrunk += len(slot.blocks) < lay.peak_blocks(
                    total, start=len(slot.request.prompt))
                table = eng._tables[slot.index]
                assert list(table[:len(slot.blocks)]) == slot.blocks
                assert not table[len(slot.blocks):].any()
            if slot.busy:
                held += len(slot.blocks)
        assert eng.pool.stats()["allocated"] == held
        gauges = eng._gauge_fields()
        assert gauges["summary_blocks"] + gauges["window_blocks"] <= held
    pool, counts = eng.pool.stats(), eng.trace_counts()
    assert pool["allocated"] == 0 and pool["free"] == eng.num_blocks - 1
    assert shrunk  # slots that gave blocks back before they ended
    assert counts["decode"] == 1 and counts["prefill"] == 4  # 8 .. 64 wide
    assert counts["eva"] == counts["prefill"] + counts["decode"] + 1
    assert counts["flash_real_rows"] == 0  # its windows are whole: no length
    # 16->56: 2 (at 32, 48); 7->37: 2; 32->37: 0; 21->71: 3; 50->120: 4; 20->29: 0
    assert eng._gauge_fields()["window_rollovers_total"] == 11
    for rid, prompt, new in sent:
        tokens = eng.result(rid)
        assert len(tokens) == new
        # float32 on both sides: the served byte IS the reference's best
        assert _served_gap(params, prompt, np.asarray(tokens)) < TOL


def test_decode_is_traced_once_and_nothing_compiles_after_warm_up(params):
    """As the benchmark's runner warms up: one prompt of ``width - 2`` bytes
    a prefill width and two new bytes — no window fills by decoding. What a
    roll-over runs was compiled when the engine was built."""
    eng = _engine(params)
    for width in (8, 16, 32, 64):
        eng.add_request(_ids(width - 2, width), max_new_tokens=2)
    while eng.has_work:
        eng.step()
    assert eng._gauge_fields()["window_rollovers_total"] == 0
    monitor = get_compile_monitor()
    before, traced = monitor.snapshot(), eng.trace_counts()
    # an earlier test's roll-overs may have left this process every small
    # program: hold the call to what can compile nothing — numpy rows (a
    # Python list through jnp.asarray compiles a conversion, once a shape:
    # the chip's first run of ISSUE 30 counted 2 compiles in its window)
    real, handed = eng._rollover_fn, []

    def rollover(params, cache, src, dst):
        handed.append((type(src), src.dtype, type(dst), dst.dtype))
        return real(params, cache, src, dst)

    eng._rollover_fn = rollover
    for i, (p, new) in enumerate([(7, 60), (16, 40), (33, 50), (60, 30)]):
        eng.add_request(_ids(p, 40 + i), max_new_tokens=new)
    while eng.has_work:
        eng.step()
    delta = monitor.delta(before)
    assert eng._gauge_fields()["window_rollovers_total"] == len(handed) >= 8
    assert set(handed) == {(np.ndarray, np.dtype("int32")) * 2}
    assert eng.trace_counts() == traced and traced["decode"] == 1
    assert common.compiles_in(delta) == 0 and delta["compile_time_s"] == 0


def test_gauges_and_the_decode_span_count_rows_not_positions(params):
    eng = _engine(params)
    eng.add_request(_ids(40, 1), max_new_tokens=4)
    eng.step()
    gauges = eng._gauge_fields()
    # the step prefilled 40 positions and decoded one: two windows of 4
    # summaries and 9 rows
    assert gauges["cache_rows_live"] == 17 and gauges["tokens_in_flight"] == 41
    assert gauges["cache_rows_per_token"] == pytest.approx(17 / 41)
    assert gauges["summary_blocks"] == 2 and gauges["window_blocks"] == 3
    plain = ServingEngine(
        CausalLM(TransformerConfig.tiny(max_seq_len=64)), {}, max_slots=2)
    assert "cache_rows_live" not in plain._gauge_fields()
    assert plain.trace_counts()["eva"] == 0


# (each feature refused when an engine is BUILT: tests/test_cache_regime.py,
# one table over the regimes)
def test_the_same_features_are_refused_on_a_warm_engine(params):
    eng = _engine(params)
    for name, call in (
        ("prefix_cache", lambda: eng.set_prefix_cache(True)),
        ("spec_decode", lambda: eng.set_speculation(SpecConfig(k=2))),
        ("role 'decode'", lambda: eng.set_role("decode")),
        ("hand-off", lambda: eng.acquire(None)),
    ):
        with pytest.raises(NotImplementedError, match=name):
            call()
    eng.set_prefix_cache(False)  # turning a feature OFF is no request for it
    eng.set_speculation(None)
    eng.set_role("colocated")


@pytest.mark.parametrize("eva_model", [True, False])
def test_a_request_longer_than_max_seq_len_is_refused_at_add_request(
        params, eva_model):
    if eva_model:
        eng = _engine(params)
    else:
        eng = ServingEngine(
            CausalLM(TransformerConfig.tiny(max_seq_len=128)), {}, max_slots=2)
    with pytest.raises(ValueError, match="max_seq_len 128"):
        eng.add_request(_ids(100), max_new_tokens=29)
    eng.add_request(_ids(100), max_new_tokens=28)  # exactly the table's width


# --------------------------------------------------------------------------- #
# shared code: without the class, the dense model's programs are the parent's
# --------------------------------------------------------------------------- #
# sha256 of the lowered text at commit 276d936 (the parent of ISSUE 30), as
# this test lowers them: the tiny dense model's forward, and the engine's
# decode and 16-wide prefill over abstract weights. "decode" was re-pinned
# at ISSUE 31 (parent b456505, where it read e0b6a19d9582e3dd): a decode call
# of one position pins its q/k/v projections two-dimensional
# (``transformer.qkv_in_place``), one ``optimization_barrier`` a layer more;
# "forward" is the one of 276d936 still. "prefill" was re-pinned at ISSUE 38
# (parent 6dd8b99, where it read 0e358c278cc3492e): every prefill hands the
# model the row it samples from (``CausalLM(logits_at=)``), so the final norm
# and the head run over that one row and not over the padded bucket; nothing
# else of the text moved. The two train cells' own configurations (the
# gradient of ``CausalLM.loss_fn`` at the cells' rows and widths, abstract
# weights) were pinned at b456505; "train-moe-conv-1chip" was re-pinned at
# ISSUE 39 (parent 03870a6, where it read 53210cc0beedf81c): its expert
# layers move rows between token order and expert order by gathers alone
# (``ops.moe._to_experts`` / ``_to_tokens``), no scatter-add forward or
# backward; the dense entries did not move
PARENT = {
    "forward": "185f96c4ca8bf1ef",
    "decode": "afba1e62f208a5b5",
    "prefill": "fe2eb56c78af16d5",
    "train-dense-1chip": "9ce6fdb3752bf4cd",
    "train-moe-conv-1chip": "a959e69a400d65ff",
}


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _dense_programs() -> dict:
    cfg = TransformerConfig.tiny(max_seq_len=64)
    model = CausalLM(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    shapes = nn.unbox(shapes)
    eng = ServingEngine(model, {}, max_slots=2, block_size=8)
    cache = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), eng.cache)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    key = jax.ShapeDtypeStruct(eng._key.shape, eng._key.dtype)
    table = eng._max_table
    texts = {
        "forward": jax.jit(lambda p, ids: model.apply({"params": p}, ids)).lower(
            shapes, i32(2, 32)).as_text(),
        "decode": eng._decode_fn.lower(
            shapes, cache, i32(2, 1), i32(2, table), i32(2), i32(2), f32(2),
            key).as_text(),
        "prefill": eng._prefill_fn.lower(
            shapes, cache, i32(1, 16), i32(1, table), i32(1), i32(1), key,
            f32(1)).as_text(),
    }
    return {name: _sha(text) for name, text in texts.items()}


def _train_gradient(name: str) -> str:
    from harness import cell as cells

    cell = cells.load_cell(name)
    spec, cfg = cell["spec"], cell["config"]
    _, weights = common.modules_of(cfg)
    seq = spec["traffic"]["seq_len"]
    model = CausalLM(common.program_config(
        cfg, max_seq_len=seq, remat=spec["remat"], dtype=spec["compute_dtype"]))
    ids = jax.ShapeDtypeStruct((spec["rows_per_chip"], seq), jnp.int32)
    return _sha(jax.jit(jax.grad(CausalLM.loss_fn(model))).lower(
        weights.abstract_tree(cfg, jnp.float32), {"input_ids": ids}).as_text())


def test_without_the_class_the_dense_programs_are_the_parents():
    got = _dense_programs()
    assert got == {name: PARENT[name] for name in got}


@pytest.mark.parametrize("name", ["train-dense-1chip", "train-moe-conv-1chip"])
def test_a_train_cells_gradient_lowers_to_the_parents_text(name):
    """Neither train step takes a decode branch: the gradient of the cell's
    own loss over its own configuration is the text it was."""
    assert _train_gradient(name) == PARENT[name]

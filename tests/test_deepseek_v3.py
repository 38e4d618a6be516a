"""Multi-head latent attention with YaRN rope, a dense layer and layers of
group-limited sigmoid-routed experts with a shared expert, through the normal
path — ``CausalLM`` and ``ServingEngine``'s own prefill (expanded) and decode
(absorbed, through the latent cache) programs — held against the benchmark's
plain float32 reference (``benchmark/harness/deepseek_v3_reference.py``: the
expanded form only, one full causal pass) on seeded weights
(``deepseek_v3_weights.py``), at widths the CPU can hold that keep every
ratio of the published ones."""

import hashlib
import math
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

import tiny_deepseek_v3 as tiny  # noqa: E402
from harness import common  # noqa: E402
from harness import deepseek_v3_reference as ref  # noqa: E402
from harness import deepseek_v3_weights as W  # noqa: E402
from harness import deepseek_v3_work as work  # noqa: E402

from accelerate_tpu.models import CausalLM, TransformerConfig  # noqa: E402
from accelerate_tpu.models import transformer as transformer_module  # noqa: E402
from accelerate_tpu.models.transformer import (  # noqa: E402
    MoE, _scale_rope_freqs, yarn_mscale)
from accelerate_tpu.ops import attention as attn_ops  # noqa: E402
from accelerate_tpu.ops import moe as moe_ops  # noqa: E402
from accelerate_tpu.ops import paged_attention as paged_ops  # noqa: E402
from accelerate_tpu.ops.attention import PagedKVState  # noqa: E402
from accelerate_tpu.ops.flash_attention import (  # noqa: E402
    flash_attention, kernel_interpret_mode)
from accelerate_tpu.serving import ServingEngine, SpecConfig  # noqa: E402
from accelerate_tpu.serving import engine as engine_module  # noqa: E402

SEED = 2**31 + 40
TOL = 2e-4  # float32 both sides; the absorbed form sums in another order
CFG = tiny.config()


def _model(cfg=CFG, **kw):
    return CausalLM(common.program_config(
        cfg, max_seq_len=cfg["max_position_embeddings"], dtype="float32", **kw))


@pytest.fixture(scope="module")
def params():
    return W.make_tree(CFG, SEED, jnp.float32)


def _flat(tree):
    return {jax.tree_util.keystr(p): x
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _ids(n, seed=0, cfg=CFG):
    return np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], n).astype(np.int32)


# --------------------------------------------------------------------------- #
# the trees, the counts, rope and the router, against numbers worked here
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("held", [4, 16], ids=["share", "whole"])
def test_seeded_tree_is_the_programs_tree(held):
    cfg = tiny.config(n_routed_experts=held)
    made = jax.eval_shape(
        _model(cfg).init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    mine = {k: (v.shape, v.dtype) for k, v in _flat(
        nn.meta.unbox(made["params"])).items()}
    seeded = {k: (v.shape, v.dtype) for k, v in _flat(
        W.abstract_tree(cfg, jnp.float32)).items()}
    assert mine == seeded
    assert work.params_held(cfg) == sum(
        math.prod(shape) for shape, _ in mine.values())


def test_params_held_at_the_published_widths_is_the_issues_table():
    cfg = tiny.real()
    parts = work.parts(cfg)
    assert round(parts["mla"] / 1e6, 1) == 187.1
    assert round(parts["dense_mlp"] / 1e6, 1) == 396.4
    assert round(parts["expert"] / 1e6, 2) == 44.04
    assert round(work.params_held(cfg) / 1e6, 1) == 4565.7
    assert work.latent_row_bytes(cfg) == 5760  # 576 x 2 B x 5 layers
    assert cfg["shared_expert_intermediate_size"] == (
        cfg["moe_intermediate_size"] * cfg["n_shared_experts"])


def test_yarn_frequencies_and_m_are_the_numbers_worked_by_hand():
    """DeepSeek-V3's own: d 64, theta 1e4, factor 40 over 4096, beta 32 / 1.
    low = floor(64 ln(4096 / (2 pi 32)) / (2 ln 1e4)) = floor(10.47) = 10,
    high = ceil(64 ln(4096 / (2 pi)) / (2 ln 1e4)) = ceil(22.51) = 23."""
    scaling = tiny.real()["rope_scaling"]
    plain = 1e4 ** (-np.arange(0, 64, 2) / 64.0)
    got = np.asarray(_scale_rope_freqs(jnp.asarray(plain, jnp.float32), scaling, 1e4))
    assert math.floor(64 * math.log(4096 / (2 * math.pi * 32)) / (2 * math.log(1e4))) == 10
    assert math.ceil(64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(1e4))) == 23
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)  # fast: as they were
    np.testing.assert_allclose(got[23:], plain[23:] / 40, rtol=1e-6)  # slow: / factor
    ramp = 6 / 13  # i = 16
    np.testing.assert_allclose(
        got[16], plain[16] / 40 * ramp + plain[16] * (1 - ramp), rtol=1e-6)
    np.testing.assert_allclose(got, ref.rope_frequencies(tiny.real()), rtol=1e-6)
    assert abs(yarn_mscale(40, 1) - 1.3688879) < 1e-6
    assert abs(ref.softmax_scale(tiny.real()) - 192 ** -0.5 * 1.3688879 ** 2) < 1e-6
    assert yarn_mscale(1.0, 1) == 1.0


def _x(b, s, seed=1, h=CFG["hidden_size"]):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, s, h), jnp.float32)


def _subtree(lw: dict, prefix: str) -> dict:
    out: dict = {}
    for name, leaf in lw.items():
        if name.startswith(prefix + "/"):
            node = out
            parts = name[len(prefix) + 1:].split("/")
            for part in parts[:-1]:
                node = node.setdefault(part, {})
            node[parts[-1]] = leaf
    return out


def _moe(cfg, lw, x):
    pcfg = common.program_config(cfg, max_seq_len=256, dtype="float32")
    return MoE(pcfg).apply({"params": _subtree(lw, "moe")}, x)


def _loop_ff(cfg, lw, x):
    """The expert layer a token at a time, in numpy: sigmoid scores, the bias
    for the choice alone, a group's score the sum of its two largest, the best
    groups kept, the choices inside them, weights over their sum."""
    x = np.asarray(x, np.float64)
    router = np.asarray(lw["moe/router/kernel"], np.float64)
    bias = np.asarray(lw["moe/expert_bias"], np.float64)
    kernels = [np.asarray(lw[f"moe/{n}"], np.float64)
               for n in ("gate_proj", "up_proj", "down_proj")]
    groups, kept, k = cfg["n_group"], cfg["topk_group"], cfg["num_experts_per_tok"]
    out = np.zeros_like(x)
    chosen = []
    for t, row in enumerate(x.reshape(-1, x.shape[-1])):
        s = 1 / (1 + np.exp(-(row @ router)))
        biased = (s + bias).reshape(groups, -1)
        best = np.argsort(-np.sort(biased, axis=1)[:, -2:].sum(1), kind="stable")[:kept]
        masked = np.full_like(biased, -np.inf)
        masked[best] = biased[best]
        sel = np.argsort(-masked.ravel(), kind="stable")[:k]
        assert set(sel // biased.shape[1]) <= set(best)
        chosen.append(sel)
        w = s[sel] / (s[sel].sum() + 1e-20) * cfg["routed_scaling_factor"]
        for e, we in zip(sel, w):
            local = e - cfg["expert_offset"]
            if 0 <= local < cfg["n_routed_experts"]:
                g, u, d = (kern[local] for kern in kernels)
                gate = row @ g
                out.reshape(-1, x.shape[-1])[t] += we * (
                    (gate / (1 + np.exp(-gate)) * (row @ u)) @ d)
    return out, np.asarray(chosen)


def test_group_limited_choice_is_the_loop_written_here():
    whole = tiny.config(n_routed_experts=16)
    lw = W.layer_view(W.make_tree(whole, SEED, jnp.float32), whole, 1)
    x = _x(2, 19, seed=3)
    want, chosen = _loop_ff(whole, lw, x)
    with jax.default_matmul_precision("highest"):
        shared = ref.shared_ff(x, lw)
        got = _moe(whole, lw, x) - shared
        theirs = ref.routed_ff(x, lw, whole)
    assert float(np.max(np.abs(np.asarray(got) - want))) < TOL
    assert float(np.max(np.abs(np.asarray(theirs) - want))) < TOL
    # the limit bites: without it another choice is made for some token
    free = np.argsort(-(1 / (1 + np.exp(-(np.asarray(x, np.float64).reshape(
        -1, x.shape[-1]) @ np.asarray(lw["moe/router/kernel"], np.float64))))
        + np.asarray(lw["moe/expert_bias"], np.float64)), axis=1)[:, :3]
    assert any(set(a) != set(b) for a, b in zip(free, chosen))


def test_the_shares_add_up_to_the_uncut_layer():
    """Experts 0-3, 4-7, 8-11 and 12-15 of the router's 16 (four groups of
    four, two kept), each share with the shared expert whole as every chip of
    the four computes it: the four routed parts and the shared expert counted
    once are the reference's uncut layer."""
    x = _x(2, 29, seed=5)
    whole = tiny.config(n_routed_experts=16)
    lw = W.layer_view(W.make_tree(whole, SEED, jnp.float32), whole, 1)
    with jax.default_matmul_precision("highest"):
        shared = ref.shared_ff(x, lw)
        want = ref.routed_ff(x, lw, whole) + shared
        parts = []
        for offset in (0, 4, 8, 12):
            cfg = tiny.config(expert_offset=offset)
            slw = W.layer_view(W.make_tree(cfg, SEED, jnp.float32), cfg, 1)
            parts.append(_moe(cfg, slw, x) - shared)
            assert float(jnp.max(jnp.abs(
                parts[-1] - ref.routed_ff(x, slw, cfg)))) < TOL
    assert float(jnp.max(jnp.abs(sum(parts) + shared - want))) < TOL
    # no share is the layer: each leaves out what the others hold
    assert all(float(jnp.max(jnp.abs(p + shared - want))) > 100 * TOL for p in parts)


@pytest.mark.parametrize("length", [31, 64, 150])
def test_full_forward_matches_the_reference(params, length):
    ids = jnp.asarray(np.stack([_ids(length, 1), _ids(length, 2)]))
    got = _model().apply({"params": params}, ids)
    with jax.default_matmul_precision("highest"):
        want = ref.forward(params, CFG, ids)
    assert float(jnp.max(jnp.abs(got - want))) < 5 * TOL


def test_the_reference_regenerates_the_programs_weights(params):
    assert W.probe(params, CFG, SEED, jnp.float32) < 1e-6
    moved = jax.tree.map(lambda x: x, params)
    moved["layer_2"]["attn"]["kv_b_proj"]["kernel"] = 1.5 * params[
        "layer_2"]["attn"]["kv_b_proj"]["kernel"]
    assert W.probe(moved, CFG, SEED, jnp.float32) > 0.3


def test_the_dense_decode_cache_is_refused_by_name(params):
    with pytest.raises(NotImplementedError, match="serve it through ServingEngine"):
        _model().apply({"params": params}, jnp.zeros((1, 4), jnp.int32),
                       decode=True, mutable=["cache"])


# --------------------------------------------------------------------------- #
# the two forms of one layer, and the kernel against the gather form
# --------------------------------------------------------------------------- #
def _state(tables, cache_len, lengths, num_blocks, block_size, **kw):
    return PagedKVState(
        block_table=jnp.asarray(tables, jnp.int32),
        cache_len=jnp.asarray(cache_len, jnp.int32),
        lengths=jnp.asarray(lengths, jnp.int32),
        num_blocks=num_blocks, block_size=block_size, **kw)


def test_the_absorbed_form_is_the_expanded_form_at_equal_inputs(params):
    """One sequence: 21 positions expanded in one pass, against 16 expanded
    (a prefill: it writes the latent rows) and then 5 more ABSORBED onto the
    cached rows — several positions at once through the gather form, which is
    what a decode step's kernel is held against."""
    model = _model()
    ids = jnp.asarray(_ids(21, 9))[None]
    want = model.apply({"params": params}, ids)
    table = np.arange(1, 9)[None]
    fresh = _state(table, [0], [16], 9, 4, fresh=True)
    cache = model.init(jax.random.PRNGKey(0), ids[:, :1], decode=True,
                       paged=fresh)["cache"]
    first, mutated = model.apply(
        {"params": params, "cache": cache}, ids[:, :16], decode=True,
        paged=fresh, mutable=["cache"])
    more, mutated = model.apply(
        {"params": params, "cache": mutated["cache"]}, ids[:, 16:], decode=True,
        paged=_state(table, [16], [5], 9, 4), mutable=["cache"])
    assert float(jnp.max(jnp.abs(first - want[:, :16]))) < TOL
    assert float(jnp.max(jnp.abs(more - want[:, 16:]))) < TOL
    # what was cached: 21 rows a layer, [c_kv | k_rope] and zeros behind them
    rows = np.asarray(mutated["cache"]["layer_0"]["attn"]["latent_pool"])
    assert rows.shape == (9, 4, 128)
    live = rows[1:].reshape(-1, 128)[:21]
    assert np.all(np.abs(live[:, :32]).sum(1) > 0) and not live[:, 32:].any()
    assert not rows[1:].reshape(-1, 128)[21:].any()


@pytest.mark.parametrize("stacked", [False, True], ids=["pool", "stack"])
def test_the_latent_kernel_interpreted_is_the_gather_form(stacked, monkeypatch):
    """Slots of 0, a part of a block, whole blocks and several chunks of
    positions; the pool alone and as one layer of a stack of three."""
    monkeypatch.setattr(paged_ops, "CHUNK_ROWS", 32)  # 4 blocks a chunk
    heads, width, value, bs, nb = 4, 128, 96, 8, 40
    key = jax.random.PRNGKey(3)
    pool = jax.random.normal(key, ((3,) if stacked else ()) + (nb, bs, width))
    q = jax.random.normal(jax.random.fold_in(key, 1), (5, 1, heads, 104))
    lens = [0, 5, 31, 64, 100]
    tables = np.zeros((5, 16), np.int32)
    free = iter(np.random.default_rng(0).permutation(np.arange(1, nb)))
    for b, n in enumerate(lens):
        for t in range(n // bs + 1):
            tables[b, t] = next(free)
    layer = jnp.asarray(1) if stacked else None
    gather = attn_ops.latent_attention(
        q, pool, _state(tables, lens, [1] * 5, nb, bs), value_width=value,
        scale=0.3, layer=layer)
    with kernel_interpret_mode():
        state = _state(tables, lens, [1] * 5, nb, bs, single_device=True)
        assert attn_ops.latent_kernel_eligible(state, 1, pool)
        kernel = attn_ops.latent_attention(
            q, pool, state, value_width=value, scale=0.3, layer=layer)
    assert gather.shape == kernel.shape == (5, 1, heads, value)
    assert float(jnp.max(jnp.abs(gather - kernel))) < 1e-5


def test_flash_at_a_value_narrower_than_the_score_is_the_plain_attention():
    key = jax.random.PRNGKey(0)
    q, k = (jax.random.normal(jax.random.fold_in(key, i), (1, 256, 2, 24))
            for i in range(2))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 256, 2, 16))
    want = attn_ops.xla_attention(q, k, v, causal=True, scale=0.2)
    with kernel_interpret_mode():
        got = flash_attention(q, k, v, scale=0.2, block_q=128, block_k=128)
        with pytest.raises(NotImplementedError, match="forward pass alone"):
            jax.grad(lambda q: flash_attention(q, k, v).sum())(q)
    assert got.shape == (1, 256, 2, 16)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


# --------------------------------------------------------------------------- #
# the engine's own programs: prefill then decode is one forward pass
# --------------------------------------------------------------------------- #
def _serve(params, monkeypatch, schedule, max_slots=3, cfg=CFG, block_size=8, **kw):
    """Drive an engine over ``schedule`` — [(steps to make first, prompt,
    max_new_tokens)] — and read the LOGITS its own prefill and decode
    programs sampled from, as ``tests/test_qwen3_next.py`` does."""
    seen = {"prefill": [], "decode": []}
    real = engine_module.sample_tokens
    now = {}

    def sample(logits, *a, **kws):
        kind = now["tracing"]  # read while the program is traced
        jax.debug.callback(lambda x: seen[kind].append(np.asarray(x)), logits,
                           ordered=True)
        return real(logits, *a, **kws)

    monkeypatch.setattr(engine_module, "sample_tokens", sample)
    eng = ServingEngine(_model(cfg), params, max_slots=max_slots,
                        block_size=block_size, **kw)
    calls = {"prefill": [], "decode": []}
    flying = []
    prefill_fn, decode_fn = eng._prefill_fn, eng._decode_fn

    def prefill(p, cache, ids, table, length, *rest):
        blocks = [int(t) for t in np.asarray(table)[0] if t]
        req = next(s.request for s in eng.scheduler.slots
                   if s.busy and s.blocks == blocks)
        calls["prefill"].append((req.request_id, int(length[0]) - 1))
        flying.append(eng._ahead is not None)
        now["tracing"] = "prefill"
        return prefill_fn(p, cache, ids, table, length, *rest)

    def decode(p, cache, tokens, tables, cache_lens, lengths, *rest):
        calls["decode"].append([
            (i, s.request.request_id, int(np.asarray(cache_lens)[i]))
            for i, s in enumerate(eng.scheduler.slots)
            if int(np.asarray(lengths)[i])])
        now["tracing"] = "decode"
        return decode_fn(p, cache, tokens, tables, cache_lens, lengths, *rest)

    eng._prefill_fn, eng._decode_fn = prefill, decode
    out = {}
    for steps_first, prompt, new in schedule:
        for _ in range(steps_first):
            eng.step()
        out[eng.add_request(prompt, max_new_tokens=new)] = (prompt, [], [])
    while eng.has_work:
        eng.step()
    jax.effects_barrier()
    assert len(seen["prefill"]) == len(calls["prefill"])
    assert len(seen["decode"]) == len(calls["decode"])
    for logits, (rid, position) in zip(seen["prefill"], calls["prefill"]):
        out[rid][2].append((position, logits[0]))
    for logits, rows in zip(seen["decode"], calls["decode"]):
        for slot, rid, position in rows:
            out[rid][2].append((position, logits[slot]))
    for rid, (_, tokens, _) in out.items():
        tokens += eng.result(rid)
    return eng, out, flying


def _hold_against_one_forward_pass(params, served, cfg=CFG):
    worst = 0.0
    for prompt, tokens, logits in served.values():
        assert len(tokens) >= 1
        seq = jnp.asarray(np.concatenate([prompt, tokens]).astype(np.int32))[None]
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref.forward(params, cfg, seq))[0]
        assert {p for p, _ in logits} >= set(
            range(len(prompt) - 1, len(prompt) + len(tokens) - 1))
        for position, got in logits:
            if position < len(seq[0]):
                worst = max(worst, float(np.max(np.abs(got - want[position]))))
    return worst


@pytest.mark.parametrize("kernel,budget,flash", [
    (False, None, False), (True, None, False), (False, 1 << 12, False),
    (True, None, True),
], ids=["gather", "latent_decode", "in_parts", "flash_real_rows"])
def test_prefill_then_decode_through_the_latent_cache_is_one_forward_pass(
        params, monkeypatch, kernel, budget, flash):
    """Three requests of unequal length in three slots, the later ones
    prefilled while a decode step of the earlier is in flight: the prefill
    EXPANDS and writes latent rows alone, every decode step reads them
    ABSORBED — through the gather form, and through the ``latent_decode``
    kernel (interpreted) — and both are the reference's one full pass; so
    they are under a budget so small (``FORWARD_PART_BYTES``) that every
    prefill walks its heads in groups and its experts' rows in parts. Every
    prefill hands attention its prompt's real length, and the second prompt
    fills half its bucket and one token: the rows past it come out of
    attention as zeros (the xla path; ``flash_real_rows``: the interpreted
    ``flash_fwd`` kernel told both lengths) and nothing real moves."""
    schedule = [(0, _ids(23, 1), 12), (4, _ids(65, 2), 9), (2, _ids(8, 3), 14)]
    taken = {}
    lengths = []
    if flash:
        from accelerate_tpu.ops import flash_attention as flash_ops

        fwd = flash_ops._fwd

        def told(q, k, v, kv_lengths, *a):
            lengths.append((q.shape[2], kv_lengths is not None, a[-1] is not None))
            return fwd(q, k, v, kv_lengths, *a)

        monkeypatch.setattr(flash_ops, "_fwd", told)
        # the dispatch of a TPU at this test's widths: whole sublanes
        monkeypatch.setattr(
            attn_ops, "flash_self_attention_eligible", lambda s: s % 8 == 0)
    if budget:
        def parts(nbytes, of, _=None):
            n = attn_ops.forward_parts(nbytes, of, budget)
            taken[of] = max(n, taken.get(of, 1))
            return n

        monkeypatch.setattr(transformer_module, "forward_parts", parts)
        monkeypatch.setattr(moe_ops, "forward_parts", parts)
    if kernel:
        with kernel_interpret_mode():
            eng, served, flying = _serve(params, monkeypatch, schedule)
    else:
        eng, served, flying = _serve(params, monkeypatch, schedule)
    assert eng.decode_ahead and flying == [False, True, True]
    worst = _hold_against_one_forward_pass(params, served)
    print("engine vs one forward pass, widest logit error:", worst)
    assert worst < 5 * TOL
    counts = eng.trace_counts()
    assert counts["decode"] == 1 and counts["mla_decode_kernel"] == int(kernel)
    assert counts["mla_prefill_expanded"] == counts["prefill"] >= 2
    assert counts["flash_real_rows"] == counts["prefill"]
    # 23 + 65 + 8 real tokens in buckets of 32 + 128 + 8
    assert eng._gauge_fields()["prefill_real_token_share"] == 96 / 168
    if flash:  # a kernel call a layer a bucket, each told both lengths
        assert sorted(set(lengths)) == [
            (8, True, True), (32, True, True), (128, True, True)]
    assert counts["kv_in_place"] == counts["prefill"] + 1
    assert eng.pool.stats()["allocated"] == 0 and eng.decode_ahead_share > 0.5
    if budget:  # the heads of a prefill, the tokens of its 32 / 128 / 8 rows
        assert taken[CFG["num_attention_heads"]] > 1
        assert taken[32] > 1 and taken[128] > 1, taken


def test_the_cache_holds_latent_rows_and_nothing_a_head(params):
    """By name and by bytes: one ``latent_pool`` a layer, a row of [c_kv 24 |
    k_rope 8] on one 128-lane tile; per-head K and V would be 4 heads x (24 +
    16) values a position a layer."""
    eng = ServingEngine(_model(), params, max_slots=2, block_size=8)
    leaves = _flat(eng.cache)
    assert len(leaves) == 3 and all(
        k.endswith("['attn']['latent_pool']") for k in leaves)
    assert {v.shape for v in leaves.values()} == {(eng.num_blocks, 8, 128)}
    assert eng.kv_bytes_per_token == 3 * 128 * 4 and eng.state_bytes_per_slot == 0
    assert eng.kv_pool_bytes == sum(v.nbytes for v in leaves.values())
    fields = eng._gauge_fields()
    assert fields["latent_row_bytes"] == 3 * 128 * 4 == fields["kv_bytes_per_token"]
    # the published row: 32 values a layer, which the padding makes 128
    assert work.latent_row_bytes(CFG) == 3 * 32 * 2
    # kv_b_proj is in memory once: the engine's arguments are the seeded tree
    assert sum(v.nbytes for v in jax.tree.leaves(eng.params)) == (
        4 * work.params_held(CFG))
    assert sum("kv_b_proj" in k for k in _flat(eng.params)) == 3


WHY = "not written for latent attention"


# (each feature refused when an engine is BUILT: tests/test_cache_regime.py,
# one table over the regimes)
def test_the_same_features_are_refused_on_a_warm_engine(params):
    eng = ServingEngine(_model(), params, max_slots=2, block_size=8)
    with pytest.raises(NotImplementedError, match="prefix_cache.*" + WHY):
        eng.set_prefix_cache(True)
    with pytest.raises(NotImplementedError, match="spec_decode.*" + WHY):
        eng.set_speculation(SpecConfig(k=2))
    with pytest.raises(NotImplementedError, match="role.*" + WHY):
        eng.set_role("prefill")
    with pytest.raises(NotImplementedError, match="hand-off.*" + WHY):
        eng.acquire(None)
    assert eng.decode_ahead  # none of them landed it


# --------------------------------------------------------------------------- #
# what the configuration refuses, and what it leaves as it was
# --------------------------------------------------------------------------- #
_MLA = dict(q_lora_rank=32, kv_lora_rank=24, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16)
_MOE = dict(num_experts=8, moe_router="sigmoid", num_experts_per_tok=2)


@pytest.mark.parametrize("kw,why", [
    (dict(_MLA, num_kv_heads=2), r"cannot be combined with \['num_kv_heads'\]"),
    (dict(_MLA, head_dim=32), r"\['head_dim'\]"),
    (dict(_MLA, qk_norm=True), r"\['qk_norm'\]"),
    (dict(_MLA, attn_output_gate=True), "attn_output_gate"),
    (dict(_MLA, sliding_window=16), "sliding_window"),
    (dict(_MLA, attention_class="eva"), "attention_class"),
    (dict(_MLA, fused_kernels=True), "fused_kernels"),
    (dict(_MLA, fp8=True), r"\['fp8'\]"),
    (dict(_MLA, use_rope=False), "use_rope=False"),
    (dict(_MLA, layer_types=("conv", "full_attention")), "layer_types"),
    (dict(_MLA, qk_rope_head_dim=7), "even"),
    (dict(q_lora_rank=32), "set kv_lora_rank too"),
    (dict(_MLA, q_lora_rank=None), "must be set"),
    (dict(rope_scaling={"rope_type": "yarn", "factor": 4.0}),
     "requires keys.*original_max_position_embeddings"),
    (dict(rope_scaling={"rope_type": "yarn", "factor": 4.0,
                        "original_max_position_embeddings": 32},
          fused_kernels=True), "scales cos and sin"),
    (dict(_MOE, moe_n_group=3), "moe_n_group 3"),
    (dict(_MOE, moe_n_group=2, moe_topk_group=3), "moe_topk_group 3"),
    (dict(_MOE, moe_n_group=4, moe_topk_group=1, num_experts_per_tok=3),
     "hold the 3 choices"),
    (dict(_MOE, moe_n_group=2, moe_router="softmax"), "written for moe_router 'sigmoid'"),
])
def test_config_refuses_what_it_cannot_be(kw, why):
    with pytest.raises(ValueError, match=why):
        TransformerConfig.tiny(**kw)


def test_latent_attention_sets_the_score_width_and_takes_groups():
    cfg = TransformerConfig.tiny(**_MLA, **_MOE, moe_n_group=4, moe_topk_group=2)
    assert cfg.head_dim == 24 and cfg.num_kv_heads == cfg.num_heads


# sha256[:16] of the lowered text at commit 7ba56be (the parent of ISSUE 40):
# the gradient of each expert train cell at its own shapes over abstract
# weights, the serving cell's expert layer (``MoE(decode=True)``) at a decode
# step's 64 rows and at its widest prefill's 16,384, and flash forward +
# backward at equal widths (interpreted)
_AS_IT_WAS = {
    "train-moe-conv-1chip": "a959e69a400d65ff",
    "train-ssm-moe-1chip": "3b55b402b0caef26",
    "serve-gdn-moe-sat.decode": "0c9f6e73be16588b",
    "serve-gdn-moe-sat.prefill": "309d33b59e6de56b",
    "flash": "fe1c4bc1d274e1e1",
}


def _lowered(what: str) -> str:
    from harness import cell as cells

    if what == "flash":
        q = jax.ShapeDtypeStruct((1, 512, 4, 128), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((1, 512, 2, 128), jnp.bfloat16)
        with kernel_interpret_mode():
            return jax.jit(jax.grad(
                lambda q, k, v: flash_attention(q, k, v).astype(jnp.float32).sum(),
                argnums=(0, 1, 2))).lower(q, k, k).as_text()
    name, _, program = what.partition(".")
    cell = cells.load_cell(name)
    spec, cfg = cell["spec"], cell["config"]
    if program:  # the serving path's expert layer alone
        pcfg = common.program_config(cfg, max_seq_len=16384, dtype="bfloat16")
        moe = MoE(pcfg, decode=True)
        h = cfg["hidden_size"]
        shapes = jax.eval_shape(lambda: nn.meta.unbox(moe.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, h), jnp.bfloat16))["params"]))
        rows = {"decode": 64, "prefill": 16384}[program]
        return jax.jit(lambda p, x: moe.apply(
            {"params": p}, x, mutable=["intermediates"])).lower(
                shapes, jax.ShapeDtypeStruct((1, rows, h), jnp.bfloat16)).as_text()
    _, weights = common.modules_of(cfg)
    seq = spec["traffic"]["seq_len"]
    model = CausalLM(common.program_config(
        cfg, max_seq_len=seq, remat=spec["remat"], dtype=spec["compute_dtype"]))
    ids = jax.ShapeDtypeStruct((spec["rows_per_chip"], seq), jnp.int32)
    return jax.jit(jax.grad(CausalLM.loss_fn(model))).lower(
        weights.abstract_tree(cfg, jnp.float32), {"input_ids": ids}).as_text()


@pytest.mark.parametrize("what", list(_AS_IT_WAS))
def test_what_was_there_lowers_to_the_text_it_had(what):
    """Groups of (1, 1), a serving call under the part budget and flash at
    equal widths: the three expert cells' programs and every flash caller's
    kernels are byte for byte what they were before this PR."""
    assert hashlib.sha256(_lowered(what).encode()).hexdigest()[:16] == _AS_IT_WAS[what]

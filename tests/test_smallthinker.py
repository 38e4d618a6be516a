"""Window layers that hold a ring a slot beside full layers that hold every
row, rope by layer kind, a router that reads the attention's input and ReGLU
experts, through the normal path — ``CausalLM`` and ``ServingEngine``'s own
prefill and decode programs — held against the benchmark's plain float32
reference (``benchmark/harness/smallthinker_reference.py``: one full causal
pass, no cache) on seeded weights (``smallthinker_weights.py``), at widths the
CPU can hold: a window of 8 positions in blocks of 4, two periods of (full,
sliding, sliding, sliding)."""

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

import smallthinker_faults as planted  # noqa: E402
import tiny_deepseek_v3  # noqa: E402
import tiny_qwen3_next  # noqa: E402
import tiny_smallthinker as tiny  # noqa: E402
from harness import common  # noqa: E402
from harness import deepseek_v3_weights, qwen3_next_weights  # noqa: E402
from harness import smallthinker_reference as ref  # noqa: E402
from harness import smallthinker_weights as W  # noqa: E402
from harness import smallthinker_work as work  # noqa: E402

from accelerate_tpu.models import CausalLM, TransformerConfig  # noqa: E402
from accelerate_tpu.models.transformer import layer_kinds, plan_layers  # noqa: E402
from accelerate_tpu.ops.flash_attention import kernel_interpret_mode  # noqa: E402
from accelerate_tpu.serving import ServingEngine  # noqa: E402
from accelerate_tpu.serving import engine as engine_module  # noqa: E402

SEED = 2**31 + 45
TOL = 1e-3  # float32 both sides; reads 2e-5 to 1e-4
CFG = tiny.config()
RING, BLOCK = CFG["sliding_window_size"], 4


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
# the plan, the tree and the count
# --------------------------------------------------------------------------- #
def test_the_period_is_one_scanned_body_and_the_cell_holds_its_layers_alone():
    kinds = layer_kinds(common.program_config(CFG))
    period = [("full_attention", "moe", False)] + [("sliding_attention", "moe", True)] * 3
    assert kinds == period * 2
    assert [(s, p, r) for s, p, r in plan_layers(kinds)] == [(0, tuple(period), 2)]
    assert W.segments(W.layer_kinds(CFG)) == plan_layers(kinds)
    # the published 52 layers: thirteen periods, one scan
    deep = tiny.config(periods=13)
    assert [(s, len(p), r) for s, p, r in plan_layers(
        layer_kinds(common.program_config(deep)))] == [(0, 4, 13)]
    # the committed file states scan_layers false: eight modules, none stacked
    assert CFG["scan_layers"] is True
    real = tiny.real()
    assert real["scan_layers"] is False
    assert not any(row["stacked"] for row in W.leaf_table(real))
    assert {row["path"][0] for row in W.leaf_table(real)} == {
        "embed", "final_norm", "lm_head", *(f"layer_{i}" for i in range(8))}


@pytest.mark.parametrize("scan", [True, False], ids=["scanned", "alone"])
def test_seeded_tree_is_the_programs_tree(scan):
    cfg = tiny.config(scan_layers=scan)
    model = _model(cfg)
    own = nn.unbox(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"])
    made = W.abstract_tree(cfg, jnp.float32)
    assert {k: (v.shape, v.dtype) for k, v in _flat(own).items()} == {
        k: (v.shape, v.dtype) for k, v in _flat(made).items()}


def test_params_held_and_the_cache_at_the_published_widths_are_the_issues_count():
    cfg = tiny.real()
    tree = W.abstract_tree(cfg, jnp.bfloat16)
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    p = work.parts(cfg)
    assert (p["attn"], p["router"], p["expert"]) == (20_971_520, 163_840, 5_898_240)
    assert work.params_held(cfg) == count == 3_966_937_600  # 3.97 B, 7.93 GB
    # a slot of 16,384 positions: 32 MiB a full layer, 8 MiB a window layer
    held = work.cache_bytes_per_slot(cfg, 16384)
    assert held == {"full": 2 * 32 << 20, "window": 6 * 8 << 20, "uniform": 256 << 20}


# --------------------------------------------------------------------------- #
# the forward pass, and the five faults it must not pass over
# --------------------------------------------------------------------------- #
def _forward_error(params, cfg=CFG, length=40):
    ids = _ids(2 * length, 4, cfg).reshape(2, length)
    with jax.default_matmul_precision("highest"):
        got = _model(cfg).apply({"params": params}, ids)
        want = ref.forward(params, cfg, ids)
    return float(jnp.max(jnp.abs(got - want)))


@pytest.mark.parametrize("length", [5, 8, 40])
def test_full_forward_matches_the_reference(params, length):
    assert _forward_error(params, length=length) < TOL


def test_the_unscanned_stack_is_the_same_forward_pass():
    cfg = tiny.config(scan_layers=False)
    assert _forward_error(W.make_tree(cfg, SEED, jnp.float32), cfg) < TOL


@pytest.mark.parametrize("fault", planted.NAMES[:5])
def test_a_planted_layer_fault_fails_the_same_comparison(params, fault):
    """The band left out of a sliding layer, rope on a full layer, rope left
    off a sliding layer, the router fed the feed-forward's input, ``silu``
    for ``relu``: each moves a logit by orders more than the tolerance."""
    undo = planted.plant(fault)
    try:
        assert _forward_error(params) > 50 * TOL
    finally:
        undo()
    assert _forward_error(params) < TOL


# --------------------------------------------------------------------------- #
# the engine's own programs, through the rings
# --------------------------------------------------------------------------- #
def _serve(params, monkeypatch, schedule, max_slots=4, cfg=CFG, block=BLOCK,
           **kw):
    """Drive an engine over ``schedule`` — [(steps to make first, prompt,
    max_new_tokens)] — and read the LOGITS its own prefill and decode
    programs sampled from, as ``tests/test_deepseek_v3.py`` does."""
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
                        block_size=block, **kw)
    calls = {"prefill": [], "decode": []}
    prefill_fn, decode_fn = eng._prefill_fn, eng._decode_fn

    def prefill(p, cache, ids, table, length, cached, key, temp, slot, *rest):
        req = eng.scheduler.slots[int(slot[0])].request
        calls["prefill"].append((req.request_id, int(length[0]) - 1))
        now["tracing"] = "prefill"
        return prefill_fn(p, cache, ids, table, length, cached, key, temp,
                          slot, *rest)

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
    return eng, out


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


# (a) never reaches the window, (b) crosses it while decoding and wraps its
# rings four times, (c) arrives longer than two windows, (d) ends exactly on
# a ring boundary; the later two prefilled while a decode step is in flight
SCHEDULE = [(0, _ids(3, 1), 3), (0, _ids(5, 2), 30), (3, _ids(19, 3), 9),
            (2, _ids(11, 4), 5)]


@pytest.mark.parametrize("scan", [True, False], ids=["scanned", "alone"])
def test_prefill_then_decode_through_the_rings_is_one_forward_pass(
        params, monkeypatch, scan):
    cfg = tiny.config(scan_layers=scan)
    if not scan:
        params = W.make_tree(cfg, SEED, jnp.float32)
    eng, served = _serve(params, monkeypatch, SCHEDULE, cfg=cfg)
    worst = _hold_against_one_forward_pass(params, served, cfg)
    print("engine vs one forward pass, widest logit error:", worst)
    assert worst < TOL
    counts = eng.trace_counts()
    # the zero-retrace contract over a mix of lengths: buckets 4, 8, 16, 32
    assert counts["decode"] == 1 and counts["prefill"] == 4
    assert counts["window_ring"] == counts["prefill"] + counts["decode"]
    assert counts["flash_real_rows"] == counts["prefill"]
    assert counts["kv_in_place"] == counts["prefill"] + 1
    assert counts["window_decode_kernel"] == counts["decode_attn_kernel"] == 0
    assert eng.decode_ahead and eng.decode_ahead_share > 0.5
    assert eng.pool.stats()["allocated"] == 0
    # (b) wrapped at positions 8, 16, 24, 32; (c) 8, 16, 24; (d) 8
    assert eng._gauge_fields()["ring_wraps_total"] == 4 + 3 + 1


@pytest.mark.parametrize("fault", planted.NAMES[5:])
def test_a_planted_ring_fault_fails_the_engines_comparison(
        params, monkeypatch, fault):
    """The ring not written by the prefill; the ring read one block short
    after a wrap."""
    undo = planted.plant(fault)
    try:
        _, served = _serve(params, monkeypatch, SCHEDULE)
    finally:
        undo()
    assert _hold_against_one_forward_pass(params, served) > 50 * TOL


def test_the_rings_are_read_through_the_decode_kernel_where_it_runs(monkeypatch):
    """Heads of 128 lanes in blocks of whole sublane tiles (a ring of one
    block), the kernel interpreted: a decode step reads each window layer's
    ring through ``paged_decode`` with the ring's own table and
    ``min(position + 1, ring)`` rows, and no band."""
    cfg = tiny.config(periods=1, hidden_size=64, num_attention_heads=2,
                      num_key_value_heads=1, head_dim=128)
    params = W.make_tree(cfg, SEED, jnp.float32)
    with kernel_interpret_mode():
        eng, served = _serve(params, monkeypatch, SCHEDULE[:3], cfg=cfg, block=8)
    assert _hold_against_one_forward_pass(params, served, cfg) < TOL
    counts = eng.trace_counts()
    assert counts["window_decode_kernel"] == counts["decode_attn_kernel"] == 1


def test_a_seat_reused_after_a_longer_request_serves_the_new_one_alone(
        params, monkeypatch):
    """One seat: a request that wrapped its rings, then one that never fills
    them. The short request's length bounds what it reads, so its logits are
    those of a fresh engine and of the reference."""
    long, short = (0, _ids(20, 5), 6), (0, _ids(3, 6), 4)
    _, both = _serve(params, monkeypatch, [long, short], max_slots=1)
    _, alone = _serve(params, monkeypatch, [short], max_slots=1)
    (_, tokens, logits), (_, fresh_tokens, fresh) = (
        list(both.values())[1], list(alone.values())[0])
    assert tokens == fresh_tokens
    assert max(float(np.max(np.abs(a - b)))
               for (_, a), (_, b) in zip(logits, fresh)) < 1e-5
    assert _hold_against_one_forward_pass(params, both) < TOL


def test_a_window_layer_holds_a_ring_a_slot_and_the_pool_is_sized_so(params):
    """By name and by bytes: ``key_ring`` / ``value_ring`` in the six window
    layers, ``ring`` rows a slot whatever its length; ``key_pool`` /
    ``value_pool`` in the two full layers, a row a position; the default pool
    of a 4-slot engine is exactly the sum."""
    eng = ServingEngine(_model(), params, max_slots=4, block_size=BLOCK)
    hkv, d, seq = CFG["num_key_value_heads"], CFG["head_dim"], 128
    row = 2 * hkv * d * 4  # K and V of one position in one layer, float32
    names = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(eng.cache)[0]:
        name = path[-1].key
        names.setdefault(name, []).append(leaf)
    assert sorted(names) == ["key_pool", "key_ring", "value_pool", "value_ring"]
    table = seq // BLOCK
    assert eng.num_blocks == 4 * table + 1 and eng._max_table == table
    ring_blocks = 4 * RING // BLOCK + 1
    # scanned: one leaf a block of the period, stacked over its two repeats
    assert [x.shape[:2] for x in names["key_ring"]] == [(2, ring_blocks)] * 3
    assert [x.shape[:2] for x in names["key_pool"]] == [(2, 4 * table + 1)]
    assert eng.state_bytes_per_slot == 6 * RING * row
    assert eng.kv_bytes_per_token == 2 * row
    assert eng.kv_pool_bytes == sum(
        x.nbytes for x in jax.tree.leaves(eng.cache)) == (
            2 * (4 * seq + BLOCK) * row + 6 * (4 * RING + BLOCK) * row)
    # a request of 30 positions holds ceil(30 / 4) blocks of the full layers
    # and not one block more for its window layers
    rid = eng.add_request(_ids(21, 7), max_new_tokens=9)
    for _ in range(4):
        eng.step()
    slot = next(s for s in eng.scheduler.slots if s.busy)
    assert len(slot.blocks) == -(-30 // BLOCK) and eng.pool.stats()["allocated"] == 8
    fields = eng._gauge_fields()
    assert fields["full_rows_live"] == slot.cache_len > RING
    assert fields["window_rows_live"] == RING
    assert fields["cache_rows_per_token"] == (
        6 * RING + 2 * slot.cache_len) / (8 * slot.cache_len)
    while eng.has_work:
        eng.step()
    assert len(eng.result(rid)) == 9


def test_a_window_that_is_no_whole_number_of_blocks_is_refused():
    with pytest.raises(ValueError, match="whole blocks of block_size 3"):
        ServingEngine(_model(), {}, max_slots=2, block_size=3)


def test_the_same_features_are_refused_on_a_warm_engine(params):
    """What ``tests/test_cache_regime.py`` holds at build, letter for letter,
    is refused on a running engine too, by name and with Reach A2."""
    from accelerate_tpu.serving import SpecConfig

    eng = ServingEngine(_model(), params, max_slots=2, block_size=BLOCK)
    for feature, call in (
            ("prefix_cache", lambda: eng.set_prefix_cache(True)),
            ("spec_decode", lambda: eng.set_speculation(SpecConfig(k=2))),
            ("role 'decode'", lambda: eng.set_role("decode")),
            ("hand-off (acquire)", lambda: eng.acquire(None))):
        with pytest.raises(NotImplementedError) as err:
            call()
        assert str(err.value).startswith(f"{feature} is not written for a stack "
                                         "with 'sliding_attention' layers")
        assert str(err.value).endswith("(ROADMAP Reach A2)")
    assert eng.decode_ahead  # nothing landed


@pytest.mark.parametrize("kw,why", [
    (dict(layer_types=("full_attention", "sliding_attention")), "set both or neither"),
    (dict(layer_types=("full_attention",) * 2, sliding_window=8), "set both or neither"),
    (dict(rope_layout=(0, 1, 1)), "one entry a layer"),
    (dict(rope_layout=(0, 1), use_rope=False), "use_rope=False"),
    (dict(moe_router_pre_attention=True), "needs experts"),
    (dict(layer_types=("full_attention",) * 2, layer_windows=(None, 8)),
     "layer_windows and layer_types"),
    (dict(mlp_activation="gelu"), "unknown mlp_activation"),
])
def test_config_refuses_what_it_cannot_be(kw, why):
    with pytest.raises(ValueError, match=why):
        TransformerConfig.tiny(num_layers=2, **kw)


def test_hf_interop_refuses_what_it_cannot_map_and_names_layer_types(tmp_path):
    import json

    from accelerate_tpu.utils.hf_interop import _export_arch, infer_config_from_hf

    with open(tmp_path / "config.json", "w") as f:
        json.dump({"model_type": "smallthinker", **{
            k: v for k, v in tiny.real().items() if k in (
                "hidden_size", "num_attention_heads", "num_hidden_layers",
                "vocab_size", "rope_layout", "sliding_window_layout")}}, f)
    with pytest.raises(ValueError, match="'smallthinker' is not supported"):
        infer_config_from_hf(str(tmp_path))
    with pytest.raises(ValueError, match=r"carries \['layer_types', "
                       r"'rope_layout', 'moe_router_pre_attention'\]"):
        _export_arch(common.program_config(CFG))


# --------------------------------------------------------------------------- #
# nothing moves for the others
# --------------------------------------------------------------------------- #
# sha256[:16] of the lowered text at commit c056b79 (the parent of ISSUE 45):
# the prefill (bucket 16) and decode programs of a tiny dense engine under a
# sliding window (Mistral's regime) and the decode programs of the tiny
# Qwen3-Next and DeepSeek-V3 engines. The two expert train steps, the
# serving expert layer and flash are pinned by ``tests/test_deepseek_v3.py``
# and ``tests/test_hybrid_moe.py``, LFM2's and Nemotron's programs by
# ``tests/test_eva_attention.py``; all pass unchanged on this tree.
_AS_IT_WAS = {
    "rows.prefill": "ea951be573bff172",
    "rows.decode": "5aec2745213de36d",
    "recurrent.decode": "24aa7b15217fa4f3",
    "latent.decode": "31555d4887173eab",
}


def lowered(what: str) -> str:
    """The text a tiny engine's own program lowers to (no source locations)."""
    regime, program = what.split(".")
    if regime == "rows":
        model = CausalLM(TransformerConfig.tiny(max_seq_len=64, sliding_window=16))
        params = nn.unbox(jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32)))["params"])
        block = 8
    else:
        mod, weights, block = {
            "recurrent": (tiny_qwen3_next, qwen3_next_weights, 4),
            "latent": (tiny_deepseek_v3, deepseek_v3_weights, 8)}[regime]
        cfg = mod.config()
        model = CausalLM(common.program_config(
            cfg, max_seq_len=cfg["max_position_embeddings"], dtype="float32"))
        params = weights.abstract_tree(cfg, jnp.float32)
    params = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), params)
    eng = ServingEngine(model, params, max_slots=2, block_size=block)
    n, reg = eng.max_slots, eng._regime
    none = np.zeros(n, np.int32)
    tables = np.zeros((n, eng._max_table), np.int32)
    if program == "prefill":
        fn, args = eng._prefill_fn, reg.prefill_args(
            np.zeros((1, 16), np.int32), tables[:1], 0, 0, eng._key, 0.0, 0)
    else:
        fn, args = eng._decode_fn, reg.decode_args(
            none[:, None], tables, none, none, eng.sampling.temperatures(),
            eng._key, reg.host_positions())
    specs = jax.eval_shape(lambda *a: a, eng.params, eng.cache, *args)
    return fn.lower(*specs).as_text()


@pytest.mark.parametrize("what", list(_AS_IT_WAS))
def test_what_was_there_lowers_to_the_text_it_had(what):
    """With none of this PR's fields set, a dense engine's prefill and decode
    programs and the recurrent and latent engines' decode programs are byte
    for byte what they were."""
    assert hashlib.sha256(lowered(what).encode()).hexdigest()[:16] == _AS_IT_WAS[what]

"""A stack of Gated DeltaNet layers and gated GQA attention, every layer
followed by softmax-routed experts with a gated shared expert, through the
normal path — ``CausalLM`` and ``ServingEngine``'s own prefill and decode
programs, a recurrent state a slot beside the paged K/V pool — held against
the benchmark's plain float32 reference
(``benchmark/harness/qwen3_next_reference.py``, whose DeltaNet layer is the
token-by-token recurrence itself) on seeded weights
(``qwen3_next_weights.py``), at widths the CPU can hold."""

import contextlib
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

import tiny_qwen3_next as tiny  # noqa: E402
from harness import common  # noqa: E402
from harness import qwen3_next_reference as ref  # noqa: E402
from harness import qwen3_next_weights as W  # noqa: E402
from harness import qwen3_next_work as work  # noqa: E402

from accelerate_tpu.models import CausalLM, TransformerConfig  # noqa: E402
from accelerate_tpu.models.transformer import (  # noqa: E402
    Attention, MoE, layer_kinds, plan_layers)
from accelerate_tpu.ops import gated_delta  # noqa: E402
from accelerate_tpu.ops.flash_attention import kernel_interpret_mode  # noqa: E402
from accelerate_tpu.ops.gated_delta import (  # noqa: E402
    gated_delta_chunked, gated_delta_step)
from accelerate_tpu.serving import ServingEngine, SpecConfig  # noqa: E402
from accelerate_tpu.serving import engine as engine_module  # noqa: E402

SEED = 2**31 + 38
TOL = 2e-4  # float32 both sides; the chunked form sums in another order
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
# the plan, the tree and the count
# --------------------------------------------------------------------------- #
def test_the_period_is_found_and_the_held_layers_stand_alone():
    kinds = layer_kinds(common.program_config(CFG))
    assert kinds == [("linear_attention", "moe")] * 3 + [("full_attention", "moe")]
    plan = plan_layers(kinds)
    assert [(s, len(p), r) for s, p, r in plan] == [(0, 1, 3), (3, 1, 1)]
    assert W.segments(W.layer_kinds(CFG)) == plan  # the benchmark's own rule
    # the published 48 layers: twelve periods of four, one scan
    deep = tiny.config(layers=48)
    assert [(s, len(p), r) for s, p, r in plan_layers(
        layer_kinds(common.program_config(deep)))] == [(0, 4, 12)]
    # the configuration states scan_layers false: four modules, none stacked
    assert CFG["scan_layers"] is False
    assert not any(row["stacked"] for row in W.leaf_table(CFG))
    assert {row["path"][0] for row in W.leaf_table(CFG)} == {
        "embed", "final_norm", "lm_head", "layer_0", "layer_1", "layer_2", "layer_3"}


@pytest.mark.parametrize("layers,scan", [(4, False), (4, True), (8, True)])
def test_seeded_tree_is_the_programs_tree(layers, scan):
    cfg = tiny.config(layers=layers, scan_layers=scan)
    model = _model(cfg)
    own = nn.unbox(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"])
    made = W.abstract_tree(cfg, jnp.float32)
    assert {k: (v.shape, v.dtype) for k, v in _flat(own).items()} == {
        k: (v.shape, v.dtype) for k, v in _flat(made).items()}


def test_params_held_is_the_seeded_trees_count_at_the_published_widths():
    cfg = tiny.real()
    tree = W.abstract_tree(cfg, jnp.bfloat16)
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert work.params_held(cfg) == count == 3_677_613_120  # 3.68 B, 7.36 GB
    # the published widths stand, the cut is written down
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"]) == (2048, 256, 16, 2)
    assert int(cfg["head_dim"] * cfg["partial_rotary_factor"]) == 64
    assert W.gdn_dims(cfg) == (16, 32, 128, 128, 8192)
    assert (cfg["linear_conv_kernel_dim"], cfg["router_width"],
            cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"]) == (4, 512, 10, 512, 512)
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size",
                              "max_position_embeddings"]
    assert work.state_bytes_per_slot(cfg) == 3 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)
    assert work.kv_row_bytes(cfg) == 2048


# --------------------------------------------------------------------------- #
# the gated delta rule: chunked and one position against the recurrence
# --------------------------------------------------------------------------- #
def _rule_inputs(b, s, hk=2, hv=4, dk=8, dv=8, seed=0, log_decay=(-6.0, 2.0)):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, s, hk, dk))
    k = jax.random.normal(ks[1], (b, s, hk, dk))
    v = jax.random.normal(ks[2], (b, s, hv, dv))
    # log-decays from fast (exp(g) ~ 1e-3) to none, writes from weak to whole
    g = -jnp.exp(jax.random.uniform(
        ks[3], (b, s, hv), minval=log_decay[0], maxval=log_decay[1]))
    beta = jax.nn.sigmoid(3.0 * jax.random.normal(ks[4], (b, s, hv)))
    return q, k, v, g, beta


def _recurrence(q, k, v, g, beta):
    """The reference's token-by-token rule over the program's raw heads."""
    group = v.shape[2] // q.shape[2]
    qn = jnp.repeat(ref.l2_normalize(q) * q.shape[-1] ** -0.5, group, axis=2)
    kn = jnp.repeat(ref.l2_normalize(k), group, axis=2)
    with jax.default_matmul_precision("highest"):
        return ref.delta_rule(qn, kn, v, g, beta)


@pytest.mark.parametrize("length", [1, 37, 64, 100, 128, 200])
def test_chunked_form_is_the_recurrence(length):
    args = _rule_inputs(2, length, seed=length)
    want_o, want_s = _recurrence(*args)
    got_o, got_s = gated_delta_chunked(*args)
    assert float(jnp.max(jnp.abs(got_o - want_o))) < TOL
    assert float(jnp.max(jnp.abs(got_s - want_s))) < TOL


@pytest.mark.parametrize("lengths", [(100, 128), (1, 77), (64, 5)])
def test_a_padded_tail_does_not_move_the_state(lengths):
    """Rows of a bucket of 128 that end before it: the state handed back is
    the one after each row's last real position, whatever lies behind."""
    args = _rule_inputs(2, 128, seed=sum(lengths))
    got_o, got_s = gated_delta_chunked(*args, lengths=jnp.asarray(lengths))
    for row, n in enumerate(lengths):
        want_o, want_s = _recurrence(*(a[row:row + 1, :n] for a in args))
        assert float(jnp.max(jnp.abs(got_o[row, :n] - want_o[0]))) < TOL
        assert float(jnp.max(jnp.abs(got_s[row] - want_s[0]))) < TOL


@pytest.mark.parametrize("prompt,steps", [(37, 5), (64, 9), (130, 3)])
def test_chunked_prefill_then_steps_is_the_recurrence_over_both(prompt, steps):
    q, k, v, g, beta = _rule_inputs(2, prompt + steps, seed=prompt)
    want_o, want_s = _recurrence(q, k, v, g, beta)
    _, state = gated_delta_chunked(*(a[:, :prompt] for a in (q, k, v, g, beta)))
    for t in range(prompt, prompt + steps):
        o, state = gated_delta_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], state)
        assert float(jnp.max(jnp.abs(o - want_o[:, t]))) < TOL
    assert float(jnp.max(jnp.abs(state - want_s))) < TOL


# --------------------------------------------------------------------------- #
# the chunked form as one kernel (interpreted): against the recurrence and
# against the jax.numpy form it replaces on a chip
# --------------------------------------------------------------------------- #
_DECAYS = {"mixed": (-6.0, 2.0), "fast": (0.0, 2.0), "slow": (-9.0, -5.0)}


def _gap(a, b):
    return float(jnp.max(jnp.abs(a - b))) if a.size else 0.0


def test_off_the_chip_the_chunked_form_is_the_jax_numpy_one():
    """No TPU and no interpreter: the present tests above run the kept form,
    bit for bit; inside ``kernel_interpret_mode`` the kernel takes heads of
    whole sublanes alone."""
    assert not gated_delta.chunked_kernel_eligible(8, 8)
    args = _rule_inputs(2, 100, seed=3)
    for got, want in zip(gated_delta_chunked(*args),
                         gated_delta._chunked_reference(*args)):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    with kernel_interpret_mode():
        assert gated_delta.chunked_kernel_eligible(8, 16)
        assert not gated_delta.chunked_kernel_eligible(12, 8)
        assert not gated_delta.chunked_kernel_eligible(8, 4)


@pytest.mark.parametrize("hk", [2, 4], ids=["two_value_heads_a_key", "one"])
@pytest.mark.parametrize("length", [1, 37, 64, 100, 128, 200])
def test_the_kernel_is_the_recurrence_and_the_jax_numpy_form(length, hk):
    args = _rule_inputs(2, length, hk=hk, seed=length)
    with kernel_interpret_mode():
        got_o, got_s = gated_delta_chunked(*args)
    for want_o, want_s in (_recurrence(*args),
                           gated_delta._chunked_reference(*args)):
        assert _gap(got_o, want_o) < TOL
        assert _gap(got_s, want_s) < TOL


@pytest.mark.parametrize("decay", list(_DECAYS))
@pytest.mark.parametrize("lengths", [(100, 128), (1, 77), (64, 5), (0, 192)])
def test_the_kernel_walks_no_chunk_past_a_length(lengths, decay):
    """Rows of a bucket of 192 that end before it, inside a chunk, on a
    chunk's end, before the first position: the real rows are the
    recurrence's (and the kept form's), every row at or past the length is
    zeros, and the state is the one after the row's last real position."""
    args = _rule_inputs(2, 192, seed=sum(lengths), log_decay=_DECAYS[decay])
    lens = jnp.asarray(lengths)
    with kernel_interpret_mode():
        got_o, got_s = gated_delta_chunked(*args, lengths=lens)
    kept_o, kept_s = gated_delta._chunked_reference(*args, lengths=lens)
    assert _gap(got_s, kept_s) < TOL
    for row, n in enumerate(lengths):
        want_o, want_s = _recurrence(*(a[row:row + 1, :n] for a in args))
        assert _gap(got_o[row, :n], want_o[0]) < TOL
        assert _gap(got_o[row, :n], kept_o[row, :n]) < TOL
        assert _gap(got_s[row], want_s[0]) < TOL
        assert not np.any(np.asarray(got_o[row, n:]))


@pytest.mark.parametrize("prompt,steps", [(37, 5), (64, 9), (130, 3)])
def test_the_kernels_prefill_then_steps_is_the_recurrence_over_both(prompt, steps):
    q, k, v, g, beta = _rule_inputs(2, prompt + steps, seed=prompt)
    want_o, want_s = _recurrence(q, k, v, g, beta)
    with kernel_interpret_mode():
        _, state = gated_delta_chunked(*(a[:, :prompt] for a in (q, k, v, g, beta)))
    for t in range(prompt, prompt + steps):
        o, state = gated_delta_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], state)
        assert _gap(o, want_o[:, t]) < TOL
    assert _gap(state, want_s) < TOL


@pytest.mark.parametrize("lengths", [None, (70, 128)], ids=["whole", "padded"])
def test_the_kernels_gradient_is_the_jax_numpy_forms(lengths):
    """The kernel's forward pass under ``custom_vjp``, the kept form's
    backward pass: every input's gradient is what it was, and a row past a
    length (zeros out of the kernel whatever the inputs) pulls nothing back."""
    args = _rule_inputs(2, 128, seed=9)
    lens = None if lengths is None else jnp.asarray(lengths)
    w_o = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 4, 8))
    w_s = jax.random.normal(jax.random.PRNGKey(2), (2, 4, 8, 8))
    real = (jnp.arange(128)[None] < (
        jnp.full((2,), 128) if lens is None else lens)[:, None])[..., None, None]

    def loss(fn, *a):
        o, s = fn(*a, lengths=lens)
        return jnp.sum(jnp.where(real, o, 0.0) * w_o) + jnp.sum(s * w_s)

    want = jax.grad(lambda *a: loss(gated_delta._chunked_reference, *a),
                    argnums=range(5))(*args)
    with kernel_interpret_mode():
        got = jax.grad(lambda *a: loss(gated_delta_chunked, *a),
                       argnums=range(5))(*args)
        value = loss(gated_delta_chunked, *args)
    assert abs(float(value - loss(gated_delta._chunked_reference, *args))) < 1e-3
    for name, a, b in zip("qkvgb", got, want):
        assert _gap(a, b) < 1e-5, name


def test_heads_the_kernel_does_not_tile_take_the_jax_numpy_form():
    """A key head of 12 is no whole number of sublanes (nor of lanes on a
    chip): the call falls back inside the interpreter too, and still holds."""
    args = _rule_inputs(2, 100, dk=12, seed=5)
    with kernel_interpret_mode():
        got = gated_delta_chunked(*args, lengths=jnp.asarray([100, 41]))
    want = gated_delta._chunked_reference(*args, lengths=jnp.asarray([100, 41]))
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    want_o, want_s = _recurrence(*(a[1:, :41] for a in args))
    assert _gap(got[0][1, :41], want_o[0]) < TOL and _gap(got[1][1], want_s[0]) < TOL


# --------------------------------------------------------------------------- #
# the modules against the reference
# --------------------------------------------------------------------------- #
def _x(b, s, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, s, CFG["hidden_size"]))


def _subtree(lw: dict, prefix: str) -> dict:
    """``layer_view``'s flat names under ``prefix`` as a module's tree."""
    out: dict = {}
    for name, leaf in lw.items():
        if name.startswith(prefix + "/"):
            node = out
            *path, last = name[len(prefix) + 1:].split("/")
            for part in path:
                node = node.setdefault(part, {})
            node[last] = leaf
    return out


def test_gated_attention_with_partial_rotary_and_qk_norm_is_the_references(params):
    lw = W.layer_view(params, CFG, 3)
    x = _x(2, 40)
    pos = jnp.broadcast_to(jnp.arange(40)[None], (2, 40))
    got = Attention(common.program_config(CFG, dtype="float32")).apply(
        {"params": _subtree(lw, "attn")}, x, pos)
    with jax.default_matmul_precision("highest"):
        want = ref.gated_attention(x, lw, CFG, pos)
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    # each of the three is in the result: leaving it out is seen
    for key, value in (("attn_output_gate", False), ("partial_rotary_factor", 1.0)):
        other = common.program_config({**CFG, key: value}, dtype="float32")
        tree = _subtree(lw, "attn")
        if key == "attn_output_gate":  # the q half of each head's columns
            d = CFG["head_dim"]
            tree["q_proj"] = {"kernel": tree["q_proj"]["kernel"].reshape(
                -1, CFG["num_attention_heads"], 2 * d)[..., :d].reshape(
                    CFG["hidden_size"], -1)}
        off = Attention(other).apply({"params": tree}, x, pos)
        assert float(jnp.max(jnp.abs(off - want))) > 100 * TOL, key


def _moe(cfg, lw, x):
    return MoE(common.program_config(cfg, dtype="float32")).apply(
        {"params": _subtree(lw, "moe")}, x)


def test_experts_with_the_gated_shared_expert_are_the_references(params):
    lw = W.layer_view(params, CFG, 1)
    x = _x(2, 33)
    with jax.default_matmul_precision("highest"):
        want = ref.experts_ff(x, lw, CFG)
        ungated = ref.routed_ff(x, lw, CFG) + ref.swiglu(x, *(
            lw[f"moe/shared/{n}/kernel"] for n in ("gate_proj", "up_proj", "down_proj")))
    got = _moe(CFG, lw, x)
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    assert float(jnp.max(jnp.abs(got - ungated))) > 100 * TOL  # the gate counts


def test_the_shares_add_up_to_the_uncut_layer():
    """Experts 0-3 and 4-7 of the router's 8, each with the gated shared
    expert whole as every chip of the pair computes it: the two results, the
    shared expert counted once, are the reference's uncut layer."""
    x = _x(2, 29, seed=5)
    halves = []
    for offset in (0, 4):
        cfg = tiny.config(expert_offset=offset)
        lw = W.layer_view(W.make_tree(cfg, SEED, jnp.float32), cfg, 0)
        halves.append(_moe(cfg, lw, x))
    whole = tiny.config(num_experts=8)
    lw = W.layer_view(W.make_tree(whole, SEED, jnp.float32), whole, 0)
    with jax.default_matmul_precision("highest"):
        want = ref.experts_ff(x, lw, whole)
        shared = ref.shared_ff(x, lw)
        routed = ref.routed_ff(x, lw, whole)
    assert float(jnp.max(jnp.abs(halves[0] + halves[1] - shared - want))) < TOL
    # neither half is the layer: each leaves out what the other holds
    assert float(jnp.max(jnp.abs(halves[0] - shared))) > 100 * TOL
    assert float(jnp.max(jnp.abs(halves[0] - shared - routed))) > 100 * TOL


@pytest.mark.parametrize("length", [31, 64, 150])
def test_full_forward_matches_the_reference(params, length):
    ids = jnp.asarray(np.stack([_ids(length, 1), _ids(length, 2)]))
    got = _model().apply({"params": params}, ids)
    with jax.default_matmul_precision("highest"):
        want = ref.forward(params, CFG, ids)
    assert float(jnp.max(jnp.abs(got - want))) < 5 * TOL


def test_the_reference_regenerates_the_programs_weights_leaf_by_leaf(params):
    change = ref.param_change_leaf_norms(CFG, SEED, params)
    assert set(change) == set(_flat(params)) and max(change.values()) < 1e-6
    assert W.probe(params, CFG, SEED, jnp.float32) < 1e-6
    moved = jax.tree.map(lambda x: x, params)
    moved["layer_1"]["gdn"]["out_proj"]["kernel"] = 1.5 * params[
        "layer_1"]["gdn"]["out_proj"]["kernel"]
    assert ref.param_change_leaf_norms(CFG, SEED, moved)[
        "['layer_1']['gdn']['out_proj']['kernel']"] > 0.1


def test_the_dense_decode_cache_is_refused_by_name(params):
    with pytest.raises(NotImplementedError, match="serve it through ServingEngine"):
        _model().apply({"params": params}, jnp.zeros((1, 4), jnp.int32),
                       decode=True, mutable=["cache"])


# --------------------------------------------------------------------------- #
# the engine's own programs: prefill then decode is one forward pass
# --------------------------------------------------------------------------- #
def _serve(params, monkeypatch, schedule, max_slots=2, cfg=CFG, **kw):
    """Drive an engine over ``schedule`` — [(steps to make first, prompt,
    max_new_tokens)] — and read the LOGITS its own prefill and decode
    programs sampled from: ``sample_tokens`` hands them to the host in the
    order the programs ran, and the programs' arguments say whose they are.
    Returns ``(engine, {request id: (prompt, tokens, [(position, logits)])},
    [a decode step was in flight at each prefill])``."""
    seen = {"prefill": [], "decode": []}
    real = engine_module.sample_tokens

    now = {}

    def sample(logits, *a, **kws):
        kind = now["tracing"]  # read while the program is traced
        jax.debug.callback(lambda x: seen[kind].append(np.asarray(x)), logits,
                           ordered=True)
        return real(logits, *a, **kws)

    monkeypatch.setattr(engine_module, "sample_tokens", sample)
    eng = ServingEngine(_model(cfg), params, max_slots=max_slots, block_size=4, **kw)
    calls = {"prefill": [], "decode": []}
    flying = []
    prefill_fn, decode_fn = eng._prefill_fn, eng._decode_fn

    def prefill(p, cache, ids, table, length, cached, key, temp, slot, *rest):
        req = eng.scheduler.slots[int(slot[0])].request
        calls["prefill"].append((req.request_id, int(length[0]) - 1))
        flying.append(eng._ahead is not None)
        now["tracing"] = "prefill"
        return prefill_fn(p, cache, ids, table, length, cached, key, temp, slot, *rest)

    def decode(p, cache, tokens, tables, cache_lens, lengths, *rest):
        # a seated slot's row: its request, and the position its token stands at
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
        # every position that produced a served token was read (a step
        # dispatched ahead may read one more, behind the last token)
        assert {p for p, _ in logits} >= set(
            range(len(prompt) - 1, len(prompt) + len(tokens) - 1))
        for position, got in logits:
            if position < len(seq[0]):
                worst = max(worst, float(np.max(np.abs(got - want[position]))))
    return worst


@pytest.mark.parametrize("scan,kernel", [(False, False), (True, False), (False, True)],
                         ids=["layers_alone", "scanned", "gdn_kernel"])
def test_prefill_then_decode_through_the_engine_is_one_forward_pass(
        params, monkeypatch, scan, kernel):
    """Two requests of different length in different slots, the second
    prefilled while the first's decode step is in flight (decode-ahead), a
    prompt that fills no whole chunk and one that fills one and a part; with
    every layer a module of its own (the configuration's) and with the three
    DeltaNet layers one scan whose state leaves are stacked. The second
    prompt leaves nearly half its bucket empty: attention zeroes those rows,
    and (``gdn_kernel``: the interpreted kernel, as on a chip) the DeltaNet
    layers walk its two real chunks alone."""
    cfg = tiny.config(scan_layers=scan)
    if scan:
        params = W.make_tree(cfg, SEED, jnp.float32)
    with kernel_interpret_mode() if kernel else contextlib.nullcontext():
        eng, served, flying = _serve(params, monkeypatch, [
            (0, _ids(23, 1), 12), (4, _ids(65, 2), 9)], cfg=cfg)
    assert eng.decode_ahead and flying == [False, True]
    worst = _hold_against_one_forward_pass(params, served, cfg)
    print("engine vs one forward pass, widest logit error:", worst)
    assert worst < 5 * TOL
    counts = eng.trace_counts()
    assert counts["decode"] == 1 and counts["recurrent_state"] == counts["prefill"] + 1
    # the attention layer is handed each prompt's real length (the second
    # fills half its bucket and one token: 23 + 65 tokens in 32 + 128)
    assert counts["flash_real_rows"] == counts["prefill"] == 2
    assert counts["gdn_kernel"] == (2 if kernel else 0)  # 0 off the chip
    assert eng._gauge_fields()["prefill_real_token_share"] == 88 / 160
    assert eng.pool.stats()["allocated"] == 0
    assert len(jax.tree.leaves(eng.cache)) == (4 if scan else 8)


def test_a_reused_slot_serves_its_new_request_not_a_continuation(
        params, monkeypatch):
    """One slot, three requests one after the other: the second and third
    start from a zero state in the seat the one before left."""
    eng, served, _ = _serve(params, monkeypatch, [
        (0, _ids(40, 3), 6), (0, _ids(17, 4), 7), (0, _ids(66, 5), 5)],
        max_slots=1)
    assert _hold_against_one_forward_pass(params, served) < 5 * TOL
    assert eng.trace_counts()["decode"] == 1


def test_a_step_leaves_the_state_of_a_slot_it_does_not_decode(params):
    """The decode program over a batch in which one seat is empty: that
    seat's state and taps, and every K/V block, are bit for bit what they
    were."""
    eng = ServingEngine(_model(), params, max_slots=3, block_size=4)
    rid = eng.add_request(_ids(20, 7), max_new_tokens=8)
    eng.step()
    flat = lambda: {k: np.array(v) for k, v in _flat(eng.cache).items()  # noqa: E731
                    if k.endswith("['state']") or k.endswith("['taps']")}
    before = flat()
    while eng.has_work:
        eng.step()
    after = flat()
    slot_axis = {k: v.ndim - (4 if k.endswith("['state']") else 3)
                 for k, v in before.items()}
    moved = 0
    for k in before:
        b, a = (np.moveaxis(x, slot_axis[k], 0) for x in (before[k], after[k]))
        assert np.array_equal(b[1:], a[1:]), k  # seats 1 and 2 were never seated
        moved += not np.array_equal(b[0], a[0])
    assert moved == len(before) and len(eng.result(rid)) == 8
    assert eng.state_bytes_per_slot == sum(
        v.nbytes for v in before.values()) / 3
    assert eng.kv_bytes_per_token == 2 * 2 * 16 * 4  # the ONE attention layer's


# --------------------------------------------------------------------------- #
# pools stored heads first: the same rows, each KV head's together
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("q_len", [1, 5], ids=["decode", "several"])
@pytest.mark.parametrize("stacked", [False, True], ids=["pool", "stack"])
def test_pools_stored_heads_first_hold_the_same_rows(q_len, stacked):
    """``paged_update`` then ``paged_attention`` — the gather form and, at one
    position a slot, the decode kernel — over pools whose blocks are ``(Hkv,
    block_size, D)`` give what they give over ``(block_size, Hkv, D)``, and
    the pools hold the same rows."""
    from accelerate_tpu.ops.attention import (
        PagedKVState, decode_kernel_eligible, paged_attention, paged_update,
        pool_heads_first)
    from accelerate_tpu.ops.flash_attention import kernel_interpret_mode

    assert pool_heads_first(2, 256) and pool_heads_first(1, 128)
    assert not pool_heads_first(8, 128) and not pool_heads_first(32, 128)
    assert not pool_heads_first(2, 64)  # no kernel reads such a pool
    rng = np.random.default_rng(11)
    nb, bs, hkv, h, d, slots, layers = 9, 8, 2, 4, 128, 3, 2
    lead = (layers,) if stacked else ()
    rows_first = [jnp.asarray(rng.standard_normal(lead + (nb, bs, hkv, d)), jnp.float32)
                  for _ in range(2)]
    heads_first = [jnp.swapaxes(p, -3, -2) for p in rows_first]
    cache_len = np.asarray([bs + 3, 2, 0])
    table = np.zeros((slots, 4), np.int32)
    ids = list(rng.permutation(np.arange(1, nb)))
    for b, n in enumerate(cache_len):
        for t in range((n + q_len - 1) // bs + 1):
            table[b, t] = ids.pop()
    k, v = (jnp.asarray(rng.standard_normal((slots, q_len, hkv, d)), jnp.float32)
            for _ in range(2))
    q = jnp.asarray(rng.standard_normal((slots, q_len, h, d)), jnp.float32)
    layer = jnp.asarray(1, jnp.int32) if stacked else None

    def run(pools, first, kernel):
        st = PagedKVState(
            block_table=jnp.asarray(table), cache_len=jnp.asarray(cache_len),
            lengths=jnp.asarray([q_len, q_len, 0]), num_blocks=nb, block_size=bs,
            single_device=kernel, heads_first=first)
        new = paged_update(*pools, k, v, st, layer=layer)
        assert decode_kernel_eligible(st, q_len, new[0]) == (kernel and q_len == 1)
        return new, paged_attention(q, *new, st, layer=layer)

    want_pools, want = run(rows_first, False, False)
    got_pools, got = run(heads_first, True, False)
    for a, b in zip(want_pools, got_pools):
        assert np.array_equal(np.asarray(a), np.asarray(jnp.swapaxes(b, -3, -2)))
    assert float(jnp.max(jnp.abs(got[:2] - want[:2]))) < 1e-5
    if q_len == 1:
        with kernel_interpret_mode():
            _, kern = jax.jit(lambda *p: run(list(p), True, True))(*heads_first)
        assert float(jnp.max(jnp.abs(kern[:2] - want[:2]))) < 1e-5


def test_a_model_of_few_wide_kv_heads_is_served_from_pools_stored_heads_first():
    """The engine asks the rule of the model's shapes, says what it found in
    every paged state, and serves the same tokens through either form of
    ``paged_attention``; copy-on-write finds the pools by what the model
    declares them to be."""
    from accelerate_tpu.ops.flash_attention import kernel_interpret_mode

    cfg = TransformerConfig.tiny(hidden_size=256, num_heads=2, num_kv_heads=1,
                                 max_seq_len=128)
    model = CausalLM(cfg)
    params = nn.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    shared = _ids(24, 3, {"vocab_size": cfg.vocab_size})
    prompts = [np.concatenate([shared, _ids(n, n, {"vocab_size": cfg.vocab_size})])
               for n in (5, 9)]

    def serve(**kw):
        eng = ServingEngine(model, params, max_slots=2, block_size=8, **kw)
        pool = jax.tree.leaves(eng.cache)[0]
        assert pool.shape[-3:] == (1, 8, 128)  # (Hkv, block_size, D)
        rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        while eng.has_work:
            eng.step()
        return eng, [eng.result(r) for r in rids]

    _, plain = serve()
    with kernel_interpret_mode():
        eng, kernel = serve()
    assert kernel == plain and eng.trace_counts()["decode_attn_kernel"] == 1
    eng, warm = serve(prefix_cache=True)
    assert warm == plain and eng.prefix_cache is not None
    want = np.asarray(model.apply({"params": params}, jnp.asarray(
        np.concatenate([prompts[0], plain[0]])[None])))[0]
    at = np.arange(len(prompts[0]) - 1, len(prompts[0]) + 5)
    assert float(np.max(want[at].max(-1) - want[at, np.asarray(plain[0])])) < 1e-4


@pytest.mark.parametrize("dispatch", ["ragged", "dense"])
def test_any_stack_with_experts_says_how_many_a_decode_step_touched(dispatch):
    """The count rides on the model having experts, not on the kind of its
    mixers or on the dispatch: a plain attention stack's decode program hands
    it back behind the tokens, one number for the step's rows over both
    expert layers, and the tokens served are the forward pass's."""
    cfg = TransformerConfig.tiny(num_layers=2, num_experts=4, num_experts_per_tok=2,
                                 moe_dispatch=dispatch, max_seq_len=64)
    model = CausalLM(cfg)
    params = nn.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    eng = ServingEngine(model, params, max_slots=2, block_size=8)
    assert eng._counts_experts and eng._experts_held == 2 * 4
    fetched, fetch = [], eng._fetch
    eng._fetch = lambda *a: fetched.append(fetch(*a)) or fetched[-1]
    prompt = _ids(11, 2, {"vocab_size": cfg.vocab_size})
    rid = eng.add_request(prompt, max_new_tokens=6)
    while eng.has_work:
        eng.step()
    # two rows a step (one seat is empty; its row is computed all the same),
    # two distinct choices a row and layer: 2 to 4 experts a layer
    assert len(fetched) >= 5 and all(
        len(host) == 2 + 1 and 2 * 2 <= host[2] <= 2 * 4 for host in fetched)
    tokens = eng.result(rid)
    want = np.asarray(model.apply({"params": params}, jnp.asarray(
        np.concatenate([prompt, tokens])[None])))[0]
    at = np.arange(len(prompt) - 1, len(prompt) + 5)
    assert float(np.max(want[at].max(-1) - want[at, np.asarray(tokens)])) < 1e-4
    # a dense prefill gathers its table: no state a slot, no length to flash
    counts = eng.trace_counts()
    assert counts["prefill"] == 1
    assert counts["recurrent_state"] == counts["flash_real_rows"] == 0
    assert counts["gdn_kernel"] == 0
    assert eng._gauge_fields()["prefill_real_token_share"] == 11 / 16


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_the_engines_programs_scatter_no_rows_of_an_expert_layer(
        params, program, row_scatters):
    """The tiny twin's own ``jit__prefill`` (32 positions wide) and
    ``jit__decode``, lowered: the expert layers bring their rows back into
    token order by a gather (``ops.moe._to_tokens``) and a sum over the choices,
    so the only ``stablehlo.scatter``s of whole rows are the K/V write's two
    (``attn/kv_write``: rows of ``head_dim`` into the paged pools, which a
    fresh prefill writes a block of 4 positions an update, PR 46);
    ``bincount``'s and ``moe_experts_touched``'s are scalar."""
    eng = ServingEngine(_model(), params, max_slots=2, block_size=4)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    n, table = eng.max_slots, eng._max_table
    if program == "prefill":
        lowered = eng._prefill_fn.lower(
            params, eng.cache, i32(1, 32), i32(1, table), i32(1), i32(1), eng._key,
            f32(1), i32(1))
    else:
        lowered = eng._decode_fn.lower(
            params, eng.cache, i32(n, 1), i32(n, table), i32(n), i32(n), f32(n),
            eng._key)
    text = lowered.as_text()
    hidden, rows = CFG["hidden_size"], 32 if program == "prefill" else n
    picked = f"tensor<{CFG['num_experts_per_tok']}x{rows}x{hidden}xf32>"
    assert picked in text  # the gather: a row a choice, (k, T, hidden)
    kv_row = f"{CFG['num_key_value_heads']}x{CFG['head_dim']}xf32"
    kv_row = (f"{rows // 4}x4x" if program == "prefill" else f"{rows}x") + kv_row
    assert [update for _, update in row_scatters(text, CFG["head_dim"])] == [kv_row] * 2


@pytest.mark.parametrize("hybrid", [False, True], ids=["dense", "hybrid"])
def test_the_head_read_at_one_row_is_that_row_of_every_rows_logits(params, hybrid):
    """``CausalLM(logits_at=)``: what every prefill of the engine samples
    from, whatever the stack."""
    if hybrid:
        model, tree, vocab = _model(), params, CFG["vocab_size"]
    else:
        model = CausalLM(TransformerConfig.tiny(max_seq_len=64))
        tree = nn.unbox(model.init(
            jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"])
        vocab = model.config.vocab_size
    ids = jnp.asarray(np.stack([_ids(32, 5, {"vocab_size": vocab}),
                                _ids(32, 6, {"vocab_size": vocab})]))
    rows = jnp.asarray([7, 31])
    whole = model.apply({"params": tree}, ids)
    one = model.apply({"params": tree}, ids, logits_at=rows)
    assert one.shape == (2, 1, whole.shape[-1])
    assert float(jnp.max(jnp.abs(one[:, 0] - whole[jnp.arange(2), rows]))) < 1e-5


# --------------------------------------------------------------------------- #
# what is refused, by name
# --------------------------------------------------------------------------- #
WHY = "not written for a stack with 'linear_attention' layers"


# (each feature refused when an engine is BUILT: tests/test_cache_regime.py,
# one table over the regimes)
def test_the_same_features_are_refused_on_a_warm_engine(params):
    eng = ServingEngine(_model(), params, max_slots=2, block_size=4)
    with pytest.raises(NotImplementedError, match="prefix_cache.*" + WHY):
        eng.set_prefix_cache(True)
    with pytest.raises(NotImplementedError, match="spec_decode.*" + WHY):
        eng.set_speculation(SpecConfig(k=2))
    with pytest.raises(NotImplementedError, match="role.*" + WHY):
        eng.set_role("prefill")
    with pytest.raises(NotImplementedError, match="hand-off.*" + WHY):
        eng.acquire(None)
    assert eng.decode_ahead  # none of them landed it


_GDN = dict(layer_types=("linear_attention", "full_attention"), gdn_num_k_heads=2,
            gdn_num_v_heads=4, num_layers=2)


@pytest.mark.parametrize("kw,why", [
    (dict(_GDN, gdn_num_v_heads=3), "multiple of gdn_num_k_heads"),
    (dict(_GDN, gdn_num_k_heads=0), "multiple of gdn_num_k_heads"),
    (dict(_GDN, gdn_conv_kernel=1), "gdn_conv_kernel 1 >= 2"),
    (dict(_GDN, gdn_head_v_dim=0), "must be >= 1"),
    (dict(partial_rotary_factor=0.0), r"outside \(0, 1\]"),
    (dict(partial_rotary_factor=0.5, attention_class="eva"), "plain softmax attention"),
    (dict(attn_output_gate=True, fused_kernels=True), "plain softmax attention"),
    (dict(num_experts=4, moe_shared_gate=True), "moe_shared_gate gates a shared expert"),
    (dict(layer_types=("linear_attn",), num_layers=1), "layer_types"),
])
def test_config_refuses_what_it_cannot_be(kw, why):
    with pytest.raises(ValueError, match=why):
        TransformerConfig.tiny(**kw)

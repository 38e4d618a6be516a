"""Layers of several kinds in one stack — gated short convolution and GQA
attention with q/k norms, a leading dense layer, sigmoid-routed experts of
which a layer holds a share — through the normal path, held against the
benchmark's plain float32 reference (``benchmark/harness/lfm2_reference.py``)
on seeded weights (``lfm2_weights.py``), at widths the CPU can hold."""

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

import tiny_hybrid  # noqa: E402
from harness import common  # noqa: E402
from harness import lfm2_reference as ref  # noqa: E402
from harness import lfm2_weights as W  # noqa: E402

from accelerate_tpu.models import CausalLM, TransformerConfig  # noqa: E402
from accelerate_tpu.models.transformer import (  # noqa: E402
    MoE, ShortConv, layer_kinds, plan_layers)
from accelerate_tpu.ops.moe import (  # noqa: E402
    moe_ragged, ragged_load_stats, share_window_rows)

SEED = 2**31 + 5


def _ids(cfg, rows=2, seq=48, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (rows, seq)), jnp.int32)


def _model(cfg, **kw):
    return CausalLM(common.program_config(
        cfg, max_seq_len=cfg["max_position_embeddings"], **kw))


def _flat(tree):
    return {jax.tree_util.keystr(p): x
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


# --------------------------------------------------------------------------- #
# the plan and the tree
# --------------------------------------------------------------------------- #
def test_published_list_scans_its_periods_and_unrolls_nothing():
    cfg = tiny_hybrid.config(tiny_hybrid.PUBLISHED_LAYER_TYPES, num_dense_layers=2)
    plan = plan_layers(layer_kinds(common.program_config(cfg)))
    assert [(s, len(p), r) for s, p, r in plan] == [(0, 1, 2), (2, 4, 4), (18, 3, 2)]
    assert plan[1][1] == (("full_attention", "moe"),) + (("conv", "moe"),) * 3
    # the benchmark's own statement of the rule cuts the same way
    assert W.segments(W.layer_kinds(cfg)) == plan


def test_the_cut_is_two_single_layers_and_one_scan():
    cfg = tiny_hybrid.config()
    plan = plan_layers(layer_kinds(common.program_config(cfg)))
    assert [(s, p, r) for s, p, r in plan] == [
        (0, (("conv", "mlp"),), 1), (1, (("full_attention", "moe"),), 1),
        (2, (("conv", "moe"),), 3)]


@pytest.mark.parametrize("layer_types,dense", [
    (("conv", "full_attention", "conv", "conv", "conv"), 1),
    (tuple(tiny_hybrid.PUBLISHED_LAYER_TYPES), 2)])
@pytest.mark.parametrize("scan_layers", [True, False])
def test_seeded_tree_is_the_programs_tree(layer_types, dense, scan_layers):
    cfg = tiny_hybrid.config(layer_types, num_dense_layers=dense)
    model = _model(cfg, scan_layers=scan_layers)
    own = nn.unbox(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), _ids(cfg, 1, 8)))["params"])
    if scan_layers:
        made = W.abstract_tree(cfg, jnp.float32)
        assert {k: (v.shape, v.dtype) for k, v in _flat(own).items()} == {
            k: (v.shape, v.dtype) for k, v in _flat(made).items()}
    else:  # every layer alone: the same leaves under layer_<i>
        per_layer = sum(len(W.layer_leaves(cfg, k)) for k in W.layer_kinds(cfg))
        assert len(_flat(own)) == per_layer + 2
        assert set(own) == {"embed", "final_norm"} | {
            f"layer_{i}" for i in range(len(layer_types))}


# hashes of (path, shape, bytes) of CausalLM.init(PRNGKey(0)) at the parent of
# the PR that brought layer kinds (d9fc0ae): no existing configuration's tree
# or initial values may move
PARENT_TREES = {
    "dense": (dict(), "0feeec8cffdfb4b3"),
    "gqa_tied": (dict(num_kv_heads=2, tie_embeddings=True), "f1ea0548f0947577"),
    "mixtral": (dict(num_experts=4, num_experts_per_tok=2), "ed78958e4fff27d7"),
    "windows": (dict(layer_windows=(8, None), attention_impl="xla"),
                "0feeec8cffdfb4b3"),
    "unrolled": (dict(scan_layers=False), "71f9692f3279c83b"),
    "unrolled_moe_remat": (dict(scan_layers=False, num_experts=4,
                                remat="dots_ragged"), "3535099229d3ba16"),
    "gemma": (dict(norm_offset=True, post_norms=True, embed_scale=True,
                   mlp_activation="gelu_tanh"), "9f48aedb9d5d32bb"),
    "qkv_bias_one_layer": (dict(qkv_bias=True, num_layers=1), "d65299ca8e959689"),
}


@pytest.mark.parametrize("case", sorted(PARENT_TREES))
def test_existing_configurations_keep_their_tree_byte_for_byte(case):
    kw, want = PARENT_TREES[case]
    model = CausalLM(TransformerConfig.tiny(**kw))
    params = nn.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))["params"])
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(leaf.shape).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest()[:16] == want


# --------------------------------------------------------------------------- #
# the program against the reference
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("layer_types,dense,remat", [
    (("conv", "full_attention", "conv", "conv", "conv"), 1, "dots_ragged"),
    (tuple(tiny_hybrid.PUBLISHED_LAYER_TYPES), 2, None)])
def test_logits_loss_and_every_gradient_leaf_match_the_reference(
        layer_types, dense, remat):
    cfg = tiny_hybrid.config(layer_types, num_dense_layers=dense)
    params = W.make_tree(cfg, SEED, jnp.float32)
    ids = _ids(cfg)
    model = _model(cfg, remat=remat)
    with jax.default_matmul_precision("highest"):
        logits = model.apply({"params": params}, ids)
        loss, grads = jax.value_and_grad(CausalLM.loss_fn(model))(
            params, {"input_ids": ids})
    want_logits = ref.forward(params, cfg, ids)
    want_loss, want_grads = jax.value_and_grad(ref.loss)(params, cfg, ids)
    np.testing.assert_allclose(logits, want_logits, atol=2e-4, rtol=2e-4)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    got, want = _flat(grads), _flat(want_grads)
    assert set(got) == set(want)
    for key in want:
        scale = float(jnp.max(jnp.abs(want[key]))) + 1e-8
        np.testing.assert_allclose(got[key], want[key], atol=2e-4 * scale,
                                   rtol=2e-3, err_msg=key)
        if key.endswith("['expert_bias']"):  # moves the choice, not the weight
            assert not np.any(np.asarray(got[key]))


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four chips hold 8 experts each of a 32-wide router; what their expert
    layers return for the same input sums to the uncut reference layer."""
    whole = tiny_hybrid.config(num_experts=32, expert_offset=0, router_width=32)
    base = W.base_key(SEED)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 40, whole["hidden_size"]))
    whole_lw = W.layer_slice(base, whole, 2, jnp.float32)
    want = ref.experts_ff(x, whole_lw, whole)
    total, shares = 0.0, []
    for offset in (0, 8, 16, 24):
        cfg = tiny_hybrid.config(num_experts=8, expert_offset=offset,
                                 router_width=32)
        lw = W.layer_slice(base, cfg, 2, jnp.float32)
        np.testing.assert_array_equal(  # the share's experts ARE the whole's
            lw["moe/up_proj"], whole_lw["moe/up_proj"][offset:offset + 8])
        moe = MoE(common.program_config(cfg))
        out, sown = moe.apply({"params": {
            "router": {"kernel": lw["moe/router/kernel"]},
            "expert_bias": lw["moe/expert_bias"],
            "gate_proj": lw["moe/gate_proj"], "up_proj": lw["moe/up_proj"],
            "down_proj": lw["moe/down_proj"]}}, x, mutable=["intermediates"])
        np.testing.assert_allclose(out, ref.experts_ff(x, lw, cfg), atol=1e-5)
        total = total + out
        shares.append(float(sown["intermediates"]["moe_local_choice_share"][0]))
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert abs(sum(shares) - 1.0) < 1e-6  # every choice lies on one chip


# --------------------------------------------------------------------------- #
# the convolution
# --------------------------------------------------------------------------- #
def test_short_conv_is_causal_and_equals_an_explicit_loop():
    cfg = TransformerConfig.tiny(hidden_size=32, num_heads=2)
    conv = ShortConv(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 32))
    params = nn.unbox(conv.init(jax.random.PRNGKey(1), x)["params"])
    out = np.asarray(conv.apply({"params": params}, x))
    w_in, w_out = (np.asarray(params[n]["kernel"]) for n in ("in_proj", "out_proj"))
    taps = np.asarray(params["conv1d"]["kernel"])  # (3, h)
    u = np.asarray(x) @ w_in
    gate_b, gate_c, xs = np.split(u, 3, axis=-1)
    z = gate_b * xs
    want = np.zeros_like(out)
    for t in range(12):
        c = sum(taps[j] * z[:, t - 2 + j] for j in range(3) if t - 2 + j >= 0)
        want[:, t] = (gate_c[:, t] * c) @ w_out
    np.testing.assert_allclose(out, want, atol=1e-5)
    # causal: a later token moves no earlier output
    x2 = x.at[:, 7].add(1.0)
    out2 = np.asarray(conv.apply({"params": params}, x2))
    np.testing.assert_array_equal(out2[:, :7], out[:, :7])
    assert np.abs(out2[:, 7:10] - out[:, 7:10]).max() > 1e-3
    np.testing.assert_array_equal(out2[:, 10:], out[:, 10:])  # three taps reach two back


# --------------------------------------------------------------------------- #
# routing
# --------------------------------------------------------------------------- #
def _moe_parts(cfg_kw, x, bias):
    cfg = TransformerConfig.tiny(
        hidden_size=32, num_heads=2, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=16, moe_router="sigmoid", moe_expert_bias=True,
        moe_dispatch="dense", **cfg_kw)
    moe = MoE(cfg)
    params = nn.unbox(moe.init(jax.random.PRNGKey(0), x)["params"])
    params["expert_bias"] = bias
    logits = x @ params["router"]["kernel"]
    return moe, params, jax.nn.sigmoid(logits)


def test_expert_bias_moves_the_choice_and_not_the_weight():
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 6, 32))
    moe, params, scores = _moe_parts({"moe_norm_topk_prob": False}, x, jnp.zeros(8))
    # a bias that lifts expert 5 over everything: it is chosen everywhere...
    bias = jnp.zeros(8).at[5].set(10.0)
    plain = moe.apply({"params": params}, x)
    lifted = moe.apply({"params": {**params, "expert_bias": bias}}, x)
    top2 = jax.lax.top_k(scores, 2)[1]
    # ...and the result is the unbiased top-1's and expert 5's outputs, each
    # weighted by its UNBIASED score (no 10.0 in any weight)
    w_gate, w_up, w_down = (params[n] for n in ("gate_proj", "up_proj", "down_proj"))

    def expert(e, v):
        return (jax.nn.silu(v @ w_gate[e]) * (v @ w_up[e])) @ w_down[e]

    for t in range(6):
        first = int(top2[0, t, 0]) if int(top2[0, t, 0]) != 5 else int(top2[0, t, 1])
        want = sum(scores[0, t, e] * expert(e, x[0, t]) for e in (first, 5))
        np.testing.assert_allclose(lifted[0, t], want, atol=1e-5)
    assert np.abs(np.asarray(lifted - plain)).max() > 1e-4
    # its gradient is exactly zero
    g = jax.grad(lambda p: jnp.sum(moe.apply({"params": p}, x)))(params)
    assert not np.any(np.asarray(g["expert_bias"]))


def test_normalised_weights_sum_to_one_less_the_epsilon():
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 6, 32))
    moe, params, scores = _moe_parts({}, x, jnp.zeros(8))
    top = jax.lax.top_k(scores, 2)[0]
    w = top / (jnp.sum(top, -1, keepdims=True) + 1e-6)
    assert float(jnp.max(jnp.sum(w, -1))) < 1.0
    w_gate, w_up, w_down = (params[n] for n in ("gate_proj", "up_proj", "down_proj"))
    sel = jax.lax.top_k(scores, 2)[1]
    want = sum(
        w[0, :, k, None] * jnp.stack([
            (jax.nn.silu(x[0, t] @ w_gate[e]) * (x[0, t] @ w_up[e])) @ w_down[e]
            for t, e in enumerate(np.asarray(sel[0, :, k]))])
        for k in range(2))
    np.testing.assert_allclose(moe.apply({"params": params}, x)[0], want, atol=1e-5)


@pytest.mark.parametrize("target", ["one_held_expert", "no_held_expert"])
def test_an_imbalanced_router_drops_nothing(target):
    """Every token's every choice on ONE expert held here (the worst case
    the static shapes are sized for), or on none of them."""
    t, k, h, f, held = 24, 2, 16, 8, 4
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (t, h))
    w_gate, w_up = jax.random.normal(key, (2, held, h, f)) * 0.3
    w_down = jax.random.normal(key, (held, f, h)) * 0.3
    weights = jnp.full((t, k), 0.5)
    chosen = 6 if target == "one_held_expert" else 1  # held: experts 4..7
    sel = jnp.full((t, k), chosen, jnp.int32)
    out = moe_ragged(x, sel, weights, w_gate, w_up, w_down, expert_offset=4,
                     router_width=16)
    stats = ragged_load_stats(sel, held, expert_offset=4)
    if target == "no_held_expert":
        assert not np.any(np.asarray(out))
        assert float(stats["moe_local_choice_share"]) == 0.0
        return
    e = chosen - 4
    want = (jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]  # 2 x 0.5
    np.testing.assert_allclose(out, want, atol=1e-5)
    assert float(stats["moe_local_choice_share"]) == 1.0
    assert float(stats["moe_expert_load_max_over_mean"]) == held
    assert float(stats["moe_rows_computed_over_needed"]) == 1.0
    # gradients reach every token and only the chosen expert
    gx, gw = jax.grad(lambda x, w: jnp.sum(moe_ragged(
        x, sel, weights, w, w_up, w_down, expert_offset=4, router_width=16)),
        (0, 1))(x, w_gate)
    assert np.all(np.abs(np.asarray(gx)).sum(-1) > 0)
    assert np.any(np.asarray(gw[e])) and not np.any(np.asarray(gw[:e]))


# --------------------------------------------------------------------------- #
# the static window of a layer that holds a share
# --------------------------------------------------------------------------- #
_WIN = dict(t=256, k=4, h=16, f=8, held=4, offset=4, width=16)  # T k = 1024, window 512


def _window_case(where):
    """Choices that put the held rows inside the first window, past it, on
    no held expert, or all on one: ``(sel, held rows)``."""
    c = _WIN
    key = jax.random.PRNGKey(11)
    lo, hi = c["offset"], c["offset"] + c["held"]
    if where == "inside":  # an even router: about a quarter of the choices
        sel = jax.random.randint(key, (c["t"], c["k"]), 0, c["width"])
    elif where == "past":  # nine in ten held: the rest window has to run
        sel = jax.random.randint(key, (c["t"], c["k"]), lo, hi).at[:24].set(0)
    elif where == "none":
        sel = jnp.zeros((c["t"], c["k"]), jnp.int32)
    else:  # "one_expert": every choice on one held expert
        sel = jnp.full((c["t"], c["k"]), lo + 2, jnp.int32)
    return sel, int(jnp.sum((sel >= lo) & (sel < hi)))


def _window_operands():
    c = _WIN
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    return (jax.random.normal(ks[0], (c["t"], c["h"])),
            jax.random.uniform(ks[1], (c["t"], c["k"])),
            jax.random.normal(ks[2], (c["held"], c["h"], c["f"])) * 0.3,
            jax.random.normal(ks[3], (c["held"], c["h"], c["f"])) * 0.3,
            jax.random.normal(ks[4], (c["held"], c["f"], c["h"])) * 0.3,
            jax.random.normal(ks[5], (c["t"], c["h"])))


def _dense_oracle(x, weights, w_gate, w_up, w_down, sel):
    """``moe_dispatch="dense"`` restricted to the held experts: each of them
    computes every token, combined by the weights of the choices on it."""
    out = 0.0
    for e in range(_WIN["held"]):
        y = (jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]
        on_e = jnp.sum(jnp.where(sel == e + _WIN["offset"], weights, 0.0), -1)
        out = out + y * on_e[:, None]
    return out


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "dots_ragged"])
@pytest.mark.parametrize("where", ["inside", "past", "none", "one_expert"])
def test_the_window_form_equals_the_dense_oracle(where, remat):
    """Value, dx, dweights, dw_gate, dw_up, dw_down of a share-holding
    ``moe_ragged`` (float32), whether or not the rest window runs, plain
    and under ``jax.checkpoint`` with the policy the cell trains under."""
    from accelerate_tpu.models.transformer import _REMAT_POLICIES

    c = _WIN
    sel, held_rows = _window_case(where)
    *operands, cot = _window_operands()
    window = share_window_rows(c["t"] * c["k"], c["held"], c["width"])
    assert window == 512
    stats = ragged_load_stats(sel, c["held"], c["offset"], c["width"])
    ran = float(held_rows > window)
    assert ran == {"inside": 0.0, "past": 1.0, "none": 0.0, "one_expert": 1.0}[where]
    assert float(stats["moe_rest_window_share"]) == ran
    np.testing.assert_allclose(
        float(stats["moe_rows_computed_over_needed"]),
        (window + (c["t"] * c["k"] - window) * ran) / max(held_rows, 1), rtol=1e-6)

    def layer(*a):
        return moe_ragged(a[0], sel, *a[1:], expert_offset=c["offset"],
                          router_width=c["width"])

    if remat:
        layer = jax.checkpoint(layer, policy=_REMAT_POLICIES["dots_ragged"]())
    names = ("value", "dx", "dweights", "dw_gate", "dw_up", "dw_down")
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(layer(*a) * cot), argnums=(0, 1, 2, 3, 4)))(*operands)
        want = jax.value_and_grad(
            lambda *a: jnp.sum(_dense_oracle(*a, sel) * cot),
            argnums=(0, 1, 2, 3, 4))(*operands)
    for name, g, w in zip(names, jax.tree_util.tree_leaves(got),
                          jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(w))) + 1e-6
        np.testing.assert_allclose(g, w, atol=2e-5 * scale, rtol=2e-4, err_msg=name)
        if where == "none":
            assert not np.any(np.asarray(g)), name


# (t, k, h, f, held, offset, router width, gated, forward only): the callers
# of ``moe_ragged`` beside the two windows above
_GATHER_FORMS = {
    # a serving call: one window, no zero group, rows past the live ones undefined
    "forward_only": (64, 4, 16, 8, 4, 4, 16, True, True),
    "forward_only_nothing_held": (64, 4, 16, 8, 4, 12, 16, True, True),
    # 4 of 64 held: under an eighth, so all 1024 rows in ONE window with the
    # zero group; 448 x 464 reach the grouped matmuls as 512 x 512
    "one_window_padded": (256, 4, 448, 464, 4, 0, 64, False, False),
    # Mixtral-style: every expert held, no zero group
    "every_expert": (128, 2, 16, 8, 4, 0, 4, True, False),
}


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "dots_ragged"])
@pytest.mark.parametrize("case", sorted(_GATHER_FORMS))
def test_the_gather_form_equals_the_dense_oracle(case, remat, monkeypatch):
    """Value, dx, dweights, dw_gate, dw_up, dw_down of ``moe_ragged``
    (float32) against the dense oracle where rows move by gathers alone:
    a serving call, one window with a zero group and padded sides, every
    expert held — plain and under ``jax.checkpoint``. A serving call is
    never differentiated: its value is held, with every row the grouped
    matmuls did NOT write (past the live rows) poisoned with NaN, so a
    product with zero in place of the select would show."""
    from accelerate_tpu.models.transformer import _REMAT_POLICIES
    from accelerate_tpu.ops import moe

    t, k, h, f, held, offset, width, gated, forward_only = _GATHER_FORMS[case]
    ks = jax.random.split(jax.random.PRNGKey(17), 7)
    sel = jax.random.randint(ks[0], (t, k), 0, width)
    if case == "forward_only_nothing_held":
        sel = sel % offset  # every choice on an expert below the held ones
    operands = (jax.random.normal(ks[1], (t, h)), jax.random.uniform(ks[2], (t, k)),
                jax.random.normal(ks[3], (held, h, f)) * h ** -0.5 if gated else None,
                jax.random.normal(ks[4], (held, h, f)) * h ** -0.5,
                jax.random.normal(ks[5], (held, f, h)) * f ** -0.5)
    cot = jax.random.normal(ks[6], (t, h))
    act = None if gated else _relu2
    argnums = tuple(i for i, a in enumerate(operands) if a is not None)
    live = int(jnp.sum((sel >= offset) & (sel < offset + held)))
    assert (live == 0) == (case == "forward_only_nothing_held")
    if forward_only:
        real = jax.lax.ragged_dot

        def poisoned(lhs, rhs, group_sizes, **kw):
            written = jnp.arange(lhs.shape[0])[:, None] < jnp.sum(group_sizes)
            return jnp.where(written, real(lhs, rhs, group_sizes, **kw), jnp.nan)

        monkeypatch.setattr(moe.jax.lax, "ragged_dot", poisoned)

    def layer(*a):
        return moe_ragged(a[0], sel, *a[1:], expert_offset=offset, router_width=width,
                          activation=act, forward_only=forward_only)

    def oracle(*a):
        return _plain_experts(a[0], sel, *a[1:], offset, act or _relu2)

    if remat:
        layer = jax.checkpoint(layer, policy=_REMAT_POLICIES["dots_ragged"]())
    with jax.default_matmul_precision("highest"):
        if forward_only:
            got, want = (jax.jit(layer)(*operands),), (oracle(*operands),)
        else:
            got, want = (jax.tree.leaves(jax.jit(jax.value_and_grad(
                lambda *a: jnp.sum(fn(*a) * cot), argnums=argnums))(*operands))
                for fn in (layer, oracle))
    names = ["value"] if forward_only else (
        ["value", "dx", "dweights"] + ["dw_gate"] * gated + ["dw_up", "dw_down"])
    assert len(got) == len(want) == len(names)
    for name, g, w in zip(names, got, want):
        scale = float(jnp.max(jnp.abs(w))) + 1e-6
        assert np.all(np.isfinite(np.asarray(g))), name
        np.testing.assert_allclose(g, w, atol=2e-5 * scale, rtol=2e-4, err_msg=name)


def _eqns(jaxpr, name):
    return [e for e in jaxpr.eqns if e.primitive.name == name]


@pytest.mark.parametrize("held,width,want", [
    (8, 32, 1024), (8, 16, 2048), (4, 32, 512)])
def test_a_share_holding_layer_is_one_window_and_one_cond(held, width, want):
    """T k = 2048 sorted rows: the first window is the smallest multiple of
    512 at or above twice the even share, capped at all of them; what lies
    past it runs in the taken branch of exactly one ``cond``, three grouped
    matmuls in each; with half the router held there is no ``cond``."""
    t, k, h, f = 512, 4, 16, 8
    even = t * k * held / width
    assert want == min(t * k, 512 * -(-2 * even // 512))  # the stated rule
    assert share_window_rows(t * k, held, width) == want
    jaxpr = jax.make_jaxpr(lambda *a: moe_ragged(*a, router_width=width))(
        jnp.zeros((t, h)), jnp.zeros((t, k), jnp.int32), jnp.zeros((t, k)),
        jnp.zeros((held, h, f)), jnp.zeros((held, h, f)), jnp.zeros((held, f, h))).jaxpr
    first = _eqns(jaxpr, "ragged_dot_general")
    assert [e.invars[0].aval.shape[0] for e in first] == [want] * 3
    conds = _eqns(jaxpr, "cond")
    if want == t * k:
        assert not conds
        return
    (cond,) = conds
    dots = [_eqns(b.jaxpr, "ragged_dot_general") for b in cond.params["branches"]]
    assert sorted(len(d) for d in dots) == [0, 3]  # skipped: zeros; taken: the rest
    taken = max(dots, key=len)
    assert [e.invars[0].aval.shape[0] for e in taken] == [t * k - want] * 3


# sha256 of jit(moe_ragged).lower(...).as_text() as PR 39 left it, when the rows
# came to move by gathers alone (T 64, k 2, 4 experts, h 16, f 8, float32);
# until then the text of bf6ebce, the parent of the PR that brought the window
_ALL_HELD_LOWERED = "5588d3e948ae8bfb42a097ac93f1d4bbe7a5571fe88a4fa756f04b9b60de28f8"


@pytest.mark.parametrize("router_width", [None, 4])
def test_with_every_expert_held_the_lowered_text_is_one_run_of_gathers(
        router_width, row_scatters):
    """No window, no zero group, no ``cond``, no row scattered: a
    Mixtral-style layer lowers to one run of all its rows — the same text
    whether the router's width is given or not — and a change of that text
    is a change of every Mixtral-style program, to be made knowingly."""
    t, k, e, h, f = 64, 2, 4, 16, 8
    sds = jax.ShapeDtypeStruct
    text = jax.jit(lambda *a: moe_ragged(*a, router_width=router_width)).lower(
        sds((t, h), jnp.float32), sds((t, k), jnp.int32), sds((t, k), jnp.float32),
        sds((e, h, f), jnp.float32), sds((e, h, f), jnp.float32),
        sds((e, f, h), jnp.float32)).as_text()
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    assert not row_scatters(text, 1) and f"tensor<{k}x{t}x{h}xf32>" in text
    assert hashlib.sha256(text.encode()).hexdigest() == _ALL_HELD_LOWERED


# --------------------------------------------------------------------------- #
# through the Accelerator, with the counters
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("rows,seq,window", [(8, 32, 512), (2, 32, 256)],
                         ids=["first_window_of_two", "one_window"])
def test_unified_step_trains_the_stack_and_returns_its_counters(rows, seq, window):
    """2 of 8 experts held, 4 choices a token: 8 x 32 tokens are 1024 sorted
    rows a layer, cut at 512 (twice the even share) with the held ones inside
    the cut; 2 x 32 tokens are 256 rows, fewer than one multiple of 512, so
    every row is in the one window."""
    import optax

    from accelerate_tpu import Accelerator

    cfg = tiny_hybrid.config()
    model = _model(cfg, remat="dots_ragged")
    acc = Accelerator()
    params, optimizer = acc.prepare(
        W.make_tree(cfg, SEED, jnp.float32), optax.adamw(1e-3))
    step = acc.unified_step(CausalLM.loss_fn(model, with_aux=True), has_aux=True)
    carry = acc.init_carry(params, optimizer)
    ids = _ids(cfg, rows=rows, seq=seq, seed=1)
    losses = []
    for _ in range(4):
        carry, metrics = step(carry, {"input_ids": ids})
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    aux = {k: float(v) for k, v in metrics["aux"].items()}
    choices = rows * seq * cfg["num_experts_per_tok"]
    assert window == share_window_rows(
        choices, cfg["num_experts"], cfg["router_width"]) <= choices
    assert 0.0 < aux["moe_local_choice_share"] < window / choices
    assert aux["moe_expert_load_max_over_mean"] >= 1.0
    # the held rows of every expert layer lie inside the first window: no
    # rest window ran, and each layer computed `window` rows for its needed
    # ones (the mean of ratios is at least the ratio of the means)
    assert aux["moe_rest_window_share"] == 0.0
    assert aux["moe_rows_computed_over_needed"] * aux["moe_local_choice_share"] \
        >= window / choices - 1e-6
    assert step.detector.retraces == 0 if hasattr(step, "detector") else True


# --------------------------------------------------------------------------- #
# what the configuration refuses
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("kw,match", [
    (dict(layer_types=("conv",)), "layer_types has 1 entries"),
    (dict(layer_types=("conv", "hyena")), "unknown layer_types"),
    (dict(layer_types=("conv", "mamba")), "set single_sublayer"),
    (dict(layer_types=("conv", "conv"), layer_windows=(4, None)), "cannot be combined"),
    (dict(num_dense_layers=3), "num_dense_layers"),
    (dict(num_experts=4, moe_router="tanh"), "unknown moe_router"),
    (dict(num_experts=4, moe_router_width=8, moe_expert_offset=6), "do not lie inside"),
    (dict(num_experts=4, moe_router_width=8, moe_dispatch="capacity"), "ragged dispatch only"),
])
def test_configuration_refuses_what_it_cannot_run(kw, match):
    with pytest.raises(ValueError, match=match):
        TransformerConfig.tiny(**kw)


def test_a_convolution_layer_refuses_to_decode():
    cfg = TransformerConfig.tiny(layer_types=("conv", "full_attention"))
    model = CausalLM(cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    with pytest.raises(NotImplementedError, match="Reach A4"):
        model.apply({"params": params}, ids, decode=True, mutable=["cache"])


# --------------------------------------------------------------------------- #
# the scope paths the benchmark's per-layer metrics read
# --------------------------------------------------------------------------- #
def _op_names(model, ids):
    """Every operation's raw path in the compiled gradient of the loss."""
    import re

    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)["params"]
    text = jax.jit(jax.grad(CausalLM.loss_fn(model))).lower(
        params, {"input_ids": ids}).compile().as_text()
    return set(re.findall(r'op_name="([^"]+)"', text))


def _scope_paths(model, ids):
    """Every operation's path in the loss's gradient, cleaned as the
    benchmark's ``scope_share`` cleans the profiler's."""
    from harness import program_trace

    return {program_trace.scope_of(raw, "CausalLM") for raw in _op_names(model, ids)}


def _metric_scope(name):
    import json

    with open(os.path.join(ROOT, "benchmark", "metrics", f"{name}.json")) as f:
        return json.load(f)["args"]["scope"]


@pytest.mark.parametrize("stack", ["dense", "hybrid"])
def test_the_per_layer_metrics_find_their_scope_paths(stack):
    """A flax METHOD called from ``__call__`` writes ``<module>.<method>``
    into every path below it, and ``layers/mlp`` then matches nothing: the
    dense stack's paths are pinned as the accepted metrics read them."""
    import re

    if stack == "dense":
        model = CausalLM(TransformerConfig.tiny(remat="dots"))
        ids = jnp.zeros((2, 32), jnp.int32)
        metrics = {"mlp_device_share.train": "layers/mlp/up_proj/dot_general",
                   "attn_device_share.train": "layers/attn/q_proj/dot_general",
                   "head_loss_device_share.train": "lm_head/dot_general"}
    else:
        cfg = tiny_hybrid.config()
        model, ids = _model(cfg, remat="dots_ragged"), _ids(cfg)
        metrics = {"mlp_device_share.moe_train": "layer_0/mlp/up_proj/dot_general",
                   "conv_device_share.train": "layers_2/conv/conv1d/",
                   "attn_device_share.moe_train": "layer_1/attn/qk_norm/",
                   "moe_route_device_share.train": "layers_2/moe/route/router/dot_general",
                   "moe_experts_device_share.train": "layer_1/moe/experts/",
                   "head_loss_device_share.moe_train": "tied_head/"}
    paths = _scope_paths(model, ids)
    assert not [p for p in paths if "._" in p], "a method's name is in a path"
    for name, sample in metrics.items():
        rx = re.compile(_metric_scope(name))
        hit = [p for p in paths if rx.search(p)]
        assert any(sample in p for p in hit), (name, sample, sorted(hit)[:8])


def test_the_backward_gathers_of_an_expert_layer_lie_in_its_own_scopes():
    """The backward passes of ``_to_tokens`` and ``_to_experts`` are traced
    when the gradient is: their gathers (the combine's way back, ``g[tok]``,
    and the dispatch's, the sum over a token's k rows) must still read
    ``moe/combine/`` and ``moe/dispatch/``,
    or ``moe_route_device_share.*`` and the two shares that split it would
    lose them to UNSCOPED and read a gain that is none. Every scatter the
    gradient keeps in an expert layer is scalar (``bincount``; the router's
    ``take_along_axis``)."""
    import re

    from harness import program_trace

    cfg = tiny_hybrid.config()
    names = _op_names(_model(cfg, remat="dots_ragged"), _ids(cfg))
    backward = {program_trace.scope_of(raw, "CausalLM") for raw in names
                if "transpose(" in raw and "rematted_computation" not in raw}
    for metric in ("moe_combine_device_share.train", "moe_dispatch_device_share.train"):
        rx = re.compile(_metric_scope(metric))
        hit = sorted(p for p in backward if rx.search(p))
        assert any(p.endswith("/gather") for p in hit), (metric, hit)
        assert re.compile(_metric_scope("moe_route_device_share.train")).search(hit[0])
    moved = {p.rsplit("/", 1)[-1] for p in backward if "/moe/" in p}
    assert "reduce_sum" in moved and "scatter-add" in moved  # the sum over k; the router's
    assert not [p for p in backward
                if re.search(r"moe/(dispatch|combine)/.*scatter", p)], backward


def test_the_grouped_matmul_that_skips_rows_equals_the_one_that_does_not():
    """``benchmark/tests/ragged_leak_on_chip.py``'s ``masked_ragged_dot`` —
    group sizes that sum to the held choices alone, rows of no group zeroed
    forward and backward — against ``moe_ragged``'s zero-weight group: the
    form a later PR may switch to (PERF.md section 7, row 9)."""
    import ragged_leak_on_chip as leak

    make, args, live = leak.build(48, 4, 16, 8, 8, 32, seed=5, dtype=jnp.float32)
    assert 0 < live < 48 * 4
    want = leak.results(make("zero_group"), args)
    got = leak.results(make("vjp_masks"), args)
    for name in leak.NAMES:
        np.testing.assert_allclose(got[name], want[name], atol=1e-5, err_msg=name)
    assert not np.any(want["dw_gate"][3]) and not np.any(got["dw_gate"][3])  # the empty group


@pytest.mark.parametrize("layout", ["cut", "published"])
def test_first_gradient_norms_leave_out_the_first_expert_layer_where_it_stands_alone(layout):
    """``lfm2_reference.leaf_norms`` (what ``first_grad_worst_leaf_gap`` is
    read over): without the router and the expert stacks of ``layer_1`` in
    the cut; every leaf where the first expert layer lies inside a scan."""
    cfg = (tiny_hybrid.config() if layout == "cut" else tiny_hybrid.config(
        tiny_hybrid.PUBLISHED_LAYER_TYPES, num_dense_layers=2))
    tree = W.make_tree(cfg, SEED, jnp.float32)
    every, kept = set(_flat(tree)), set(jax.jit(ref.leaf_norms)(tree))
    left_out = {k for k in every if k.startswith("['layer_1']['moe']")}
    assert len(left_out) == (5 if layout == "cut" else 0)
    assert kept == every - left_out
    assert set(ref.param_change_leaf_norms(cfg, SEED, tree)) == every


# --------------------------------------------------------------------------- #
# an expert width that is no whole number of lanes, on whole tiles
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("h,f,want", [
    (2688, 1856, (3072, 2048)),   # train-ssm-moe-1chip: both sides padded
    (16, 464, (16, 512)),         # the cell's own ratio, 29 : 32; h 16 stays
    (448, 464, (512, 512)),       # ... and 7 : 8 on the hidden side: the widest pad
    (440, 464, (440, 512)),       # a hidden side that would grow by more than a seventh
    (2048, 1792, (2048, 1792)),   # train-moe-conv-1chip: whole lanes, 3.5 tiles
    (4096, 14336, (4096, 14336)),  # Mixtral
    (2688, 1920, (2688, 1920)),   # whole lanes: left, and the hidden size with it
    (2688, 3712, (2688, 3712)),
    (16, 256, (16, 256)), (16, 512, (16, 512)),
    (16, 8, (16, 8)), (16, 16, (16, 16)), (48, 40, (48, 40)),
    (16, 130, (16, 130)),         # more than a seventh to pad
    (16, 447, (16, 447)), (16, 448, (16, 512)), (16, 1800, (16, 2048)),
])
def test_only_a_width_off_the_lanes_and_near_a_tile_is_padded(h, f, want):
    """A rule of the two sides alone: whole lanes (any multiple of 128) stay
    as they are and so does their hidden size; any other width goes to the
    next multiple of 512 where that is at most a seventh wider, and then
    the hidden size by the same bound."""
    from accelerate_tpu.ops.moe import padded_expert_shape

    assert padded_expert_shape(h, f) == want


def _plain_experts(x, sel, weights, w_gate, w_up, w_down, offset, activation):
    """The published product written out: each held expert over every token
    at its own width, combined by the weights of the choices on it."""
    out = 0.0
    for e in range(w_up.shape[0]):
        up = x @ w_up[e]
        hidden = activation(up) if w_gate is None else jax.nn.silu(x @ w_gate[e]) * up
        on_e = jnp.sum(jnp.where(sel == e + offset, weights, 0.0), -1)
        out = out + (hidden @ w_down[e]) * on_e[:, None]
    return out


def _relu2(v):
    return jnp.square(jax.nn.relu(v))


def _off_lane_case(h, f, held, width, gated, t=96, k=4, seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    sel = jax.random.randint(ks[0], (t, k), 0, width)
    w_gate = jax.random.normal(ks[1], (held, h, f)) * h ** -0.5 if gated else None
    return sel, (jax.random.normal(ks[2], (t, h)), jax.random.uniform(ks[3], (t, k)),
                 w_gate, jax.random.normal(ks[4], (held, h, f)) * h ** -0.5,
                 jax.random.normal(ks[5], (held, f, h)) * f ** -0.5), \
        jax.random.normal(ks[6], (t, h))


@pytest.mark.parametrize("h,f", [(16, 464), (448, 464)], ids=["width", "both_sides"])
@pytest.mark.parametrize("held,width", [(4, 16), (4, 4)], ids=["a_share", "every_expert"])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
def test_the_padded_product_equals_the_published_one(gated, held, width, h, f, monkeypatch):
    """Value and ``jax.grad`` to x, the routing weights, w_up, w_down (and
    w_gate) of ``moe_ragged`` at a width the rule pads (464 -> 512, with the
    hidden side 448 -> 512 or 16 left), float32: equal to the dense oracle
    at the published width, and to the SAME program with the pad switched
    off within 2e-6 of the largest entry (float32 sums of 512 terms for 448
    or 464, in another order); the gradients keep the operands' shapes (the
    pad's transpose is a slice)."""
    from accelerate_tpu.ops import moe

    sel, operands, cot = _off_lane_case(h, f, held, width, gated)
    act = None if gated else _relu2
    assert moe.padded_expert_shape(h, f) == (512 if h == 448 else h, 512)
    argnums = tuple(i for i, a in enumerate(operands) if a is not None)

    def run(fn):  # the layer's output, then its gradients under one cotangent
        return jax.jit(lambda *a: (fn(*a), jax.grad(
            lambda *a: jnp.sum(fn(*a) * cot), argnums=argnums)(*a)))(*operands)

    def program(x, weights, w_gate, w_up, w_down):
        return moe_ragged(x, sel, weights, w_gate, w_up, w_down,
                          router_width=width, activation=act)

    def oracle(x, weights, w_gate, w_up, w_down):
        return _plain_experts(x, sel, weights, w_gate, w_up, w_down, 0, act or _relu2)

    with jax.default_matmul_precision("highest"):
        got, want = run(program), run(oracle)
        monkeypatch.setattr(moe, "padded_expert_shape", lambda h, f: (h, f))
        unpadded = run(program)
    names = ["value", "dx", "dweights"] + ["dw_gate"] * gated + ["dw_up", "dw_down"]
    for name, g, w, u, a in zip(names, jax.tree.leaves(got), jax.tree.leaves(want),
                                jax.tree.leaves(unpadded),
                                [operands[0]] + [operands[i] for i in argnums]):
        scale = float(jnp.max(jnp.abs(w))) + 1e-9
        np.testing.assert_allclose(g / scale, u / scale, atol=2e-6, err_msg=name)
        np.testing.assert_allclose(g / scale, w / scale, atol=2e-5, err_msg=name)
        assert g.shape == a.shape, name


@pytest.mark.parametrize("held,width,rows", [
    (8, 128, [3072]), (8, 32, [1536, 1536]), (8, 8, [3072])],
    ids=["8_of_128", "8_of_32", "every_expert"])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
def test_the_grouped_matmuls_are_given_whole_tiles_and_the_same_rows(
        gated, held, width, rows):
    """T k = 3072 sorted rows at h 448, f 464: every ``ragged_dot_general``
    multiplies 512-wide sides — rows (r, 512), kernels (groups, 512, 512) —
    over the row counts the window rule gives (all of them at 8 of 128 and
    with every expert held; 1536 and, under the ``cond``, the other 1536 at
    8 of 32), with ONE pad a kernel and one of the tokens, and no
    ``concatenate`` of a zero group beside them."""
    t, k, h, f = 512, 6, 448, 464
    n = 3 if gated else 2
    jaxpr = jax.make_jaxpr(lambda x, s, w, *ws: moe_ragged(
        x, s, w, ws[0] if gated else None, *ws[-2:], router_width=width,
        activation=None if gated else _relu2))(
        jnp.zeros((t, h)), jnp.zeros((t, k), jnp.int32), jnp.zeros((t, k)),
        *[jnp.zeros((held, h, f))] * (n - 1), jnp.zeros((held, f, h))).jaxpr
    groups = held + (width != held)

    def shapes(j):
        return [(e.invars[0].aval.shape, e.invars[1].aval.shape)
                for e in _eqns(j, "ragged_dot_general")]

    assert shapes(jaxpr) == [((rows[0], 512), (groups, 512, 512))] * n
    conds = _eqns(jaxpr, "cond")
    assert len(conds) == len(rows) - 1
    for cond, r in zip(conds, rows[1:]):
        taken = max((b.jaxpr for b in cond.params["branches"]),
                    key=lambda j: len(j.eqns))
        assert shapes(taken) == [((r, 512), (groups, 512, 512))] * n
    pads = [e for e in jaxpr.eqns
            if "pad" in (e.primitive.name, str(e.params.get("name", "")).strip("_"))]
    assert len(pads) == n + 1  # the kernels and the tokens, once a call
    kernel_concats = [e for e in _eqns(jaxpr, "concatenate")
                      if len(e.outvars[0].aval.shape) == 3]
    assert not kernel_concats


# sha256[:16] of jit(moe_ragged).lower(...).as_text() (T 64, k 2, 4 experts, h
# 16, float32), by (router width, gated, expert width); ungated with
# activation=jax.nn.relu. Pinned at bdfc916, the parent of the PR that brought
# the pad, and again at PR 39, which moved every ``moe_ragged`` text (rows by
# gathers alone) and no pad
_UNPADDED_LOWERED = {
    (None, True, 8): "5588d3e948ae8bfb", (None, True, 130): "f76432b61f77068d",
    (None, True, 256): "7a7c877faee6b69b", (None, False, 8): "6a5520e25d010214",
    (None, False, 130): "66091cafb51b6ae2", (None, False, 256): "a90d5a6c6422c8a8",
    (16, True, 8): "1b17e1e9e1c14b96", (16, True, 130): "5bfaa52903197b4a",
    (16, True, 256): "13b851b3f210366b", (16, False, 8): "30e5f6cd2651dc0a",
    (16, False, 130): "be38a12acc96e7e9", (16, False, 256): "844d9aa7a86e1f96",
}


@pytest.mark.parametrize("case", sorted(_UNPADDED_LOWERED, key=str), ids=str)
def test_a_width_the_rule_leaves_lowers_to_the_pinned_text(case):
    """Whole lanes (256), and widths too far from a tile (8, 130): with a
    share held (the zero group is still a ``concatenate``) and with every
    expert held, gated and not, the lowered text holds no pad and is byte
    for byte the pinned one."""
    router_width, gated, f = case
    t, k, e, h = 64, 2, 4, 16
    sds = jax.ShapeDtypeStruct
    kernels = [sds((e, h, f), jnp.float32)] * (2 if gated else 1) + [
        sds((e, f, h), jnp.float32)]
    if gated:
        fn = lambda *a: moe_ragged(*a, router_width=router_width)  # noqa: E731
    else:
        fn = lambda x, s, w, wu, wd: moe_ragged(  # noqa: E731
            x, s, w, None, wu, wd, router_width=router_width, activation=jax.nn.relu)
    text = jax.jit(fn).lower(
        sds((t, h), jnp.float32), sds((t, k), jnp.int32), sds((t, k), jnp.float32),
        *kernels).as_text()
    assert "stablehlo.pad" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == _UNPADDED_LOWERED[case]
    assert _ALL_HELD_LOWERED.startswith(_UNPADDED_LOWERED[(None, True, 8)])


@pytest.mark.parametrize("f,ratio", [(464, 512 / 464), (8, 1.0), (256, 1.0)])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
def test_the_layer_keeps_its_tree_and_sows_the_width_it_computed(gated, f, ratio):
    """Through the ``MoE`` module: the parameters and their gradients keep
    the published shapes (the pad lives between the parameters and the
    grouped matmuls), and ``moe_width_computed_over_published`` is sown
    beside ``ragged_load_stats``' four: 512 / 464 where the rule pads, 1.0
    where it does not."""
    h, held = 16, 2
    cfg = TransformerConfig.tiny(
        hidden_size=h, moe_intermediate_size=f, num_experts=held,
        moe_router_width=8, moe_expert_offset=2, num_experts_per_tok=4,
        moe_router="sigmoid", mlp_gated=gated,
        mlp_activation="silu" if gated else "relu2")
    moe = MoE(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, h))
    params = nn.unbox(moe.init(jax.random.PRNGKey(1), x)["params"])
    want = {"router": {"kernel": (h, 8)}, "up_proj": (held, h, f),
            "down_proj": (held, f, h)}
    if gated:
        want["gate_proj"] = (held, h, f)
    assert jax.tree.map(lambda p: p.shape, params) == want

    def loss(p):
        out, sown = moe.apply({"params": p}, x, mutable=["intermediates"])
        return jnp.sum(out ** 2), sown["intermediates"]

    (_, sown), grads = jax.value_and_grad(loss, has_aux=True)(params)
    assert jax.tree.map(lambda g: g.shape, grads) == want
    assert all(np.any(np.asarray(g)) for g in jax.tree.leaves(grads))
    assert {"moe_local_choice_share", "moe_expert_load_max_over_mean",
            "moe_rows_computed_over_needed", "moe_rest_window_share",
            "moe_width_computed_over_published"} <= set(sown)
    np.testing.assert_allclose(
        float(sown["moe_width_computed_over_published"][0]), ratio, rtol=1e-6)

"""A stack whose layers are ONE sublayer each — Mamba-2, sigmoid-routed relu²
experts with a shared expert, GQA attention without rope — through the
normal path, held against the benchmark's plain float32 reference
(``benchmark/harness/nemotron_h_reference.py``, whose state-space layer is
the recurrence itself) on seeded weights (``nemotron_h_weights.py``), at
widths the CPU can hold."""

import hashlib
import json
import os
import re
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "tests"))

import tiny_nemotron_h as tiny  # noqa: E402
from harness import cell as cells  # noqa: E402
from harness import common  # noqa: E402
from harness import nemotron_h_reference as ref  # noqa: E402
from harness import nemotron_h_weights as W  # noqa: E402
from harness import nemotron_h_work as work  # noqa: E402

from accelerate_tpu.models import CausalLM, TransformerConfig  # noqa: E402
from accelerate_tpu.models.transformer import (  # noqa: E402
    Attention, Mamba2, MoE, layer_kinds, plan_layers)
from accelerate_tpu.ops import ssd  # noqa: E402
from accelerate_tpu.ops.flash_attention import kernel_interpret_mode  # noqa: E402
from accelerate_tpu.ops.moe import moe_ragged, share_window_rows  # noqa: E402

SEED = 2**31 + 7
HIGHEST = jax.default_matmul_precision("highest")


def _ids(cfg, rows=2, seq=44, seed=0):
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
def test_the_cut_is_one_scan_of_two_periods_and_five_layers_alone():
    cfg = tiny.config()
    kinds = layer_kinds(common.program_config(cfg))
    assert kinds[:2] == [("mamba", None), (None, "moe")]
    assert kinds[5] == ("full_attention", None)
    plan = plan_layers(kinds)
    print("plan of MEMEM*EME:", plan)
    assert [(s, len(p), r) for s, p, r in plan] == [
        (0, 2, 2), (4, 1, 1), (5, 1, 1), (6, 1, 1), (7, 1, 1), (8, 1, 1)]
    assert plan[0][1] == (("mamba", None), (None, "moe"))
    assert W.segments(W.layer_kinds(cfg)) == plan  # the benchmark's own rule


def test_the_published_52_layers_find_their_own_segments():
    """``plan_layers`` is not special-cased: on the published pattern it
    finds the period of seven, MEMEM*E, five times over, then the runs of
    (M, E) and (E, M) pairs around the last attention layer."""
    cfg = tiny.config(tiny.PUBLISHED_PATTERN)
    plan = plan_layers(layer_kinds(common.program_config(cfg)))
    print("plan of the published 52:", [(s, len(p), r) for s, p, r in plan])
    assert [(s, len(p), r) for s, p, r in plan] == [
        (0, 7, 5), (35, 2, 3), (41, 1, 1), (42, 1, 1), (43, 2, 4), (51, 1, 1)]
    kinds = {"M": ("mamba", None), "E": (None, "moe"), "*": ("full_attention", None)}
    assert plan[0][1] == tuple(kinds[c] for c in "MEMEM*E")
    assert plan[3][1] == (kinds["*"],)
    assert sum(len(p) * r for _, p, r in plan if r > 1) == 49  # 3 layers unrolled
    assert W.segments(W.layer_kinds(cfg)) == plan


@pytest.mark.parametrize("pattern", ["MEMEM*EME", "ME-*", tiny.PUBLISHED_PATTERN])
@pytest.mark.parametrize("scan_layers", [True, False])
def test_seeded_tree_is_the_programs_tree(pattern, scan_layers):
    cfg = tiny.config(pattern)
    model = _model(cfg, scan_layers=scan_layers)
    own = nn.unbox(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), _ids(cfg, 1, 8)))["params"])
    if scan_layers:
        made = W.abstract_tree(cfg, jnp.float32)
        assert {k: (v.shape, v.dtype) for k, v in _flat(own).items()} == {
            k: (v.shape, v.dtype) for k, v in _flat(made).items()}
    else:  # every layer alone: the same leaves under layer_<i>
        per_layer = sum(len(W.layer_leaves(cfg, k)) for k in W.layer_kinds(cfg))
        assert len(_flat(own)) == per_layer + 3
        assert set(own) == {"embed", "final_norm", "lm_head"} | {
            f"layer_{i}" for i in range(len(pattern))}


def test_params_held_is_the_seeded_trees_count_at_the_published_widths():
    cfg = tiny.real()
    tree = W.abstract_tree(cfg, jnp.float32)
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert work.params_held(cfg) == count == 666_963_456  # 667.0 M, 10.67 GB
    small = tiny.config()
    tree = W.abstract_tree(small, jnp.float32)
    assert work.params_held(small) == sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def test_the_configuration_file_keeps_every_published_number():
    cfg = tiny.real()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == cfg["source"])
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"]) == set(cfg["published"])
    assert all(cfg["published"][k] == row["config"][k] for k in differs)
    assert cfg["hybrid_override_pattern"] == row["config"][
        "hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert cfg["layer_types"] == [tiny.LAYER_TYPES[c] for c in "MEMEM*EME"]
    for key in ("use_rope", "time_step_limit", "expand", "initializer",
                "e_score_correction_bias"):
        assert key in cfg["assumed"]
    program = common.program_config(cfg)
    assert (program.mamba_num_heads * program.mamba_head_dim == 4096
            != row["config"]["expand"] * program.hidden_size)


# --------------------------------------------------------------------------- #
# the program against the reference
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("pattern,scan_layers,remat", [
    ("MEMEM*EME", True, "dots_with_no_batch_dims"),
    ("ME*", False, None),
    ("MEME-*", True, "dots_ragged")])
def test_logits_loss_and_every_gradient_leaf_match_the_reference(
        pattern, scan_layers, remat):
    cfg = tiny.config(pattern)
    ids = _ids(cfg)
    model = _model(cfg, remat=remat, scan_layers=scan_layers)
    stacked = W.make_tree(cfg, SEED, jnp.float32)
    if scan_layers:
        params = stacked
    else:  # the same weights, every layer alone
        params = {k: stacked[k] for k in ("embed", "final_norm", "lm_head")}
        for l in range(len(pattern)):
            flat = W.layer_view(stacked, cfg, l)
            params[f"layer_{l}"] = W._nest(
                {tuple(k.split("/")): v for k, v in flat.items()})
    with HIGHEST:
        logits = model.apply({"params": params}, ids)
        loss, grads = jax.value_and_grad(CausalLM.loss_fn(model))(
            params, {"input_ids": ids})
    want_logits = ref.forward(stacked, cfg, ids)
    want_loss, want_grads = jax.value_and_grad(ref.loss)(stacked, cfg, ids)
    np.testing.assert_allclose(logits, want_logits, atol=2e-4, rtol=2e-4)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    if not scan_layers:
        return  # the gradient is compared leaf by leaf on the program's tree
    got, want = _flat(grads), _flat(want_grads)
    assert set(got) == set(want)
    for key in want:
        scale = float(jnp.max(jnp.abs(want[key]))) + 1e-8
        np.testing.assert_allclose(got[key], want[key], atol=2e-4 * scale,
                                   rtol=2e-3, err_msg=key)
        if key.endswith("['expert_bias']"):  # moves the choice, not the weight
            assert not np.any(np.asarray(got[key]))


# --------------------------------------------------------------------------- #
# the state-space operator
# --------------------------------------------------------------------------- #
def _scan_operands(seq, seed=0, b=2, heads=4, p=8, g=2, n=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, seq, heads, p))
    delta = jax.nn.softplus(jax.random.normal(ks[1], (b, seq, heads)) - 1.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0, maxval=2.5))
    b_mat = jax.random.normal(ks[3], (b, seq, g, n))
    c_mat = jax.random.normal(ks[4], (b, seq, g, n))
    return x, delta, a, b_mat, c_mat


def _explicit(x, delta, a, b_mat, c_mat):
    """The recurrence as a Python loop over positions, numpy float64."""
    x, delta, a, b_mat, c_mat = (np.asarray(t, np.float64)
                                 for t in (x, delta, a, b_mat, c_mat))
    bsz, seq, heads, p = x.shape
    r = heads // b_mat.shape[2]
    state = np.zeros((bsz, heads, p, b_mat.shape[-1]))
    y = np.zeros_like(x)
    for t in range(seq):
        b_t, c_t = (np.repeat(m[:, t], r, axis=1) for m in (b_mat, c_mat))
        state = (np.exp(delta[:, t] * a)[..., None, None] * state
                 + (delta[:, t, :, None] * x[:, t])[..., None] * b_t[:, :, None])
        y[:, t] = np.einsum("bhpn,bhn->bhp", state, c_t)
    return y


def _reference_recurrence(x, delta, a, b_mat, c_mat):
    """The reference's position-by-position scan on ``ssd_chunked``'s operands
    (it takes the heads as groups of heads)."""
    b, s, heads, p = x.shape
    g = b_mat.shape[2]
    return ref.recurrence(
        x.reshape(b, s, g, heads // g, p), delta.reshape(b, s, g, heads // g),
        a.reshape(g, heads // g), b_mat, c_mat).reshape(x.shape)


@pytest.mark.parametrize("seq,chunk", [(32, 8), (29, 8), (5, 8), (24, 24), (17, 1)])
def test_the_chunked_scan_equals_the_explicit_recurrence(seq, chunk):
    ops = _scan_operands(seq)
    with HIGHEST:
        got = ssd.ssd_chunked(*ops, chunk)
        plain = _reference_recurrence(*ops)
    want = _explicit(*ops)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(plain, want, atol=2e-5, rtol=2e-5)


def test_the_chunked_scan_is_causal_and_differentiates_as_the_recurrence():
    ops = _scan_operands(29, seed=3)
    with HIGHEST:
        base = ssd.ssd_chunked(*ops, 8)
        moved = ssd.ssd_chunked(ops[0].at[:, 19].add(1.0), *ops[1:], 8)
    np.testing.assert_array_equal(base[:, :19], moved[:, :19])
    assert np.abs(np.asarray(moved - base)[:, 19:]).max() > 1e-3

    def both(fn):
        def loss(x, delta, a, b_mat, c_mat):
            return jnp.sum(jnp.sin(fn(x, delta, a, b_mat, c_mat)))
        with HIGHEST:
            return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*ops)

    for got, want in zip(both(lambda *o: ssd.ssd_chunked(*o, 8)),
                         both(_reference_recurrence)):
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-3)


def test_a_chunk_that_forgets_everything_underflows_to_zero_and_stays_finite():
    x, delta, a, b_mat, c_mat = _scan_operands(32)
    out = ssd.ssd_chunked(x, delta * 40.0, a * 40.0, b_mat, c_mat, 8)
    assert np.all(np.isfinite(np.asarray(out)))
    assert float(ssd.chunk_decay_min(delta * 40.0, a * 40.0, 8)) == 0.0
    assert 0.0 < float(ssd.chunk_decay_min(delta * 0.01, a, 8)) < 1.0


# the chunked scan as two kernels (``ssd_chunked_fwd`` / ``ssd_chunked_bwd``),
# interpreted at tiles of 8: several heads a group two to a tile (heads of 4),
# several heads a group one to a tile (heads of 8), one head a group
_KERNEL_GROUPS = {"two_heads_a_tile": (8, 4, 2), "a_head_a_tile": (4, 8, 2),
                  "one_head_a_group": (2, 8, 2)}


def _kernel_operands(seq, group, dtype, seed=0):
    heads, p, g = _KERNEL_GROUPS[group]
    x, delta, a, b_mat, c_mat = _scan_operands(seq, seed=seed, heads=heads, p=p, g=g)
    skip = 1.0 + 0.5 * jax.random.normal(jax.random.PRNGKey(seed + 7), (heads,))
    return (x.astype(dtype), delta, a, b_mat.astype(dtype), c_mat.astype(dtype),
            skip)


def _recurrence_with_skip(x, delta, a, b_mat, c_mat, skip):
    """The reference's position-by-position scan plus ``D x``, float32."""
    x, b_mat, c_mat = (t.astype(jnp.float32) for t in (x, b_mat, c_mat))
    return (_reference_recurrence(x, delta, a, b_mat, c_mat)
            + x * skip[:, None])


def _value_and_grads(fn, ops):
    def loss(*o):
        y = fn(*o).astype(jnp.float32)
        return jnp.sum(jnp.sin(y)), y

    with HIGHEST:
        (_, y), grads = jax.value_and_grad(
            loss, argnums=tuple(range(6)), has_aux=True)(*ops)
    return y, grads


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("seq", [32, 29], ids=["whole_chunks", "ragged_tail"])
@pytest.mark.parametrize("group", sorted(_KERNEL_GROUPS))
def test_the_scan_kernels_equal_the_recurrence_and_the_jax_numpy_form(
        group, seq, dtype):
    """``y`` and the gradients of ``x``, ``delta``, ``a``, ``B``, ``C`` and
    ``D`` out of the interpreted kernels, against the explicit recurrence and
    against the ``jax.numpy`` form (in bfloat16 both forms round their
    operands to it and sum in float32: the recurrence, float32 throughout on
    the same rounded operands, is a rounding's distance from either)."""
    ops = _kernel_operands(seq, group, dtype)
    heads, p, g = _KERNEL_GROUPS[group]
    with kernel_interpret_mode():
        assert ssd.ssd_kernel_eligible(heads, p, g, 8, 8)
        got_y, got = _value_and_grads(
            lambda *o: ssd.ssd_chunked(*o[:5], 8, skip=o[5]), ops)
    assert got_y.shape == ops[0].shape
    jnp_y, jnp_grads = _value_and_grads(
        lambda *o: ssd._chunked_reference(*o[:5], 8, skip=o[5]), ops)
    plain_y, plain = _value_and_grads(_recurrence_with_skip, ops)
    if dtype == jnp.float32:
        np.testing.assert_allclose(
            got_y, _explicit(*ops[:5]) + np.asarray(ops[0] * ops[5][:, None]),
            atol=2e-5, rtol=2e-5)
    # bfloat16: 2 ** -8 a rounding; the two forms round the same operands in
    # other places (3 of them: over seeds 0-2 and these six cases the widest
    # read 9.9e-3, ``dA``; at the cell's size on the chip 7.8e-3, ``dx``:
    # PERF.md section 6, PR 47), the float32 recurrence rounds none (20)
    near, far = (2e-4, 2e-4) if dtype == jnp.float32 else (1.2e-2, 8e-2)
    for name, a_, b_, c_ in zip(("y", "x", "delta", "a", "B", "C", "D"),
                                (got_y,) + got, (jnp_y,) + jnp_grads,
                                (plain_y,) + plain):
        a_, b_, c_ = (np.asarray(t, np.float32) for t in (a_, b_, c_))
        scale = np.abs(c_).max() + 1e-6
        assert np.abs(a_ - c_).max() <= far * scale, (name, "recurrence")
        assert np.abs(a_ - b_).max() <= near * scale, (name, "jax.numpy form")
    assert all(g_.dtype == o.dtype for g_, o in zip(got, ops))


def test_the_scan_kernels_take_no_skip_and_differentiate_under_jit():
    ops = _kernel_operands(24, "two_heads_a_tile", jnp.float32, seed=2)[:5]

    def loss(fn):
        return lambda *o: jnp.sum(jnp.sin(fn(*o, 8)))

    with kernel_interpret_mode(), HIGHEST:
        got = jax.jit(jax.grad(loss(ssd.ssd_chunked), argnums=(0, 1, 2, 3, 4)))(*ops)
    with HIGHEST:
        want = jax.grad(loss(ssd._chunked_reference), argnums=(0, 1, 2, 3, 4))(*ops)
    for g_, w_ in zip(got, want):
        np.testing.assert_allclose(g_, w_, atol=1e-4, rtol=1e-3)


@pytest.mark.parametrize("group", ["two_heads_a_tile", "one_head_a_group"])
def test_the_kernels_chunk_that_forgets_everything_is_an_exact_zero_and_finite(group):
    """``exp`` of a whole chunk's log-decay underflows to an exact 0.0 in the
    kernels too: a position reads nothing of the chunks before its own, and
    both passes stay finite (the differences are masked before their
    ``exp``, and ``exp(total - a_j)`` never exceeds 1)."""
    x, delta, a, b_mat, c_mat, skip = _kernel_operands(32, group, jnp.float32)
    delta, a = delta * 40.0, a * 40.0
    assert float(ssd.chunk_decay_min(delta, a, 8)) == 0.0

    def run(x):
        return ssd.ssd_chunked(x, delta, a, b_mat, c_mat, 8, skip=skip)

    with kernel_interpret_mode(), HIGHEST:
        base = run(x)
        moved = run(x.at[:, :8].add(1.0))  # the first chunk, whole
        grads = jax.grad(lambda *o: jnp.sum(jnp.sin(
            ssd.ssd_chunked(*o[:5], 8, skip=o[5]))), argnums=tuple(range(6)))(
                x, delta, a, b_mat, c_mat, skip)
        want = ssd._chunked_reference(x, delta, a, b_mat, c_mat, 8, skip=skip)
    assert np.all(np.isfinite(np.asarray(base)))
    np.testing.assert_allclose(base, want, atol=2e-5, rtol=2e-5)
    # every later chunk forgot the first: not a bit of it is left
    np.testing.assert_array_equal(base[:, 8:], moved[:, 8:])
    assert np.abs(np.asarray(moved - base)[:, :8]).max() > 1e-3
    for g_ in grads:
        assert np.all(np.isfinite(np.asarray(g_)))


# sha256[:16] of what ``ssd_chunked`` lowers to off a TPU at commit 23ee05a,
# the parent of the PR that brought the kernels: (S, dtype, pass)
_JNP_LOWERED = {
    (32, "float32", "fwd"): "3bd95573c8850810",
    (32, "float32", "grad"): "0fd0f6429024896b",
    (29, "bfloat16", "fwd"): "2d4383adf65c9bd0",
    (29, "bfloat16", "grad"): "5920923926d25609",
}


@pytest.mark.parametrize("case", sorted(_JNP_LOWERED), ids=str)
def test_off_a_tpu_the_scan_lowers_to_the_pinned_jax_numpy_text(case):
    """No TPU and no interpreter: the predicate is False and the call, and its
    gradient, lower byte for byte to the einsums they were."""
    seq, dtype, which = case
    b, heads, p, g, n = 2, 4, 8, 2, 8
    assert not ssd.ssd_kernel_eligible(heads, p, g, n, 8)
    sds = jax.ShapeDtypeStruct
    ops = (sds((b, seq, heads, p), dtype), sds((b, seq, heads), jnp.float32),
           sds((heads,), jnp.float32), sds((b, seq, g, n), dtype),
           sds((b, seq, g, n), dtype), sds((heads,), jnp.float32))

    def fwd(x, delta, a, b_mat, c_mat, skip):
        return ssd.ssd_chunked(x, delta, a, b_mat, c_mat, 8, skip=skip)

    def total(*o):
        return jnp.sum(fwd(*o).astype(jnp.float32))

    fn = fwd if which == "fwd" else jax.grad(total, argnums=tuple(range(6)))
    text = jax.jit(fn).lower(*ops).as_text()
    assert "custom_call" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == _JNP_LOWERED[case]


def test_the_kernel_predicate_reads_the_backend_the_mesh_and_the_shapes():
    """``ssd_kernel_eligible(heads, p, groups, n, chunk)``: the cell's shapes
    are taken on a TPU, and only there (or interpreted, at tiles of 8)."""
    from accelerate_tpu import Accelerator, ParallelismPlugin

    cell = (64, 64, 8, 128, 128)
    assert not ssd.ssd_kernel_eligible(*cell)  # the CPU, no interpreter
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        assert ssd.ssd_kernel_eligible(*cell)
        for odd in ((64, 64, 8, 128, 64),  # a chunk of half a tile
                    (64, 64, 8, 64, 128),  # a state of half the lanes
                    (64, 64, 64, 128, 128),  # one head of 64 a group: half a tile
                    (64, 48, 8, 128, 128),  # a head across a tile's edge
                    (64, 64, 4, 128, 128),  # 16 heads a group: masks past a step's VMEM
                    (64, 64, 8, 256, 128),  # states past it
                    (64, 64, 8, 128, 256),  # chunks past it
                    (64, 64, 0, 128, 128), (64, 64, 7, 128, 128)):
            assert not ssd.ssd_kernel_eligible(*odd), odd
        assert ssd.ssd_kernel_eligible(64, 128, 16, 128, 128)  # a head a whole tile
    with kernel_interpret_mode():
        assert ssd.ssd_kernel_eligible(4, 8, 2, 8, 8)
        assert ssd.ssd_kernel_eligible(4, 8, 4, 8, 16)
        assert not ssd.ssd_kernel_eligible(4, 8, 2, 8, 12)
        assert not ssd.ssd_kernel_eligible(4, 8, 2, 12, 8)
        assert not ssd.ssd_kernel_eligible(4, 3, 2, 8, 8)
        # the call says which form it took: what ``Mamba2`` sows
        assert ssd.ssd_chunked(*_scan_operands(16), 8, with_form=True)[1] is True
        Accelerator(parallelism_plugin=ParallelismPlugin(dp_size=2, fsdp_size=4))
        assert not ssd.ssd_kernel_eligible(4, 8, 2, 8, 8)  # a live mesh
        # and the call itself then takes the jax.numpy form
        ops = _scan_operands(16)
        text = jax.jit(lambda *o: ssd.ssd_chunked(*o, 8)).lower(*ops).as_text(
            debug_info=True)
        assert "ssd_chunked_fwd" not in text
        assert ssd.ssd_chunked(*ops, 8, with_form=True)[1] is False


def _mamba_parts(cfg_dict, seed=1, seq=21):
    cfg = common.program_config(cfg_dict)
    lw = W.layer_slice(W.base_key(SEED), cfg_dict, 0, jnp.float32)
    params = W._nest({tuple(k.split("/")[1:]): v for k, v in lw.items()
                      if k.startswith("ssm/")})
    u = jax.random.normal(jax.random.PRNGKey(seed), (2, seq, cfg.hidden_size))
    return Mamba2(cfg), params, lw, u


def test_the_operator_equals_the_reference_and_sows_its_gauges():
    cfg = tiny.config()
    op, params, lw, u = _mamba_parts(cfg)
    with HIGHEST:
        out, sown = op.apply({"params": params}, u, mutable=["intermediates"])
    np.testing.assert_allclose(out, ref.mamba2(u, lw, cfg), atol=2e-5, rtol=2e-4)
    gauges = {k: float(v[0]) for k, v in sown["intermediates"].items()}
    assert set(gauges) == {"ssm_delta_mean", "ssm_chunk_decay_min",
                           "ssm_scan_kernel"}
    assert gauges["ssm_scan_kernel"] == 0.0  # the CPU: the jax.numpy form
    assert 1e-4 < gauges["ssm_delta_mean"] < 0.2  # the seeded dt_bias's range
    assert 0.0 < gauges["ssm_chunk_decay_min"] < 1.0


def test_the_convolution_has_a_bias_and_a_silu():
    """x | B | C = silu(conv1d(.) + bias): with the taps zeroed the operator
    sees silu(bias) at every position; with the bias zeroed too, nothing."""
    cfg = tiny.config()
    op, params, lw, u = _mamba_parts(cfg)
    heads, p, groups, n, conv = W.mamba_dims(cfg)
    bias = lw["ssm/conv1d/bias"]
    assert bias.shape == (conv,) and np.any(np.asarray(bias))
    no_taps = dict(params, conv1d={"kernel": jnp.zeros_like(lw["ssm/conv1d/kernel"]),
                                   "bias": bias})
    lw0 = dict(lw, **{"ssm/conv1d/kernel": jnp.zeros_like(lw["ssm/conv1d/kernel"])})
    with HIGHEST:
        got = op.apply({"params": no_taps}, u)
    np.testing.assert_allclose(got, ref.mamba2(u, lw0, cfg), atol=2e-5, rtol=2e-4)
    # the recurrence's inputs are then silu(bias), the same at every position
    xbc = jax.nn.silu(bias)
    assert np.abs(np.asarray(xbc[:heads * p])).max() > 0
    silent = dict(no_taps, conv1d={"kernel": no_taps["conv1d"]["kernel"],
                                   "bias": jnp.zeros_like(bias)})
    assert not np.any(np.asarray(op.apply({"params": silent}, u)))
    # and the convolution is causal with 4 taps: position t moves t..t+3 of
    # the convolution, and through the state everything after, nothing before
    with HIGHEST:
        base = op.apply({"params": params}, u)
        moved = op.apply({"params": params}, u.at[:, 9].add(1.0))
    np.testing.assert_array_equal(base[:, :9], moved[:, :9])


def test_the_gate_comes_before_the_grouped_norm():
    """y <- GroupRMSNorm(y * silu(z)), groups of d / G under ONE weight of d:
    not norm(y) * silu(z), and not a norm over the whole width."""
    cfg = tiny.config()
    op, params, lw, u = _mamba_parts(cfg)
    heads, p, groups, n, conv = W.mamba_dims(cfg)
    d = heads * p
    weight = jnp.linspace(0.5, 1.5, d)
    params = dict(params, norm=weight,
                  out_proj={"kernel": jnp.eye(d, cfg["hidden_size"])})
    lw = dict(lw, **{"ssm/norm": weight,
                     "ssm/out_proj/kernel": jnp.eye(d, cfg["hidden_size"])})
    with HIGHEST:
        got = np.asarray(op.apply({"params": params}, u))
    np.testing.assert_allclose(got, ref.mamba2(u, lw, cfg), atol=2e-5, rtol=2e-4)
    # out_proj = the first 48 of the 32 channels... the identity keeps them:
    # each group of d / G normalised channels has mean square weight^2
    out = got[..., :d] / np.asarray(weight)
    per_group = (out.reshape(out.shape[:-1] + (groups, d // groups)) ** 2).mean(-1)
    # (less what eps 1e-5 takes from a small mean square)
    np.testing.assert_allclose(per_group, 1.0, atol=2e-2)


# --------------------------------------------------------------------------- #
# the experts
# --------------------------------------------------------------------------- #
def _moe_params(lw):
    return {"router": {"kernel": lw["moe/router/kernel"]},
            "expert_bias": lw["moe/expert_bias"],
            "up_proj": lw["moe/up_proj"], "down_proj": lw["moe/down_proj"],
            "shared": {"up_proj": {"kernel": lw["moe/shared/up_proj/kernel"]},
                       "down_proj": {"kernel": lw["moe/shared/down_proj/kernel"]}}}


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """16 chips hold 8 experts each of a 128-wide router; what their expert
    layers return for the same input, with the shared expert — which every
    chip computes alike — counted ONCE, sums to the uncut reference layer."""
    whole = tiny.config(n_routed_experts=128, expert_offset=0, router_width=128)
    base = W.base_key(SEED)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 40, whole["hidden_size"]))
    whole_lw = W.layer_slice(base, whole, 1, jnp.float32)
    with HIGHEST:
        want = ref.experts_ff(x, whole_lw, whole)
        shared = ref.shared_ff(x, whole_lw)
    routed, shares = 0.0, []
    for offset in range(0, 128, 8):
        cfg = tiny.config(n_routed_experts=8, expert_offset=offset, router_width=128)
        lw = W.layer_slice(base, cfg, 1, jnp.float32)
        np.testing.assert_array_equal(  # the share's experts ARE the whole's
            lw["moe/up_proj"], whole_lw["moe/up_proj"][offset:offset + 8])
        with HIGHEST:
            out, sown = MoE(common.program_config(cfg)).apply(
                {"params": _moe_params(lw)}, x, mutable=["intermediates"])
            np.testing.assert_allclose(out, ref.experts_ff(x, lw, cfg), atol=2e-5)
        routed = routed + (out - shared)  # every chip added the shared expert
        shares.append(float(sown["intermediates"]["moe_local_choice_share"][0]))
    np.testing.assert_allclose(routed + shared, want, atol=5e-5)
    assert abs(sum(shares) - 1.0) < 1e-6  # every choice lies on one chip
    # the weights: 6 of 128 scores over (their sum + 1e-20), times 2.5
    sel, w = ref.route(x, whole_lw, whole)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-6)


def _ungated_case(where, t=512, k=6, held=8, width=128, h=16, f=8, seed=0):
    """Operands of an ungated layer holding ``held`` of ``width`` experts,
    with the held rows ``inside`` the first window, ``past`` it (every choice
    on held experts), or ``none`` at all."""
    rng = np.random.default_rng(seed)
    if where == "inside":
        sel = rng.integers(0, width, (t, k))
    elif where == "past":
        sel = rng.integers(0, held, (t, k))
    elif where == "one_expert":
        sel = np.full((t, k), 3)
    else:
        sel = rng.integers(held, width, (t, k))
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (t, h)), jnp.asarray(sel, jnp.int32),
            jax.random.uniform(ks[1], (t, k)),
            jax.random.normal(ks[2], (held, h, f)) * h ** -0.5,
            jax.random.normal(ks[3], (held, f, h)) * f ** -0.5)


def _ungated_oracle(x, sel, weights, w_up, w_down):
    out = jnp.zeros_like(x)
    for e in range(w_up.shape[0]):
        w_e = jnp.sum(jnp.where(sel == e, weights, 0.0), axis=-1)
        out = out + w_e[:, None] * (ref.relu2(x @ w_up[e]) @ w_down[e])
    return out


@pytest.mark.parametrize("width", [128, 32])
@pytest.mark.parametrize("where", ["inside", "past", "none", "one_expert"])
def test_ungated_experts_equal_the_dense_oracle_and_drop_nothing(where, width):
    """8 of 128: all rows in one window; 8 of 32: a first window of half the
    rows and, where a held row lies past it, the rest."""
    x, sel, weights, w_up, w_down = _ungated_case(where, width=width)

    def run(x, weights, w_up, w_down):
        return moe_ragged(x, sel, weights, None, w_up, w_down, router_width=width,
                          activation=ref.relu2)

    def loss(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a)))

    oracle = lambda x, weights, w_up, w_down: _ungated_oracle(  # noqa: E731
        x, sel, weights, w_up, w_down)
    with HIGHEST:
        np.testing.assert_allclose(run(x, weights, w_up, w_down),
                                   oracle(x, weights, w_up, w_down), atol=2e-5)
        got = jax.grad(loss(run), argnums=(0, 1, 2, 3))(x, weights, w_up, w_down)
        want = jax.grad(loss(oracle), argnums=(0, 1, 2, 3))(x, weights, w_up, w_down)
    for g, w in zip(got, want):  # 3072 choices on one expert: long float32 sums
        np.testing.assert_allclose(
            g, w, atol=1e-4 * float(jnp.max(jnp.abs(w))) + 1e-6, rtol=1e-4)


def _ungated_jaxpr(t, k, h, f, width):
    return jax.make_jaxpr(lambda *a: moe_ragged(
        a[0], a[1], a[2], None, a[3], a[4], router_width=width,
        activation=ref.relu2))(
        jnp.zeros((t, h)), jnp.zeros((t, k), jnp.int32), jnp.zeros((t, k)),
        jnp.zeros((8, h, f)), jnp.zeros((8, f, h))).jaxpr


def _eqns(j, name):
    return [e for e in j.eqns if e.primitive.name == name]


def test_eight_of_128_is_one_window_of_two_grouped_matmuls_and_no_cond():
    """A share thinner than an eighth of the router is not cut: twice the even
    share (an eighth of the rows) is under a quarter of them, and the routing
    of such a share trained alone passes 2, 4 and 8 times the even share on
    the chip (PERF.md, PR 32). All T k rows in one window, two grouped matmuls,
    not three, no ``cond``: a constant of the shapes."""
    t, k = 512, 6
    assert share_window_rows(t * k, 8, 128) == t * k
    assert share_window_rows(16384 * 6, 8, 128) == 16384 * 6  # the cell
    jaxpr = _ungated_jaxpr(t, k, 16, 8, 128)
    assert not _eqns(jaxpr, "cond")
    assert [e.invars[0].aval.shape[0]
            for e in _eqns(jaxpr, "ragged_dot_general")] == [t * k] * 2


def test_eight_of_32_ungated_is_one_window_of_two_grouped_matmuls_and_one_cond():
    """T k = 3072 sorted rows, a quarter of the router held: the first window
    is twice the even share (1536); what lies past it runs in the taken
    branch of exactly one ``cond``; two grouped matmuls in each, not three."""
    t, k = 512, 6
    assert share_window_rows(t * k, 8, 32) == 1536
    jaxpr = _ungated_jaxpr(t, k, 16, 8, 32)
    assert [e.invars[0].aval.shape[0]
            for e in _eqns(jaxpr, "ragged_dot_general")] == [1536] * 2
    (cond,) = _eqns(jaxpr, "cond")
    dots = [_eqns(b.jaxpr, "ragged_dot_general") for b in cond.params["branches"]]
    assert sorted(len(d) for d in dots) == [0, 2]
    assert [e.invars[0].aval.shape[0] for e in max(dots, key=len)] == [
        t * k - 1536] * 2


def test_the_dense_dispatch_computes_the_ungated_expert_too():
    cfg = tiny.config(n_routed_experts=16, expert_offset=0, router_width=16)
    lw = W.layer_slice(W.base_key(SEED), cfg, 1, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 12, cfg["hidden_size"]))
    with HIGHEST:
        outs = [MoE(common.program_config(cfg, moe_dispatch=d)).apply(
            {"params": _moe_params(lw)}, x) for d in ("ragged", "dense")]
        np.testing.assert_allclose(outs[0], outs[1], atol=2e-5)
        np.testing.assert_allclose(outs[0], ref.experts_ff(x, lw, cfg), atol=2e-5)


# --------------------------------------------------------------------------- #
# attention without rope
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_attention_without_rope_at_sixteen_query_heads_a_kv_head(impl):
    """32 query heads over 2 KV heads — the published 16 : 1 — and no
    position: the program's attention equals the reference's, and moving
    nothing but the positions it is handed moves nothing."""
    from accelerate_tpu.ops.flash_attention import kernel_interpret_mode

    cfg = tiny.config(num_attention_heads=32, num_key_value_heads=2, head_dim=8)
    lw = W.layer_slice(W.base_key(SEED), cfg, 5, jnp.float32)
    params = W._nest({tuple(k.split("/")[1:]): v for k, v in lw.items()
                      if k.startswith("attn/")})
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 128, cfg["hidden_size"]))
    attn = Attention(common.program_config(cfg, attention_impl=impl))
    pos = jnp.broadcast_to(jnp.arange(128)[None], (2, 128))
    with HIGHEST, kernel_interpret_mode():
        got = attn.apply({"params": params}, x, pos)
        shifted = attn.apply({"params": params}, x, pos + 1000)
    np.testing.assert_allclose(got, ref.attention_op(x, lw, cfg), atol=3e-5, rtol=1e-4)
    np.testing.assert_array_equal(got, shifted)


# --------------------------------------------------------------------------- #
# through the Accelerator, with the counters
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("kernels", [False, True], ids=["jax_numpy", "kernels"])
def test_unified_step_trains_the_stack_and_returns_its_counters(kernels):
    """Over the 8-device mesh the scan is the ``jax.numpy`` form
    (``ssm_scan_kernel`` 0.0); on one device under the interpreter every
    Mamba-2 layer's scan is the two kernels, forward, recomputed under the
    remat and backward (1.0), and the stack trains the same."""
    import contextlib

    import optax

    from accelerate_tpu import Accelerator

    cfg = tiny.config()
    model = _model(cfg, remat="dots_with_no_batch_dims")
    acc = Accelerator()
    if kernels:
        acc.reform_mesh(jax.devices()[:1])  # a Mosaic kernel is not partitioned
    params, optimizer = acc.prepare(
        W.make_tree(cfg, SEED, jnp.float32), optax.adamw(1e-3))
    step = acc.unified_step(CausalLM.loss_fn(model, with_aux=True), has_aux=True)
    carry = acc.init_carry(params, optimizer)
    ids = _ids(cfg, rows=4, seq=60, seed=1)
    losses = []
    with kernel_interpret_mode() if kernels else contextlib.nullcontext():
        for _ in range(4):
            carry, metrics = step(carry, {"input_ids": ids})
            losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    aux = {k: float(v) for k, v in metrics["aux"].items()}
    assert {"ssm_delta_mean", "ssm_chunk_decay_min", "ssm_scan_kernel",
            "moe_local_choice_share", "moe_rest_window_share",
            "moe_rows_computed_over_needed"} <= set(aux)
    assert aux["ssm_scan_kernel"] == float(kernels)
    assert 0.0 < aux["ssm_chunk_decay_min"] <= 1.0 and aux["ssm_delta_mean"] > 0
    assert 0.0 < aux["moe_local_choice_share"] < 1.0
    assert step.detector.retraces == 0 if hasattr(step, "detector") else True


# --------------------------------------------------------------------------- #
# what the configuration refuses
# --------------------------------------------------------------------------- #
_SSM = dict(single_sublayer=True, layer_types=("mamba", "moe"), mamba_num_heads=4,
            mamba_head_dim=8, mamba_n_groups=2, mamba_state_size=8,
            num_experts=4, moe_router="sigmoid")


@pytest.mark.parametrize("kw,match", [
    (dict(_SSM, moe_n_group=3), "moe_n_group 3 / moe_topk_group 1"),
    (dict(_SSM, moe_topk_group=4), "moe_n_group 1 / moe_topk_group 4"),
    (dict(_SSM, single_sublayer=False), "set single_sublayer"),
    (dict(_SSM, layer_types=None), "layer_types=None"),
    (dict(_SSM, num_dense_layers=1), "num_dense_layers"),
    (dict(_SSM, post_norms=True), "post_norms"),
    (dict(_SSM, fused_kernels=True), "fused_kernels"),
    (dict(_SSM, num_experts=0), "num_experts is 0"),
    (dict(_SSM, mamba_num_heads=0), "mamba_num_heads 0"),
    (dict(_SSM, mamba_n_groups=3), "multiple of mamba_n_groups"),
    (dict(_SSM, mlp_gated=False, moe_dispatch="capacity"), "without a gate matrix"),
    (dict(_SSM, mlp_activation="relu2"), "relu2 experts are"),
    (dict(_SSM, moe_shared_gate=True), "moe_shared_gate gates a shared expert"),
    (dict(use_rope=False, attention_class="eva"), "carries no position"),
    (dict(use_rope=False, rope_scaling={"rope_type": "linear", "factor": 2.0}),
     "carries no position"),
    (dict(mlp_activation="swish"), "unknown mlp_activation"),
])
def test_configuration_refuses_what_it_cannot_run(kw, match):
    with pytest.raises(ValueError, match=match):
        TransformerConfig.tiny(**kw)


@pytest.mark.parametrize("layer_types,kw,match", [
    (("mamba", "full_attention"), {}, "'mamba' layer keeps no state"),
    (("full_attention", "mlp"), dict(use_rope=False), "use_rope=False"),
])
def test_a_state_space_layer_and_attention_without_rope_refuse_to_decode(
        layer_types, kw, match):
    cfg = TransformerConfig.tiny(**dict(_SSM, layer_types=layer_types, **kw))
    model = CausalLM(cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    with pytest.raises(NotImplementedError, match=match):
        model.apply({"params": params}, ids, decode=True, mutable=["cache"])


def test_a_one_sublayer_stack_takes_no_adapters():
    from accelerate_tpu.models.transformer import Block

    cfg = TransformerConfig.tiny(**_SSM)
    block = Block(cfg, mixer="mamba", ff=None)
    x = jnp.zeros((1, 8, cfg.hidden_size))
    with pytest.raises(NotImplementedError, match="adapters"):
        block.init(jax.random.PRNGKey(0), x, None, None, None, None, object())


# --------------------------------------------------------------------------- #
# the scope paths the benchmark's per-layer metrics read
# --------------------------------------------------------------------------- #
def _metric_args(name):
    with open(os.path.join(ROOT, "benchmark", "metrics", f"{name}.json")) as f:
        return json.load(f)["args"]


def test_the_per_layer_metrics_find_their_scope_paths():
    """Every scope the new cell's metrics read is in the lowered gradient of
    the loss, cleaned as the benchmark's ``scope_share`` cleans a trace's."""
    from harness import program_trace

    cfg = tiny.config()
    model, ids = _model(cfg, remat="dots_with_no_batch_dims"), _ids(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)["params"]
    text = jax.jit(jax.grad(CausalLM.loss_fn(model))).lower(
        params, {"input_ids": ids}).compile().as_text()
    paths = {program_trace.scope_of(raw, "CausalLM")
             for raw in re.findall(r'op_name="([^"]+)"', text)}
    assert not [p for p in paths if "._" in p], "a method's name is in a path"
    metrics = {
        "ssm_device_share.train": [
            "layers_0/b0/ssm/in_proj/dot_general", "layer_4/ssm/conv1d/",
            "layers_0/b0/ssm/scan/intra/", "layer_7/ssm/scan/states/",
            "layers_0/b0/ssm/scan/inter/", "layer_4/ssm/gate_norm/",
            "layer_4/ssm/out_proj/dot_general", "layer_4/ssm_norm/"],
        "ssm_scan_roofline.train": [
            "layer_4/ssm/scan/intra/", "layer_4/ssm/scan/states/",
            "layer_4/ssm/scan/inter/"],
        "moe_shared_device_share.train": [
            "layer_6/moe/shared/up_proj/dot_general",
            "layers_0/b1/moe/shared/down_proj/dot_general"],
        "moe_route_device_share.train": [
            "layers_0/b1/moe/route/router/dot_general", "layer_8/moe/dispatch/",
            "layer_8/moe/combine/"],
        "moe_experts_device_share.train": ["layer_6/moe/experts/"],
        "attn_device_share.moe_train": [
            "layer_5/attn/q_proj/dot_general", "layer_5/attn_norm/"],
        "head_loss_device_share.train": ["lm_head/dot_general"],
    }
    for name, samples in metrics.items():
        rx = re.compile(_metric_args(name)["scope"])
        hit = [p for p in paths if rx.search(p)]
        for sample in samples:
            assert any(sample in p for p in hit), (name, sample, sorted(hit)[:8])
    # what one metric reads no other of the cell's model-step shares reads
    scan = re.compile(_metric_args("ssm_scan_roofline.train")["scope"])
    shared = re.compile(_metric_args("moe_shared_device_share.train")["scope"])
    route = re.compile(_metric_args("moe_route_device_share.train")["scope"])
    assert not [p for p in paths if shared.search(p) and route.search(p)]
    assert not [p for p in paths if scan.search(p) and "ssm/scan/" not in p]
    for name in ("step_roofline.ssm_moe_train", "ssm_scan_roofline.train",
                 "moe_experts_roofline.ssm_moe_train"):
        fn = cells.named(_metric_args(name)["work"])
        need = fn(tiny.real(), {"tokens_per_step_per_chip": 16384, "seq_len": 8192})
        assert need["flops"] > 0 and need["bytes"] > 0
    # where the scan is the two kernels (PR 47) both calls lie under the same
    # scope in all three passes — the forward, the forward again under the
    # remat, the backward —, so both scan metrics go on reading the work
    with kernel_interpret_mode():
        lowered = jax.jit(jax.grad(CausalLM.loss_fn(model))).lower(
            params, {"input_ids": ids}).as_text(debug_info=True)
    calls = {raw for raw in re.findall(r'loc\("([^"]+/pallas_call)"', lowered)
             if "ssd_chunked" in raw}
    for layer in ("layer_4/", "layer_7/", "/b0/"):
        mine = sorted(raw for raw in calls if layer in raw)
        for kernel, passes in (("ssd_chunked_fwd", [
                lambda r: "rematted_computation" not in r,
                lambda r: "rematted_computation" in r]),
                               ("ssd_chunked_bwd", [lambda r: "checkpoint/" in r])):
            for which in passes:
                hit = [r for r in mine if kernel in r and which(r)]
                assert hit, (layer, kernel, mine)
                for raw in hit:
                    cleaned = program_trace.scope_of(raw, "CausalLM")
                    assert cleaned.endswith(f"ssm/scan/{kernel}/pallas_call"), raw
                    assert scan.search(cleaned) and re.search(
                        _metric_args("ssm_device_share.train")["scope"], cleaned)
    assert not [r for r in calls if "intra" in r or "states" in r or "inter/" in r]


def test_the_flash_share_reads_the_flash_kernels_and_counts_one_attention_layer():
    """``flash_roofline.ssm_moe_train`` selects the three Mosaic kernels by
    their names — not XLA's grouped-matmul custom calls, which the accepted
    ``flash_roofline.train`` pattern would also take — and its needed work is
    that of the ONE attention layer of the nine."""
    args = _metric_args("flash_roofline.ssm_moe_train")
    ops = re.compile(args["ops"])
    for label in ("flash_fwd.1 custom-call tpu_custom_call",
                  "flash_bwd_dq.1 custom-call tpu_custom_call",
                  "flash_bwd_dkv.1 custom-call tpu_custom_call"):
        assert ops.search(label)
    assert not ops.search("ragged-dot-none.20 custom-call tpu_custom_call")
    need = cells.named(args["work"])(
        tiny.real(), {"rows_per_chip": 2, "seq_len": 8192})
    # seven causal matmuls of 2 x 4096.5 keys x 128 a query row and head
    assert need["flops"] == 7 * 2.0 * 2 * 8192 * 32 * 128 * 4096.5
    assert need["bytes"] == 6.0 * 2 * 8192 * (32 + 2) * 128 * 2


# --------------------------------------------------------------------------- #
# the experts' width on whole tiles (ops.moe.padded_expert_shape)
# --------------------------------------------------------------------------- #
def test_the_cell_pads_both_sides_and_the_tiny_stack_neither():
    """The published 2688 x 1856 goes to 3072 x 2048 (the width is 14.5
    lanes; a tenth and a seventh of zeros); the shared expert's 3712 and the
    tiny stack's 48 x 40 are left, so every test above runs the program it
    ran; the needed work counts the published width whatever is multiplied."""
    from accelerate_tpu.ops.moe import padded_expert_shape

    real = tiny.real()
    h, f = real["hidden_size"], real["moe_intermediate_size"]
    assert padded_expert_shape(h, f) == (3072, 2048)
    assert padded_expert_shape(h, real["moe_shared_expert_intermediate_size"])[1] == 3712
    cfg = tiny.config()
    assert padded_expert_shape(cfg["hidden_size"], cfg["moe_intermediate_size"]) == (48, 40)
    needed = work.moe_experts_work(real, {"tokens_per_step_per_chip": 16384})
    # 4 expert layers, 6 x 8 / 128 local choices a token, two products, x 3
    # for the backward pass: at the published 2688 x 1856
    assert needed["flops"] == 3 * 4 * (6 * 8 / 128) * 2 * (2 * 2688 * 1856) * 16384


@pytest.mark.parametrize("f,ratio", [(40, 1.0), (464, 512 / 464)])
def test_the_stack_returns_the_width_it_computed_beside_the_other_counters(f, ratio):
    """``loss_fn(with_aux=True)`` hands ``moe_width_computed_over_published``
    out with the load counters: 1.0 at the tiny width, 512 / 464 at a width
    the rule pads — the same value in every expert layer, so its mean —, and
    the padded stack's loss and gradients are finite with the seeded tree's
    shapes."""
    cfg = tiny.config(pattern="ME", moe_intermediate_size=f)
    model = _model(cfg)
    params = W.make_tree(cfg, SEED, jnp.float32)
    batch = {"input_ids": _ids(cfg, rows=2, seq=24)}
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        CausalLM.loss_fn(model, with_aux=True), has_aux=True))(params, batch)
    assert np.isfinite(float(loss))
    np.testing.assert_allclose(
        float(aux["moe_width_computed_over_published"]), ratio, rtol=1e-6)
    assert {"moe_local_choice_share", "moe_rows_computed_over_needed"} <= set(aux)
    assert jax.tree.map(jnp.shape, grads) == jax.tree.map(jnp.shape, params)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in jax.tree.leaves(grads))


def test_the_cells_grouped_matmuls_are_given_whole_tiles_at_its_real_shapes():
    """``train-ssm-moe-1chip``'s own gradient, lowered at 2 x 8192 tokens
    over abstract weights: every stack of expert kernels a grouped matmul
    sees — the eight held experts' and the zero group's, forward and
    backward — is 3072 x 2048 or 2048 x 3072, none the published 2688 x
    1856; the parameters and their gradients keep the published shape."""
    cell = cells.load_cell(tiny.TWIN)
    spec, cfg = cell["spec"], cell["config"]
    seq = spec["traffic"]["seq_len"]
    model = CausalLM(common.program_config(
        cfg, max_seq_len=seq, remat=spec["remat"], dtype=spec["compute_dtype"]))
    ids = jax.ShapeDtypeStruct((spec["rows_per_chip"], seq), jnp.int32)
    text = jax.jit(jax.grad(CausalLM.loss_fn(model))).lower(
        W.abstract_tree(cfg, jnp.float32), {"input_ids": ids}).as_text()
    stacks = set(re.findall(r"tensor<9x(\d{4})x(\d{4})xbf16>", text))
    assert stacks == {("3072", "2048"), ("2048", "3072")}, stacks
    assert "tensor<8x2688x1856xf32>" in text and "tensor<8x1856x2688xf32>" in text


def test_the_cells_expert_layers_scatter_no_rows_at_its_real_shapes(row_scatters):
    """``train-ssm-moe-1chip``'s own gradient, lowered at 2 x 8192 tokens
    over abstract weights: the one ``stablehlo.scatter`` left that adds
    whole rows is the embedding's gradient (16,384 rows of the table from 2
    x 8192 tokens); none moves the 98,304 sorted rows of an expert layer,
    forward (the combine) or backward (the dispatch's transpose) — both are
    gathers by the sort's permutation and its inverse (``ops.moe._to_experts``, ``_to_tokens``)."""
    cell = cells.load_cell(tiny.TWIN)
    spec, cfg = cell["spec"], cell["config"]
    seq = spec["traffic"]["seq_len"]
    model = CausalLM(common.program_config(
        cfg, max_seq_len=seq, remat=spec["remat"], dtype=spec["compute_dtype"]))
    ids = jax.ShapeDtypeStruct((spec["rows_per_chip"], seq), jnp.int32)
    text = jax.jit(jax.grad(CausalLM.loss_fn(model))).lower(
        W.abstract_tree(cfg, jnp.float32), {"input_ids": ids}).as_text()
    assert row_scatters(text, 128) == [
        ("16384x2688xbf16", "2x8192x2688xbf16")], row_scatters(text, 128)
    # the gathers are there: 98,304 rows at the padded 3072 and the published 2688
    assert "tensor<98304x3072xbf16>" in text and "tensor<6x16384x3072xbf16>" in text

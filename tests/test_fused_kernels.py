"""Fused Pallas step kernels (ops/fused.py — ISSUE 10): the attention
prologue must match the unfused module chain (forward AND grads) in
interpret mode on CPU, the adamw epilogue must be BITWISE-fp32 identical
to the optax `_sync_apply` tail (including the fp16 overflow hold), the
zero-retrace-after-warmup contract must survive ``fused_kernels=True``,
and the config flag must round-trip through ``prepare`` into telemetry.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from accelerate_tpu import Accelerator
from accelerate_tpu.models import CausalLM, TransformerConfig
from accelerate_tpu.ops.fused import (
    adamw_epilogue_reference,
    fused_adamw,
    fused_qkv_prologue,
    maybe_fused_epilogue,
    prologue_reference,
    prologue_supported,
    rope_inv_freqs,
)
from accelerate_tpu.ops.flash_attention import kernel_interpret_mode
from accelerate_tpu.state import AcceleratorState, GradientState


@pytest.fixture(autouse=True)
def _interpret_kernels():
    """Nothing in ops/ picks interpret mode by itself: without this
    context the kernels lower for Mosaic and fail on the CPU backend."""
    with kernel_interpret_mode():
        yield


def _reset():
    AcceleratorState._reset_state()
    GradientState._reset_state()


def _tree_bitwise_equal(a, b):
    leaves_a, leaves_b = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(leaves_a) == len(leaves_b)
    for la, lb in zip(leaves_a, leaves_b):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# --------------------------------------------------------------------- #
# prologue: fused kernel vs the plain-JAX reference (direct)
# --------------------------------------------------------------------- #
def _prologue_inputs(b=2, s=32, hidden=64, heads=4, kv_heads=2, d=16,
                     bias=False, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(ks[0], (b, s, hidden), jnp.float32)
    scale = jax.random.normal(ks[1], (hidden,), jnp.float32) * 0.1
    wq = jax.random.normal(ks[2], (hidden, heads * d), jnp.float32) * 0.05
    wk = jax.random.normal(ks[3], (hidden, kv_heads * d), jnp.float32) * 0.05
    wv = jax.random.normal(ks[4], (hidden, kv_heads * d), jnp.float32) * 0.05
    bq = bk = bv = None
    if bias:
        bq = jax.random.normal(ks[5], (heads * d,), jnp.float32)
        bk = jax.random.normal(ks[6], (kv_heads * d,), jnp.float32)
        bv = jax.random.normal(ks[7], (kv_heads * d,), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    statics = dict(
        eps=1e-6, norm_offset=False, num_heads=heads, num_kv_heads=kv_heads,
        head_dim=d, dtype=jnp.float32,
    )
    return (x, scale, wq, wk, wv, bq, bk, bv, positions), statics


@pytest.mark.parametrize("bias", [False, True])
def test_prologue_kernel_matches_reference(bias):
    args, statics = _prologue_inputs(bias=bias)
    theta = 10000.0
    inv = rope_inv_freqs(statics["head_dim"], theta, None)
    ref = prologue_reference(*args, inv, **statics)
    out = fused_qkv_prologue(
        *args, theta=theta, scaling=None,
        **{k: v for k, v in statics.items()},
    )
    for o, r, name in zip(out, ref, "qkv"):
        assert o.shape == r.shape, name
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(r), rtol=1e-6, atol=1e-6, err_msg=name
        )


def test_prologue_grad_matches_reference():
    """The custom_vjp backward (jax.vjp of the reference) must give the
    reference chain's grads for x, the norm scale, and every weight."""
    args, statics = _prologue_inputs()
    theta = 10000.0
    inv = rope_inv_freqs(statics["head_dim"], theta, None)
    diff = args[:5]  # x, scale, wq, wk, wv (no biases in this case)

    def fused_loss(x, scale, wq, wk, wv):
        q, k, v = fused_qkv_prologue(
            x, scale, wq, wk, wv, None, None, None, args[8],
            theta=theta, scaling=None, **statics,
        )
        return jnp.sum(q * q) + jnp.sum(k) + jnp.sum(v * 2.0)

    def ref_loss(x, scale, wq, wk, wv):
        q, k, v = prologue_reference(
            x, scale, wq, wk, wv, None, None, None, args[8], inv, **statics
        )
        return jnp.sum(q * q) + jnp.sum(k) + jnp.sum(v * 2.0)

    g_f = jax.grad(fused_loss, argnums=tuple(range(5)))(*diff)
    g_r = jax.grad(ref_loss, argnums=tuple(range(5)))(*diff)
    for gf, gr in zip(g_f, g_r):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), rtol=1e-5, atol=1e-6
        )


def test_prologue_supported_gates_shapes():
    # rope pairs i with i + D/2: odd head_dim can never fuse
    assert not prologue_supported(4, 2, 15, 2, 32, 64)
    # interpret mode (CPU) has no tiling constraints beyond row blocking
    assert prologue_supported(4, 2, 16, 2, 32, 64)


# --------------------------------------------------------------------- #
# prologue: whole-model parity, fused_kernels=True vs the module chain
# --------------------------------------------------------------------- #
def _tiny_pair():
    cfg = TransformerConfig.tiny(num_layers=2)
    return cfg, dataclasses.replace(cfg, fused_kernels=True)


def test_model_forward_parity_fused_vs_unfused():
    cfg_u, cfg_f = _tiny_pair()
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg_u.vocab_size, (2, 64)),
        jnp.int32,
    )
    params = CausalLM(cfg_u).init(jax.random.PRNGKey(0), ids)["params"]
    # same param tree both ways: _ProjParams declares nn.Dense's exact
    # names/shapes/init streams, so checkpoints interchange
    params_f = CausalLM(cfg_f).init(jax.random.PRNGKey(0), ids)["params"]
    _tree_bitwise_equal(params, params_f)
    logits_u = CausalLM(cfg_u).apply({"params": params}, ids)
    logits_f = CausalLM(cfg_f).apply({"params": params}, ids)
    np.testing.assert_allclose(
        np.asarray(logits_f), np.asarray(logits_u), rtol=1e-5, atol=1e-5
    )


def test_model_grad_parity_fused_vs_unfused():
    cfg_u, cfg_f = _tiny_pair()
    ids = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg_u.vocab_size, (2, 64)),
        jnp.int32,
    )
    batch = {"input_ids": ids}
    params = CausalLM(cfg_u).init(jax.random.PRNGKey(0), ids)["params"]
    g_u = jax.grad(CausalLM.loss_fn(CausalLM(cfg_u)))(params, batch)
    g_f = jax.grad(CausalLM.loss_fn(CausalLM(cfg_f)))(params, batch)
    for (pu, lu), (pf, lf) in zip(
        jax.tree_util.tree_leaves_with_path(g_u),
        jax.tree_util.tree_leaves_with_path(g_f),
    ):
        assert pu == pf
        np.testing.assert_allclose(
            np.asarray(lf), np.asarray(lu), rtol=2e-4, atol=1e-5,
            err_msg=jax.tree_util.keystr(pu),
        )


# --------------------------------------------------------------------- #
# epilogue: bitwise fp32 parity with the optax chain
# --------------------------------------------------------------------- #
def _epilogue_tree(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    params = {
        "w": jax.random.normal(ks[0], (37, 19), jnp.float32),
        "b": jax.random.normal(ks[1], (19,), jnp.float32),
        "s": jax.random.normal(ks[2], (), jnp.float32),
    }
    grads = {
        "w": jax.random.normal(ks[3], (37, 19), jnp.float32) * 3.0,
        "b": jax.random.normal(ks[4], (19,), jnp.float32) * 3.0,
        "s": jax.random.normal(ks[5], (), jnp.float32) * 3.0,
    }
    return params, grads


@pytest.mark.parametrize("finite", [True, False])
def test_epilogue_kernel_bitwise_vs_reference(finite):
    """maybe_fused_epilogue == the spelled-out optax chain, bitwise, with
    the clip scale TRACED from the global norm (as `_sync_apply` computes
    it — a compile-time-constant clip lets XLA fold the multiplies and
    breaks the comparison, so constants are exactly what NOT to test)."""
    params, grads = _epilogue_tree()
    opt = fused_adamw(3e-4)
    state = opt.init(params)
    fin = jnp.asarray(finite)

    @jax.jit
    def run_fused(params, grads, state):
        gnorm = optax.global_norm(grads)
        scale_c = jnp.minimum(1.0, 0.5 / (gnorm + 1e-6))
        return maybe_fused_epilogue(
            opt, grads, state, params, clip_scale=scale_c, finite=fin
        )

    @jax.jit
    def run_ref(params, grads, state):
        gnorm = optax.global_norm(grads)
        scale_c = jnp.minimum(1.0, 0.5 / (gnorm + 1e-6))
        adam = state[0]
        return adamw_epilogue_reference(
            grads, params, adam.mu, adam.nu, adam.count,
            hp=opt.hyperparams, clip_scale=scale_c, finite=fin,
            step_size=jnp.asarray(-3e-4, jnp.float32),
        )

    new_params, new_state = run_fused(params, grads, state)
    ref_params, ref_mu, ref_nu, ref_count = run_ref(params, grads, state)
    _tree_bitwise_equal(new_params, ref_params)
    _tree_bitwise_equal(new_state[0].mu, ref_mu)
    _tree_bitwise_equal(new_state[0].nu, ref_nu)
    assert int(new_state[0].count) == int(ref_count) == (1 if finite else 0)
    if not finite:
        _tree_bitwise_equal(new_params, params)  # the hold held


def test_epilogue_declines_non_fp32_trees():
    params, grads = _epilogue_tree()
    params = jax.tree.map(lambda l: l.astype(jnp.bfloat16), params)
    opt = fused_adamw(3e-4)
    state = opt.init(params)
    assert maybe_fused_epilogue(
        opt, grads, state, params,
        clip_scale=None, finite=jnp.asarray(True),
    ) is None  # bitwise contract is scoped to fp32; caller falls back


def test_fused_adamw_env_knob(monkeypatch):
    monkeypatch.setenv("ACCELERATE_TPU_FUSED_EPILOGUE", "0")
    opt = fused_adamw(1e-3)
    assert opt.fused is False
    params, grads = _epilogue_tree()
    assert maybe_fused_epilogue(
        opt, grads, opt.init(params), params,
        clip_scale=None, finite=jnp.asarray(True),
    ) is None
    monkeypatch.delenv("ACCELERATE_TPU_FUSED_EPILOGUE")
    assert fused_adamw(1e-3).fused is True


# --------------------------------------------------------------------- #
# epilogue end-to-end: fused_adamw through unified_step == optax.adamw
# --------------------------------------------------------------------- #
def _loss_fn(params, batch):
    pred = batch["x"][:, 0] * params["w"] + params["b"]
    return jnp.mean((pred - batch["y"]) ** 2)


def _train(optimizer, *, steps=4, max_grad_norm=None, w0=0.0,
           mixed_precision=None, policy=None):
    _reset()
    kwargs = {}
    if mixed_precision is not None:
        kwargs["mixed_precision"] = mixed_precision
    if policy is not None:
        kwargs["mixed_precision_policy"] = policy
    acc = Accelerator(**kwargs)
    params = {"w": jnp.asarray(w0), "b": jnp.asarray(0.0)}
    params, opt = acc.prepare(params, optimizer)
    step = acc.unified_step(_loss_fn, opt, max_grad_norm=max_grad_norm)
    carry = acc.init_carry(params, opt)
    rng = np.random.default_rng(0)
    metrics = None
    for _ in range(steps):
        x = rng.normal(size=(8, 1)).astype(np.float32)
        y = (2.0 * x[:, 0] + 3.0).astype(np.float32)
        carry, metrics = step(
            carry, {"x": jnp.asarray(x), "y": jnp.asarray(y)}
        )
    return carry, metrics


def test_sync_apply_parity_fp32_bitwise():
    """ISSUE 10 acceptance: fused epilogue == existing `_sync_apply`
    chain, bitwise in fp32, after several real optimizer steps."""
    ref, _ = _train(optax.adamw(0.1))
    fused, _ = _train(fused_adamw(0.1))
    assert int(ref["opt_step"]) == int(fused["opt_step"]) == 4
    _tree_bitwise_equal(ref["params"], fused["params"])
    _tree_bitwise_equal(ref["opt_state"], fused["opt_state"])


def test_sync_apply_parity_with_traced_clip():
    """Clipping engaged (w0 far from optimum -> gnorm > max_grad_norm):
    params stay BITWISE identical. The stored adam moments are asserted
    to 1 ulp instead: XLA:CPU duplicates the clipped-grad expression
    into two fusions of the unfused program (one feeding the stored mu,
    one feeding the update) with different fma contraction, so the
    existing program's own stored moments are fusion-context-dependent
    at the last bit (jit-vs-eager optax agrees exactly; the divergence
    appears only inside the full unified_step program). The same-context
    bitwise contract is covered by
    test_epilogue_kernel_bitwise_vs_reference."""
    ref, mr = _train(optax.adamw(0.1), max_grad_norm=0.5, w0=50.0)
    fused, mf = _train(fused_adamw(0.1), max_grad_norm=0.5, w0=50.0)
    assert float(mr["grad_norm"]) == float(mf["grad_norm"]) > 0.5
    _tree_bitwise_equal(ref["params"], fused["params"])
    for lr, lf in zip(
        jax.tree.leaves(ref["opt_state"]), jax.tree.leaves(fused["opt_state"])
    ):
        lr, lf = np.asarray(lr), np.asarray(lf)
        if lr.dtype == np.float32:
            np.testing.assert_array_almost_equal_nulp(lr, lf, nulp=1)
        else:
            np.testing.assert_array_equal(lr, lf)


def test_sync_apply_parity_fp16_overflow_hold():
    """fp16 loss-scaling overflow: the fused epilogue's finite-hold must
    match the unfused skip — params held, scale halved, identically."""
    from accelerate_tpu import MixedPrecisionPolicy

    def make_policy():
        policy = MixedPrecisionPolicy.from_precision("fp16")
        policy.loss_scale_init = 2.0**15
        return policy

    out = {}
    for name, opt in (("ref", optax.adamw(1e-4)),
                      ("fused", fused_adamw(1e-4))):
        carry, metrics = _train(
            opt, mixed_precision="fp16", policy=make_policy(), w0=1e4,
        )
        assert not bool(metrics["grads_finite"])  # the overflow was real
        out[name] = carry
    _tree_bitwise_equal(out["ref"]["params"], out["fused"]["params"])
    _tree_bitwise_equal(out["ref"]["opt_state"], out["fused"]["opt_state"])
    assert float(out["fused"]["params"]["w"]) == 1e4  # held at init
    assert float(out["fused"]["loss_scale"].scale) == 2.0**15 / 2**4


# --------------------------------------------------------------------- #
# zero-retrace contract + config/telemetry round-trip
# --------------------------------------------------------------------- #
def test_zero_retraces_after_warmup_with_fused_kernels():
    """The fused prologue/epilogue must not perturb the retrace contract:
    after the first (tracing) call, every step dispatches the cached
    executable — trace-counter-asserted, and the step records carry
    fused_kernels=True for attribution."""
    _reset()
    cfg = TransformerConfig.tiny(num_layers=2, fused_kernels=True)
    model = CausalLM(cfg)
    acc = Accelerator(telemetry=True)
    params = acc.prepare(
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))[
            "params"
        ]
    )
    opt = acc.prepare(fused_adamw(3e-4))
    carry = acc.init_carry(params, opt)
    step = acc.unified_step(CausalLM.loss_fn(model), max_grad_norm=1.0)
    batch = {
        "input_ids": jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 64)),
            jnp.int32,
        )
    }
    carry, metrics = step(carry, batch)  # warmup: the one real trace
    np.asarray(metrics["loss"])
    detector = acc.telemetry.detector(step.label)
    signatures = len(detector._seen)
    retraces = detector.retraces
    for _ in range(3):
        carry, metrics = step(carry, batch)
    np.asarray(metrics["loss"])
    assert detector.retraces == retraces
    assert len(detector._seen) == signatures
    recs = [r for r in acc.telemetry.records if r.get("kind") == "step"]
    assert len(recs) == 4
    for rec in recs[1:]:
        assert rec["retraced"] is False
        assert rec["fused_kernels"] is True


def test_config_flag_round_trips_through_prepare():
    _reset()
    cfg = TransformerConfig.tiny(fused_kernels=True)
    assert TransformerConfig.tiny().fused_kernels is False  # default off
    model = CausalLM(cfg)
    loss = CausalLM.loss_fn(model)
    assert loss.fused_kernels is True  # unified_step reads this for telemetry
    acc = Accelerator()
    opt = acc.prepare(fused_adamw(1e-3))
    # prepare wraps in AcceleratedOptimizer but must keep the transform
    # (and its kernel opt-in) intact — _sync_apply reads these attrs
    assert isinstance(opt.optimizer, optax.GradientTransformation)
    assert opt.optimizer.fused is True
    assert opt.optimizer.hyperparams["learning_rate"] == 1e-3


def test_unfused_step_records_fused_false():
    _reset()
    acc = Accelerator(telemetry=True)
    params = {"w": jnp.asarray(0.0), "b": jnp.asarray(0.0)}
    params, opt = acc.prepare(params, optax.adamw(0.1))
    step = acc.unified_step(_loss_fn, opt)
    carry = acc.init_carry(params, opt)
    x = np.ones((4, 1), np.float32)
    carry, _ = step(
        carry, {"x": jnp.asarray(x), "y": jnp.asarray(x[:, 0])}
    )
    recs = [r for r in acc.telemetry.records if r.get("kind") == "step"]
    assert recs and recs[-1]["fused_kernels"] is False

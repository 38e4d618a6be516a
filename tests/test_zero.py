"""ZeRO-1/2 (SHARD_OPT / SHARD_GRAD_OP) semantics.

Reference capability: DeepSpeed ZeRO stages 1/2 (utils/dataclasses.py:739,
utils/deepspeed.py) — optimizer-state (and grad-buffer) sharding with
replicated params. Here the TPU expression: explicit out_shardings on
optax.init over the fsdp mesh axis + a sharded accumulated-grad carry.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from accelerate_tpu import Accelerator
from accelerate_tpu.utils.dataclasses import ParallelismPlugin, ShardingStrategy


def _params(key=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(key))
    return {
        "w1": jax.random.normal(k1, (16, 32)),
        "w2": jax.random.normal(k2, (32, 8)),
    }


def _loss(p, b):
    h = jnp.tanh(b["x"] @ p["w1"])
    return jnp.mean((h @ p["w2"] - b["y"]) ** 2)


def _train(strategy, num_accum=1, steps=6, seed=0):
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    plugin = ParallelismPlugin(
        fsdp_size=8, sharding_strategy=strategy, min_weight_size=8
    )
    acc = Accelerator(
        parallelism_plugin=plugin, gradient_accumulation_steps=num_accum
    )
    params = acc.prepare(_params())
    opt = acc.prepare(optax.adam(1e-2))
    carry = acc.init_carry(params, opt)
    step = acc.unified_step(_loss)
    rng = np.random.default_rng(seed)
    for _ in range(steps):
        batch = {
            "x": jnp.asarray(rng.normal(size=(8, 16)), jnp.float32),
            "y": jnp.asarray(rng.normal(size=(8, 8)), jnp.float32),
        }
        from accelerate_tpu.parallel.sharding import batch_sharding

        batch = jax.device_put(batch, batch_sharding(acc.mesh))
        carry, metrics = step(carry, batch)
    return acc, carry


def _specs(tree):
    return [
        tuple(l.sharding.spec) if hasattr(l.sharding, "spec") else None
        for l in jax.tree.leaves(tree)
    ]


def test_zero1_shards_opt_state_replicates_params():
    acc, carry = _train(ShardingStrategy.SHARD_OPT)
    # params replicated
    for spec in _specs(carry["params"]):
        assert all(s is None for s in spec), spec
    # at least the Adam moment buffers (shape == param shape) fsdp-sharded
    moment_specs = [
        s for s, l in zip(_specs(carry["opt_state"]), jax.tree.leaves(carry["opt_state"]))
        if getattr(l, "ndim", 0) >= 2
    ]
    assert moment_specs, "no moment buffers found"
    for spec in moment_specs:
        assert any(s == "fsdp" for s in spec), spec


def test_zero2_additionally_shards_grad_buffer():
    acc, carry = _train(ShardingStrategy.SHARD_GRAD_OP, num_accum=2)
    for spec in _specs(carry["params"]):
        assert all(s is None for s in spec), spec
    accum_specs = [
        s for s, l in zip(_specs(carry["accum_grads"]), jax.tree.leaves(carry["accum_grads"]))
        if getattr(l, "ndim", 0) >= 2
    ]
    for spec in accum_specs:
        assert any(s == "fsdp" for s in spec), spec


@pytest.mark.parametrize(
    "strategy", [ShardingStrategy.SHARD_OPT, ShardingStrategy.SHARD_GRAD_OP]
)
def test_zero_trains_equivalently_to_dp(strategy):
    """Sharding opt state / grads must not change the math (reference
    training_check pattern: identical weights across configs)."""
    _, carry_dp = _train(ShardingStrategy.NO_SHARD, num_accum=2)
    _, carry_z = _train(strategy, num_accum=2)
    for a, b in zip(
        jax.tree.leaves(carry_dp["params"]), jax.tree.leaves(carry_z["params"])
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_zero1_step_built_before_init_carry():
    """Building unified_step before init_carry must still pin ZeRO-1 opt
    shardings (review finding: build-time capture silently disabled it)."""
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    plugin = ParallelismPlugin(
        fsdp_size=8, sharding_strategy=ShardingStrategy.SHARD_OPT,
        min_weight_size=8,
    )
    acc = Accelerator(parallelism_plugin=plugin)
    params = acc.prepare(_params())
    opt = acc.prepare(optax.adam(1e-2))
    step = acc.unified_step(_loss)  # built FIRST: opt state not created yet
    carry = acc.init_carry(params, opt)
    batch = {
        "x": jnp.ones((8, 16), jnp.float32),
        "y": jnp.zeros((8, 8), jnp.float32),
    }
    carry, _ = step(carry, batch)
    moment_specs = [
        tuple(l.sharding.spec)
        for l in jax.tree.leaves(carry["opt_state"])
        if getattr(l, "ndim", 0) >= 2
    ]
    assert moment_specs
    for spec in moment_specs:
        assert any(s == "fsdp" for s in spec), spec


def test_zero1_keeps_embedding_replicated():
    """The embedding's ("vocab","zero") annotation is a WEIGHT-shard seat:
    under ZeRO-1 (SHARD_OPT) params stay replicated — the fsdp axis must
    not leak into param shardings through the zero rule (code-review r3)."""
    from accelerate_tpu.models import CausalLM, TransformerConfig
    from accelerate_tpu.parallel.mesh import build_mesh
    from accelerate_tpu.parallel.sharding import (
        get_logical_specs,
        infer_param_shardings,
        unbox_params,
    )
    from accelerate_tpu.utils.dataclasses import ParallelismPlugin, ShardingStrategy

    plugin = ParallelismPlugin(
        fsdp_size=8, sharding_strategy=ShardingStrategy.SHARD_OPT,
        min_weight_size=16,
    )
    mesh = build_mesh(plugin)
    cfg = TransformerConfig.tiny()
    variables = CausalLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    specs = infer_param_shardings(
        unbox_params(variables)["params"], mesh, plugin,
        logical_specs=get_logical_specs(variables)["params"],
    )
    embed_spec = specs["embed"]["embedding"].spec
    assert "fsdp" not in str(embed_spec), embed_spec


def test_zero3_moments_are_born_on_their_params_shards():
    """FULL_SHARD through the README line ParallelismPlugin(fsdp_size=-1):
    adam's mu/nu take their parameter's sharding AT INIT. optax builds
    them with zeros_like (no data dependence on the sharded params), so a
    bare jit left every moment whole on device 0 until the first step."""
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    # dp's default -1 yields to the axis the caller marks auto
    plugin = ParallelismPlugin(fsdp_size=-1, min_weight_size=8)
    assert (plugin.dp_size, plugin.fsdp_size) == (1, -1)
    acc = Accelerator(parallelism_plugin=plugin)
    assert dict(acc.mesh.shape)["fsdp"] == 8
    params, opt = acc.prepare(_params(), optax.adamw(1e-2))
    adam = opt.opt_state[0]
    for moments in (adam.mu, adam.nu):
        for name, leaf in moments.items():
            assert leaf.committed, name
            assert leaf.sharding == params[name].sharding, name
            assert len(leaf.addressable_shards) == 8
            assert leaf.addressable_shards[0].data.size == leaf.size // 8
    assert adam.count.committed and adam.count.sharding.is_fully_replicated

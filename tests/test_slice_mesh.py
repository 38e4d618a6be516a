"""Hierarchical (multi-slice) mesh tests: slice-aware device assignment,
the DCN-aware gradient reduction (reduce-scatter in-slice -> all-reduce
cross-slice -> all-gather in-slice) proven numerically equivalent to the
flat all-reduce, the collective-overlap policy for DCN-crossing meshes,
and zero retraces after warmup on the hierarchical layout.

All CPU-runnable: ``ACCELERATE_TPU_NUM_SLICES`` simulates a multi-slice
topology on the virtual 8-device backend (CPU devices carry no
``slice_index``, so the env override is the only way to exercise these
paths off-TPU — which is exactly what it exists for).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from accelerate_tpu import Accelerator, ParallelismPlugin
from accelerate_tpu.compilation.overlap import (
    DCN_OVERLAP_OPTIONS,
    overlap_options,
)
from accelerate_tpu.parallel.mesh import (
    NUM_SLICES_ENV,
    build_mesh,
    fault_domain_of_rank,
    mesh_num_slices,
    resolve_num_slices,
)
from accelerate_tpu.parallel.sharding import (
    hierarchical_psum,
    wants_collective_overlap,
)
from accelerate_tpu.utils.dataclasses import ShardingStrategy


def _fresh_accelerator(**kwargs) -> Accelerator:
    from accelerate_tpu.state import AcceleratorState, GradientState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    return Accelerator(**kwargs)


def _hier_mesh(monkeypatch, num_slices=2, dp=2, fsdp=4):
    """A dp(DCN) x fsdp(ICI) mesh simulating ``num_slices`` slices."""
    monkeypatch.setenv(NUM_SLICES_ENV, str(num_slices))
    return build_mesh(
        ParallelismPlugin(dp_size=dp, fsdp_size=fsdp, min_weight_size=1)
    )


# ---------------------------------------------------------------------- #
# slice resolution + slice-aware device assignment
# ---------------------------------------------------------------------- #
def test_resolve_num_slices_env_overrides(monkeypatch):
    monkeypatch.setenv(NUM_SLICES_ENV, "3")
    assert resolve_num_slices() == 3
    monkeypatch.delenv(NUM_SLICES_ENV)
    # CPU devices carry no slice_index -> single slice
    assert resolve_num_slices() == 1


def test_resolve_num_slices_rejects_nonpositive(monkeypatch):
    monkeypatch.setenv(NUM_SLICES_ENV, "0")
    with pytest.raises(ValueError, match="NUM_SLICES"):
        resolve_num_slices()


def test_build_mesh_hierarchical_layout(monkeypatch):
    mesh = _hier_mesh(monkeypatch)
    assert int(mesh.shape["dp"]) == 2
    assert int(mesh.shape["fsdp"]) == 4
    assert mesh_num_slices(mesh) == 2
    # slice-major assignment: each dp block (one slice in the simulation)
    # is a contiguous id range, so fsdp collectives stay inside a slice
    # and only the dp hop crosses DCN
    ids = [d.id for d in mesh.devices.flat]
    assert ids == sorted(ids)
    blocks = np.asarray(ids).reshape(2, 4)
    assert blocks[0].tolist() == [0, 1, 2, 3]
    assert blocks[1].tolist() == [4, 5, 6, 7]


def test_build_mesh_rejects_layout_that_cannot_tile_slices(monkeypatch):
    monkeypatch.setenv(NUM_SLICES_ENV, "2")
    # dp*pp = 1 cannot tile 2 slices: fsdp would span DCN silently
    with pytest.raises(ValueError, match="tile"):
        build_mesh(
            ParallelismPlugin(dp_size=1, fsdp_size=8, min_weight_size=1)
        )


def test_fault_domain_of_rank():
    assert [fault_domain_of_rank(r, 8, 2) for r in range(8)] == [
        0, 0, 0, 0, 1, 1, 1, 1,
    ]
    assert [fault_domain_of_rank(r, 4, 4) for r in range(4)] == [0, 1, 2, 3]
    # single slice: everything is domain 0
    assert fault_domain_of_rank(3, 4, 1) == 0
    with pytest.raises(ValueError, match="divisible"):
        fault_domain_of_rank(0, 6, 4)


# ---------------------------------------------------------------------- #
# hierarchical gradient reduction == flat all-reduce (CPU-mesh parity)
# ---------------------------------------------------------------------- #
def _psum_fns(mesh):
    spec = P(("dp", "fsdp"))
    flat = shard_map(
        lambda v: jax.lax.psum(v, ("dp", "fsdp")),
        mesh=mesh,
        in_specs=spec,
        out_specs=P(),
    )
    # check_vma=False: shard_map's static replication checker cannot
    # infer that the closing all_gather replicates over fsdp
    hier = shard_map(
        hierarchical_psum,
        mesh=mesh,
        in_specs=spec,
        out_specs=P(),
        check_vma=False,
    )
    return flat, hier


def test_hierarchical_psum_matches_flat_psum(monkeypatch):
    mesh = _hier_mesh(monkeypatch)
    flat, hier = _psum_fns(mesh)
    # 32 rows / 8 devices = 4 local rows, divisible by fsdp=4: the real
    # reduce-scatter -> cross-slice all-reduce -> all-gather path runs
    x = np.random.default_rng(0).normal(size=(32, 3)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(hier(x)), np.asarray(flat(x)), rtol=1e-6
    )
    # integer-valued floats sum exactly in any reduction order: the two
    # lowerings must agree BITWISE, proving they compute the same sum
    xi = np.arange(32 * 3, dtype=np.float32).reshape(32, 3)
    np.testing.assert_array_equal(np.asarray(hier(xi)), np.asarray(flat(xi)))


def test_hierarchical_psum_lowers_to_reduce_scatter(monkeypatch):
    # structured auditor inventory instead of HLO-text string matching:
    # the hierarchical path must lower to reduce-scatter + all-gather
    # (plus the cross-slice reduction), with the in-slice legs on ICI
    # and cross-slice traffic attributed to DCN under the slice-major
    # device assignment
    from accelerate_tpu.profiling import audit_compiled

    mesh = _hier_mesh(monkeypatch)
    _, hier = _psum_fns(mesh)
    x = jnp.zeros((32, 3), jnp.float32)
    compiled = jax.jit(hier).lower(x).compile()
    audit = audit_compiled("hier_psum", compiled, num_slices=2)
    kinds = set(audit.by_kind)
    assert {"reduce-scatter", "all-gather"} <= kinds
    # every collective's bytes estimate is positive and attributed
    for op in audit.collectives:
        if op.kind in ("reduce-scatter", "all-gather", "all-reduce"):
            assert op.bytes_moved > 0
            assert op.fabric in ("ici", "dcn")
    # the in-slice scatter/gather legs stay on ICI
    assert audit.ici_bytes > 0


def test_hierarchical_psum_fallback_when_rows_do_not_tile(monkeypatch):
    mesh = _hier_mesh(monkeypatch)
    flat, hier = _psum_fns(mesh)
    # 8 rows / 8 devices = 1 local row, not divisible by fsdp=4: the
    # divisibility guard must fall back to the flat psum, bitwise
    x = np.random.default_rng(1).normal(size=(8,)).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(hier(x)), np.asarray(flat(x)))


# ---------------------------------------------------------------------- #
# collective-overlap policy: DCN-crossing collectives ranked first
# ---------------------------------------------------------------------- #
def test_wants_overlap_for_multislice_even_under_no_shard(monkeypatch):
    plugin = ParallelismPlugin(
        dp_size=2,
        fsdp_size=4,
        sharding_strategy=ShardingStrategy.NO_SHARD,
        min_weight_size=1,
    )
    assert wants_collective_overlap(plugin, _hier_mesh(monkeypatch)) is True
    # single slice, NO_SHARD: nothing worth scheduling (original policy)
    monkeypatch.setenv(NUM_SLICES_ENV, "1")
    flat_mesh = build_mesh(plugin)
    assert wants_collective_overlap(plugin, flat_mesh) is False


def test_overlap_options_adds_dcn_ranking_on_multislice(monkeypatch):
    plugin = ParallelismPlugin(dp_size=2, fsdp_size=4, min_weight_size=1)
    hier = overlap_options(plugin, _hier_mesh(monkeypatch), backend="tpu")
    for key in DCN_OVERLAP_OPTIONS:
        assert key in hier
    monkeypatch.setenv(NUM_SLICES_ENV, "1")
    single = overlap_options(plugin, build_mesh(plugin), backend="tpu")
    assert single  # still wants overlap (FULL_SHARD)...
    for key in DCN_OVERLAP_OPTIONS:
        assert key not in single  # ...but no DCN ranking on one slice
    # non-TPU backends get nothing, as before
    assert overlap_options(plugin, _hier_mesh(monkeypatch), backend="cpu") == {}


def test_zero2_shardings_pin_grads_on_multislice_replicated_params(
    monkeypatch,
):
    """On a hierarchical mesh, even replicated-param strategies (ZeRO-0/1)
    pin the grad buffer to fsdp shards so the accumulation lowers to
    reduce-scatter in-slice and only 1/fsdp of the bytes cross DCN."""
    monkeypatch.setenv(NUM_SLICES_ENV, "2")
    acc = _fresh_accelerator(
        parallelism_plugin=ParallelismPlugin(
            dp_size=2,
            fsdp_size=4,
            sharding_strategy=ShardingStrategy.SHARD_OPT,
            min_weight_size=1,
        )
    )
    params = acc.prepare({"w": jnp.zeros((16, 4), jnp.float32)})
    shardings = acc._zero2_grad_shardings(params)
    assert shardings is not None
    assert "fsdp" in jax.tree.leaves(shardings)[0].spec

    # single slice keeps the old behavior: ZeRO-1 grads stay replicated
    monkeypatch.setenv(NUM_SLICES_ENV, "1")
    acc = _fresh_accelerator(
        parallelism_plugin=ParallelismPlugin(
            dp_size=2,
            fsdp_size=4,
            sharding_strategy=ShardingStrategy.SHARD_OPT,
            min_weight_size=1,
        )
    )
    params = acc.prepare({"w": jnp.zeros((16, 4), jnp.float32)})
    assert acc._zero2_grad_shardings(params) is None


# ---------------------------------------------------------------------- #
# zero retraces after warmup on the hierarchical layout
# ---------------------------------------------------------------------- #
def test_hierarchical_layout_zero_retraces_after_warmup(monkeypatch):
    monkeypatch.setenv(NUM_SLICES_ENV, "2")
    acc = _fresh_accelerator(
        parallelism_plugin=ParallelismPlugin(
            dp_size=2, fsdp_size=4, min_weight_size=1
        )
    )
    assert mesh_num_slices(acc.mesh) == 2

    def loss_fn(p, batch):
        pred = batch["x"] @ p["w"]
        return jnp.mean((pred - batch["y"]) ** 2)

    params = acc.prepare({"w": jnp.zeros((8, 8), jnp.float32)})
    opt = acc.prepare(optax.sgd(0.1))
    carry = acc.init_carry(params, opt)
    step = acc.unified_step(loss_fn)

    def batch(i):
        g = np.random.default_rng(i)
        x = g.normal(size=(16, 8)).astype(np.float32)
        return {"x": x, "y": (x * 2.0).astype(np.float32)}

    acc.warmup(step, carry, batch(0))
    detector = acc.telemetry.detector(step.label)
    signatures = len(detector._seen)
    for i in range(3):
        carry, metrics = step(carry, batch(i))
    assert np.isfinite(float(metrics["loss"]))
    assert detector.retraces == 0
    assert len(detector._seen) == signatures

"""The sharding-rules engine: how params, optimizer state and batches map
onto the device mesh.

This module is the TPU-native replacement for the reference's entire model-
wrapping machinery — DDP wrap (reference accelerator.py:1425-1443), FSDP
auto-wrap policies (:1444-1553, utils/dataclasses.py:1234), DeepSpeed ZeRO
stages (utils/deepspeed.py), and Megatron TP sharding (utils/megatron_lm.py).
Instead of wrapping modules, we compute a :class:`NamedSharding` for every
leaf of the param/opt-state pytree and let GSPMD lower the annotations to
reduce-scatter/all-gather/all-to-all over ICI.

Two mechanisms, compounding:

* **Logical-axis rules** — models annotate params with logical axis names
  (flax ``nn.with_partitioning`` / ``nn.get_partition_spec``); rules map
  logical names -> mesh axes (``("embed", None), ("mlp", "tp"), ...``).
  This is how TP/SP/EP are expressed (Megatron parity).
* **Heuristic FSDP** — for un-annotated leaves: shard the largest dimension
  divisible by the fsdp axis size, replicate small arrays
  (``min_weight_size`` — the analogue of FSDP's ``min_num_params``
  auto-wrap policy). Zero model changes needed, like wrapping a model in
  FSDP without touching its code.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils.constants import (
    MESH_AXIS_DATA,
    MESH_AXIS_FSDP,
    MESH_AXIS_SEQUENCE,
    MESH_AXIS_TENSOR,
)
from ..utils.dataclasses import ParallelismPlugin, ShardingStrategy
from .mesh import data_axes, mesh_num_slices

# Default logical-axis -> mesh-axis rules, in priority order. Models using
# flax logical axis names (t5x/maxtext convention) get TP/SP for free.
DEFAULT_LOGICAL_RULES: tuple[tuple[str, Optional[str]], ...] = (
    ("batch", MESH_AXIS_DATA),
    ("vocab", MESH_AXIS_TENSOR),
    # "zero": explicit ZeRO-3 weight-shard seat, stacked onto the same dim
    # as another logical axis (e.g. the embedding's vocab dim carries
    # ("vocab", "zero") -> (tp, fsdp)). Used where the heuristic fsdp
    # merge must NOT pick a free dim: sharding the embedding's feature dim
    # makes every lookup output hidden-sharded and forces an involuntary
    # full reshard to the batch-sharded activation layout (and the mirror
    # reshard on the grad scatter) at dp x tp meshes.
    ("zero", MESH_AXIS_FSDP),
    ("embed", None),
    ("heads", MESH_AXIS_TENSOR),
    ("kv", None),
    ("mlp", MESH_AXIS_TENSOR),
    ("expert", "ep"),
    ("length", MESH_AXIS_SEQUENCE),
    ("norm", None),
    ("layers", None),  # nn.scan stacked-layer dim
)


def unbox_params(variables: Any) -> Any:
    """Strip flax ``nn.Partitioned`` metadata boxes -> raw array pytree."""
    import flax.linen as nn

    return nn.meta.unbox(variables)


def get_logical_specs(variables: Any) -> Any:
    """Extract the logical-axis PartitionSpec pytree from flax params created
    with ``nn.with_partitioning`` (input to :func:`infer_param_shardings`)."""
    import flax.linen as nn

    return nn.get_partition_spec(variables)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, *, seq_dim: Optional[int] = None) -> NamedSharding:
    """Sharding for a data batch: leading dim over all data axes
    (dp x fsdp x ep), optional sequence dim over sp (context parallelism)."""
    axes = data_axes(mesh)
    spec: list[Any] = [axes]
    if seq_dim is not None:
        while len(spec) <= seq_dim:
            spec.append(None)
        if mesh.shape[MESH_AXIS_SEQUENCE] > 1:
            spec[seq_dim] = MESH_AXIS_SEQUENCE
    return NamedSharding(mesh, P(*spec))


def live_mesh() -> Optional[Mesh]:
    """The AcceleratorState's mesh when one is initialized and non-trivial,
    else None — the shared guard for trace-time sharding constraints."""
    from ..state import AcceleratorState

    if not AcceleratorState._shared_state:
        return None
    mesh = AcceleratorState().mesh
    if mesh is None or mesh.devices.size == 1:
        return None
    return mesh


def constrain_activations(x, seq_dim: Optional[int] = 1):
    """Pin a (B, S, H) activation to the canonical layout: batch over the
    data axes, sequence over sp, hidden replicated (tp lives in the
    weights; activations between blocks stay hidden-replicated, the
    Megatron layout).

    Without the pin, GSPMD propagation can alternate an activation between
    the batch-sharded layout (from the inputs) and a weight-following
    layout (e.g. the tied-embedding logits matmul pulling hidden onto
    fsdp), producing "involuntary full rematerialization" resharding on
    every layer boundary. No-op when no AcceleratorState is live or the
    mesh is trivial.
    """
    mesh = live_mesh()
    if mesh is None:
        return x
    import math

    axes = data_axes(mesh)
    if x.shape[0] % math.prod(mesh.shape[a] for a in axes):
        return x  # probe shapes (init at batch 1) can't tile the data axes
    spec: list[Any] = [axes] + [None] * (x.ndim - 1)
    if (
        seq_dim is not None
        and seq_dim < x.ndim
        and mesh.shape[MESH_AXIS_SEQUENCE] > 1
        and x.shape[seq_dim] % mesh.shape[MESH_AXIS_SEQUENCE] == 0
    ):
        spec[seq_dim] = MESH_AXIS_SEQUENCE
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def _fsdp_spec_for_leaf(
    arr: Any, fsdp_size: int, min_weight_size: int
) -> P:
    """Heuristic: shard the largest divisible dim on the fsdp axis."""
    shape = tuple(getattr(arr, "shape", ()))
    if not shape or int(np.prod(shape)) < min_weight_size:
        return P()
    # largest-first, prefer later dims on ties (output features usually last
    # and largest; sharding them turns matmul grads into reduce-scatter).
    order = sorted(range(len(shape)), key=lambda i: (shape[i], i), reverse=True)
    for dim in order:
        if shape[dim] % fsdp_size == 0 and shape[dim] >= fsdp_size:
            spec: list[Any] = [None] * len(shape)
            spec[dim] = MESH_AXIS_FSDP
            return P(*spec)
    return P()


def _merge_fsdp_into_spec(
    spec: P, arr: Any, fsdp_size: int, min_weight_size: int
) -> P:
    """Add fsdp sharding to a TP-annotated spec on a free dimension (the
    combination the reference reaches only via Megatron+DeepSpeed)."""
    shape = tuple(getattr(arr, "shape", ()))
    entries = list(spec) + [None] * (len(shape) - len(spec))
    if not shape or int(np.prod(shape)) < min_weight_size:
        return spec
    # a "zero"-annotated leaf already carries its fsdp placement — adding
    # a second fsdp dim would produce an invalid spec
    flat = [
        a
        for e in entries
        for a in (e if isinstance(e, (list, tuple)) else (e,))
    ]
    if MESH_AXIS_FSDP in flat:
        return P(*entries)
    order = sorted(range(len(shape)), key=lambda i: (shape[i], i), reverse=True)
    for dim in order:
        if entries[dim] is None and shape[dim] % fsdp_size == 0 and shape[dim] >= fsdp_size:
            entries[dim] = MESH_AXIS_FSDP
            return P(*entries)
    return spec


def infer_param_shardings(
    params: Any,
    mesh: Mesh,
    plugin: Optional[ParallelismPlugin] = None,
    logical_specs: Any = None,
    rules: Optional[Sequence[tuple[str, Optional[str]]]] = None,
) -> Any:
    """Compute a NamedSharding pytree for ``params``.

    ``logical_specs``: optional matching pytree of logical-axis
    PartitionSpecs (from ``nn.get_partition_spec``); mapped through
    ``rules``. Leaves without logical specs fall back to the FSDP heuristic.
    """
    plugin = plugin or ParallelismPlugin()
    rule_map = dict(DEFAULT_LOGICAL_RULES)
    if plugin.sharding_rules:
        rule_map.update(dict(plugin.sharding_rules))
    if rules:
        rule_map.update(dict(rules))
    fsdp_on = (
        plugin.sharding_strategy
        in (ShardingStrategy.FULL_SHARD, ShardingStrategy.HYBRID_SHARD)
        and mesh.shape[MESH_AXIS_FSDP] > 1
    )
    fsdp_size = mesh.shape[MESH_AXIS_FSDP]

    def _usable(axis: Optional[str]) -> bool:
        # the fsdp axis ("zero" seat) is a WEIGHT-shard placement: it only
        # applies under ZeRO-3-style strategies — under ZeRO-1/2 params
        # stay replicated and only opt state / grads shard over fsdp
        if axis == MESH_AXIS_FSDP and not fsdp_on:
            return False
        return bool(axis) and mesh.shape[axis] > 1

    def _map_logical(leaf_spec: P, arr: Any) -> P:
        entries = []
        for name in leaf_spec:
            if name is None:
                entries.append(None)
            elif isinstance(name, (list, tuple)):
                axes = [rule_map.get(n) for n in name]
                axes = [a for a in axes if _usable(a)]
                entries.append(tuple(axes) if axes else None)
            else:
                axis = rule_map.get(name)
                entries.append(axis if _usable(axis) else None)
        spec = P(*entries)
        if fsdp_on:
            spec = _merge_fsdp_into_spec(spec, arr, fsdp_size, plugin.min_weight_size)
        return spec

    def _infer_one(arr: Any, lspec: Optional[P]) -> NamedSharding:
        if lspec is not None:
            return NamedSharding(mesh, _map_logical(lspec, arr))
        if fsdp_on:
            return NamedSharding(
                mesh, _fsdp_spec_for_leaf(arr, fsdp_size, plugin.min_weight_size)
            )
        return NamedSharding(mesh, P())

    if logical_specs is None:
        return jax.tree.map(lambda a: _infer_one(a, None), params)
    return jax.tree.map(
        _infer_one, params, logical_specs, is_leaf=lambda x: isinstance(x, P)
    )


def infer_opt_state_shardings(
    opt_state_shapes: Any,
    mesh: Mesh,
    plugin: Optional[ParallelismPlugin] = None,
) -> Any:
    """NamedSharding pytree for an optimizer state under ZeRO-1/2
    (``ShardingStrategy.SHARD_OPT`` / ``SHARD_GRAD_OP``): moment buffers
    shard over the fsdp axis while the params stay replicated — the
    DeepSpeed stage-1/2 capability (reference utils/dataclasses.py:739)
    expressed as out_shardings on ``optax.init``.

    ``opt_state_shapes``: the (abstract) opt-state pytree, e.g. from
    ``jax.eval_shape(opt.init, params)``. Scalars/small leaves (schedule
    counts) replicate via the ``min_weight_size`` threshold.
    """
    plugin = plugin or ParallelismPlugin()
    fsdp_size = mesh.shape[MESH_AXIS_FSDP]

    def _one(leaf):
        return NamedSharding(
            mesh, _fsdp_spec_for_leaf(leaf, fsdp_size, plugin.min_weight_size)
        )

    return jax.tree.map(_one, opt_state_shapes)


def grad_buffer_shardings(
    params: Any,
    mesh: Mesh,
    plugin: Optional[ParallelismPlugin] = None,
) -> Any:
    """NamedSharding pytree for the accumulated-grad carry buffer under
    ZeRO-2 (``SHARD_GRAD_OP``): grads reduce-scatter into fsdp shards
    instead of living replicated between micro-steps."""
    return infer_opt_state_shardings(params, mesh, plugin)


def hierarchical_psum(
    x: Any,
    *,
    cross_slice_axis: str = MESH_AXIS_DATA,
    in_slice_axis: str = MESH_AXIS_FSDP,
    axis_sizes: Optional[dict[str, int]] = None,
):
    """Gradient all-reduce restructured for a hierarchical (multi-slice)
    mesh, usable inside ``shard_map``:

        reduce-scatter in-slice (ICI) -> all-reduce cross-slice (DCN)
        -> all-gather in-slice (ICI)

    Mathematically ``psum(x, (cross_slice_axis, in_slice_axis))``, but the
    slow DCN hop moves ``1/in_slice_size`` of the bytes: each in-slice
    group first reduce-scatters over fast ICI, only the scattered shard
    crosses DCN, and the result is re-gathered inside each slice.

    Falls back to the flat psum when the leading dim does not tile the
    in-slice axis (scalars, odd remainders) — correctness first, the
    byte savings only apply to the tileable majority.
    """
    if axis_sizes is not None:
        in_size = axis_sizes.get(in_slice_axis, 1)
    else:
        in_size = jax.lax.psum(1, in_slice_axis)
    shape = tuple(getattr(x, "shape", ()))
    if not shape or (isinstance(in_size, int) and shape[0] % in_size != 0):
        return jax.lax.psum(x, (cross_slice_axis, in_slice_axis))
    shard = jax.lax.psum_scatter(
        x, in_slice_axis, scatter_dimension=0, tiled=True
    )
    shard = jax.lax.psum(shard, cross_slice_axis)
    return jax.lax.all_gather(shard, in_slice_axis, axis=0, tiled=True)


def wants_collective_overlap(
    plugin: Optional[ParallelismPlugin], mesh: Optional[Mesh]
) -> bool:
    """Does this sharding layout issue per-step collectives worth hiding
    under compute? True for the ZeRO/FSDP strategies (``SHARD_OPT`` /
    ``SHARD_GRAD_OP`` / ``FULL_SHARD`` / ``HYBRID_SHARD``) on a mesh
    whose data axes actually span devices — exactly the paths where the
    step emits all-gather/reduce-scatter chains the latency-hiding
    scheduler can reorder (``compilation.overlap`` consumes this to
    decide whether to emit the XLA overlap options).

    Also true — regardless of strategy, including pure-DP ``NO_SHARD`` —
    when the mesh spans multiple slices and dp > 1: the gradient
    reduction then crosses DCN every step, the single most important
    collective to schedule first and hide (``compilation.overlap`` adds
    the DCN-ranking options on top for this case)."""
    if plugin is None or mesh is None:
        return False
    if mesh_num_slices(mesh) > 1 and int(mesh.shape[MESH_AXIS_DATA]) > 1:
        return True
    if plugin.sharding_strategy == ShardingStrategy.NO_SHARD:
        return False
    return (
        int(mesh.shape[MESH_AXIS_DATA]) * int(mesh.shape[MESH_AXIS_FSDP])
        > 1
    )


def shard_params(
    params: Any,
    shardings: Any,
) -> Any:
    """Place a param pytree according to a sharding pytree. Uses device_put,
    which moves each leaf once (host->HBM or HBM->HBM reshard)."""
    return jax.tree.map(
        lambda p, s: jax.device_put(p, s), params, shardings
    )


def shardings_of(tree: Any) -> Any:
    """The sharding pytree of an array pytree (for jit in_shardings)."""
    return jax.tree.map(
        lambda x: x.sharding if isinstance(x, jax.Array) else None, tree
    )


def constrain(tree: Any, mesh: Mesh, spec: P) -> Any:
    """with_sharding_constraint over a pytree (inside-jit annotation)."""
    return jax.tree.map(
        lambda x: jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec)), tree
    )


# --------------------------------------------------------------------- #
# expected-collective contracts (the sharding X-ray's ground truth)
# --------------------------------------------------------------------- #
def mesh_axes_of_params(params: Any) -> set:
    """The mesh axis names any leaf of ``params`` is actually sharded
    over (empty set = fully replicated / single device / uncommitted)."""
    axes: set = set()
    for leaf in jax.tree.leaves(params):
        spec = getattr(getattr(leaf, "sharding", None), "spec", None)
        if spec is None:
            continue
        for entry in spec:
            if entry is None:
                continue
            if isinstance(entry, (tuple, list)):
                axes.update(str(a) for a in entry)
            else:
                axes.add(str(entry))
    return axes


def collective_contract_for_train(
    plugin: Optional[ParallelismPlugin] = None,
    mesh: Optional[Mesh] = None,
) -> Any:
    """Derive the train step's expected-collective contract from its
    sharding layout — what the HLO auditor treats as *voluntary*.

    The layout explains collectives; anything else in the compiled
    program is an involuntary reshard. Per layout:

    * pure DP (NO_SHARD, dp > 1): grad sync is ``all-reduce`` only;
    * ZeRO-1 (SHARD_OPT): ``all-reduce`` grads + ``all-gather`` the
      sharded optimizer update back into replicated params;
    * ZeRO-2/3 (SHARD_GRAD_OP / FULL_SHARD / HYBRID_SHARD):
      ``reduce-scatter`` + ``all-gather`` (+ ``all-reduce`` for scalar
      metrics / non-tileable leaves);
    * multi-slice meshes: the hierarchical grad path
      (scatter-in-slice -> reduce-across -> gather-in-slice) regardless
      of strategy — the ZeRO-2 grad-buffer pinning kicks in at > 1
      slice even under replicated-param strategies;
    * tp / sp / ep axes add their Megatron/ring/MoE traffic.

    Returns a :class:`~accelerate_tpu.profiling.hlo_audit.CollectiveContract`.
    """
    from ..profiling.hlo_audit import RESHARD_COPY, CollectiveContract

    shape = dict(mesh.shape) if mesh is not None else {}

    def _deg(axis: str, plugin_val: int) -> int:
        if shape:
            return int(shape.get(axis, 1))
        if plugin_val == -1:  # "absorb the rest": > 1 unless proven not
            try:
                return max(int(jax.device_count()), 1)
            except Exception:  # noqa: BLE001
                return 2
        return int(plugin_val)

    dp = _deg(MESH_AXIS_DATA, plugin.dp_size if plugin else -1)
    fsdp = _deg(MESH_AXIS_FSDP, plugin.fsdp_size if plugin else 1)
    tp = _deg(MESH_AXIS_TENSOR, plugin.tp_size if plugin else 1)
    sp = _deg(MESH_AXIS_SEQUENCE, plugin.sp_size if plugin else 1)
    ep = _deg("ep", plugin.ep_size if plugin else 1)
    strategy = plugin.sharding_strategy if plugin is not None else None
    num_slices = mesh_num_slices(mesh) if mesh is not None else 1

    allowed: set = set()
    notes: list = []
    if dp > 1 or fsdp > 1:
        allowed.add("all-reduce")  # grad sync + scalar metric psums
    if fsdp > 1 and strategy in (
        ShardingStrategy.SHARD_GRAD_OP,
        ShardingStrategy.FULL_SHARD,
        ShardingStrategy.HYBRID_SHARD,
    ):
        allowed |= {"reduce-scatter", "all-gather"}
        notes.append("zero: grad reduce-scatter + param/opt all-gather")
        # XLA:TPU pads the shards of a reduce-scatter to its own tile
        # multiple (vocab 32000 / 4 = 8000 rows -> 8064; even 1024-row
        # shards -> 1056) and re-tiles the result with a neighbour halo
        # exchange of the few padding rows — seen in every ZeRO-3 step
        # compiled for a v5e 2x2
        allowed.add("collective-permute")
        notes.append("padded reduce-scatter halo exchange (XLA:TPU)")
    if fsdp > 1 and strategy is ShardingStrategy.SHARD_OPT:
        allowed.add("all-gather")
        notes.append("zero-1: sharded opt update gathers into params")
    if num_slices > 1 and (dp > 1 or fsdp > 1):
        allowed |= {"reduce-scatter", "all-reduce", "all-gather"}
        notes.append("hierarchical cross-slice grad sync")
    if tp > 1:
        allowed |= {"all-reduce", "all-gather", "reduce-scatter"}
        notes.append("tensor-parallel partial sums")
    if sp > 1:
        allowed |= {"all-to-all", "collective-permute",
                    "all-reduce", "all-gather"}
        notes.append("sequence-parallel ring exchange")
    if ep > 1:
        allowed |= {"all-to-all", "all-reduce"}
        notes.append("expert-parallel token routing")
    if allowed:
        # shard_map bodies (hierarchical psum, pipeline loop, overlap)
        # legitimately cross the manual/auto boundary
        allowed.add(RESHARD_COPY)
    name = strategy.name.lower() if strategy is not None else "default"
    origin = (
        f"train:{name}(dp={dp},fsdp={fsdp},tp={tp},sp={sp},ep={ep},"
        f"slices={num_slices})"
    )
    return CollectiveContract(
        allowed=frozenset(allowed), origin=origin, notes=tuple(notes),
    )


def collective_contract_for_params(
    params: Any, *, family: str = "serve"
) -> Any:
    """Derive a forward-only (serving) program's expected-collective
    contract from how its params are *actually* sharded.

    Under pure data/fsdp-replicated serving (no leaf sharded: the
    common single-replica engine) the contract is EMPTY — the
    decode/verify/COW/prefill-bucket programs expect zero cross-device
    collectives, and any collective the compiler emitted is an
    involuntary reshard. Weight-sharded layouts explain their own
    traffic: ``fsdp`` shards gather (or partial-sum) on use, ``tp``
    partials reduce on use. Nothing ever explains ``all-to-all`` /
    ``collective-permute`` in a dense serving program — those stay
    violations under every dense layout.
    """
    from ..profiling.hlo_audit import CollectiveContract

    axes = mesh_axes_of_params(params)
    allowed: set = set()
    notes: list = []
    if MESH_AXIS_FSDP in axes or MESH_AXIS_DATA in axes:
        allowed |= {"all-gather", "all-reduce", "reduce-scatter"}
        notes.append("weight shards gather / partial-sum on use")
    if MESH_AXIS_TENSOR in axes:
        allowed |= {"all-reduce", "all-gather", "reduce-scatter"}
        notes.append("tensor-parallel partial sums reduce on use")
    if MESH_AXIS_SEQUENCE in axes:
        allowed |= {"all-to-all", "collective-permute"}
    if "ep" in axes:
        allowed |= {"all-to-all", "all-reduce"}
    origin = f"{family}:{'+'.join(sorted(axes)) if axes else 'replicated'}"
    return CollectiveContract(
        allowed=frozenset(allowed), origin=origin, notes=tuple(notes),
    )

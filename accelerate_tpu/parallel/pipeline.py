"""Pipeline parallelism: a GPipe microbatch schedule over the ``pp`` mesh
axis, built from ``shard_map`` + ``ppermute``.

Parity: the reference reaches pipeline-parallel *training* only through
Megatron-LM (``MegatronLMPlugin.pp_degree`` utils/dataclasses.py:1318, the
pipelined ``train_step`` utils/megatron_lm.py:1037-1058) and inference
through PiPPy (inference.py:126). TPU-native redesign (SURVEY §7.6): the
layer stack is a *stacked array* (the ``nn.scan`` layout this repo's models
already use), its layer dimension shards over the ``pp`` mesh axis, and one
``shard_map`` program runs the classic GPipe schedule — each device group
runs its layer block on microbatch ``t`` while ``ppermute`` rotates
activations to the next stage. Backward falls out of jax.grad through the
scan (reverse pipeline schedule), so the same ``unified_step`` trains a
pipelined model with zero engine code.

Composition rules (v3): pp composes with dp/fsdp batch sharding, with tp,
AND with sp — the stage shard_map is PARTIAL-MANUAL
(``axis_names={"pp"}``): only the pp axis is manual; every other mesh
axis stays automatic, so GSPMD partitions the stage body over
tp/dp/fsdp/sp and inserts their collectives inside each pipeline stage
(the Megatron pp x tp, pp x sp and pp x ep layouts, reference
utils/dataclasses.py:1323,1338 and utils/megatron_lm.py:1641-, reached
with zero engine code). Ring attention under pp nests its own sp
shard_map on the context mesh (ops/ring_attention.py); moe_ragged_ep
nests its ep shard_map the same way (ops/moe.py) — the r5 lift of the
last composition rejection.

Two schedules:

* :func:`pipeline_apply` — GPipe forward; backward falls out of jax.grad
  (reverse schedule). Simple, composable with any downstream computation,
  but autodiff saves residuals for ALL M microbatches per stage and the
  output carry holds the full (M, ...) buffer.
* :func:`pipeline_train_step` — true 1F1B: forward and backward microbatch
  work interleave in ONE scan, per-stage in-flight inputs are bounded by a
  ring buffer of depth 2S-1 (independent of M), backward recomputes the
  stage from its saved input (activation-checkpoint style), and no output
  buffer exists at all — the loss is computed per-microbatch on the last
  stage. Peak activation HBM ~ (2S-1)/M of the GPipe path for M >> S.
  Requires the loss to decompose per-microbatch (any mean/sum loss does).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils.constants import MESH_AXIS_PIPELINE
from ..utils.dataclasses import ParallelismPlugin


def _stage_shard_map(mesh, in_specs, out_specs):
    """shard_map over ONLY the pp axis (partial-manual): tp/dp/fsdp stay
    automatic so GSPMD partitions the stage body and inserts their
    collectives inside each stage — this is what makes pp x tp compose."""
    return functools.partial(
        shard_map, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False, axis_names={MESH_AXIS_PIPELINE},
    )


def validate_pipeline_plugin(
    plugin: ParallelismPlugin, resolved_shape: Optional[dict] = None
) -> None:
    """A pipeline needs at least one microbatch per stage. (tp, sp and ep
    all compose with pp: they stay auto axes inside the partial-manual
    stage body; ring attention and moe_ragged_ep nest their own sp/ep
    shard_maps on the context mesh — ops/ring_attention.py, ops/moe.py.)

    ``resolved_shape`` (from ``resolve_mesh_shape``) covers the ``-1`` auto
    axes — validation must run on the *resolved* degrees, else ``pp_size=-1``
    slips past every check.
    """
    pp = resolved_shape["pp"] if resolved_shape is not None else plugin.pp_size
    if pp in (1, -1):
        return
    if plugin.num_micro_batches < pp:
        raise ValueError(
            f"num_micro_batches ({plugin.num_micro_batches}) must be >= "
            f"pp_size ({pp}) or the pipeline bubbles dominate"
        )


def stacked_layer_shardings(
    stacked_params: Any, mesh: Mesh, layer_dim: int = 0
) -> Any:
    """NamedSharding pytree sharding each leaf's ``layer_dim`` over pp.

    For params produced by ``nn.scan`` (leading layer dimension) this is the
    whole pipeline placement: stage ``i`` holds layers
    ``[i*L/S, (i+1)*L/S)`` in its HBM and nothing else.
    """

    def _one(leaf):
        shape = getattr(leaf, "shape", ())
        if len(shape) <= layer_dim or shape[layer_dim] % mesh.shape[MESH_AXIS_PIPELINE]:
            return NamedSharding(mesh, P())
        spec = [None] * len(shape)
        spec[layer_dim] = MESH_AXIS_PIPELINE
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(_one, stacked_params)


def pipeline_apply(
    block_fn: Callable[[Any, jax.Array], jax.Array],
    stacked_params: Any,
    x: jax.Array,
    *,
    mesh: Mesh,
    num_micro_batches: int,
    batch_dim: int = 0,
) -> jax.Array:
    """Run a stacked layer sequence as a GPipe pipeline over the pp axis.

    ``block_fn(local_layers, x_micro) -> y_micro`` applies this stage's
    layer block (leaves have leading dim ``num_layers // pp``) to one
    microbatch; it must preserve ``x_micro``'s shape (a residual-block
    stack). ``stacked_params`` leaves carry a leading ``num_layers`` dim.
    ``x``: activations, microbatched along ``batch_dim``.

    Equivalent to sequentially applying all layers; wall-clock is
    ``(M + S - 1)/M`` of ideal with M microbatches, S stages.
    """
    S = mesh.shape[MESH_AXIS_PIPELINE]
    M = num_micro_batches
    if S == 1:
        return block_fn(stacked_params, x)
    B = x.shape[batch_dim]
    xm = _microbatch(x, M, batch_dim)  # (B, ...) -> (M, B/M, ...)

    # partial-manual: specs constrain only the pp axis; dp/fsdp/tp
    # sharding of x and params is propagated by GSPMD (auto axes)
    x_spec = P()
    param_specs = jax.tree.map(
        lambda l: P(MESH_AXIS_PIPELINE), stacked_params
    )

    @_stage_shard_map(mesh, (param_specs, x_spec), x_spec)
    def _pipelined(local_params, local_xm):
        stage = jax.lax.axis_index(MESH_AXIS_PIPELINE)
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            state, outputs = carry
            # stage 0 consumes microbatch t (clamped once the feed is done)
            feed = jax.lax.dynamic_index_in_dim(
                local_xm, jnp.clip(t, 0, M - 1), 0, keepdims=False
            )
            inp = jnp.where(stage == 0, feed, state)
            y = block_fn(local_params, inp)
            # last stage owns microbatch t-(S-1) once the pipe is full
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            prev = jax.lax.dynamic_index_in_dim(outputs, out_idx, 0, keepdims=False)
            write = jnp.logical_and(stage == S - 1, t >= S - 1)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(write, y, prev), out_idx, 0
            )
            # rotate activations one stage forward
            state = jax.lax.ppermute(y, MESH_AXIS_PIPELINE, perm)
            return (state, outputs), None

        init = (
            jnp.zeros_like(local_xm[0]),
            jnp.zeros_like(local_xm),
        )
        (state, outputs), _ = jax.lax.scan(
            tick, init, jnp.arange(M + S - 1)
        )
        # only the last stage holds real outputs; sum-broadcast over pp
        outputs = jnp.where(stage == S - 1, outputs, 0)
        return jax.lax.psum(outputs, MESH_AXIS_PIPELINE)

    ym = _pipelined(stacked_params, xm)
    y = ym.reshape((B,) + ym.shape[2:])
    return jnp.moveaxis(y, 0, batch_dim) if batch_dim != 0 else y


def _microbatch(tree: Any, M: int, batch_dim: int = 0) -> Any:
    """(B, ...) leaves -> (M, B/M, ...), microbatch-major."""

    def _one(x):
        B = x.shape[batch_dim]
        if B % M:
            raise ValueError(f"batch {B} not divisible into {M} microbatches")
        xm = jnp.moveaxis(x, batch_dim, 0)
        return xm.reshape((M, B // M) + xm.shape[1:])

    return jax.tree.map(_one, tree)


def pipeline_train_step(
    block_fn: Callable[[Any, jax.Array], jax.Array],
    loss_fn: Callable[[jax.Array, Any], jax.Array],
    stacked_params: Any,
    x: jax.Array,
    targets: Any,
    *,
    mesh: Mesh,
    num_micro_batches: int,
    batch_dim: int = 0,
    _force_replicated_feed: bool = False,
) -> tuple[jax.Array, Any]:
    """One 1F1B pipeline training step: ``(loss, grads)`` in a single pass.

    The schedule (synchronous 1F1B, Narayanan et al. PipeDream-Flush /
    Megatron's default, reference utils/megatron_lm.py:1037-1058): each
    scan tick carries a forward sub-phase and a backward sub-phase —
    forward of microbatch ``j`` runs on stage ``i`` at tick ``i + j``; its
    backward runs at tick ``2S - 2 - i + j`` (the last stage turns a
    microbatch around in the same tick, feeding the loss cotangent
    straight back). Activations ``ppermute`` forward, cotangents
    ``ppermute`` backward, every tick.

    Memory: each stage's RECOMPUTE state is an input ring buffer of depth
    ``2S - 1`` — independent of ``M`` — and the block re-runs under
    ``jax.vjp`` in the backward sub-phase (activation recompute). No
    (M, ...) output buffer exists: ``loss_fn(y_mb, target_mb)`` is
    evaluated per microbatch on the last stage and only the scalar sum
    crosses stages (one psum), vs the GPipe path's full output
    psum-broadcast. The raw ``x``/``targets`` (M, ...) buffers shard over
    pp along the microbatch dim whenever ``M % S == 0`` (each stage holds
    M/S microbatches; the consumed one arrives by a masked psum-gather
    from its owner each tick) — Megatron's feed discipline of giving data
    only to the boundary stages, reference utils/megatron_lm.py:1037-1058.
    Non-divisible M falls back to replicated buffers.

    ``loss_fn`` must decompose over microbatches: total loss is
    ``mean_j loss_fn(y_j, t_j)`` (any per-sample mean/sum loss qualifies).
    ``grads`` matches ``stacked_params``' structure (layer dim sharded
    over pp). tp/dp/fsdp compose: the stage body runs under auto axes.
    """
    S = mesh.shape[MESH_AXIS_PIPELINE]
    M = num_micro_batches
    if S == 1:
        def total(p):
            xm = _microbatch(x, M, batch_dim)
            tm = _microbatch(targets, M, batch_dim)
            losses = jax.vmap(
                lambda xx, tt: loss_fn(block_fn(p, xx), tt)
            )(xm, tm)
            return jnp.mean(losses)

        return jax.value_and_grad(total)(stacked_params)

    xm = _microbatch(x, M, batch_dim)
    tm = _microbatch(targets, M, batch_dim)
    param_specs = jax.tree.map(lambda l: P(MESH_AXIS_PIPELINE), stacked_params)
    # Feed discipline (Megatron feeds data only to stage 0 / targets only
    # to the last stage, reference utils/megatron_lm.py:1037-1058): when M
    # divides by S the (M, ...) input/target buffers SHARD over pp along
    # the microbatch dim — each stage holds M/S microbatches and the one
    # consumed each tick is delivered by a psum-gather from its owner
    # (the tick's feed index is the same static value on every stage, so
    # the gather is one masked psum of a single microbatch). Per-stage
    # input memory drops from O(M) to O(M/S). With M % S != 0 the buffers
    # stay replicated (correct, just the old footprint).
    feed_sharded = M % S == 0 and not _force_replicated_feed
    Mloc = M // S if feed_sharded else M
    data_spec = P(MESH_AXIS_PIPELINE) if feed_sharded else P()
    t_specs = jax.tree.map(lambda _: data_spec, tm)
    R = 2 * S - 1  # ring depth: max input lifetime is 2(S-1) ticks (stage 0)
    T = M + 2 * S - 2

    @_stage_shard_map(
        mesh, (param_specs, data_spec, t_specs), (P(), param_specs)
    )
    def _run(local_params, local_xm, local_tm):
        stage = jax.lax.axis_index(MESH_AXIS_PIPELINE)
        is_last = stage == S - 1
        fwd_perm = [(i, i + 1) for i in range(S - 1)]  # i -> i+1, 0 gets zeros
        bwd_perm = [(i + 1, i) for i in range(S - 1)]  # i -> i-1, S-1 gets zeros

        def fetch(local_buf, idx):
            """Microbatch ``idx`` (a global index, identical on every
            stage) out of a pp-sharded (Mloc, ...) buffer: the owning
            stage contributes its slice, everyone else zeros, one psum
            delivers it — the distributed-gather feed."""
            if not feed_sharded:
                return jax.lax.dynamic_index_in_dim(
                    local_buf, idx, 0, keepdims=False
                )
            owner = idx // Mloc
            piece = jax.lax.dynamic_index_in_dim(
                local_buf, idx % Mloc, 0, keepdims=False
            )
            piece = jnp.where(stage == owner, piece, jnp.zeros_like(piece))
            return jax.lax.psum(piece, MESH_AXIS_PIPELINE)

        def tick(carry, t):
            fwd_msg, bwd_msg, ring, dparams, loss_acc = carry
            # ---- forward sub-phase: microbatch jf = t - stage ---------- #
            jf = t - stage
            active_f = jnp.logical_and(jf >= 0, jf < M)
            jf_c = jnp.clip(jf, 0, M - 1)
            # stage 0's feed index == the LAST stage's target index shifted
            # by S-1 ticks; both are stage-independent statics per tick
            feed = fetch(local_xm, jnp.clip(t, 0, M - 1))
            x_in = jnp.where(stage == 0, feed, fwd_msg)
            y = block_fn(local_params, x_in)
            slot_f = jf_c % R
            prev = jax.lax.dynamic_index_in_dim(ring, slot_f, 0, keepdims=False)
            ring = jax.lax.dynamic_update_index_in_dim(
                ring, jnp.where(active_f, x_in, prev), slot_f, 0
            )
            # targets are consumed ONLY by the last stage (loss_acc / the
            # turned-around cotangent are masked elsewhere), so fetch at
            # the last stage's index t - (S-1)
            tgt_idx = jnp.clip(t - (S - 1), 0, M - 1)
            tgt = jax.tree.map(
                lambda a: fetch(a, tgt_idx), local_tm
            )
            # per-microbatch loss + cotangent — the last stage turns the
            # microbatch around within this same tick
            l_j, dy_j = jax.value_and_grad(lambda yy: loss_fn(yy, tgt))(y)
            loss_acc = loss_acc + jnp.where(
                jnp.logical_and(active_f, is_last), l_j, 0.0
            )
            # ---- backward sub-phase: microbatch jb = t - (2S-2-stage) -- #
            jb = t - (2 * S - 2 - stage)
            active_b = jnp.logical_and(jb >= 0, jb < M)
            jb_c = jnp.clip(jb, 0, M - 1)
            x_saved = jax.lax.dynamic_index_in_dim(ring, jb_c % R, 0, keepdims=False)
            # on the last stage jb == jf at every active bwd tick, so dy_j
            # computed above IS the cotangent for jb
            ct = jnp.where(is_last, dy_j, bwd_msg)
            _, vjp_fn = jax.vjp(block_fn, local_params, x_saved)
            dp, dx = vjp_fn(ct.astype(y.dtype))
            dparams = jax.tree.map(
                lambda acc, g: acc + jnp.where(active_b, g, 0.0), dparams, dp
            )
            # ---- rotate messages --------------------------------------- #
            fwd_msg = jax.lax.ppermute(y, MESH_AXIS_PIPELINE, fwd_perm)
            bwd_msg = jax.lax.ppermute(dx, MESH_AXIS_PIPELINE, bwd_perm)
            return (fwd_msg, bwd_msg, ring, dparams, loss_acc), None

        mb = local_xm[0]
        init = (
            jnp.zeros_like(mb),
            jnp.zeros_like(mb),
            jnp.zeros((R,) + mb.shape, mb.dtype),
            jax.tree.map(jnp.zeros_like, local_params),
            jnp.zeros((), jnp.float32),
        )
        (f_msg, b_msg, ring, dparams, loss_acc), _ = jax.lax.scan(
            tick, init, jnp.arange(T)
        )
        loss = jax.lax.psum(loss_acc, MESH_AXIS_PIPELINE) / M
        dparams = jax.tree.map(lambda g: g / M, dparams)
        return loss, dparams

    return _run(stacked_params, xm, tm)

"""Ring attention: sequence/context-parallel attention over the ``sp`` mesh
axis.

The long-context capability the reference does NOT have (SURVEY.md §5.7:
only Megatron-style activation SP exists there; no ring/Ulysses/context-
parallel code) — on TPU this is the idiomatic answer: each device holds a
contiguous sequence shard of q/k/v; k/v chunks rotate around the ``sp`` ring
via ``lax.ppermute`` (XLA lowers it to ICI collective-permute, overlapping
the transfer with the current chunk's compute), so attention over a sequence
of length S costs O(S/n) memory per device and never materializes a global
(S, S) score matrix.

Math: for each (local-q, rotated-kv) chunk pair we compute unnormalized
blockwise attention plus its logsumexp; chunk results combine as
``out = sum_i out_i * exp(lse_i - lse)`` with ``lse = logsumexp_i lse_i`` —
the same stable combination flash attention uses across kv blocks, here
across ring steps. Causality is decided per chunk pair: kv chunks strictly
ahead of the q chunk are skipped (lse = -inf), the diagonal pair is masked
triangularly, chunks behind attend fully.

Differentiable end-to-end: the ring rotation is a ``lax.scan`` of
``ppermute`` (whose transpose is the reverse permute), so ``jax.grad``
produces the reverse ring automatically — no hand-written backward needed.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..utils.constants import (
    MESH_AXIS_DATA,
    MESH_AXIS_EXPERT,
    MESH_AXIS_FSDP,
    MESH_AXIS_SEQUENCE,
    MESH_AXIS_TENSOR,
)

NEG_INF = -1e30


def _chunk_attend(q, k, v, scale, mode, q_index=None, kv_index=None):
    """Blockwise attention for one (q-chunk, kv-chunk) pair.

    Returns (out_unnormalized, lse) with shapes ((B,Sq,H,D), (B,H,Sq)).
    ``mode``: 0 = full attend, 1 = causal-diagonal (triangular mask),
    2 = skip (zero contribution). Passed as a traced int; all three branches
    are computed via masking (cheap: the mask is (Sq, Sk)) so the step stays
    a single fused XLA program inside lax.scan.
    """
    n_rep = q.shape[2] // k.shape[2]
    if n_rep > 1:
        b, s, h, d = k.shape
        k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
            b, s, h * n_rep, d
        )
        v = jnp.broadcast_to(v[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
            b, s, h * n_rep, d
        )
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    sq, sk = logits.shape[-2], logits.shape[-1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
    diag_mask = cols <= rows + (sk - sq)
    # mode 0 -> all True; mode 1 -> triangular; mode 2 -> all False
    mask = jnp.where(
        mode == 0, True, jnp.where(mode == 1, diag_mask, False)
    )
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    m = jnp.max(logits, axis=-1)  # (B,H,Sq)
    m_safe = jnp.where(m <= NEG_INF / 2, 0.0, m)
    p = jnp.exp(logits - m_safe[..., None])
    p = jnp.where(mask[None, None], p, 0.0)
    l = jnp.sum(p, axis=-1)  # (B,H,Sq)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    # normalize within the chunk: out is now softmax(logits_chunk) @ v
    l_safe = jnp.maximum(l, 1e-37)
    out = out / jnp.swapaxes(l_safe, 1, 2)[..., None]  # (B,Sq,H,1)
    lse = jnp.where(l > 0.0, m_safe + jnp.log(l_safe), NEG_INF)
    return out, lse


def _ring_attention_local(
    q, k, v, *, axis_name: str, axis_size: int, scale: float, causal: bool
):
    """Per-device body (inside shard_map): local q stays put, k/v rotate."""
    # the ring length must be a static python int (it unrolls the scan
    # permutation below); the caller reads it off the mesh
    n = axis_size
    my = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]  # chunks move to the right,
    # i.e. each device receives its left neighbour's chunk: after s steps a
    # device holds kv chunk (my - s) mod n

    def step(carry, s):
        kc, vc = carry
        kv_index = (my - s) % n
        if causal:
            mode = jnp.where(
                kv_index < my, 0, jnp.where(kv_index == my, 1, 2)
            )
        else:
            mode = jnp.zeros((), jnp.int32)
        out_s, lse_s = _chunk_attend(q, kc, vc, scale, mode)
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return (kc, vc), (out_s, lse_s)

    (_, _), (outs, lses) = jax.lax.scan(step, (k, v), jnp.arange(n))
    # outs: (n, B, Sq, H, D), each softmax-normalized within its chunk;
    # lses: (n, B, H, Sq). Exact combination across chunks:
    #   out = sum_s out_s * exp(lse_s - logsumexp_s(lse_s))
    lse = jax.scipy.special.logsumexp(lses, axis=0)  # (B,H,Sq)
    weights = jnp.exp(lses - lse[None])  # (n,B,H,Sq)
    out = jnp.einsum("nbqhd,nbhq->bqhd", outs, weights)
    return out.astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    scale: Optional[float] = None,
    causal: bool = True,
    mesh: Optional[Mesh] = None,
    axis_name: str = MESH_AXIS_SEQUENCE,
) -> jax.Array:
    """Sequence-parallel attention, global shapes (B, S, H, D).

    Call inside jit on arrays whose sequence dim is sharded over
    ``axis_name``; the batch dim may be sharded over the data axes and heads
    over ``tp``. Requires S divisible by the sp degree.
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if mesh is None:
        from ..state import AcceleratorState

        mesh = AcceleratorState().mesh
    if mesh.shape[axis_name] == 1:
        from .attention import xla_attention

        return xla_attention(q, k, v, scale=scale, causal=causal)

    batch_axes = tuple(
        a for a in (MESH_AXIS_DATA, MESH_AXIS_FSDP, MESH_AXIS_EXPERT)
        if mesh.shape[a] > 1
    ) or None
    heads = MESH_AXIS_TENSOR if mesh.shape[MESH_AXIS_TENSOR] > 1 else None

    # shapes are static under tracing: when they cannot tile the mesh
    # (model.init probes with (1, tiny_seq); tiny eval batches), run the
    # dense path instead of failing — checking q AND k/v (GQA kv heads can
    # be the indivisible ones)
    import math

    batch_div = math.prod(mesh.shape[a] for a in (batch_axes or ()))
    heads_div = mesh.shape[heads] if heads else 1
    sp_div = mesh.shape[axis_name]
    indivisible = any(
        x.shape[0] % max(batch_div, 1)
        or x.shape[1] % sp_div
        or x.shape[2] % heads_div
        for x in (q, k, v)
    )
    if indivisible:
        from ..logging import get_logger
        from .attention import xla_attention

        if q.shape[1] >= 2048:
            # at long context the dense fallback materializes the O(S^2)
            # score matrix — the cliff ring attention exists to avoid;
            # make it visible instead of an opaque OOM later
            get_logger(__name__).warning(
                f"ring_attention: shapes q{q.shape}/kv{k.shape} do not "
                f"tile mesh axes (batch%{batch_div}, seq%{sp_div}, "
                f"heads%{heads_div}) — falling back to DENSE attention; "
                "fix batch/seq/head divisibility to keep the ring"
            )
        return xla_attention(q, k, v, scale=scale, causal=causal)

    spec = P(batch_axes, axis_name, heads, None)

    from jax import shard_map

    # sp under pp: when this runs INSIDE the pipeline's partial-manual
    # stage body (parallel/pipeline.py — pp is already Manual there), the
    # inner shard_map must be built on the tracing context's abstract
    # mesh; the concrete mesh no longer matches and jax rejects it. The
    # nesting is sound: sp is an auto axis of the stage body, so shapes
    # here are global over sp and this shard_map manualizes exactly sp.
    # check_vma must be ON in that nested position — with it off, the
    # transpose of this shard_map under the stage's jax.vjp loses the
    # replication accounting and produces silently wrong cotangents
    # (verified by the pp x sp equivalence test; loss matches, grads
    # diverge ~1e3 without it).
    from ..utils.operations import nested_manual_mesh

    ctx = nested_manual_mesh()
    sm_mesh = ctx if ctx is not None else mesh
    body = functools.partial(
        _ring_attention_local, axis_name=axis_name,
        axis_size=sm_mesh.shape[axis_name], scale=scale, causal=causal,
    )

    return shard_map(
        body, mesh=sm_mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=ctx is not None,
    )(q, k, v)

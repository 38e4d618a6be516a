"""Capacity-based sparse MoE dispatch (the production expert path).

The dense one-hot dispatch in ``models/transformer.py`` runs every expert
on every token — O(E) FLOPs, the r1 VERDICT's blocker for the Mixtral
target. This module implements the TPU-idiomatic sparse alternative, the
GShard/Switch *capacity* schedule, with fully static shapes (XLA cannot
tile dynamic shapes onto the MXU):

1. each token's k-th routing choice claims a slot in its expert's buffer
   (position = running count of earlier claims on that expert);
2. tokens claiming past the per-expert ``capacity`` are dropped (weighted
   combine makes a dropped choice contribute zero — with
   ``capacity_factor >= E/K`` nothing can drop and the result equals the
   dense path exactly, which the tests exploit as an oracle);
3. experts run batched on their (E, C, h) buffers — FLOPs scale with
   ``T*K*capacity_factor``, independent of E;
4. outputs scatter back to token order with the routing weights.

With ``ep_size > 1`` the (E, C, h) buffer's expert dim shards over the
``ep`` mesh axis: XLA lowers the gather/scatter into an all-to-all between
data and expert shards — the Switch/GShard comm pattern — with zero
collective code here.

Reference capability anchor: the reference reaches MoE only through
vendor engines (DeepSpeed-MoE / Megatron ``num_experts``
utils/megatron_lm.py:1641-); this is the native equivalent.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp


def expert_capacity(
    num_tokens: int,
    num_experts: int,
    num_selected: int,
    capacity_factor: float,
) -> int:
    """Per-expert buffer length C: perfectly balanced load times
    ``capacity_factor`` headroom, MXU-aligned (multiple of 8) and >= 1."""
    ideal = num_tokens * num_selected / num_experts
    cap = int(math.ceil(ideal * capacity_factor))
    return max(8 * int(math.ceil(cap / 8)), 8)


def no_drop_capacity_factor(num_experts: int, num_selected: int) -> float:
    """The factor at which dropping is impossible (every token could route
    to the same expert): C >= T*K/E * f  with f = E/K  gives C >= T."""
    return num_experts / num_selected


# Why twice the even share: on sound seeds a layer's held share reads up to
# 1.12 x the even one (E / R of the choices) and the drift of an unbalanced
# router lowers it from there, so a first window of 2 x holds every held row
# of every step measured (PERF.md, PR 29) — and where it does not, the rest
# window runs and the result is the same.
_WINDOW_OVER_EVEN_SHARE = 2
_WINDOW_MULTIPLE = 512  # rows: a window is whole tiles at any tiling XLA picks


def share_window_rows(num_choices: int, num_experts: int, router_width: int) -> int:
    """Rows of :func:`moe_ragged`'s first window when a layer holds
    ``num_experts`` of a router ``router_width`` wide: the smallest multiple
    of 512 that is at least twice the even share of the ``num_choices``
    (T*K) sorted rows, and never more than all of them. A constant of the
    shapes: the routing does not move it."""
    even = num_choices * num_experts / router_width
    rows = _WINDOW_MULTIPLE * math.ceil(
        _WINDOW_OVER_EVEN_SHARE * even / _WINDOW_MULTIPLE)
    return min(rows, num_choices)


def _expert_rows(xs, tok, order, weights, group_sizes, w_gate, w_up, w_down,
                 num_tokens):
    """A run of sorted rows ``xs`` (every one in a group) through the three
    grouped matmuls, scattered back onto their tokens ``tok`` with the
    routing weights of the sorted choices ``order``: (num_tokens, h)."""
    with jax.named_scope("experts"):
        hidden = jax.nn.silu(
            jax.lax.ragged_dot(xs, w_gate, group_sizes)
        ) * jax.lax.ragged_dot(xs, w_up, group_sizes)  # (rows, f)
        out = jax.lax.ragged_dot(hidden, w_down, group_sizes)  # (rows, h)

    with jax.named_scope("combine"):
        w_flat = weights.reshape(-1)[order].astype(out.dtype)
        # weighted scatter-add back into token order (sums the K expert
        # contributions per token)
        return jnp.zeros((num_tokens, xs.shape[-1]), out.dtype).at[tok].add(
            out * w_flat[:, None])


def moe_ragged(
    x: jax.Array,
    sel: jax.Array,
    weights: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    expert_offset: int = 0,
    router_width: Optional[int] = None,
) -> jax.Array:
    """Exact sparse MoE via grouped matmuls (``jax.lax.ragged_dot``).

    Tokens sort by their selected expert; each expert's contiguous group
    multiplies against its weights with NO capacity padding and NO drops —
    exactly ``T*K`` token-expert pairs of FLOPs (the capacity schedule
    computes ``capacity_factor`` times that and drops overflow).

    **A share of the experts.** ``w_*`` hold experts ``[expert_offset,
    expert_offset + E)`` of a router ``router_width`` wide. A choice of an
    expert that is not held sorts behind every held one and adds nothing.
    The sorted rows are cut at a STATIC row ``C`` (:func:`share_window_rows`:
    twice the even share E / R of the ``T*K`` choices, a multiple of 512,
    from shapes alone):

    * the **first window**, rows ``[0, C)``, always runs: the gather, the
      three grouped matmuls and the weighted scatter-add over ``C`` rows,
      the held experts' groups clipped into the window and one more group,
      of ZERO weights, for whatever else lies in it;
    * the **rest window**, rows ``[C, T*K)``, is the same under a
      ``lax.cond`` that is true only when a held row lies past ``C``; its
      transpose is a ``cond`` on the same predicate, so the backward skips
      it too. Otherwise it adds zeros.

    Every row a grouped matmul is given is in a group, so every row it
    returns is defined; no choice of a held expert is dropped at any
    imbalance (all ``T*K`` on one held expert: both windows run); and while
    the held rows fit the first window the step's time is a constant of
    the shapes and does not follow the routing. When ``C == T*K`` (half
    the router or more held) there is one window and no ``cond``; with every
    expert held there is no zero group either: the program is the one it
    always was. (Why not give ``ragged_dot`` group sizes that sum to the
    live rows alone: XLA:TPU's kernel then SKIPS the other rows but leaves
    stale memory in them, in the backward kernel too: autodiff's ``d xs``
    then has undefined rows that no ``jnp.where`` on a forward output
    reaches, and the gather's transpose adds them into ``dx``. A
    ``custom_vjp`` that also zeroes cotangents and backward outputs is
    exact, and its time follows the routing, which drifts: PERF.md, PR 26.)
    On the v5e at LFM2-8B-A1B's widths (8 of 32 experts held, 4 x 4096
    tokens, top 4: 65,536 sorted rows a layer, C = 32,768) all rows through
    the grouped matmuls took 65.7 ms a layer forward + backward and the
    masked quarter 34.4 (my chip runs, PR 26); in the training step of four
    such layers the window took XLA's ``ragged-dot`` kernels from 166.5 to
    81.3 ms and the step from 486.5 to 375.5 ms, the rest window never
    taken in 256 steps; the 12 ``cond``s skipped cost 11.5 ms a step, the
    zeros a branch not taken writes for its residuals (my chip runs, PR 29;
    PERF.md section 6).

    Measured on v5e (bf16, B=16, S=1024, E=8, K=2, round-4 sweep): at
    Mixtral-width experts (h=4096, f=3584, L=1) ragged reaches 0.516 MFU
    vs capacity-1.25's 0.490 (no remat) / 0.475 (remat="dots") — ~5-9%
    faster AND exact. Under plain remat="dots" the advantage inverts
    (the dots policy recomputes ragged_dot in backward); use the
    "dots_ragged" policy (models/transformer._REMAT_POLICIES), which
    saves grouped-matmul outputs too (h=4096: 0.509 with dots_ragged).
    This is why ``moe_dispatch="auto"`` resolves to ragged at ep==1
    (and to :func:`moe_ragged_ep` at ep>1 — its docstring carries the
    drop-rate/collective-bytes evidence).

    Fully differentiable (ragged_dot has grad rules; sort / gather /
    scatter-add are linear; ``cond`` differentiates branch by branch).

    Use on single-chip / data-parallel meshes. With ``ep_size > 1``
    the per-expert group sizes are data-dependent, which GSPMD cannot
    shard over the ep axis — :func:`moe_ragged_ep` (a manual shard_map
    shard-capacity schedule) is the expert-parallel ragged path, and the
    per-expert capacity schedule remains the GSPMD-auto alternative.

    ``x``: (T, h); ``sel``/``weights``: (T, K); ``w_gate``/``w_up``:
    (E, h, f); ``w_down``: (E, f, h). Returns (T, h).
    """
    T, h = x.shape
    K = sel.shape[-1]
    E = w_gate.shape[0]
    TK = T * K
    R = router_width or E
    with jax.named_scope("dispatch"):
        local = sel.reshape(TK) - expert_offset
        flat_sel = jnp.where((local >= 0) & (local < E), local, E)
        order = jnp.argsort(flat_sel)  # stable: ties keep token order
        tok = jnp.repeat(jnp.arange(T), K)[order]  # source token per sorted row
    if R == E:  # every choice is of a held expert: one run of T*K rows
        with jax.named_scope("dispatch"):
            xs = jnp.take(x, tok, axis=0)  # (TK, h) rows grouped by expert
            group_sizes = jnp.bincount(flat_sel, length=E + 1).astype(jnp.int32)
        return _expert_rows(xs, tok, order, weights, group_sizes[:E],
                            w_gate, w_up, w_down, T)

    with jax.named_scope("dispatch"):
        # one more group, of zero weights, for the choices of absent experts
        w_gate, w_up, w_down = (
            jnp.concatenate([w, jnp.zeros((1,) + w.shape[1:], w.dtype)])
            for w in (w_gate, w_up, w_down)
        )
        held = jnp.bincount(flat_sel, length=E + 1).astype(jnp.int32)[:E]
        ends = jnp.cumsum(held)  # where each held expert's sorted rows end
        starts = ends - held

    def window(lo: int, hi: int):
        """Rows ``[lo, hi)`` of the sorted choices: the held experts' groups
        clipped into them, the zero-weight group taking the remainder."""
        rows = slice(lo, hi)
        with jax.named_scope("dispatch"):
            sizes = jnp.clip(ends, lo, hi) - jnp.clip(starts, lo, hi)
            sizes = jnp.concatenate([sizes, (hi - lo - jnp.sum(sizes))[None]])
            xs = jnp.take(x, tok[rows], axis=0)  # (hi - lo, h)
        return _expert_rows(xs, tok[rows], order[rows], weights, sizes,
                            w_gate, w_up, w_down, T)

    C = share_window_rows(TK, E, R)
    out = window(0, C)
    if C < TK:
        out = out + jax.lax.cond(
            ends[-1] > C,  # a held row lies past the first window
            lambda: window(C, TK),
            lambda: jnp.zeros(out.shape, out.dtype),
        )
    return out


def ragged_load_stats(
    sel: jax.Array,
    num_experts: int,
    expert_offset: int = 0,
    router_width: Optional[int] = None,
) -> dict:
    """What the choices ``sel`` (..., K) say of the load of a layer that
    holds experts ``[expert_offset, expert_offset + num_experts)`` of a
    router ``router_width`` wide (None: all of them), as float32 scalars:
    the share of the choices that fell on experts held here (held / router
    width when routing is even), the fullest held expert's load over the
    mean, the rows that went through :func:`moe_ragged`'s grouped matmuls —
    its first window, and the rest of the T*K when a held row lay past it —
    over the rows that were needed, and whether that rest window ran (1.0
    or 0.0: the mean over the expert layers is the share of them in which
    it did)."""
    local = sel.reshape(-1) - expert_offset
    num_choices = local.shape[0]
    sizes = jnp.bincount(
        jnp.where((local >= 0) & (local < num_experts), local, num_experts),
        length=num_experts + 1,
    )[:num_experts].astype(jnp.float32)
    needed = jnp.sum(sizes)
    window = share_window_rows(
        num_choices, num_experts, router_width or num_experts)
    rest_ran = (needed > window).astype(jnp.float32)
    return {
        "moe_local_choice_share": needed / num_choices,
        "moe_expert_load_max_over_mean": jnp.max(sizes)
        / jnp.maximum(jnp.mean(sizes), 1.0),
        "moe_rows_computed_over_needed": (
            window + (num_choices - window) * rest_ran
        ) / jnp.maximum(needed, 1.0),
        "moe_rest_window_share": rest_ran,
    }


def moe_ragged_ep(
    x: jax.Array,
    sel: jax.Array,
    weights: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    mesh,
    capacity_factor: float = 1.25,
    axis_name: str = "ep",
) -> jax.Array:
    """Expert-parallel grouped-matmul MoE: the ragged schedule under an
    ``ep``-sharded expert dim (lifts ``moe_ragged``'s single-shard limit).

    Shard-capacity design (static shapes, which per-expert ragged routing
    cannot give GSPMD): tokens sort by selected expert — identically on
    every shard — so each ep shard's experts own ONE contiguous region of
    the sorted (T*K) rows. Each shard processes a fixed-size window of
    ``C_s = ceil(T*K/ep * capacity_factor)`` rows starting at its
    region's offset: inside the window, LOCAL experts' rows hit their
    expert via ``ragged_dot`` with NO per-expert padding; rows past the
    local region fall into a zero-weight dummy group (free of wrong
    results, they belong to the next shard's region and are computed
    there). Combine is a weighted scatter-add + one psum over ep.

    vs the per-expert capacity schedule: padding waste is per-SHARD, not
    per-expert — drops happen only when a shard's whole expert-group
    overflows ``capacity_factor`` headroom (much rarer than one hot
    expert overflowing), and the expert matmuls stay ragged-packed.
    ``capacity_factor >= ep`` (each shard's window covers all T*K rows)
    cannot drop and equals the dense oracle exactly.

    Measured (r5, the evidence behind ``moe_dispatch="auto"`` resolving
    here at ep>1; both schedules compute the same cf*T*K padded row-FLOPs
    so drops and comm decide): at T=8192 E=8 K=2 cf=1.25 with
    Gumbel-perturbed Dirichlet routing, per-expert capacity drops
    3.5%/9.5%/23.7% of token-choices at Dirichlet concentration
    10/3/1 (ep=2) where this schedule drops 0%/1.0%/2.9% — 3-10x fewer
    at every skew tried, both ep=2 and ep=4; and the compiled fwd+bwd
    CausalLM step on a dp=2 x ep=4 CPU mesh moves 2.5 MB of collective
    output bytes vs capacity's 5.2 MB (~2.1x; all-gather 0.32 MB vs
    1.78 MB, all-reduce 2.19 MB vs 3.41 MB).

    Built as a nested shard_map manual over ONLY the ep axis (the same
    context-mesh pattern as ring attention under pp, with
    ``check_vma=True`` — its transpose is what makes the backward
    correct). ``x``: (T, h) global; ``w_*``: (E, h, f)/(E, f, h) with E
    sharded over ep; returns (T, h).
    """
    from jax.sharding import PartitionSpec as P

    ep = mesh.shape[axis_name]
    T, h = x.shape
    K = sel.shape[-1]
    E = w_gate.shape[0]
    El = E // ep
    TK = T * K
    C_s = max(8 * math.ceil(TK * capacity_factor / ep / 8), 8)

    def body(xl, sell, wl, wg, wu, wd):
        shard = jax.lax.axis_index(axis_name)
        flat_sel = sell.reshape(TK)
        order = jnp.argsort(flat_sel)  # stable: ties keep token order
        tok = jnp.repeat(jnp.arange(T), K)[order]
        w_flat = wl.reshape(TK)[order]
        counts = jnp.bincount(flat_sel, length=E).astype(jnp.int32)
        offsets = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)]
        )  # (E+1,) exclusive prefix
        my_first = shard * El
        off_s = offsets[my_first]

        # static-size window of the sorted rows starting at this shard's
        # region; pad so the slice never reads out of bounds (padded tok
        # indices point at row 0 but always land in the dummy group)
        pad = lambda a: jnp.concatenate(
            [a, jnp.zeros((C_s,) + a.shape[1:], a.dtype)]
        )
        tok_win = jax.lax.dynamic_slice(pad(tok), (off_s,), (C_s,))
        w_win = jax.lax.dynamic_slice(pad(w_flat), (off_s,), (C_s,))
        xs = jnp.take(xl, tok_win, axis=0)  # (C_s, h)

        # local group sizes clipped into the window + dummy tail group
        lo, hi = off_s, off_s + C_s
        starts = jnp.clip(
            jax.lax.dynamic_slice(offsets, (my_first,), (El,)), lo, hi
        )
        ends = jnp.clip(
            jax.lax.dynamic_slice(offsets, (my_first + 1,), (El,)), lo, hi
        )
        gs = (ends - starts).astype(jnp.int32)
        gs = jnp.concatenate([gs, (C_s - jnp.sum(gs))[None].astype(jnp.int32)])

        zed = jnp.zeros((1,) + wg.shape[1:], wg.dtype)
        hidden = jax.nn.silu(
            jax.lax.ragged_dot(xs, jnp.concatenate([wg, zed]), gs)
        ) * jax.lax.ragged_dot(xs, jnp.concatenate([wu, zed]), gs)
        out = jax.lax.ragged_dot(
            hidden, jnp.concatenate([wd, jnp.zeros((1,) + wd.shape[1:], wd.dtype)]),
            gs,
        )  # (C_s, h); dummy-group rows are exact zeros

        contrib = jnp.zeros((T, h), out.dtype).at[tok_win].add(
            out * w_win[:, None].astype(out.dtype)
        )
        return jax.lax.psum(contrib, axis_name)

    # nested-manual aware, same as ops/ring_attention.py
    from ..utils.operations import nested_manual_mesh

    ctx = nested_manual_mesh()
    sm_mesh = ctx if ctx is not None else mesh

    from jax import shard_map

    return shard_map(
        body,
        mesh=sm_mesh,
        in_specs=(P(), P(), P(), P(axis_name), P(axis_name), P(axis_name)),
        out_specs=P(),
        check_vma=True,
        axis_names={axis_name},
    )(x, sel, weights, w_gate, w_up, w_down)


def moe_dispatch_combine(
    x: jax.Array,
    sel: jax.Array,
    weights: jax.Array,
    experts_fn: Callable[[jax.Array], jax.Array],
    num_experts: int,
    capacity_factor: float = 2.0,
    capacity: Optional[int] = None,
) -> jax.Array:
    """Route tokens through their selected experts under a capacity limit.

    ``x``: (T, h) tokens. ``sel``/``weights``: (T, K) top-K expert ids and
    combine weights. ``experts_fn``: (E, C, h) -> (E, C, h), the batched
    expert computation. Returns (T, h).
    """
    T, h = x.shape
    K = sel.shape[-1]
    E = num_experts
    C = capacity or expert_capacity(T, E, K, capacity_factor)

    flat_sel = sel.reshape(T * K)  # token-major: earlier tokens win slots
    onehot = jax.nn.one_hot(flat_sel, E, dtype=jnp.int32)  # (TK, E)
    # position of each (token, choice) within its expert's buffer
    pos = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)  # (TK,)
    keep = pos < C
    # slot in the flattened (E*C) buffer; dropped claims point one past the
    # end so scatter/gather OOB modes erase them (never another expert's 0)
    slot = jnp.where(keep, flat_sel * C + pos, E * C)

    tok_idx = jnp.repeat(jnp.arange(T), K)  # (TK,)
    buf = (
        jnp.zeros((E * C, h), x.dtype)
        .at[slot]
        .set(x[tok_idx], mode="drop")
        .reshape(E, C, h)
    )
    buf = _constrain_expert_buffer(buf)

    expert_out = _constrain_expert_buffer(experts_fn(buf))  # (E, C, h)

    y = jnp.take(
        expert_out.reshape(E * C, h), slot, axis=0,
        mode="fill", fill_value=0,
    )  # (TK, h); dropped choices read zeros
    y = y.reshape(T, K, h) * weights.reshape(T, K, 1).astype(y.dtype)
    return jnp.sum(y, axis=1)


def _constrain_expert_buffer(buf: jax.Array) -> jax.Array:
    """Pin the (E, C, h) buffer: experts over ep, capacity over the
    remaining data axes — so GSPMD lowers dispatch/combine to one
    all-to-all instead of flip-flopping the buffer between token- and
    expert-sharded layouts, AND the expert einsums stay divided across
    dp/fsdp instead of replicated (every dp replica computing all C slots
    would multiply the expert FLOPs). No-op without a live mesh or ep==1."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.sharding import live_mesh
    from ..utils.constants import MESH_AXIS_DATA, MESH_AXIS_EXPERT, MESH_AXIS_FSDP

    from ..utils.operations import nested_manual_mesh

    mesh = live_mesh()
    if mesh is None or mesh.shape.get(MESH_AXIS_EXPERT, 1) <= 1:
        return buf
    if nested_manual_mesh() is not None:
        # inside a pipeline stage body the concrete mesh no longer
        # matches the trace; a constraint here would raise. The capacity
        # path under pp runs unconstrained — moe_ragged_ep (the ep>1
        # default) is the pinned-layout pipeline path.
        return buf
    if buf.shape[0] % mesh.shape[MESH_AXIS_EXPERT]:
        return buf
    cap_axes = tuple(
        a for a in (MESH_AXIS_DATA, MESH_AXIS_FSDP) if mesh.shape[a] > 1
    )
    cap_div = math.prod(mesh.shape[a] for a in cap_axes)
    spec_c = cap_axes if cap_axes and buf.shape[1] % cap_div == 0 else None
    return jax.lax.with_sharding_constraint(
        buf, NamedSharding(mesh, P(MESH_AXIS_EXPERT, spec_c, None))
    )


def load_balancing_loss(
    logits: jax.Array, sel: jax.Array, num_experts: int
) -> jax.Array:
    """Switch-style auxiliary loss: E * sum_e density_e * router_prob_e,
    minimized by a uniform routing distribution. ``logits``: (..., E),
    ``sel``: (..., K)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    routed = jnp.max(
        jax.nn.one_hot(sel, num_experts, dtype=jnp.float32), axis=-2
    )  # (..., E): 1 where the token picked expert e
    axes = tuple(range(routed.ndim - 1))
    density = jnp.mean(routed, axis=axes)
    prob_mean = jnp.mean(probs, axis=axes)
    return num_experts * jnp.sum(density * prob_mean)

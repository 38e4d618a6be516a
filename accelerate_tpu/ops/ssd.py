"""The selective state-space recurrence of Mamba-2, computed in chunks
(Dao & Gu 2024, "state space duality"): two Mosaic kernels behind one
``custom_vjp`` on a TPU, ``jax.numpy`` einsums elsewhere.

A head ``h`` with ``P`` channels keeps a state ``H`` of ``P x N``:

    H_t = exp(delta_t A) H_{t-1} + delta_t x_t B_t^T        H_0 = 0
    y_t = H_t C_t + D x_t

``A`` one negative scalar a head, ``delta_t`` one positive scalar a head and
position, ``B_t`` and ``C_t`` vectors of ``N`` shared by the heads of a
group. Unrolled, ``y_i = sum_{j <= i} exp(a_i - a_j) delta_j (C_i . B_j) x_j``
with ``a`` the running sum of ``delta A``: a causal attention whose mask is
a decay. The sequence is cut into chunks of ``Q`` positions and that sum is
split at the chunk's start:

* ``intra``: inside a chunk the masked quadratic form, two matmuls
  (``C B^T``: Q x Q a group; the masked, decayed scores times ``x``);
* ``states``: what each chunk adds to the state by its end, one matmul
  (``(decay-to-end x delta x)^T B``: P x N a head);
* the states are handed from chunk to chunk by the recurrence itself over
  ``S / Q`` steps (float32);
* ``inter``: what the state a chunk starts from adds to its outputs, one
  matmul (``C H``), decayed from the chunk's start.

Precision, in both forms: ``delta``, the cumulative log-decays, every ``exp``
of them and the chunk states are float32; the four matmuls (and their
transposes in the backward pass) take their operands in ``x``'s dtype
(bfloat16 in a mixed-precision step) and accumulate in float32. Within a
chunk ``a_i - a_j`` is formed BEFORE the ``exp`` (never ``exp(a_i) /
exp(a_j)``) and masked before it, so nothing overflows however long the
chunk's decay; ``exp(a)`` itself only ever multiplies (it underflows to an
exact 0 where a chunk forgets everything: :func:`chunk_decay_min` is the
gauge).

**Which form runs where.** :func:`ssd_kernel_eligible` is the one predicate:
a TPU (or ``kernel_interpret_mode()``), no live mesh, whole tiles. There
:func:`ssd_chunked` is ``pallas_call(name="ssd_chunked_fwd")`` and, under
differentiation, ``pallas_call(name="ssd_chunked_bwd")``; elsewhere — the
CPU, a mesh (a Mosaic kernel is not partitioned), an odd shape —
:func:`_chunked_reference`, the ``jax.numpy`` form under plain autodiff,
which is also the tests' second reference.

**The kernels.** Both walk a grid of (row, group, chunk), the chunk axis
sequential; a step holds ONE group's heads side by side on the lanes (heads
of 64 two to a tile of 128: no slice cuts a tile) so that ``C B^T`` is formed
once a group and the group's ``dB`` and ``dC`` are summed inside the step.

* In VMEM only: every head's Q x Q decay mask (at 2 x 8192 tokens, 64 heads
  and chunks of 128 they would be 537 MB in float32, and the ``jax.numpy``
  form reads or writes one in each of a dozen passes), the masked scores,
  the decayed ``x``, and the group's float32 states (N x heads P: a scratch
  that stays resident along the chunk axis), in the backward kernel their
  cotangent.
* To HBM: the forward kernel reads ``x``, ``B``, ``C`` and ``delta`` once
  (``delta`` as (B, C, G, heads, Q): the chunk on the lanes, nothing padded)
  and writes ``y`` once (``D x`` included). Under differentiation it also
  writes the state each chunk STARTS from (float32, 268 MB a layer at those
  shapes; under the layer's ``nn.remat`` it lives from the recomputed
  forward to the backward kernel of the same layer).
* The running sum of ``delta A`` inside a chunk, both as a row and as a
  column of the masks, is made in the kernels, on the matrix unit: a product
  with a triangle of ones, exact — each float32 term goes in as three
  bfloat16 pieces (8 + 8 + 8 bits), a piece times a one is exact, the sums are
  float32 (:func:`_exact`). The same product with an identity is how one
  number a head and position goes between (heads, Q) and (Q, heads).
* The backward kernel walks the chunks from the last to the first. It
  recomputes a chunk's masks, scores and decayed ``x`` from the inputs, reads
  the saved start state, and writes ``dx``, ``dB``, ``dC``, ``d delta`` (the
  reverse running sum inside the chunk is one more product with ones) and a
  row's and group's shares of ``dA`` and ``dD``, summed along the walk in a
  block that stays resident; ``jax.numpy`` outside only sums those shares and
  puts ``d delta`` back as (B, S, H).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# what a grid step may hold: the cell's group (8 heads of 64, N 128, chunks
# of 128) is what was compiled and timed on the chip; a larger group, state
# or chunk takes the ``jax.numpy`` form
_MASK_ELEMENTS = 8 * 128 * 128  # heads a group x Q x Q
_STATE_ELEMENTS = 512 * 128  # heads a group x P x N


def _cumulative_log_decay(log_decay: jax.Array) -> jax.Array:
    """Running sum of ``delta A`` along a chunk (axis 2 of (B, C, Q, H)),
    in float32 whatever came in: 128 terms of a bfloat16 sum would lose the
    small ones."""
    return jnp.cumsum(log_decay.astype(jnp.float32), axis=2)


def _carry_states(states: jax.Array, chunk_decay: jax.Array) -> jax.Array:
    """The state each chunk STARTS from: ``states`` (B, C, ...) is what each
    chunk adds by its end, ``chunk_decay`` (B, C, H) what it multiplies the
    state it was handed by. The recurrence itself, over the chunks."""
    def step(carry, inp):
        add, decay = inp
        return carry * decay[..., None, None] + add, carry

    b, c = states.shape[:2]
    heads = chunk_decay.shape[-1]
    # (B, C, G, R, P, N) <-> (C, B, H, P, N): heads flat for the decay
    flat = states.reshape((b, c, heads) + states.shape[-2:])
    _, starts = jax.lax.scan(
        step, jnp.zeros_like(flat[:, 0]),
        (jnp.moveaxis(flat, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    return jnp.moveaxis(starts, 0, 1).reshape(states.shape)


def _chunked_reference(x, delta, a, b_mat, c_mat, chunk: int, skip=None):
    """:func:`ssd_chunked` in ``jax.numpy``: four einsums, the Q x Q decay
    masks and the chunk states as arrays in HBM, the states handed on by a
    ``lax.scan``; differentiated by plain autodiff."""
    bsz, s, heads, p = x.shape
    x_in = x
    groups, n = b_mat.shape[-2:]
    r = heads // groups  # heads a group
    pad = -s % chunk
    if pad:
        x, delta, b_mat, c_mat = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, delta, b_mat, c_mat))
    c = (s + pad) // chunk
    dtype = x.dtype
    f32 = jnp.float32
    x = x.reshape(bsz, c, chunk, groups, r, p)
    b_mat = b_mat.reshape(bsz, c, chunk, groups, n)
    c_mat = c_mat.reshape(bsz, c, chunk, groups, n)
    delta = delta.astype(f32).reshape(bsz, c, chunk, heads)
    cum = _cumulative_log_decay(delta * a.astype(f32))  # (B, C, Q, H), <= 0
    total = cum[:, :, -1]  # (B, C, H): a whole chunk's log-decay

    with jax.named_scope("intra"):
        scores = jnp.einsum("bcign,bcjgn->bcgij", c_mat, b_mat,
                            preferred_element_type=f32)
        # exp(a_i - a_j) delta_j for j <= i, 0 above the diagonal; the
        # difference is taken before the exp and masked before it too, so
        # the upper triangle's positive differences never reach it
        cum_h = jnp.moveaxis(cum, 2, 3)  # (B, C, H, Q)
        diff = cum_h[..., :, None] - cum_h[..., None, :]
        keep = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.exp(jnp.where(keep, diff, -jnp.inf))
        decay = decay * jnp.moveaxis(delta, 2, 3)[..., None, :]
        decay = decay.reshape(bsz, c, groups, r, chunk, chunk)
        masked = (scores[:, :, :, None] * decay).astype(dtype)
        y = jnp.einsum("bcgrij,bcjgrp->bcigrp", masked, x,
                       preferred_element_type=f32)

    with jax.named_scope("states"):
        # what position j still weighs when its chunk ends
        to_end = jnp.exp(total[:, :, None] - cum) * delta  # (B, C, Q, H)
        weighted = (x * to_end.reshape(bsz, c, chunk, groups, r, 1)).astype(dtype)
        states = jnp.einsum("bcjgrp,bcjgn->bcgrpn", weighted, b_mat,
                            preferred_element_type=f32)
        starts = _carry_states(states, jnp.exp(total))

    with jax.named_scope("inter"):
        from_start = jnp.einsum("bcign,bcgrpn->bcigrp", c_mat,
                                starts.astype(dtype), preferred_element_type=f32)
        y = y + from_start * jnp.exp(cum).reshape(bsz, c, chunk, groups, r, 1)

    y = y.reshape(bsz, c * chunk, heads, p)[:, :s]
    if skip is not None:
        y = y + x_in * skip.astype(f32)[:, None]
    return y.astype(dtype)


# --------------------------------------------------------------------------- #
# the chunked form as two kernels
# --------------------------------------------------------------------------- #
def _tile() -> int:
    """Lanes a tile: 128; whole sublanes of 8 under the interpreter, as
    ``fit_block`` relaxes."""
    from .flash_attention import MIN_BLOCK, kernels_interpreted

    return MIN_BLOCK if kernels_interpreted() else _LANES


def ssd_kernel_eligible(heads: int, p: int, groups: int, n: int,
                        chunk: int) -> bool:
    """Whether :func:`ssd_chunked` runs as the ``ssd_chunked_fwd`` /
    ``ssd_chunked_bwd`` kernels at ``heads`` heads of ``p`` in ``groups``
    groups of state ``n`` and chunks of ``chunk``: a TPU or
    ``kernel_interpret_mode()``, one device's rows and heads (a Mosaic kernel
    is not partitioned over a mesh; the ``jax.numpy`` form is), a chunk of
    whole tiles (128; 8 under the interpreter), ``n`` whole lanes, a group's
    heads side by side filling whole lanes with no head across a tile's edge,
    and a group whose masks and states a grid step can hold. ONE predicate,
    asked once a call, by :func:`ssd_chunked`."""
    from ..parallel.sharding import live_mesh
    from .flash_attention import kernels_interpreted

    tile = _tile()
    r = heads // max(groups, 1)
    return (
        (jax.default_backend() == "tpu" or kernels_interpreted())
        and live_mesh() is None
        and groups > 0 and heads % groups == 0
        and chunk % tile == 0 and n % tile == 0
        and (r * p) % tile == 0 and (p % tile == 0 or tile % p == 0)
        and r * chunk * chunk <= _MASK_ELEMENTS
        and r * p * n <= _STATE_ELEMENTS
    )


def _dot(a, b, contract):
    """``a`` and ``b`` contracted over one axis each, float32 out."""
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


def _exact(ones, v, contract, ones_first=True):
    """The product of a matrix of ones and zeros with float32 ``v``, to the
    last bit of ``v``'s terms, on the matrix unit: ``v`` is the sum of three
    bfloat16 pieces (8 + 8 + 8 bits), each piece times a one is exact, and
    the sums are float32. A running sum or a transpose in one pass a piece."""
    low = jnp.bfloat16
    ones = ones.astype(low)
    hi = v.astype(low)
    rest = v - hi.astype(jnp.float32)
    mid = rest.astype(low)
    pieces = (hi, mid, (rest - mid.astype(jnp.float32)).astype(low))
    return sum(_dot(ones, piece, contract) if ones_first
               else _dot(piece, ones, contract) for piece in pieces)


def _triangle(q, keep=lambda k, j: k <= j):
    """``keep(k, j)`` over a (Q, Q) of ones, float32: by default the upper
    triangle with its diagonal."""
    k = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return keep(k, j).astype(jnp.float32)


def _identity(q):
    return _triangle(q, lambda k, j: k == j)


def _decays(drow_ref, arow_ref):
    """A chunk's ``delta`` as it comes, (r, Q), and what the kernels make of
    it on the matrix unit: ``delta`` as (Q, r), and the running sum of
    ``delta A`` along the chunk both ways, (r, Q) and (Q, r), float32 — ONE
    sum and its transpose, so that ``a_i - a_i`` is an exact 0."""
    drow = drow_ref[0, 0, 0]
    q = drow.shape[1]
    crow = _exact(_triangle(q), drow * arow_ref[0], (1, 0), ones_first=False)
    return (drow, _exact(_identity(q), drow, (1, 1)), crow,
            _exact(_identity(q), crow, (1, 1)))


def _side_by_side(parts):
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]


class _Lanes:
    """How a group's ``r`` heads of ``p`` lie on the lanes of a (rows, r p)
    array: in slabs of one tile (or one head, where a head is whole tiles),
    ``per`` heads a slab. Everything that is one number a head goes to and
    from the lanes slab by slab, with selects: no slice cuts a tile."""

    def __init__(self, r: int, p: int):
        self.r, self.p = r, p
        self.slab = max(p, _tile())
        self.per = self.slab // p
        self.slabs = r * p // self.slab
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, self.slab), 1)
        # lanes of the k-th head of a slab
        self.of = [(lane >= k * p) & (lane < (k + 1) * p)
                   for k in range(self.per)]

    def cut(self, wide, s):
        return wide[:, s * self.slab:(s + 1) * self.slab]

    def only(self, slab, k):
        """The slab with every head's lanes but the k-th zeroed."""
        return slab if self.per == 1 else jnp.where(
            self.of[k], slab, jnp.zeros_like(slab))

    def merge(self, wides):
        """``r`` arrays (rows, slab), a head's number over all a slab's
        lanes -> (rows, r p), each head's over its own lanes."""
        out = []
        for s in range(self.slabs):
            acc = wides[s * self.per]
            for k in range(1, self.per):
                acc = jnp.where(self.of[k], wides[s * self.per + k], acc)
            out.append(acc)
        return _side_by_side(out)

    def spread(self, cols):
        """(rows, r), a number a head -> (rows, r p), over the head's lanes."""
        rows = cols.shape[0]
        return self.merge([jnp.broadcast_to(cols[:, h:h + 1], (rows, self.slab))
                           for h in range(self.r)])

    def rows(self, wide):
        """(Q, r p) float32 -> (r, Q): the sum over each head's lanes, the
        chunk on the lanes of the result; a product with ones, exact."""
        head = jax.lax.broadcasted_iota(jnp.int32, (self.r, self.r * self.p), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (self.r, self.r * self.p), 1)
        mine = (lane >= head * self.p) & (lane < (head + 1) * self.p)
        return _exact(mine.astype(jnp.float32), wide, (1, 1))


def _decay_masks(cwide, crow, drow):
    """``exp(a_i - a_j)`` for j <= i and 0 above the diagonal, (r, Q, Q)
    float32 — the difference before the ``exp`` and masked before it — and
    ``delta_j`` as (r, 1, Q). ``cwide``: a head's ``a_i`` over the lanes."""
    r, q = crow.shape
    heads = range(r)
    ccol = jnp.stack([cwide[h][:, :q] for h in heads])  # (r, Q, Q)
    crow = jnp.stack([crow[h:h + 1] for h in heads])  # (r, 1, Q)
    drow = jnp.stack([drow[h:h + 1] for h in heads])
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return jnp.exp(jnp.where(row >= col, ccol - crow, -jnp.inf)), drow


def _chunk_scalars(ccol, dcol, lanes):
    """Of one chunk, over the lanes: ``exp`` of each head's whole log-decay
    (1, r p), ``exp(a_i)`` and ``exp(total - a_j) delta_j`` (Q, r p); and the
    heads' ``a_i`` each over a slab's lanes, which the masks read too."""
    q = ccol.shape[0]
    total = lanes.spread(ccol[q - 1:])
    cwide = [jnp.broadcast_to(ccol[:, h:h + 1], (q, max(q, lanes.slab)))
             for h in range(lanes.r)]
    wide = lanes.merge([w[:, :lanes.slab] for w in cwide])
    return (cwide, jnp.exp(total), jnp.exp(wide),
            jnp.exp(total - wide) * lanes.spread(dcol))


def _intra(masked, x, lanes, transposed=False):
    """Every head's masked scores (r, Q, Q) times its channels of ``x``
    (Q, r p), float32 — ``transposed``: the scores' transposes, as the
    backward pass wants them. A slab's heads in ONE product: their masks side
    by side (one above the other when transposed) against their channels one
    above the other, each with the other heads' lanes zeroed."""
    return _side_by_side([
        _dot(jnp.concatenate([masked[s * lanes.per + k]
                              for k in range(lanes.per)], axis=int(not transposed)),
             jnp.concatenate([lanes.only(lanes.cut(x, s), k)
                              for k in range(lanes.per)], axis=0),
             (int(not transposed), 0))
        for s in range(lanes.slabs)])


def _fwd_kernel(x_ref, b_ref, c_ref, drow_ref, arow_ref, skip_ref, y_ref,
                *rest, p, save):
    """One row, one group, one chunk. ``state`` (N, r p) float32 is a scratch
    that stays in VMEM from the row's first chunk to its last; with ``save``
    the state the chunk starts from also goes out, for the backward kernel."""
    state = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _from_zero():
        state[...] = jnp.zeros_like(state)

    x, bm, cm = x_ref[0], b_ref[0], c_ref[0]
    dtype = x.dtype
    drow, dcol, crow, ccol = _decays(drow_ref, arow_ref)
    lanes = _Lanes(drow.shape[0], p)
    cwide, kept, from_start, to_end = _chunk_scalars(ccol, dcol, lanes)
    decay, drow = _decay_masks(cwide, crow, drow)
    scores = _dot(cm, bm, (1, 1))  # C B^T, once a group
    masked = (scores[None] * (decay * drow)).astype(dtype)  # (r, Q, Q)
    y = _intra(masked, x, lanes)

    start = state[...]
    if save:
        rest[0][0, 0, 0] = start
    xf = x.astype(jnp.float32)
    y = y + _dot(cm, start.astype(dtype), (1, 0)) * from_start
    weighted = (xf * to_end).astype(dtype)
    state[...] = kept * start + _dot(bm, weighted, (0, 0))
    y_ref[0] = (y + xf * skip_ref[0]).astype(y_ref.dtype)


def _bwd_kernel(x_ref, b_ref, c_ref, drow_ref, arow_ref, skip_ref, starts_ref,
                dy_ref, dx_ref, db_ref, dc_ref, ddelta_ref, da_ref, dskip_ref,
                dstate, *, p):
    """One row, one group, one chunk, the chunks from the last to the first.
    ``dstate`` (N, r p) float32, the cotangent of the state the chunk ENDS in,
    stays in VMEM along the walk. Out: ``dx``, the group's ``dB`` and ``dC``
    (summed over its heads here), ``d delta`` (r, Q), and the group's shares
    of ``dA`` and ``dD`` (summed over the walk)."""
    @pl.when(pl.program_id(2) == 0)
    def _from_zero():
        dstate[...] = jnp.zeros_like(dstate)
        da_ref[...] = jnp.zeros_like(da_ref)
        dskip_ref[...] = jnp.zeros_like(dskip_ref)

    x, dy, bm, cm = x_ref[0], dy_ref[0], b_ref[0], c_ref[0]
    dtype = x.dtype
    f32 = jnp.float32
    drow, dcol, crow, ccol = _decays(drow_ref, arow_ref)
    q, r = ccol.shape
    lanes = _Lanes(r, p)
    cwide, kept, from_start_wide, to_end_wide = _chunk_scalars(ccol, dcol, lanes)
    decay, drow3 = _decay_masks(cwide, crow, drow)
    xf, dyf = x.astype(f32), dy.astype(f32)
    scores = _dot(cm, bm, (1, 1))
    mask = decay * drow3
    masked = (scores[None] * mask).astype(dtype)
    start = starts_ref[0, 0, 0]
    start_low = start.astype(dtype)
    dend = dstate[...]
    dend_low = dend.astype(dtype)
    weighted = (xf * to_end_wide).astype(dtype)

    # intra: the cotangent of each head's masked scores, dy_h x_h^T
    dmasked = jnp.stack([
        _dot(lanes.only(lanes.cut(dy, s), k), lanes.cut(x, s), (1, 1))
        for s in range(lanes.slabs) for k in range(lanes.per)])  # (r, Q, Q)
    dscores = jnp.sum(dmasked * mask, axis=0).astype(dtype)
    through = dmasked * scores[None] * decay  # d / d delta_j, before its sum
    dlow = jnp.sum(through, axis=1)  # (r, Q): over i
    # of a_j as a column index of the masks, over i: of the masked scores AS
    # MULTIPLIED (rounded to the operands' dtype), as dy . y below has them
    # for a_i as a row index — the two sums are of one matrix and cancel
    dcolumn = jnp.sum(dmasked * masked.astype(f32), axis=1)
    dx = _intra(masked, dy, lanes, transposed=True)

    # inter: y += exp(a_i) (C H)
    read = _dot(cm, start_low, (1, 0))  # (Q, r p)
    dread = (dyf * from_start_wide).astype(dtype)
    # states: H' = exp(total) H + B^T (to_end x)
    dweighted = _dot(bm, dend_low, (1, 0))  # (Q, r p)
    dstate[...] = kept * dend + _dot(cm, dread, (0, 0))

    # delta and the running log-decay a, one number a head and position, as
    # (r, Q): the chunk on the lanes, every product with ones of r rows.
    # a_i as a row index, of the masks and of exp(a_i), is dy . (y - D x)
    # summed over a head's lanes; the chunk's whole decay through the state
    # is a's last entry; then a position's weight at its chunk's end
    last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    dcum = lanes.rows(
        dyf * (_intra(masked, x, lanes) + read * from_start_wide)
        + jnp.where(last, kept * jnp.sum(dend * start, axis=0, keepdims=True), 0.0))
    dto_end = lanes.rows(dweighted * xf)
    to_end = jnp.exp(crow[:, q - 1:] - crow)  # (r, Q) now
    through_end = dto_end * to_end * drow  # d / d (total - a_j)
    last = jax.lax.broadcasted_iota(jnp.int32, (r, q), 1) == q - 1
    dcum = dcum - dcolumn - through_end + jnp.where(
        last, jnp.sum(through_end, axis=1, keepdims=True), 0.0)
    # a_i is a running sum inside its chunk: its cotangent runs back
    dlog = _exact(_triangle(q, lambda k, i: k >= i), dcum, (1, 0),
                  ones_first=False)
    ddelta_ref[0, 0, 0] = dto_end * to_end + dlow + dlog * arow_ref[0]
    da_ref[0, 0] += jnp.sum(dlog * drow, axis=1, keepdims=True)

    dx_ref[0] = (dx + dweighted * to_end_wide
                 + dyf * skip_ref[0]).astype(dx_ref.dtype)
    dskip_ref[0, 0] += jnp.sum(dyf * xf, axis=0, keepdims=True)
    dc_ref[0] = (_dot(dscores, bm, (1, 0))
                 + _dot(dread, start_low, (1, 1))).astype(dc_ref.dtype)
    db_ref[0] = (_dot(dscores, cm, (0, 0))
                 + _dot(weighted, dend_low, (1, 1))).astype(db_ref.dtype)


class _Chunks:
    """The operands as the kernels take them: whole chunks (the tail padded
    with ``delta`` 0), a group's heads flat on the last axis, ``delta`` as
    (B, C, G, r, Q) — the chunk on the lanes, nothing padded — and ``A`` a
    group, (G, r, 1)."""

    def __init__(self, x, delta, a, b_mat, c_mat, skip, chunk):
        self.shape = bsz, s, heads, p = x.shape
        groups, n = b_mat.shape[-2:]
        self.chunk, self.groups, self.n, self.r = chunk, groups, n, heads // groups
        self.pad = pad = -s % chunk
        self.chunks = (s + pad) // chunk
        self.x = self.rows(x.reshape(bsz, s, heads * p))
        self.b = self.rows(b_mat.reshape(bsz, s, groups * n))
        self.c = self.rows(c_mat.reshape(bsz, s, groups * n))
        delta = self.rows(delta.astype(jnp.float32))
        self.drow = jnp.transpose(delta.reshape(
            bsz, self.chunks, chunk, groups, self.r), (0, 1, 3, 4, 2))
        self.arow = a.astype(jnp.float32).reshape(groups, self.r, 1)
        # D (H,) over its head's lanes, (G, 1, r p); zeros for None
        skip = (jnp.zeros((heads,), jnp.float32) if skip is None
                else skip.astype(jnp.float32))
        self.skip = jnp.repeat(skip, p).reshape(groups, 1, self.r * p)

    def rows(self, wide):
        return jnp.pad(wide, ((0, 0), (0, self.pad), (0, 0)))

    def unrow(self, per_group):
        """(B, C, G, r, Q) -> (B, S, H), the padding dropped."""
        bsz, s, heads, _ = self.shape
        return jnp.transpose(per_group, (0, 1, 4, 2, 3)).reshape(
            bsz, self.chunks * self.chunk, heads)[:, :s]

    def specs(self, backward: bool):
        """Block specs of a (B, S, lanes) operand, of a (B, C, G, ., .) one
        and of a group's (G, ., .) one, and the grid; the backward kernel
        walks the chunks in reverse."""
        last = self.chunks - 1

        def at(i):
            return last - i if backward else i

        def wide(width):
            return pl.BlockSpec((1, self.chunk, width),
                                lambda b, g, i: (b, at(i), g))

        def small(rows, cols):
            return pl.BlockSpec((1, 1, 1, rows, cols),
                                lambda b, g, i: (b, at(i), g, 0, 0))

        def group(rows, cols):
            return pl.BlockSpec((1, rows, cols), lambda b, g, i: (g, 0, 0))

        return wide, small, group, (self.shape[0], self.groups, self.chunks)


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)


def _forward_call(ch: _Chunks, save: bool):
    """``y`` (B, S + pad, H P) and, with ``save``, the state every chunk
    starts from, (B, C, G, N, r P) float32."""
    from .flash_attention import kernels_interpreted

    bsz, _, heads, p = ch.shape
    q, r, n, width = ch.chunk, ch.r, ch.n, ch.r * p
    wide, small, group, grid = ch.specs(backward=False)
    out_shape = [jax.ShapeDtypeStruct(ch.x.shape, ch.x.dtype)]
    out_specs = [wide(width)]
    if save:
        out_shape.append(jax.ShapeDtypeStruct(
            (bsz, ch.chunks, ch.groups, n, width), jnp.float32))
        out_specs.append(small(n, width))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, p=p, save=save),
        grid=grid,
        in_specs=[wide(width), wide(n), wide(n), small(r, q), group(r, 1),
                  group(1, width)],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, width), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=kernels_interpreted(),
        name="ssd_chunked_fwd",
    )(ch.x, ch.b, ch.c, ch.drow, ch.arow, ch.skip)
    return out if save else out[0]


def _backward_call(ch: _Chunks, starts, dy):
    """``dx``, ``dB``, ``dC`` (B, S + pad, .), ``d delta`` (B, C, G, r, Q),
    and a row's and group's shares of ``dA`` (B, G, r, 1) and of ``dD`` over
    the lanes (B, G, 1, r P)."""
    from .flash_attention import kernels_interpreted

    bsz, _, heads, p = ch.shape
    q, r, n, width = ch.chunk, ch.r, ch.n, ch.r * p
    wide, small, group, grid = ch.specs(backward=True)
    f32 = jnp.float32

    def summed(rows, cols):  # over the walk: the block stays along the chunk axis
        return pl.BlockSpec((1, 1, rows, cols), lambda b, g, i: (b, g, 0, 0))

    return pl.pallas_call(
        functools.partial(_bwd_kernel, p=p),
        grid=grid,
        in_specs=[wide(width), wide(n), wide(n), small(r, q), group(r, 1),
                  group(1, width), small(n, width), wide(width)],
        out_specs=[wide(width), wide(n), wide(n), small(r, q), summed(r, 1),
                   summed(1, width)],
        out_shape=[
            jax.ShapeDtypeStruct(ch.x.shape, ch.x.dtype),
            jax.ShapeDtypeStruct(ch.b.shape, ch.b.dtype),
            jax.ShapeDtypeStruct(ch.c.shape, ch.c.dtype),
            jax.ShapeDtypeStruct(ch.drow.shape, f32),
            jax.ShapeDtypeStruct((bsz, ch.groups, r, 1), f32),
            jax.ShapeDtypeStruct((bsz, ch.groups, 1, width), f32),
        ],
        scratch_shapes=[pltpu.VMEM((n, width), f32)],
        compiler_params=_PARAMS,
        interpret=kernels_interpreted(),
        name="ssd_chunked_bwd",
    )(ch.x, ch.b, ch.c, ch.drow, ch.arow, ch.skip, starts, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _chunked_kernels(x, delta, a, b_mat, c_mat, skip, chunk):
    ch = _Chunks(x, delta, a, b_mat, c_mat, skip, chunk)
    return _forward_call(ch, save=False)[:, :x.shape[1]].reshape(x.shape)


def _chunked_kernels_fwd(x, delta, a, b_mat, c_mat, skip, chunk):
    ch = _Chunks(x, delta, a, b_mat, c_mat, skip, chunk)
    y, starts = _forward_call(ch, save=True)
    return (y[:, :x.shape[1]].reshape(x.shape),
            (x, delta, a, b_mat, c_mat, skip, starts))


def _chunked_kernels_bwd(chunk, saved, dy):
    x, delta, a, b_mat, c_mat, skip, starts = saved
    ch = _Chunks(x, delta, a, b_mat, c_mat, skip, chunk)
    bsz, s, heads, p = x.shape
    dx, db, dc, ddelta, da, dskip = _backward_call(
        ch, starts, ch.rows(dy.reshape(bsz, s, heads * p)))
    dskip = None if skip is None else jnp.sum(
        dskip.reshape(bsz, heads, p), axis=(0, 2)).astype(skip.dtype)
    return (dx[:, :s].reshape(x.shape), ch.unrow(ddelta).astype(delta.dtype),
            jnp.sum(da, axis=0).reshape(heads).astype(a.dtype),
            db[:, :s].reshape(b_mat.shape), dc[:, :s].reshape(c_mat.shape), dskip)


_chunked_kernels.defvjp(_chunked_kernels_fwd, _chunked_kernels_bwd)


def ssd_chunked(x, delta, a, b_mat, c_mat, chunk: int, skip=None,
                with_form: bool = False):
    """``y`` (B, S, H, P) of the recurrence above. ``x``: (B, S, H, P);
    ``delta``: (B, S, H), positive; ``a``: (H,), negative; ``b_mat``,
    ``c_mat``: (B, S, G, N) with H a multiple of G; ``skip``: ``D``, (H,),
    or None for no ``D x``. Any S: the tail is padded with ``delta`` 0,
    positions that neither decay nor add.

    Where :func:`ssd_kernel_eligible` the two kernels, elsewhere
    :func:`_chunked_reference`; ``with_form`` also returns which it took
    (True: the kernels), for ``Mamba2``'s ``ssm_scan_kernel`` counter."""
    heads, p = x.shape[-2:]
    groups, n = b_mat.shape[-2:]
    kernels = ssd_kernel_eligible(heads, p, groups, n, chunk)
    if kernels:
        y = _chunked_kernels(x, delta, a, b_mat, c_mat, skip, chunk)
    else:
        y = _chunked_reference(x, delta, a, b_mat, c_mat, chunk, skip=skip)
    return (y, kernels) if with_form else y


def chunk_decay_min(delta, a, chunk: int):
    """The smallest ``exp`` of a whole chunk's summed ``delta A`` over every
    head, row and chunk: how much of its state the most forgetful chunk
    hands on (0.0: float32 underflowed and that chunk forgot everything).
    The sequence's ragged tail counts as a chunk of its own length."""
    bsz, s, heads = delta.shape
    pad = -s % chunk
    log_decay = jnp.pad(delta.astype(jnp.float32) * a.astype(jnp.float32),
                        ((0, 0), (0, pad), (0, 0)))
    total = jnp.sum(log_decay.reshape(bsz, -1, chunk, heads), axis=2)
    return jnp.exp(jnp.min(total))

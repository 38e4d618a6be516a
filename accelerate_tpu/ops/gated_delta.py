"""The gated delta rule (Gated DeltaNet; Yang, Kautz & Hatamizadeh 2024): a
chunked form for a whole sequence — one Mosaic kernel on a TPU, ``jax.numpy``
elsewhere — and a one-position form for a decode step.

A value head keeps a state ``S`` of ``Dk x Dv`` in float32, zero before the
first token. Position ``t`` brings a query and a key of ``Dk`` (unit length:
L2-normalised here, the query times ``Dk ** -0.5``), a value of ``Dv``, a
write strength ``beta_t`` in (0, 1) and a log-decay ``g_t <= 0``:

    S   <- exp(g_t) S                      forget
    d_t  = beta_t (v_t - S^T k_t)          what the key reads, corrected
    S   <- S + k_t d_t^T                   write
    o_t  = S^T q_t

:func:`gated_delta_step` is those four lines over a batch of slots.

:func:`gated_delta_chunked` cuts the sequence into chunks of ``CHUNK``
positions. With ``c`` the running sum of ``g`` inside a chunk and ``S_0`` the
state the chunk starts from, the corrections ``d`` of a chunk solve one
unit lower-triangular system (the WY form of a product of Householder-like
updates):

    (I + tril(diag(beta) (K K^T * exp(c_i - c_j)), -1)) D
        = diag(beta) (V - diag(exp(c)) K S_0)

then

    O   = diag(exp(c)) Q S_0 + tril(Q K^T * exp(c_i - c_j)) D
    S_C = exp(c_C) S_0 + (diag(exp(c_C - c)) K)^T D

``c_i - c_j`` is formed before its ``exp``, so nothing overflows however fast
a head forgets.

**The kernel** (``gdn_chunked``, where :func:`chunked_kernel_eligible`): a grid
over (row, a block of value heads, chunk), the chunk axis last and sequential.
The heads' states are the kernel's second output, whose block does not move
along the chunk axis: they stay in VMEM from a row's first chunk to its last
and go to HBM once. A chunk's ``q``, ``k``, ``v`` (a key head's block serves
its value heads from one copy) and running sums are read once, ``o`` is
written once; the decay matrix, ``K K^T``, ``Q K^T`` and the system never
leave VMEM. The system is not solved against ``S_0``'s right side alone but
inverted, since the left side does not know ``S_0``: forward substitution row
by row inside its 8 x 8 diagonal blocks, then blocks of ``s`` paired into
blocks of ``2 s`` by ``[[T1, 0], [-T2 A21 T1, T2]]`` (two small products a
pair) — the same arithmetic as a triangular solve, no series.

Positions at or past a row's ``length`` (the tail of a padded bucket) are
given ``beta = 0`` and ``g = 0``: they write nothing and forget nothing, and
the state handed back is the one after the row's last real position. The
kernel computes nothing for a chunk that lies wholly past the length — its
grid step names the blocks of the row's last real chunk, resident already,
and writes its rows of ``o`` as zeros, as it does the rows past the length
inside the chunk that straddles it; the ``jax.numpy`` form computes every
chunk of the bucket and leaves what a zero write strength leaves there.

Precision: everything here is float32; the products that meet the float32
state, and the solve, run at ``Precision.HIGHEST``, in the kernel too (Mosaic
makes a float32 product of six bfloat16 passes) — on a TPU a float32 matmul
otherwise rounds its operands to bfloat16, and the state is what a request
carries for thousands of positions.

The ``jax.numpy`` form (:func:`_chunked_reference`: one ``solve_triangular``
over two right sides, ``D = U - W S_0``, the state handed on by a
``lax.scan``) is what runs off a TPU and over a mesh, the kernel's backward
pass (a ``custom_vjp`` recomputes its VJP from the inputs: a gradient is what
it was), and the tests' second reference beside the recurrence itself.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
_EXACT = jax.lax.Precision.HIGHEST
# value heads a grid step of the kernel: their chains are independent, so one
# head's matrix products fill the latency of another's
_HEADS_A_STEP = 8


def l2_normalize(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """``x / sqrt(sum x^2 + eps)`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _prepare(q, k, heads: int):
    """Unit queries (scaled) and keys, each key head repeated for the value
    heads it serves (value head ``j`` reads key head ``j // group``)."""
    group = heads // q.shape[-2]
    q = l2_normalize(q) * (q.shape[-1] ** -0.5)
    k = l2_normalize(k)
    if group > 1:
        q, k = (jnp.repeat(a, group, axis=-2) for a in (q, k))
    return q, k


def gated_delta_step(q, k, v, g, beta, state):
    """One position a slot. ``q``, ``k`` (B, Hk, Dk), ``v`` (B, Hv, Dv),
    ``g``, ``beta`` (B, Hv) float32, ``state`` (B, Hv, Dk, Dv) float32.
    Returns ``(o (B, Hv, Dv) float32, new state)``."""
    q, k = _prepare(q, k, v.shape[-2])
    v = v.astype(jnp.float32)
    state = state * jnp.exp(g)[..., None, None]
    read = jnp.einsum("bhkv,bhk->bhv", state, k, precision=_EXACT)
    delta = beta[..., None] * (v - read)
    state = state + k[..., :, None] * delta[..., None, :]
    out = jnp.einsum("bhkv,bhk->bhv", state, q, precision=_EXACT)
    return out, state


def _chunked_reference(q, k, v, g, beta, lengths: Optional[jax.Array] = None):
    """:func:`gated_delta_chunked` in ``jax.numpy``, every chunk of the
    sequence computed: with ``U`` and ``W`` one triangular solve a chunk over
    the right sides ``diag(beta) V`` and ``diag(beta exp(c)) K``, ``D = U - W
    S_0``, and the state goes from chunk to chunk by a ``lax.scan``."""
    chunk = CHUNK
    b, s, hv, dv = v.shape
    q, k = _prepare(q, k, hv)
    dk = q.shape[-1]
    v = v.astype(jnp.float32)
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    if lengths is not None:
        real = (jnp.arange(s)[None, :] < lengths[:, None])[..., None]
        g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
    pad = -s % chunk
    if pad:  # whole chunks: the added positions write and forget nothing
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (g, beta))
    n = (s + pad) // chunk

    def chunks(a):  # (B, S, H, ...) -> (N, B, H, C, ...)
        a = a.reshape(b, n, chunk, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 2, 3), 1, 0)

    q, k, v = chunks(q), chunks(k), chunks(v)
    g, beta = chunks(g), chunks(beta)  # (N, B, H, C)
    c = jnp.cumsum(g, axis=-1)
    rel = c[..., :, None] - c[..., None, :]  # c_i - c_j, before any exp
    row, col = jnp.tril_indices(chunk)
    lower = jnp.zeros((chunk, chunk), bool).at[row, col].set(True)
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, rel, 0.0)), 0.0)
    strict = lower & ~jnp.eye(chunk, dtype=bool)
    kk = jnp.einsum("nbhik,nbhjk->nbhij", k, k, precision=_EXACT)
    system = jnp.where(strict, beta[..., None] * kk * decay, 0.0) + jnp.eye(chunk)
    rhs = jnp.concatenate(
        [beta[..., None] * v, (beta * jnp.exp(c))[..., None] * k], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        system, rhs, lower=True, unit_diagonal=True)
    u, w = solved[..., :dv], solved[..., dv:]
    scores = jnp.einsum("nbhik,nbhjk->nbhij", q, k, precision=_EXACT) * decay
    q_in = q * jnp.exp(c)[..., None]  # what a query reads of the start state
    k_out = k * jnp.exp(c[..., -1:] - c)[..., None]  # a key's write, at the end
    end_decay = jnp.exp(c[..., -1])  # (N, B, H)

    def step(s0, xs):
        u_i, w_i, scores_i, q_i, k_i, decay_i = xs
        d = u_i - jnp.einsum("bhck,bhkv->bhcv", w_i, s0, precision=_EXACT)
        o = (jnp.einsum("bhck,bhkv->bhcv", q_i, s0, precision=_EXACT)
             + jnp.einsum("bhij,bhjv->bhiv", scores_i, d, precision=_EXACT))
        s1 = s0 * decay_i[..., None, None] + jnp.einsum(
            "bhck,bhcv->bhkv", k_i, d, precision=_EXACT)
        return s1, o

    state, out = jax.lax.scan(
        step, jnp.zeros((b, hv, dk, dv), jnp.float32),
        (u, w, scores, q_in, k_out, end_decay))
    out = jnp.moveaxis(jnp.moveaxis(out, 0, 1), 2, 3).reshape(b, s + pad, hv, dv)
    return out[:, :s], state


# --------------------------------------------------------------------------- #
# the chunked form as one kernel
# --------------------------------------------------------------------------- #
def chunked_kernel_eligible(dk: int, dv: int) -> bool:
    """Whether :func:`gated_delta_chunked` runs as the ``gdn_chunked`` kernel
    at key and value heads of ``dk`` and ``dv``: a TPU or
    ``kernel_interpret_mode()``, one device's rows and heads (a Mosaic kernel
    is not partitioned over a mesh; the ``jax.numpy`` form is), and heads that
    are whole lanes (128; whole sublanes of 8 under the interpreter, as
    ``fit_block`` relaxes). ONE predicate: the serving engine asks it for its
    ``gdn_kernel`` trace count."""
    from ..parallel.sharding import live_mesh
    from .flash_attention import MIN_BLOCK, kernels_interpreted

    lanes = MIN_BLOCK if kernels_interpreted() else 128
    return (
        (jax.default_backend() == "tpu" or kernels_interpreted())
        and dk % lanes == 0 and dv % lanes == 0
        and live_mesh() is None
    )


def _dot(a, b, contract=((2,), (1,))):
    """``a @ b`` a head (the leading axis of both), float32 at full precision."""
    return jax.lax.dot_general(
        a, b, (contract, ((0,), (0,))), precision=_EXACT,
        preferred_element_type=jnp.float32)


_ROWS = 8  # a float32 tile's sublanes: the diagonal blocks solved row by row


def _diagonal_block_inverse(a):
    """``(I + a_B)^-1`` of each ``_ROWS`` x ``_ROWS`` diagonal block ``a_B``
    of the strictly lower triangular ``a`` (H, C, C), as block-diagonal
    matrices: forward substitution itself, a row at a time on the vector
    unit. Row ``j`` of a block's inverse is final once the rows above it have
    been taken out of it; it is then taken out of the rows below, ``a_ij``
    times it each."""
    heads, size = a.shape[:2]
    sub = jax.lax.broadcasted_iota(jnp.int32, (heads, _ROWS, size), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (heads, _ROWS, size), 2)
    blocks = []
    for start in range(0, size, _ROWS):
        rows = a[:, start:start + _ROWS]
        t = jnp.where(lane == sub + start, 1.0, 0.0)
        for j in range(_ROWS - 1):  # a_ij is zero at and above the diagonal
            t = t - rows[:, :, start + j:start + j + 1] * t[:, j:j + 1]
        blocks.append(t)
    return jnp.concatenate(blocks, axis=1)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` of the strictly lower triangular ``a`` (H, C, C), C a
    power of two: forward substitution, rearranged. Row by row inside the
    diagonal blocks of ``_ROWS`` (:func:`_diagonal_block_inverse`); diagonal
    blocks of ``s`` then pair into blocks of ``2 s`` by ``[[T1, 0], [-T2 A21
    T1, T2]]``: two small matrix products a pair and head."""
    size = a.shape[1]
    t = _diagonal_block_inverse(a)
    s = _ROWS
    while s < size:
        pairs = [(low, low + s, low + 2 * s) for low in range(0, size, 2 * s)]
        # A21 T1 over T1's rows whole: its columns outside the block are
        # zeros, and so are the product's
        below = [_dot(a[:, mid:high, low:mid], t[:, low:mid])
                 for low, mid, high in pairs]
        t = jnp.concatenate([
            rows for (low, mid, high), a21_t1 in zip(pairs, below)
            for rows in (t[:, low:mid],
                         t[:, mid:high] - _dot(t[:, mid:high, mid:high], a21_t1))],
            axis=1)
        s *= 2
    return t


@jax.jit
def _chunk(k, q, v, ccol, crow, beta, s0):
    """One chunk of C positions over H value heads, every value (H, ...) so
    that a stage runs over all the heads before the next one starts and their
    independent chains fill one another's latency: ``k``, ``q`` (Hk, C, Dk)
    as the projections left them, ``v`` (H, C, Dv), the running sum of ``g``
    as ``ccol`` (H, C, 1) and ``crow`` (H, 1, C), ``beta`` (H, C, 1), the
    state ``s0`` (H, Dk, Dv) the chunk starts from. Returns ``(o (H, C, Dv),
    the state it ends in)``. Under ``jit`` so that the kernels of a program
    (a layer and bucket each) trace it once between them."""
    heads, chunk = v.shape[:2]
    group, dk = heads // k.shape[0], k.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower, strict = row >= col, row > col

    def a_value_head(x):  # (Hk, ...) -> (H, ...): head i reads key head i // group
        return x if group == 1 else jnp.stack(
            [x[i // group] for i in range(heads)])

    k = l2_normalize(k)
    kq = jnp.concatenate([k, l2_normalize(q) * (dk ** -0.5)], axis=1)
    kkqk = a_value_head(_dot(kq, k, ((2,), (2,))))  # K K^T over Q K^T
    k, kq = a_value_head(k), a_value_head(kq)
    # c_i - c_j before its exp: nothing overflows
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, ccol - crow, 0.0)), 0.0)
    system = jnp.where(strict, beta * kkqk[:, :chunk] * decay, 0.0)
    # the products that need no solve go first: the matrix units run them
    # while the vector unit walks the diagonal blocks' rows
    from_s0 = _dot(kq, s0)  # K S_0 over Q S_0
    solve = _unit_lower_inverse(system)
    kept = jnp.exp(ccol)  # of the start state, at each position
    d = _dot(solve, beta * (v.astype(jnp.float32) - kept * from_s0[:, :chunk]))
    o = kept * from_s0[:, chunk:] + _dot(kkqk[:, chunk:] * decay, d)
    end = ccol[:, chunk - 1:]
    # (H, 1, 1) over lanes, then over sublanes: Mosaic has no broadcast along
    # both at once
    s1 = (jnp.exp(jnp.broadcast_to(end, (heads, 1, s0.shape[-1]))) * s0
          + _dot(k * jnp.exp(end - ccol), d, ((1,), (1,))))
    return o, s1


def _chunked_kernel(len_ref, q_ref, k_ref, v_ref, ccol_ref, crow_ref, beta_ref,
                    o_ref, state_ref, *, dk, dv):
    """One row, ``heads`` value heads, one chunk. ``state_ref`` is the heads'
    state: its block does not move along the chunk axis, so it stays in VMEM
    from the row's first chunk to its last and goes to HBM once."""
    length = len_ref[pl.program_id(0)]
    n = pl.program_id(2)
    chunk, heads = q_ref.shape[1], state_ref.shape[1]

    @pl.when(n == 0)
    def _from_zero():
        state_ref[...] = jnp.zeros_like(state_ref)

    @pl.when(n * chunk < length)
    def _walk():
        every = range(heads)

        def by_head(ref, width):  # (C, heads width) -> (heads, C, width)
            return jnp.stack([ref[0, :, j * width:(j + 1) * width]
                              for j in range(ref.shape[2] // width)])

        o, state_ref[0] = _chunk(
            by_head(k_ref, dk), by_head(q_ref, dk), by_head(v_ref, dv),
            jnp.stack([ccol_ref[0, 0, 0, :, i:i + 1] for i in every]),
            jnp.stack([crow_ref[0, 0, 0, i:i + 1, :] for i in every]),
            jnp.stack([beta_ref[0, 0, 0, :, i:i + 1] for i in every]),
            state_ref[0])
        real = (n * chunk + jax.lax.broadcasted_iota(
            jnp.int32, (chunk, 1), 0)) < length
        for i in every:
            o_ref[0, :, i * dv:(i + 1) * dv] = jnp.where(real, o[i], 0.0)

    @pl.when(n * chunk >= length)
    def _no_rows():
        # a chunk wholly past the length: written once, never as
        # uninitialised memory (the gated norm and out_proj read the rows)
        o_ref[...] = jnp.zeros_like(o_ref)


@jax.jit
def _chunked_call(q, k, v, g, beta, lengths):
    """The ``gdn_chunked`` kernel over ``q``, ``k`` (B, S, Hk, Dk) and ``v``
    (B, S, Hv, Dv) as the projections left them (the kernel widens and
    normalises a chunk's heads where it reads them: no float32 copy of the
    sequence is made), ``g``, ``beta`` (B, S, Hv) and ``lengths`` (B,) int32.
    A chunk at or past a row's length is a grid step that computes nothing
    and copies nothing: its blocks are the last real chunk's, resident
    already. Under ``jit`` so that the layers of a program trace and lower
    the kernel once between them (each call keeps its own scope path)."""
    from .flash_attention import kernels_interpreted

    chunk = CHUNK
    b, s, hv, dv = v.shape
    hk, dk = q.shape[-2:]
    group = hv // hk
    # the key heads a step: all their value heads ride on one copy of q and k
    key_heads = max(n for n in range(1, hk + 1)
                    if hk % n == 0 and n * group <= max(_HEADS_A_STEP, group))
    heads, steps = key_heads * group, hk // key_heads
    real = (jnp.arange(s)[None, :] < lengths[:, None])[..., None]
    g = jnp.where(real, g.astype(jnp.float32), 0.0)
    beta = jnp.where(real, beta.astype(jnp.float32), 0.0)
    pad = -s % chunk
    n = (s + pad) // chunk

    def rows(a, width):  # (B, S, H, D) -> (B, S + pad, H D): heads along lanes
        return jnp.pad(a.reshape(b, s, width), ((0, 0), (0, pad), (0, 0)))

    def scalars(a):  # (B, S, Hv) -> (B, N, steps, C, heads)
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        return jnp.moveaxis(a.reshape(b, n, chunk, steps, heads), 3, 2)

    c = jnp.cumsum(scalars(g), axis=3)  # the running sum inside each chunk

    def last_real(lens, row):
        return jnp.maximum(jax.lax.div(lens[row] + (chunk - 1), chunk) - 1, 0)

    def wide(row, h, i, lens):
        return (row, jnp.minimum(i, last_real(lens, row)), h)

    def small(*step):
        return wide(*step) + (0, 0)

    out, state = pl.pallas_call(
        functools.partial(_chunked_kernel, dk=dk, dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, steps, n),
            in_specs=[
                pl.BlockSpec((1, chunk, key_heads * dk), wide),
                pl.BlockSpec((1, chunk, key_heads * dk), wide),
                pl.BlockSpec((1, chunk, heads * dv), wide),
                pl.BlockSpec((1, 1, 1, chunk, heads), small),
                pl.BlockSpec((1, 1, 1, heads, chunk), small),
                pl.BlockSpec((1, 1, 1, chunk, heads), small),
            ],
            out_specs=[
                pl.BlockSpec((1, chunk, heads * dv),
                             lambda row, h, i, lens: (row, i, h)),
                pl.BlockSpec((1, heads, dk, dv),
                             lambda row, h, i, lens: (row, h, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, s + pad, hv * dv), jnp.float32),
            jax.ShapeDtypeStruct((b, hv, dk, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=kernels_interpreted(),
        name="gdn_chunked",
    )(lengths.astype(jnp.int32), rows(q, hk * dk), rows(k, hk * dk),
      rows(v, hv * dv), c, jnp.swapaxes(c, 3, 4), scalars(beta))
    return out[:, :s].reshape(b, s, hv, dv), state


_chunked = jax.custom_vjp(_chunked_call)


def _chunked_fwd(q, k, v, g, beta, lengths):
    return _chunked_call(q, k, v, g, beta, lengths), (q, k, v, g, beta, lengths)


def _chunked_bwd(saved, cotangents):
    # the jax.numpy form's own gradient, recomputed from the inputs; the
    # kernel's rows past a length are zeros whatever the inputs
    *inputs, lengths = saved
    d_out, d_state = cotangents
    real = jnp.arange(d_out.shape[1])[None, :] < lengths[:, None]
    d_out = jnp.where(real[..., None, None], d_out, 0.0)
    _, pull = jax.vjp(
        lambda *a: _chunked_reference(*a, lengths=lengths), *inputs)
    return pull((d_out, d_state)) + (None,)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def gated_delta_chunked(q, k, v, g, beta, lengths: Optional[jax.Array] = None):
    """A whole sequence from a zero state. ``q``, ``k`` (B, S, Hk, Dk), ``v``
    (B, S, Hv, Dv), ``g``, ``beta`` (B, S, Hv) float32; ``lengths`` (B,):
    positions at or past it leave the state as it is (None: every position
    is real). Returns ``(o (B, S, Hv, Dv) float32, final state float32)``.

    Where :func:`chunked_kernel_eligible` the ``gdn_chunked`` kernel, whose
    rows of ``o`` at or past a length are zeros and whose gradient is the
    ``jax.numpy`` form's; elsewhere that form itself."""
    if not chunked_kernel_eligible(q.shape[-1], v.shape[-1]):
        return _chunked_reference(q, k, v, g, beta, lengths)
    if lengths is None:
        lengths = jnp.full((v.shape[0],), v.shape[1], jnp.int32)
    return _chunked(q, k, v, g, beta, lengths)

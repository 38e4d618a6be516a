"""The gated delta rule (Gated DeltaNet; Yang, Kautz & Hatamizadeh 2024), in
``jax.numpy``: a chunked form for a whole sequence and a one-position form
for a decode step.

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

The left side does not know ``S_0``, so one triangular solve a chunk over the
two right sides ``diag(beta) V`` and ``diag(beta exp(c)) K`` gives ``U`` and
``W`` with ``D = U - W S_0``; then

    O   = diag(exp(c)) Q S_0 + tril(Q K^T * exp(c_i - c_j)) D
    S_C = exp(c_C) S_0 + (diag(exp(c_C - c)) K)^T D

and the state goes from chunk to chunk by a ``lax.scan``. ``c_i - c_j`` is
formed before its ``exp``, so nothing overflows however fast a head forgets.

Positions at or past a row's ``length`` (the tail of a padded bucket) are
given ``beta = 0`` and ``g = 0``: they write nothing and forget nothing, and
the state handed back is the one after the row's last real position.

Precision: everything here is float32; the products that meet the float32
state, and the triangular solve, run at ``Precision.HIGHEST`` — on a TPU a
float32 matmul otherwise rounds its operands to bfloat16, and the state is
what a request carries for thousands of positions.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

CHUNK = 64
_EXACT = jax.lax.Precision.HIGHEST


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


def gated_delta_chunked(q, k, v, g, beta, lengths: Optional[jax.Array] = None):
    """A whole sequence from a zero state. ``q``, ``k`` (B, S, Hk, Dk), ``v``
    (B, S, Hv, Dv), ``g``, ``beta`` (B, S, Hv) float32; ``lengths`` (B,):
    positions at or past it leave the state as it is (None: every position
    is real). Returns ``(o (B, S, Hv, Dv) float32, final state float32)``."""
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

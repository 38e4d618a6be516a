"""EVA attention (EvaByte; Zheng et al., "Efficient Attention via Control
Variates", ICLR 2023): chunk summaries beside a window of exact keys and
values, under one softmax.

Positions are cut into windows of ``window`` and chunks of ``chunk``
(``window`` is whole chunks). Per KV head ``h`` with learned vectors
``mu_h``, ``phi_h`` and ``s = D ** -0.5``, chunk ``c`` leaves one summary

    kt_c = sum_j softmax_j(s * mu_h . k_j) k_j
    vt_c = sum_j softmax_j(s * (phi_h . k_j - |k_j|^2 / 2)) v_j

(``j`` over the chunk's positions, keys already roped). Query ``i`` sees the
tokens ``j <= i`` of its own window and the summaries of every chunk of an
EARLIER window: one softmax over ``s * q_i . k_j`` and ``s * q_i . kt_c``,
values ``v_j`` and ``vt_c``. Windows do not slide.

What a request at position ``n`` keeps is therefore ``window // chunk`` rows a
completed window and one row a position of the window being filled
(:class:`EvaLayout`): ``O(window + n / chunk)``. A summary has the shape of
one position's K/V row, so both kinds live in the ONE paged pool, summaries
first, and the decode step reads them through the block table as it reads any
cache (``ops/attention.paged_attention``: rule ``c <= r`` with ``r`` the
request's LAST ROW, not its position).

Three forms, one arithmetic (fp32 scores and softmax statistics everywhere;
summaries are made in fp32 and stored at the pool's dtype):

* :func:`eva_attention` — a whole sequence from position 0 (the plain forward
  pass, and a serving prefill): exact causal attention inside each window
  (the windows are a reshape of the sequence), attention over the earlier
  windows' summaries, the two merged by their log-sum-exps;
* :func:`eva_prefill_write` — what of such a call goes into the pool: the
  summaries of the windows the prompt completes and the rows of its last,
  partial window;
* :func:`eva_roll_over` — a window that a decoding request has just filled:
  its blocks of rows are read out of the pool, summarised, and the summaries
  written over the first of them: the slot's table stays as it is, and the
  rest of the window's blocks take the next window's rows.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import NEG_INF


@dataclasses.dataclass(frozen=True)
class EvaLayout:
    """Where a request's cache rows lie, by its position ``n`` (tokens
    cached so far): ``windows(n)`` completed windows of ``per_window``
    summary rows each, then ``n % window`` rows of the window being filled.
    Whole numbers or arrays alike."""

    window: int
    chunk: int
    block_size: int

    def __post_init__(self):
        if self.window % self.chunk or self.per_window % self.block_size \
                or self.window % self.block_size:
            raise ValueError(
                f"a window of {self.window} positions must be whole chunks of "
                f"{self.chunk}, and its {self.window // self.chunk} summaries "
                f"and its rows whole blocks of {self.block_size}"
            )

    @property
    def per_window(self) -> int:
        """Summary rows a completed window leaves."""
        return self.window // self.chunk

    @property
    def summary_blocks(self) -> int:
        """Blocks a completed window's summaries fill."""
        return self.per_window // self.block_size

    @property
    def window_blocks(self) -> int:
        """Blocks a full window's rows fill."""
        return self.window // self.block_size

    def rows(self, n):
        """E(n): cache rows held at position ``n``."""
        return self.per_window * (n // self.window) + n % self.window

    def blocks(self, n) -> int:
        """Blocks that hold ``rows(n)`` rows."""
        return -(-self.rows(n) // self.block_size)

    def peak_blocks(self, total: int, start: int = 0) -> int:
        """The most blocks a request holds at once on its way from position
        ``start`` to ``total``. With no window left to fill, what it holds
        at ``total``; else the larger of that and what it holds when its
        last window is full: the earlier windows as summaries beside a whole
        window of rows (whose summaries then take the window's own first
        blocks: :func:`eva_roll_over` needs no room beside them)."""
        done = total // self.window
        if done == start // self.window:
            return self.blocks(total)
        return max(
            self.blocks(total),
            self.summary_blocks * (done - 1) + self.window_blocks,
        )


@jax.named_scope("eva_summarise")
def chunk_summaries(k, v, mu, phi, *, chunk: int, scale: float):
    """``k``, ``v`` (B, S, Hkv, D), ``S`` whole chunks, keys roped; ``mu``,
    ``phi`` (Hkv, D). Returns ``kt``, ``vt`` (B, S // chunk, Hkv, D) at the
    inputs' dtype, computed in fp32 (products and sums on the vector unit,
    so no matmul precision applies)."""
    b, s, hkv, d = k.shape
    kf = k.astype(jnp.float32).reshape(b, s // chunk, chunk, hkv, d)
    vf = v.astype(jnp.float32).reshape(b, s // chunk, chunk, hkv, d)
    mu = mu.astype(jnp.float32)
    phi = phi.astype(jnp.float32)
    a = jax.nn.softmax(scale * jnp.sum(kf * mu, axis=-1), axis=2)
    kt = jnp.sum(a[..., None] * kf, axis=2)
    half_sq = 0.5 * jnp.sum(kf * kf, axis=-1)
    w = jax.nn.softmax(scale * (jnp.sum(kf * phi, axis=-1) - half_sq), axis=2)
    vt = jnp.sum(w[..., None] * vf, axis=2)
    return kt.astype(k.dtype), vt.astype(v.dtype)


def _attend_lse(q, k, v, *, scale, causal, kv_lengths=None, kernel=False):
    """Softmax attention of ``q`` (B, S, H, D) over ``k``/``v`` (B, Skv, Hkv,
    D), causal from position 0 or over the first ``kv_lengths[b]`` keys.
    Returns ``(out (B, S, H, D), lse (B, S, H) fp32)``; a query that sees no
    key gets ``out`` 0 and ``lse`` NEG_INF. ``kernel``: the flash forward
    kernel, which returns its log-sum-exp; else the same arithmetic in XLA
    (scores materialised: short sequences and the CPU)."""
    b, s, h, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    if kernel:
        from .flash_attention import (
            DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, _fwd, fit_block,
        )

        qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
        lengths = None if kv_lengths is None else kv_lengths.astype(jnp.int32)
        out, lse = _fwd(
            qt, kt, vt, lengths, scale, causal,
            fit_block(s, DEFAULT_BLOCK_Q), fit_block(skv, DEFAULT_BLOCK_K),
            None,
        )
        return jnp.swapaxes(out, 1, 2), jnp.swapaxes(lse[..., 0], 1, 2)
    g = h // hkv
    logits = jnp.einsum(
        "bqhgd,bkhd->bhgqk", q.reshape(b, s, hkv, g, d), k,
        preferred_element_type=jnp.float32,
    ) * scale
    cols = jnp.arange(skv)
    if causal:
        keep = (cols[None, :] <= jnp.arange(s)[:, None])[None]
    else:
        keep = (cols[None, :] < kv_lengths[:, None])[:, None, :]
    keep = keep[:, None, None]  # (B | 1, 1, 1, S | 1, Skv)
    logits = jnp.where(keep, logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.where(keep, jnp.exp(logits - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ) / jnp.moveaxis(l_safe, 3, 1)
    lse = (m + jnp.log(l_safe))[..., 0].reshape(b, h, s)
    return out.reshape(b, s, h, d).astype(q.dtype), jnp.swapaxes(lse, 1, 2)


def flash_eligible(q_len: int, kv_len: int, head_dim: int) -> bool:
    """Whether one attention of :func:`eva_attention` goes to the flash
    forward kernel: a TPU (or ``kernel_interpret_mode()``) and whole 128-row
    tiles. The caller knows besides whether its arrays sit on one device (a
    Mosaic kernel cannot be partitioned): its ``kernel`` argument."""
    from .flash_attention import kernels_interpreted

    return (
        (jax.default_backend() == "tpu" or kernels_interpreted())
        and q_len % 128 == 0 and kv_len % 128 == 0 and head_dim % 128 == 0
    )


def eva_attention(q, k, v, mu, phi, *, chunk: int, window: int,
                  scale: Optional[float] = None, kernel: bool = False):
    """A whole sequence from position 0: ``q`` (B, S, H, D), ``k``/``v`` (B,
    S, Hkv, D), roped. Returns ``(out (B, S, H, D), kt, vt)`` with the
    summaries of every chunk of the sequence's whole windows, (B, nC, Hkv,
    D) — nC 0 where the sequence is shorter than a window. A sequence longer
    than a window is padded to whole windows; the padding lies after every
    real position, so no real query sees it. ``kernel``: the arrays sit on
    one device, so each attention whose shape allows it
    (:func:`flash_eligible`) may take the flash forward kernel."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    scale = d ** -0.5 if scale is None else scale
    if s < window:
        with jax.named_scope("eva_window"):
            out, _ = _attend_lse(q, k, v, scale=scale, causal=True,
                                 kernel=kernel and flash_eligible(s, s, d))
        empty = jnp.zeros((b, 0, hkv, d), k.dtype)
        return out, empty, empty
    pad = -s % window
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for x in (q, k, v))
    nw = (s + pad) // window
    per_window = window // chunk
    kt, vt = chunk_summaries(k, v, mu, phi, chunk=chunk, scale=scale)

    def in_windows(x):
        return x.reshape(b * nw, window, *x.shape[2:])

    qw = in_windows(q)
    with jax.named_scope("eva_window"):
        out, lse = _attend_lse(
            qw, in_windows(k), in_windows(v), scale=scale, causal=True,
            kernel=kernel and flash_eligible(window, window, d))
    if nw > 1:
        with jax.named_scope("eva_summaries"):
            # window w sees the first w * per_window summaries; the last
            # window's are seen by none of this call's queries
            seen = (nw - 1) * per_window

            def per_query_window(x):
                return jnp.broadcast_to(
                    x[:, None, :seen], (b, nw, seen, hkv, d)
                ).reshape(b * nw, seen, hkv, d)

            lens = jnp.tile(jnp.arange(nw, dtype=jnp.int32) * per_window, b)
            out_s, lse_s = _attend_lse(
                qw, per_query_window(kt), per_query_window(vt), scale=scale,
                causal=False, kv_lengths=lens,
                kernel=kernel and flash_eligible(window, seen, d),
            )
            m = jnp.maximum(lse, lse_s)
            w_own = jnp.exp(lse - m)[..., None]
            w_sum = jnp.exp(lse_s - m)[..., None]
            out = (
                (w_own * out.astype(jnp.float32)
                 + w_sum * out_s.astype(jnp.float32)) / (w_own + w_sum)
            ).astype(q.dtype)
    return out.reshape(b, nw * window, h, d)[:, :s], kt, vt


@jax.named_scope("kv_write")
def eva_prefill_write(key_pool, value_pool, k, v, kt, vt, state, *,
                      chunk: int, window: int, layer=None):
    """What a prefill from position 0 leaves in the pools: of a prompt of
    ``state.lengths[b]`` positions inside the padded ``k``/``v`` (B, S, Hkv,
    D), the summaries ``kt``/``vt`` of the ``lengths // window`` windows it
    completes, at table rows ``0 ..``, then the rows of its last, partial
    window. Everything else (later chunks' summaries, the completed windows'
    own rows, the padding) goes to the garbage block. Pools and ``layer`` as
    :func:`..attention.paged_update`."""
    from .attention import _first_block, _flat_pool

    b, s = k.shape[:2]
    bs = state.block_size
    per_window = window // chunk
    max_blocks = state.block_table.shape[1]
    done = state.lengths // window  # (B,) completed windows
    c_idx = jnp.arange(kt.shape[1])[None, :]
    rows = [jnp.broadcast_to(c_idx, (b, kt.shape[1]))]
    valid = [c_idx < (done * per_window)[:, None]]
    # the one window that may be partial starts at done * window: take a
    # window's worth of rows from there (clamped into the call; what the
    # clamp drags in lies in a completed window and is masked out)
    take = min(window, s)
    start = jnp.minimum(done * window, s - take)
    tok = start[:, None] + jnp.arange(take)[None, :]

    def last_window(x):
        return jax.vmap(
            lambda xb, st: jax.lax.dynamic_slice_in_dim(xb, st, take, 0)
        )(x, start)

    first = (done * window)[:, None]
    rows.append((done * per_window)[:, None] + tok - first)
    valid.append(jnp.logical_and(tok >= first, tok < state.lengths[:, None]))
    rows = jnp.concatenate(rows, axis=1)
    valid = jnp.concatenate(valid, axis=1)
    tbl = jnp.clip(rows // bs, 0, max_blocks - 1)
    blocks = jnp.take_along_axis(state.block_table, tbl, axis=1)
    blocks = jnp.where(valid, blocks, 0) + _first_block(state, layer)
    bf, of = blocks.reshape(-1), (rows % bs).reshape(-1)

    def put(pool, summaries, x):
        data = jnp.concatenate([summaries, last_window(x)], axis=1)
        return _flat_pool(pool, 3).at[bf, of].set(
            data.reshape(-1, *data.shape[2:])).reshape(pool.shape)

    return put(key_pool, kt, k), put(value_pool, vt, v)


@jax.named_scope("roll_over")
def eva_roll_over(key_pool, value_pool, mu, phi, src, dst, *, chunk: int,
                  scale: float):
    """A filled window becomes its summaries: the rows of blocks ``src``
    (window // block_size of them, in order) are read out of every layer's
    pool, summarised, and written into blocks ``dst`` (per_window //
    block_size; the engine hands the first of ``src``: a layer's rows are
    read before its summaries are written). Pools (L, num_blocks, block_size, Hkv, D) with ``mu``/
    ``phi`` (L, Hkv, D), or one layer's without the leading axis; the layers
    go one at a time through a loop that carries the pools, written in
    place."""
    stacked = key_pool.ndim == 5
    if not stacked:
        key_pool, value_pool, mu, phi = (
            x[None] for x in (key_pool, value_pool, mu, phi))
    bs, hkv, d = key_pool.shape[2:]

    def body(l, pools):
        kp, vp = pools
        kt, vt = chunk_summaries(
            kp[l, src].reshape(1, -1, hkv, d), vp[l, src].reshape(1, -1, hkv, d),
            mu[l], phi[l], chunk=chunk, scale=scale,
        )
        return (kp.at[l, dst].set(kt.reshape(-1, bs, hkv, d)),
                vp.at[l, dst].set(vt.reshape(-1, bs, hkv, d)))

    key_pool, value_pool = jax.lax.fori_loop(
        0, key_pool.shape[0], body, (key_pool, value_pool))
    if not stacked:
        return key_pool[0], value_pool[0]
    return key_pool, value_pool

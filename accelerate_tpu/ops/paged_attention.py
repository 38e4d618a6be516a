"""Decode-time paged attention as a Pallas (Mosaic) TPU kernel.

One query token per slot (``S == 1``) against that slot's blocks of the
paged K/V pools, read THROUGH the block table: the kernel walks only the
blocks that hold live positions of the slot, so a decode step moves each
live K/V byte from HBM once. (The gather form of
:func:`..attention.paged_attention` reads ``max_blocks * block_size``
positions for every slot whatever ``cache_len`` is. On the v5e, 16 slots
x 1024 positions x 24 layers, that read with the GQA repeat it used to
feed was 57 % of the decode program, 32 ms a step; grouped, without the
repeat, 11 %, 2.9 ms; this kernel 2.6 %, 0.55 ms — PERF.md, PR 25.)

Layout. The pools stay as they are, ``(num_blocks, block_size, Hkv, D)``,
in HBM. One block is ``block_size * Hkv`` rows of ``D`` lanes; a chunk of
blocks copied to VMEM is a ``(tokens * Hkv, D)`` matrix whose row
``t * Hkv + h`` is token ``t`` of KV head ``h`` (pools stored heads first,
``(num_blocks, Hkv, block_size, D)``: row ``h * block_size + t`` of its
block; only the mask's two index computations differ). All ``H`` query heads are
scored against all those rows in ONE matmul, and the columns of another
KV head are masked out (``row % Hkv == head // G``) together with the
dead positions: the masked softmax over ``tokens * Hkv`` columns IS the
per-head softmax over ``tokens``, and ``P @ V`` needs no un-shuffling
because the foreign columns carry probability 0. The ``Hkv``-fold wasted
MXU work is small beside the bytes; no relayout, no strided access.

Mask rule, shared with the gather path: query at global row ``r =
cache_len`` sees column ``c`` iff ``c <= r`` (and ``c > r - window``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF, kernels_interpreted

# Rows (tokens x KV heads) copied and scored per step of the walk: at 16
# tokens x 8 KV heads a block, 8 blocks and a (H, 1024) f32 score tile.
# On the v5e (16 slots, 32/8 heads, contexts 100-500) 512 rows cost 10 %
# more time a layer and 2048 the same as 1024; a full 1024-token table
# likes 2048 (-12 %), a batch of idle slots 512 (-8 %).
CHUNK_ROWS = 1024
# Chunk buffers in VMEM: the copies run N_BUFFERS - 1 chunks ahead of the
# scoring. A third and fourth buffer measured no gain on the v5e: a step
# of the walk waits on its own chain of matmul, softmax, matmul, not on
# the DMA.
N_BUFFERS = 2


def _decode_kernel(*refs, scale: float, softcap: Optional[float],
                   windowed: bool, block_size: int, chunk: int,
                   kv_heads: int, heads_first: bool):
    if windowed:
        (table_ref, len_ref, win_ref, q_ref, k_hbm, v_hbm, o_ref,
         k_buf, v_buf, sems) = refs
    else:
        (table_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
         k_buf, v_buf, sems) = refs
        win_ref = None
    n_slots, n_heads, _ = q_ref.shape
    max_blocks = table_ref.shape[1]
    n_buf = k_buf.shape[0]
    group = n_heads // kv_heads
    blk_rows = block_size * kv_heads
    rows = chunk * blk_rows

    def live_blocks(b):
        """[lo, hi): the table entries of slot ``b`` that hold a position
        the query sees — never empty (an idle slot walks one block)."""
        last = len_ref[b]
        hi = jnp.minimum(last // block_size + 1, max_blocks)
        if not windowed:
            return jnp.int32(0), hi
        lo = jnp.maximum(last - win_ref[0] + 1, 0) // block_size
        return jnp.minimum(lo, hi - 1), hi

    def chunk_range(b):
        lo, hi = live_blocks(b)
        return lo // chunk, (hi + chunk - 1) // chunk

    def for_live_copies(b, c, buf, act):
        """``act`` on each DMA of chunk ``c`` of slot ``b`` into buffer
        ``buf``: one copy per LIVE block and pool. Starting and waiting
        walk the same list, so every started copy is waited for."""
        lo, hi = live_blocks(b)
        for i in range(chunk):
            j = c * chunk + i
            blk = table_ref[b, jnp.minimum(j, max_blocks - 1)]
            dst = pl.ds(i * blk_rows, blk_rows)

            @pl.when(jnp.logical_and(j >= lo, j < hi))
            def _():
                act(pltpu.make_async_copy(
                    k_hbm.at[blk], k_buf.at[buf, dst], sems.at[0, buf]))
                act(pltpu.make_async_copy(
                    v_hbm.at[blk], v_buf.at[buf, dst], sems.at[1, buf]))

    def issue(cursor):
        """Start the copies of the chunk the issue cursor ``(slot, chunk,
        sequence number)`` points at, if any is left, and move it on in
        walk order: the slot's next chunk, else the next slot's first."""
        b, c, k = cursor

        @pl.when(b < n_slots)
        def _():
            for_live_copies(b, c, k % n_buf, lambda cp: cp.start())

        safe_b = jnp.minimum(b, n_slots - 1)
        more = c + 1 < chunk_range(safe_b)[1]
        nxt_b = jnp.where(more, b, b + 1)
        nxt_c = jnp.where(
            more, c + 1, chunk_range(jnp.minimum(nxt_b, n_slots - 1))[0])
        return nxt_b, nxt_c, k + 1

    # a block the walk skips inside a live chunk leaves its rows as they
    # were: masked to probability 0, so they must be finite, not garbage
    k_buf[...] = jnp.zeros_like(k_buf)
    v_buf[...] = jnp.zeros_like(v_buf)
    # the copies run n_buf - 1 chunks ahead of the scoring, across slots:
    # a slot of one or two chunks would otherwise wait out a DMA's latency
    cursor = (jnp.int32(0), chunk_range(0)[0], jnp.int32(0))
    for _ in range(n_buf - 1):
        cursor = issue(cursor)

    col = jax.lax.broadcasted_iota(jnp.int32, (n_heads, rows), 1)
    if heads_first:  # row h * block_size + t of a block
        col_head = (col % blk_rows) // block_size
        col_token = (col // blk_rows) * block_size + col % block_size
    else:  # row t * Hkv + h
        col_head = col % kv_heads
        col_token = col // kv_heads
    own_head = col_head == (
        jax.lax.broadcasted_iota(jnp.int32, (n_heads, rows), 0) // group
    )

    def slot_body(b, carry):
        c_lo, c_hi = chunk_range(b)
        last = len_ref[b]
        q = q_ref[b]  # (H, D)

        def chunk_body(c, carry):
            k_seq, cursor, m_prev, l_prev, acc = carry
            cursor = issue(cursor)  # into the buffer scored last step
            buf = k_seq % n_buf
            for_live_copies(b, c, buf, lambda cp: cp.wait())
            k = k_buf[buf]  # (rows, D)
            v = v_buf[buf]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # (H, rows) f32
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            token = c * (chunk * block_size) + col_token
            keep = jnp.logical_and(own_head, token <= last)
            if windowed:
                keep = jnp.logical_and(keep, token > last - win_ref[0])
            s = jnp.where(keep, s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
            acc = acc * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return k_seq + 1, cursor, m_new, l_new, acc

        k_seq, cursor = carry
        init = (
            k_seq, cursor,
            jnp.full((n_heads, 1), NEG_INF, jnp.float32),
            jnp.zeros((n_heads, 1), jnp.float32),
            jnp.zeros(q.shape, jnp.float32),
        )
        k_seq, cursor, _, l, acc = jax.lax.fori_loop(
            c_lo, c_hi, chunk_body, init)
        o_ref[b] = (acc / l).astype(o_ref.dtype)
        return k_seq, cursor

    jax.lax.fori_loop(0, n_slots, slot_body, (jnp.int32(0), cursor))


def paged_decode_attention(
    q: jax.Array,
    key_pool: jax.Array,
    value_pool: jax.Array,
    block_table: jax.Array,
    cache_len: jax.Array,
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    window=None,
    layer=None,
    heads_first: bool = False,
) -> jax.Array:
    """``q`` (B, 1, H, D) against the blocks ``block_table`` (B,
    max_blocks) names in the pools (num_blocks, block_size, Hkv, D) — with
    ``heads_first`` (num_blocks, Hkv, block_size, D): the same rows of a
    block, each KV head's together (``ops.attention.pool_heads_first``);
    slot ``b``'s query sits at global position ``cache_len[b]`` (its own
    K/V already written there). Returns (B, 1, H, D) at ``q``'s dtype:
    fp32 scores and softmax statistics, the pools' dtype into the MXU.
    ``window``: None, an int or a traced scalar — the sliding band.
    ``layer``: the pools are the stack of every layer's, (L, num_blocks,
    ...), and this traced index names the layer: the kernel is handed the
    whole stack and a table offset into it, and copies what it always
    copied — a slice of the stack would be a copy of a layer's pool."""
    b, s, h, d = q.shape
    if s != 1:
        raise ValueError(f"paged_decode_attention is the S == 1 shape, got {s}")
    block_size, kv_heads = key_pool.shape[-3:-1]
    if heads_first:
        kv_heads, block_size = block_size, kv_heads
    if layer is not None:
        block_table = block_table + layer * key_pool.shape[-4]
    scale = scale if scale is not None else d ** -0.5
    chunk = max(1, min(CHUNK_ROWS // (block_size * kv_heads),
                       block_table.shape[1]))
    rows = chunk * block_size * kv_heads
    windowed = window is not None
    prefetch = [block_table.astype(jnp.int32), cache_len.astype(jnp.int32)]
    if windowed:
        prefetch.append(jnp.asarray(window, jnp.int32).reshape(1))
    kernel = functools.partial(
        _decode_kernel, scale=scale, softcap=softcap, windowed=windowed,
        block_size=block_size, chunk=chunk, kv_heads=kv_heads,
        heads_first=heads_first,
    )
    whole = pl.BlockSpec((b, h, d), lambda i, *refs: (0, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    pool_shape = (-1, block_size * kv_heads, d)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(1,),
            in_specs=[whole, in_hbm, in_hbm],
            out_specs=whole,
            scratch_shapes=[
                pltpu.VMEM((N_BUFFERS, rows, d), key_pool.dtype),
                pltpu.VMEM((N_BUFFERS, rows, d), value_pool.dtype),
                pltpu.SemaphoreType.DMA((2, N_BUFFERS)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=kernels_interpreted(),
        name="paged_decode",
    )(
        *prefetch, q.reshape(b, h, d),
        key_pool.reshape(pool_shape), value_pool.reshape(pool_shape),
    )
    return out.reshape(b, 1, h, d)


def _latent_kernel(table_ref, len_ref, q_ref, pool_hbm, o_ref, buf, sems, *,
                   scale: float, block_size: int, chunk: int, value_width: int):
    """One slot a grid step: its live blocks in chunks of ``chunk`` blocks,
    each copied to VMEM once (the next chunk's copies fly while this one is
    scored) and used for BOTH matmuls — all heads' scores against the rows'
    lanes, then the probabilities against the rows' first ``value_width``
    lanes."""
    b = pl.program_id(0)
    max_blocks = table_ref.shape[1]
    rows = chunk * block_size
    last = len_ref[b]
    live = jnp.minimum(last // block_size + 1, max_blocks)  # never 0
    n_chunks = (live + chunk - 1) // chunk

    def for_live_copies(c, slot, act):
        def one(i, _):
            j = c * chunk + i
            blk = table_ref[b, jnp.minimum(j, max_blocks - 1)]

            @pl.when(j < live)
            def _():
                act(pltpu.make_async_copy(
                    pool_hbm.at[blk],
                    buf.at[slot, pl.ds(pl.multiple_of(i * block_size, block_size),
                                       block_size)],
                    sems.at[slot]))
            return 0

        jax.lax.fori_loop(0, chunk, one, 0)

    # a block the walk skips inside a live chunk leaves its rows as they
    # were: masked to probability 0, so they must be finite, not garbage
    @pl.when(b == 0)
    def _():
        buf[...] = jnp.zeros_like(buf)

    for_live_copies(0, 0, lambda cp: cp.start())
    q = q_ref[0]  # (H, w)
    w = q.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], rows), 1)

    def chunk_body(c, carry):
        m_prev, l_prev, acc = carry
        slot = c % N_BUFFERS

        @pl.when(c + 1 < n_chunks)
        def _():
            for_live_copies(c + 1, (c + 1) % N_BUFFERS, lambda cp: cp.start())

        for_live_copies(c, slot, lambda cp: cp.wait())
        latent = buf[slot]  # (rows, W)
        s = jax.lax.dot_general(
            q, latent[:, :w], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (H, rows) f32
        s = jnp.where(c * rows + col <= last, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p.astype(latent.dtype), latent[:, :value_width],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    heads = q.shape[0]
    _, l, acc = jax.lax.fori_loop(0, n_chunks, chunk_body, (
        jnp.full((heads, 1), NEG_INF, jnp.float32),
        jnp.zeros((heads, 1), jnp.float32),
        jnp.zeros((heads, value_width), jnp.float32)))
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def latent_decode_attention(
    q: jax.Array,
    pool: jax.Array,
    block_table: jax.Array,
    cache_len: jax.Array,
    value_width: int,
    scale: float,
    layer=None,
) -> jax.Array:
    """Absorbed latent attention for one position a slot. ``q`` (B, 1, H, w):
    each head's query in the latent's coordinates (``ops.attention.
    latent_attention``), against the rows ``block_table`` (B, max_blocks)
    names in the latent pool (num_blocks, block_size, W >= w), ONE row a
    position for all heads; slot ``b``'s query sits at global position
    ``cache_len[b]``, its own row already written. Returns (B, 1, H,
    value_width): the softmax-weighted sums of the rows' first
    ``value_width`` lanes. Every live row is copied from HBM once and serves
    the scores and the values both; with all heads against the same rows
    nothing is masked but the dead positions. ``layer``: as
    :func:`paged_decode_attention`."""
    b, s, h, w = q.shape
    if s != 1:
        raise ValueError(f"latent_decode_attention is the S == 1 shape, got {s}")
    block_size, width = pool.shape[-2:]
    if layer is not None:
        block_table = block_table + layer * pool.shape[-3]
    # the query on whole lanes like the rows, zeros behind it
    lanes = min(-(-w // 128) * 128, width)
    if lanes > w:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, lanes - w)))
    chunk = max(1, min(CHUNK_ROWS // block_size, block_table.shape[1]))
    kernel = functools.partial(
        _latent_kernel, scale=scale, block_size=block_size, chunk=chunk,
        value_width=value_width)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, h, lanes), lambda i, *refs: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, h, value_width), lambda i, *refs: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((N_BUFFERS, chunk * block_size, width), pool.dtype),
                pltpu.SemaphoreType.DMA((N_BUFFERS,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, value_width), q.dtype),
        interpret=kernels_interpreted(),
        name="latent_decode",
    )(
        block_table.astype(jnp.int32), cache_len.astype(jnp.int32),
        q.reshape(b, h, lanes), pool.reshape(-1, block_size, width),
    )
    return out.reshape(b, 1, h, value_width)

"""Flash attention as a Pallas (Mosaic) TPU kernel — forward + backward.

This is the project's flagship "native" kernel (SURVEY.md §2.4 native-code
note: where the reference leans on cuDNN/NCCL fused kernels, the TPU build
writes Pallas). Blockwise-softmax attention computed tile-by-tile in VMEM:
O(seq) memory instead of O(seq^2) HBM traffic for the logits matrix, the
enabling kernel for long-context training.

Algorithm (Dao et al. 2022, adapted to TPU memory spaces):
  forward: for each query block, stream key/value blocks through VMEM
  keeping running row-max ``m``, row-sum ``l`` and output accumulator in
  fp32 scratch; rescale on each new max. Saves logsumexp for backward.
  backward: two passes — dq accumulates over kv blocks; dk/dv accumulate
  over q blocks — using the saved lse and delta = rowsum(dout * out).

Layout: kernels run on (batch, heads, seq, head_dim); the public wrapper
takes (batch, seq, heads, head_dim) like ops.attention. GQA is handled by
index-mapping each query head onto its kv group head — kv is never
materialized per-query-head.

Grid iteration on TPU is sequential over the trailing grid dims, so output
blocks whose index_map ignores the kv dim stay resident in VMEM across the
kv loop — that is what makes the accumulator pattern work.
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P


# jit-cache-sensitive: a function traced inside the context is never
# served to a call outside it (or the reverse)
_INTERPRET = jax.make_user_context(False)


@contextlib.contextmanager
def kernel_interpret_mode():
    """Run the Pallas kernels of ``ops/`` under the Pallas interpreter —
    same kernel code, exact semantics — so CPU tests cover them without a
    chip. This context is the ONLY thing that turns interpretation on:
    outside it every ``pallas_call`` is lowered for Mosaic, and a machine
    without a TPU fails instead of quietly interpreting. No-op on a real
    TPU backend (the kernels compile)."""
    if jax.default_backend() == "tpu":
        yield
        return
    with _INTERPRET(True):
        yield


def kernels_interpreted() -> bool:
    """Whether the caller is inside :func:`kernel_interpret_mode`: the
    ``interpret=`` of every ``pallas_call`` in ``ops/``, and the reason
    shape gates relax the Mosaic tiling rules there. ``chip_smoke.py``
    asserts it is False."""
    return bool(_INTERPRET.value)


# Measured on v5e at (B8, S1024, H32/8, D128) fwd+bwd: 1024/1024 runs ~15%
# faster than 512/512 (fewer grid steps, better MXU occupancy); the
# (bq x bk) f32 score tile at 1024^2 (4 MiB) still fits v5e VMEM. Sequences
# not divisible by the preferred block step down via fit_block, so e.g.
# S=1536 still runs flash at block 512.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
MIN_BLOCK = 8  # f32 sublane granularity; small blocks run, just slowly


def fit_block(seq: int, preferred: int):
    """Largest block <= preferred that divides ``seq`` AND is a multiple of
    the 8-row f32 sublane granularity, halving down from preferred. None
    when no aligned divisor exists (callers fall back to dense): unaligned
    blocks may run in CPU interpret mode but fail to compile or pad badly
    on real TPU Pallas."""
    b = min(preferred, seq)
    while b >= MIN_BLOCK:
        if seq % b == 0 and b % MIN_BLOCK == 0:
            return b
        b //= 2
    return None
NEG_INF = -1e30  # large-negative instead of -inf: avoids NaN from inf-inf


def _causal_mask_block(iq, ik, bq, bk, offset, window=None):
    """Boolean (bq, bk) mask for the (iq, ik) block pair: True = attend.
    ``offset = kv_len - q_len`` end-aligns the diagonal (decode: a short
    query block attends to the whole preceding kv context), matching
    ops.attention.make_causal_mask. ``window`` adds the sliding-window
    lower bound (col > row + offset - window, HF band semantics)."""
    rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    keep = cols <= rows + offset
    if window is not None:
        keep = jnp.logical_and(keep, cols > rows + offset - window)
    return keep


def _block_visible(iq, ik, bq, bk, causal: bool, offset: int = 0, kvlen=None,
                   window=None):
    """Whether block pair (iq, ik) contains any unmasked entry. ``kvlen``
    (traced scalar, padding mode) additionally skips kv blocks that sit
    entirely in the padded tail — heavily padded batches do
    proportionally less work, the flash analog of ragged attention.
    ``window`` skips kv blocks entirely BELOW the sliding band (max col
    of the block <= min row's lower bound): with it, per-query-block work
    is O(window), the block-skip machinery the banded mask rides on."""
    vis = jnp.asarray(True) if not causal else ik * bk <= iq * bq + (bq - 1) + offset
    if window is not None:
        # rows of this q block see cols in (iq*bq + offset - window,
        # iq*bq + bq - 1 + offset]; the block is dead when its last col
        # cannot exceed the smallest row's lower bound
        vis = jnp.logical_and(
            vis, (ik + 1) * bk - 1 > iq * bq + offset - window
        )
    if kvlen is not None:
        vis = jnp.logical_and(vis, ik * bk < kvlen)
    return vis


def _apply_kv_padding(s, ik, bq, bk, kvlen):
    """NEG_INF out score columns at-or-beyond the valid kv length."""
    cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(cols < kvlen, s, NEG_INF)


def _apply_masks_under_lengths(s, iq, ik, bq, bk, causal, offset, window,
                               kvlen):
    """The forward kernel's masks in the mode that carries lengths, under ONE
    ``cond``: the causal mask of :func:`_apply_causal` and the key-length
    mask of :func:`_apply_kv_padding` only on a block that straddles the
    diagonal (or the band's lower edge) or the length — a second ``cond``
    would hand the whole score tile through VMEM once more on every block
    (8.6-9.8 % of a call at 192 / 128, PERF.md section 6, PR 41). Blocks
    wholly past either never run (``_block_visible``)."""
    clear = (ik + 1) * bk <= kvlen
    if causal:
        clear = jnp.logical_and(clear, (ik + 1) * bk - 1 <= iq * bq + offset)
        if window is not None:
            clear = jnp.logical_and(
                clear, ik * bk > iq * bq + (bq - 1) + offset - window)

    def masked(s):
        cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        keep = cols < kvlen
        if causal:
            keep = jnp.logical_and(
                keep, _causal_mask_block(iq, ik, bq, bk, offset, window))
        return jnp.where(keep, s, NEG_INF)

    return jax.lax.cond(clear, lambda s: s, masked, s)


def _last_block(length, block):
    """The block that holds row ``length - 1`` (block 0 for no row)."""
    return jax.lax.div(jnp.maximum(length - 1, 0), block)


def _real_rows(lens_ref, qlens_ref, b):
    """How many of batch row ``b``'s queries the forward kernel computes: its
    query length, and none where it has no key (every query then sees
    nothing, and is skipped to the same zeros)."""
    return jnp.where(lens_ref[b] > 0, qlens_ref[b], 0)


def _apply_causal(s, iq, ik, bq, bk, offset, window=None):
    """Mask only when the block straddles the diagonal (or the band's
    lower edge); interior blocks skip the iota/compare/where entirely
    (attention here is VPU-bound — the mask is ~30% of the vector work,
    needed on ~1/nk of blocks)."""
    fully_visible = (ik + 1) * bk - 1 <= iq * bq + offset
    if window is not None:
        # also fully inside the band: hardest at (max row, min col)
        fully_visible = jnp.logical_and(
            fully_visible, ik * bk > iq * bq + (bq - 1) + offset - window
        )
    return jax.lax.cond(
        fully_visible,
        lambda s: s,
        lambda s: jnp.where(
            _causal_mask_block(iq, ik, bq, bk, offset, window), s, NEG_INF
        ),
        s,
    )


# ---------------------------------------------------------------------- #
# forward
# ---------------------------------------------------------------------- #
def _fwd_kernel(*refs, scale: float, causal: bool, block_q: int,
                block_k: int, offset: int, padded: bool, window,
                rows: bool):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    # the prefetched lengths come first: the keys', then the queries'
    lens = refs[:padded + rows]
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs[len(lens):]
    kvlen = lens[0][pl.program_id(0)] if padded else None
    qlen = live = None
    if rows:  # a q block wholly past the query length runs nothing
        qlen = _real_rows(*lens, pl.program_id(0))
        live = iq * block_q < qlen

    def _and_live(when):
        return when if live is None else jnp.logical_and(when, live)

    @pl.when(_and_live(ik == 0))
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # block is fully masked out when the q block sits above the diagonal
    # or entirely inside the padded kv tail
    run = _block_visible(iq, ik, block_q, block_k, causal, offset, kvlen,
                         window)

    @pl.when(_and_live(run))
    def _body():
        # matmul inputs stay in the native (bf16) dtype — the MXU multiplies
        # bf16 at full rate with fp32 accumulation; upcasting inputs to f32
        # would quarter the matmul throughput
        q = q_ref[0, 0]  # (bq, d)
        k = k_ref[0, 0]  # (bk, d)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk) f32
        if padded:
            s = _apply_masks_under_lengths(
                s, iq, ik, block_q, block_k, causal, offset, window, kvlen)
        elif causal:
            s = _apply_causal(s, iq, ik, block_q, block_k, offset, window)
        m_prev = m_scr[:, 0:1]  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        if (padded and not (rows and window is None)) or (causal and offset < 0):
            # Rows fully masked within a *visible* block keep m_new ==
            # NEG_INF and exp(s - m_new) would be 1 everywhere — force p
            # (and hence l, acc) to 0 so _finish emits zero output, not
            # mean-of-v. Happens when the causal diagonal crosses
            # mid-block with q_len > kv_len, or (padding mode) when
            # kvlen == 0. Without either, every row sees >= 1 column and
            # the guard is compiled out of the hot path — as it is under a
            # query length (causal from row 0, and a q block runs only
            # where there is a key): every row that is walked sees column 0.
            p = jnp.where(m_new <= NEG_INF * 0.5, 0.0, jnp.exp(s - m_new))
        else:
            p = jnp.exp(s - m_new)  # (bq, bk) f32
        corr = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_new = l_scr[:, 0:1] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:, 0:1] = m_new
        l_scr[:, 0:1] = l_new

    @pl.when(_and_live(ik == nk - 1))
    def _finish():
        l = l_scr[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        out = acc_scr[:] / l_safe
        if rows:
            # the rows past the length in the block that straddles it: they
            # were walked beside the real ones, and read like the skipped
            real = (iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)) < qlen
            out = jnp.where(real, out, 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)
        lse = m_scr[:, 0:1] + jnp.log(l_safe)
        if rows:
            lse = jnp.where(real, lse, NEG_INF)
        # lse broadcast into the 128-lane dim (TPU min tile; see out_shape)
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])

    if rows:
        @pl.when(jnp.logical_and(ik == nk - 1, jnp.logical_not(live)))
        def _no_rows():
            # a q block wholly past the length: written once, never as
            # uninitialised memory (o_proj, a router, kv_write read the rows)
            o_ref[0, 0] = jnp.zeros_like(o_ref[0, 0])
            lse_ref[0, 0] = jnp.full_like(lse_ref[0, 0], NEG_INF)


def _fwd_index_maps(bq, bk, g, offset, causal, padded, rows):
    """The q and the k / v ``index_map`` of the forward grid ``(b, h, iq,
    ik)``. ``*refs`` absorbs the scalar-prefetch refs PrefetchScalarGridSpec
    appends to every index_map call in padding mode: the key lengths, then
    the query lengths where there are any. Without lengths every step names
    its own block."""

    def q_index(b, h, iq, ik, *refs):
        if rows:  # a skipped q block names the last real one: no copy
            iq = jnp.minimum(iq, _last_block(_real_rows(*refs, b), bq))
        return (b, h, iq, 0)

    def kv_index(b, h, iq, ik, *refs):
        if padded:
            # a step that computes nothing names the last block its q block
            # sees — the lower of the causal diagonal and the length —, which
            # is resident already: consecutive skipped steps copy nothing
            last = _last_block(refs[0][b], bk)
            if rows:
                qlen = _real_rows(*refs, b)
                skipped = iq * bq >= qlen
                iq = jnp.minimum(iq, _last_block(qlen, bq))
            if causal:
                last = jnp.minimum(last, jax.lax.div(
                    jnp.maximum(iq * bq + (bq - 1) + offset, 0), bk))
            ik = jnp.minimum(ik, last)
            if rows:
                ik = jnp.where(skipped, last, ik)
        return (b, h // g, ik, 0)

    return q_index, kv_index


def _fwd(q, k, v, lengths, scale, causal, block_q, block_k, window,
         q_lengths=None):
    """``lengths`` (B,) int32 or None: keys ``[0, len)`` are valid, and a grid
    step whose kv block lies wholly past them (or past the causal diagonal)
    computes nothing and copies nothing — its k / v block is the one already
    resident. ``q_lengths`` (B,) beside it (causal, ``S == Skv``): queries
    ``[0, len)`` are real; the rest come out as zeros with a log-sum-exp of
    ``NEG_INF``, and a q block wholly past the length costs one write."""
    B, H, S, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    # the value's width, and the output's: the score's (q and k) except where
    # a layer's keys carry more than its values do (latent attention: 192 /
    # 128); the forward pass alone is written for that
    Dv = v.shape[3]
    g = H // Hkv
    bq, bk = min(block_q, S), min(block_k, Skv)
    nq, nk = pl.cdiv(S, bq), pl.cdiv(Skv, bk)
    padded = lengths is not None
    rows = q_lengths is not None
    offset = Skv - S

    q_index, kv_index = _fwd_index_maps(bq, bk, g, offset, causal, padded, rows)
    in_specs = [
        pl.BlockSpec((1, 1, bq, D), q_index),
        pl.BlockSpec((1, 1, bk, D), kv_index),
        pl.BlockSpec((1, 1, bk, Dv), kv_index),
    ]
    out_specs = [
        pl.BlockSpec((1, 1, bq, Dv), lambda b, h, iq, ik, *refs: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, bq, 128), lambda b, h, iq, ik, *refs: (b, h, iq, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B, H, S, Dv), q.dtype),
        jax.ShapeDtypeStruct((B, H, S, 128), jnp.float32),
    ]
    scratch_shapes = [
        pltpu.VMEM((bq, 128), jnp.float32),
        pltpu.VMEM((bq, 128), jnp.float32),
        pltpu.VMEM((bq, Dv), jnp.float32),
    ]
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        offset=offset, padded=padded, window=window, rows=rows,
    )
    prefix = ((lengths,) if padded else ()) + ((q_lengths,) if rows else ())
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefix),
            grid=(B, H, nq, nk),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch_shapes,
        ),
        out_shape=out_shape,
        interpret=kernels_interpreted(),
        name="flash_fwd",
    )(*(prefix + (q, k, v)))
    return out, lse


# ---------------------------------------------------------------------- #
# backward
# ---------------------------------------------------------------------- #
def _bwd_dq_kernel(*refs, scale, causal, block_q, block_k, offset, padded,
                   window):
    if padded:
        (lens_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, acc_scr) = refs
        kvlen = lens_ref[pl.program_id(0)]
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_scr = refs
        kvlen = None
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = _block_visible(iq, ik, block_q, block_k, causal, offset, kvlen,
                         window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, 0:1]  # (bq, 1)
        delta = delta_ref[0, 0][:, 0:1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            s = _apply_causal(s, iq, ik, block_q, block_k, offset, window)
        if padded:
            s = _apply_kv_padding(s, ik, block_q, block_k, kvlen)
        if padded or (causal and offset < 0):
            # fully-masked query rows store lse=NEG_INF in forward;
            # exp(NEG_INF - NEG_INF) = 1 would fabricate gradients for rows
            # whose output is correctly zero — force p to 0 there
            # (compiled out when unpadded with offset >= 0: no row can be
            # fully masked)
            p = jnp.where(lse <= NEG_INF * 0.5, 0.0, jnp.exp(s - lse))
        else:
            p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        acc_scr[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0, 0] = acc_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, block_q, block_k, group, offset,
                    padded, window):
    # grid: (B, Hkv, n_kv, G, n_q) — dk/dv blocks live across (G, n_q)
    if padded:
        (lens_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        kvlen = lens_ref[pl.program_id(0)]
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        kvlen = None
    ik = pl.program_id(2)
    ig, iq = pl.program_id(3), pl.program_id(4)
    ng, nq = pl.num_programs(3), pl.num_programs(4)

    @pl.when((iq == 0) & (ig == 0))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = _block_visible(iq, ik, block_q, block_k, causal, offset, kvlen,
                         window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]  # (bq, d)
        k = k_ref[0, 0]  # (bk, d)
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, 0:1]
        delta = delta_ref[0, 0][:, 0:1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            s = _apply_causal(s, iq, ik, block_q, block_k, offset, window)
        if padded:
            s = _apply_kv_padding(s, ik, block_q, block_k, kvlen)
        if padded or (causal and offset < 0):
            # see _bwd_dq_kernel: zero fully-masked rows (lse == NEG_INF)
            p = jnp.where(lse <= NEG_INF * 0.5, 0.0, jnp.exp(s - lse))
        else:
            p = jnp.exp(s - lse)  # (bq, bk) f32
        pc = p.astype(do.dtype)
        dv_scr[:] += jax.lax.dot_general(
            pc, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bk, d)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta) * scale).astype(q.dtype)  # (bq, bk)
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bk, d)

    @pl.when((iq == nq - 1) & (ig == ng - 1))
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(scale, causal, block_q, block_k, window, res, dout):
    q, k, v, lengths, out, lse = res
    B, H, S, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    g = H // Hkv
    bq, bk = min(block_q, S), min(block_k, Skv)
    nq, nk = pl.cdiv(S, bq), pl.cdiv(Skv, bk)
    padded = lengths is not None

    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, 128))

    dq_in_specs = [
        pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik, *refs: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik, *refs, g=g: (b, h // g, ik, 0)),
        pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik, *refs, g=g: (b, h // g, ik, 0)),
        pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik, *refs: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, bq, 128), lambda b, h, iq, ik, *refs: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, bq, 128), lambda b, h, iq, ik, *refs: (b, h, iq, 0)),
    ]
    dq_out_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik, *refs: (b, h, iq, 0))
    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        offset=Skv - S, padded=padded, window=window,
    )
    dq_scratch = [pltpu.VMEM((bq, D), jnp.float32)]
    prefix = (lengths,) if padded else ()
    dq = pl.pallas_call(
        dq_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 if padded else 0,
            grid=(B, H, nq, nk),
            in_specs=dq_in_specs,
            out_specs=dq_out_spec,
            scratch_shapes=dq_scratch,
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=kernels_interpreted(),
        name="flash_bwd_dq",
    )(*(prefix + (q, k, v, dout, lse, delta)))

    dkv_in_specs = [
        pl.BlockSpec((1, 1, bq, D), lambda b, hk, ik, ig, iq, *refs, g=g: (b, hk * g + ig, iq, 0)),
        pl.BlockSpec((1, 1, bk, D), lambda b, hk, ik, ig, iq, *refs: (b, hk, ik, 0)),
        pl.BlockSpec((1, 1, bk, D), lambda b, hk, ik, ig, iq, *refs: (b, hk, ik, 0)),
        pl.BlockSpec((1, 1, bq, D), lambda b, hk, ik, ig, iq, *refs, g=g: (b, hk * g + ig, iq, 0)),
        pl.BlockSpec((1, 1, bq, 128), lambda b, hk, ik, ig, iq, *refs, g=g: (b, hk * g + ig, iq, 0)),
        pl.BlockSpec((1, 1, bq, 128), lambda b, hk, ik, ig, iq, *refs, g=g: (b, hk * g + ig, iq, 0)),
    ]
    dkv_out_specs = [
        pl.BlockSpec((1, 1, bk, D), lambda b, hk, ik, ig, iq, *refs: (b, hk, ik, 0)),
        pl.BlockSpec((1, 1, bk, D), lambda b, hk, ik, ig, iq, *refs: (b, hk, ik, 0)),
    ]
    dkv_out_shape = [
        jax.ShapeDtypeStruct(k.shape, k.dtype),
        jax.ShapeDtypeStruct(v.shape, v.dtype),
    ]
    dkv_scratch = [
        pltpu.VMEM((bk, D), jnp.float32),
        pltpu.VMEM((bk, D), jnp.float32),
    ]
    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        group=g, offset=Skv - S, padded=padded, window=window,
    )
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 if padded else 0,
            grid=(B, Hkv, nk, g, nq),
            in_specs=dkv_in_specs,
            out_specs=dkv_out_specs,
            scratch_shapes=dkv_scratch,
        ),
        out_shape=dkv_out_shape,
        interpret=kernels_interpreted(),
        name="flash_bwd_dkv",
    )(*(prefix + (q, k, v, dout, lse, delta)))
    return dq, dk, dv, None


# ---------------------------------------------------------------------- #
# public wrapper with custom VJP
# ---------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, lengths, q_lengths, scale, causal, block_q, block_k,
           window):
    out, _ = _fwd(q, k, v, lengths, scale, causal, block_q, block_k, window,
                  q_lengths)
    return out

def _flash_fwd(q, k, v, lengths, q_lengths, scale, causal, block_q, block_k,
               window):
    if v.shape[-1] != q.shape[-1]:
        raise NotImplementedError(
            f"flash attention at a score width {q.shape[-1]} and a value width "
            f"{v.shape[-1]} that differ is the forward pass alone: the "
            "backward kernels take one head_dim")
    out, lse = _fwd(q, k, v, lengths, scale, causal, block_q, block_k, window,
                    q_lengths)
    # the backward kernels take no query length: a row past it left the
    # forward pass with lse == NEG_INF, which zeroes its p in both of them
    return out, (q, k, v, lengths, out, lse)

def _flash_bwd(scale, causal, block_q, block_k, window, res, dout):
    return _bwd(scale, causal, block_q, block_k, window, res, dout) + (None,)

_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    scale: Optional[float] = None,
    causal: bool = True,
    kv_lengths: Optional[jax.Array] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    window: Optional[int] = None,
    q_lengths: Optional[jax.Array] = None,
) -> jax.Array:
    """Flash attention, (batch, seq, heads, head_dim) layout, GQA-aware.
    ``v`` may be narrower or wider a head than ``q`` and ``k`` (forward only):
    the result has ``v``'s width.

    ``window`` (requires ``causal``): the Mistral/Qwen2 sliding-window
    band — query row r sees keys (r - window, r], HF semantics. kv blocks
    entirely below the band are SKIPPED in forward and both backward
    passes (the same block-skip machinery as the causal upper triangle),
    so compute scales with S*window instead of S^2/2.

    ``causal=False`` runs full bidirectional attention (the BERT-family
    encoder path). ``kv_lengths`` (B,) int32 marks keys ``[0, len)`` valid
    per batch row — the right-padding convention of every HF tokenizer
    (reference examples/nlp_example.py:83-96 collate) — and masks the rest;
    kv blocks entirely inside the padded tail are skipped, so heavily
    padded batches do proportionally less work. Queries in the padded tail
    still compute (their outputs are garbage); mask them downstream in
    pooling/loss exactly as with a dense attention mask over keys.

    ``q_lengths`` (B,) int32 beside ``kv_lengths`` (causal self-attention
    only) marks queries ``[0, len)`` real — a prompt in a padded bucket. The
    rows past it are ZEROS (their log-sum-exp ``NEG_INF``, their gradients
    zero), and the forward kernel computes and copies nothing for a q block
    that lies wholly past the length: the work is the real rows' half-square.

    Blocks adapt downward to divide the sequence (1024 -> 512 -> 256 -> 128
    steps), so any multiple of 128 works; non-contiguous key masks need the
    xla path.
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if window is not None:
        if not causal:
            raise ValueError("sliding window requires causal attention")
        window = int(window)
        if window <= 0:
            raise ValueError(f"window must be positive, got {window}")
    bq = fit_block(q.shape[1], block_q)
    bk = fit_block(k.shape[1], block_k)
    if bq is None or bk is None:
        raise ValueError(
            f"flash_attention needs seq divisible by a block size >= "
            f"{MIN_BLOCK}: q seq {q.shape[1]}, kv seq {k.shape[1]}"
        )
    if q_lengths is not None and not (
            causal and kv_lengths is not None and q.shape[1] == k.shape[1]):
        raise ValueError(
            "q_lengths needs causal self-attention and kv_lengths beside it")
    lengths = []
    for name, given in (("kv_lengths", kv_lengths), ("q_lengths", q_lengths)):
        if given is None:
            continue
        if given.shape != (q.shape[0],):
            raise ValueError(
                f"{name} must be shape ({q.shape[0]},), got {given.shape}"
            )
        lengths.append(given.astype(jnp.int32))

    def local(q, k, v, kv_lengths=None, q_lengths=None):
        # (B,S,H,D) -> (B,H,S,D)
        qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
        out = _flash(qt, kt, vt, kv_lengths, q_lengths, scale, causal, bq, bk,
                     window)
        return jnp.swapaxes(out, 1, 2)

    return _over_mesh(local, q, k, v, *lengths)


def _over_mesh(local, q, k, v, *lengths):
    """Run the per-device kernel body over the live mesh. A Mosaic kernel
    cannot be partitioned by GSPMD ("Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map"): on any mesh of more
    than one device the kernel runs inside a ``shard_map`` — batch over the
    data axes, heads over ``tp``, each device on its own rows and heads
    (attention never mixes either). Whatever cannot tile an axis (the
    batch-1 init probe) is replicated instead; the sequence is never
    split here — that is ring attention's job."""
    from ..parallel.mesh import data_axes
    from ..parallel.sharding import live_mesh
    from ..utils.constants import MESH_AXIS_TENSOR

    mesh = live_mesh()
    if mesh is None:
        return local(q, k, v, *lengths)
    from ..utils.operations import nested_manual_mesh

    # inside a pipeline stage (pp already Manual) the nested shard_map is
    # built on the context mesh and manualizes the remaining axes; see
    # ops/ring_attention.py for why check_vma is on exactly there
    ctx = nested_manual_mesh()
    sm_mesh = ctx if ctx is not None else mesh
    manual = set() if ctx is None else {
        name for name, kind in zip(ctx.axis_names, ctx.axis_types)
        if kind == jax.sharding.AxisType.Manual
    }
    batch_axes = tuple(a for a in data_axes(mesh) if a not in manual)
    if q.shape[0] % math.prod(mesh.shape[a] for a in batch_axes):
        batch_axes = ()
    tp = mesh.shape[MESH_AXIS_TENSOR]
    heads = (
        MESH_AXIS_TENSOR
        if MESH_AXIS_TENSOR not in manual and tp > 1
        and q.shape[2] % tp == 0 and k.shape[2] % tp == 0
        else None
    )
    spec = P(batch_axes or None, None, heads, None)
    # the lengths ((B,) each: the keys', then the queries') split with the batch
    in_specs = (spec, spec, spec) + (P(batch_axes or None),) * len(lengths)
    return shard_map(
        local, mesh=sm_mesh, in_specs=in_specs, out_specs=spec,
        axis_names=set(mesh.axis_names) - manual,
        check_vma=ctx is not None,
    )(q, k, v, *lengths)

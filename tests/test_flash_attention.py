"""Flash-attention kernel correctness vs the XLA reference path.

On CPU the Pallas kernel runs under the Mosaic interpreter
(``force_tpu_interpret_mode``) — same kernel code, exact semantics — so CI
covers it without a chip; on a real TPU the same tests exercise the
compiled kernel.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.ops.attention import xla_attention
from accelerate_tpu.ops.flash_attention import (
    flash_attention,
    kernel_interpret_mode as _kernel_mode,
)


def _qkv(B=1, S=256, H=4, Hkv=2, D=64, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), dtype)
    return q, k, v


def _assert_grads_close(got, want, atol=2e-2):
    """Compare grad triples normalized by the reference's max magnitude."""
    for a, b in zip(got, want):
        scale = float(jnp.max(jnp.abs(b))) + 1e-6
        np.testing.assert_allclose(
            np.asarray(a) / scale, np.asarray(b) / scale, atol=atol
        )


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_xla(causal):
    q, k, v = _qkv()
    ref = xla_attention(q, k, v, causal=causal)
    with _kernel_mode():
        out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(out), atol=5e-3, rtol=1e-2
    )


def test_backward_matches_xla():
    q, k, v = _qkv(S=256)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, block_q=128, block_k=128) ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=True) ** 2)

    with _kernel_mode():  # backward kernels run here too
        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    _assert_grads_close(g1, g2)


@pytest.mark.parametrize("window", [1, 7, 64, 200, 1000])
def test_sliding_window_forward_matches_xla(window):
    """The banded causal mask (Mistral/Qwen2 sliding window, r5): the
    flash kernel's band — including block skipping below it — must match
    the dense banded oracle at windows crossing every block-geometry
    case (sub-block, block-straddling, larger-than-seq)."""
    q, k, v = _qkv(S=256)
    ref = xla_attention(q, k, v, causal=True, window=window)
    with _kernel_mode():
        out = flash_attention(
            q, k, v, causal=True, block_q=64, block_k=64, window=window
        )
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(out), atol=5e-3, rtol=1e-2
    )


@pytest.mark.parametrize("window", [7, 100])
def test_sliding_window_backward_matches_xla(window):
    """Band gradients: dq/dk/dv through both backward kernels (with their
    own block-skip predicates) vs the dense banded oracle."""
    q, k, v = _qkv(S=256)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(
                q, k, v, causal=True, block_q=64, block_k=64, window=window
            ) ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=True, window=window) ** 2)

    with _kernel_mode():
        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    _assert_grads_close(g1, g2)


def test_sliding_window_decode_alignment():
    """Decode: a short query block end-aligned on a long kv context sees
    exactly the last `window` keys at its global position."""
    rng = np.random.default_rng(3)
    S, Skv, W = 8, 128, 16
    q = jnp.asarray(rng.normal(size=(1, S, 2, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, Skv, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, Skv, 2, 64)), jnp.float32)
    ref = xla_attention(q, k, v, causal=True, window=W)
    with _kernel_mode():
        out = flash_attention(
            q, k, v, causal=True, block_q=8, block_k=64, window=W
        )
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(out), atol=5e-3, rtol=1e-2
    )


@pytest.mark.parametrize("causal", [True, False])
def test_kv_lengths_padding_matches_xla(causal):
    """Ragged right-padded batches: the flash kernel's per-row kv-length
    mask must agree with the dense key-mask oracle (VERDICT r2 missing #2
    'done' criterion). Lengths deliberately straddle block boundaries,
    include a full row and a tiny prefix."""
    from accelerate_tpu.ops.attention import lengths_to_mask

    q, k, v = _qkv(B=4, S=256, seed=3)
    lengths = jnp.asarray([256, 133, 7, 64], jnp.int32)
    ref = xla_attention(
        q, k, v, causal=causal, mask=lengths_to_mask(lengths, 256)
    )
    with _kernel_mode():
        out = flash_attention(
            q, k, v, causal=causal, kv_lengths=lengths,
            block_q=128, block_k=128,
        )
    # only rows with >= 1 visible key are comparable; with causal +
    # padding both paths zero/garbage the same *valid* region, so compare
    # the full tensor — the oracle defines it everywhere
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(out), atol=5e-3, rtol=1e-2
    )


@pytest.mark.parametrize("causal", [True, False])
def test_kv_lengths_backward_matches_xla(causal):
    """Gradients through the padding-masked kernel equal the dense-mask
    oracle, including zero grads for padded-out keys/values."""
    from accelerate_tpu.ops.attention import lengths_to_mask

    q, k, v = _qkv(B=3, S=256, seed=4)
    lengths = jnp.asarray([256, 160, 40], jnp.int32)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(
                q, k, v, causal=causal, kv_lengths=lengths,
                block_q=128, block_k=128,
            ) ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(
            xla_attention(
                q, k, v, causal=causal, mask=lengths_to_mask(lengths, 256)
            ) ** 2
        )

    with _kernel_mode():
        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    # padded-out kv positions must get exactly zero grad (k: (B,S,Hkv,D))
    np.testing.assert_array_equal(
        np.asarray(g1[1][1, 160:]), np.zeros_like(np.asarray(g1[1][1, 160:]))
    )
    _assert_grads_close(g1, g2)


def test_kv_lengths_zero_row():
    """A fully-padded row (length 0) yields zero output, not NaN."""
    q, k, v = _qkv(B=2, S=128, seed=5)
    lengths = jnp.asarray([128, 0], jnp.int32)
    with _kernel_mode():
        out = flash_attention(
            q, k, v, causal=False, kv_lengths=lengths,
            block_q=128, block_k=128,
        )
    out = np.asarray(out)
    assert np.all(np.isfinite(out))
    np.testing.assert_array_equal(out[1], np.zeros_like(out[1]))


def test_mha_no_gqa():
    q, k, v = _qkv(H=4, Hkv=4)
    ref = xla_attention(q, k, v, causal=True)
    with _kernel_mode():
        out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=5e-3, rtol=1e-2)


def test_decode_alignment_q_shorter_than_kv():
    """causal with q_len < kv_len must end-align the diagonal (a short query
    block sees the full preceding context), like make_causal_mask."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 128, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 512, 4, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 512, 4, 64)), jnp.float32)
    ref = xla_attention(q, k, v, causal=True)
    with _kernel_mode():
        out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=5e-3, rtol=1e-2)


def test_rejects_indivisible_seq():
    q, k, v = _qkv(S=192)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, block_q=128, block_k=128)


def test_causal_q_longer_than_kv_masked_rows_zero_grads():
    """ADVICE r1: with q_len > kv_len the first q_len-kv_len rows are fully
    masked; their forward output is zero and their gradients must be zero
    too (the backward previously fabricated p=1 for them)."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 128, 2, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 64, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 64, 2, 64)), jnp.float32)
    n_masked = 128 - 64

    with _kernel_mode():
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
        np.testing.assert_allclose(np.asarray(out[:, :n_masked]), 0.0)

        def loss(q, k, v):
            o = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
            return jnp.sum(o ** 2)

        dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    # masked query rows: exactly zero gradient
    np.testing.assert_allclose(np.asarray(dq[:, :n_masked]), 0.0)
    assert np.isfinite(np.asarray(dq)).all()

    # valid region must agree with the XLA oracle on the equivalent
    # end-aligned problem (q2 = last 64 queries, same kv)
    q2 = q[:, n_masked:]

    def loss_ref(q2, k, v):
        return jnp.sum(xla_attention(q2, k, v, causal=True) ** 2)

    dq2, dk2, dv2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q2, k, v)
    np.testing.assert_allclose(
        np.asarray(dq[:, n_masked:]), np.asarray(dq2), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dk2), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(dv2), rtol=2e-4, atol=2e-4)


def test_forward_masked_rows_inside_visible_block():
    """When the diagonal crosses mid-block (block_q > kv deficit), fully
    masked rows share a VISIBLE block with valid rows; their forward output
    must still be zero, not mean-of-v (review finding on the fwd kernel)."""
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(1, 128, 2, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 64, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 64, 2, 64)), jnp.float32)
    with _kernel_mode():
        # block_q=128 covers masked rows 0..63 AND valid rows 64..127
        out = flash_attention(q, k, v, causal=True, block_q=128, block_k=64)
    np.testing.assert_allclose(np.asarray(out[:, :64]), 0.0)
    ref = xla_attention(q[:, 64:], k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out[:, 64:]), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_fit_block_and_nonpow2_seq():
    """S=1536 (multiple of 512, not 1024) must still run flash with an
    adapted block (review finding: raising defaults broke such lengths)."""
    from accelerate_tpu.ops.flash_attention import MIN_BLOCK, fit_block

    assert fit_block(1536, 1024) == 512
    assert fit_block(1024, 1024) == 1024
    assert fit_block(64, 1024) == 64  # short seqs are their own block
    assert fit_block(192, 128) == 64
    assert fit_block(128, 64) == 64  # explicit small block still honored
    # unaligned seqs (not a multiple of the 8-row sublane) must fall back
    # to dense rather than hand Pallas a misaligned block
    assert fit_block(100, 1024) is None
    assert fit_block(20, 1024) is None
    assert fit_block(1001, 512) is None  # odd seq > preferred: no block
    assert fit_block(24, 1024) == 24  # aligned short seq is its own block

    q, k, v = _qkv(S=384)  # 384 = 3*128: needs the adaptive step-down
    ref = xla_attention(q, k, v, causal=True)
    with _kernel_mode():
        out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_flash_runs_per_device_over_a_live_mesh():
    """A Mosaic kernel cannot be partitioned by GSPMD, so under a live
    multi-device mesh the kernel runs inside a shard_map: batch over the
    data axes, heads over tp. Values and grads must match dense attention
    on the global arrays (kv_lengths rides along, split with the batch)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from accelerate_tpu import Accelerator, ParallelismPlugin

    acc = Accelerator(parallelism_plugin=ParallelismPlugin(
        dp_size=2, fsdp_size=2, tp_size=2,
    ))
    q, k, v = _qkv(B=4, S=128, H=4, Hkv=2, D=32)
    lens = jnp.asarray([128, 77, 128, 31], jnp.int32)
    place = lambda x, *spec: jax.device_put(x, NamedSharding(acc.mesh, P(*spec)))
    qs, ks, vs = (place(x, ("dp", "fsdp"), None, "tp") for x in (q, k, v))
    valid = (jnp.arange(128)[None, :] < lens[:, None])[:, :, None, None]

    def loss(attn, q, k, v):
        return jnp.sum(jnp.where(valid, attn(q, k, v) ** 2, 0.0))

    flash = lambda q, k, v: flash_attention(q, k, v, causal=True, kv_lengths=lens)
    dense = lambda q, k, v: xla_attention(q, k, v, causal=True, kv_lengths=lens)
    with _kernel_mode():
        out = jax.jit(flash)(qs, ks, vs)
        grads = jax.jit(jax.grad(
            lambda q, k, v: loss(flash, q, k, v), argnums=(0, 1, 2)
        ))(qs, ks, vs)
        # the batch-1 init probe cannot tile dp x fsdp: replicated, not split
        probe = jax.jit(lambda q, k, v: flash_attention(q, k, v))(
            q[:1], k[:1], v[:1]
        )
    ref = dense(q, k, v)
    np.testing.assert_allclose(
        np.asarray(jnp.where(valid, out, 0)), np.asarray(jnp.where(valid, ref, 0)),
        atol=5e-3, rtol=1e-2,
    )
    _assert_grads_close(
        grads, jax.grad(lambda q, k, v: loss(dense, q, k, v),
                        argnums=(0, 1, 2))(q, k, v),
    )
    np.testing.assert_allclose(
        np.asarray(probe), np.asarray(xla_attention(q[:1], k[:1], v[:1], causal=True)),
        atol=5e-3, rtol=1e-2,
    )
    # each device really got its own rows and heads
    assert out.sharding.is_equivalent_to(qs.sharding, out.ndim)


# --------------------------------------------------------------------------- #
# a query length beside the key length: a prompt in a padded bucket
# --------------------------------------------------------------------------- #
# S = 512 in blocks of 128: a length that straddles a block, fills one
# exactly, leaves whole q blocks empty (two, three and all four of them),
# fills the bucket, and 0
_REAL = [133, 128, 256, 40, 512, 0]


def _both_lengths(length, B=1):
    lens = jnp.full((B,), length, jnp.int32)
    return {"kv_lengths": lens, "q_lengths": lens}


def _fwd_with_lse(q, k, v, length=None, block=128):
    """The forward kernel itself, for its log-sum-exp: (out (B, S, H, Dv),
    lse (B, S, H))."""
    from accelerate_tpu.ops.flash_attention import _fwd

    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    lens = None if length is None else jnp.full((q.shape[0],), length, jnp.int32)
    with _kernel_mode():
        out, lse = _fwd(qt, kt, vt, lens, q.shape[-1] ** -0.5, True, block,
                        block, None, lens)
    return np.asarray(jnp.swapaxes(out, 1, 2)), np.asarray(
        jnp.swapaxes(lse[..., 0], 1, 2))


@pytest.mark.parametrize("widths", [(64, 64), (192, 128)], ids=["64", "192-128"])
@pytest.mark.parametrize("length", _REAL)
def test_q_lengths_real_rows_are_the_plain_calls_and_the_rest_zeros(
        length, widths):
    """Causal self-attention told how many rows are real: the real rows are
    those of the call without lengths (no real row sees a key past the
    length), the rows past it exactly zero with a log-sum-exp of NEG_INF,
    and nothing is left uninitialised — at one head_dim and at a 192-wide
    score over a 128-wide value."""
    from accelerate_tpu.ops.flash_attention import NEG_INF

    d, dv = widths
    hkv = 2 if d == dv else 4  # keys wider than values: one KV head a head
    q, k, _ = _qkv(S=512, H=4, Hkv=hkv, D=d, seed=11)
    v = _qkv(S=512, H=4, Hkv=hkv, D=dv, seed=12)[2]
    plain, plain_lse = _fwd_with_lse(q, k, v)
    out, lse = _fwd_with_lse(q, k, v, length)
    assert out.shape == (1, 512, 4, dv)
    assert np.all(np.isfinite(out)) and np.all(np.isfinite(lse))
    np.testing.assert_allclose(out[:, :length], plain[:, :length],
                               atol=5e-3, rtol=1e-2)
    np.testing.assert_allclose(lse[:, :length], plain_lse[:, :length],
                               atol=5e-3, rtol=1e-2)
    np.testing.assert_array_equal(out[:, length:], 0.0)
    np.testing.assert_array_equal(lse[:, length:], np.float32(NEG_INF))
    # the public call, and the xla path beside it: the same rows, the same zeros
    with _kernel_mode():
        public = flash_attention(q, k, v, causal=True, block_q=128,
                                 block_k=128, **_both_lengths(length))
    np.testing.assert_array_equal(np.asarray(public), out)
    ref = np.asarray(xla_attention(q, k, v, causal=True,
                                   **_both_lengths(length)))
    np.testing.assert_array_equal(ref[:, length:], 0.0)
    np.testing.assert_allclose(out, ref, atol=5e-3, rtol=1e-2)


def test_q_lengths_differ_a_row_of_the_batch():
    """Each row of a batch has its own length: every one of ``_REAL`` beside
    the others in one call."""
    q, k, v = _qkv(B=len(_REAL), S=512, seed=13)
    lens = jnp.asarray(_REAL, jnp.int32)
    with _kernel_mode():
        out = np.asarray(flash_attention(
            q, k, v, causal=True, kv_lengths=lens, q_lengths=lens,
            block_q=128, block_k=128))
        plain = np.asarray(flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128))
    for b, n in enumerate(_REAL):
        np.testing.assert_allclose(out[b, :n], plain[b, :n], atol=5e-3, rtol=1e-2)
        np.testing.assert_array_equal(out[b, n:], 0.0)


@pytest.mark.parametrize("length", [133, 256, 40, 0])
def test_q_lengths_backward_matches_xla_with_the_same_rows_zeroed(length):
    """Gradients of a call with both lengths are the oracle's with the same
    rows zeroed: the rows past the length give none, and get none."""
    q, k, v = _qkv(S=512, seed=14)

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v, causal=True, **_both_lengths(length)) ** 2)

    flash = functools.partial(flash_attention, block_q=128, block_k=128)
    with _kernel_mode():
        g1 = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(xla_attention), argnums=(0, 1, 2))(q, k, v)
    for g in g1:
        assert np.all(np.isfinite(np.asarray(g)))
        np.testing.assert_array_equal(np.asarray(g[:, length:]), 0.0)
    if length:
        _assert_grads_close(g1, g2)


def test_q_lengths_under_a_window_keep_the_fully_masked_row_guard():
    """A key length shorter than the query length under a sliding window
    leaves real rows that see no key: zeros, not the mean of v."""
    q, k, v = _qkv(S=512, seed=15)
    kv, ql = jnp.asarray([100], jnp.int32), jnp.asarray([400], jnp.int32)
    with _kernel_mode():
        out = np.asarray(flash_attention(
            q, k, v, causal=True, window=64, kv_lengths=kv, q_lengths=ql,
            block_q=128, block_k=128))
    ref = np.asarray(xla_attention(
        q, k, v, causal=True, window=64, kv_lengths=kv, q_lengths=ql))
    assert np.all(np.isfinite(out))
    np.testing.assert_allclose(out[:, :163], ref[:, :163], atol=5e-3, rtol=1e-2)
    np.testing.assert_array_equal(out[:, 163:], 0.0)  # row 163 sees (99, 163]


@pytest.mark.parametrize("case", ["alone", "bidirectional", "cross", "shape"])
def test_q_lengths_are_refused_where_they_mean_nothing(case):
    from accelerate_tpu.ops.attention import dot_product_attention

    q, k, v = _qkv(S=128)
    lens = jnp.asarray([64], jnp.int32)
    kwargs = {
        "alone": dict(causal=True, q_lengths=lens),
        "bidirectional": dict(causal=False, kv_lengths=lens, q_lengths=lens),
        "cross": dict(causal=True, kv_lengths=lens, q_lengths=lens),
        "shape": dict(causal=True, kv_lengths=lens,
                      q_lengths=jnp.asarray([64, 64], jnp.int32)),
    }[case]
    if case == "cross":
        q = q[:, :64]
    for call in (flash_attention, dot_product_attention):
        if case == "shape" and call is dot_product_attention:
            continue  # the kernel's own check
        with pytest.raises(ValueError, match="q_lengths"):
            call(q, k, v, **kwargs)


def _head_walk(length, rows, S=512, block=128):
    """One head's grid as the kernel's own predicate and index maps see it
    (concrete values): how many steps compute, how many name another k / v
    block than the step before (a copy), how many another q block."""
    from accelerate_tpu.ops.flash_attention import (
        _block_visible, _fwd_index_maps,
    )

    n = S // block
    padded = length is not None
    q_index, kv_index = _fwd_index_maps(block, block, 1, 0, True, padded, rows)
    refs = (np.asarray([length or 0], np.int32),) * (padded + rows)
    run = kv = qs = 0
    at_kv = at_q = None
    for iq in range(n):
        for ik in range(n):
            live = (not rows) or iq * block < length
            vis = bool(_block_visible(
                iq, ik, block, block, True, 0, length if padded else None))
            here_kv = int(kv_index(0, 0, iq, ik, *refs)[2])
            here_q = int(q_index(0, 0, iq, ik, *refs)[2])
            if live and vis:  # a step that runs reads its own blocks
                run += 1
                assert (here_q, here_kv) == (iq, ik)
            kv += here_kv != at_kv
            qs += here_q != at_q
            at_kv, at_q = here_kv, here_q
    return run, kv, qs


@pytest.mark.parametrize("length,rows,want", [
    # no lengths: the causal half-square, and every step copies a block
    (None, False, (10, 16, 4)),
    # the key length alone: every q block walks the real keys
    # (9 copies for 10 steps: a q block ends on the block the next begins on)
    (512, False, (10, 9, 4)), (133, False, (7, 6, 4)), (128, False, (4, 1, 4)),
    # both: the real rows' half-square, and past it nothing moves
    (512, True, (10, 9, 4)), (384, True, (6, 5, 3)), (133, True, (3, 2, 2)),
    (128, True, (1, 1, 1)), (0, True, (0, 1, 1)),
])
def test_a_step_past_the_lengths_names_the_block_already_resident(
        length, rows, want):
    """What interpret mode cannot see: a step that computes nothing copies
    nothing. With both lengths a head walks the real rows' half-square; a
    q block past the length names the blocks already there."""
    assert _head_walk(length, rows) == want


# --------------------------------------------------------------------------- #
# a call without lengths: the kernels it had
# --------------------------------------------------------------------------- #
def _mosaic_kernels(lowered_text):
    """The Mosaic kernels in a text lowered for the TPU, as MLIR WITHOUT
    locations: the serialized body also holds the source lines of
    ``ops/flash_attention.py``, which move with any edit to the file."""
    import base64
    import re

    from jax._src.lib.mlir import ir

    kernels = []
    for body in re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', lowered_text):
        with ir.Context() as ctx:
            # serialized under a versioned dialect name (``stable_mosaic``):
            # printed in the generic form, which is all a comparison needs
            ctx.allow_unregistered_dialects = True
            module = ir.Module.parse(base64.b64decode(body))
            kernels.append(module.operation.get_asm(enable_debug_info=False))
    return kernels


# sha256 of the kernels at commit 772de96 (the parent of ISSUE 41), as this
# test lowers them: what the three train cells and eva's windows run
_NO_LENGTHS = {
    "causal_grad": ("f78e0cc03dce4976", 3),
    "window_grad": ("7f1a15335f7a76bc", 3),
    "bidirectional": ("317d4304959a2ded", 1),
    "score_192_value_128": ("780f5851efd4c14f", 1),
}


@pytest.mark.parametrize("case", sorted(_NO_LENGTHS))
def test_a_call_without_lengths_lowers_to_the_kernels_it_had(case):
    """The mode that carries lengths changed (ISSUE 41); a call without them
    — ``padded=False`` — keeps its forward and backward kernels operation
    for operation."""
    import hashlib

    bf = jnp.bfloat16
    spec = lambda *shape: jax.ShapeDtypeStruct(shape, bf)  # noqa: E731
    q, k = spec(2, 2048, 32, 128), spec(2, 2048, 8, 128)
    total = lambda out: out.astype(jnp.float32).sum()  # noqa: E731
    fn, args = {
        "causal_grad": (jax.grad(lambda q, k, v: total(
            flash_attention(q, k, v)), argnums=(0, 1, 2)), (q, k, k)),
        "window_grad": (jax.grad(lambda q, k, v: total(
            flash_attention(q, k, v, window=512)), argnums=(0, 1, 2)), (q, k, k)),
        "bidirectional": (
            lambda q, k, v: flash_attention(q, k, v, causal=False), (q, k, k)),
        "score_192_value_128": (
            lambda q, k, v: flash_attention(q, k, v),
            (spec(1, 8192, 32, 192), spec(1, 8192, 32, 192), spec(1, 8192, 32, 128))),
    }[case]
    text = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    kernels = _mosaic_kernels(text)
    sha = hashlib.sha256("\n".join(kernels).encode()).hexdigest()[:16]
    assert (sha, len(kernels)) == _NO_LENGTHS[case], (sha, len(kernels))

"""Flash-attention kernel correctness vs the XLA reference path.

On CPU the Pallas kernel runs under the Mosaic interpreter
(``force_tpu_interpret_mode``) — same kernel code, exact semantics — so CI
covers it without a chip; on a real TPU the same tests exercise the
compiled kernel.
"""

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

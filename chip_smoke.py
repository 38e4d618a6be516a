#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two hot paths once, through the entry points a user calls, at the
published widths of Mistral-7B (vocab 32,000, hidden 4096, FFN 14,336, 32
heads / 8 KV heads, head_dim 128, sliding_window 4096; no width is cut, depth
is, and the cut is printed), with random weights made from a seed:

* kernels — every reachable ``pl.pallas_call`` in ``accelerate_tpu/ops`` is
  lowered, compiled by Mosaic, executed and compared with its ``jax.numpy``
  reference;
* train   — ``Accelerator`` -> ``prepare`` -> ``unified_step`` -> ``warmup``
  -> a few steps (ZeRO-3 over every chip of the host);
* hybrid  — the same path over layers of two kinds at LFM2-8B-A1B's widths:
  a conv layer, an attention layer (head_dim 64) with 8 of 32 experts held;
* serve   — ``ServingEngine`` answering more requests than it has slots (one
  engine per chip behind ``FleetRouter`` when the host has several);
* mla     — the same engine over DeepSeek-V3's layers at its widths (latent
            attention: an expanded prefill, an absorbed decode through the
            ``latent_decode`` kernel over ONE latent row a position; a dense
            layer and a layer of 16 of 256 group-limited experts): the pool
            donated and written in place, decode dispatched ahead.
* eva     — the same engine over EvaByte's layer (chunk summaries beside a
  2,048-byte window in the one pool), with windows that fill.

Everything runs in THIS one process, which holds the chip(s); the phases key
off ``jax.device_count()``. A failed assertion is an exception and a non-zero
exit — nothing is caught and summarised. Wall times are printed as set-up
facts and asserted on nowhere.

    python chip_smoke.py                # needs a TPU; exits 2 without one
    python chip_smoke.py --phases hybrid    # some phases only: no pass line
    JAX_PLATFORMS=cpu python chip_smoke.py --cpu-dry-run
                                        # rehearses the control flow at
                                        # TransformerConfig.tiny size; every
                                        # line says DRY RUN and the pass
                                        # line is never printed

On success the last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.metadata
import json
import math
import statistics
import sys
import time

SEED = 20260926
PHASES = ("kernels", "train", "hybrid", "serve", "eva", "mla")


# --------------------------------------------------------------------------- #
# sizes: the real run and its CPU rehearsal differ in numbers only
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class Sizes:
    name: str
    # model widths (never cut in the real run)
    vocab: int
    hidden: int
    ffn: int
    heads: int
    kv_heads: int
    head_dim: int
    window: int
    # train: depth cut to fit fp32 params + AdamW moments in 16 GB
    train_layers: int
    train_batch_per_chip: int
    train_seq: int
    train_steps: int
    # serve: bf16 weights + ONE KV pool must fit: the engine's programs keep
    # the pool they are given (donated, carried through the layer loop)
    serve_layers: int
    serve_slots: int
    serve_block: int
    serve_max_seq: int
    serve_requests: int
    serve_new_tokens: int
    serve_prompt_range: tuple
    # kernels
    flash_short: tuple  # (batch, seq)
    flash_long: tuple
    # (window?, kv_lengths?) pairs run per shape; "rows" for the second: the
    # query lengths beside the key lengths (a prompt in a padded bucket)
    flash_variants: tuple
    adamw_leaf: tuple


REAL = Sizes(
    name="Mistral-7B", vocab=32000, hidden=4096, ffn=14336, heads=32, kv_heads=8, head_dim=128,
    window=4096,
    train_layers=3, train_batch_per_chip=8, train_seq=1024, train_steps=6,
    serve_layers=24, serve_slots=8, serve_block=16, serve_max_seq=512,
    serve_requests=16, serve_new_tokens=32, serve_prompt_range=(17, 250),
    flash_short=(8, 1024), flash_long=(1, 8192),
    flash_variants=((False, False), (False, True), (True, False), (True, True),
                    (False, "rows")),
    adamw_leaf=(4096, 14336),
)
TINY = Sizes(
    name="TransformerConfig.tiny", vocab=1024, hidden=128, ffn=352, heads=4, kv_heads=2, head_dim=32,
    window=96,
    train_layers=2, train_batch_per_chip=2, train_seq=64, train_steps=6,
    serve_layers=2, serve_slots=2, serve_block=8, serve_max_seq=128,
    serve_requests=5, serve_new_tokens=4, serve_prompt_range=(5, 60),
    flash_short=(2, 128), flash_long=(1, 256),
    flash_variants=((False, False), (True, True), (False, "rows")),
    adamw_leaf=(128, 352),
)

# Tolerances, as normalized max error: max|x - ref| / max|ref|.
# bf16 keeps 8 mantissa bits, so one rounding is <= 2**-9 = 0.2 % relative.
# The flash kernels round p (and ds) to bf16 before the second matmul and
# the result once more, against an f32 reference on the same bf16 inputs:
# a few roundings, 2 % leaves them 2-3x headroom.
TOL_KERNEL_BF16 = 2e-2
# Paged prefill-then-decode vs one full forward round the SAME math in a
# different order (padded bucket + gathered cache vs a contiguous
# sequence) in every layer; the differences random-walk over ~2 roundings x
# L layers: sqrt(2 * 24) * 0.4 % = 2.7 % at the serve depth. 5 % of the
# largest logit bounds that without admitting a wrong cache row (which
# moves logits by O(100 %)).
TOL_LOGITS_BF16 = 5e-2
# The gated delta rule is float32 on both sides at full-precision products;
# the kernel and the jax.numpy form sum 2,048 positions in another order
# (the state is O(1)): an absolute 1e-3 is ~100 x what that leaves, and far
# under what one bfloat16 pass would (1e-2).
TOL_GDN = 1e-3


def mistral_config(sz: Sizes, **kw):
    from accelerate_tpu.models import TransformerConfig

    return TransformerConfig(
        vocab_size=sz.vocab, hidden_size=sz.hidden, intermediate_size=sz.ffn,
        num_heads=sz.heads, num_kv_heads=sz.kv_heads, head_dim=sz.head_dim,
        sliding_window=sz.window, rope_theta=10000.0, rms_norm_eps=1e-5,
        dtype="bfloat16", **kw,
    )


def nerr(x, ref) -> float:
    """Normalized max error, computed on the host in f64."""
    import numpy as np

    x = np.asarray(x, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(x - ref)) / (np.max(np.abs(ref)) + 1e-30))


def check_errors(say, name: str, errs: dict, tolerance: float) -> None:
    """Print a kernel's normalized max errors and fail on any over
    ``tolerance`` (or not finite)."""
    say(f"kernel {name}: normalized max error "
        + " ".join(f"{n}={e:.2e}" for n, e in errs.items())
        + f" (tolerance {tolerance:.0e})")
    for n, e in errs.items():
        assert math.isfinite(e) and e <= tolerance, (
            f"{name}: {n} off its reference by {e:.3e}"
        )


def timed(fn, *args):
    """(result, seconds) with the device fence INSIDE the timed region."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def live_bytes() -> int:
    import jax

    return sum(a.nbytes for a in jax.live_arrays())


# --------------------------------------------------------------------------- #
# kernel phase
# --------------------------------------------------------------------------- #
def reference_attention(q, k, v, lengths, window, chunk=1024):
    """f32 causal attention from ops.attention.xla_attention under an
    explicit mask, by query chunks so the S=8192 score matrix never exists
    whole (32 heads x 8192^2 f32 is 8 GiB); chunks rematerialize under
    grad. ``lengths`` (B,) and ``window`` are runtime values, so ONE
    compiled reference serves all four window x kv_lengths variants of a
    shape (full lengths / a window wider than the sequence mean "none")."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.ops.attention import xla_attention

    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    batch, seq, heads, dim = q.shape
    chunk = min(chunk, seq)
    cols = jnp.arange(seq)[None, None, None, :]

    @jax.checkpoint
    def rows(i):
        qc = jax.lax.dynamic_slice_in_dim(q, i * chunk, chunk, axis=1)
        r = (i * chunk + jnp.arange(chunk))[None, None, :, None]
        keep = (cols <= r) & (cols > r - window)
        keep = keep & (cols < lengths[:, None, None, None])
        return xla_attention(qc, k, v, mask=keep, causal=False)

    out = jax.lax.map(rows, jnp.arange(seq // chunk))  # (n, B, chunk, H, D)
    return jnp.moveaxis(out, 0, 1).reshape(batch, seq, heads, dim)


def run_compiled(say, name, fn, args, *, expect_mosaic, dry):
    """Lower + compile ``fn`` for ``args``, prove Mosaic compiled the
    kernels (``expect_mosaic`` custom calls in the optimized HLO — an
    interpreted pallas_call lowers to plain HLO loops and has none), run
    it once. Returns the outputs."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    if not dry:
        n = compiled.as_text().count('custom_call_target="tpu_custom_call"')
        assert n >= expect_mosaic, (
            f"{name}: {n} Mosaic custom calls in the compiled HLO, expected "
            f">= {expect_mosaic} — a kernel was interpreted or dropped"
        )
    out, run_s = timed(compiled, *args)
    say(f"kernel {name}: compile {compile_s:.2f}s run {run_s * 1e3:.1f}ms"
        + ("" if dry else f" mosaic_calls={n}"))
    return out


def flash_cases(say, sz: Sizes, dry: bool, shapes=None) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.ops.flash_attention import flash_attention

    for batch, seq in shapes or (sz.flash_short, sz.flash_long):
        rng = np.random.default_rng(SEED + seq)
        shape_q = (batch, seq, sz.heads, sz.head_dim)
        shape_kv = (batch, seq, sz.kv_heads, sz.head_dim)
        q = jnp.asarray(rng.standard_normal(shape_q), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal(shape_kv), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal(shape_kv), jnp.bfloat16)
        cot = jnp.asarray(rng.standard_normal(shape_q), jnp.float32)
        # right-padding lengths: one full row, the rest cut at random
        lens = rng.integers(seq // 4, seq, size=(batch,))
        lens[0] = seq
        lens = jnp.asarray(lens, jnp.int32)
        full = jnp.full((batch,), seq, jnp.int32)

        def masked_loss(out, lengths):
            # queries in the padded tail are garbage by contract: compare,
            # and differentiate through, valid rows only
            valid = jnp.arange(seq)[None, :] < lengths[:, None]
            return jnp.sum(jnp.where(valid[:, :, None, None],
                                     out.astype(jnp.float32), 0.0) * cot)

        ref_fwd = jax.jit(reference_attention)
        ref_bwd = jax.jit(jax.grad(
            lambda q, k, v, lengths, window: masked_loss(
                reference_attention(q, k, v, lengths, window), lengths),
            argnums=(0, 1, 2),
        ))
        for use_window, use_lengths in sz.flash_variants:
            window = sz.window if use_window else None
            kv_lengths = lens if use_lengths else None
            q_lengths = lens if use_lengths == "rows" else None
            name = (f"flash B{batch} S{seq} H{sz.heads}/{sz.kv_heads} "
                    f"D{sz.head_dim} window={window} "
                    f"kv_lengths={'yes' if kv_lengths is not None else 'no'}"
                    + (" q_lengths=yes" if q_lengths is not None else ""))
            lengths = full if kv_lengths is None else kv_lengths
            valid = (jnp.arange(seq)[None, :]
                     < lengths[:, None])[:, :, None, None]

            def kernel(q, k, v, kv_lengths=kv_lengths, window=window,
                       q_lengths=q_lengths):
                return flash_attention(
                    q, k, v, causal=True, window=window,
                    kv_lengths=kv_lengths, q_lengths=q_lengths,
                )

            out = run_compiled(say, name + " fwd", kernel, (q, k, v),
                               expect_mosaic=1, dry=dry)
            if q_lengths is not None:  # the rows past it: zeros, by contract
                assert not bool(jnp.any(jnp.where(valid, 0, out) != 0)), name
            grads = run_compiled(
                say, name + " fwd+dq+dkv",
                jax.grad(lambda q, k, v, kernel=kernel, lengths=lengths:
                         masked_loss(kernel(q, k, v), lengths),
                         argnums=(0, 1, 2)),
                (q, k, v), expect_mosaic=3, dry=dry,
            )
            ref_window = jnp.asarray(seq + 1 if window is None else window)
            with jax.default_matmul_precision("highest"):  # true f32
                out_ref = ref_fwd(q, k, v, lengths, ref_window)
                grads_ref = ref_bwd(q, k, v, lengths, ref_window)
            errs = {"out": nerr(jnp.where(valid, out, 0),
                                jnp.where(valid, out_ref, 0))}
            for gname, g, g_ref in zip(("dq", "dk", "dv"), grads,
                                       grads_ref):
                errs[gname] = nerr(g, g_ref)
            check_errors(say, name, errs, TOL_KERNEL_BF16)


def prologue_case(say, sz: Sizes, dry: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.ops.fused import (
        fused_qkv_prologue,
        prologue_reference,
        prologue_supported,
        rope_inv_freqs,
    )

    batch, seq = sz.flash_short
    assert prologue_supported(sz.heads, sz.kv_heads, sz.head_dim, batch, seq,
                              sz.hidden), "prologue shape gate refused"
    rng = np.random.default_rng(SEED + 1)
    q_cols, kv_cols = sz.heads * sz.head_dim, sz.kv_heads * sz.head_dim
    x = jnp.asarray(rng.standard_normal((batch, seq, sz.hidden)), jnp.bfloat16)
    scale = jnp.asarray(1 + 0.1 * rng.standard_normal(sz.hidden), jnp.float32)
    # fp32 master weights, as unified_step hands them to the model
    std = sz.hidden ** -0.5
    wq = jnp.asarray(std * rng.standard_normal((sz.hidden, q_cols)), jnp.float32)
    wk = jnp.asarray(std * rng.standard_normal((sz.hidden, kv_cols)), jnp.float32)
    wv = jnp.asarray(std * rng.standard_normal((sz.hidden, kv_cols)), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(seq)[None, :], (batch, seq))
    statics = dict(eps=1e-5, norm_offset=False, num_heads=sz.heads,
                   num_kv_heads=sz.kv_heads, head_dim=sz.head_dim,
                   dtype=jnp.bfloat16)

    def kernel(x, scale, wq, wk, wv, positions):
        return fused_qkv_prologue(
            x, scale, wq, wk, wv, None, None, None, positions,
            theta=10000.0, scaling=None, **statics,
        )

    name = (f"fused_qkv_prologue B{batch} S{seq} E{sz.hidden} "
            f"H{sz.heads}/{sz.kv_heads} D{sz.head_dim} fp32 weights")
    out = run_compiled(say, name, kernel, (x, scale, wq, wk, wv, positions),
                       expect_mosaic=1, dry=dry)
    inv = rope_inv_freqs(sz.head_dim, 10000.0, None)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(
            lambda *a: prologue_reference(*a, None, None, None, positions,
                                          inv, **statics)
        )(x, scale, wq, wk, wv)
    check_errors(say, name,
                 {n: nerr(o, r) for n, o, r in zip("qkv", out, ref)},
                 TOL_KERNEL_BF16)


def adamw_case(say, sz: Sizes, dry: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.ops.fused import (
        adamw_epilogue_reference,
        fused_adamw,
        maybe_fused_epilogue,
    )

    rng = np.random.default_rng(SEED + 2)
    leaf = lambda s: jnp.asarray(s * rng.standard_normal(sz.adamw_leaf),
                                 jnp.float32)
    params, grads = {"w": leaf(1.0)}, {"w": leaf(0.1)}
    opt = fused_adamw(3e-4)
    state = opt.init(params)
    # a warm state: non-zero moments, count 7
    adam = state[0]._replace(
        count=jnp.asarray(7, jnp.int32), mu={"w": leaf(0.01)},
        nu={"w": jnp.square(leaf(0.01))},
    )
    state = (adam,) + tuple(state[1:])
    clip = jnp.asarray(0.5, jnp.float32)

    def kernel(params, grads, state):
        return maybe_fused_epilogue(opt, grads, state, params,
                                    clip_scale=clip, finite=jnp.asarray(True))

    name = f"fused adamw leaf {sz.adamw_leaf[0]}x{sz.adamw_leaf[1]} fp32"
    new_params, new_state = run_compiled(
        say, name, kernel, (params, grads, state), expect_mosaic=1, dry=dry
    )
    ref_p, ref_mu, ref_nu, ref_count = jax.jit(
        lambda p, g, mu, nu, c: adamw_epilogue_reference(
            g, p, mu, nu, c, hp=opt.hyperparams, clip_scale=clip,
            finite=jnp.asarray(True), step_size=jnp.asarray(-3e-4, jnp.float32),
        )
    )(params, grads, adam.mu, adam.nu, adam.count)
    # fp32 elementwise chain: XLA:TPU and Mosaic may contract the FMAs and
    # approximate the divide/sqrt differently — a few ulp (2**-23 = 1.2e-7)
    for what, got, want in (
        ("params", new_params, ref_p), ("mu", new_state[0].mu, ref_mu),
        ("nu", new_state[0].nu, ref_nu),
    ):
        np.testing.assert_allclose(
            np.asarray(got["w"]), np.asarray(want["w"]), rtol=1e-5, atol=1e-6,
            err_msg=f"{name}: {what}",
        )
    assert int(new_state[0].count) == int(ref_count) == 8
    say(f"kernel {name}: params, mu, nu match the optax chain "
        "(rtol 1e-5, atol 1e-6)")


def paged_decode_case(say, sz: Sizes, dry: bool) -> None:
    """The decode kernel (live blocks only, straight out of the pools)
    against the gather form of the same read, at the serve shape: ragged
    contexts, one idle slot, one at the end of its table, scattered
    blocks, the sliding band on."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.ops.attention import PagedKVState, paged_attention
    from accelerate_tpu.ops.paged_attention import paged_decode_attention

    rng = np.random.default_rng(SEED + 5)
    slots, block = sz.serve_slots, sz.serve_block
    max_table = -(-sz.serve_max_seq // block)
    num_blocks = slots * max_table + 1
    pool = lambda: jnp.asarray(
        rng.standard_normal((num_blocks, block, sz.kv_heads, sz.head_dim)),
        jnp.bfloat16)
    key_pool, value_pool = pool(), pool()
    q = jnp.asarray(rng.standard_normal((slots, 1, sz.heads, sz.head_dim)),
                    jnp.bfloat16)
    cache_len = rng.integers(1, sz.serve_max_seq - 1, slots)
    cache_len[0], cache_len[-1] = 0, sz.serve_max_seq - 1
    table = np.zeros((slots, max_table), np.int32)
    ids = 1 + rng.permutation(num_blocks - 1)
    for b, n in enumerate(cache_len):
        used = int(n) // block + 1
        table[b, :used] = ids[b * max_table:b * max_table + used]
    table, cache_len = jnp.asarray(table), jnp.asarray(cache_len, jnp.int32)
    window = sz.serve_max_seq // 3

    def kernel(q, key_pool, value_pool, table, cache_len):
        return paged_decode_attention(q, key_pool, value_pool, table,
                                      cache_len, window=window)

    name = (f"paged decode bf16 {slots} slots x {sz.heads}/{sz.kv_heads} "
            f"heads, table {max_table} x {block}, window {window}")
    got = run_compiled(say, name, kernel,
                       (q, key_pool, value_pool, table, cache_len),
                       expect_mosaic=1, dry=dry)
    state = PagedKVState(  # single_device left False: the gather form
        block_table=table, cache_len=cache_len,
        lengths=jnp.ones((slots,), jnp.int32), num_blocks=num_blocks,
        block_size=block)
    want = jax.jit(
        lambda q, k, v: paged_attention(q, k, v, state, window=window)
    )(q, key_pool, value_pool)
    check_errors(say, name, {"out": nerr(got, want)}, TOL_KERNEL_BF16)


def gdn_case(say, dry: bool) -> None:
    """The chunked gated delta rule as the ``gdn_chunked`` kernel against the
    ``jax.numpy`` form of the same rule, at Qwen3-Next's heads (16 key and 32
    value heads of 128, float32 state), one row of a 2,048 bucket: a prompt
    that fills it and one that ends inside its eleventh chunk. Real rows and
    the state agree; the kernel's rows past the length are zeros."""
    import jax
    import jax.numpy as jnp
    from gdn_scan_on_chip import inputs

    from accelerate_tpu.ops import gated_delta

    hk, hv, d, width = (2, 4, 8, 256) if dry else (16, 32, 128, 2048)
    q, k, v, g, beta = inputs(width, hk, hv, d, d, SEED + 6)
    assert gated_delta.chunked_kernel_eligible(d, d)
    want_fn = jax.jit(gated_delta._chunked_reference)
    for length in (width * 700 // 2048, width):
        lengths = jnp.asarray([length], jnp.int32)
        name = (f"gdn f32 1 x {width} x {hk}/{hv} heads of {d}, "
                f"length {length}")
        o, state = run_compiled(
            say, name, gated_delta.gated_delta_chunked,
            (q, k, v, g, beta, lengths), expect_mosaic=1, dry=dry)
        want_o, want_state = want_fn(q, k, v, g, beta, lengths)
        gaps = {"o": float(jnp.max(jnp.abs(o[:, :length] - want_o[:, :length]))),
                "state": float(jnp.max(jnp.abs(state - want_state)))}
        say(f"kernel {name}: max abs difference "
            + " ".join(f"{n}={e:.2e}" for n, e in gaps.items())
            + f" (bound {TOL_GDN:.0e})")
        assert all(e <= TOL_GDN for e in gaps.values()), gaps
        assert not bool(jnp.any(o[:, length:] != 0)), "rows past the length"


def ssd_case(say, dry: bool) -> None:
    """The chunked state-space scan as the ``ssd_chunked_fwd`` /
    ``ssd_chunked_bwd`` kernels against the ``jax.numpy`` form of the same
    scan, at the heads of ``train-ssm-moe-1chip`` (64 heads of 64 in 8 groups
    of state 128, chunks of 128, bfloat16 operands), 2 rows of 2,048: ``y``
    and all six gradients within the microbenchmark's gap
    (``ssd_scan_on_chip.GAP``, each over its own largest entry)."""
    import jax.numpy as jnp
    from ssd_scan_on_chip import GAP, forms, operands, relative

    from accelerate_tpu.ops import ssd

    rows, seq, heads, p, groups, n, chunk = (
        (2, 24, 8, 4, 2, 8, 8) if dry else (2, 2048, 64, 64, 8, 128, 128))
    assert ssd.ssd_kernel_eligible(heads, p, groups, n, chunk)
    ops = operands(rows, seq, heads, p, groups, n, SEED + 7,
                   jnp.float32 if dry else jnp.bfloat16)
    both = forms(heads, p, groups, n, chunk)
    name = (f"ssd {ops[0].dtype} {rows} x {seq}, {heads} heads of {p} in "
            f"{groups} groups of state {n}")
    y = run_compiled(say, name + " fwd", both["kernel"][0], ops,
                     expect_mosaic=1, dry=dry)
    grads = run_compiled(say, name + " grad", both["kernel"][1], ops,
                         expect_mosaic=2, dry=dry)
    want_y, want = both["jnp"][0](*ops), both["jnp"][1](*ops)
    gaps = {"y": relative(y, want_y), **{
        "d" + k: relative(g, w)
        for k, g, w in zip(("x", "delta", "a", "B", "C", "D"), grads, want)}}
    say(f"kernel {name}: widest difference over the largest entry "
        + " ".join(f"{k}={e:.2e}" for k, e in gaps.items())
        + f" (bound {GAP:.0e})")
    assert all(e <= GAP for e in gaps.values()), gaps


def kernel_phase(say, sz: Sizes, dry: bool) -> None:
    import contextlib

    from accelerate_tpu.ops.flash_attention import (
        kernel_interpret_mode,
        kernels_interpreted,
    )

    # the rehearsal is the only place the interpreter is allowed
    ctx = kernel_interpret_mode() if dry else contextlib.nullcontext()
    with ctx:
        assert kernels_interpreted() == dry, "a kernel would run interpreted"
        flash_cases(say, sz, dry)
        # the hybrid stack's attention: heads of 64 (never compiled here
        # before PR 26), causal, no window, at its training shape
        flash_cases(say, dataclasses.replace(
            sz, head_dim=sz.head_dim // 2, flash_variants=((False, False),)),
            dry, shapes=((2, 128) if dry else (4, 4096),))
        prologue_case(say, sz, dry)
        adamw_case(say, sz, dry)
        paged_decode_case(say, sz, dry)
        gdn_case(say, dry)
        ssd_case(say, dry)
    say("kernel phase PASSED")


# --------------------------------------------------------------------------- #
# train phase
# --------------------------------------------------------------------------- #
def bytes_per_device(tree) -> dict:
    import jax

    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device.id] = out.get(shard.device.id, 0) + shard.data.nbytes
    return out


def train_phase(say, sz: Sizes, dry: bool) -> None:
    import jax
    import numpy as np
    import optax

    from accelerate_tpu import (
        Accelerator,
        AcceleratorState,
        DataLoader,
        GradientState,
        ParallelismPlugin,
        get_program_registry,
    )
    from accelerate_tpu.compilation import get_compile_monitor
    from accelerate_tpu.models import CausalLM, count_params

    n_dev = jax.device_count()
    cfg = mistral_config(sz, num_layers=sz.train_layers,
                         max_seq_len=sz.train_seq, remat="dots")
    say(f"train: {sz.name} widths, depth cut 32 -> {cfg.num_layers} layers "
        f"(fp32 params + AdamW moments must fit), remat=dots, "
        f"batch {sz.train_batch_per_chip}x{sz.train_seq} tokens per chip "
        f"x {n_dev} chip(s), fsdp_size=-1")
    mon = get_compile_monitor()
    cache_before = mon.snapshot()

    # --- the README quick-start path ------------------------------------ #
    acc = Accelerator(
        mixed_precision="bf16",
        parallelism_plugin=ParallelismPlugin(fsdp_size=-1),
        telemetry=True,
    )
    say("train: mesh " + str(dict(acc.mesh.shape)) + " device order "
        + str([(d.id, getattr(d, "coords", None))
               for d in acc.mesh.devices.flat]))
    model = CausalLM(cfg)
    raw, init_s = timed(lambda: model.init(
        jax.random.PRNGKey(SEED), np.zeros((1, 16), np.int32))["params"])
    rng = np.random.default_rng(SEED)
    batch_size = sz.train_batch_per_chip * n_dev
    ids = rng.integers(0, cfg.vocab_size, (batch_size, sz.train_seq))
    dataset = [{"input_ids": row.astype(np.int32)} for row in ids]
    params, opt, loader = acc.prepare(
        raw, optax.adamw(3e-4), DataLoader(dataset, batch_size=batch_size)
    )
    del raw
    step = acc.unified_step(CausalLM.loss_fn(model), max_grad_norm=1.0)
    carry = acc.init_carry(params, opt)
    n_params = count_params(params)
    del params
    say(f"train: {n_params / 1e6:.0f}M parameters, init {init_s:.1f}s")

    warm = acc.warmup(step, carry, loader)
    say(f"train: warmup compile {warm['compile_time_s']:.1f}s (backend "
        f"{warm['backend_compile_s']:.1f}s) persistent-cache "
        f"hits={warm['persistent_cache_hits']} "
        f"misses={warm['persistent_cache_misses']}")
    after_warm = mon.stats_for(step.label)

    # --- steps: the one seeded batch, once per epoch --------------------- #
    losses, times = [], []
    for _ in range(sz.train_steps):
        for batch in loader:
            t0 = time.perf_counter()
            carry, metrics = step(carry, batch)
            loss = float(jax.block_until_ready(metrics["loss"]))
            times.append(time.perf_counter() - t0)
            losses.append(loss)
    say("train: loss " + " ".join(f"{l:.4f}" for l in losses))
    say("train: step wall time "
        + " ".join(f"{t * 1e3:.0f}ms" for t in times)
        + f" (median {statistics.median(times) * 1e3:.0f}ms; set-up fact, "
          "not a benchmark)")

    # --- assertions ------------------------------------------------------ #
    assert len(losses) >= 5
    assert all(math.isfinite(l) for l in losses), losses
    # random init: the final RMSNorm hands lm_head unit-RMS activations and
    # lecun_normal gives it variance 1/hidden, so logits are ~N(0, 1) and
    # E[cross-entropy] = ln V + sigma^2 / 2 — half a nat above uniform
    expect = math.log(cfg.vocab_size) + 0.5
    assert abs(losses[0] - expect) <= 0.3, (
        f"first-step loss {losses[0]:.3f} is not ln({cfg.vocab_size}) + 1/2 "
        f"= {expect:.3f} +- 0.3, the level of a random init"
    )
    assert losses[-1] < losses[0], "loss did not fall on a repeated batch"
    retraces = acc.telemetry.detector(step.label).retraces
    assert retraces == 0, f"{retraces} retraces after warmup"
    assert step.aot_fallbacks == 0, (
        f"the warmed executable rejected {step.aot_fallbacks} call(s): the "
        "AOT->jit fallback fired and compiled the step a second time"
    )
    after_steps = mon.stats_for(step.label)
    for key in ("trace_time_s", "compile_time_s", "persistent_cache_hits",
                "persistent_cache_misses"):
        assert after_steps[key] == after_warm[key], (
            f"{key} moved after warmup ({after_warm[key]} -> "
            f"{after_steps[key]}): a real step traced or compiled"
        )
    say(f"train: {len(losses)} steps, 0 retraces, 0 AOT fallbacks, no "
        "compile after warmup")
    if not dry:
        n_mosaic = step.compiled.as_text().count(
            'custom_call_target="tpu_custom_call"'
        )
        assert n_mosaic >= 1, (
            "no Mosaic custom call in the compiled train step: auto-dispatch "
            f"routed S={sz.train_seq} attention to xla_attention"
        )
        say(f"train: compiled step holds {n_mosaic} Mosaic custom call(s) — "
            "auto-dispatch took the flash kernel")
    audit = get_program_registry().get_audit(step.label)
    assert audit is not None, f"no sharding audit of {step.label}"
    say(f"train: sharding audit clean={audit.clean}, collectives "
        f"{dict(audit.by_kind)}")
    # (the CPU partitioner of the rehearsal makes other choices — an
    # all-to-all on the logits — so only the chip's verdict is binding)
    assert dry or audit.clean, audit.to_record()
    per_dev = {
        name: bytes_per_device(carry[name]) for name in ("params", "opt_state")
    }
    for name, by_dev in per_dev.items():
        total = sum(by_dev.values())
        say(f"train: {name} bytes per device "
            + " ".join(f"d{d}={b / 2**20:.0f}MiB"
                       for d, b in sorted(by_dev.items())))
        assert len(by_dev) == n_dev, f"{name} lives on {sorted(by_dev)} only"
        # ZeRO-3: an even 1/n share each (norm scales and scalars are
        # replicated — well under 1 % of the bytes)
        for d, b in by_dev.items():
            assert abs(b / total - 1 / n_dev) <= 0.02, (
                f"{name}: device {d} holds {b / total:.1%} of the bytes, "
                f"expected {1 / n_dev:.1%}"
            )
    if n_dev > 1 and not dry:
        kinds = set(audit.by_kind)
        assert {"all-gather", "reduce-scatter"} <= kinds, (
            f"ZeRO-3 step without all-gather/reduce-scatter: {kinds}"
        )
    delta = mon.delta(cache_before)
    say(f"train: persistent cache over the phase hits="
        f"{int(delta['persistent_cache_hits'])} "
        f"misses={int(delta['persistent_cache_misses'])}")

    # --- free the chip for the engine ------------------------------------ #
    acc.telemetry.close()
    del carry, step, opt, loader, acc, metrics, batch
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    gc.collect()
    say(f"train phase PASSED; freed, {live_bytes() / 2**20:.0f}MiB live")


# --------------------------------------------------------------------------- #
# hybrid phase: layers of two kinds and a share of the experts, one chip
# --------------------------------------------------------------------------- #
def hybrid_phase(say, dry: bool) -> None:
    """One conv layer with the dense MLP and one attention layer with the
    expert layer, at LFM2-8B-A1B's published widths (hidden 2048, FFN 7168,
    expert width 1792, 32/8 heads of 64, q/k norms, 4 of 32 sigmoid-routed
    experts a token) with one chip's share of a 4-chip deployment: experts
    0-7 and 16,384 rows of the vocabulary. Through ``unified_step`` with the
    model's counters as aux, on ONE chip whatever the host has."""
    import jax
    import numpy as np
    import optax

    from accelerate_tpu import (
        Accelerator, AcceleratorState, DataLoader, GradientState,
        ParallelismPlugin,
    )
    from accelerate_tpu.models import CausalLM, TransformerConfig

    if dry:
        w = dict(vocab_size=512, hidden_size=64, intermediate_size=160,
                 moe_intermediate_size=48, num_heads=4, num_kv_heads=2,
                 head_dim=16)
        rows, seq = 4, 64
    else:
        w = dict(vocab_size=16384, hidden_size=2048, intermediate_size=7168,
                 moe_intermediate_size=1792, num_heads=32, num_kv_heads=8,
                 head_dim=64)
        rows, seq = 4, 4096
    cfg = TransformerConfig(
        **w, num_layers=2, layer_types=("conv", "full_attention"),
        num_dense_layers=1, qk_norm=True, num_experts=8, moe_router_width=32,
        moe_expert_offset=0, num_experts_per_tok=4, moe_router="sigmoid",
        moe_expert_bias=True, tie_embeddings=True, rope_theta=1e6,
        max_seq_len=seq, remat="dots_ragged", dtype="bfloat16")
    say(f"hybrid: conv + dense MLP, attention + 8 of 32 experts (top 4), "
        f"head_dim {cfg.head_dim}, {rows}x{seq} tokens, remat=dots_ragged")
    acc = Accelerator(mixed_precision="bf16",
                      parallelism_plugin=ParallelismPlugin(fsdp_size=-1),
                      telemetry=True)
    if jax.device_count() > 1:  # a share of the experts lives on one chip
        acc.reform_mesh(jax.devices()[:1])
    model = CausalLM(cfg)
    raw = model.init(jax.random.PRNGKey(SEED), np.zeros((1, 16), np.int32))["params"]
    ids = np.random.default_rng(SEED).integers(0, cfg.vocab_size, (rows, seq))
    params, opt, loader = acc.prepare(
        raw, optax.adamw(3e-4),
        DataLoader([{"input_ids": r.astype(np.int32)} for r in ids], batch_size=rows))
    del raw
    step = acc.unified_step(CausalLM.loss_fn(model, with_aux=True),
                            has_aux=True, max_grad_norm=1.0)
    carry = acc.init_carry(params, opt)
    del params
    warm = acc.warmup(step, carry, loader)
    say(f"hybrid: warmup compile {warm['compile_time_s']:.1f}s")
    losses = []
    for _ in range(4):
        for batch in loader:
            carry, metrics = step(carry, batch)
            losses.append(float(metrics["loss"]))
    aux = {k: float(v) for k, v in metrics["aux"].items()}
    say("hybrid: loss " + " ".join(f"{l:.4f}" for l in losses)
        + "; " + " ".join(f"{k}={v:.4f}" for k, v in sorted(aux.items())))
    assert all(math.isfinite(l) for l in losses) and losses[-1] < losses[0], losses
    retraces = acc.telemetry.detector(step.label).retraces
    assert retraces == 0, f"{retraces} retraces after warmup"
    assert step.aot_fallbacks == 0, step.aot_fallbacks
    # a quarter of the router's experts are held: about a quarter of the
    # choices fall here, sorted first. The grouped matmuls take a STATIC
    # first window of the sorted rows, twice the even share (half of the
    # T x k at 8 of 32), the choices of absent experts inside it in a
    # group of zero weights, so that every row is defined and the step's time
    # does not follow the routing; the rows past the window run only when a
    # held one lies there, which it does not here: rows computed over rows
    # needed is window / (T x k) / the local share, about 2 at an even routing
    from accelerate_tpu.ops.moe import share_window_rows

    choices = rows * seq * cfg.num_experts_per_tok
    window = share_window_rows(choices, cfg.num_experts, cfg.moe_router_width)
    share = aux["moe_local_choice_share"]
    say(f"hybrid: first window {window} of {choices} sorted rows a layer; "
        f"moe_rest_window_share {aux['moe_rest_window_share']:.4f}, "
        f"moe_rows_computed_over_needed {aux['moe_rows_computed_over_needed']:.4f}")
    assert 0.1 < share < 0.5, aux
    assert aux["moe_expert_load_max_over_mean"] >= 1.0, aux
    assert aux["moe_rest_window_share"] == 0.0, aux
    assert abs(aux["moe_rows_computed_over_needed"] * share
               - window / choices) < 1e-3, aux
    if not dry:
        calls = [line for line in step.compiled.as_text().splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line]
        # an instruction is named after its kernel: flash_fwd (twice: the
        # remat policy saves matmul outputs, not the kernel's), flash_bwd_dq,
        # flash_bwd_dkv; XLA's own grouped matmuls are ragged-dot-*
        def named(prefix):
            return sorted(c.split("=")[0].strip().lstrip("%") for c in calls
                          if c.strip().lstrip("%").startswith(prefix))
        flash, grouped = named("flash_"), named("ragged-dot-none")
        assert {f.split(".")[0] for f in flash} == {
            "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}, (
            f"Mosaic calls {flash}: head_dim {cfg.head_dim} left the flash "
            "kernels for xla_attention")
        assert len(grouped) >= 18, (
            f"{len(grouped)} grouped-matmul kernels {grouped}: expected 3 "
            "forward and 6 backward from XLA's ragged-dot lowering, in the "
            "first window and again in the rest window's conditional")
        say(f"hybrid: compiled step holds flash calls {flash} at head_dim "
            f"{cfg.head_dim} and {len(grouped)} grouped-matmul calls")
    acc.telemetry.close()
    del carry, step, opt, loader, acc, metrics, batch
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    gc.collect()
    say(f"hybrid phase PASSED; freed, {live_bytes() / 2**20:.0f}MiB live")


# --------------------------------------------------------------------------- #
# serve phase
# --------------------------------------------------------------------------- #
def random_bf16_params(model, device):
    """Random bf16 weights straight on ``device`` (an fp32 ``model.init``
    of the serve depth would not fit): flax's own distributions — normal
    1/sqrt(fan_in) kernels, 0.02 embedding, unit norm scales."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.parallel.sharding import unbox_params

    abstract = unbox_params(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    ))["params"]
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    keys = jax.random.split(jax.random.PRNGKey(SEED), len(flat))

    def make():
        leaves = []
        for key, (path, leaf) in zip(keys, flat):
            name = jax.tree_util.keystr(path)
            if "scale" in name:
                leaves.append(jnp.ones(leaf.shape, jnp.bfloat16))
                continue
            if leaf.ndim < 2:  # a bias a router output: none
                leaves.append(jnp.zeros(leaf.shape, leaf.dtype))
                continue
            std = 0.02 if "embed" in name else leaf.shape[-2] ** -0.5
            leaves.append(
                std * jax.random.normal(key, leaf.shape, jnp.bfloat16)
            )
        return jax.tree_util.tree_unflatten(treedef, leaves)

    sharding = jax.sharding.SingleDeviceSharding(device)
    return jax.jit(make, out_shardings=sharding)()


def paged_logits_check(say, model, params, sz: Sizes, device) -> None:
    """For one prompt: prefill then token-by-token decode through the paged
    cache must give the logits of ONE full forward pass over the same
    tokens (logits, not tokens: random weights flip an argmax on rounding).
    Same model.apply(decode=True, paged=...) calls the engine compiles."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.models.generation import init_cache
    from accelerate_tpu.ops.attention import PagedKVState

    cfg = model.config
    rng = np.random.default_rng(SEED + 3)
    lo, hi = sz.serve_prompt_range
    prompt_len, n_decode = (lo + hi) // 2 + 1, 3  # mid-bucket, off-block
    total = prompt_len + n_decode
    tokens = rng.integers(0, cfg.vocab_size, (total,)).astype(np.int32)
    block = sz.serve_block
    max_table = -(-cfg.max_seq_len // block)
    num_blocks = max_table + 1
    table = np.zeros((1, max_table), np.int32)
    used = -(-total // block)
    # scattered, never block 0 (the reserved garbage block)
    table[0, :used] = 1 + rng.permutation(max_table)[:used]

    def state(cache_len, length):
        return PagedKVState(
            block_table=jnp.asarray(table), num_blocks=num_blocks,
            cache_len=jnp.asarray([cache_len], jnp.int32),
            lengths=jnp.asarray([length], jnp.int32), block_size=block,
            # the pools below sit whole on ``device``: on the chip the
            # decode steps run the paged_decode kernel, as the engine's do
            single_device=True,
        )

    with jax.default_device(device):
        cache = init_cache(
            model.init, jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
            decode=True, paged=state(0, 1), device=device,
        )

        @jax.jit
        def paged(params, cache, ids, st):
            logits, mutated = model.apply(
                {"params": params, "cache": cache}, ids, decode=True,
                paged=st, mutable=["cache"],
            )
            return mutated["cache"], logits

        bucket = 1 << (prompt_len - 1).bit_length()
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :prompt_len] = tokens[:prompt_len]
        cache, logits = paged(params, cache, jnp.asarray(ids),
                              state(0, prompt_len))
        got = [logits[0, prompt_len - 1]]
        for i in range(n_decode):
            pos = prompt_len + i
            cache, logits = paged(params, cache,
                                  jnp.asarray(tokens[None, pos:pos + 1]),
                                  state(pos, 1))
            got.append(logits[0, 0])
        full = jax.jit(lambda p, x: model.apply({"params": p}, x))(
            params, jnp.asarray(tokens[None, :])
        )
        want = full[0, prompt_len - 1:total]
        got = jnp.stack(got)
    assert got.shape == want.shape == (n_decode + 1, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))
    err = nerr(got, want)
    say(f"serve: paged prefill({prompt_len} in bucket {bucket})+"
        f"{n_decode} decode vs one full forward, last-position logits: "
        f"normalized max error {err:.2e} (tolerance {TOL_LOGITS_BF16:.0e}), "
        f"max|logit| {float(jnp.max(jnp.abs(want.astype(jnp.float32)))):.2f}")
    assert err <= TOL_LOGITS_BF16, (
        f"paged cache disagrees with the full forward pass by {err:.3e}"
    )


def assert_pool_in_place(say, engine) -> None:
    """Every prefill, decode and verify program the engine traced holds
    each pool as ONE buffer from its input to its output: the engine's
    count says so, and the compiler's: the bytes the captured programs
    give back in the buffers they came in are the pool's, all of them."""
    from accelerate_tpu.profiling.registry import ProgramRegistry

    counts = engine.trace_counts()
    programs = counts["prefill"] + counts["decode"] + counts["verify"]
    assert counts["kv_in_place"] == programs > 0, counts
    engine.capture_programs(ProgramRegistry())
    assert engine.trace_counts() == counts, "capture_programs retraced"
    aliased, pool = engine.pool_alias_bytes, engine.kv_pool_bytes
    assert aliased == engine._gauge_fields()["pool_alias_bytes"] == pool, (
        f"the compiled programs keep {aliased} bytes in place of a "
        f"{pool}-byte pool: a call returns a second pool"
    )
    say(f"serve: {programs} programs carry their pools in place; "
        f"pool_alias_bytes {aliased} == the pool's {pool} bytes")


def assert_decode_kernel(say, engine, cfg) -> None:
    """The compiled decode program reads the KV pools through the Pallas
    kernel: exactly one Mosaic custom call per compiled layer body (one
    under ``nn.scan``), every one under scope ``paged_attention``, none
    interpreted — as the train phase counts its flash kernels."""
    from accelerate_tpu.ops.flash_attention import kernels_interpreted
    from accelerate_tpu.profiling.registry import ProgramRegistry

    assert not kernels_interpreted(), "a kernel would run interpreted"
    before = engine.trace_counts()
    engine.capture_programs(ProgramRegistry())
    assert engine.trace_counts() == before
    text = engine._captured_programs["serve_decode"].as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    bodies = 1 if cfg.scan_layers else cfg.num_layers
    assert len(calls) == bodies, (
        f"decode program holds {len(calls)} Mosaic custom call(s), expected "
        f"{bodies}: the paged_decode kernel was dropped or duplicated"
    )
    assert all("paged_attention/paged_decode" in line for line in calls), calls
    say(f"serve: compiled decode program holds {len(calls)} Mosaic custom "
        f"call(s) under scope paged_attention ({bodies} compiled layer "
        "body), none interpreted")


def serve_phase(say, sz: Sizes, dry: bool) -> None:
    import jax
    import numpy as np

    from accelerate_tpu import ServingEngine
    from accelerate_tpu.compilation import get_compile_monitor
    from accelerate_tpu.models import CausalLM, count_params
    from accelerate_tpu.router import FleetRouter, InProcessReplica

    devices = jax.devices()
    cfg = mistral_config(sz, num_layers=sz.serve_layers,
                         max_seq_len=sz.serve_max_seq)
    model = CausalLM(cfg)
    say(f"serve: {sz.name} widths, depth cut 32 -> {cfg.num_layers} layers "
        "(bf16 weights + one KV pool, written in place, must fit), "
        f"max_slots={sz.serve_slots} block_size={sz.serve_block} "
        f"max_seq_len={cfg.max_seq_len}, one engine per chip x "
        f"{len(devices)}")
    mon = get_compile_monitor()
    cache_before = mon.snapshot()

    engines = []
    for dev in devices:
        params, init_s = timed(random_bf16_params, model, dev)
        eng = ServingEngine(model, params, max_slots=sz.serve_slots,
                            block_size=sz.serve_block)
        engines.append(eng)
        say(f"serve: engine on device {dev.id}: "
            f"{count_params(params) / 1e6:.0f}M bf16 parameters "
            f"(init {init_s:.1f}s), pool {eng.num_blocks} blocks, "
            f"{eng.kv_bytes_per_token / 1024:.1f} KiB of KV per token")
    paged_logits_check(say, model, engines[0].params, sz, devices[0])

    def assert_placement(when: str) -> None:
        for dev, eng in zip(devices, engines):
            for what, tree in (("params", eng.params), ("cache", eng.cache)):
                for leaf in jax.tree.leaves(tree):
                    assert leaf.devices() == {dev}, (
                        f"{when}: engine of device {dev.id} holds a {what} "
                        f"leaf on {leaf.devices()}"
                    )

    assert_placement("before traffic")

    # --- traffic: more requests than slots, >= 2 pow2 prefill buckets ----- #
    front = engines[0] if len(engines) == 1 else FleetRouter(
        [InProcessReplica(f"chip{d.id}", e) for d, e in zip(devices, engines)]
    )
    rng = np.random.default_rng(SEED + 4)
    lo, hi = sz.serve_prompt_range
    n_req = sz.serve_requests * len(engines)
    lengths = rng.integers(lo, hi + 1, size=n_req)
    lengths[0], lengths[1] = lo, hi  # pin both ends of the range
    buckets = sorted({1 << (int(n) - 1).bit_length() for n in lengths})
    assert len(buckets) >= 2 and n_req > sz.serve_slots * len(engines)
    rids = [
        front.add_request(rng.integers(0, cfg.vocab_size, int(n)),
                          max_new_tokens=sz.serve_new_tokens)
        for n in lengths
    ]
    prefilled = lambda: sum(e.prefill_bucket_tokens_total for e in engines)
    prefill_s, decode_s = [], []  # steps that ingested a prompt / did not
    t_all = time.perf_counter()
    while front.has_work:
        before, t0 = prefilled(), time.perf_counter()
        front.step()  # ends in a host fetch of the sampled tokens: a fence
        dt = time.perf_counter() - t0
        (prefill_s if prefilled() > before else decode_s).append(dt)
        for dev, eng in zip(devices, engines):
            # a plain engine decodes ahead: whoever goes on decoding has its
            # next step on the device when step() returns
            goes_on = any(s.busy and not s.done for s in eng.scheduler.slots)
            assert (eng._ahead is not None) == goes_on, (
                f"engine of device {dev.id}: step in flight "
                f"{eng._ahead is not None}, slots that go on decoding {goes_on}"
            )
    wall = time.perf_counter() - t_all
    warm_decode = decode_s[len(decode_s) // 2:]  # all programs compiled
    say(f"serve: {n_req} requests, prompt lengths {lo}..{hi} in prefill "
        f"buckets {buckets}, {sz.serve_new_tokens} new tokens each: "
        f"{len(prefill_s) + len(decode_s)} steps in {wall:.1f}s; "
        f"{len(prefill_s)} steps with prefill, median "
        f"{statistics.median(prefill_s) * 1e3:.0f}ms, slowest "
        f"{max(prefill_s):.1f}s (compiles); {len(decode_s)} decode-only "
        f"steps, warm median {statistics.median(warm_decode) * 1e3:.1f}ms "
        "(set-up facts)")

    # --- assertions ------------------------------------------------------ #
    for rid in rids:
        out = front.result(rid)
        assert out is not None and len(out) == sz.serve_new_tokens, (rid, out)
        assert all(0 <= int(t) < cfg.vocab_size for t in out), (rid, out)
    for dev, eng in zip(devices, engines):
        counts, stats = eng.trace_counts(), eng.pool.stats()
        say(f"serve: engine on device {dev.id} trace_counts {counts} "
            f"pool allocated={stats['allocated']} free={stats['free']}")
        assert counts["decode"] == 1, f"decode traced {counts['decode']}x"
        assert counts["qkv_in_place"] == 1, (
            "the decode program did not pin its q/k/v projections flat"
        )
        assert 1 <= counts["prefill"] <= len(buckets), (
            f"{counts['prefill']} prefill traces for buckets {buckets}: "
            "a retrace"
        )
        assert stats["allocated"] == 0, f"leaked blocks: {stats}"
        # of its decode steps, those whose tokens were on the device before
        # their step() began: all but the first behind each wave of prefills
        share = eng.decode_ahead_share
        say(f"serve: engine on device {dev.id} decode_ahead_share {share:.3f}")
        assert eng.decode_ahead and share >= (0.5 if dry else 0.9), share
        if not dry:
            assert counts["decode_attn_kernel"] == 1, (
                "the decode program took the gather form of paged_attention"
            )
    assert_pool_in_place(say, engines[0])
    if not dry:
        assert_decode_kernel(say, engines[0], cfg)
    assert_placement("after traffic")
    delta = mon.delta(cache_before)
    say(f"serve: persistent cache over the phase hits="
        f"{int(delta['persistent_cache_hits'])} "
        f"misses={int(delta['persistent_cache_misses'])}")
    say("serve phase PASSED")


def eva_phase(say, dry: bool) -> None:
    """``ServingEngine`` over three layers at EvaByte's published widths
    (hidden 4096, 32 heads of 128, SwiGLU 11,008, byte vocabulary 320, 8
    prediction heads, chunks of 16 in windows of 2,048; depth cut 32 -> 3) on
    ONE chip: prompts that end inside a second window, exactly on a window
    and inside the first, two of them decoding across a window's end — so the flash
    forward kernel runs inside and across windows, the decode kernel walks
    summaries and window rows, and windows roll over. The served bytes are
    held against the model's OWN plain forward pass of prompt + answer (the
    XLA form of the same attention, no cache)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu import ServingEngine
    from accelerate_tpu.models import CausalLM, TransformerConfig, count_params

    if dry:
        w = dict(vocab_size=40, hidden_size=64, intermediate_size=160,
                 num_heads=4, num_kv_heads=4, head_dim=16, chunk_size=4,
                 window_size=16, num_pred_heads=3)
        block, max_seq, new = 4, 128, 12
        prompts = (16 + 10, 16, 9)
    else:
        w = dict(vocab_size=320, hidden_size=4096, intermediate_size=11008,
                 num_heads=32, num_kv_heads=32, head_dim=128, chunk_size=16,
                 window_size=2048, num_pred_heads=8)
        block, max_seq, new = 16, 8192, 24
        prompts = (2048 + 2040, 2048, 2030)
    cfg = TransformerConfig(
        **w, num_layers=3, attention_class="eva", norm_offset=True,
        rope_theta=100000.0, max_seq_len=max_seq, dtype="bfloat16",
        # as the published file states: the stream and the logits float32
        fp32_residual=True, fp32_logits=True)
    window = cfg.window_size
    model = CausalLM(cfg)
    device = jax.devices()[0]
    params = random_bf16_params(model, device)
    eng = ServingEngine(model, params, max_slots=2, block_size=block)
    say(f"eva: depth cut 32 -> {cfg.num_layers} layers, "
        f"{count_params(params) / 1e6:.0f}M bf16 parameters, 2 slots x "
        f"{max_seq} bytes: table {eng._max_table} blocks, pool "
        f"{eng.num_blocks} blocks, {eng.kv_bytes_per_token / 1024:.1f} KiB "
        "a cache row")
    rng = np.random.default_rng(SEED + 5)
    asked = [rng.integers(0, cfg.vocab_size, n).astype(np.int32) for n in prompts]
    rids = [eng.add_request(p, max_new_tokens=new) for p in asked]
    while eng.has_work:
        eng.step()
    counts, stats, gauges = eng.trace_counts(), eng.pool.stats(), eng._gauge_fields()
    say(f"eva: trace_counts {counts} pool allocated={stats['allocated']} "
        f"roll-overs {gauges['window_rollovers_total']}")
    assert counts["decode"] == counts["qkv_in_place"] == 1, counts
    assert counts["eva"] == counts["prefill"] + counts["decode"] + 1, counts
    assert stats["allocated"] == 0, stats
    # a window rolls over when a request that goes on decoding fills it
    filled = sum((len(p) + new - 2) // window - len(p) // window for p in asked)
    assert gauges["window_rollovers_total"] == filled >= 2, (gauges, filled)
    if not dry:
        assert counts["decode_attn_kernel"] == 1, counts
    assert_pool_in_place(say, eng)
    apply = jax.jit(lambda p, ids: model.apply({"params": p}, ids))
    gaps = []
    for rid, prompt in zip(rids, asked):
        out = np.asarray(eng.result(rid))
        assert len(out) == new and ((0 <= out) & (out < cfg.vocab_size)).all()
        seq = np.concatenate([prompt, out])
        logits = np.asarray(apply(params, jnp.asarray(seq)[None])[0].astype(
            jnp.float32))[len(prompt) - 1:len(seq) - 1]
        gaps.append(logits.max(-1) - logits[np.arange(new), out])
    gaps = np.concatenate(gaps)
    say(f"eva: served bytes against the plain forward pass: mean logit gap "
        f"{gaps.mean():.4g}, widest {gaps.max():.4g}, "
        f"{int((gaps > 0).sum())} of {gaps.size} off its best")
    # two bf16 programs of one arithmetic: a near-tie may fall otherwise, a
    # byte from elsewhere reads 2 and more (PERF.md section 4)
    assert gaps.mean() < 0.05 and gaps.max() < 1.0, (gaps.mean(), gaps.max())
    say("eva phase PASSED")


def mla_phase(say, dry: bool) -> None:
    """``ServingEngine`` over two layers at DeepSeek-V3's published widths
    (hidden 7168; 128 heads of 128 + 64 over a latent of 512 beside 64
    rotated, queries through 1536, values 128; YaRN at factor 40; one dense
    layer of 18,432 and one of 16 of 256 group-limited sigmoid experts of
    2,048 with a shared expert; depth cut 61 -> 2) on ONE chip, 4 slots x
    4,096 positions: a prefill EXPANDS (flash at 192 / 128, the heads in
    groups at the widest) and writes latent rows alone, every decode step
    reads them ABSORBED through the ``latent_decode`` kernel, donated and
    dispatched ahead. The served tokens are held against the model's OWN
    plain forward pass of prompt + answer (the expanded form, no cache)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu import ServingEngine
    from accelerate_tpu.models import CausalLM, TransformerConfig, count_params

    if dry:
        w = dict(vocab_size=96, hidden_size=64, intermediate_size=96,
                 moe_intermediate_size=24, moe_shared_intermediate_size=24,
                 num_heads=4, q_lora_rank=32, kv_lora_rank=24,
                 qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                 num_experts=4, moe_router_width=16, num_experts_per_tok=3,
                 moe_n_group=4, moe_topk_group=2)
        block, max_seq, new, prompts, old = 8, 256, 12, (40, 9, 100, 64), 32
    else:
        w = dict(vocab_size=16160, hidden_size=7168, intermediate_size=18432,
                 moe_intermediate_size=2048, moe_shared_intermediate_size=2048,
                 num_heads=128, q_lora_rank=1536, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 num_experts=16, moe_router_width=256, num_experts_per_tok=8,
                 moe_n_group=8, moe_topk_group=4)
        block, max_seq, new, prompts, old = 16, 4096, 64, (3000, 130, 2048, 700), 4096
    cfg = TransformerConfig(
        **w, num_layers=2, num_dense_layers=1, moe_router="sigmoid",
        moe_expert_bias=True, moe_norm_topk_eps=1e-20,
        moe_routed_scaling_factor=2.5, rope_theta=10000.0, rms_norm_eps=1e-6,
        rope_scaling={"type": "yarn", "factor": 40, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": old},
        scan_layers=False, fp32_logits=True, max_seq_len=max_seq,
        dtype="bfloat16")
    model = CausalLM(cfg)
    device = jax.devices()[0]
    params = random_bf16_params(model, device)
    eng = ServingEngine(model, params, max_slots=4, block_size=block,
                        decode_ahead=True)
    gauges = eng._gauge_fields()
    say(f"mla: depth cut 61 -> {cfg.num_layers} layers, "
        f"{count_params(params) / 1e6:.0f}M bf16 parameters, 4 slots x "
        f"{max_seq} positions: pool {eng.num_blocks} blocks, "
        f"latent_row_bytes {gauges['latent_row_bytes']:.0f} a position")
    rng = np.random.default_rng(SEED + 6)
    asked = [rng.integers(0, cfg.vocab_size, n).astype(np.int32) for n in prompts]
    rids = [eng.add_request(p, max_new_tokens=new) for p in asked]
    while eng.has_work:
        eng.step()
    counts, stats = eng.trace_counts(), eng.pool.stats()
    say(f"mla: trace_counts {counts} pool allocated={stats['allocated']} "
        f"decode_ahead_share {eng.decode_ahead_share:.3f} "
        "prefill_real_token_share "
        f"{eng._gauge_fields()['prefill_real_token_share']:.3f}")
    assert counts["decode"] == 1 and stats["allocated"] == 0, (counts, stats)
    assert counts["mla_prefill_expanded"] == counts["prefill"] >= 1, counts
    # every prefill told flash how many rows of its bucket are real
    assert counts["flash_real_rows"] == counts["prefill"], counts
    # and wrote its latent rows by the block (prompts of 130 and up: every
    # bucket is whole blocks)
    assert counts["kv_block_write"] == counts["prefill"], counts
    assert {k.split("'")[-2] for k in map(
        jax.tree_util.keystr, dict(jax.tree_util.tree_flatten_with_path(
            eng.cache)[0]))} == {"latent_pool"}
    assert eng.state_bytes_per_slot == 0
    if not dry:
        assert counts["mla_decode_kernel"] >= 1, counts
    assert eng.decode_ahead_share >= 0.9, eng.decode_ahead_share
    assert_pool_in_place(say, eng)
    apply = jax.jit(lambda p, ids: model.apply({"params": p}, ids))
    gaps = []
    for rid, prompt in zip(rids, asked):
        out = np.asarray(eng.result(rid))
        assert len(out) == new and ((0 <= out) & (out < cfg.vocab_size)).all()
        seq = np.concatenate([prompt, out])
        logits = np.asarray(apply(params, jnp.asarray(seq)[None])[0].astype(
            jnp.float32))[len(prompt) - 1:len(seq) - 1]
        gaps.append(logits.max(-1) - logits[np.arange(new), out])
    gaps = np.concatenate(gaps)
    say(f"mla: served tokens against the plain forward pass: mean logit gap "
        f"{gaps.mean():.4g}, widest {gaps.max():.4g}, "
        f"{int((gaps > 0).sum())} of {gaps.size} off its best")
    # two bf16 programs of one arithmetic in two forms: a near-tie may fall
    # otherwise
    assert gaps.mean() < 0.1 and gaps.max() < 2.0, (gaps.mean(), gaps.max())
    say("mla phase PASSED")


# --------------------------------------------------------------------------- #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--cpu-dry-run", action="store_true",
        help="rehearse the control flow on the CPU backend at tiny size; "
             "never prints the pass line",
    )
    ap.add_argument(
        "--phases", default=",".join(PHASES),
        help="comma-separated subset of %(default)s, run in that order; a "
             "subset never prints the pass line",
    )
    args = ap.parse_args(argv)
    dry = args.cpu_dry_run
    phases = [p for p in PHASES if p in args.phases.split(",")]
    if not phases or set(args.phases.split(",")) - set(PHASES):
        ap.error(f"--phases takes names out of {PHASES}")

    import jax

    if dry:
        jax.config.update("jax_platforms", "cpu")
    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices())}
    if not dry and device["platform"] != "tpu":
        print(
            f"chip_smoke: JAX found platform={device['platform']!r} "
            f"({device['kind']}), not a TPU — nothing ran. Use "
            "--cpu-dry-run to rehearse the control flow on CPU.",
            file=sys.stderr,
        )
        return 2

    tag = (f"[DRY RUN cpu x{device['count']}]" if dry else
           f"[{device['platform']} {device['kind']} x{device['count']}]")

    def say(msg: str) -> None:
        print(f"{tag} {msg}", flush=True)

    from accelerate_tpu.compilation import (
        activate_persistent_cache,
        get_compile_monitor,
    )
    from accelerate_tpu.utils.dataclasses import CompilePlugin

    monitor = get_compile_monitor()  # listeners on before the first compile

    def version(pkg: str) -> str:
        try:
            return importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            return "absent"

    say("versions " + " ".join(
        f"{p}={version(p)}" for p in ("jax", "jaxlib", "libtpu", "flax", "optax")
    ) + f" python={sys.version.split()[0]}")
    say("devices " + str([(d.id, d.device_kind, getattr(d, "coords", None))
                          for d in jax.devices()]))
    # persist EVERY compile (JAX's default floor is 1 s): a second run must
    # find each program of the first
    cache_dir = activate_persistent_cache(CompilePlugin(
        cache_min_compile_time_secs=0.0, cache_min_entry_size_bytes=-1,
    ))
    say(f"persistent compile cache: {cache_dir}")

    sz = TINY if dry else REAL
    run = {"kernels": lambda: kernel_phase(say, sz, dry),
           "train": lambda: train_phase(say, sz, dry),
           "hybrid": lambda: hybrid_phase(say, dry),
           "serve": lambda: serve_phase(say, sz, dry),
           "eva": lambda: eva_phase(say, dry),
           "mla": lambda: mla_phase(say, dry)}
    wall = []
    for phase in phases:
        t0 = time.perf_counter()
        run[phase]()
        wall.append(f"{phase} {time.perf_counter() - t0:.0f}s")

    totals = monitor.snapshot()
    say(f"wall: {' '.join(wall)}; process persistent-cache hits="
        f"{int(totals['persistent_cache_hits'])} misses="
        f"{int(totals['persistent_cache_misses'])}, XLA compile "
        f"{totals['compile_time_s']:.0f}s")
    if dry:
        say("rehearsal complete — this is NOT a pass: nothing ran on a chip")
        return 0
    if phases != list(PHASES):
        say(f"only {phases} ran: NOT the pass line of a whole run")
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

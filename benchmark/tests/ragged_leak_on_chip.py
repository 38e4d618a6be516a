#!/usr/bin/env python3
"""Where XLA:TPU's grouped matmul (``jax.lax.ragged_dot``) leaks stale memory
into a gradient, and the form that does not — by hand, on the chip, at one
expert layer's shapes of ``train-moe-conv-1chip`` (PERF.md, PR 26, call 10):

    python3 benchmark/tests/ragged_leak_on_chip.py [--seed N]
    JAX_PLATFORMS=cpu python3 benchmark/tests/ragged_leak_on_chip.py --tiny

Three forms of the held experts' grouped matmuls over ``T*K`` sorted rows of
which only the first ``live`` belong to a group:

* ``zero_group``: the rest in one more group of zero weights — what
  ``accelerate_tpu.ops.moe.moe_ragged`` does; every row is defined.
* ``fwd_masks``: group sizes sum to ``live``; ``jnp.where`` on the three
  forward outputs. The kernel skips the other rows and leaves what was in
  memory there, in the BACKWARD grouped matmul too: ``d xs`` comes out of
  autodiff with undefined rows that no forward ``where`` reaches, and the
  gather's transpose adds them into ``dx``.
* ``vjp_masks``: the same group sizes behind ``masked_ragged_dot``, a
  ``custom_vjp`` that also zeroes the cotangent, the backward output and an
  empty group's block of the weight gradient. Exact, and its time follows
  the live rows.

Device memory is filled with NaN before every call. Prints, per form, how
many of the calls gave a non-finite result, the last call's widest gap to
``zero_group``, and the time of forward + backward."""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

NAMES = ("y", "dx", "dweights", "dw_gate", "dw_up", "dw_down")


@jax.custom_vjp
def masked_ragged_dot(lhs, rhs, sizes):
    """``ragged_dot`` whose rows past ``sum(sizes)`` are zero, forward and
    backward."""
    return _masked_fwd(lhs, rhs, sizes)[0]


def _live(rows, sizes):
    return (jnp.arange(rows) < jnp.sum(sizes))[:, None]


def _masked_fwd(lhs, rhs, sizes):
    live = _live(lhs.shape[0], sizes)
    lhs = jnp.where(live, lhs, 0)
    return jnp.where(live, jax.lax.ragged_dot(lhs, rhs, sizes), 0), (lhs, rhs, sizes)


def _masked_bwd(res, g):
    lhs, rhs, sizes = res
    live = _live(lhs.shape[0], sizes)
    _, vjp = jax.vjp(lambda l, r: jax.lax.ragged_dot(l, r, sizes), lhs, rhs)
    d_lhs, d_rhs = vjp(jnp.where(live, g, 0))
    return (jnp.where(live, d_lhs, 0),
            jnp.where((sizes > 0)[:, None, None], d_rhs, 0),
            np.zeros(sizes.shape, jax.dtypes.float0))


masked_ragged_dot.defvjp(_masked_fwd, _masked_bwd)


def build(t, k, h, f, e, r, seed=0, dtype=jnp.bfloat16):
    """``(make, args)``: ``make(form)`` is the jitted value-and-gradient of one
    expert layer's dispatch, grouped matmuls and combine in that form, over
    ``args``; expert 3 is held and chosen by no token (an empty group)."""
    tk = t * k
    kx, ks, kw, k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(kx, (t, h), dtype)
    sel = jax.random.randint(ks, (t, k), 0, r)
    sel = jnp.where(sel == 3, 4, sel)
    weights = jax.random.uniform(kw, (t, k), jnp.float32)
    w_gate = (jax.random.normal(k1, (e, h, f)) / h ** 0.5).astype(dtype)
    w_up = (jax.random.normal(k2, (e, h, f)) / h ** 0.5).astype(dtype)
    w_down = (jax.random.normal(k3, (e, f, h)) / f ** 0.5).astype(dtype)

    def layer(form, x, weights, w_gate, w_up, w_down):
        flat = jnp.where(sel.reshape(tk) < e, sel.reshape(tk), e)
        order = jnp.argsort(flat)
        tok = jnp.repeat(jnp.arange(t), k)[order]
        sizes = jnp.bincount(flat, length=e + 1).astype(jnp.int32)
        xs = jnp.take(x, tok, axis=0)
        if form == "zero_group":
            def dot(a, w):
                zero = jnp.zeros((1,) + w.shape[1:], w.dtype)
                return jax.lax.ragged_dot(a, jnp.concatenate([w, zero]), sizes)
        elif form == "fwd_masks":
            live = _live(tk, sizes[:e])
            dot = lambda a, w: jnp.where(  # noqa: E731
                live, jax.lax.ragged_dot(a, w, sizes[:e]), 0)
        else:
            dot = lambda a, w: masked_ragged_dot(a, w, sizes[:e])  # noqa: E731
        out = dot(jax.nn.silu(dot(xs, w_gate)) * dot(xs, w_up), w_down)
        w_flat = weights.reshape(tk)[order].astype(out.dtype)
        return jnp.zeros((t, h), out.dtype).at[tok].add(out * w_flat[:, None])

    def make(form):
        def loss(*args):
            y = layer(form, *args)
            return jnp.sum(y.astype(jnp.float32) * 1e-3), y
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True))

    live = int(jnp.sum(sel < e))
    return make, (x, weights, w_gate, w_up, w_down), live


def results(fn, args) -> dict:
    (_, y), grads = fn(*args)
    return {name: np.asarray(v.astype(jnp.float32))
            for name, v in zip(NAMES, (y,) + grads)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--tiny", action="store_true", help="CPU rehearsal")
    args = ap.parse_args()
    if args.tiny:
        shape, junk, dtype = (64, 4, 32, 16, 8, 32), 1024, jnp.float32
    elif jax.devices()[0].platform != "tpu":
        print("needs the chip (or --tiny)", file=sys.stderr)
        return 2
    else:  # one expert layer of the cell; 6 GB of NaN
        shape, junk, dtype = (16384, 4, 2048, 1792, 8, 32), 3 * 2**30, jnp.bfloat16
    make, inputs, live = build(*shape, seed=args.seed, dtype=dtype)
    tk = shape[0] * shape[1]
    print(f"T*K={tk} live={live} ({live / tk:.3f}); expert 3 is empty", flush=True)
    want = None
    for form in ("zero_group", "fwd_masks", "vjp_masks"):
        fn = make(form)
        bad_calls, first_bad = 0, {}
        for _ in range(args.calls):
            jnp.full((junk,), jnp.nan, jnp.bfloat16).block_until_ready()  # freed at once
            got = results(fn, inputs)
            bad = {k: int(np.sum(~np.isfinite(v))) for k, v in got.items()}
            bad = {k: n for k, n in bad.items() if n}
            bad_calls += bool(bad)
            first_bad = first_bad or bad
        want = want or got
        gap = {k: float(np.max(np.abs(got[k] - want[k])) / (np.max(np.abs(want[k])) + 1e-30))
               for k in NAMES}
        t0 = time.perf_counter()
        for _ in range(10):
            out = fn(*inputs)
        jax.block_until_ready(out)
        print(f"{form}: {bad_calls} of {args.calls} poisoned calls non-finite "
              f"{json.dumps(first_bad)}; last call's widest gap to zero_group / max "
              f"{json.dumps(gap)}; forward+backward "
              f"{(time.perf_counter() - t0) * 100:.2f} ms", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

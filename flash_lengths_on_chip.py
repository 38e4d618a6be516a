#!/usr/bin/env python3
"""What ``flash_fwd`` costs a serving prefill whose prompt does not fill its
bucket, by hand, on the chip, at the shapes of one head group of
``serve-mla-moe-longctx-sat`` (PERF.md section 6, PR 41):

    python3 flash_lengths_on_chip.py [--phases kernel] [--widths 8192,16384] [--shares 1,0.75,0.55]
    JAX_PLATFORMS=cpu python3 flash_lengths_on_chip.py --tiny

``kernel``: ``ops.flash_attention._fwd`` alone, causal, one row of 32 heads, a
192-wide score over a 128-wide value, bf16, at a bucket of ``W`` positions of
which ``length`` are real, three forms a line:

* ``bucket`` — no length at all: the call a prefill made before PR 41 (the
  bucket's causal half-square);
* ``keys`` — the key length alone (``kv_lengths``): kv blocks past the length
  are skipped, every padded q block still walks the real keys;
* ``rows`` — the key length and the query length: the real rows' half-square,
  the rest written as zeros.

A line holds the milliseconds a call, the blocks a head walks (arithmetic,
at the kernel's 1,024 x 1,024 blocks) and what the real tokens' causal
half-square needs over the time (share of the chip's bf16 peak). ``rows`` at
``length == W`` against ``bucket`` is the check that telling the kernel a
length adds no vector work to a block it walks: at most 3 % over it.

A CPU run (``--tiny``) interprets the kernels at a toy size, holds the three
forms' real rows to one another and prints no time."""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp

HEADS, SCORE, VALUE = 32, 192, 128


def timed_ms(fn, args, reps: int) -> float:
    """Median over three sets of the milliseconds one execution takes: ``reps``
    dispatched back to back, the last one waited for."""
    jax.block_until_ready(fn(*args))  # compile
    jax.block_until_ready(fn(*args))
    sets = []
    for _ in range(3):
        start = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        sets.append((time.perf_counter() - start) / reps * 1e3)
    return statistics.median(sets)


def blocks_walked(width: int, length: int, block: int, form: str) -> int:
    """Grid steps of one head that compute: q block ``i`` sees kv blocks up
    to its diagonal, of those that hold a real key, if it holds a real row."""
    n = width // block
    real = -(-length // block)
    if form == "bucket":
        return n * (n + 1) // 2
    q_blocks = n if form == "keys" else real
    return sum(min(i + 1, real) for i in range(q_blocks))


def kernel_phase(tag, widths, shares, heads, score, value, block, reps, peak,
                 seed):
    """``peak``: the chip's bf16 FLOP/s, None on a CPU (no time is read)."""
    from accelerate_tpu.ops.flash_attention import _fwd

    scale = score ** -0.5
    on_chip = peak is not None

    def form(name):
        def call(q, k, v, length):
            lens = None if name == "bucket" else length
            rows = length if name == "rows" else None
            return _fwd(q, k, v, lens, scale, True, block, block, None, rows)[0]
        return jax.jit(call)

    forms = {name: form(name) for name in ("bucket", "keys", "rows")}
    table = []
    for width in widths:
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (1, heads, width, score), jnp.bfloat16)
        k = jax.random.normal(ks[1], (1, heads, width, score), jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, heads, width, value), jnp.bfloat16)
        for share in shares:
            length = int(round(width * share))
            lens = jnp.asarray([length], jnp.int32)
            line = {"width": width, "length": length}
            outs = {name: fn(q, k, v, lens) for name, fn in forms.items()}
            # the same work: the real rows agree, the rest are zeros
            real = [o[:, :, :length].astype(jnp.float32) for o in outs.values()]
            line["gap"] = max(float(jnp.max(jnp.abs(r - real[0]))) for r in real)
            assert line["gap"] < 0.05, line
            assert not bool(jnp.any(outs["rows"][:, :, length:] != 0))
            needed = 2 * heads * (length * (length + 1) // 2) * (score + value)
            for name, fn in forms.items():
                line[name + "_blocks"] = blocks_walked(width, length, block, name)
                if on_chip:
                    ms = timed_ms(fn, (q, k, v, lens), reps)
                    line[name + "_ms"] = ms
                    line[name + "_peak_share"] = needed / (ms * 1e-3) / peak
            print(f"{tag} kernel W {width} length {length}: " + "  ".join(
                f"{name} {line[name + '_blocks']} blocks" + (
                    f" {line[name + '_ms']:.3f} ms "
                    f"{100 * line[name + '_peak_share']:.1f} %" if on_chip else "")
                for name in forms) + f"  gap {line['gap']:.2e}", flush=True)
            table.append(line)
        if on_chip:
            full = next((l for l in table
                         if l["width"] == width and l["length"] == width), None)
            if full:
                ratio = full["rows_ms"] / full["bucket_ms"]
                print(f"{tag} kernel W {width}: told the whole bucket is real, a "
                      f"call takes {ratio:.4f} x the call without lengths "
                      f"({'within' if ratio <= 1.03 else 'NOT within'} 3 %)",
                      flush=True)
    return table


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phases", default="kernel")
    ap.add_argument("--widths", default="8192,16384")
    ap.add_argument("--shares", default="1,0.75,0.55",
                    help="real length over the bucket's width")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--tiny", action="store_true",
                    help="toy shapes, interpreted, for a CPU rehearsal: no time")
    args = ap.parse_args()

    dev = jax.devices()[0]
    tag = f"[{dev.platform} {dev.device_kind} x{jax.device_count()}]"
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.tiny:
        print(f"{tag} no TPU: a time comes from a chip alone (--tiny rehearses)",
              file=sys.stderr)
        return 2
    widths = [int(w) for w in args.widths.split(",")]
    shares = [float(s) for s in args.shares.split(",")]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": jax.device_count()}}
    if "kernel" in args.phases.split(","):
        if args.tiny:
            from accelerate_tpu.ops.flash_attention import kernel_interpret_mode

            with kernel_interpret_mode():
                out["kernel"] = kernel_phase(
                    tag, [512], shares, 2, 48, 32, 128, 1, None, args.seed)
        else:
            from accelerate_tpu.profiling.registry import device_peaks

            out["kernel"] = kernel_phase(
                tag, widths, shares, HEADS, SCORE, VALUE, 1024, args.reps,
                device_peaks(dev.device_kind)["flops_per_s"], args.seed)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

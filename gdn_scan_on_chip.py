#!/usr/bin/env python3
"""What the chunked gated delta rule costs one DeltaNet layer of a serving
prefill, by hand, on the chip, at the heads of ``serve-gdn-moe-sat`` (one row,
16 key heads and 32 value heads of 128; PERF.md section 6, PR 42):

    python3 gdn_scan_on_chip.py [--cases 1024,2048,4096,8192,16384,16384:9011] [--heads 8]
    JAX_PLATFORMS=cpu python3 gdn_scan_on_chip.py --tiny

A case is a bucket's width, or ``width:length`` for a prompt that ends inside
it. Two forms a line, the milliseconds one call of each takes:

* ``jnp`` — ``ops.gated_delta._chunked_reference``: the ``jax.numpy`` form a
  prefill ran before PR 42 (einsums, XLA's ``triangular_solve`` and a
  ``lax.scan`` over the bucket's chunks, padded ones included);
* ``kernel`` — ``ops.gated_delta.gated_delta_chunked``: the ``gdn_chunked``
  Mosaic kernel, which walks the real chunks alone.

Beside them the chunks a head walks, the widest difference between the two
forms' real rows and states, and what the real tokens' recurrence needs
(``benchmark/harness/qwen3_next_work.py::gdn_scan_work``, one layer) over the
kernel's time: the share of the larger of its FLOPs at the chip's bf16 peak and
its bytes at the HBM's rate.
``--heads``: the value heads a grid step, to try another split than the
module's.

A CPU run (``--tiny``) interprets the kernel at a toy size, holds the two forms
to one another and prints no time."""

from __future__ import annotations

import argparse
import json
import os
import sys

import jax
import jax.numpy as jnp

from flash_lengths_on_chip import timed_ms

ROOT = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(
    ROOT, "benchmark", "configs", "qwen3-next-80b-a3b-serve-1chip.json")
GAP = 2e-3  # float32 both sides, thousands of positions summed in another order


def inputs(width: int, hk: int, hv: int, dk: int, dv: int, seed: int):
    """What a layer's projections and convolution hand the rule: bfloat16
    heads, float32 write strengths and log-decays from fast to none."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (1, width, hk, dk), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, width, hk, dk), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, width, hv, dv), jnp.bfloat16)
    g = -jnp.exp(jax.random.uniform(ks[3], (1, width, hv), minval=-6.0, maxval=1.0))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, width, hv)))
    return q, k, v, g, beta


def cases_phase(tag, cases, hk, hv, dk, dv, reps, needs, seed):
    """``needs(length)``: the least seconds the chip could take over one
    layer's recurrence of that many real positions; None on a CPU (no time is
    read)."""
    from accelerate_tpu.ops import gated_delta

    forms = {"jnp": jax.jit(gated_delta._chunked_reference),
             "kernel": jax.jit(gated_delta.gated_delta_chunked)}
    chunk = gated_delta.CHUNK
    table = []
    for width, length in cases:
        args = inputs(width, hk, hv, dk, dv, seed) + (
            jnp.asarray([length], jnp.int32),)
        line = {"width": width, "length": length,
                "jnp_chunks": -(-width // chunk),
                "kernel_chunks": -(-length // chunk)}
        outs = {name: fn(*args) for name, fn in forms.items()}
        (o_ref, s_ref), (o, s) = outs["jnp"], outs["kernel"]
        line["gap"] = max(float(jnp.max(jnp.abs(o[:, :length] - o_ref[:, :length]))),
                          float(jnp.max(jnp.abs(s - s_ref))))
        assert line["gap"] < GAP, line
        assert not bool(jnp.any(o[:, length:] != 0)), line
        text = ""
        if needs is not None:
            for name, fn in forms.items():
                line[name + "_ms"] = timed_ms(fn, args, reps)
            line["kernel_roofline"] = needs(length) / (line["kernel_ms"] * 1e-3)
            text = (f"  jnp {line['jnp_ms']:.3f} ms  kernel "
                    f"{line['kernel_ms']:.3f} ms  "
                    f"{line['jnp_ms'] / line['kernel_ms']:.2f} x  "
                    f"{100 * line['kernel_roofline']:.1f} % of its roofline")
        print(f"{tag} gdn W {width} length {length}: chunks a head "
              f"{line['jnp_chunks']} / {line['kernel_chunks']}{text}  "
              f"gap {line['gap']:.2e}", flush=True)
        table.append(line)
    return table


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cases", default="1024,2048,4096,8192,16384,16384:9011",
                    help="bucket widths, or width:length")
    ap.add_argument("--heads", type=int, default=None,
                    help="value heads a grid step (default: the module's)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=5)
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
    from accelerate_tpu.ops import gated_delta

    if args.heads:
        gated_delta._HEADS_A_STEP = args.heads
    cases = [tuple(int(n) for n in (c if ":" in c else f"{c}:{c}").split(":"))
             for c in args.cases.split(",")]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": jax.device_count()},
           "heads_a_step": gated_delta._HEADS_A_STEP}
    if args.tiny:
        from accelerate_tpu.ops.flash_attention import kernel_interpret_mode

        with kernel_interpret_mode():
            out["cases"] = cases_phase(
                tag, [(256, 256), (256, 141), (256, 64)], 2, 4, 16, 8, 1, None,
                args.seed)
    else:
        sys.path.insert(0, os.path.join(ROOT, "benchmark"))
        from harness.qwen3_next_weights import gdn_dims
        from harness.qwen3_next_work import gdn_scan_work

        from accelerate_tpu.profiling.registry import device_peaks

        with open(CONFIG) as f:
            cfg = json.load(f)
        peaks = device_peaks(dev.device_kind)
        layers = cfg["layer_types"].count("linear_attention")

        def needs(length):  # the benchmark's own count, one layer of its three
            work = gdn_scan_work(cfg, {"traced_tokens": length})
            return max(work["flops"] / peaks["flops_per_s"],
                       work["bytes"] / peaks["hbm_bytes_per_s"]) / layers

        out["cases"] = cases_phase(
            tag, cases, *gdn_dims(cfg)[:4], args.reps, needs, args.seed)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""What XLA:TPU's grouped matmul (``jax.lax.ragged_dot``) costs at an expert
width that is no whole number of the chip's 128 lanes, and what padding the
width with zeros gives back — by hand, on the chip, at one expert layer's
shapes of ``train-ssm-moe-1chip`` (PERF.md section 6, PR 35):

    python3 ragged_width_on_chip.py [--phases kernels,layer] [--shapes HxF,...] [--seed N]
    JAX_PLATFORMS=cpu python3 ragged_width_on_chip.py --tiny

``kernels``: ``ragged_dot`` alone, bf16, 9 groups whose last (the zero-weight
group of ``accelerate_tpu.ops.moe.moe_ragged``) holds 15/16 of 98,304 sorted
rows, at hidden 2688 and expert widths 1856 (published: 14.5 x 128), 1920
(15 x 128) and 2048 (16 x 128 = 4 x 512), once more at hidden 3072 = 6 x 512
and width 2048, and at LFM2's 2048 x 1792 for the same rows (whole lanes
both ways); ``--shapes`` reads other pairs. Six kernels a line — the up and the down product
forward, and ``jax.vjp`` of each to the rows and to the weights apart — with
the milliseconds of one execution and the share of the chip's peak that the
line's own 2 x rows x hidden x width FLOPs come to.

``layer``: the program's own expert layer (``models.transformer.MoE``: 8 of
128 sigmoid-routed non-gated relu2 experts of width 1856, top 6, 2 x 8192
tokens) forward + backward, with the counter
``moe_width_computed_over_published`` it sows.

A CPU run (``--tiny``) rehearses the control flow and prints no time."""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp

ROWS, GROUPS = 98_304, 9
# (hidden, expert width) of each line; the first is the published pair
SHAPES = ((2688, 1856), (2688, 1920), (2688, 2048), (3072, 2048), (2048, 1792))
KERNELS = ("up.fwd", "up.d_rows", "up.d_weights",
           "down.fwd", "down.d_rows", "down.d_weights")


def group_sizes(rows: int) -> jax.Array:
    """Eight held experts sharing a sixteenth of the rows evenly and the
    zero-weight group with the rest: an even routing at 8 of 128."""
    held = rows // 16 // (GROUPS - 1)
    return jnp.asarray([held] * (GROUPS - 1) + [rows - held * (GROUPS - 1)],
                       jnp.int32)


def kernels(rows: int, h: int, f: int, key) -> dict:
    """``name -> (jitted function, operands)`` of the six grouped matmuls of
    a non-gated expert at ``(rows, h)`` activations and width ``f``."""
    ks = jax.random.split(key, 4)
    bf = jnp.bfloat16
    xs = jax.random.normal(ks[0], (rows, h), bf)
    hid = jax.random.normal(ks[1], (rows, f), bf)
    w_up = jax.random.normal(ks[2], (GROUPS, h, f), bf) * h ** -0.5
    w_down = jax.random.normal(ks[3], (GROUPS, f, h), bf) * f ** -0.5
    gs = group_sizes(rows)

    def dot(a, w):
        return jax.lax.ragged_dot(a, w, gs)

    def d_rows(a, w, ct):
        return jax.vjp(lambda a: dot(a, w), a)[1](ct)[0]

    def d_weights(a, w, ct):
        return jax.vjp(lambda w: dot(a, w), w)[1](ct)[0]

    return {
        "up.fwd": (jax.jit(dot), (xs, w_up)),
        "up.d_rows": (jax.jit(d_rows), (xs, w_up, hid)),
        "up.d_weights": (jax.jit(d_weights), (xs, w_up, hid)),
        "down.fwd": (jax.jit(dot), (hid, w_down)),
        "down.d_rows": (jax.jit(d_rows), (hid, w_down, xs)),
        "down.d_weights": (jax.jit(d_weights), (hid, w_down, xs)),
    }


def timed_ms(fn, args, reps: int) -> float:
    """Median over three sets of the milliseconds one execution takes: ``reps``
    dispatched back to back, the last one waited for (a device runs its
    programs in the order they were dispatched)."""
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


def kernels_phase(tag: str, rows: int, shapes, peak, reps: int, seed: int):
    table = []
    for h, f in shapes:
        flops = 2.0 * rows * h * f
        line = {"rows": rows, "hidden": h, "width": f,
                "ms_at_peak": flops / peak * 1e3 if peak else None}
        for name, (fn, args) in kernels(rows, h, f, jax.random.PRNGKey(seed)).items():
            ms = timed_ms(fn, args, reps)
            if peak:  # a time only from a chip
                line[name] = {"ms": ms, "peak_share_pct": flops / peak / ms * 1e5}
        if peak:
            line["six_ms"] = sum(line[k]["ms"] for k in KERNELS)
            print(f"{tag} kernels h={h} f={f} rows={rows}: " + "  ".join(
                f"{k} {line[k]['ms']:.2f} ms {line[k]['peak_share_pct']:.1f}%"
                for k in KERNELS) + f"  | six {line['six_ms']:.2f} ms, "
                f"{line['ms_at_peak']:.2f} ms a product at the peak", flush=True)
        else:
            print(f"{tag} kernels h={h} f={f} rows={rows}: six kernels ran "
                  "(no time from a CPU)", flush=True)
        table.append(line)
    return table


def layer_phase(tag: str, tokens: int, h: int, f: int, peak, reps: int, seed: int):
    """The program's expert layer at the cell's widths, forward + backward."""
    from accelerate_tpu.models import TransformerConfig
    from accelerate_tpu.models.transformer import MoE

    cfg = TransformerConfig(
        vocab_size=256, hidden_size=h, intermediate_size=f,
        moe_intermediate_size=f, num_layers=1, num_heads=1, num_kv_heads=1,
        head_dim=128, mlp_activation="relu2", mlp_gated=False, num_experts=8,
        num_experts_per_tok=6, moe_router_width=128, moe_expert_offset=0,
        moe_router="sigmoid", moe_norm_topk_prob=True, moe_norm_topk_eps=1e-20,
        moe_routed_scaling_factor=2.5, dtype="bfloat16", scan_layers=False)
    moe = MoE(cfg)
    kx, kp = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (2, tokens // 2, h), jnp.bfloat16)
    params = jax.jit(moe.init)(kp, x)["params"]

    def loss(params, x):
        out, sown = moe.apply({"params": params}, x, mutable=["intermediates"])
        return jnp.sum(out.astype(jnp.float32) ** 2), sown["intermediates"]

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))
    (_, sown), _ = step(params, x)
    counters = {k: float(v[0]) for k, v in sown.items()}
    ratio = counters.get("moe_width_computed_over_published")
    line = {"tokens": tokens, "hidden": h, "width": f, "counters": counters}
    if peak:
        line["fwd_bwd_ms"] = timed_ms(step, (params, x), reps)
        print(f"{tag} layer h={h} f={f} tokens={tokens}: forward + backward "
              f"{line['fwd_bwd_ms']:.2f} ms, "
              f"moe_width_computed_over_published {ratio}", flush=True)
    else:
        print(f"{tag} layer h={h} f={f} tokens={tokens}: ran (no time from a "
              f"CPU), moe_width_computed_over_published {ratio}", flush=True)
    return line


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phases", default="kernels,layer")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--shapes", default=None,
                    help="other lines for the kernels phase, as 2816x2048,3072x2304")
    ap.add_argument("--tiny", action="store_true",
                    help="tiny shapes, for a CPU rehearsal: no time is printed")
    args = ap.parse_args()

    dev = jax.devices()[0]
    tag = f"[{dev.platform} {dev.device_kind} x{jax.device_count()}]"
    if dev.platform != "tpu" and not args.tiny:
        print(f"{tag} no TPU: a time comes from a chip alone (--tiny rehearses)",
              file=sys.stderr)
        return 2
    peak = None
    if dev.platform == "tpu":
        from accelerate_tpu.profiling.registry import device_peaks

        peak = device_peaks(dev.device_kind)["flops_per_s"]
    rows, shapes, tokens, layer = ROWS, SHAPES, 16_384, (2688, 1856)
    if args.shapes:
        shapes = tuple(tuple(int(n) for n in s.split("x"))
                       for s in args.shapes.split(","))
    if args.tiny:
        rows, shapes, tokens, layer = 512, ((48, 29), (48, 32)), 64, (16, 464)
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": jax.device_count()}, "peak_flops_per_s": peak}
    phases = args.phases.split(",")
    if "kernels" in phases:
        out["kernels"] = kernels_phase(tag, rows, shapes, peak, args.reps, args.seed)
    if "layer" in phases:
        out["layer"] = layer_phase(tag, tokens, *layer, peak, args.reps, args.seed)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

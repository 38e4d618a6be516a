#!/usr/bin/env python3
"""What the chunked state-space scan costs one Mamba-2 layer of a training
step, by hand, on the chip, at the shapes of ``train-ssm-moe-1chip`` (2 rows
of 8,192, 64 heads of 64 in 8 groups of state 128, chunks of 128; PERF.md
section 6, PR 47):

    python3 ssd_scan_on_chip.py [--groups 8,16] [--rows 2] [--seq 8192]
    python3 ssd_scan_on_chip.py --stack
    JAX_PLATFORMS=cpu python3 ssd_scan_on_chip.py --tiny

Two forms a line, the milliseconds one call of each takes, forward alone
(``fwd``) and the gradient of a sum over ``y`` with respect to all six operands
(``grad``: the forward that saves what the backward needs, and the backward):

* ``jnp`` — ``ops.ssd._chunked_reference``: four einsums, the Q x Q decay
  masks in HBM, a ``lax.scan`` over the chunks, plain autodiff (what a step
  ran before PR 47 and runs off a TPU and over a mesh);
* ``kernel`` — ``ops.ssd.ssd_chunked``: ``ssd_chunked_fwd`` and
  ``ssd_chunked_bwd``.

Beside them the widest difference between the two forms, in ``y`` and in each
gradient over the gradient's own largest entry, and ``step``: what a layer's
scan costs a training step under the layer's remat (``fwd + grad``: forward,
forward again, backward) with the share of it that the needed work of
``benchmark/harness/nemotron_h_work.py::ssm_scan_work`` (one layer of the
cell's four) would take at the chip's peaks — what ``ssm_scan_roofline.train``
reads over the four layers.
``--groups``: other groupings of the same 64 heads (a grid step holds one
group: 8 groups are 8 heads a step, 16 are 4), to choose the block.

``--stack``: instead of the scan alone, the cell's own stack as its runner
builds it (``Accelerator(mixed_precision="bf16")``, the seeded weights, remat
``dots_with_no_batch_dims``, AdamW): one forward pass that prints the
``ssm_scan_kernel`` counter of each Mamba-2 layer, then three steps of
``unified_step(loss_fn(with_aux=True), has_aux=True)`` that print the
counters' means as the step returns them.

A CPU run (``--tiny``) interprets the kernels at a toy size, holds the two
forms to one another and prints no time."""

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
    ROOT, "benchmark", "configs", "nemotron-3-nano-30b-a3b-train-1chip.json")
# bfloat16 operands, float32 sums taken in another order: y and dx, dB, dC are
# rounded to bfloat16 once by either form (one unit in the last place is
# 2 ** -8 of the value, the widest entries a few units); the float32
# gradients are sums over 2 x 8,192 x 64 such products. Read on the chip at
# the cell's shapes: at most 7.8e-3 (dx; PERF.md section 6, PR 47)
GAP = 2e-2


def operands(rows, seq, heads, p, groups, n, seed, dtype=jnp.bfloat16):
    """What a layer's projection and convolution hand the scan: ``x``, ``B``
    and ``C`` behind a silu, ``delta`` log-uniform in [1e-3, 0.1] as the
    family's ``dt_bias`` draws it, ``A`` = -U(1, 16), ``D`` near 1. ``x``
    comes flat (B, S, H P), as the convolution leaves it."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    act = lambda k, shape: jax.nn.silu(jax.random.normal(k, shape)).astype(dtype)
    x = act(ks[0], (rows, seq, heads * p))
    delta = jnp.exp(jax.random.uniform(
        ks[1], (rows, seq, heads), minval=jnp.log(1e-3), maxval=jnp.log(0.1)))
    a = -jax.random.uniform(ks[2], (heads,), minval=1.0, maxval=16.0)
    b_mat = act(ks[3], (rows, seq, groups * n))
    c_mat = act(ks[4], (rows, seq, groups * n))
    skip = 1.0 + 0.1 * jax.random.normal(ks[5], (heads,))
    return x, delta, a, b_mat, c_mat, skip


def forms(heads, p, groups, n, chunk):
    """``{form: (fwd, grad)}``, jitted, over the flat operands."""
    from accelerate_tpu.ops import ssd

    def scan(fn):
        def fwd(x, delta, a, b_mat, c_mat, skip):
            bsz, s = x.shape[:2]
            y = fn(x.reshape(bsz, s, heads, p), delta, a,
                   b_mat.reshape(bsz, s, groups, n),
                   c_mat.reshape(bsz, s, groups, n), chunk, skip=skip)
            return y.reshape(bsz, s, heads * p)

        def total(*ops):  # a cotangent that differs from entry to entry
            y = fwd(*ops).astype(jnp.float32)
            return jnp.sum(y * jnp.cos(jnp.arange(y.shape[-1]) * 0.37))

        return jax.jit(fwd), jax.jit(jax.grad(total, argnums=tuple(range(6))))

    return {"jnp": scan(ssd._chunked_reference), "kernel": scan(ssd.ssd_chunked)}


def relative(got, want):
    got, want = (t.astype(jnp.float32) for t in (got, want))
    return float(jnp.max(jnp.abs(got - want)) / (jnp.max(jnp.abs(want)) + 1e-30))


def case(tag, rows, seq, heads, p, groups, n, chunk, reps, needs, seed, gap):
    """``needs``: the least seconds the chip could take over one layer's
    scan of a step; None on a CPU (no time is read)."""
    from accelerate_tpu.ops import ssd

    assert ssd.ssd_kernel_eligible(heads, p, groups, n, chunk), (
        heads, p, groups, n, chunk)
    ops = operands(rows, seq, heads, p, groups, n, seed,
                   jnp.float32 if needs is None else jnp.bfloat16)
    both = forms(heads, p, groups, n, chunk)
    line = {"rows": rows, "seq": seq, "heads": heads, "groups": groups,
            "heads_a_step": heads // groups}
    y = {name: fwd(*ops) for name, (fwd, _) in both.items()}
    grads = {name: grad(*ops) for name, (_, grad) in both.items()}
    line["gap"] = {"y": relative(y["kernel"], y["jnp"])}
    for name, got, want in zip(("x", "delta", "a", "B", "C", "D"),
                               grads["kernel"], grads["jnp"]):
        line["gap"]["d" + name] = relative(got, want)
    text = ""
    if needs is not None:
        for name, (fwd, grad) in both.items():
            line[name + "_fwd_ms"] = timed_ms(fwd, ops, reps)
            line[name + "_grad_ms"] = timed_ms(grad, ops, reps)
            line[name + "_step_ms"] = line[name + "_fwd_ms"] + line[name + "_grad_ms"]
        line["kernel_roofline"] = needs / (line["kernel_step_ms"] * 1e-3)
        line["jnp_roofline"] = needs / (line["jnp_step_ms"] * 1e-3)
        text = "".join(
            f"  {name} fwd {line[name + '_fwd_ms']:.3f} grad "
            f"{line[name + '_grad_ms']:.3f} step {line[name + '_step_ms']:.3f} ms "
            f"({100 * line[name + '_roofline']:.1f} % of its roofline)"
            for name in both)
        text += f"  {line['jnp_step_ms'] / line['kernel_step_ms']:.2f} x"
    print(f"{tag} ssd {rows} x {seq}, {heads} heads of {p} in {groups} groups "
          f"of state {n}, chunks of {chunk}:{text}  widest gap "
          + " ".join(f"{k}={v:.1e}" for k, v in line["gap"].items()), flush=True)
    assert all(g < gap for g in line["gap"].values()), line
    return line


def stack(tag, tiny: bool, seed: int) -> dict:
    """The counter from the cell's own stack: a layer each, then through
    ``unified_step``."""
    import numpy as np
    import optax

    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    sys.path.insert(0, os.path.join(ROOT, "benchmark", "tests"))
    from harness import common
    from harness import nemotron_h_weights as weights

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import CausalLM

    if tiny:
        import tiny_nemotron_h

        cfg, rows, seq, dtype, precision = tiny_nemotron_h.config(), 2, 48, "float32", "no"
    else:
        with open(CONFIG) as f:
            cfg = json.load(f)
        rows, seq, dtype, precision = 2, cfg["max_position_embeddings"], "bfloat16", "bf16"
    acc = Accelerator(mixed_precision=precision)
    model = CausalLM(common.program_config(
        cfg, max_seq_len=seq, remat="dots_with_no_batch_dims", dtype=dtype))
    params, optimizer = acc.prepare(
        weights.make_tree(cfg, seed, jnp.float32), optax.adamw(3e-4))
    ids = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (rows, seq)), jnp.int32)
    _, sown = jax.jit(lambda p, i: model.apply(
        {"params": p}, i, mutable=["intermediates"]))(params, ids)
    flat = jax.tree_util.tree_flatten_with_path(sown["intermediates"])[0]
    layers = {jax.tree_util.keystr(path): [float(v) for v in np.ravel(value)]
              for path, value in flat if "ssm_scan_kernel" in jax.tree_util.keystr(path)}
    step = acc.unified_step(CausalLM.loss_fn(model, with_aux=True), has_aux=True)
    carry = acc.init_carry(params, optimizer)
    del params
    means = []
    for _ in range(3):
        carry, metrics = step(carry, {"input_ids": ids})
        means.append({"loss": float(metrics["loss"]), **{
            k: float(v) for k, v in metrics["aux"].items() if k.startswith("ssm_")}})
    print(f"{tag} ssm_scan_kernel a layer: {layers}", flush=True)
    print(f"{tag} through unified_step, three steps: {means}", flush=True)
    return {"ssm_scan_kernel": layers, "steps": means}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--groups", default=None,
                    help="groupings of the heads to try (default: the cell's)")
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--stack", action="store_true",
                    help="the cell's own stack and its ssm_scan_kernel counter")
    ap.add_argument("--tiny", action="store_true",
                    help="toy shapes, interpreted, for a CPU rehearsal: no time")
    args = ap.parse_args()

    dev = jax.devices()[0]
    tag = f"[{dev.platform} {dev.device_kind} x{jax.device_count()}]"
    if dev.platform != "tpu" and not args.tiny:
        print(f"{tag} no TPU: a time comes from a chip alone (--tiny rehearses)",
              file=sys.stderr)
        return 2
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": jax.device_count()}}
    if args.stack:
        from accelerate_tpu.ops.flash_attention import kernel_interpret_mode

        with kernel_interpret_mode():  # nothing on a TPU
            out["stack"] = stack(tag, args.tiny, args.seed)
    elif args.tiny:
        from accelerate_tpu.ops.flash_attention import kernel_interpret_mode

        with kernel_interpret_mode(), jax.default_matmul_precision("highest"):
            out["cases"] = [
                case(tag, 2, seq, 8, 4, groups, 8, 8, 1, None, args.seed, 1e-4)
                for seq, groups in ((32, 2), (29, 2), (32, 4))]
    else:
        sys.path.insert(0, os.path.join(ROOT, "benchmark"))
        from harness.nemotron_h_weights import mamba_dims
        from harness.nemotron_h_work import ssm_scan_work

        from accelerate_tpu.profiling.registry import device_peaks

        with open(CONFIG) as f:
            cfg = json.load(f)
        heads, p, groups, n, _ = mamba_dims(cfg)
        rows = args.rows or 2
        seq = args.seq or cfg["max_position_embeddings"]
        peaks = device_peaks(dev.device_kind)
        layers = cfg["hybrid_override_pattern"].count("M")
        # the benchmark's own count, one layer of its four (the needed work
        # does not depend on the grouping tried: B and C are read once)
        work = ssm_scan_work(cfg, {"tokens_per_step_per_chip": rows * seq})
        needs = max(work["flops"] / peaks["flops_per_s"],
                    work["bytes"] / peaks["hbm_bytes_per_s"]) / layers
        out["needed_ms_a_layer"] = needs * 1e3
        out["cases"] = [
            case(tag, rows, seq, heads, p, g, n, cfg["chunk_size"], args.reps,
                 needs, args.seed, GAP)
            for g in ([int(g) for g in args.groups.split(",")]
                      if args.groups else [groups])]
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

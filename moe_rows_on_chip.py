#!/usr/bin/env python3
"""What it costs ``accelerate_tpu.ops.moe.moe_ragged`` to move rows between
token order and expert-sorted order, by hand, on the chip, at the shapes of
one expert layer of the three cells that run one (PERF.md section 6, PR 39):

    python3 moe_rows_on_chip.py [--phases inverse,layer,pieces,profile] [--cases a,b] [--parent FILE]
    JAX_PLATFORMS=cpu python3 moe_rows_on_chip.py --tiny

``inverse``: the inverse of the sort's permutation of ``T k`` choices three
ways — ``argsort(order)``, one ``lax.sort`` of ``(order, iota)``, and the
scalar ``.at[order].set(arange)``.

``layer``: ``moe_ragged`` itself (bf16, float32 routing weights) — forward
alone for the serving cases, forward + backward to every operand under the
cell's own ``jax.checkpoint`` policy for the training ones. With ``--parent
FILE`` (an ``ops/moe.py`` of another commit, ``git show HEAD:... > FILE``)
the same line is read for that file's ``moe_ragged`` too.

``profile`` (not among the default phases): the same layer lines under the
profiler, self time by scope, parent beside change.

``pieces``: the operations alone at a case's rows — the gather of ``T k``
rows from ``T`` (dispatch), the weighted scatter-add of a window's rows onto
``T`` tokens, and the gather of ``T k`` places from the window's rows with
the sum over the ``k`` choices.

A CPU run (``--tiny``) rehearses the control flow and prints no time."""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp

# tokens, choices a token, hidden, expert width, held, router width, gated,
# forward only, remat policy (training), the cell
CASES = {
    "prefill_4k": (4096, 10, 2048, 512, 256, 512, True, True, None, "serve-gdn-moe-sat"),
    "prefill_8k": (8192, 10, 2048, 512, 256, 512, True, True, None, "serve-gdn-moe-sat"),
    "decode_64": (64, 10, 2048, 512, 256, 512, True, True, None, "serve-gdn-moe-sat"),
    "ssm_moe": (16384, 6, 2688, 1856, 8, 128, False, False,
                "dots_with_no_batch_dims", "train-ssm-moe-1chip"),
    "moe_conv": (16384, 4, 2048, 1792, 8, 32, True, False, "dots_ragged",
                 "train-moe-conv-1chip"),
}
TINY = {
    "prefill_4k": (96, 4, 16, 8, 4, 8, True, True, None, "tiny"),
    "ssm_moe": (256, 4, 16, 8, 2, 16, False, False, "dots_with_no_batch_dims", "tiny"),
    "moe_conv": (256, 4, 16, 8, 4, 16, True, False, "dots_ragged", "tiny"),
}


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


def _relu2(v):
    return jnp.square(jax.nn.relu(v))


def operands(case, seed: int):
    t, k, h, f, held, width, gated, *_ = case
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    bf = jnp.bfloat16
    sel = jax.lax.top_k(jax.random.uniform(ks[0], (t, width)), k)[1]  # k distinct, even
    return sel, (
        jax.random.normal(ks[1], (t, h), bf),
        jax.random.uniform(ks[2], (t, k), jnp.float32) / k,
        jax.random.normal(ks[3], (held, h, f), bf) * h ** -0.5 if gated else None,
        jax.random.normal(ks[4], (held, h, f), bf) * h ** -0.5,
        jax.random.normal(ks[5], (held, f, h), bf) * f ** -0.5)


def layer_fn(moe_ragged, case):
    """The jitted thing a cell runs of one expert layer: the forward pass of
    a serving call, or value and gradients under the cell's remat policy
    (the choices an argument: a constant would be sorted by the compiler)."""
    from accelerate_tpu.models.transformer import _REMAT_POLICIES

    *_, width, gated, forward_only, remat, _ = case

    def layer(x, weights, w_gate, w_up, w_down, sel):
        return moe_ragged(
            x, sel, weights, w_gate, w_up, w_down, router_width=width,
            activation=None if gated else _relu2, forward_only=forward_only)

    if forward_only:
        return jax.jit(layer)
    kept = jax.checkpoint(layer, policy=_REMAT_POLICIES[remat]())
    argnums = (0, 1, 2, 3, 4) if gated else (0, 1, 3, 4)
    return jax.jit(jax.grad(
        lambda *a: jnp.sum(kept(*a).astype(jnp.float32) ** 2), argnums=argnums))


def inverse_phase(tag, names, cases, reps, on_chip):
    forms = {
        "argsort": lambda order: jnp.argsort(order),
        "argsort_unstable": lambda order: jnp.argsort(order, stable=False),
        "sort_pair": lambda order: jax.lax.sort(
            (order, jnp.arange(order.shape[0], dtype=order.dtype)),
            num_keys=1, is_stable=False)[1],
        "scatter": lambda order: jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype), unique_indices=True),
    }
    table = []
    for name in names:
        t, k = cases[name][:2]
        order = jax.random.permutation(jax.random.PRNGKey(1), t * k).astype(jnp.int32)
        want = jnp.argsort(order)
        line = {"case": name, "choices": t * k}
        for form, fn in forms.items():
            got = jax.jit(fn)(order)
            assert bool(jnp.all(got == want)), form
            if on_chip:
                line[form] = timed_ms(jax.jit(fn), (order,), reps)
        print(f"{tag} inverse {name} T k = {t * k}: " + (
            "  ".join(f"{f} {line[f]:.3f} ms" for f in forms) if on_chip
            else "four forms agree (no time from a CPU)"), flush=True)
        table.append(line)
    return table


def layer_phase(tag, names, cases, reps, on_chip, seed, parent, dump):
    from accelerate_tpu.ops import moe

    sides = {"change": moe.moe_ragged}
    if parent is not None:
        sides["parent"] = parent.moe_ragged
    table = []
    for name in names:
        case = cases[name]
        sel, args = operands(case, seed)
        line = {"case": name, "cell": case[-1],
                "what": "forward" if case[7] else "forward+backward"}
        outs = {}
        for side, fn in sides.items():
            step = layer_fn(fn, case)
            outs[side] = jax.tree.leaves(step(*args, sel))
            if dump:  # the compiled text: which fusion holds which gather
                os.makedirs(dump, exist_ok=True)
                with open(os.path.join(dump, f"{name}.{side}.hlo.txt"), "w") as f:
                    f.write(step.lower(*args, sel).compile().as_text())
            if on_chip:
                line[side + "_ms"] = timed_ms(step, (*args, sel), reps)
        if "parent" in outs:  # the same work: the widest gap over its largest entry
            line["gap"] = max(
                float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
                      / (jnp.max(jnp.abs(b.astype(jnp.float32))) + 1e-30))
                for a, b in zip(outs["change"], outs["parent"]))
        print(f"{tag} layer {name} ({line['what']}, {case[0]} x {case[1]} rows of "
              f"{case[2]}): " + "  ".join(
                  f"{k} {v:.3f}" if isinstance(v, float) else f"{k} {v}"
                  for k, v in line.items() if k not in ("case", "cell", "what")),
              flush=True)
        table.append(line)
    return table


def profile_phase(tag, names, cases, reps, seed, parent, top=28):
    """Where a layer line's time goes: ``reps`` executions under the
    profiler, self time by the scope each operation was traced in
    (``benchmark/readers/scope_share.by_scope``), milliseconds an execution."""
    import tempfile

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "benchmark"))
    from harness import trace_reduce
    from readers import scope_share

    from accelerate_tpu.ops import moe

    sides = {"change": moe.moe_ragged}
    if parent is not None:
        sides["parent"] = parent.moe_ragged
    table = []
    for name in names:
        case = cases[name]
        sel, args = operands(case, seed)
        for side, fn in sides.items():
            step = layer_fn(fn, case)
            jax.block_until_ready(step(*args, sel))
            where = tempfile.mkdtemp(prefix=f"moe_rows_{name}_{side}_")
            with jax.profiler.trace(where):
                for _ in range(reps):
                    out = step(*args, sel)
                jax.block_until_ready(out)
            seconds, total = scope_share.by_scope(
                trace_reduce.find_xplane(where), "jit_", "")
            rows = sorted(seconds.items(), key=lambda kv: -kv[1])
            print(f"{tag} profile {name} {side}: {total / reps * 1e3:.3f} ms of "
                  "device self time an execution", flush=True)
            for scope, secs in rows[:top]:
                print(f"    {secs / reps * 1e3:8.3f} ms  {scope}", flush=True)
            table.append({"case": name, "side": side, "ms": total / reps * 1e3,
                          "scopes": {k: v / reps * 1e3 for k, v in rows}})
    return table


def pieces_phase(tag, names, cases, reps, on_chip, seed):
    """The operations alone: rows ``(T k, h)`` bf16 in sorted order, a window
    of the first ``C`` of them (``share_window_rows``)."""
    from accelerate_tpu.ops.moe import share_window_rows

    table = []
    for name in names:
        t, k, h, _, held, width = cases[name][:6]
        tk = t * k
        c = share_window_rows(tk, held, width)
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        order = jax.random.permutation(ks[0], tk).astype(jnp.int32)
        inv = jnp.argsort(order).astype(jnp.int32).reshape(k, t)  # choice-major
        tok = order % t
        x = jax.random.normal(ks[1], (t, h), jnp.bfloat16)
        rows = jax.random.normal(ks[2], (c, h), jnp.bfloat16)
        w = jax.random.uniform(ks[3], (k, t), jnp.float32)
        w_flat = w.reshape(-1)[order][:c].astype(jnp.bfloat16)
        place, ok = jnp.clip(inv, 0, c - 1), inv < c

        def gather(x, tok):
            return jnp.take(x, tok[:c], axis=0)

        def scatter_add(rows, tok, w_flat):
            return jnp.zeros((t, h), rows.dtype).at[tok[:c]].add(rows * w_flat[:, None])

        def gather_sum(rows, place, ok, w):
            picked = rows.at[place].get(mode="promise_in_bounds")
            picked = jnp.where(ok[..., None], picked, 0).astype(jnp.float32)
            return jnp.sum(picked * w[..., None], axis=0).astype(rows.dtype)

        forms = {"gather_C_rows": (gather, (x, tok)),
                 "scatter_add_C_rows": (scatter_add, (rows, tok, w_flat)),
                 "gather_Tk_places_and_sum": (gather_sum, (rows, place, ok, w))}
        line = {"case": name, "choices": tk, "window": c, "hidden": h}
        for form, (fn, args) in forms.items():
            jax.block_until_ready(jax.jit(fn)(*args))
            if on_chip:
                line[form] = timed_ms(jax.jit(fn), args, reps)
        print(f"{tag} pieces {name} T k = {tk}, window {c}, h {h}: " + (
            "  ".join(f"{f} {line[f]:.3f} ms" for f in forms) if on_chip
            else "three forms ran (no time from a CPU)"), flush=True)
        table.append(line)
    return table


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phases", default="inverse,layer,pieces")
    ap.add_argument("--cases", default=None, help="of " + ",".join(CASES))
    ap.add_argument("--parent", default=None,
                    help="an ops/moe.py of another commit to read the same lines for")
    ap.add_argument("--dump", default=None,
                    help="a directory for the compiled text of every layer line")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--tiny", action="store_true",
                    help="tiny shapes, for a CPU rehearsal: no time is printed")
    args = ap.parse_args()

    dev = jax.devices()[0]
    tag = f"[{dev.platform} {dev.device_kind} x{jax.device_count()}]"
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.tiny:
        print(f"{tag} no TPU: a time comes from a chip alone (--tiny rehearses)",
              file=sys.stderr)
        return 2
    cases = TINY if args.tiny else CASES
    names = args.cases.split(",") if args.cases else list(cases)
    parent = None
    if args.parent:
        spec = importlib.util.spec_from_file_location("parent_moe", args.parent)
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": jax.device_count()}}
    phases = args.phases.split(",")
    if "inverse" in phases:
        out["inverse"] = inverse_phase(tag, names, cases, args.reps, on_chip)
    if "layer" in phases:
        out["layer"] = layer_phase(
            tag, names, cases, args.reps, on_chip, args.seed, parent, args.dump)
    if "profile" in phases:
        out["profile"] = profile_phase(tag, names, cases, args.reps, args.seed, parent)
    if "pieces" in phases:
        out["pieces"] = pieces_phase(tag, names, cases, args.reps, on_chip, args.seed)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

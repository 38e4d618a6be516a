"""The ``train`` kind: a closed loop of optimizer steps through
``Accelerator.prepare`` + ``unified_step`` (AOT-warmed), fed by a
``DataLoader`` over the seeded token stream, every step a new batch.

Set-up builds ONE object — the compiled step with its state — drives it from
the seed through its first ``checked_steps`` steps through the window's own
call and feed, and hands that same object to the window. After the window the
program is freed and the plain reference follows those first steps.
"""

from __future__ import annotations

import collections
import gc
import math
import statistics
import time

import numpy as np

from . import common, traffic


class _Rows:
    """Map-style dataset over the token rows (what DataLoader indexes)."""

    def __init__(self, rows):
        self.rows = rows

    def __len__(self):
        return len(self.rows)

    def __getitem__(self, i):
        return {"input_ids": self.rows[i]}


def _adam_mu(opt_state):
    """The first moment inside an optax chain's state."""
    import jax

    found = [s.mu for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise ValueError(f"expected one Adam state, found {len(found)}")
    return found[0]


def _leaf_norms(tree, leaf_norms) -> dict:
    import jax

    return {k: float(v) for k, v in jax.jit(leaf_norms)(tree).items()}


def worst_leaf_gap(got: dict, want: dict) -> tuple[float, str]:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some gradients are all but zero)."""
    floor = statistics.median(want.values())
    worst, where = 0.0, ""
    for key, ref in want.items():
        gap = abs(got[key] - ref) / max(ref, floor)
        if not gap <= worst:  # NaN counts as worst
            worst, where = gap, key
    return worst, where


def compare(prog: dict, ref: dict, limits: dict, chk) -> None:
    """Hold ``prog``'s first steps (the program's, or the control's) against
    the reference's: each step's loss, the first gradient and the parameters'
    change, both by the worst leaf."""
    for k, (got, want) in enumerate(zip(prog["loss"], ref["loss"]), start=1):
        chk.limit(f"loss_gap_step{k}", abs(got - want), limits["loss_gap"][k - 1])
    gap, where = worst_leaf_gap(prog["first_grad_leaf_norms"],
                                ref["first_grad_leaf_norms"])
    chk.limit("first_grad_worst_leaf_gap", gap, limits["first_grad_worst_leaf_gap"])
    chk.say(f"  (worst first-gradient leaf: {where})")
    gap, where = worst_leaf_gap(prog["param_change_leaf_norms"],
                                ref["param_change_leaf_norms"])
    chk.limit("param_change_worst_leaf_gap", gap,
              limits["param_change_worst_leaf_gap"])
    chk.say(f"  (worst parameter-change leaf: {where})")


def run(cell, seed, seconds, trace, t_start, say):
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu import (
        Accelerator, AcceleratorState, DataLoader, GradientState,
        ParallelismPlugin,
    )
    from accelerate_tpu.models import CausalLM

    spec, cfg = cell["spec"], cell["config"]
    reference, weights = common.modules_of(cfg)
    opt, limits = spec["optimizer"], spec["limits"]
    n_dev = cell["chips"]
    cache_dir, monitor = common.activate_cache()
    say(f"cell {cell['name']}: {cfg['num_hidden_layers']} layers, "
        f"{spec['rows_per_chip']}x{spec['traffic']['seq_len']} tokens per chip "
        f"x {n_dev}; compile cache {cache_dir}")
    compile_before = monitor.snapshot()

    # ---- inputs from the seed ------------------------------------------ #
    rows = traffic.token_rows(spec["traffic"], seed, cfg["vocab_size"])
    batch_rows = spec["rows_per_chip"] * n_dev
    seq = spec["traffic"]["seq_len"]
    n_checked = int(spec["checked_steps"])

    # ---- the system under test ------------------------------------------ #
    acc = Accelerator(mixed_precision=spec["mixed_precision"],
                      parallelism_plugin=ParallelismPlugin(fsdp_size=-1))
    model = CausalLM(common.program_config(
        cfg, max_seq_len=seq, remat=spec["remat"], dtype=spec["compute_dtype"]))
    # born on the shards the program will keep them on (one chip: whole)
    from accelerate_tpu.parallel.sharding import infer_param_shardings

    raw = weights.make_tree(
        cfg, seed, jnp.float32, out_shardings=infer_param_shardings(
            weights.abstract_tree(cfg, jnp.float32), acc.mesh,
            acc.state.parallelism_plugin))
    params, optimizer, loader = acc.prepare(
        raw, optax.adamw(opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                         weight_decay=opt["weight_decay"]),
        DataLoader(_Rows(rows), batch_size=batch_rows, drop_last=True))
    del raw
    step = acc.unified_step(CausalLM.loss_fn(model),
                            max_grad_norm=opt["max_grad_norm"])
    carry = acc.init_carry(params, optimizer)
    del params
    warm = acc.warmup(step, carry, loader)
    say(f"warmup: compile {warm['compile_time_s']:.1f}s, persistent cache "
        f"hits={warm['persistent_cache_hits']} misses={warm['persistent_cache_misses']}")

    def batches():
        epoch = 0
        while True:
            loader.set_epoch(epoch)
            yield from loader
            epoch += 1

    feed = batches()

    # ---- the first steps, through the window's own call and feed -------- #
    prog = {"loss": [], "fed_rows_match": True}
    for k in range(n_checked):
        batch = next(feed)
        want = rows[k * batch_rows:(k + 1) * batch_rows]
        prog["fed_rows_match"] &= bool(
            np.array_equal(np.asarray(batch["input_ids"]), want))
        carry, metrics = step(carry, batch)
        prog["loss"].append(float(metrics["loss"]))
        if k == 0:
            mu = _leaf_norms(_adam_mu(carry["opt_state"]), reference.leaf_norms)
            prog["first_grad_leaf_norms"] = {
                key: v / (1.0 - opt["b1"]) for key, v in mu.items()}
    prog["param_change_leaf_norms"] = reference.param_change_leaf_norms(
        cfg, seed, carry["params"])
    jax.block_until_ready(carry["params"])
    setup_compile = monitor.delta(compile_before)

    # ---- the measured window -------------------------------------------- #
    tracer = common.Tracer(cell, trace)
    in_window = monitor.snapshot()
    losses, done_t, wait_s = [], [], 0.0
    # the loop reads a loss ``lag`` steps behind its dispatch, as a loop that
    # logs every few steps does: the device then has work queued while the
    # host prepares the next batch — or stalls for a second, as this machine's
    # host does now and then (PERF.md, PR 23)
    lag = int(spec.get("loss_fetch_lag", 8))
    pending = collections.deque()
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    while True:
        tracer.tick(time.perf_counter() - t0, seconds)
        tw = time.perf_counter()
        with tracer.span("next_batch"):
            batch = next(feed)
        wait_s += time.perf_counter() - tw
        with tracer.span("step"):
            carry, metrics = step(carry, batch)
        pending.append(metrics)
        if len(pending) > lag:
            with tracer.span("fetch_loss"):
                losses.append(float(pending.popleft()["loss"]))
            done_t.append(time.perf_counter() - t0)
        if time.perf_counter() - t0 >= seconds:
            break
    for metrics in pending:  # the window ends when the last step has ended
        losses.append(float(metrics["loss"]))
        done_t.append(time.perf_counter() - t0)
    jax.block_until_ready(carry["params"])
    window_s = done_t[-1] = time.perf_counter() - t0
    tracer.stop()
    window_compile = monitor.delta(in_window)

    # ---- what the window produced, then free the program ---------------- #
    peak = common.memory_peak_bytes_of(jax)
    fallbacks = int(step.aot_fallbacks) if hasattr(step, "aot_fallbacks") else 0
    feed.close()
    del carry, step, optimizer, loader, acc, metrics, batch, pending, feed
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    gc.collect()

    t_ref = time.perf_counter()
    ref = reference.train_reference(
        cfg, opt, seed,
        [rows[k * batch_rows:(k + 1) * batch_rows] for k in range(n_checked)],
        rows_per_block=int(spec.get("reference_rows_per_block", 1)),
        sharding=weights.spread_shardings(cfg, jax.local_devices()[:n_dev]))
    ref_s = time.perf_counter() - t_ref

    chk = common.Checks(say)
    chk.exact("fed_rows_match_the_seeded_stream", prog["fed_rows_match"], True)
    compare(prog, ref, limits, chk)
    chk.exact("losses_in_window_all_finite",
              all(math.isfinite(x) for x in losses), True)
    chk.exact("last_window_loss_below_first_checked",
              bool(losses[-1] < prog["loss"][0]), True)
    chk.exact("compiles_in_window", common.compiles_in(window_compile), 0)
    chk.exact("aot_fallbacks", fallbacks, 0)
    say(f"setup_s {setup_s:.2f} (compile {setup_compile['compile_time_s']:.1f}s "
        f"inside it); peak {peak / 2**30:.2f} GiB")
    say(f"longest interval between step completions "
        f"{max(np.diff([0.0] + done_t)) * 1e3:.0f} ms")
    say(f"program losses {prog['loss']} reference {ref['loss']}; window: "
        f"{len(losses)} steps in {window_s:.3f}s, loss {losses[0]:.4f} -> "
        f"{losses[-1]:.4f}; reference took {ref_s:.1f}s (outside setup_s)")

    steps = len(losses)
    record = {
        "correct": chk.ok, "attempted": steps,
        "failed": sum(not math.isfinite(x) for x in losses),
        "memory_peak_bytes": peak,
        "setup_s": setup_s, "window_s": window_s, "chips": n_dev,
        "steps": steps, "tokens": steps * batch_rows * seq,
        "tokens_per_step_per_chip": spec["rows_per_chip"] * seq,
        "seq_len": seq, "rows_per_chip": spec["rows_per_chip"],
        "data_wait_s": wait_s,
        "step_interval_ms": list(np.diff([0.0] + done_t) * 1e3),
        "cold_compile_s": float(setup_compile["compile_time_s"]),
        "reference_s": ref_s,
        "checks": chk.rows, "reference": ref,
    }
    return record, tracer.result()

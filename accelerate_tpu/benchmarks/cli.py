"""Bench entry point (``python bench.py`` / ``python -m
accelerate_tpu.benchmarks``).

Three modes:

* **parent** (default): detect the backend in a subprocess (the parent
  never imports jax — a process that touched JAX holds the chip and its
  children would fail or hang), build the registry, plan against the
  deadline, launch one child per process group through
  :class:`~.runner.BenchRunner`. Without a chip only ``--fast`` (the
  CPU harness smoke) runs; anything else exits non-zero.
* **child** (``--child A B ... --budget S --partial-dir D``): run the
  listed members in-process under a self-enforced budget, stream
  fsync'd partial snapshots, print one JSON line per member. Explicit
  buffer teardown (``gc.collect`` + ``jax.clear_caches``) between
  members keeps a shared child from carrying one config's HBM into the
  next.
* **direct** (``python bench.py accum``): bare variant names with no
  ``--deadline`` run in-process and print their lines — the historical
  single-variant interface (Makefile smokes use it).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from typing import Optional

from .partial import ENV_PARTIAL_DIR, PartialWriter, partial_path
from .registry import build_registry
from .runner import BenchRunner, SubprocessLauncher, load_baseline
from .scheduler import (
    ENV_DEADLINE,
    Deadline,
    DeadlineScheduler,
    Estimates,
    skip_record,
)


def _detect_backend() -> str:
    """The JAX backend, probed in a throwaway subprocess so THIS process
    never imports jax. A probe that cannot answer is fatal: guessing
    would either initialize JAX here (starving every child of the chip)
    or quietly select the wrong registry."""
    import subprocess

    probe = subprocess.run(
        [sys.executable, "-c", "import jax; print(jax.default_backend())"],
        capture_output=True, text=True, timeout=300,
    )
    lines = probe.stdout.strip().splitlines()
    if probe.returncode != 0 or not lines:
        raise SystemExit(
            f"bench: backend probe failed (rc={probe.returncode}):\n"
            + probe.stderr[-2000:]
        )
    return lines[-1]


def _parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="bench", description="accelerate_tpu benchmark harness",
    )
    p.add_argument("variants", nargs="*",
                   help="variant names to run (default: the full matrix)")
    p.add_argument("--fast", action="store_true",
                   help="CI subset: the CPU-safe fast-flagged variants")
    p.add_argument("--deadline", type=float, default=None,
                   help=f"global wall-clock budget in seconds "
                        f"(env {ENV_DEADLINE})")
    p.add_argument("--list", action="store_true",
                   help="print the registry (names, priorities, groups)")
    p.add_argument("--baseline", default=None,
                   help="previous BENCH_*.json (or raw JSON-lines output) "
                        "to stamp prev_*/regression trend fields against "
                        "(no implicit lookup)")
    p.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--budget", type=float, default=None,
                   help=argparse.SUPPRESS)
    p.add_argument("--partial-dir", default=None, help=argparse.SUPPRESS)
    return p


def _activate_cache() -> None:
    """Every process that measures joins the one persistent compile cache
    (compilation/cache.py's rule — children of one sweep and of the next
    all resolve the same directory), persisting EVERY compile: the sweep's
    small programs fall under JAX's default 1 s floor."""
    from accelerate_tpu.compilation import activate_persistent_cache
    from accelerate_tpu.utils.dataclasses import CompilePlugin

    activate_persistent_cache(CompilePlugin(
        cache_min_compile_time_secs=0.0, cache_min_entry_size_bytes=-1,
    ))


def _run_child(names: list[str], budget_s: Optional[float],
               partial_dir: Optional[str]) -> int:
    """Run ``names`` in THIS process under a self-enforced budget.

    The parent's subprocess timeout is the hard kill; the child's own
    Deadline only lets it skip later members it can already see won't
    fit — an explicit ``{"skipped": "budget"}`` line beats dying
    mid-compile."""
    import gc

    import jax

    from .measure import result_line

    _activate_cache()
    on_tpu = jax.default_backend() == "tpu"
    registry = build_registry(on_tpu)
    estimates = Estimates().load()
    deadline = Deadline(budget_s)
    rc = 0
    for i, name in enumerate(names):
        variant = registry.get(name)
        est = estimates.estimate(name, variant.default_estimate_s)
        if i > 0 and not deadline.fits(est):
            # later group member that can't fit the leftover budget:
            # skip explicitly rather than get SIGKILLed mid-compile
            print(json.dumps(skip_record(
                name, est, deadline.remaining(), reason="budget",
            )), flush=True)
            continue
        writer = PartialWriter(
            partial_path(partial_dir, name) if partial_dir else None, name,
        )
        try:
            rec = result_line(variant, partial=writer)
        except Exception as exc:  # noqa: BLE001 — isolate group members
            from accelerate_tpu.profiling.oom import (
                is_resource_exhausted,
                write_oom_report,
            )

            if is_resource_exhausted(exc):
                # the autopsy lands next to the partial snapshots, where
                # the parent harvests it (expected-OOM variants included)
                write_oom_report(
                    exc, context=f"bench:{name}", directory=partial_dir,
                )
            print(f"variant {name} failed: {exc!r}",
                  file=sys.stderr, flush=True)
            rc = 1
        else:
            print(json.dumps({"variant": name, **rec}), flush=True)
        finally:
            if i < len(names) - 1:
                # explicit buffer teardown between group members: drop
                # python refs, then the jit executable + donated-buffer
                # caches, so the next config starts with a clean device
                gc.collect()
                jax.clear_caches()
                gc.collect()
    return rc


def _run_direct(names: list[str]) -> int:
    """Historical interface: run the named variants in-process and print
    their lines (``python bench.py accum``)."""
    import jax

    from .measure import result_line

    _activate_cache()
    registry = build_registry(jax.default_backend() == "tpu")
    partial_dir = os.environ.get(ENV_PARTIAL_DIR)
    for name in names:
        variant = registry.get(name)
        writer = PartialWriter(
            partial_path(partial_dir, name) if partial_dir else None, name,
        )
        rec = result_line(variant, partial=writer)
        print(json.dumps({"variant": name, **rec}), flush=True)
    return 0


def main(argv: Optional[list[str]] = None) -> int:
    args = _parser().parse_args(argv)

    if args.child:
        return _run_child(args.variants, args.budget, args.partial_dir)

    backend = _detect_backend()
    on_tpu = backend == "tpu"
    if not on_tpu and not args.fast:
        # the CPU-tiny registry exists for the harness smoke only; a
        # measurement run that finds no chip fails, it does not fall back
        print(
            f"bench: JAX backend is {backend!r}, not 'tpu' — refusing to "
            "run the benchmark off-chip (use --fast for the CPU harness "
            "smoke)", file=sys.stderr,
        )
        return 2
    registry = build_registry(on_tpu)
    try:
        registry = registry.select(
            names=args.variants or None, fast=args.fast,
        )
    except KeyError as exc:
        print(exc.args[0], file=sys.stderr)
        return 2

    if args.list:
        for name in registry.names:
            v = registry.get(name)
            print(json.dumps({
                "variant": v.name, "kind": v.kind, "priority": v.priority,
                "group": v.group, "fast": v.fast, "headline": v.headline,
                "default_estimate_s": v.default_estimate_s,
            }))
        return 0

    if args.variants and args.deadline is None and not args.fast:
        # bare names, no scheduling flags: the historical in-process path
        return _run_direct(args.variants)

    deadline = Deadline.from_env(args.deadline)
    estimates = Estimates().load()
    scheduler = DeadlineScheduler(
        deadline,
        # CPU CI variants finish in seconds; a 60s floor would let one
        # group starve the plan on a 120s deadline
        min_budget_s=60.0 if on_tpu else 30.0,
    )
    partial_dir = tempfile.mkdtemp(prefix="accelerate_tpu_bench_partial_")
    runner = BenchRunner(
        registry, scheduler, estimates,
        SubprocessLauncher(partial_dir),
        partial_dir=partial_dir,
        baseline=load_baseline(args.baseline),
    )
    return runner.run()

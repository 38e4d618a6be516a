"""Deadline-aware budget allocation for the bench matrix.

The driver gives the whole bench one wall-clock window; r05 spent it all
on serial compiles and got killed with an empty tail. The scheduler
turns that window into explicit per-group budgets:

* a :class:`Deadline` tracks the global window (``--deadline`` /
  ``ACCELERATE_TPU_BENCH_DEADLINE_S``; absent = unbounded);
* :class:`Estimates` persists each variant's measured wall cost
  (compile + warmup + iters) next to the XLA compile cache, so round
  *n*+1 schedules against round *n*'s reality instead of guesses;
* :class:`DeadlineScheduler.plan` walks the groups in priority order and
  either grants a budget (sum of grants never exceeds the window) or
  emits an explicit ``{"skipped": "deadline", "estimated_s": ...}``
  record — a variant that does not run is visible, never vanished.

Everything takes an injectable ``clock`` so the budget arithmetic is
unit-testable with a fake clock.
"""

from __future__ import annotations

import json
import math
import os
import time
from dataclasses import dataclass, field
from typing import Callable, Iterable, Optional, Sequence

ENV_DEADLINE = "ACCELERATE_TPU_BENCH_DEADLINE_S"


class Deadline:
    """A wall-clock window starting at construction. ``seconds=None``
    means unbounded (``remaining()`` is ``inf``, nothing ever expires)."""

    def __init__(self, seconds: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic):
        if seconds is not None and seconds <= 0:
            raise ValueError("deadline seconds must be > 0")
        self.seconds = float(seconds) if seconds is not None else None
        self._clock = clock
        self._t0 = clock()

    @classmethod
    def from_env(cls, override: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic) -> "Deadline":
        if override is not None:
            return cls(override, clock=clock)
        env = os.environ.get(ENV_DEADLINE)
        return cls(float(env) if env else None, clock=clock)

    def elapsed(self) -> float:
        return self._clock() - self._t0

    def remaining(self) -> float:
        if self.seconds is None:
            return math.inf
        return self.seconds - self.elapsed()

    def expired(self) -> bool:
        return self.remaining() <= 0

    def fits(self, estimate_s: float) -> bool:
        return estimate_s <= self.remaining()


class Estimates:
    """Per-variant measured wall cost, persisted NEXT TO the XLA cache
    (``<cache_dir>.estimates.json``) so it shares the cache's lifetime:
    wiping the compile cache also resets the cost model to defaults,
    which is exactly when estimates go stale."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or self.default_path()
        self.data: dict[str, dict] = {}

    @staticmethod
    def default_path() -> str:
        from ..compilation.cache import resolve_cache_dir

        return os.path.abspath(resolve_cache_dir()) + ".estimates.json"

    def load(self) -> "Estimates":
        try:
            with open(self.path) as f:
                data = json.load(f)
            if isinstance(data, dict):
                self.data = {
                    k: v for k, v in data.items() if isinstance(v, dict)
                }
        except (OSError, ValueError):
            self.data = {}
        return self

    def save(self) -> None:
        tmp = self.path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(self.data, f, indent=1, sort_keys=True)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.path)
        except OSError:
            pass

    def observe(self, variant: str, total_s: float,
                step_time_s: Optional[float] = None,
                compile_time_s: Optional[float] = None) -> None:
        self.data[variant] = {
            "total_s": round(float(total_s), 3),
            "step_time_s": step_time_s,
            "compile_time_s": compile_time_s,
            "time_unix": time.time(),
        }

    def estimate(self, variant: str, default: float) -> float:
        """Estimated minimum wall cost: last measured total (which already
        contains that round's compile + warmup + iters), else the
        registry default."""
        rec = self.data.get(variant)
        if rec and isinstance(rec.get("total_s"), (int, float)):
            return float(rec["total_s"])
        return float(default)


def skip_record(variant: str, estimated_s: float, remaining_s: float,
                reason: str = "deadline") -> dict:
    """The explicit record a variant emits instead of silently vanishing."""
    return {
        "variant": variant,
        "skipped": reason,
        "estimated_s": round(float(estimated_s), 1),
        "remaining_s": (
            None if math.isinf(remaining_s) else round(float(remaining_s), 1)
        ),
        "time_unix": time.time(),
    }


@dataclass
class Planned:
    """One scheduled unit (a process group) with its granted budget."""

    name: str
    estimate_s: float
    budget_s: float
    members: tuple[str, ...] = field(default_factory=tuple)


class DeadlineScheduler:
    """Allocates the deadline across priority-ordered items.

    ``plan`` is the static pass: walking the items in order, each gets
    ``min(pool, max(slack * estimate, min_budget))`` out of a pool that
    starts at the remaining deadline — so the **sum of granted budgets
    can never exceed the global window** — and items whose bare estimate
    no longer fits the pool become skip records. ``grant`` is the
    runtime pass: just before launch, a planned item's budget is
    re-clamped to actual remaining wall clock (minus what later planned
    items reserved), so early finishers donate their slack forward and
    overruns upstream shrink (or void) downstream budgets.
    """

    def __init__(self, deadline: Deadline, *, slack: float = 1.5,
                 min_budget_s: float = 60.0):
        if slack < 1.0:
            raise ValueError("slack must be >= 1")
        self.deadline = deadline
        self.slack = slack
        self.min_budget_s = min_budget_s

    def plan(
        self, items: Sequence[tuple[str, float]],
        members: Optional[dict[str, Sequence[str]]] = None,
    ) -> tuple[list[Planned], list[dict]]:
        """``items``: (name, estimate_s) in priority order. Returns the
        planned runs and the skip records for everything that didn't fit."""
        members = members or {}
        pool = self.deadline.remaining()
        planned: list[Planned] = []
        skipped: list[dict] = []
        for name, est in items:
            if est > pool:
                skipped.append(skip_record(name, est, pool))
                continue
            budget = min(pool, max(est * self.slack, self.min_budget_s))
            planned.append(Planned(
                name, float(est), budget, tuple(members.get(name, (name,))),
            ))
            if not math.isinf(pool):
                pool -= budget
        return planned, skipped

    def grant(self, item: Planned, reserved_later_s: float = 0.0
              ) -> Optional[float]:
        """Runtime budget for ``item`` right now, or None when its
        estimate exceeds the remaining window (caller emits the skip)."""
        rem = self.deadline.remaining()
        if item.estimate_s > rem:
            return None
        if math.isinf(rem):
            return item.budget_s
        return min(rem, max(item.budget_s, rem - reserved_later_s))

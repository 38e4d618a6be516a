"""Compile-cost attribution via ``jax.monitoring``.

JAX emits monitoring events around every compilation: persistent-cache
hits/misses, backend (XLA) compile seconds, trace seconds, and the
seconds a cache hit saved. Nothing consumes them by default. The
:class:`CompileMonitor` registers process-wide listeners once and
aggregates the events two ways:

* **totals** — a monotonically growing counter dict; callers snapshot
  before a region and diff after (:meth:`snapshot` / :meth:`delta`);
* **by label** — the Accelerator step wrappers bracket each jitted call
  with :meth:`label`, so compile cost lands on the step fn that paid it
  (``unified_step#0`` etc.), not on an anonymous process-wide pile.

The listeners are cheap (a dict update under a lock, only fired when JAX
actually compiles or hits the cache) and are installed lazily on first
use, so merely importing the package registers nothing.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Any, Optional

# jax.monitoring event name -> our counter key (counts)
_COUNT_EVENTS = {
    "/jax/compilation_cache/cache_hits": "persistent_cache_hits",
    "/jax/compilation_cache/cache_misses": "persistent_cache_misses",
}
# duration-event name -> our accumulator key (seconds). The backend
# compile duration is the honest "XLA compiled for this long" signal: it
# does NOT fire when the persistent cache serves the executable.
_DURATION_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "compile_time_s",
    "/jax/core/compile/jaxpr_trace_duration": "trace_time_s",
    "/jax/compilation_cache/compile_time_saved_sec": "compile_time_saved_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
}

_KEYS = tuple(_COUNT_EVENTS.values()) + tuple(_DURATION_EVENTS.values())


def _zeros() -> dict:
    return {k: 0.0 for k in _KEYS}


class CompileMonitor:
    """Process-wide aggregator for JAX compile/cache monitoring events."""

    def __init__(self):
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._installed = False
        self.totals: dict[str, float] = _zeros()
        self.by_label: dict[str, dict[str, float]] = {}

    # ------------------------------------------------------------------ #
    # listener plumbing
    # ------------------------------------------------------------------ #
    def install(self) -> "CompileMonitor":
        """Register the jax.monitoring listeners (once per process)."""
        with self._lock:
            if self._installed:
                return self
            from jax import monitoring

            monitoring.register_event_listener(self._on_event)
            monitoring.register_event_duration_secs_listener(self._on_duration)
            self._installed = True
        return self

    def _bump(self, key: str, amount: float) -> None:
        label = getattr(self._tls, "label", None)
        with self._lock:
            self.totals[key] = self.totals.get(key, 0.0) + amount
            if label is not None:
                per = self.by_label.setdefault(label, _zeros())
                per[key] = per.get(key, 0.0) + amount

    def _on_event(self, event: str, **kwargs: Any) -> None:
        key = _COUNT_EVENTS.get(event)
        if key is not None:
            self._bump(key, 1.0)

    def _on_duration(self, event: str, duration: float, **kwargs: Any) -> None:
        key = _DURATION_EVENTS.get(event)
        if key is not None:
            self._bump(key, float(duration))

    # ------------------------------------------------------------------ #
    # attribution / reading
    # ------------------------------------------------------------------ #
    @contextmanager
    def label(self, name: Optional[str]):
        """Attribute events fired inside the block to ``name`` (on this
        thread; nested labels shadow, restoring the outer one on exit)."""
        prev = getattr(self._tls, "label", None)
        self._tls.label = name
        try:
            yield self
        finally:
            self._tls.label = prev

    def snapshot(self) -> dict[str, float]:
        with self._lock:
            return dict(self.totals)

    def delta(self, before: Optional[dict]) -> dict[str, float]:
        """Totals accumulated since ``before`` (a :meth:`snapshot`)."""
        now = self.snapshot()
        if not before:
            return now
        return {k: now.get(k, 0.0) - before.get(k, 0.0) for k in now}

    def stats_for(self, label: str) -> dict[str, float]:
        with self._lock:
            return dict(self.by_label.get(label, _zeros()))


_monitor: Optional[CompileMonitor] = None
_monitor_lock = threading.Lock()


def get_compile_monitor() -> CompileMonitor:
    """The process singleton, listeners installed on first call."""
    global _monitor
    with _monitor_lock:
        if _monitor is None:
            _monitor = CompileMonitor().install()
    return _monitor

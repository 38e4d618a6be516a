"""Profiling & measurement subsystem (SURVEY §5.1).

Parity: the reference's ``measures_util.py`` (start/end_measure wall
time + CPU RSS + per-GPU peak memory, peak-CPU monitor thread) and the
peak-memory CI gates (``test_utils/scripts/external_deps/
test_peak_memory_usage.py``). TPU-native additions: the XLA profiler
(``jax.profiler.trace`` -> TensorBoard/perfetto traces, the tool that shows
MXU utilization and HBM traffic per op) is exposed as a first-class
``Accelerator.profile()`` context, and step timing understands async
dispatch (a step is only *done* at ``block_until_ready``).
"""

from __future__ import annotations

import contextlib
import gc
import os
import threading
import time
import weakref
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import numpy as np

from ..logging import get_logger

logger = get_logger(__name__)


# ---------------------------------------------------------------------- #
# device / host memory probes
# ---------------------------------------------------------------------- #
def device_memory_stats(device: Optional[jax.Device] = None) -> dict[str, int]:
    """Live/peak HBM bytes for one device. Keys: ``bytes_in_use``,
    ``peak_bytes_in_use``, ``bytes_limit`` (0 when the backend does not
    report, e.g. CPU)."""
    device = device or jax.local_devices()[0]
    try:
        stats = device.memory_stats() or {}
    except Exception:
        stats = {}
    return {
        "bytes_in_use": int(stats.get("bytes_in_use", 0)),
        "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
        "bytes_limit": int(stats.get("bytes_limit", 0)),
    }


def host_memory_rss() -> int:
    """Current process RSS in bytes (no psutil dependency)."""
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        import resource

        # ru_maxrss is KiB on Linux (peak, not current — best effort)
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


class PeakHostMemory:
    """Background sampler for peak host RSS (reference PeakCPUMemory:22).

    The monitor thread holds only a WEAK reference to the tracker: a
    bracket abandoned without ``stop()`` (exception between start_measure
    and end_measure) exits its thread as soon as the tracker is GC'd,
    instead of busy-polling a core for the process lifetime. The 1 ms
    poll quantum bounds sampling at ~1 kHz — still far denser than real
    RSS transients — and gives the GC a chance to run.

    ``stop()`` is deterministic: the per-bracket stop :class:`~threading.
    Event` wakes the thread out of its wait immediately and the join has
    no timeout, so when ``stop()`` returns the thread is GONE — repeated
    ``start()``/``stop()`` cycles on one tracker never stack daemon
    threads.
    """

    def __init__(self):
        self._stop_event = threading.Event()
        self._peak = -1
        self._thread: Optional[threading.Thread] = None

    @staticmethod
    def _monitor(ref: "weakref.ref[PeakHostMemory]", stop_event: threading.Event):
        # the event is passed by value: a GC'd tracker still unblocks the
        # loop via the dead weakref, and a live tracker's stop() wakes the
        # wait without the 1 ms worst-case latency of a sleep
        while not stop_event.is_set():
            self = ref()
            if self is None:
                break
            self._peak = max(self._peak, host_memory_rss())
            del self  # don't pin the tracker between samples
            stop_event.wait(0.001)

    def start(self):
        if self._thread is not None and self._thread.is_alive():
            raise RuntimeError(
                "PeakHostMemory.start() while already monitoring; use one "
                "tracker per measurement bracket"
            )
        self._stop_event = threading.Event()  # fresh per bracket
        self._peak = host_memory_rss()
        self._thread = threading.Thread(
            target=PeakHostMemory._monitor,
            args=(weakref.ref(self), self._stop_event),
            daemon=True,
        )
        self._thread.start()

    def stop(self) -> int:
        """Stop and JOIN the monitor thread; returns the observed peak.
        Idempotent — extra calls just return the last peak."""
        self._stop_event.set()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        return self._peak


def start_measure() -> dict[str, Any]:
    """Snapshot wall time + host RSS + per-device HBM (reference
    ``start_measure``, measures_util.py:52)."""
    gc.collect()
    measures: dict[str, Any] = {"time": time.perf_counter()}
    measures["host"] = host_memory_rss()
    for i, d in enumerate(jax.local_devices()):
        stats = device_memory_stats(d)
        measures[f"device:{i}"] = stats["bytes_in_use"]
        measures[f"device:{i}-peak"] = stats["peak_bytes_in_use"]
    # fresh tracker per bracket: a shared singleton races under nested or
    # concurrent measurement windows (second start() orphans the first
    # thread and loses its peak)
    tracker = PeakHostMemory()
    tracker.start()
    measures["_tracker"] = tracker
    return measures


def end_measure(start: dict[str, Any]) -> dict[str, Any]:
    """Deltas since :func:`start_measure` (reference ``end_measure``:68):
    seconds elapsed, host RSS delta + peak, per-device HBM delta.

    ``device:{i}-peak`` is the HIGH-WATER GROWTH inside the window: XLA has
    no peak-reset API (unlike torch.cuda.reset_peak_memory_stats), so a
    region whose allocations stay below an earlier lifetime peak reports 0
    — use the ``device:{i}`` delta for such regions.
    """
    out: dict[str, Any] = {"time": time.perf_counter() - start["time"]}
    gc.collect()
    out["host"] = host_memory_rss() - start["host"]
    out["host-peak"] = max(0, start["_tracker"].stop() - start["host"])
    for i, d in enumerate(jax.local_devices()):
        stats = device_memory_stats(d)
        out[f"device:{i}"] = stats["bytes_in_use"] - start[f"device:{i}"]
        out[f"device:{i}-peak"] = max(
            0, stats["peak_bytes_in_use"] - start[f"device:{i}-peak"]
        )
    return out


def log_measures(measures: dict[str, Any], description: str = "run") -> None:
    """Human-readable dump (reference ``log_measures``:86)."""
    print(f"{description}:")
    print(f"- Time: {measures['time']:.2f}s")
    for key, value in measures.items():
        if key.startswith(("device", "host")):
            print(f"- {key}: {value >> 20} MiB")


# ---------------------------------------------------------------------- #
# step timing (async-dispatch aware)
# ---------------------------------------------------------------------- #
class StepTimer:
    """Wall-clock timer for compiled steps.

    JAX dispatch is asynchronous: ``step(carry, batch)`` returns before the
    TPU finishes, so naive timing measures Python overhead. ``tick``
    blocks on the result it is handed, charging the full device time to
    the step. First ``skip`` ticks (compile) are excluded from stats.
    """

    def __init__(self, skip: int = 1):
        self.skip = skip
        self.times: list[float] = []
        self._count = 0
        self._t0: Optional[float] = None

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        pass

    def tick(self, result: Any = None) -> float:
        """Mark one step done (blocking on ``result`` if given); returns
        the step's seconds."""
        if result is not None:
            jax.block_until_ready(result)
        now = time.perf_counter()
        dt = now - self._t0 if self._t0 is not None else 0.0
        self._t0 = now
        self._count += 1
        if self._count > self.skip:
            self.times.append(dt)
        return dt

    def summary(self) -> dict[str, float]:
        if not self.times:
            return {"steps": 0}
        arr = np.asarray(self.times)
        return {
            "steps": len(arr),
            "mean_s": float(arr.mean()),
            "median_s": float(np.median(arr)),
            "p90_s": float(np.percentile(arr, 90)),
            "min_s": float(arr.min()),
            "total_s": float(arr.sum()),
        }


class AsyncStepTimer:
    """Single-step timer that separates *dispatch* from *device* time.

    JAX returns from a jitted call as soon as the XLA program is enqueued;
    the wall time of the call alone measures Python + dispatch overhead,
    not the step. One bracket is::

        timer.start()          # before the step call
        out = step(...)        # returns immediately (async dispatch)
        total, dispatch = timer.stop(out)   # blocks on out

    ``total`` charges the full device execution to the step (the
    ``block_until_ready`` boundary); ``dispatch`` is the host-side cost of
    getting the program enqueued. ``dispatch ≈ total`` means the host is
    the bottleneck (Python overhead or an already-synced result);
    ``dispatch << total`` is the healthy async regime. Used by
    ``telemetry.StepTelemetry`` for per-step records; :class:`StepTimer`
    remains the aggregate-stats tool.
    """

    def __init__(self):
        self._t0: Optional[float] = None

    def start(self) -> None:
        self._t0 = time.perf_counter()

    @property
    def started(self) -> bool:
        return self._t0 is not None

    def stop(self, result: Any = None) -> tuple[float, float]:
        """Returns ``(total_s, dispatch_s)``; blocks on ``result``."""
        if self._t0 is None:
            return 0.0, 0.0
        dispatch = time.perf_counter() - self._t0
        if result is not None:
            jax.block_until_ready(result)
        total = time.perf_counter() - self._t0
        self._t0 = None
        return total, dispatch


# ---------------------------------------------------------------------- #
# the XLA profiler
# ---------------------------------------------------------------------- #
@dataclass
class ProfileKwargs:
    """Configuration for :meth:`Accelerator.profile` (the reference's
    ``ProfileKwargs`` handler shape, re-targeted from torch.profiler to
    ``jax.profiler``).

    ``output_trace_dir``: where the TensorBoard/perfetto trace goes. When
    None, profiling is a no-op (so ``accelerator.profile()`` can stay in
    the loop unconditionally). ``skip_first``: un-profiled warmup steps
    (compile steps drown the timeline otherwise) — requires the loop to
    call :meth:`ProfileHandle.step` once per step so the handle knows when
    the warmup is over.
    """

    output_trace_dir: Optional[str] = None
    skip_first: int = 0
    # jax.profiler options (host_tracer_level 2 adds python annotations)
    host_tracer_level: int = 2
    python_tracer_level: int = 0
    create_perfetto_link: bool = False


def _start_trace_kwargs(kw: ProfileKwargs) -> dict:
    """``jax.profiler.start_trace`` keyword arguments for ``kw``."""
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = kw.host_tracer_level
    opts.python_tracer_level = kw.python_tracer_level
    return {
        "create_perfetto_link": kw.create_perfetto_link,
        "profiler_options": opts,
    }


class ProfileHandle:
    """A live profiling session. ``dir`` is the trace directory. With
    ``skip_first > 0`` the trace starts lazily at the ``skip_first``-th
    :meth:`step` call; otherwise it is already running on entry."""

    def __init__(self, target: str, kw: ProfileKwargs):
        self.dir = target
        self._kw = kw
        self._started = False
        self._stopped = False
        self._steps = 0

    def _start(self):
        if self._started:
            return
        logger.info(f"XLA profiler trace -> {self.dir}")
        jax.profiler.start_trace(self.dir, **_start_trace_kwargs(self._kw))
        self._started = True

    def step(self):
        """Mark one training step done (only needed with ``skip_first``)."""
        self._steps += 1
        if not self._started and self._steps >= self._kw.skip_first:
            self._start()

    def _stop(self):
        if self._started and not self._stopped:
            jax.profiler.stop_trace()
        self._stopped = True


@contextlib.contextmanager
def profile(
    output_trace_dir: Optional[str] = None,
    kwargs: Optional[ProfileKwargs] = None,
):
    """Capture an XLA profiler trace around the enclosed steps; yields a
    :class:`ProfileHandle` (or None when no directory is configured).

    View with TensorBoard (`tensorboard --logdir <dir>`; the Profile tab
    shows per-op device time, MXU utilization and the HBM roofline) or the
    perfetto link.
    """
    kw = kwargs or ProfileKwargs(output_trace_dir=output_trace_dir)
    target = output_trace_dir or kw.output_trace_dir
    if target is None:
        yield None
        return
    os.makedirs(target, exist_ok=True)
    handle = ProfileHandle(target, kw)
    if kw.skip_first <= 0:
        handle._start()
    try:
        yield handle
    finally:
        handle._stop()


def annotate(name: str, **stats):
    """A host span in the profiler's own trace
    (``jax.profiler.TraceAnnotation``): it lands in the session's
    ``.xplane.pb`` on the clock of the device planes, so an idle gap of the
    chip can be laid to what the host was doing in it. ``stats`` become the
    event's stats; one known only at the end is added with
    ``span.set_metadata(...)`` before the exit. A span exists when a profiler
    session does; with none it costs the object and an inactive-check.
    ``ServingEngine.step`` draws its phases with it (``atpu:serve.*``)."""
    return jax.profiler.TraceAnnotation(name, **stats)

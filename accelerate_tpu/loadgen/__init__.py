"""Heavy-traffic soak & chaos harness (see ISSUE 16 / ROADMAP item 5).

Open-loop seeded load generation against the serving engine, a
warmup → ramp → soak → fault → recovery phase program, serving-scoped
chaos via the ``ACCELERATE_TPU_FAULT_INJECT`` grammar, and an
atomically-written ``soak-report.json`` with goodput-under-SLO and
capacity-at-breach-point headlines. Everything is default-off and
record-only: nothing here runs unless a test or user code
builds a :class:`SoakHarness`.
"""

from .chaos import ChaosAdapter
from .harness import SoakClock, SoakConfig, SoakHarness
from .phases import Phase, phase_bounds, standard_program, total_duration_s
from .report import (
    REPORT_BASENAME,
    lag_histogram,
    read_report,
    write_report,
)
from .workload import (
    SoakRequest,
    WorkloadConfig,
    build_trace,
    trace_fingerprint,
)

__all__ = [
    "ChaosAdapter",
    "Phase",
    "REPORT_BASENAME",
    "SoakClock",
    "SoakConfig",
    "SoakHarness",
    "SoakRequest",
    "WorkloadConfig",
    "build_trace",
    "lag_histogram",
    "phase_bounds",
    "read_report",
    "standard_program",
    "total_duration_s",
    "trace_fingerprint",
    "write_report",
]

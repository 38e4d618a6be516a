"""HBM & compute attribution plane.

Three host-side pieces answering "where do the bytes and FLOPs go":

* :mod:`registry` — every compiled executable registers its
  ``memory_analysis()``/``cost_analysis()`` under a label; the registry
  folds them into an HBM budget ledger and per-program rooflines.
* :mod:`census` — ``jax.live_arrays()`` aggregated by logical owner
  (params / opt / KV pools / adapters / unowned), the source of the
  ``kind="memory"`` telemetry records and the leak detector's signal.
* :mod:`oom` — RESOURCE_EXHAUSTED autopsies: an atomic
  ``oom-report.json`` written from already-resident data at the
  step/engine boundaries.
* :mod:`hlo_audit` — the sharding X-ray: per-program collective
  inventories (kind / bytes moved / ICI-vs-DCN) parsed from compiled
  HLO, checked against each program's expected-collective contract;
  unexplained collectives surface as ``sharding_violation`` anomalies.

All default-on behavior is record-only; nothing here changes numerics
or trace shapes (the zero-retrace contracts are asserted with the plane
enabled in ``tests/test_profiling.py``).
"""

from .._lazy import lazy_exports

# name -> submodule, imported on first access: importing this package
# must not import jax (a parent that spawns chip children stays off it)
_EXPORTS = {
    "BufferCensus": ".census",
    "COLLECTIVE_KINDS": ".hlo_audit",
    "CONTRACT_ZERO": ".hlo_audit",
    "RESHARD_COPY": ".hlo_audit",
    "CollectiveContract": ".hlo_audit",
    "CollectiveOp": ".hlo_audit",
    "ProgramAudit": ".hlo_audit",
    "audit_compiled": ".hlo_audit",
    "audit_hlo_text": ".hlo_audit",
    "parse_hlo_collectives": ".hlo_audit",
    "parse_replica_groups": ".hlo_audit",
    "summarize_audits": ".hlo_audit",
    "ENV_OOM_DIR": ".oom",
    "OOM_REPORT_NAME": ".oom",
    "is_resource_exhausted": ".oom",
    "oom_report_dir": ".oom",
    "parse_requested_bytes": ".oom",
    "read_oom_report": ".oom",
    "write_oom_report": ".oom",
    "ProgramRecord": ".registry",
    "ProgramRegistry": ".registry",
    "get_program_registry": ".registry",
    "reset_program_registry": ".registry",
}
__getattr__, __dir__ = lazy_exports(__name__, _EXPORTS)

__all__ = [
    "BufferCensus",
    "COLLECTIVE_KINDS",
    "CONTRACT_ZERO",
    "RESHARD_COPY",
    "CollectiveContract",
    "CollectiveOp",
    "ProgramAudit",
    "audit_compiled",
    "audit_hlo_text",
    "parse_hlo_collectives",
    "parse_replica_groups",
    "summarize_audits",
    "ENV_OOM_DIR",
    "OOM_REPORT_NAME",
    "is_resource_exhausted",
    "oom_report_dir",
    "parse_requested_bytes",
    "read_oom_report",
    "write_oom_report",
    "ProgramRecord",
    "ProgramRegistry",
    "get_program_registry",
    "reset_program_registry",
]

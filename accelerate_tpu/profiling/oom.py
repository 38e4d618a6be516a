"""OOM forensics: turn RESOURCE_EXHAUSTED into an autopsy, not a shrug.

An XLA out-of-memory kills the process with a wall of allocator text and
no record of *what was resident*. The step/engine boundaries catch
the error, and :func:`write_oom_report` writes an atomic
``oom-report.json`` from data that is **already in memory** — the
program ledger, the last census, pool stats, the top-3 largest programs
— plus the requested bytes parsed out of the error message. Nothing in
this module compiles, allocates device memory, or takes a fresh census
walk it wasn't handed: at crash time the allocator is full and the only
safe work is host-side serialization of what we already know.

Report location: ``ACCELERATE_TPU_OOM_DIR`` env > explicit ``directory``
> the diagnostics dir when one is configured > cwd. Writing never
raises — an autopsy that can't land on disk logs and gives up, it does
not mask the original OOM.
"""

from __future__ import annotations

import json
import os
import re
import time
from typing import Any, Optional

from ..logging import get_logger

logger = get_logger(__name__)

#: filename of the autopsy (searched for by diagnose)
OOM_REPORT_NAME = "oom-report.json"
#: env override for where autopsies land
ENV_OOM_DIR = "ACCELERATE_TPU_OOM_DIR"

_OOM_MARKERS = (
    "RESOURCE_EXHAUSTED",
    "Resource exhausted",
    "Ran out of memory",
    "Out of memory",
)

# "trying to allocate 12.34GiB", "allocating 123456 bytes",
# "Attempting to reserve 11.25G at the bottom of memory"
_BYTES_RE = re.compile(
    r"(?:allocat\w*|reserve)\s+(\d+(?:\.\d+)?)\s*"
    r"([KMGT]i?B?\b|bytes?\b)?",
    re.IGNORECASE,
)
_UNIT = {
    "b": 1, "byte": 1, "bytes": 1,
    "k": 1024, "kb": 1000, "kib": 1024,
    "m": 1024**2, "mb": 1000**2, "mib": 1024**2,
    "g": 1024**3, "gb": 1000**3, "gib": 1024**3,
    "t": 1024**4, "tb": 1000**4, "tib": 1024**4,
}


def is_resource_exhausted(exc: BaseException) -> bool:
    """Is this exception an XLA device-memory exhaustion?

    Matched on the message markers XLA uses (jaxlib raises
    ``XlaRuntimeError`` whose *text* carries the grpc status name), so
    synthetic ``RuntimeError("RESOURCE_EXHAUSTED: ...")`` tests exercise
    the same path a real TPU OOM takes.
    """
    text = f"{type(exc).__name__}: {exc}"
    return any(marker in text for marker in _OOM_MARKERS)


def parse_requested_bytes(message: str) -> Optional[int]:
    """Best-effort extraction of the allocation size that failed."""
    best = None
    for m in _BYTES_RE.finditer(message or ""):
        value = float(m.group(1))
        unit = (m.group(2) or "bytes").lower()
        scale = _UNIT.get(unit) or _UNIT.get(unit.rstrip("b")) or 1
        n = int(value * scale)
        best = max(best or 0, n)
    return best


def oom_report_dir(directory: Optional[str] = None) -> str:
    """Resolve where the autopsy lands (see module docstring)."""
    env = os.environ.get(ENV_OOM_DIR)
    if env:
        return env
    if directory:
        return directory
    return os.getcwd()


def write_oom_report(
    exc: BaseException,
    *,
    context: Optional[str] = None,
    registry: Any = None,
    census: Optional[dict] = None,
    pool_stats: Optional[dict] = None,
    directory: Optional[str] = None,
    extra: Optional[dict] = None,
) -> Optional[str]:
    """Write the autopsy atomically; returns its path, or None when it
    could not be written. Never raises.

    ``registry`` defaults to the process-wide
    :class:`~.registry.ProgramRegistry`; ``census`` is the **last
    already-taken** census record (callers must not take a fresh walk
    mid-crash).
    """
    try:
        if registry is None:
            from .registry import get_program_registry

            registry = get_program_registry()
        message = f"{exc}"
        report: dict[str, Any] = {
            "kind": "oom_report",
            "time_unix": time.time(),
            "context": context or "unknown",
            "error_type": type(exc).__name__,
            "error_message": message[:4000],
            "requested_bytes": parse_requested_bytes(message),
        }
        owner_bytes = (census or {}).get("census_owner_bytes") or {}
        try:
            report["ledger"] = registry.ledger(owner_bytes)
            report["top_programs"] = registry.top_programs(
                3, by="total_bytes",
            )
        except Exception as e:  # noqa: BLE001 — partial autopsy > none
            logger.debug(f"oom report ledger failed: {e}")
        if census:
            report["census"] = census
        if pool_stats:
            report["pool_stats"] = pool_stats
        if extra:
            report["extra"] = extra
        target_dir = oom_report_dir(directory)
        os.makedirs(target_dir, exist_ok=True)
        path = os.path.join(target_dir, OOM_REPORT_NAME)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(report, f, indent=2, default=str)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        logger.error(
            f"RESOURCE_EXHAUSTED in {report['context']}: autopsy -> {path}"
        )
        return path
    except Exception as e:  # noqa: BLE001 — never mask the real OOM
        logger.debug(f"write_oom_report failed: {e}")
        return None


def read_oom_report(directory: str) -> Optional[dict]:
    """Load the autopsy from ``directory`` (or a path straight to the
    file); None when absent or unparseable."""
    path = directory
    if os.path.isdir(path):
        path = os.path.join(path, OOM_REPORT_NAME)
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None

"""Compiled-program registry: the HBM & compute attribution ledger.

Every compiled executable the framework creates — the unified train
step, pipeline step, serving prefill buckets, the ONE decode program,
spec-verify widths, the COW copy, the fused-accum scan — registers here
at compile/warmup time with a label plus whatever XLA's
``Compiled.memory_analysis()`` (argument/output/temp/generated-code
bytes) and ``cost_analysis()`` (flops, bytes accessed) report. The
registry then answers the two questions one aggregate step-time number
cannot:

* **Where does the HBM go?** :meth:`ProgramRegistry.ledger` folds
  owner-attributed resident bytes (params / opt state / KV pools /
  adapter stacks, from the live-buffer census) with the per-program
  scratch peak (``max`` of temp bytes — XLA programs run one at a
  time per device) against device capacity.
* **Where does the MFU go?** :meth:`ProgramRegistry.roofline` computes
  each program's analytic arithmetic intensity and the peak-bound MFU
  a perfectly-scheduled chip could reach, so an achieved step time
  attributes the 0.63-vs-0.70 gap to a *specific* program instead of a
  guess.

Everything is defensive: ``memory_analysis``/``cost_analysis`` are
partial on CPU (and can raise on exotic backends), so extraction
failures degrade to zeros, never to an exception on the train loop.
Registration is idempotent per label — a re-warmed shape replaces its
record.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Optional

from ..logging import get_logger

logger = get_logger(__name__)

#: published per-chip peaks by device kind (public cloud specs): bf16
#: FLOP/s and HBM bytes/s side by side — the roofline's two roofs. Matched
#: as a substring of ``device_kind`` in this order, so the bare "TPU v5"
#: (how a v5p reports itself) comes after "TPU v5 lite". No CPU row: a
#: kind that is not here has no peak, never a default.
DEVICE_PEAKS = {
    "TPU v4": {"flops_per_s": 275e12, "hbm_bytes_per_s": 1.2e12},
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5p": {"flops_per_s": 459e12, "hbm_bytes_per_s": 2.77e12},
    "TPU v5": {"flops_per_s": 459e12, "hbm_bytes_per_s": 2.77e12},
    "TPU v6 lite": {"flops_per_s": 918e12, "hbm_bytes_per_s": 1.64e12},
    "TPU v6e": {"flops_per_s": 918e12, "hbm_bytes_per_s": 1.64e12},
}


def device_peaks(device_kind: str) -> dict:
    """The ``DEVICE_PEAKS`` row of ``device_kind``; ``ValueError`` for a
    kind with no published peak (CPU included)."""
    kind = device_kind.lower()
    for name, row in DEVICE_PEAKS.items():
        if name.lower() in kind:
            return row
    raise ValueError(
        f"no published peak for device_kind {device_kind!r}: add it to "
        "DEVICE_PEAKS with its source before reporting a utilization"
    )


@dataclass
class ProgramRecord:
    """One compiled executable's analysis snapshot."""

    label: str
    kind: str = "train"  # "train" | "serve" | "other"
    argument_bytes: int = 0
    output_bytes: int = 0
    temp_bytes: int = 0
    alias_bytes: int = 0
    generated_code_bytes: int = 0
    flops: float = 0.0
    bytes_accessed: float = 0.0
    compile_seconds: float = 0.0
    registered_at: float = 0.0
    meta: dict = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        """Peak incremental HBM while this program runs: scratch + code
        (arguments/outputs are the resident buffers the census already
        owns — counting them here would double-book the ledger)."""
        return int(self.temp_bytes) + int(self.generated_code_bytes)

    @property
    def arithmetic_intensity(self) -> Optional[float]:
        """FLOPs per byte accessed — the roofline x-coordinate."""
        if self.flops > 0 and self.bytes_accessed > 0:
            return self.flops / self.bytes_accessed
        return None

    def as_dict(self) -> dict:
        d = {
            "label": self.label,
            "kind": self.kind,
            "argument_bytes": int(self.argument_bytes),
            "output_bytes": int(self.output_bytes),
            "temp_bytes": int(self.temp_bytes),
            "alias_bytes": int(self.alias_bytes),
            "generated_code_bytes": int(self.generated_code_bytes),
            "flops": float(self.flops),
            "bytes_accessed": float(self.bytes_accessed),
            "compile_seconds": round(float(self.compile_seconds), 4),
        }
        ai = self.arithmetic_intensity
        if ai is not None:
            d["arithmetic_intensity"] = round(ai, 4)
        if self.meta:
            d["meta"] = dict(self.meta)
        return d


def _first_scalar(analysis: Any, key: str) -> float:
    """Pull ``key`` out of a ``cost_analysis()`` result across the two
    shapes JAX has shipped: a list of per-computation dicts, or one
    dict."""
    if analysis is None:
        return 0.0
    items = analysis if isinstance(analysis, (list, tuple)) else [analysis]
    total = 0.0
    for item in items:
        try:
            value = item.get(key)
        except AttributeError:
            continue
        if value is not None:
            try:
                v = float(value)
            except (TypeError, ValueError):
                continue
            if v > 0:
                total += v
    return total


class ProgramRegistry:
    """Thread-safe label -> :class:`ProgramRecord` map.

    One process-wide instance (see :func:`get_program_registry`) is
    shared by the Accelerator's warmup path and the serving engine's
    ``capture_programs`` so diagnose/OOM forensics see every program
    regardless of which subsystem compiled it.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._programs: dict[str, ProgramRecord] = {}
        self._audits: dict[str, Any] = {}  # label -> ProgramAudit

    def __len__(self) -> int:
        with self._lock:
            return len(self._programs)

    def __contains__(self, label: str) -> bool:
        with self._lock:
            return label in self._programs

    def clear(self) -> None:
        with self._lock:
            self._programs.clear()
            self._audits.clear()

    def get(self, label: str) -> Optional[ProgramRecord]:
        with self._lock:
            return self._programs.get(label)

    def programs(self) -> list[ProgramRecord]:
        with self._lock:
            return list(self._programs.values())

    def labels(self) -> list[str]:
        with self._lock:
            return list(self._programs)

    # ------------------------------------------------------------- #
    # registration
    # ------------------------------------------------------------- #
    def register_compiled(
        self,
        label: str,
        compiled: Any,
        *,
        kind: str = "train",
        compile_seconds: float = 0.0,
        **meta: Any,
    ) -> Optional[ProgramRecord]:
        """Register one ``jax.stages.Compiled`` under ``label``.

        Extraction is best-effort: each analysis is probed independently
        and a failure leaves its fields zero (CPU's ``cost_analysis`` is
        partial; some backends raise). Never raises.
        """
        rec = ProgramRecord(
            label=label, kind=kind,
            compile_seconds=float(compile_seconds),
            registered_at=time.time(), meta=dict(meta),
        )
        try:
            mem = compiled.memory_analysis()
        except Exception as exc:  # noqa: BLE001 — observability never fatal
            logger.debug(f"memory_analysis({label}) unavailable: {exc}")
            mem = None
        if mem is not None:
            for attr, fld in (
                ("argument_size_in_bytes", "argument_bytes"),
                ("output_size_in_bytes", "output_bytes"),
                ("temp_size_in_bytes", "temp_bytes"),
                ("alias_size_in_bytes", "alias_bytes"),
                ("generated_code_size_in_bytes", "generated_code_bytes"),
            ):
                try:
                    setattr(rec, fld, int(getattr(mem, attr, 0) or 0))
                except (TypeError, ValueError):
                    pass
        try:
            cost = compiled.cost_analysis()
        except Exception as exc:  # noqa: BLE001
            logger.debug(f"cost_analysis({label}) unavailable: {exc}")
            cost = None
        rec.flops = _first_scalar(cost, "flops")
        rec.bytes_accessed = _first_scalar(cost, "bytes accessed")
        with self._lock:
            self._programs[label] = rec
        return rec

    def register_analysis(
        self,
        label: str,
        *,
        kind: str = "train",
        argument_bytes: int = 0,
        output_bytes: int = 0,
        temp_bytes: int = 0,
        alias_bytes: int = 0,
        generated_code_bytes: int = 0,
        flops: float = 0.0,
        bytes_accessed: float = 0.0,
        compile_seconds: float = 0.0,
        **meta: Any,
    ) -> ProgramRecord:
        """Direct registration from already-extracted numbers (tests,
        synthetic programs, external tooling)."""
        rec = ProgramRecord(
            label=label, kind=kind,
            argument_bytes=int(argument_bytes),
            output_bytes=int(output_bytes),
            temp_bytes=int(temp_bytes),
            alias_bytes=int(alias_bytes),
            generated_code_bytes=int(generated_code_bytes),
            flops=float(flops), bytes_accessed=float(bytes_accessed),
            compile_seconds=float(compile_seconds),
            registered_at=time.time(), meta=dict(meta),
        )
        with self._lock:
            self._programs[label] = rec
        return rec

    # ------------------------------------------------------------- #
    # collective audits (the sharding X-ray)
    # ------------------------------------------------------------- #
    def attach_audit(self, label: str, audit: Any) -> Any:
        """Store an already-built :class:`ProgramAudit` under ``label``
        (idempotent — a re-audit replaces its predecessor)."""
        with self._lock:
            self._audits[label] = audit
        return audit

    def audit(
        self,
        label: str,
        compiled: Any,
        *,
        contract: Any = None,
        num_devices: Optional[int] = None,
        num_slices: Optional[int] = None,
    ) -> Optional[Any]:
        """Audit one ``jax.stages.Compiled``'s HLO for collectives and
        store the result under ``label``.

        Best-effort like :meth:`register_compiled`: if the executable
        cannot render HLO text (exotic backends), returns None and
        stores nothing. Never raises.
        """
        from .hlo_audit import audit_compiled

        try:
            audit = audit_compiled(
                label, compiled, contract=contract,
                num_devices=num_devices, num_slices=num_slices,
            )
        except Exception as exc:  # noqa: BLE001 — observability never fatal
            logger.debug(f"audit({label}) failed: {exc}")
            return None
        if audit is not None:
            self.attach_audit(label, audit)
        return audit

    def get_audit(self, label: str) -> Optional[Any]:
        with self._lock:
            return self._audits.get(label)

    def audits(self) -> dict[str, Any]:
        with self._lock:
            return dict(self._audits)

    def audit_summary(self, labels: Optional[list] = None) -> dict:
        """Ledger roll-up over stored audits (optionally restricted to
        ``labels``): total/ICI/DCN bytes, violation count + details."""
        from .hlo_audit import summarize_audits

        with self._lock:
            audits = [
                a for lbl, a in self._audits.items()
                if labels is None or lbl in labels
            ]
        return summarize_audits(audits)

    # ------------------------------------------------------------- #
    # queries
    # ------------------------------------------------------------- #
    def top_programs(self, k: int = 3, by: str = "temp_bytes") -> list[dict]:
        """The ``k`` largest programs by ``by`` (an int/float record
        field, or ``"total_bytes"``), JSON-ready, descending."""
        ranked = sorted(
            self.programs(), key=lambda r: -float(getattr(r, by, 0) or 0),
        )
        return [r.as_dict() for r in ranked[: max(k, 0)]]

    def temp_peak_bytes(self) -> int:
        """Worst-case transient HBM: programs run one at a time per
        device, so the scratch peak is the MAX over programs, not the
        sum."""
        return max(
            (r.total_bytes for r in self.programs()), default=0,
        )

    def ledger(
        self,
        owner_bytes: Optional[dict[str, int]] = None,
        capacity_bytes: Optional[int] = None,
    ) -> dict:
        """The HBM budget: owner-resident bytes + the per-program temp
        peak vs device capacity.

        ``owner_bytes`` is typically the census's per-owner breakdown
        (params / opt / KV pools / adapters / unowned); ``capacity``
        defaults to the device's reported ``bytes_limit`` (0 on CPU,
        leaving headroom None).
        """
        owners = {k: int(v) for k, v in (owner_bytes or {}).items()}
        owned = sum(owners.values())
        if capacity_bytes is None:
            from ..utils.profiling import device_memory_stats

            try:
                import jax

                capacity_bytes = int(
                    device_memory_stats(jax.devices()[0]).get(
                        "bytes_limit", 0,
                    )
                )
            except Exception:  # noqa: BLE001
                capacity_bytes = 0
        temp_peak = self.temp_peak_bytes()
        ledger = {
            "owners": owners,
            "owned_bytes": owned,
            "program_temp_peak_bytes": temp_peak,
            "budget_bytes": owned + temp_peak,
            "capacity_bytes": int(capacity_bytes or 0),
            "num_programs": len(self),
        }
        if capacity_bytes:
            ledger["headroom_bytes"] = (
                int(capacity_bytes) - ledger["budget_bytes"]
            )
        return ledger

    def roofline(
        self,
        label: str,
        achieved_step_s: Optional[float] = None,
        *,
        peak_flops: Optional[float] = None,
        peak_bytes_per_s: Optional[float] = None,
    ) -> Optional[dict]:
        """Roofline placement for one program, with the achieved-vs-
        peak-bound MFU gap when a measured step time is supplied.

        ``peak_bound_mfu`` is the ceiling the roofline permits at this
        program's arithmetic intensity — ``min(1, intensity / ridge)``;
        a program left of the ridge point is memory-bound and no
        scheduler can push it past ``intensity * BW / peak_flops``.
        ``attribution_gap`` (peak_bound − achieved) is the share of MFU
        lost to *this* program's schedule rather than to physics.

        Without explicit peaks they come from the published per-chip
        table keyed by ``device_kind``; a kind that is not in it
        (CPU included) has no roofline and this returns None.
        """
        rec = self.get(label)
        if rec is None:
            return None
        if peak_flops is None or peak_bytes_per_s is None:
            import jax

            # record-only: a device kind with no published peak (CPU
            # included) has no roofline — None, never a default
            try:
                row = device_peaks(jax.devices()[0].device_kind)
            except ValueError:
                return None
            peak_flops = peak_flops or row["flops_per_s"]
            peak_bytes_per_s = peak_bytes_per_s or row["hbm_bytes_per_s"]
        intensity = rec.arithmetic_intensity
        if intensity is None or not peak_flops or not peak_bytes_per_s:
            return None
        ridge = peak_flops / peak_bytes_per_s
        peak_bound_mfu = min(1.0, intensity / ridge)
        out = {
            "label": label,
            "flops": rec.flops,
            "bytes_accessed": rec.bytes_accessed,
            "arithmetic_intensity": round(intensity, 4),
            "ridge_intensity": round(ridge, 4),
            "bound": "compute" if intensity >= ridge else "memory",
            "peak_bound_mfu": round(peak_bound_mfu, 4),
            "peak_bound_step_s": round(
                max(rec.flops / peak_flops,
                    rec.bytes_accessed / peak_bytes_per_s), 6,
            ),
        }
        if achieved_step_s and achieved_step_s > 0:
            achieved_mfu = rec.flops / achieved_step_s / peak_flops
            out["achieved_step_s"] = round(achieved_step_s, 6)
            out["achieved_mfu"] = round(achieved_mfu, 4)
            out["attribution_gap"] = round(
                peak_bound_mfu - achieved_mfu, 4,
            )
        return out

    def summary(self) -> dict:
        """Compact JSON-ready snapshot for records/diagnose/autopsies."""
        progs = self.programs()
        return {
            "num_programs": len(progs),
            "temp_peak_bytes": self.temp_peak_bytes(),
            "generated_code_bytes": sum(
                r.generated_code_bytes for r in progs
            ),
            "programs": {r.label: r.as_dict() for r in progs},
        }


_REGISTRY: Optional[ProgramRegistry] = None
_REGISTRY_LOCK = threading.Lock()


def get_program_registry() -> ProgramRegistry:
    """The process-wide registry (created on first use)."""
    global _REGISTRY
    with _REGISTRY_LOCK:
        if _REGISTRY is None:
            _REGISTRY = ProgramRegistry()
        return _REGISTRY


def reset_program_registry() -> None:
    """Drop the process-wide registry (tests; singleton reset hook)."""
    global _REGISTRY
    with _REGISTRY_LOCK:
        _REGISTRY = None

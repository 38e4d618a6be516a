"""Block-granular KV hand-off between prefill and decode replicas.

Prefill/decode disaggregation (Mooncake/DistServe) splits a serving
fleet into two pools: prefill replicas ingest prompts and publish each
finished KV chain as a :class:`TransferManifest`; decode replicas
``acquire()`` a manifest and seat the request straight into the decode
batch. The manifest IS the PR 13 content-addressed chain — per-block
rolling keys plus the per-block host images the PR 17 swap path already
round-trips bitwise (int8 scale rows included) — so a decode replica
that already holds a prefix block (warm CACHED index) dedups it and
only the tail blocks move.

:class:`TransferPlane` is the byte mover + instrument:

* ``inprocess`` backend — zero-copy: manifests carry numpy host arrays
  by reference between engines in one process (CPU tests);
* ``host_buffer`` backend — the real-mesh shape: the prefill side's
  ``jax.device_get`` produced the images; delivery round-trips them
  through contiguous host buffers so a follow-up transport (RDMA, ICI
  proxy) has a single staging contract, and the decode side's
  ``device_put`` happens inside the engine's compiled scatter-restore
  (``_restore_blocks`` puts into the existing cache sharding).

Both backends share the accounting surface the PR 15 plane renders:
bytes moved, blocks moved vs deduped, per-transfer milliseconds, and
stall/drop events (emitted as ``kind="transfer"`` records through any
attached telemetry).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import numpy as np

from ..logging import get_logger

logger = get_logger(__name__)

_BACKENDS = ("inprocess", "host_buffer")


@dataclass
class TransferManifest:
    """One finished prefill, packaged for hand-off.

    Everything a decode replica needs to seat the request
    bitwise-identically to the colocated engine: the request identity
    and sampling knobs, the chain keys addressing each FULL prompt
    block (dedup currency), the per-block host images for every paged
    cache leaf (``data``: leading axis = block position, K/V pools AND
    int8 scale rows — the ``_SwappedRequest`` layout), and the clock
    stamps that keep TTFT/e2e accounting honest across the hop."""

    request_id: str
    prompt: tuple
    max_new_tokens: int
    temperature: float
    eos_token_id: Optional[int]
    adapter: Optional[str]
    priority: int
    # content addressing: rolling chain keys for every FULL prompt
    # block (fingerprint + adapter scoped — PR 13's tenant isolation)
    keys: tuple
    fingerprint: str
    block_size: int
    # the chain: n_blocks host images covering cache_len written tokens
    n_blocks: int
    cache_len: int
    data: list
    nbytes: int
    # decode continues from here: the prefill-side sampled first token
    first_token: int
    # accounting carried across the hop
    submit_time: float
    admit_time: float
    first_token_time: float
    cached_tokens: int
    prefill_chunks: int
    src: str = ""

    def bytes_per_block(self) -> int:
        return self.nbytes // self.n_blocks if self.n_blocks else 0


@dataclass
class _TransferRecord:
    """In-flight ledger entry (router-side)."""

    manifest: TransferManifest
    started_at: float
    state: str = "pending"  # pending | stalled | delivered | dropped
    dst: str = ""
    done_at: float = 0.0
    moved_blocks: int = 0
    deduped_blocks: int = 0
    moved_bytes: int = 0
    attempts: int = 0


class TransferPlane:
    """Moves manifest payloads and keeps the books.

    The plane is deliberately dumb about placement — the router picks
    the destination; the plane's job is the byte movement contract and
    the instrumentation: cumulative counters, bounded per-transfer
    latency samples, and ``kind="transfer"`` telemetry records."""

    def __init__(
        self,
        backend: str = "inprocess",
        *,
        telemetry: Any = None,
        now: Callable[[], float] = time.monotonic,
        max_samples: int = 4096,
    ):
        if backend not in _BACKENDS:
            raise ValueError(
                f"backend must be one of {_BACKENDS}, got {backend!r}"
            )
        self.backend = backend
        self._telemetry = telemetry
        self._now = now
        self.transfers_total = 0
        self.bytes_moved_total = 0
        self.blocks_moved_total = 0
        self.blocks_deduped_total = 0
        self.stalls_total = 0
        self.stall_seconds_total = 0.0
        self.drops_total = 0
        self._ms_samples: list[float] = []
        self._max_samples = max_samples

    # ------------------------------------------------------------------ #
    # byte movement
    # ------------------------------------------------------------------ #
    def stage(self, manifest: TransferManifest) -> TransferManifest:
        """Prepare a manifest's payload for the wire.

        ``inprocess``: zero-copy — the host arrays pass by reference.
        ``host_buffer``: each leaf's rows are packed into one contiguous
        C-order buffer (what an RDMA/ICI transport would register); the
        copy also decouples the payload from the prefill engine's
        buffers, the behavior a cross-process transport guarantees."""
        if self.backend == "inprocess":
            return manifest
        manifest.data = [
            np.ascontiguousarray(d) for d in manifest.data
        ]
        return manifest

    def record_delivery(
        self,
        manifest: TransferManifest,
        *,
        src: str,
        dst: str,
        moved_blocks: int,
        deduped_blocks: int,
        moved_bytes: int,
        ms: float,
    ) -> None:
        self.transfers_total += 1
        self.bytes_moved_total += moved_bytes
        self.blocks_moved_total += moved_blocks
        self.blocks_deduped_total += deduped_blocks
        self._ms_samples.append(ms)
        if len(self._ms_samples) > self._max_samples:
            del self._ms_samples[: len(self._ms_samples) - self._max_samples]
        self._tele(
            "record_transfer",
            request_id=manifest.request_id,
            src=src,
            dst=dst,
            bytes=moved_bytes,
            blocks_moved=moved_blocks,
            blocks_deduped=deduped_blocks,
            transfer_ms=ms,
        )

    def record_stall(self, secs: float, replica: Optional[str] = None) -> None:
        self.stalls_total += 1
        self.stall_seconds_total += secs
        self._tele(
            "record_transfer_stall", secs=secs, replica=replica or ""
        )

    def record_drop(self, manifest: TransferManifest, reason: str) -> None:
        self.drops_total += 1
        self._tele(
            "record_transfer_drop",
            request_id=manifest.request_id,
            reason=reason,
        )

    def _tele(self, method: str, **fields) -> None:
        if self._telemetry is None:
            return
        fn = getattr(self._telemetry, method, None)
        if fn is not None:
            fn(**fields)

    # ------------------------------------------------------------------ #
    # the books
    # ------------------------------------------------------------------ #
    @property
    def dedup_ratio(self) -> float:
        handled = self.blocks_moved_total + self.blocks_deduped_total
        return self.blocks_deduped_total / handled if handled else 0.0

    def summary(self) -> dict:
        samples = sorted(self._ms_samples)

        def pct(p: float) -> float:
            if not samples:
                return 0.0
            rank = p * (len(samples) - 1)
            lo = int(rank)
            hi = min(lo + 1, len(samples) - 1)
            return samples[lo] + (samples[hi] - samples[lo]) * (rank - lo)

        return {
            "backend": self.backend,
            "transfers_total": self.transfers_total,
            "bytes_moved_total": self.bytes_moved_total,
            "blocks_moved_total": self.blocks_moved_total,
            "blocks_deduped_total": self.blocks_deduped_total,
            "dedup_ratio": self.dedup_ratio,
            "transfer_ms_p50": pct(0.50),
            "transfer_ms_p95": pct(0.95),
            "stalls_total": self.stalls_total,
            "stall_seconds_total": self.stall_seconds_total,
            "drops_total": self.drops_total,
        }

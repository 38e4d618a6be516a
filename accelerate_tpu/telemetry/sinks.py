"""Pluggable telemetry export sinks.

A sink receives every step record (a flat-ish JSON-able dict, schema
below) and ships it somewhere: a JSONL file, a Prometheus textfile, an
experiment tracker. Sinks must never take down training — the collector
catches and rate-limits their errors.

JSONL record schema (one object per line; ``kind`` discriminates):

``kind="meta"`` (first line): ``schema``, ``time_unix``, ``backend``,
``process_index``, ``process_count``, ``local_device_count``.

``kind="step"`` (one per completed step)::

    step               int    optimizer-step counter (host mirror)
    label              str    which step fn ("unified_step#0", ...)
    time_unix          float  wall-clock at record creation
    step_time_s        float  dispatch->block_until_ready wall time
    dispatch_s         float  host-side enqueue time (async health:
                              dispatch_s << step_time_s is the good regime)
    dataloader_wait_s  float  time the loop blocked waiting for a batch
                              since the previous record
    tokens             int?   tokens in the batch (tokens_fn / inferred)
    tokens_per_s       float? tokens / step_time_s
    model_flops_per_s  float? flops_per_token * tokens_per_s (if configured)
    mfu                float? model_flops_per_s / (device_peak_flops * n_dev)
    peak_hbm_bytes     int    device 0 lifetime peak HBM (memory_interval)
    hbm_bytes_in_use   int    device 0 live HBM
    hbm_bytes_limit    int    device 0 capacity (0 when unreported, e.g. CPU)
    host_rss_bytes     int    current process RSS
    retraced           bool   this call (re)compiled (first compile included)
    recompiles         int    cumulative retraces beyond first compiles
    microbatches       int    microbatches this record covers (fused
                              accumulation: K; unfused / no accum: 1)
    dispatches_per_opt_step
                       int    jit dispatches one optimizer step costs
                              (fused: 1; unfused with accumulation: K)
    loss/grad_norm/... float  0-d numeric step metrics (include_step_metrics).
                              grad_norm appears ONLY on sync steps with a
                              finite norm — non-sync microbatch records omit
                              it (never a fake 0.0)

Steps that paid compile cost additionally carry (from ``CompileMonitor``):

    compile_time_s            float  XLA backend-compile seconds this step
    persistent_cache_hits     int    persistent-cache executables reused
    persistent_cache_misses   int    lookups that had to compile
    compile_time_saved_s      float  compile seconds a cache hit avoided

``kind="compile"`` (one per AOT warmup / attributed out-of-step compile)::

    label                    str    step fn the compile belongs to
    source                   str    "warmup" (or caller-provided)
    compile_time_s           float  wall time of lower+compile
    backend_compile_s        float  XLA backend compile seconds within it
    persistent_cache_hits    int    cache hits during the compile
    persistent_cache_misses  int    cache misses during the compile

``kind="checkpoint"`` (one per COMMITTED save; async saves emit from the
background writer thread, after the commit rename)::

    step                        int?   optimizer step the save captured
    dir                         str    committed checkpoint directory
    mode                        str    "sync" | "async"
    blocked_s                   float  train-loop stall: sync = the whole
                                       save; async = snapshot + host-state
                                       capture + writer backpressure ONLY
    background_s                float  hidden writer-thread time
                                       (serialize + write + fsync +
                                       commit); 0 for sync saves
    bytes_written               int    this process's bytes on disk
    write_bandwidth_bytes_per_s float? bytes / IO seconds (background_s
                                       for async, blocked_s for sync)

``kind="serve"`` (one per COMPLETED serving request, emitted by the
ServingEngine at slot retirement)::

    request_id           str    engine-assigned (or caller-supplied) id
    prompt_tokens        int    prompt length in tokens
    new_tokens           int    tokens actually generated (<= max_new:
                                EOS stops early)
    queue_s              float? submit -> slot admission wait
    ttft_s               float? submit -> first token (queue + prefill)
    e2e_s                float? submit -> final token
    decode_tokens_per_s  float? steady-state decode rate for THIS request
                                (excludes the prefill token; null for
                                single-token generations)
    spec_proposed        int    speculative draft tokens proposed for the
                                request (0 when speculation is off)
    spec_accepted        int    drafts the target-model verify accepted
    accept_rate          float? spec_accepted / spec_proposed (null when
                                nothing was proposed)

    The Prometheus sink exports the latency fields and accept_rate as
    summaries — rolling-window p50/p95/p99 quantile lines plus
    cumulative _count and _sum — instead of last-value gauges, and the
    speculation tallies as per-tenant counters
    ``{prefix}_serve_spec_{proposed,accepted}_total{adapter="..."}``.

``kind="span"`` (one per request reaching a TERMINAL state — finished or
shed; emitted by the ServingEngine's span log)::

    request_id       str    the request
    state            str    "finished" | "shed"
    shed_reason      str?   "queue_full" | "queue_deadline" when shed
    prompt_tokens    int    prompt length
    cached_prefix_tokens int prompt tokens served from the prefix cache
                           (prefill skipped them; 0 when caching is off)
    new_tokens       int    tokens generated (0 for shed requests)
    accept_rate      float? speculative-draft accept rate over the
                           request's life (null when none proposed)
    submit_t         float  engine-clock (monotonic) lifecycle stamps;
    admit_t          float? null where the span never reached the edge
    prefill_start_t  float?
    first_token_t    float?
    finish_t         float  terminal stamp (finish or shed instant)
    queue_s          float? derived: admit - submit
    prefill_s        float? derived: first_token - prefill_start
    decode_s         float? derived: finish - first_token
    e2e_s            float? derived: finish - submit

    Invariant: submit_t <= admit_t <= prefill_start_t <= first_token_t
    <= finish_t for finished spans. ``ServingEngine.export_trace(path)``
    renders the span ring as Chrome-trace/Perfetto JSON.

``kind="serve_gauge"`` (live engine posture, sampled every
``gauge_interval`` engine steps; each field becomes a Prometheus gauge
``{prefix}_serve_{field}``)::

    engine_steps                         int    step() calls so far
    queue_depth                          int    requests waiting
    queue_age_p95_s                      float  p95 wait of QUEUED requests
    slots_active                         int    busy decode seats
    slot_occupancy                       float  slots_active / max_slots
    pool_blocks_free                     int    KV pool posture
    pool_blocks_allocated                int
    pool_blocks_cached                   int    refcount-0 blocks in the
                                                prefix-cache LRU
    pool_utilization                     float
    shared_blocks                        int    blocks held by >= 2 slots
    prefix_cache_hit_rate                float  lookups hitting >= 1 block
    cow_copies_total                     int    copy-on-write block copies
    prefill_tokens_saved_total           int    prompt tokens never prefilled
    tokens_in_flight                     int    KV tokens held by active slots
    live_block_share                     float  table entries holding live
                                                positions / (max_slots x
                                                max_blocks): what the decode
                                                kernel reads of a gather
    admission_blocked_no_free_slot_total  int   admit() stalls: batch full
    admission_blocked_pool_exhausted_total int  admit() stalls: pool empty
    shed_queue_full_total                int    cumulative sheds per reason
    shed_queue_deadline_total            int
    spec_rounds                          int    speculative verify rounds run
    spec_tokens_proposed                 int    cumulative drafts proposed
    spec_tokens_accepted                 int    cumulative drafts accepted
    spec_accept_rate                     float  lifetime accepted / proposed
    swapped_blocks                       int    KV blocks parked in host RAM
    swapped_requests                     int    preempted requests waiting
    swap_bytes_held                      int    host bytes of swapped KV
    preempts_total                       int    cumulative preemptions (+
    preempts_{priority,pool,growth}_total int   per-reason splits)
    resumes_total                        int    preempted requests resumed
    prefill_chunks_total                 int    chunked-prefill calls run
    prefill_real_token_share             float  real tokens / bucket tokens of
                                                every prefill so far (0.0
                                                before the first)
    kv_bytes_per_token                   float  KV+scale bytes per cached
                                                token (int8 shrinks this)
    pool_alias_bytes                     int    bytes the captured prefill/
                                                decode/verify programs keep
                                                in their input's buffers, the
                                                least of them: the pool's
                                                bytes when it is written in
                                                place (0 before a capture)

``kind="memory"`` (one per live-buffer census, every
``census_interval`` emitted step records — or on demand via
``StepTelemetry.sample_memory``; ONE schema unifies device and host,
with the step-record field names kept as-is so existing readers keep
working)::

    census_total_bytes    int   sum of every live jax.Array's nbytes
    census_unowned_bytes  int   live bytes no registered owner claimed —
                                the leak detector's signal
    census_owner_bytes    dict  {owner: bytes} per registered owner
                                (params / opt_state / kv_pool /
                                adapters / draft KV / ...); the
                                Prometheus sink exports each as
                                {prefix}_hbm_bytes{owner="..."} plus an
                                owner="unowned" series
    census_arrays         int   number of live arrays walked
    hbm_bytes_in_use      int   allocator view (same names as step
    peak_hbm_bytes        int   records — the device half of the
    hbm_bytes_limit       int   unified schema)
    host_rss_bytes        int   current process RSS (host half; the old
    host_rss_peak_bytes   int   PeakHostMemory sampling folded in — the
                                peak is the max RSS across censuses)
    step                  int?  step at sampling time when known

``kind="shed"`` (one per request refused/evicted under overload; the
Prometheus sink counts these as
``{prefix}_serve_shed_total{reason="..."}``)::

    request_id      str    the refused request
    reason          str    "queue_full" (tail-dropped at max_queue) |
                           "queue_deadline" (waited > max_queue_delay_s)
    queue_s         float  how long it waited before shedding
    prompt_tokens   int    what was refused (capacity forensics)
    max_new_tokens  int

``kind="preempt"`` (one per running request swapped out to host RAM to
fund a more important one; unlike a shed the request resumes later
bitwise-identical. The Prometheus sink counts these as
``{prefix}_serve_preempt_total{reason="..."}``)::

    request_id      str    the victim request
    reason          str    "priority" (outranked by a higher-priority
                           arrival) | "pool" (head-of-line aging past
                           its deadline budget) | "growth" (a running
                           slot could not fund its next KV block)
    blocks          int    KV blocks swapped to host
    swap_bytes      int    host bytes the swapped image occupies
    cache_len       int    tokens of KV context at preemption
    priority        int    the victim's priority

``kind="slo"`` (every ``SLOConfig.interval_steps`` engine steps;
numeric fields become ``{prefix}_slo_{field}`` gauges)::

    target               float  required attainment fraction (e.g. 0.99)
    ttft_objective_s     float  the latency objectives
    e2e_objective_s      float
    requests_total       int    lifetime finished requests
    requests_fast_window int    requests inside each burn window
    requests_slow_window int
    {ttft,e2e}_attainment        float? lifetime fraction meeting objective
    {ttft,e2e}_attainment_window float? over the slow window
    {ttft,e2e}_burn_fast         float  error_rate / (1 - target) per window
    {ttft,e2e}_burn_slow         float  (1.0 = burning budget exactly at
                                        the sustainable rate)
    max_burn_rate        float  worst burn across objectives/windows
    breach               bool   fast AND slow burn >= threshold for some
                                objective (routed to the anomaly detector)
    breached_objectives  list   which objectives breached

``kind="soak"`` (one per loadgen phase end plus a ``phase="final"``
summary; numeric fields become ``{prefix}_loadgen_{field}`` gauges —
offered vs. achieved rate and arrival lag for the open-loop soak
harness)::

    phase                str    phase name ("warmup", "ramp-2", "soak",
                                "fault", "recovery", "final")
    phase_kind           str    the phase's semantic kind
    offered_rps          float  the arrival process's configured rate
    achieved_rps         float  finished requests / phase duration
    goodput_tokens_per_s float  tokens/s counting only requests whose
                                TTFT met the objective
    arrival_lag_p95_s    float  p95 of (actual submit - scheduled
                                arrival) — the coordinated-omission
                                guard made visible
    shed                 int    requests shed during the phase
    slo_violations       int    finished requests missing the objective
    breach               bool   multi-window burn breach seen in phase
                                (routed to the anomaly detector)
    capacity_rps_at_breach_point float? (final record) ramp headline
    recovery_s           float? (final record) fault time-to-recover

``kind="goodput"`` (every ``goodput_interval`` steps when diagnostics is
on; the wall-clock attribution fold)::

    step                 int?   step at emission
    wall_s               float  run wall-clock so far
    goodput_pct          float? productive / wall * 100 (run so far)
    rolling_goodput_pct  float? same over the last goodput_window_s
    productive_s         float  step execution minus in-step compile
    badput_compile_s     float  in-step retraces + AOT warmups
    badput_dataloader_s  float  host blocked waiting for batches
    badput_checkpoint_s  float  train-loop blocked seconds of saves
                                (async background time is NOT badput)
    badput_idle_s        float  unaccounted remainder (setup, eval,
                                recovery); buckets sum to wall_s

``kind="anomaly"`` (rate-limited: at most one per type per
``anomaly_cooldown_steps`` / ``anomaly_cooldown_s``)::

    anomaly_type           str    "slow_step" | "loss_spike" | "nan_grad"
                                  | "memory_leak" (monotone unowned-
                                  census growth)
    step                   int?   offending step
    value                  float  offending value (step seconds / loss /
                                  the non-finite scalar)
    baseline_median        float  rolling median at detection (baselined
    baseline_mad           float  types only)
    suppressed_since_last  int    rate-limited repeats since the previous
                                  emitted record of this type
    total_of_type          int    cumulative count including suppressed
    record                 dict   the offending step's FULL record — the
                                  evidence travels with the alarm

Fields marked ``?`` are null when not derivable; memory fields are absent
on steps skipped by ``memory_interval``.
"""

from __future__ import annotations

import json
import os
import time
from collections import deque
from typing import Any, Iterable, Optional, Union

from ..logging import get_logger

logger = get_logger(__name__)

SCHEMA_VERSION = 1


class TelemetrySink:
    """Base class: implement ``emit``; ``close`` if you hold resources."""

    def emit(self, record: dict) -> None:
        raise NotImplementedError

    def close(self) -> None:
        pass


class JSONLSink(TelemetrySink):
    """Zero-dependency append-only JSONL file, flushed per record so a
    killed job keeps every completed step (the driver-timeout
    lesson). Greppable, rsyncable off a pod, ``pandas.read_json(...,
    lines=True)``-able."""

    def __init__(self, path: Union[str, os.PathLike]):
        self.path = os.fspath(path)
        parent = os.path.dirname(self.path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        self._file = open(self.path, "a", buffering=1)

    def emit(self, record: dict) -> None:
        self._file.write(json.dumps(record, default=str) + "\n")
        self._file.flush()

    def close(self) -> None:
        if not self._file.closed:
            # fsync before close: the JSONL is frequently the only record
            # of a run that is about to be SIGKILLed by its scheduler
            self._file.flush()
            try:
                os.fsync(self._file.fileno())
            except OSError:
                pass  # not every target supports fsync (pipes, some FUSE)
            self._file.close()


# metric-name map for the Prometheus dump: seconds get proper unit names
_PROM_RENAMES = {
    "step_time_s": "step_time_seconds",
    "dispatch_s": "dispatch_seconds",
    "dataloader_wait_s": "dataloader_wait_seconds",
    "tokens_per_s": "tokens_per_second",
    "time_unix": None,  # redundant with the scrape timestamp
    "schema": None,
}

# serve-record latency fields exported as Prometheus SUMMARIES (quantile
# lines + _count/_sum) rather than last-value gauges — a per-request
# latency gauge is meaningless the moment the next request lands
_SERVE_SUMMARY_FIELDS = {
    "ttft_s": "serve_ttft_seconds",
    "e2e_s": "serve_e2e_seconds",
    "queue_s": "serve_queue_seconds",
    "decode_tokens_per_s": "serve_decode_tokens_per_second",
    # speculative decoding: per-request draft accept rate (absent from
    # the record when no drafts were proposed, so the summary only
    # aggregates requests speculation actually touched)
    "accept_rate": "serve_spec_accept_rate",
}

# serve-record speculation tallies exported as per-tenant COUNTERS
# ({prefix}_serve_spec_{proposed,accepted}_total) — a last-value gauge
# of a per-request count is meaningless; the monotonic totals are what
# rate() wants
_SERVE_SPEC_COUNTER_FIELDS = {
    "spec_proposed": "serve_spec_proposed_total",
    "spec_accepted": "serve_spec_accepted_total",
}

_SERVE_QUANTILES = (0.5, 0.95, 0.99)


def _quantile(values: list, q: float) -> float:
    """Linear-interpolation quantile (q in [0, 1]) over a non-empty
    list — numpy's default method, without numpy."""
    vals = sorted(values)
    if len(vals) == 1:
        return vals[0]
    pos = (len(vals) - 1) * q
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


class PrometheusTextSink(TelemetrySink):
    """Latest-value gauges in Prometheus text exposition format, written
    atomically to ``path`` on every record — point node_exporter's
    textfile collector (or a sidecar cat) at it. No client library, no
    daemon: the step loop is the exporter.

    ``path=None`` keeps the sink in-memory only: :meth:`render` returns
    the current exposition text (what the HTTP ``/metrics`` endpoint
    serves) without ever touching disk."""

    def __init__(
        self,
        path: Optional[Union[str, os.PathLike]] = None,
        prefix: str = "accelerate_tpu",
        summary_window: int = 1024,
    ):
        self.path = os.fspath(path) if path is not None else None
        self.prefix = prefix
        if self.path:
            parent = os.path.dirname(self.path)
            if parent:
                os.makedirs(parent, exist_ok=True)
        self._gauges: dict[tuple[str, str], float] = {}  # (metric, label) -> value
        # (metric, label_name, label_value) -> latest value; gauges with
        # a semantic label dimension (hbm_bytes{owner=...})
        self._labeled_gauges: dict[tuple[str, str, str], float] = {}
        # (metric, label_name, label_value) -> monotonic count
        self._counters: dict[tuple[str, str, str], float] = {}
        # (metric, label) -> rolling observation window for quantiles;
        # _count/_sum stay cumulative (Prometheus summary semantics)
        self._summary_window = int(summary_window)
        self._summaries: dict[tuple[str, str], deque] = {}
        self._summary_counts: dict[tuple[str, str], int] = {}
        self._summary_sums: dict[tuple[str, str], float] = {}
        # (metric, ((lname, lvalue), ...)) -> value; gauges with several
        # label dimensions (collective_bytes{program,kind,fabric})
        self._multi_gauges: dict[
            tuple[str, tuple[tuple[str, str], ...]], float
        ] = {}

    def emit(self, record: dict) -> None:
        kind = record.get("kind")
        if kind == "serve":
            self._emit_serve(record)
            return
        if kind == "serve_gauge":
            self._emit_prefixed_gauges(record, "serve")
            return
        if kind == "memory":
            self._emit_memory(record)
            return
        if kind == "slo":
            self._emit_slo(record)
            return
        if kind == "soak":
            # loadgen posture: offered vs. achieved rate, goodput under
            # SLO, arrival lag — the open-loop harness's live gauges
            self._emit_prefixed_gauges(record, "loadgen")
            return
        if kind == "shed":
            reason = str(record.get("reason", "unknown"))
            key = (f"{self.prefix}_serve_shed_total", "reason", reason)
            self._counters[key] = self._counters.get(key, 0.0) + 1.0
            self._write()
            return
        if kind == "preempt":
            reason = str(record.get("reason", "unknown"))
            key = (f"{self.prefix}_serve_preempt_total", "reason", reason)
            self._counters[key] = self._counters.get(key, 0.0) + 1.0
            self._write()
            return
        if kind == "audit":
            self._emit_audit(record)
            return
        if kind == "span":
            return  # per-request traces belong in JSONL/Perfetto, not gauges
        if kind not in (None, "step", "goodput"):
            return
        label = str(record.get("label", "step"))
        for key, value in record.items():
            if isinstance(value, bool) or not isinstance(value, (int, float)):
                continue
            name = _PROM_RENAMES.get(key, key)
            if name is None:
                continue
            self._gauges[(f"{self.prefix}_{name}", label)] = float(value)
        self._write()

    def _emit_prefixed_gauges(self, record: dict, section: str) -> None:
        label = str(record.get("label", "serve"))
        for key, value in record.items():
            if isinstance(value, bool) or not isinstance(value, (int, float)):
                continue
            if _PROM_RENAMES.get(key, key) is None:
                continue
            self._gauges[
                (f"{self.prefix}_{section}_{key}", label)
            ] = float(value)
        self._write()

    def _emit_memory(self, record: dict) -> None:
        # per-owner HBM attribution: one gauge family with an "owner"
        # label dimension ({prefix}_hbm_bytes{owner="kv_pool"}), plus
        # the scalar fields as {prefix}_memory_* gauges
        owners = dict(record.get("census_owner_bytes") or {})
        if record.get("census_unowned_bytes") is not None:
            owners["unowned"] = record["census_unowned_bytes"]
        for owner, value in owners.items():
            if isinstance(value, bool) or not isinstance(value, (int, float)):
                continue
            self._labeled_gauges[
                (f"{self.prefix}_hbm_bytes", "owner", str(owner))
            ] = float(value)
        self._emit_prefixed_gauges(record, "memory")

    def _emit_audit(self, record: dict) -> None:
        # sharding X-ray inventory: bytes moved per compiled program,
        # collective kind and fabric —
        # {prefix}_collective_bytes{program="serve_decode",
        #   kind="all-gather",fabric="ici"} — plus a per-program
        # violation-count gauge (0 = contract clean, alertable as > 0)
        program = str(record.get("program") or record.get("label") or "")
        for combo, value in (record.get("bytes_by_kind_fabric") or {}).items():
            if isinstance(value, bool) or not isinstance(value, (int, float)):
                continue
            ckind, _, fabric = str(combo).partition("|")
            self._multi_gauges[(
                f"{self.prefix}_collective_bytes",
                (("program", program), ("kind", ckind),
                 ("fabric", fabric or "ici")),
            )] = float(value)
        viols = record.get("violations")
        if viols is not None:
            self._labeled_gauges[(
                f"{self.prefix}_sharding_violations", "program", program,
            )] = float(len(viols))
        self._write()

    def _emit_slo(self, record: dict) -> None:
        label = str(record.get("label", "serve"))
        for key, value in record.items():
            if key == "breach":  # the one bool worth a gauge (0/1 alert line)
                value = 1.0 if value else 0.0
            elif isinstance(value, bool) or not isinstance(value, (int, float)):
                continue
            if _PROM_RENAMES.get(key, key) is None:
                continue
            self._gauges[(f"{self.prefix}_slo_{key}", label)] = float(value)
        self._write()

    def _emit_serve(self, record: dict) -> None:
        label = str(record.get("label", "serve"))
        # per-tenant request counter: every finished request increments
        # {prefix}_serve_requests_total{adapter="<name>"} ("none" = the
        # base model) — the multi-tenant traffic split at a glance
        adapter = str(record.get("adapter_id") or "none")
        ckey = (f"{self.prefix}_serve_requests_total", "adapter", adapter)
        self._counters[ckey] = self._counters.get(ckey, 0.0) + 1.0
        for key, value in record.items():
            if isinstance(value, bool) or not isinstance(value, (int, float)):
                continue
            counter = _SERVE_SPEC_COUNTER_FIELDS.get(key)
            if counter is not None:
                if value:
                    sckey = (f"{self.prefix}_{counter}", "adapter", adapter)
                    self._counters[sckey] = (
                        self._counters.get(sckey, 0.0) + float(value)
                    )
                continue
            name = _SERVE_SUMMARY_FIELDS.get(key)
            if name is not None:
                slot = (f"{self.prefix}_{name}", label)
                window = self._summaries.setdefault(
                    slot, deque(maxlen=self._summary_window)
                )
                window.append(float(value))
                self._summary_counts[slot] = self._summary_counts.get(slot, 0) + 1
                self._summary_sums[slot] = (
                    self._summary_sums.get(slot, 0.0) + float(value)
                )
                continue
            if _PROM_RENAMES.get(key, key) is None:
                continue
            self._gauges[(f"{self.prefix}_serve_{key}", label)] = float(value)
        self._write()

    @staticmethod
    def _escape_label(value: str) -> str:
        # Prometheus text exposition: \, " and newline must be escaped
        # inside quoted label values or the scrape breaks
        return (
            value.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")
        )

    def render(self) -> str:
        """The full exposition text (what ``/metrics`` serves and what
        ``_write`` puts on disk)."""
        lines = []
        for metric in sorted({m for m, _ in self._gauges}):
            lines.append(f"# TYPE {metric} gauge")
            for (m, label), value in sorted(self._gauges.items()):
                if m == metric:
                    escaped = self._escape_label(label)
                    lines.append(f'{metric}{{label="{escaped}"}} {value}')
        for metric in sorted({m for m, _, _ in self._labeled_gauges}):
            lines.append(f"# TYPE {metric} gauge")
            for (m, lname, lvalue), value in sorted(
                self._labeled_gauges.items()
            ):
                if m == metric:
                    escaped = self._escape_label(lvalue)
                    lines.append(f'{metric}{{{lname}="{escaped}"}} {value}')
        for metric in sorted({m for m, _ in self._multi_gauges}):
            lines.append(f"# TYPE {metric} gauge")
            for (m, labels), value in sorted(self._multi_gauges.items()):
                if m == metric:
                    inner = ",".join(
                        f'{ln}="{self._escape_label(lv)}"'
                        for ln, lv in labels
                    )
                    lines.append(f"{metric}{{{inner}}} {value}")
        for metric in sorted({m for m, _, _ in self._counters}):
            lines.append(f"# TYPE {metric} counter")
            for (m, lname, lvalue), value in sorted(self._counters.items()):
                if m == metric:
                    escaped = self._escape_label(lvalue)
                    lines.append(f'{metric}{{{lname}="{escaped}"}} {value}')
        for metric in sorted({m for m, _ in self._summaries}):
            lines.append(f"# TYPE {metric} summary")
            for (m, label), window in sorted(self._summaries.items()):
                if m != metric or not window:
                    continue
                escaped = self._escape_label(label)
                values = list(window)
                for q in _SERVE_QUANTILES:
                    lines.append(
                        f'{metric}{{label="{escaped}",quantile="{q}"}} '
                        f"{_quantile(values, q)}"
                    )
                lines.append(
                    f'{metric}_count{{label="{escaped}"}} '
                    f"{self._summary_counts[(m, label)]}"
                )
                lines.append(
                    f'{metric}_sum{{label="{escaped}"}} '
                    f"{self._summary_sums[(m, label)]}"
                )
        return "\n".join(lines) + "\n"

    def _write(self) -> None:
        if self.path is None:
            return
        tmp = f"{self.path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(self.render())
        os.replace(tmp, self.path)  # scrapers never see a torn file

    def close(self) -> None:
        if (
            self._gauges
            or self._labeled_gauges
            or self._counters
            or self._summaries
        ):
            self._write()


class TrackerBridgeSink(TelemetrySink):
    """Forward numeric record fields to ``tracking.py`` trackers
    (``tracker.log({prefix+k: v}, step=...)``) — any of the 8 backends
    (wandb/tensorboard/mlflow/...) becomes a telemetry sink. Pass the
    tracker list (e.g. ``accelerator.trackers``) or an object exposing
    ``.trackers`` (the Accelerator itself, resolved lazily so the bridge
    can be attached before ``init_trackers``)."""

    def __init__(self, trackers: Any, prefix: str = "telemetry/"):
        self._source = trackers
        self.prefix = prefix

    def _trackers(self) -> Iterable[Any]:
        src = self._source
        if hasattr(src, "trackers"):
            return src.trackers
        return src

    def emit(self, record: dict) -> None:
        if record.get("kind") not in (None, "step", "goodput"):
            return
        values = {
            f"{self.prefix}{k}": v
            for k, v in record.items()
            if not isinstance(v, bool)
            and isinstance(v, (int, float))
            and k not in ("step", "time_unix", "schema")
        }
        if not values:
            return
        step = record.get("step")
        for tracker in self._trackers():
            tracker.log(values, step=step)

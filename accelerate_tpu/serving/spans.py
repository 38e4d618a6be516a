"""Request-lifecycle spans: every request tells its own timing story.

A completed ``kind="serve"`` record says how long a request took; a SPAN
says where the time went and — crucially — exists for requests that
never complete. Every request gets monotonic timestamps at each
lifecycle edge (submit → admit → prefill → first token → finish/shed),
and the terminal transition emits one ``kind="span"`` record through the
telemetry stack, so a stuck queue, a shedding engine and a healthy one
all look different in the stream (the blind spot this module closes:
completion-only telemetry cannot distinguish overloaded from idle).

The :class:`SpanLog` keeps the last ``maxlen`` closed spans in a ring —
:func:`spans_to_chrome_trace` turns them into Chrome-trace/Perfetto JSON
(``ServingEngine.export_trace``), and when diagnostics is attached the
span records also ride into the PR 5 flight recorder's ring, so a
SIGKILL'd server still tells its story.

Ordering invariant (asserted by tests, relied on by the exporter):
``submit_t <= admit_t <= prefill_start_t <= first_token_t <= finish_t``
for finished spans; shed spans stop at the edge they reached.
"""

from __future__ import annotations

import collections
import json
from dataclasses import dataclass
from typing import Iterable, Optional

#: terminal span states; everything else ("queued", "running") is live
TERMINAL_STATES = ("finished", "shed")


@dataclass
class RequestSpan:
    """Monotonic lifecycle timestamps for ONE request (engine clock)."""

    request_id: str
    submit_t: float
    prompt_tokens: int = 0
    # multi-tenant serving: which adapter the request decodes under
    # (None = the base model)
    adapter_id: Optional[str] = None
    admit_t: Optional[float] = None
    prefill_start_t: Optional[float] = None
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None
    state: str = "queued"  # queued | running | finished | shed
    shed_reason: Optional[str] = None  # "queue_full" | "queue_deadline"
    new_tokens: int = 0
    # prefix caching: prompt tokens whose KV came from the shared cache
    # (prefill skipped them) — 0 for cold requests / caching off
    cached_prefix_tokens: int = 0
    # speculative decoding: accepted/proposed draft tokens over the
    # request's life (None = no drafts were ever proposed for it)
    accept_rate: Optional[float] = None
    # preemption: times this request was swapped out to host RAM and
    # re-admitted (0 = it kept its seat for its whole flight)
    preempted_count: int = 0
    # chunked prefill: chunks the prompt ingested in (0 = unchunked)
    chunked: int = 0

    @property
    def terminal(self) -> bool:
        return self.state in TERMINAL_STATES

    def to_record(self) -> dict:
        """The flat ``kind="span"`` record payload (derived durations
        included so sinks need no arithmetic; None where the span never
        reached that edge)."""
        queue_s = (
            self.admit_t - self.submit_t if self.admit_t is not None else None
        )
        prefill_s = (
            self.first_token_t - self.prefill_start_t
            if self.first_token_t is not None
            and self.prefill_start_t is not None
            else None
        )
        decode_s = (
            self.finish_t - self.first_token_t
            if self.finish_t is not None and self.first_token_t is not None
            else None
        )
        e2e_s = (
            self.finish_t - self.submit_t if self.finish_t is not None else None
        )
        return {
            "request_id": self.request_id,
            "state": self.state,
            "shed_reason": self.shed_reason,
            "adapter_id": self.adapter_id,
            "prompt_tokens": self.prompt_tokens,
            "cached_prefix_tokens": self.cached_prefix_tokens,
            "new_tokens": self.new_tokens,
            "accept_rate": self.accept_rate,
            "preempted_count": self.preempted_count,
            "chunked": self.chunked,
            "submit_t": self.submit_t,
            "admit_t": self.admit_t,
            "prefill_start_t": self.prefill_start_t,
            "first_token_t": self.first_token_t,
            "finish_t": self.finish_t,
            "queue_s": queue_s,
            "prefill_s": prefill_s,
            "decode_s": decode_s,
            "e2e_s": e2e_s,
        }


class SpanLog:
    """Open spans by request id plus a bounded ring of closed ones.

    The ring bounds memory on a long-lived server the same way the
    flight recorder bounds its record ring — the LAST ``maxlen``
    terminal spans are always exportable, older ones age out.
    """

    def __init__(self, maxlen: int = 512):
        if maxlen < 1:
            raise ValueError("maxlen must be >= 1")
        self._open: dict[str, RequestSpan] = {}
        self.closed: collections.deque = collections.deque(maxlen=maxlen)
        # False turns every lifecycle hook into a no-op — the
        # observability-off arm of an overhead A/B
        self.enabled = True

    def __len__(self) -> int:
        return len(self._open) + len(self.closed)

    # ------------------------------------------------------------------ #
    # lifecycle edges (the engine stamps these with its injectable clock)
    # ------------------------------------------------------------------ #
    def on_submit(
        self, request_id: str, submit_t: float, prompt_tokens: int = 0,
        adapter_id: Optional[str] = None,
    ) -> Optional[RequestSpan]:
        if not self.enabled:
            return None
        span = RequestSpan(
            request_id=request_id, submit_t=submit_t,
            prompt_tokens=prompt_tokens, adapter_id=adapter_id,
        )
        self._open[request_id] = span
        return span

    def get(self, request_id: str) -> Optional[RequestSpan]:
        return self._open.get(request_id)

    def on_admit(self, request_id: str, t: float) -> Optional[RequestSpan]:
        span = self._open.get(request_id)
        if span is not None:
            span.admit_t = t
            span.state = "running"
        return span

    def on_prefill(
        self, request_id: str, t: float, cached_prefix_tokens: int = 0,
    ) -> Optional[RequestSpan]:
        span = self._open.get(request_id)
        if span is not None:
            span.prefill_start_t = t
            span.cached_prefix_tokens = cached_prefix_tokens
        return span

    def on_first_token(
        self, request_id: str, t: float, chunks: int = 0
    ) -> Optional[RequestSpan]:
        span = self._open.get(request_id)
        if span is not None:
            span.first_token_t = t
            span.chunked = chunks
        return span

    def on_preempt(self, request_id: str, t: float) -> Optional[RequestSpan]:
        """The request was swapped out to host RAM: the span stays OPEN
        (it will finish after resume) but records the preemption — a
        span with preempted_count > 0 and a long prefill→finish gap is
        how a paused request reads in the trace."""
        span = self._open.get(request_id)
        if span is not None:
            span.preempted_count += 1
            span.state = "preempted"
        return span

    def on_resume(self, request_id: str, t: float) -> Optional[RequestSpan]:
        span = self._open.get(request_id)
        if span is not None:
            span.state = "running"
        return span

    def on_finish(
        self, request_id: str, t: float, new_tokens: int,
        accept_rate: Optional[float] = None,
    ) -> Optional[RequestSpan]:
        span = self._open.get(request_id)
        if span is not None:
            span.accept_rate = accept_rate
        return self._close(request_id, t, "finished", None, new_tokens)

    def on_shed(
        self, request_id: str, t: float, reason: str
    ) -> Optional[RequestSpan]:
        return self._close(request_id, t, "shed", reason, 0)

    def _close(
        self,
        request_id: str,
        t: float,
        state: str,
        shed_reason: Optional[str],
        new_tokens: int,
    ) -> Optional[RequestSpan]:
        span = self._open.pop(request_id, None)
        if span is None:
            return None
        span.finish_t = t
        span.state = state
        span.shed_reason = shed_reason
        span.new_tokens = new_tokens
        self.closed.append(span)
        return span

    # ------------------------------------------------------------------ #
    @property
    def open_spans(self) -> list[RequestSpan]:
        return list(self._open.values())

    def summary(self) -> dict:
        closed = list(self.closed)
        return {
            "spans_open": len(self._open),
            "spans_closed": len(closed),
            "spans_shed": sum(1 for s in closed if s.state == "shed"),
        }


def spans_to_chrome_trace(
    spans: Iterable[RequestSpan],
    process_index: int = 0,
    time_origin: Optional[float] = None,
) -> dict:
    """Chrome-trace ("Trace Event Format") JSON payload for Perfetto /
    ``chrome://tracing``: one timeline row per request, complete-phase
    (``ph="X"``) slices for its queue / prefill / decode phases (a shed
    request renders as one ``shed:<reason>`` slice covering its whole
    life). Timestamps are microseconds from ``time_origin`` (default:
    the earliest submit among the spans), so traces start near t=0.
    """
    spans = list(spans)
    if time_origin is None:
        time_origin = min((s.submit_t for s in spans), default=0.0)

    def us(t: float) -> float:
        return (t - time_origin) * 1e6

    events: list[dict] = []
    for tid, span in enumerate(spans):
        events.append({
            "ph": "M", "name": "thread_name", "pid": process_index,
            "tid": tid, "args": {"name": span.request_id},
        })
        args = {
            "request_id": span.request_id,
            "prompt_tokens": span.prompt_tokens,
            "cached_prefix_tokens": span.cached_prefix_tokens,
            "new_tokens": span.new_tokens,
            "state": span.state,
        }
        if span.state == "shed":
            end = span.finish_t if span.finish_t is not None else span.submit_t
            events.append({
                "ph": "X", "name": f"shed:{span.shed_reason}", "cat": "serve",
                "pid": process_index, "tid": tid,
                "ts": us(span.submit_t), "dur": us(end) - us(span.submit_t),
                "args": {**args, "shed_reason": span.shed_reason},
            })
            continue
        phases = []
        if span.admit_t is not None:
            phases.append(("queue", span.submit_t, span.admit_t))
        if span.prefill_start_t is not None and span.first_token_t is not None:
            phases.append(("prefill", span.prefill_start_t, span.first_token_t))
        if span.first_token_t is not None and span.finish_t is not None:
            phases.append(("decode", span.first_token_t, span.finish_t))
        if not phases:  # still queued: render the wait so far as a slice
            phases.append(("queue", span.submit_t, span.submit_t))
        for name, start, end in phases:
            events.append({
                "ph": "X", "name": name, "cat": "serve",
                "pid": process_index, "tid": tid,
                "ts": us(start), "dur": max(us(end) - us(start), 0.0),
                "args": args,
            })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def write_chrome_trace(
    path: str,
    spans: Iterable[RequestSpan],
    process_index: int = 0,
) -> str:
    """Serialize :func:`spans_to_chrome_trace` to ``path``; returns it."""
    payload = spans_to_chrome_trace(spans, process_index=process_index)
    with open(path, "w") as f:
        json.dump(payload, f)
    return path

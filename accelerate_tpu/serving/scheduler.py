"""Continuous (iteration-level) batching: Orca's insight in host code.

A fixed array of decode SLOTS is the device-side batch (static shape —
the decode step compiles once); requests flow through it. At every step
boundary the engine retires finished slots (their blocks return to the
pool immediately) and :meth:`ContinuousScheduler.admit` refills them
from the FIFO queue — a long request never holds the whole batch
hostage the way run-to-completion batching does.

Admission reserves a request's FULL worst-case KV footprint
(``ceil((prompt_len + max_new_tokens) / block_size)`` blocks) up front:
deliberately conservative — an admitted request can never OOM
mid-flight, so there is no preemption/swap path to get wrong. The cost
is queueing earlier than an on-demand-growth scheduler would; for
bounded ``max_new_tokens`` serving that is the right trade.

Overload is observable, not silent: the queue is bounded. ``max_queue``
tail-drops submissions beyond the bound (``shed_reason="queue_full"``)
and ``max_queue_delay_s`` sheds queue-head requests whose wait exceeds
the deadline (``shed_reason="queue_deadline"`` via :meth:`shed_expired`)
— a request a client would have abandoned anyway should not consume
slots. Every shed is counted (``shed_counts``), and :meth:`admit`
attributes WHY admission stalls (``blocked_reasons``: ``no_free_slot``
vs ``pool_exhausted``) so the gauges can tell "batch full" apart from
"KV pool exhausted".
"""

from __future__ import annotations

import itertools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Optional

from .block_pool import BlockPool

_request_counter = itertools.count()


@dataclass
class Request:
    """One generation request. ``prompt`` is a token-id list (tokenizers
    live outside this engine); timing fields are stamped by the
    scheduler/engine clock."""

    prompt: list[int]
    max_new_tokens: int = 32
    temperature: float = 0.0
    eos_token_id: Optional[int] = None
    request_id: str = ""
    # preemption's priority axis: higher admits first (FIFO within a
    # priority level; 0 = the default tier). A lower-priority SEATED
    # request can be preempted — KV swapped to host, resumed later —
    # to fund a higher-priority head (see ServingEngine preemption).
    priority: int = 0
    # multi-tenant serving: name of the adapter to decode under (None =
    # the base model). Admission gates on the adapter being RESIDENT in
    # the engine's AdapterRegistry.
    adapter: Optional[str] = None
    submit_time: float = 0.0
    # set when the scheduler refuses/evicts the request instead of
    # queueing it: "queue_full" | "queue_deadline"
    shed_reason: Optional[str] = None
    # prefix caching: the request's rolling content keys, computed ONCE
    # at first admission attempt and reused at publish time
    prefix_keys: Optional[list] = None

    def __post_init__(self):
        if not self.request_id:
            self.request_id = f"req-{next(_request_counter)}"
        if not self.prompt:
            raise ValueError("empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")


@dataclass
class Slot:
    """One seat in the fixed decode batch plus its per-request state."""

    index: int
    request: Optional[Request] = None
    blocks: list[int] = field(default_factory=list)
    cache_len: int = 0          # tokens written into the paged cache
    generated: list[int] = field(default_factory=list)
    pending: int = 0            # last sampled token, fed to the next step
    done: bool = False
    admit_time: float = 0.0
    first_token_time: float = 0.0
    finish_time: float = 0.0
    # prefix caching: table positions currently pointing at SHARED
    # (read-only) cached blocks; any write into one copy-on-writes first
    shared: set[int] = field(default_factory=set)
    # prompt tokens whose KV is already in the cache — prefill skips them
    cached_tokens: int = 0
    # block reserved at admission for the full-prompt-hit COW (the tail
    # must keep >= 1 token, so a hit covering the WHOLE prompt re-writes
    # the last prompt token into a private copy of its shared block)
    cow_spare: Optional[int] = None
    # table positions whose block was COW'd: private now, but partially
    # recomputed — kept out of the content index
    cow_indices: set[int] = field(default_factory=set)
    # speculative decoding: extra tokens of block reservation granted at
    # admission (0 = this slot decodes plainly — slots seated before
    # speculation was toggled on have no verify headroom and stay plain)
    lookahead: int = 0
    # per-request speculation accounting (accept_rate at finish)
    spec_proposed: int = 0
    spec_accepted: int = 0
    # chunked prefill: prompt tokens whose KV is written so far (equals
    # cache_len while prefilling; prefill is done once it reaches the
    # prompt length) and how many chunks it took (0 = unchunked)
    chunks: int = 0
    # preemption: times this request was swapped out, and whether the
    # current seating is a resume (resumed slots are never re-preempted
    # — the anti-thrash rule)
    preempted_count: int = 0
    resumed: bool = False
    # a cache that is not one row a position (the scheduler's ``layout``):
    # ``cache_len`` stays the request's POSITION; ``windows_done`` counts
    # the windows whose summaries stand in the table
    windows_done: int = 0

    @property
    def busy(self) -> bool:
        return self.request is not None

    @property
    def mid_prefill(self) -> bool:
        """Chunked prefill still ingesting the prompt: the slot holds a
        seat but is not yet in the decode batch."""
        return (
            self.request is not None
            and self.cache_len < len(self.request.prompt)
        )

    def clear(self) -> None:
        self.request = None
        self.blocks = []
        self.cache_len = 0
        self.generated = []
        self.pending = 0
        self.done = False
        self.admit_time = 0.0
        self.first_token_time = 0.0
        self.finish_time = 0.0
        self.shared = set()
        self.cached_tokens = 0
        self.cow_spare = None
        self.cow_indices = set()
        self.lookahead = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.chunks = 0
        self.preempted_count = 0
        self.resumed = False
        self.windows_done = 0


class ContinuousScheduler:
    """Slot admission/eviction policy. ``now`` is injectable (fake-clock
    tests drive queueing-time accounting deterministically)."""

    def __init__(
        self,
        max_slots: int,
        pool: BlockPool,
        now: Callable[[], float] = time.monotonic,
        max_queue: Optional[int] = None,
        max_queue_delay_s: Optional[float] = None,
        adapter_ready: Optional[Callable[[Optional[str]], bool]] = None,
        prefix_cache=None,
        max_table_blocks: Optional[int] = None,
        regime=None,
        chunk_tokens: Optional[int] = None,
        chunked_reserve: bool = False,
    ):
        if max_slots < 1:
            raise ValueError("max_slots must be >= 1")
        if max_queue is not None and max_queue < 1:
            raise ValueError("max_queue must be >= 1 (or None for unbounded)")
        if max_queue_delay_s is not None and max_queue_delay_s <= 0:
            raise ValueError("max_queue_delay_s must be > 0 (or None)")
        self.slots = [Slot(i) for i in range(max_slots)]
        self.pool = pool
        self.queue: deque[Request] = deque()
        self._now = now
        self.max_queue = max_queue
        self.max_queue_delay_s = max_queue_delay_s
        # multi-tenant gate: a request is only seated once its adapter is
        # resident (prefilling against a not-yet-loaded adapter would
        # silently decode under the identity row). None = no gating.
        self.adapter_ready = adapter_ready
        # prefix reuse: an optional block_pool.PrefixCache — admission
        # points new slots' tables at cached chain prefixes instead of
        # allocating (and later prefilling) private copies
        self.prefix_cache = prefix_cache
        # speculative decoding: admission reserves this many EXTRA tokens
        # of block footprint per request (the verify pass writes up to k
        # candidate positions past the cursor before accept/reject is
        # known, and an in-flight verify write must never OOM the pool).
        # Set by ServingEngine.set_speculation; per-request the grant is
        # CLAMPED to what the block table / pool can ever hold so a
        # request that fit without speculation still admits with it on.
        self.lookahead_tokens = 0
        # width of the engine's per-slot block table (positions past it
        # alias the last entry) — the lookahead clamp's second ceiling
        self.max_table_blocks = max_table_blocks
        # chunked prefill: when set (by ServingEngine), admission may
        # reserve only the FIRST chunk's prompt blocks instead of the
        # full worst-case footprint — but only with chunked_reserve,
        # which the engine enables iff preemption is also on (the
        # mid-flight growth path then has preempt-and-swap as its
        # can't-allocate escape, preserving the no-mid-flight-OOM
        # guarantee the full reservation used to provide).
        self.chunk_tokens = chunk_tokens
        self.chunked_reserve = chunked_reserve
        # sticky: set once any nonzero-priority request is submitted —
        # the queue then stops being submit-ordered and shed_expired
        # must scan past the head
        self._saw_priority = False
        # drain mode (set via ServingEngine.drain): admission stops,
        # new submits are refused with shed_reason="draining", seated
        # work finishes — the router's graceful-rotation state
        self.draining = False
        # what a request's cache is (the engine's ``CacheRegime``; None: one
        # row a position): a request's footprint is its word, allocated at
        # admission whatever the kind; where a slot frees blocks before it
        # ends (eva), the engine gives back what the last filled window frees
        self.regime = regime
        self.shed_counts = {"queue_full": 0, "queue_deadline": 0}
        self.blocked_reasons = {
            "no_free_slot": 0,
            "pool_exhausted": 0,
            "adapter_not_resident": 0,
        }
        max_tokens = (pool.num_blocks - 1) * pool.block_size
        self.max_request_tokens = max_tokens

    def footprint(self, tokens: int, start: int = 0) -> int:
        """The most blocks a request holds at once on its way from
        ``start`` positions to ``tokens``."""
        if self.regime is not None:
            return self.regime.footprint(tokens, start)
        return self.pool.blocks_for_tokens(tokens)

    def submit(self, request: Request) -> str:
        need = self.footprint(len(request.prompt) + request.max_new_tokens)
        if need > self.pool.num_blocks - 1:
            raise ValueError(
                f"request needs {need} blocks "
                f"({len(request.prompt)} prompt + {request.max_new_tokens} "
                f"new tokens) but the pool only has "
                f"{self.pool.num_blocks - 1} allocatable blocks total"
            )
        request.submit_time = self._now()
        if self.draining:
            # a draining replica takes no new work; the refusal is a
            # shed (terminal, observable) so callers without a router
            # still see a definite outcome rather than a silent drop
            request.shed_reason = "draining"
            self.shed_counts["draining"] = (
                self.shed_counts.get("draining", 0) + 1
            )
            return request.request_id
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            # tail-drop: the newest request is the one refused (FIFO
            # fairness — those already waiting keep their place)
            request.shed_reason = "queue_full"
            self.shed_counts["queue_full"] += 1
            return request.request_id
        if request.priority != 0:
            self._saw_priority = True
        if self._saw_priority and request.priority != 0:
            # keep the queue (priority desc, submit order asc): walk in
            # from the tail past lower-priority entries. Priority-0
            # traffic (the common case) appends in O(1) below — equal
            # priorities stay strictly FIFO.
            i = len(self.queue)
            while i > 0 and self.queue[i - 1].priority < request.priority:
                i -= 1
            self.queue.insert(i, request)
        else:
            self.queue.append(request)
        return request.request_id

    def shed_expired(self) -> list[Request]:
        """Shed queue-head requests whose wait exceeds
        ``max_queue_delay_s``. FIFO means the head is always the oldest,
        so the scan stops at the first fresh-enough request. Called by
        the engine once per step, before admission."""
        if self.max_queue_delay_s is None:
            return []
        now = self._now()
        shed: list[Request] = []
        if self._saw_priority:
            # priority ordering breaks head-is-oldest: full scan (only
            # once any nonzero-priority request has ever been submitted
            # — pure-FIFO traffic keeps the O(expired) head scan below)
            keep: deque[Request] = deque()
            for req in self.queue:
                if now - req.submit_time > self.max_queue_delay_s:
                    req.shed_reason = "queue_deadline"
                    self.shed_counts["queue_deadline"] += 1
                    shed.append(req)
                else:
                    keep.append(req)
            self.queue = keep
            return shed
        while self.queue:
            req = self.queue[0]
            if now - req.submit_time <= self.max_queue_delay_s:
                break
            self.queue.popleft()
            req.shed_reason = "queue_deadline"
            self.shed_counts["queue_deadline"] += 1
            shed.append(req)
        return shed

    def harvest_queue(self) -> list[Request]:
        """Pop and return every still-queued (unadmitted) request. Used
        by drain/kill paths whose CALLER re-routes the harvest — no
        shed accounting here, because the requests are not lost."""
        out = list(self.queue)
        self.queue.clear()
        return out

    def release(self, slot: Slot) -> None:
        """Return a finished slot's references and empty the seat — the
        very next :meth:`admit` can refill it (continuous batching's
        point). Under prefix caching "return" means RELEASE: a shared
        block merely drops one refcount, and published blocks at
        refcount 0 retire into the pool's cached LRU instead of the free
        list."""
        if slot.blocks:
            self.pool.free(slot.blocks)
        if slot.cow_spare is not None:  # reserved but never written
            self.pool.free([slot.cow_spare])
        slot.clear()

    def admit(self) -> list[Slot]:
        """Fill free slots from the queue head while the pool can fund
        each request's full reservation. Strict FIFO: a head request that
        doesn't fit blocks later ones (no starvation of big requests).

        With a prefix cache attached, the head's longest cached
        block-chain prefix is ACQUIRED (refcounted) instead of allocated,
        and only the uncached remainder of the footprint comes off the
        free list — the engine then prefills only the tail. A hit that
        covers the whole prompt still leaves its LAST token to the tail
        (the first sampled token needs that position's logits), so one
        extra private block is reserved for the engine's copy-on-write
        of the final shared block.
        """
        if self.draining:
            # seats already filled keep decoding; nothing new admits
            # (queued entries wait for harvest_queue or undrain)
            return []
        admitted = []
        free_slots = (s for s in self.slots if not s.busy)
        while self.queue:
            slot = next(free_slots, None)
            if slot is None:
                # queue non-empty but the decode batch is full
                self.blocked_reasons["no_free_slot"] += 1
                break
            req = self.queue[0]
            if (
                self.adapter_ready is not None
                and not self.adapter_ready(req.adapter)
            ):
                # the head's adapter isn't resident yet — strict FIFO
                # means later requests wait too (no tenant starvation by
                # reordering; load the adapter to unblock)
                self.blocked_reasons["adapter_not_resident"] += 1
                break
            base_tokens = len(req.prompt) + req.max_new_tokens
            lookahead = 0
            if self.lookahead_tokens:
                # clamp the speculative grant to the hard ceilings (table
                # width, allocatable pool) so a request that fit before
                # speculation was enabled can still be seated — the head
                # of the queue must never deadlock on un-fundable slack
                cap = (self.pool.num_blocks - 1) * self.pool.block_size
                if self.max_table_blocks is not None:
                    cap = min(cap, self.max_table_blocks * self.pool.block_size)
                lookahead = max(
                    0, min(self.lookahead_tokens, cap - base_tokens)
                )
            shared: list[int] = []
            if self.prefix_cache is not None:
                if req.prefix_keys is None:
                    req.prefix_keys = self.prefix_cache.keys_for(
                        req.prompt, req.adapter
                    )
                shared = self.prefix_cache.match(
                    req.prompt, req.adapter, keys=req.prefix_keys
                )
            hit_tokens = len(shared) * self.pool.block_size
            # tail keeps >= 1 prompt token; a full-prompt hit COWs the
            # last shared block at prefill time (needs the spare below)
            cached_tokens = min(hit_tokens, len(req.prompt) - 1)
            cow_reserve = 1 if hit_tokens > cached_tokens else 0
            total_tokens = base_tokens + lookahead
            if self.chunked_reserve and self.chunk_tokens is not None:
                # chunked-prefill admission (the PR 17 over-reservation
                # fix): fund the cached prefix plus ONE chunk instead of
                # the full worst case — a 2048-token prompt admits on
                # chunk-budget blocks, not 2048/block_size of them. The
                # engine grows the table chunk-by-chunk and, when growth
                # can't allocate, preempts (swap-out) instead of OOMing.
                reserve_tokens = min(
                    total_tokens, cached_tokens + self.chunk_tokens
                )
            else:
                reserve_tokens = total_tokens
            # (a layout's engine refuses lookahead, sharing and chunking:
            # its prefill starts at the prompt's rows, summaries in place)
            need = self.footprint(reserve_tokens, start=len(req.prompt))
            if shared:
                # pin the chain BEFORE any allocation can LRU-evict it
                self.pool.acquire(shared)
            if not self.pool.can_allocate(need - len(shared) + cow_reserve):
                # a seat is free but the KV pool can't fund the head
                if shared:
                    self.pool.free(shared)
                self.blocked_reasons["pool_exhausted"] += 1
                break
            self.queue.popleft()
            slot.clear()
            slot.request = req
            slot.blocks = shared + self.pool.allocate(need - len(shared))
            slot.shared = set(range(len(shared)))
            slot.cached_tokens = cached_tokens
            slot.lookahead = lookahead
            if cow_reserve:
                slot.cow_spare = self.pool.allocate(1)[0]
            slot.admit_time = self._now()
            admitted.append(slot)
        return admitted

    def preempt_candidate(
        self, max_priority: Optional[int] = None, exclude=()
    ) -> Optional[Slot]:
        """The slot preemption should victimize, or None.

        Victim order: lowest priority first, then least progress
        (fewest KV tokens — the cheapest swap and the least work
        parked). Resumed slots are exempt — a request is preempted at
        most once per seating generation, so preemption can never
        ping-pong the same request (the anti-thrash rule). ``max_priority``
        caps eligible victims (pass ``head.priority`` to never victimize
        anyone more important than the request being funded);
        ``exclude`` skips slot indices (e.g. seats admitted this very
        step)."""
        cands = [
            s for s in self.slots
            if s.busy and not s.done and not s.resumed
            and s.index not in exclude
        ]
        if max_priority is not None:
            cands = [s for s in cands if s.request.priority <= max_priority]
        if not cands:
            return None
        return min(
            cands,
            key=lambda s: (s.request.priority, s.cache_len, -s.index),
        )

    @property
    def active(self) -> list[Slot]:
        return [s for s in self.slots if s.busy]

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or any(s.busy for s in self.slots)

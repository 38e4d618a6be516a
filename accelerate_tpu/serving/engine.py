"""The step-level serving engine: continuous batching over a paged KV
cache.

``ServingEngine`` is the host-side driver the million-user decode path
needs: ``add_request`` enqueues work, ``step`` advances the whole slot
batch by one decode iteration (retire finished -> admit + prefill ->
decode), ``stream`` drives steps to completion yielding per-token
events. Two compiled programs do all device work after warmup:

* ONE decode step at the fixed ``(max_slots, 1)`` shape — request churn
  (admissions, evictions, heterogeneous depths) is pure traced data
  (block tables, cache lengths, per-slot temperatures), so the program
  never retraces;
* one prefill per power-of-two bucket width (<= log2(max_seq_len) of
  them ever) — a long prompt runs as its own bucketed call writing into
  the paged cache instead of stalling the decode batch (prefill/decode
  split).

With speculative decoding enabled (``spec_decode=SpecConfig(...)`` /
:meth:`ServingEngine.set_speculation`) a third program joins them: ONE
verification step at ``(max_slots, k + 1)`` that scores a proposer's k
draft tokens per slot in a single target pass, lifting throughput past
the one-token-per-slot-per-step wall at token-for-token identical
outputs (see :mod:`.speculation`).

Zero-retrace is an explicit contract: trace-time counters
(:meth:`ServingEngine.trace_counts`) let tests assert it.
"""

from __future__ import annotations

import collections
import contextlib
import hashlib
import time
from dataclasses import dataclass
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..logging import get_logger
from ..models.transformer import layer_kinds
from ..utils.profiling import annotate
from .block_pool import BlockPool, PrefixCache, prefix_keys
from .cache_regime import TRACE_COUNTS, CacheRegime
from .sampling import SlotSampling, sample_tokens
from .scheduler import ContinuousScheduler, Request, Slot
from .slo import SLOConfig, SloTracker
from .spans import SpanLog, write_chrome_trace
from .speculation import DraftModelProposer, NGramProposer, SpecConfig
from .telemetry import ServeStats
from .transfer import TransferManifest

logger = get_logger(__name__)


@dataclass(frozen=True)
class TokenEvent:
    """One generated token, as surfaced by ``step``/``stream``."""

    request_id: str
    token: int
    done: bool


def _single_device_of(params: Any) -> Optional[jax.Device]:
    """The one device every array leaf of ``params`` lives on, else None
    (weights sharded over a mesh, or no device arrays at all)."""
    devices = set()
    for leaf in jax.tree.leaves(params):
        if isinstance(leaf, jax.Array):
            devices |= leaf.devices()
            if len(devices) > 1:
                return None
    return devices.pop() if devices else None


def _whole_on_every_device_of(params: Any) -> Optional[NamedSharding]:
    """Where weights sharded over a mesh keep a value that every one of
    their devices holds whole, else None (no leaf names its mesh)."""
    for leaf in jax.tree.leaves(params):
        if isinstance(leaf, jax.Array) and isinstance(
            leaf.sharding, NamedSharding
        ):
            return NamedSharding(leaf.sharding.mesh, PartitionSpec())
    return None


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length() if n > 1 else 1


@dataclass
class _SwappedRequest:
    """A preempted request parked on the host: everything needed to
    re-seat it bitwise-identically — the slot's scheduler state plus its
    blocks' gathered CONTENTS (``data``: one host array per paged-cache
    leaf, leading axis = block position in the slot's table order).
    The device block ids were recycled at swap-out; only the images and
    the ledger entry (``BlockPool.num_swapped``) remain."""

    request: Request
    generated: list[int]
    pending: int
    cache_len: int
    n_blocks: int
    data: list
    chunks: int
    preempted_count: int
    admit_time: float
    first_token_time: float
    cached_tokens: int
    swap_bytes: int
    preempt_time: float


class ServingEngine:
    """Continuous-batching serving over a paged KV cache.

    ``num_blocks`` defaults to a pool that can hold ``max_slots`` full
    ``max_seq_len`` sequences plus the reserved garbage block — the
    worst case. Real traffic with shorter sequences can shrink it: a
    request needs ``ceil((prompt_len + max_new_tokens) / block_size)``
    blocks while in flight (the block-pool sizing formula), and the pool
    only has to fund the slots' CONCURRENT reservations, which is where
    paging beats the dense ``[B, max_seq_len]`` cache on HBM.

    ``telemetry``: an optional :class:`~..telemetry.StepTelemetry`; every
    completed request emits a ``kind="serve"`` record through it (TTFT,
    queue time, end-to-end latency, decode tokens/s) — the records ride
    the existing sink/diagnostics stack unchanged. ``now`` is injectable
    for deterministic latency tests.

    Observability plane (all host-side — no new traced programs, so the
    zero-retrace contract is untouched):

    * every request gets a lifecycle SPAN (submit→admit→prefill→first
      token→finish/shed); terminal transitions emit ``kind="span"``
      records and :meth:`export_trace` writes the last ``span_history``
      spans as Chrome-trace/Perfetto JSON;
    * ``gauge_interval``: every N steps a ``kind="serve_gauge"`` record
      samples queue depth, queue-age p95, slot occupancy, pool
      utilization, tokens in flight and the blocked/shed counters;
    * ``slo``: an optional :class:`SLOConfig`; finished requests feed a
      multi-window burn-rate tracker emitting ``kind="slo"`` records on
      ``slo.interval_steps`` cadence (breaches become anomalies);
    * ``max_queue`` / ``max_queue_delay_s``: bound the admission queue —
      overloaded traffic is SHED (``kind="shed"`` record + terminal
      span), never silently parked in an unbounded deque;
    * ``max_retained_results``: FIFO bound on retained generations —
      :meth:`result` returns None once a request's tokens age out.

    ``decode_ahead``: dispatch decode step k + 1 BEFORE step k's tokens are
    fetched. A slot's next position, rows and table are known on the host
    without the token's value; the token itself is fed from step k's output
    on the device (``_feed``, one more tiny compiled program, built with
    the engine). The chip then runs step after step while the host fetches,
    emits and schedules under it: the host's ~2.5 ms round trip leaves the
    step, and a saturated engine's tokens/s stops following the host's
    speed. ``step()`` still returns one token a decoding slot. What it
    costs: a slot that a step ends by its count, or brings to a window's end
    (eva), sits the next dispatch out; a request prefilled while a step is
    in flight joins the one after (its prefill queues behind that step on
    the device, and its second token comes one ``step()`` after its first);
    a request ended by its ``eos_token_id`` has decoded one row too many,
    into its own blocks, never read. Greedy tokens are those of one step at
    a time. Sampled streams at temperature > 0 are as valid, but a request
    joins other dispatches and so draws other keys than with
    ``decode_ahead=False`` — and than a default dense engine drew before it
    decoded ahead (PR 37). Written for plain decode only, so:

    * ``None`` (default): ahead wherever the engine runs plain decode,
      whatever the model — none of prefix cache, speculation, chunked
      prefill, preemption, adapters or a hand-off role asked for when it is
      built; one step at a time beside any of them. An engine that decodes
      ahead by this choice LANDS when a warm toggle asks for one of them
      (``set_prefix_cache(True)``, ``set_speculation(spec)``, ``set_role``
      to a pool, ``acquire``): the step in flight is fetched as it is by
      the next ``step()``, none goes behind it, the feature acts from that
      step boundary on, and nothing compiles (the decode program goes on
      taking its tokens from ``_feed``). It stays landed.
    * ``True``: ahead, and each of those features is refused, by name, when
      the engine is built and on the warm toggles.
    * ``False``: one step at a time (the tests' reference).

    ``decode_ahead_share`` (also a gauge): of the decode steps fetched, the
    share whose tokens were on the device before their ``step()`` began.
    """

    def __init__(
        self,
        model: Any,
        params: Any,
        *,
        max_slots: int = 4,
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        telemetry: Any = None,
        seed: int = 0,
        now: Callable[[], float] = time.monotonic,
        max_queue: Optional[int] = None,
        max_queue_delay_s: Optional[float] = None,
        slo: Optional[SLOConfig] = None,
        gauge_interval: int = 1,
        span_history: int = 512,
        max_retained_results: Optional[int] = 4096,
        adapters: Any = None,
        prefix_cache: bool = False,
        model_fingerprint: Optional[str] = None,
        spec_decode: Optional[SpecConfig] = None,
        prefill_chunk_tokens: Optional[int] = None,
        preemption: bool = False,
        kv_dtype: str = "bf16",
        role: str = "colocated",
        transfer_plane: Any = None,
        decode_ahead: Optional[bool] = None,
    ):
        from ..compilation import activate_persistent_cache

        activate_persistent_cache()
        self.model = model
        self.params = params
        # the engine has no device of its own: it lives where its weights
        # do. With weights on ONE device (a replica of a fleet, each on
        # its own chip) the KV pool and every per-step host put are
        # placed there too — never first on chip 0 and then moved.
        # Weights sharded over a mesh leave placement to jit.
        self._device = _single_device_of(params)
        self.max_slots = max_slots
        self.block_size = block_size
        # --- PR 17 capacity levers (all default OFF) ---------------- #
        # chunked prefill: per-STEP prompt-token budget. Prompt
        # ingestion splits into <= budget chunks interleaved with
        # decode steps (same pow2-bucket prefill programs, cache_len
        # carries the true offset), so a long prompt stops head-of-
        # line-blocking the decode batch and short prompts clear first
        # (shortest-remaining-first within the budget).
        if prefill_chunk_tokens is not None and prefill_chunk_tokens < 1:
            raise ValueError("prefill_chunk_tokens must be >= 1 (or None)")
        self.prefill_chunk_tokens = prefill_chunk_tokens
        # preemption with KV swap: under pool pressure a victim slot's
        # block CONTENTS device_get to a host swap area, its blocks
        # free, and the request resumes later by restoring the images
        # into fresh blocks at true cache offsets (sheds become pauses).
        self.preemption = preemption
        if kv_dtype not in ("bf16", "int8"):
            raise ValueError(
                f"kv_dtype must be 'bf16' (native) or 'int8', got {kv_dtype!r}"
            )
        # int8 paged KV: pools store sym-quantized rows + per-token
        # scales ((num_blocks, block_size) fp32 beside each pool);
        # "bf16" keeps the pools at the model's native compute dtype.
        self.kv_dtype = kv_dtype
        # prefill/decode disaggregation (PR 19, default OFF): a
        # "prefill" engine runs prompt ingestion only and publishes each
        # finished chain as a TransferManifest (chain keys + per-block
        # host images via the swap path); a "decode" engine acquire()s
        # manifests, dedups warm prefix blocks against its CACHED index,
        # scatter-restores only the tail, and seats the request straight
        # into the decode batch. "colocated" is byte-identical to the
        # single-engine behavior — none of the hand-off code runs.
        if role not in ("colocated", "prefill", "decode"):
            raise ValueError(
                "role must be 'colocated', 'prefill' or 'decode', "
                f"got {role!r}"
            )
        self._role = role
        self._plane = transfer_plane
        self._outbox: list[TransferManifest] = []
        self._inbox: list[TransferManifest] = []
        self._transfer_stats = {
            "manifests_out": 0, "manifests_in": 0, "blocks_moved": 0,
            "blocks_deduped": 0, "bytes_moved": 0, "seat_deferred": 0,
        }
        # multi-tenant serving: an AdapterRegistry whose fixed-shape
        # stacks ride every prefill/decode call as traced data, indexed
        # by a per-slot adapter row (the per-slot-temperatures idiom).
        # Loading/evicting adapters rewrites stack ROWS — shapes never
        # change, so the zero-retrace contract holds across tenant churn.
        self.adapters = adapters
        cfg = model.config
        # what a request's cache is, and all that follows from it
        regime = self._regime = CacheRegime(
            cfg, block_size, max_slots, kv_dtype, num_blocks)
        # eva's layout of rows (None for every other model), the width of a
        # slot's table and the pool's size: the regime's, under the names
        # the tests and the benchmark's rehearsals read
        self._eva = regime.layout
        self._max_table = regime.max_table
        self.num_blocks = num_blocks = regime.num_blocks
        features = [name for name, on in (
            ("prefix_cache", prefix_cache),
            ("spec_decode", spec_decode is not None),
            ("prefill_chunk_tokens", prefill_chunk_tokens is not None),
            ("preemption", preemption),
            (f"role {role!r}", role != "colocated"),
            ("adapters", adapters is not None),
        ) if on]
        # an engine left to choose (None) decodes ahead where it runs plain
        # decode, and LANDS if a warm toggle later asks for a feature; one
        # told to (True) refuses the feature instead
        self._lands = decode_ahead is None
        self.decode_ahead = not features if self._lands else bool(decode_ahead)
        # the step dispatched ahead and not yet fetched: ((its tokens on
        # the device, its dispatch's count), [(slot, request)] it decodes for)
        self._ahead: Optional[tuple] = None
        # dispatches made so far, by the program's name in the device trace
        self._dispatched = {"jit__decode": 0, "jit__verify": 0}
        # plain decode steps fetched, and those of them whose tokens were on
        # the device before their ``step()`` began (``decode_ahead_share``)
        self._fetched = 0
        self._fetched_ahead = 0
        for feature in features:
            regime.refuse(feature)
            self._land_or_refuse(feature)
        if kv_dtype == "int8":
            regime.refuse("kv_dtype 'int8'")
        self.pool = BlockPool(num_blocks, block_size)
        # prefix caching (vLLM-style shared KV): pure host-side policy —
        # the SAME compiled programs serve cold and warm requests, warm
        # ones just prefill a shorter tail at a true cache offset.
        # Default OFF: outputs are identical either way (only TTFT and
        # HBM footprint change), but sharing is an explicit opt-in.
        self._model_fingerprint = model_fingerprint or hashlib.sha256(
            repr(cfg).encode()
        ).hexdigest()[:16]
        self.prefix_cache: Optional[PrefixCache] = (
            PrefixCache(self.pool, fingerprint=self._model_fingerprint)
            if prefix_cache else None
        )
        self.scheduler = ContinuousScheduler(
            max_slots, self.pool, now=now,
            max_queue=max_queue, max_queue_delay_s=max_queue_delay_s,
            adapter_ready=(
                (lambda a: adapters.resident(a)) if adapters is not None
                else None
            ),
            prefix_cache=self.prefix_cache,
            max_table_blocks=self._max_table,
            regime=regime,
            chunk_tokens=prefill_chunk_tokens,
            # chunk-aware admission (the over-reservation fix) is only safe
            # when preemption provides the can't-grow escape hatch: without
            # it admission keeps the full-footprint reservation that makes
            # mid-flight OOM impossible by construction.
            chunked_reserve=prefill_chunk_tokens is not None and preemption,
        )
        self.sampling = SlotSampling(max_slots)
        self.stats = ServeStats()
        self.span_log = SpanLog(maxlen=span_history)
        self.slo_tracker = SloTracker(slo) if slo is not None else None
        if gauge_interval < 0:
            raise ValueError("gauge_interval must be >= 0 (0 disables)")
        self.gauge_interval = gauge_interval
        if max_retained_results is not None and max_retained_results < 1:
            raise ValueError("max_retained_results must be >= 1 (or None)")
        self.max_retained_results = max_retained_results
        self._telemetry = telemetry
        self._now = now
        self._key = jax.random.PRNGKey(seed)
        self._tables = np.zeros((max_slots, self._max_table), np.int32)
        # cached device copy of the block tables — invalidated on every
        # host-side table write, so the per-iteration decode/verify call
        # skips a host->device put when no admission/COW/retire happened
        self._tables_dev: Optional[jax.Array] = None
        # host mirror of each slot's adapter stack row (0 = base model),
        # turned into a traced array per decode step — SlotSampling's idiom
        self._slot_adapter = np.zeros(max_slots, np.int32)
        self._results: dict[str, list[int]] = {}
        self._result_order: collections.deque = collections.deque()
        self._shed_reasons: dict[str, str] = {}
        self._shed_order: collections.deque = collections.deque()
        self._steps = 0
        self._http: Any = None
        # trace-time counters: ``regime.mark`` says which a program's trace bumps
        self._traces = dict(TRACE_COUNTS)
        self._rollovers_total = 0
        # every bucket width a prefill ever ran at — the set
        # capture_programs() reconstructs abstract specs from
        self._prefill_buckets: set[int] = set()
        # capture_programs memoizes its AOT Compiled per label so a
        # second capture (or the auditor) never pays a second compile
        self._captured_programs: dict[str, Any] = {}
        self.capture_compile_count = 0
        # the compiler's word on the pools being written in place: of the
        # captured prefill, decode and verify programs, the fewest bytes
        # one of them gives back in the buffers they came in
        # (``memory_analysis().alias_size_in_bytes``): ``kv_pool_bytes``
        # when every one keeps the whole pool; 0 before a capture, and
        # where the backend reports none
        self.pool_alias_bytes = 0
        # set when a call failed that had been given the pools to keep
        self._pool_lost = False

        from ..models.generation import init_cache

        self.cache = init_cache(
            model.init, jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
            decode=True, device=self._device, paged=regime.state(
                jnp.zeros((1, self._max_table), jnp.int32),
                jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32)),
        )
        # where the COW copy, the preemption swap and the hand-off find a
        # block, and the sizing headlines: bytes a cached token, a seat's state
        (self._kv_leaf_info, self.kv_pool_bytes, self.kv_bytes_per_token,
         self.state_bytes_per_slot) = regime.leaves(self.cache)

        traces = self._traces
        # a decode step of a stack with experts also says how many distinct
        # experts held here its rows chose (``experts_touched`` on the fetch
        # span): the experts are most of what such a step reads
        counts = int(cfg.num_experts > 0)
        self._counts_experts = bool(counts)
        self._experts_held = cfg.num_experts * sum(
            kind[1] == "moe" for kind in layer_kinds(cfg)) if counts else 0
        # of the decode steps in flight, by dispatch count: (rows, seated)
        self._step_stats: dict[int, tuple] = {}
        # what the engine knows about its pools and attention cannot see
        # from inside a trace: they sit whole on the weights' one device
        single_device = self._device is not None
        pool_leaf = self._kv_leaf_info[0][0]
        # every program below that writes the pools is given them to keep
        # (donated: each call site rebinds ``self.cache``), and the model
        # carries the pools of a scanned stack through its layer loop
        # (models/transformer.py): a pool is ONE buffer from input to
        # output, and each such program counts itself under kv_in_place.
        # ``pool_alias_bytes`` is the compiler's word on the same thing.

        def _lora_kwargs(lora_args):
            """(stacks, scales, slot_ids) trailing args -> the model's
            ``lora=`` kwarg. Empty when the engine has no registry — the
            compiled programs are then byte-identical to the pre-adapter
            engine."""
            if not lora_args:
                return {}
            from ..adapters.runtime import LoraState

            astacks, ascales, aslots = lora_args
            return {
                "lora": LoraState(
                    stacks=astacks, slot_ids=aslots, scales=ascales
                )
            }

        def _prefill(params, cache, ids, table, length, cached_len, key,
                     temp, slot=None, *lora_args):
            # cached_len > 0 is the warm-hit path: ``ids`` holds only the
            # UNCACHED tail and the paged cache already contains KV for
            # the first cached_len positions (shared prefix blocks in
            # ``table``) — writes land at cached_len + i and attention
            # sees cols <= cached_len + i, exactly a mid-sequence
            # continuation. cached_len == 0 is the cold path, and both
            # run the SAME compiled program (cached_len is traced data).
            state = regime.state(
                table, cached_len, length, slot=slot,
                single_device=single_device, prefill=True)
            # at trace time, not per call
            regime.mark(traces, "prefill", state, ids.shape[1])
            # the head reads the last VALID row of the padded bucket alone,
            # not the padded tail: width x vocabulary logits are never formed
            logits, mutated = model.apply(
                {"params": params, "cache": cache}, ids, decode=True,
                paged=state, mutable=["cache"], **_lora_kwargs(lora_args),
                logits_at=length - 1,
            )
            last = logits[:, 0]
            with jax.named_scope("sample"):
                token = sample_tokens(last, key, temp, top_k=top_k, top_p=top_p)
            return mutated["cache"], token

        def _decode(params, cache, tokens, tables, cache_lens, lengths,
                    temps, key, positions=None, *lora_args):
            # eva: ``cache_lens`` are the slots' ROWS and ``positions`` what
            # they stand for (None for every other model: one and the same)
            state = regime.state(
                tables, cache_lens, lengths, positions=positions,
                single_device=single_device)
            regime.mark(traces, "decode", state, tokens.shape[1],
                        jax.tree.leaves(cache)[pool_leaf])
            logits, mutated = model.apply(
                {"params": params, "cache": cache}, tokens, decode=True,
                paged=state, mutable=["cache"] + ["intermediates"] * counts,
                **_lora_kwargs(lora_args),
            )
            with jax.named_scope("sample"):
                token = sample_tokens(
                    logits[:, -1], key, temps, top_k=top_k, top_p=top_p
                )
            if counts:
                # the step's distinct held experts, summed over the expert
                # layers, behind the tokens: one array, one copy to the host
                touched = sum(
                    jnp.sum(leaf) for path, leaf in
                    jax.tree_util.tree_flatten_with_path(
                        mutated["intermediates"])[0]
                    if any(getattr(k, "key", None) == "moe_experts_touched"
                           for k in path))
                token = jnp.concatenate(
                    [token, touched.astype(token.dtype)[None]])
            return mutated["cache"], token

        def _rollover(params, cache, src, dst):
            regime.mark(traces, "rollover")
            from ..models.transformer import eva_roll_over_cache

            return eva_roll_over_cache(cfg, params, cache, src, dst)

        def _key_chain(key):
            # 16 sequential (key, sub) = split(key) steps in ONE compiled
            # call: the subkey STREAM is bit-identical to calling
            # jax.random.split 16 times, but the per-step dispatch (~65us
            # on CPU — real money on the warm-prefill TTFT path) is paid
            # once per 16 prefill/decode calls instead of every call.
            def body(k, _):
                k2, sub = jax.random.split(k)
                return k2, sub
            return jax.lax.scan(body, key, None, length=16)

        def _cow(cache, src, dst):
            traces["cow"] += 1  # one compiled program, reused per copy
            # Copy one block row in every paged leaf — each layer's K/V
            # pools and, under int8 KV, the per-token scale rows that
            # travel with their block's quantized contents —, found as
            # the swap finds them: ``_kv_leaf_info``'s (leaf, block axis)
            leaves = list(jax.tree.leaves(cache))
            for i, axis in self._kv_leaf_info:
                lead = (slice(None),) * axis
                leaves[i] = leaves[i].at[lead + (dst,)].set(
                    leaves[i][lead + (src,)])
            return jax.tree.unflatten(jax.tree.structure(cache), leaves)

        def _make_verify(width: int):
            # Speculative verification: ONE target pass at the fixed
            # (max_slots, width = k + 1) shape scores the pending token
            # plus every draft. Column j's logits see positions <=
            # cache_len + j (the paged causal mask), and its sample uses
            # chain key j — so out[:, j] is EXACTLY the token plain
            # decode would emit as the j-th token of this round, making
            # draft acceptance lossless at any temperature. Per-slot
            # ``lengths`` (validity) is traced data: the program traces
            # ONCE per width, the zero-retrace contract's new leg.
            def _verify(params, cache, tokens, tables, cache_lens, lengths,
                        temps, keys, *lora_args):
                regime.mark(traces, "verify")
                state = regime.state(
                    tables, cache_lens, lengths, single_device=single_device)
                logits, mutated = model.apply(
                    {"params": params, "cache": cache}, tokens, decode=True,
                    paged=state, mutable=["cache"],
                    **_lora_kwargs(lora_args),
                )
                with jax.named_scope("sample"):
                    outs = [
                        sample_tokens(
                            logits[:, j], keys[j], temps, top_k=top_k,
                            top_p=top_p,
                        )
                        for j in range(width)
                    ]
                    out = jnp.stack(outs, axis=1)
                return mutated["cache"], out

            return jax.jit(_verify, donate_argnums=1)

        self._prefill_fn = jax.jit(_prefill, donate_argnums=1)
        self._decode_fn = jax.jit(_decode, donate_argnums=1)
        self._cow_fn = jax.jit(_cow, donate_argnums=0)
        self._key_chain_fn = jax.jit(_key_chain)
        self._key_buf: collections.deque = collections.deque()
        self._feed_fn = None
        if self.decode_ahead:
            # a step's tokens: the step before's, still on the device, for
            # the slots it decoded; the host's for the others. Compiled
            # now, and every decode call of this engine takes its tokens
            # from it, after it has landed too, so the decode program sees
            # ONE kind of argument
            def _feed(prev, tokens, from_prev):
                if counts:  # the step before's count rides behind its tokens
                    prev = prev[:max_slots]
                return jnp.where(from_prev[:, None], prev[:, None], tokens)

            # the fed tokens lie where a step's own do: on the weights' one
            # device, or whole on every device of their mesh (else a first
            # step's and a fed step's would be two kinds after all)
            on_mesh = (
                _whole_on_every_device_of(params) if self._device is None
                else None
            )
            self._feed_fn = jax.jit(_feed, out_shardings=on_mesh)
            none = np.zeros(max_slots, np.int32)
            self._no_tokens = jax.device_put(
                np.zeros(max_slots + counts, np.int32),
                on_mesh if self._device is None else self._device,
            )
            with self._placed():
                self._feed_fn(self._no_tokens, none[:, None],
                              np.zeros(max_slots, bool))
        self._rollover_fn = None
        if self._eva is not None:
            # what a filling window runs is compiled now, not at the first
            # window that fills mid-traffic: one call over the garbage
            # block (reads it, writes its summaries back into it)
            self._rollover_fn = jax.jit(_rollover, donate_argnums=1)
            with self._placed():
                self.cache = self._rollover_fn(
                    self.params, self.cache, *regime.rollover_args())
        # speculative decoding: verify programs cached by width (k + 1)
        # and warm proposers cached by config identity, so set_speculation
        # toggles on a warm engine never retrace
        self._make_verify = _make_verify
        self._verify_fns: dict[int, Any] = {}
        self._proposers: dict[int, Any] = {}
        self._spec: Optional[SpecConfig] = None
        self._proposer: Any = None
        self._spec_proposed_total = 0
        self._spec_accepted_total = 0
        self._spec_rounds_total = 0
        # preemption plane: compiled swap gather/scatter cached per pow2
        # block-count width, host-parked requests (with their KV
        # images), and the preempt/resume/chunk accounting the gauges
        # export
        self._swap_fns: dict[int, tuple] = {}
        self._swapped_reqs: list[_SwappedRequest] = []
        self._preempt_counts: dict[str, int] = {
            "priority": 0, "pool": 0, "growth": 0,
        }
        self._resumes_total = 0
        self._swap_bytes_held = 0
        self._prefill_chunks_total = 0
        # padded prefill compute issued so far, in bucket tokens — the
        # pow2 bucket width of every prefill/chunk call, cumulative. A
        # per-step delta of this IS the step's prefill compute cost
        # (padding included); beside it the tokens that were real
        self.prefill_bucket_tokens_total = 0
        self.prefill_real_tokens_total = 0
        if spec_decode is not None:
            self.set_speculation(spec_decode)
        self._register_census_owners()

    # ------------------------------------------------------------------ #
    # request API
    # ------------------------------------------------------------------ #
    def add_request(
        self,
        prompt,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        eos_token_id: Optional[int] = None,
        request_id: str = "",
        adapter: Optional[str] = None,
        priority: int = 0,
    ) -> str:
        """Enqueue one request; returns its id. ``prompt`` is a token-id
        sequence. The request is admitted into a slot by a later
        :meth:`step` as soon as a seat AND its full block reservation are
        available — and, when ``adapter`` names a tenant, once that
        adapter is resident in the engine's registry. ``priority`` ranks
        admission (higher first, FIFO within a tier) and, with
        ``preemption=True``, lets the head evict a strictly
        lower-priority seat."""
        if adapter is not None and self.adapters is None:
            raise ValueError(
                f"request names adapter {adapter!r} but the engine was "
                "built without an AdapterRegistry (pass adapters=...)"
            )
        n_prompt = int(np.asarray(prompt).size)
        if n_prompt + max_new_tokens > self.model.config.max_seq_len:
            # the block table is as wide as max_seq_len needs: a longer
            # request would write past it
            raise ValueError(
                f"request of {n_prompt} prompt + {max_new_tokens} new tokens "
                f"is longer than the model's max_seq_len "
                f"{self.model.config.max_seq_len}"
            )
        req = Request(
            prompt=[int(t) for t in np.asarray(prompt).reshape(-1)],
            max_new_tokens=max_new_tokens,
            temperature=temperature,
            eos_token_id=eos_token_id,
            request_id=request_id,
            adapter=adapter,
            priority=priority,
        )
        rid = self.scheduler.submit(req)
        self.span_log.on_submit(
            rid, req.submit_time, len(req.prompt), adapter_id=adapter
        )
        if req.shed_reason is not None:  # tail-dropped at the queue bound
            self._shed(req)
        return rid

    @property
    def has_work(self) -> bool:
        # swapped-out requests hold no queue entry and no seat, but they
        # are still the engine's responsibility until resumed + finished
        # (as are acquired-but-unseated manifests on a decode replica)
        return (
            self.scheduler.has_work
            or bool(self._swapped_reqs)
            or bool(self._inbox)
        )

    @property
    def role(self) -> str:
        return self._role

    def set_role(self, role: str) -> None:
        """Switch the engine's disaggregation role on a WARM engine.
        Roles are pure host policy — the compiled programs are shared —
        so a caller can prime an engine colocated (warming its prefill
        buckets AND the decode program) and then assign it to a pool."""
        if role not in ("colocated", "prefill", "decode"):
            raise ValueError(
                "role must be 'colocated', 'prefill' or 'decode', "
                f"got {role!r}"
            )
        if role != "colocated":
            self._regime.refuse(f"role {role!r}")
            self._land_or_refuse(f"role {role!r}")
        self._role = role

    def _land_or_refuse(self, feature: str) -> None:
        """Engine features that change a slot's blocks, its place in the
        batch or the program it decodes through between two steps are not
        written beside ``decode_ahead``: the step after the next is already
        on the device when they would act. An engine that decodes ahead by
        its own choice LANDS: the step in flight is fetched as it is by the
        next ``step()``, none is dispatched behind it, and ``feature`` acts
        from that step boundary on (for good: turning the feature off again
        does not take off). One built with ``decode_ahead=True`` refuses
        ``feature``, by name."""
        if not self.decode_ahead:
            return
        if not self._lands:
            raise NotImplementedError(
                f"{feature} is not written beside decode_ahead: the next "
                "decode step is dispatched before this one's tokens are "
                "fetched (build the engine with decode_ahead=None or False)"
            )
        self.decode_ahead = False
        # the share says how the engine decodes now
        self._fetched = self._fetched_ahead = 0

    @property
    def decode_ahead_share(self) -> float:
        """Of the decode steps fetched so far (since it landed, for an
        engine that did), the share whose tokens were on the device before
        their ``step()`` began: ~1.0 for a saturated engine that decodes
        ahead (only a step behind an idle one is dispatched and awaited in
        one ``step()``), 0.0 for one that takes a step at a time."""
        return self._fetched_ahead / self._fetched if self._fetched else 0.0

    def trace_counts(self) -> dict:
        """Compiled-program counts, bumped at trace time. After warmup,
        steady-state serving must hold ``decode`` at 1, ``prefill`` at
        <= log2(max_seq_len), and — with speculation on — ``verify`` at
        1 per distinct k (plus the draft proposer's own
        ``draft_prefill``/``draft_step`` counters, merged here)."""
        out = dict(self._traces)
        for proposer in self._proposers.values():
            for name, count in proposer.trace_counts().items():
                out[name] = out.get(name, 0) + count
        return out

    def result(self, request_id: str) -> Optional[list[int]]:
        """Generated tokens of a COMPLETED request. None while the
        request is still running, if it was shed, or after its tokens
        aged out of the ``max_retained_results`` FIFO window — callers
        on a long-lived server must read results promptly."""
        return self._results.get(request_id)

    def shed_reason(self, request_id: str) -> Optional[str]:
        """Why a request was shed (None if it wasn't, or its entry aged
        out of the bounded shed history)."""
        return self._shed_reasons.get(request_id)

    # ------------------------------------------------------------------ #
    # the step loop
    # ------------------------------------------------------------------ #
    def step(self) -> list[TokenEvent]:
        """Advance serving by one iteration: shed queue-deadline-expired
        requests, retire finished slots (their blocks free immediately),
        admit + prefill queued requests into the empty seats, then run
        ONE decode step over the whole slot batch. Returns the tokens
        produced this iteration."""
        if self._pool_lost:
            raise RuntimeError(
                "this engine's KV pool went with a call that failed after "
                "taking it (the pools are donated to every program that "
                "writes them): the seated requests' state is gone; build a "
                "new engine"
            )
        try:
            with annotate("atpu:serve.step", step=self._steps), self._placed():
                return self._step_inner()
        except Exception as exc:
            # device OOM: the autopsy is written from state already in
            # memory (ledger + last census + pool stats), then the
            # original error propagates untouched
            self._handle_oom(exc, context="serving_step")
            # a call that fails on the device has already consumed the
            # pools it was donated: never serve on from deleted buffers
            self._pool_lost = any(
                leaf.is_deleted() for leaf in jax.tree.leaves(self.cache)
            )
            raise

    def _placed(self):
        """Context in which uncommitted arrays (``jnp.asarray`` of host
        step inputs, fresh zeros) land on the engine's device."""
        if self._device is None:
            return contextlib.nullcontext()
        return jax.default_device(self._device)

    def _step_inner(self) -> list[TokenEvent]:
        # the phases below are host spans in the profiler's trace
        # (schedule, prefill per request, decode.inputs, .dispatch, .wait,
        # .fetch, emit): they tile the step, so a gap of the chip falls in one
        with annotate("atpu:serve.schedule") as phase:
            had_work = self.has_work
            events: list[TokenEvent] = []
            for req in self.scheduler.shed_expired():
                self._shed(req)
            for slot in self.scheduler.slots:
                if slot.busy and slot.done:
                    self._finish(slot)
            if self.preemption:
                self._try_resume()
            if self._inbox:
                self._seat_manifests()
            blocked_before = dict(self.scheduler.blocked_reasons)
            admitted = self.scheduler.admit()
            if self.preemption and self._maybe_preempt(
                blocked_before, exclude={s.index for s in admitted}
            ):
                # the freed seat/blocks fund the queue head THIS step
                admitted += self.scheduler.admit()
            for slot in admitted:
                if self.adapters is not None:
                    # pin the adapter for the request's whole flight — evict
                    # refuses while any seated request still decodes under it
                    self.adapters.acquire(slot.request.adapter)
                self.span_log.on_admit(slot.request.request_id, slot.admit_time)
                if self.prefill_chunk_tokens is not None:
                    self._begin_chunked(slot)
            phase.set_metadata(admitted=len(admitted))
        if self.prefill_chunk_tokens is None:
            for slot in admitted:
                self._prefill_slot(slot, events)
        else:
            self._chunked_prefill_step(events)
        if self._role == "prefill":
            # prompt ingestion only: every seat whose prefill just
            # completed hands its chain off instead of joining the
            # decode batch (EOS-at-first-token requests are already
            # slot.done and finish locally — nothing to hand off)
            for slot in self.scheduler.slots:
                if slot.busy and not slot.done and not slot.mid_prefill:
                    self._handoff_slot(slot)
        # mid-prefill seats hold their slot but are not in the decode
        # batch yet (their row carries lengths=0 this step, so the
        # compiled decode shape is untouched)
        active = [
            s for s in self.scheduler.slots
            if s.busy and not s.done and not s.mid_prefill
        ]
        if active and self.scheduler.chunked_reserve:
            active = self._grow_active(active)
        emit = None  # the host half of the decode: fetched tokens -> events
        if active:
            # speculate only when some slot holds a +k block reservation
            # (granted at admission) — slots seated before speculation
            # was enabled have no verify headroom and decode plainly
            # (and not over a step in flight: an engine that has just
            # landed fetches it first, the slots seated since sit it out)
            if self._ahead is None and self._proposer is not None and any(
                s.lookahead > 0 for s in active
            ):
                emit = self._spec_step(active)
            else:
                emit = self._decode_step(active)
        else:
            # whoever it decoded for ended meanwhile (an eos)
            self._ahead = None
            self._step_stats.clear()
        with annotate("atpu:serve.emit") as phase:
            if emit is not None:
                emit(events)
            self._steps += 1
            if self.gauge_interval and self._steps % self.gauge_interval == 0:
                self._sample_gauges()
            if self.slo_tracker is not None and (
                (
                    self.slo_tracker.config.interval_steps
                    and self._steps % self.slo_tracker.config.interval_steps == 0
                )
                # drain edge: the last SLO record in the stream (and the
                # flight ring) must reflect final end-of-run attainment,
                # not the cadence snapshot from mid-flight
                or (had_work and not self.scheduler.has_work)
            ):
                self._emit_slo()
            phase.set_metadata(tokens=len(events))
        return events

    def stream(self) -> Iterator[TokenEvent]:
        """Drive :meth:`step` until all submitted work completes,
        yielding token events as they are produced."""
        while self.scheduler.has_work:
            yield from self.step()

    def generate(
        self,
        input_ids,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        eos_token_id: Optional[int] = None,
    ) -> jax.Array:
        """The classic fixed-batch ``generate`` API refactored onto the
        engine: every row becomes a request, the engine serves them (one
        paged prefill per row + continuous decode), and the outputs
        reassemble into the familiar ``(B, prompt_len + max_new_tokens)``
        array — EOS-finished rows padded with EOS, matching
        ``models.generation.generate``'s freeze semantics."""
        ids = np.asarray(input_ids)
        req_ids = [
            self.add_request(
                row, max_new_tokens=max_new_tokens, temperature=temperature,
                eos_token_id=eos_token_id,
            )
            for row in ids
        ]
        for _ in self.stream():
            pass
        rows = []
        for rid, prompt in zip(req_ids, ids):
            if rid not in self._results:
                reason = self._shed_reasons.get(rid)
                raise RuntimeError(
                    f"generate() lost request {rid}: "
                    + (f"shed ({reason})" if reason else
                       "result evicted by max_retained_results")
                    + " — raise max_queue/max_retained_results or batch less"
                )
            gen = list(self._results[rid])
            pad = eos_token_id if eos_token_id is not None else (
                gen[-1] if gen else 0
            )
            gen += [pad] * (max_new_tokens - len(gen))
            rows.append(np.concatenate([prompt, np.asarray(gen, ids.dtype)]))
        return jnp.asarray(np.stack(rows))

    # ------------------------------------------------------------------ #
    # internals
    # ------------------------------------------------------------------ #
    def _split_key(self) -> jax.Array:
        if not self._key_buf:
            self._key, subs = self._key_chain_fn(self._key)
            self._key_buf.extend(np.asarray(subs))
        return jnp.asarray(self._key_buf.popleft())

    def _peek_keys(self, n: int) -> list:
        """The next ``n`` chain keys WITHOUT consuming them. The verify
        pass samples position j with key j, but the chain must advance
        per EMITTED token — a round that commits m + 1 tokens consumes
        exactly m + 1 keys (:meth:`_consume_keys`), so the sampler
        stream stays bit-identical to plain decode under any accept
        pattern (the k=0 / spec-off parity contract)."""
        while len(self._key_buf) < n:
            self._key, subs = self._key_chain_fn(self._key)
            self._key_buf.extend(np.asarray(subs))
        return [self._key_buf[i] for i in range(n)]

    def _consume_keys(self, n: int) -> None:
        for _ in range(n):
            self._key_buf.popleft()

    def _tables_device(self) -> jax.Array:
        if self._tables_dev is None:
            self._tables_dev = jnp.asarray(self._tables)
        return self._tables_dev

    def _lora_call_args(self, slot_ids) -> tuple:
        """The (stacks, scales, slot_ids) tail every compiled call takes
        when a registry is attached — pure traced DATA: residency churn
        rewrites the stacks' rows, never their shapes."""
        if self.adapters is None:
            return ()
        return (
            self.adapters.stacks(),
            self.adapters.scales(),
            jnp.asarray(slot_ids, jnp.int32),
        )

    def _cow_block(self, slot: Slot, tindex: int) -> None:
        """Copy-on-write table position ``tindex`` of ``slot``: allocate
        a private block (the admission-reserved spare first), one
        device-side block copy, swap the table entry, drop the shared
        reference. The donor block — and every other holder's view of it
        — is untouched; the COW copy stays OUT of the content index (its
        tail will be re-written at a different bucket width, so its
        content is not canonical for the chain key)."""
        donor = slot.blocks[tindex]
        if slot.cow_spare is not None:
            private = slot.cow_spare
            slot.cow_spare = None
        else:
            private = self.pool.allocate(1)[0]
        self.cache = self._cow_fn(
            self.cache,
            jnp.asarray(donor, jnp.int32),
            jnp.asarray(private, jnp.int32),
        )
        if self._proposer is not None:
            # the draft cache shares the block id space — mirror the
            # copy so the private block's draft rows stay coherent
            self._proposer.cow(self._cow_fn, jnp.asarray(donor, jnp.int32),
                               jnp.asarray(private, jnp.int32))
        slot.blocks[tindex] = private
        self.pool.free([donor])
        slot.shared.discard(tindex)
        slot.cow_indices.add(tindex)
        self._tables[slot.index, tindex] = private
        self._tables_dev = None
        if self.prefix_cache is not None:
            self.prefix_cache.cow_copies_total += 1

    def _prefill_slot(self, slot: Slot, events: list[TokenEvent]) -> None:
        req = slot.request
        prompt_len = len(req.prompt)
        # prefix-cache hit: the first ``cached`` prompt tokens' KV is
        # already in the shared blocks the scheduler pointed our table
        # at — prefill covers only the tail (always >= 1 token: the last
        # prompt position's logits seed sampling).
        cached = slot.cached_tokens
        tail_len = prompt_len - cached
        bucket = _next_pow2(tail_len)
        with annotate("atpu:serve.prefill", request_id=req.request_id,
                      bucket=bucket, cached=cached, tokens=tail_len,
                      width=bucket):
            self.span_log.on_prefill(
                req.request_id, self._now(), cached_prefix_tokens=cached
            )
            if cached and self.prefix_cache is not None:
                self.prefix_cache.tokens_saved_total += cached
            # COW any SHARED block the tail prefill will write into. With
            # block-aligned hits the tail starts on a private block, so this
            # loop only fires on a full-prompt hit (cached == prompt_len-1):
            # the 1-token tail re-writes the last shared block's final slot.
            for t in range(cached // self.block_size,
                           (prompt_len - 1) // self.block_size + 1):
                if t in slot.shared:
                    self._cow_block(slot, t)
            tail = req.prompt[cached:]
            self._prefill_buckets.add(bucket)
            self.prefill_bucket_tokens_total += bucket
            self.prefill_real_tokens_total += tail_len
            ids = np.zeros((1, bucket), np.int32)
            ids[0, :tail_len] = tail
            table = np.zeros((1, self._max_table), np.int32)
            table[0, :len(slot.blocks)] = slot.blocks
            if self.adapters is not None:
                self._slot_adapter[slot.index] = self.adapters.slot_of(req.adapter)
            self.cache, token = self._prefill_fn(
                self.params, self.cache, *self._regime.prefill_args(
                    ids, table, tail_len, cached, self._split_key(),
                    req.temperature, slot.index,
                    self._lora_call_args([self._slot_adapter[slot.index]])),
            )
            token = int(np.asarray(token)[0])
            slot.cache_len = prompt_len
            slot.windows_done = self._regime.windows(prompt_len)
            self._regime.count_wraps(0, prompt_len)
            slot.pending = token
            slot.generated = [token]
            # index every FULL prompt block we freshly prefilled so the next
            # identical prefix skips it. Shared positions are already
            # canonical; COW copies stay out (partially recomputed content).
            if self.prefix_cache is not None:
                self.prefix_cache.publish(
                    req.prompt, req.adapter, slot.blocks,
                    skip_indices=slot.shared | slot.cow_indices,
                    keys=req.prefix_keys,
                )
            slot.first_token_time = self._now()
            self.span_log.on_first_token(req.request_id, slot.first_token_time)
            self._tables[slot.index] = table[0]
            self._tables_dev = None
            if self._proposer is not None and slot.lookahead > 0:
                # seed the proposer (the draft model prefills the FULL
                # prompt through its own paged cache; n-gram is a no-op)
                self._proposer.prefill_slot(slot)
            self.sampling.set_slot(slot.index, req.temperature)
            self._note_token(slot, token, events)

    # ------------------------------------------------------------------ #
    # chunked prefill (PR 17): prompt ingestion under a per-step budget
    # ------------------------------------------------------------------ #
    def _begin_chunked(self, slot: Slot) -> None:
        """Seat a request for chunked ingestion: stamp the prefill edge
        and leave ``cache_len`` at the cached prefix — the slot is now
        ``mid_prefill`` and :meth:`_chunked_prefill_step` feeds it."""
        req = slot.request
        cached = slot.cached_tokens
        self.span_log.on_prefill(
            req.request_id, self._now(), cached_prefix_tokens=cached
        )
        if cached and self.prefix_cache is not None:
            self.prefix_cache.tokens_saved_total += cached
        if self.adapters is not None:
            self._slot_adapter[slot.index] = self.adapters.slot_of(req.adapter)
        slot.cache_len = cached

    def _chunked_prefill_step(self, events: list[TokenEvent]) -> None:
        """Spend this step's prompt-token budget across the mid-prefill
        seats, shortest remaining prompt first — SRPT within the budget
        is what moves TTFT p95: a short prompt admitted behind a long
        one clears the prefill phase in its first step instead of
        waiting out the giant's full ingestion."""
        budget = self.prefill_chunk_tokens
        pref = [s for s in self.scheduler.slots if s.busy and s.mid_prefill]
        if not pref:
            return
        pref.sort(key=lambda s: (
            len(s.request.prompt) - s.cache_len, s.admit_time, s.index
        ))
        preempted = False  # at most one chunk-funding preemption per step
        for slot in pref:
            if budget <= 0:
                break
            if not slot.busy or not slot.mid_prefill:
                continue  # victimized by an earlier stall's preemption
            remaining = len(slot.request.prompt) - slot.cache_len
            chunk = min(remaining, budget)
            if self._prefill_chunk(slot, chunk, events):
                budget -= chunk
                continue
            # the chunk's blocks can't be funded. Without preemption the
            # seat just waits for the pool to drain — but with it, a
            # wedged prefill is the worst failure mode chunk-aware
            # admission can produce (every seat mid-prefill, pool
            # exhausted, nothing decoding, nothing ever freed), so park
            # the least-progressed seat (often this very one: a barely
            # started giant is the cheapest swap and frees the most
            # future demand). Its seat and blocks fund the shorter
            # prefills and the queue; it resumes when the pool drains.
            if self.preemption and not preempted:
                preempted = True
                victim = self.scheduler.preempt_candidate()
                if victim is None and not slot.resumed:
                    victim = slot
                if victim is not None:
                    self._preempt(victim, "growth")
                    if (
                        victim is not slot
                        and self._prefill_chunk(slot, chunk, events)
                    ):
                        budget -= chunk

    def _prefill_chunk(
        self, slot: Slot, chunk_len: int, events: list[TokenEvent]
    ) -> bool:
        """One bucketed prefill call covering ``chunk_len`` prompt
        tokens at the slot's true cache offset (``cached_len`` carries
        it — the SAME compiled pow2-bucket programs the one-shot path
        uses). Returns False if the chunk's blocks can't be funded."""
        req = slot.request
        prompt_len = len(req.prompt)
        start = slot.cache_len
        final = start + chunk_len == prompt_len
        # chunk-aware admission reserved only the first chunk: grow the
        # table on demand. The final chunk also funds the first decode
        # write + any lookahead so decode never trips on the boundary.
        tokens_needed = start + chunk_len + ((1 + slot.lookahead) if final
                                             else 0)
        if not self._ensure_blocks(slot, tokens_needed):
            return False
        bucket = _next_pow2(chunk_len)
        with annotate("atpu:serve.prefill", request_id=req.request_id,
                      bucket=bucket, cached=start):
            for t in range(start // self.block_size,
                           (start + chunk_len - 1) // self.block_size + 1):
                if t in slot.shared:
                    self._cow_block(slot, t)
            self._prefill_buckets.add(bucket)
            self.prefill_bucket_tokens_total += bucket
            self.prefill_real_tokens_total += chunk_len
            ids = np.zeros((1, bucket), np.int32)
            ids[0, :chunk_len] = req.prompt[start:start + chunk_len]
            table = np.zeros((1, self._max_table), np.int32)
            table[0, :len(slot.blocks)] = slot.blocks
            # intermediate chunks DISCARD their sampled token, so they must
            # not consume a chain key either — only the final chunk (whose
            # sample is the request's first token) draws one. A solo
            # request's outputs are bit-identical chunked or not at any
            # temperature; batched timelines interleave the shared per-step
            # decode keys differently, so cross-run parity is greedy-exact.
            key = self._split_key() if final else self._key
            self.cache, token = self._prefill_fn(
                self.params, self.cache, *self._regime.prefill_args(
                    ids, table, chunk_len, start, key, req.temperature,
                    None,  # no seat's state: a recurrent stack refuses chunks
                    self._lora_call_args([self._slot_adapter[slot.index]])),
            )
            slot.cache_len = start + chunk_len
            slot.chunks += 1
            self._prefill_chunks_total += 1
            self._tables[slot.index] = table[0]
            self._tables_dev = None
            if final:
                token = int(np.asarray(token)[0])
                slot.pending = token
                slot.generated.append(token)
                if self.prefix_cache is not None and slot.chunks == 1:
                    # single-chunk == the unchunked bucket width, so the
                    # content is canonical; multi-chunk prefills stay out of
                    # the index (their blocks were written at per-chunk
                    # bucket widths)
                    self.prefix_cache.publish(
                        req.prompt, req.adapter, slot.blocks,
                        skip_indices=slot.shared | slot.cow_indices,
                        keys=req.prefix_keys,
                    )
                slot.first_token_time = self._now()
                self.span_log.on_first_token(
                    req.request_id, slot.first_token_time, chunks=slot.chunks
                )
                if self._proposer is not None and slot.lookahead > 0:
                    self._proposer.prefill_slot(slot)
                self.sampling.set_slot(slot.index, req.temperature)
                self._note_token(slot, token, events)
        return True

    def _ensure_blocks(self, slot: Slot, tokens: int) -> bool:
        """Grow ``slot``'s block table to cover ``tokens`` cache
        positions (chunk-aware admission reserves less than the worst
        case, so chunks and decode grow on demand). False = the pool
        can't fund the growth right now."""
        need = self.pool.blocks_for_tokens(tokens) - len(slot.blocks)
        if need <= 0:
            return True
        if not self.pool.can_allocate(need):
            return False
        slot.blocks.extend(self.pool.allocate(need))
        self._tables[slot.index, :len(slot.blocks)] = slot.blocks
        self._tables_dev = None
        return True

    def _grow_active(self, active: list[Slot]) -> list[Slot]:
        """Chunk-aware reservations mean decode itself can hit the pool
        wall: fund every active slot's next write (+lookahead) before
        the batch runs, preempting to free blocks where needed."""
        eligible = []
        for slot in active:
            if not slot.busy:
                continue  # preempted by an earlier seat's growth
            if self._grow_or_preempt(slot):
                eligible.append(slot)
        # a growth preemption may have victimized a seat already vetted
        return [s for s in eligible if s.busy]

    def _grow_or_preempt(self, slot: Slot) -> bool:
        tokens = slot.cache_len + 1 + slot.lookahead
        if self._ensure_blocks(slot, tokens):
            return True
        # growth can't allocate: free blocks by preempting. The victim
        # ordering prefers non-resumed seats (possibly ``slot`` itself);
        # a RESUMED other seat is the absolute last resort — the one
        # case the anti-thrash rule yields, because the alternative is
        # a wedged pool.
        victim = self.scheduler.preempt_candidate()
        if victim is None and not slot.resumed:
            victim = slot
        if victim is None:
            others = [
                s for s in self.scheduler.slots
                if s.busy and not s.done and s is not slot
            ]
            victim = min(
                others,
                key=lambda s: (s.request.priority, s.cache_len),
                default=None,
            )
        if victim is None:
            return False  # sole seat and can't grow: stall this step
        self._preempt(victim, "growth")
        if victim is slot:
            return False
        return self._ensure_blocks(slot, tokens)

    # ------------------------------------------------------------------ #
    # preemption with KV swap (PR 17)
    # ------------------------------------------------------------------ #
    def _make_swap_fns(self, width: int) -> tuple:
        """Compiled gather/scatter over every paged-cache leaf (K/V
        pools AND int8 scale arrays — ``_kv_leaf_info``) for a pow2
        ``width`` of block ids. Ids are padded with 0, the garbage
        block, so padded scatter rows are harmless by the same contract
        invalid decode writes rely on. One trace per width, ever: the
        zero-retrace contract's swap leg."""
        info = list(self._kv_leaf_info)
        traces = self._traces

        def _gather(cache, idx):
            traces["swap_out"] += 1
            leaves = jax.tree.leaves(cache)
            return [
                jnp.moveaxis(jnp.take(leaves[i], idx, axis=ax), ax, 0)
                for i, ax in info
            ]

        def _scatter(cache, idx, *data):
            traces["swap_in"] += 1
            leaves = list(jax.tree.leaves(cache))
            treedef = jax.tree.structure(cache)
            for (i, ax), d in zip(info, data):
                leaf = leaves[i]
                lead = (slice(None),) * ax
                leaves[i] = leaf.at[lead + (idx,)].set(jnp.moveaxis(d, 0, ax))
            return jax.tree.unflatten(treedef, leaves)

        # the gather only reads; the scatter keeps the pools it is given
        return jax.jit(_gather), jax.jit(_scatter, donate_argnums=0)

    def _swap_fns_for(self, n: int) -> tuple:
        width = _next_pow2(n)
        fns = self._swap_fns.get(width)
        if fns is None:
            fns = self._swap_fns[width] = self._make_swap_fns(width)
        return width, fns

    def _swap_out_blocks(self, blocks: list[int]) -> tuple[list, int]:
        """device_get the contents of ``blocks`` across every paged
        leaf; returns (host arrays trimmed to len(blocks), total bytes)."""
        n = len(blocks)
        width, (gather, _) = self._swap_fns_for(n)
        idx = np.zeros(width, np.int32)
        idx[:n] = blocks
        host = jax.device_get(gather(self.cache, jnp.asarray(idx)))
        data = [np.asarray(d[:n]) for d in host]
        return data, sum(d.nbytes for d in data)

    def _restore_blocks(self, blocks: list[int], data: list) -> None:
        """Scatter saved host images into freshly allocated ``blocks``
        (same order as the gather: table position i -> image i)."""
        n = len(blocks)
        width, (_, scatter) = self._swap_fns_for(n)
        idx = np.zeros(width, np.int32)
        idx[:n] = blocks
        padded = []
        for d in data:
            if width > n:
                d = np.concatenate(
                    [d, np.zeros((width - n,) + d.shape[1:], d.dtype)]
                )
            padded.append(jnp.asarray(d))
        self.cache = scatter(self.cache, jnp.asarray(idx), *padded)

    def _preempt(self, slot: Slot, reason: str) -> None:
        """Swap ``slot`` out to host RAM: gather its blocks' contents
        (shared blocks included — restore must not depend on the cached
        chain surviving), park the request + images in the swap area,
        release the seat. The request's span stays OPEN (state
        "preempted"); its queue/TTFT clocks keep their original
        stamps."""
        req = slot.request
        data, nbytes = self._swap_out_blocks(slot.blocks)
        entry = _SwappedRequest(
            request=req,
            generated=list(slot.generated),
            pending=slot.pending,
            cache_len=slot.cache_len,
            n_blocks=len(slot.blocks),
            data=data,
            chunks=slot.chunks,
            preempted_count=slot.preempted_count + 1,
            admit_time=slot.admit_time,
            first_token_time=slot.first_token_time,
            cached_tokens=slot.cached_tokens,
            swap_bytes=nbytes,
            preempt_time=self._now(),
        )
        self._swapped_reqs.append(entry)
        self._swap_bytes_held += nbytes
        self._preempt_counts[reason] = self._preempt_counts.get(reason, 0) + 1
        self.pool.swap_out(slot.blocks)
        slot.blocks = []  # swap_out released them: release() must not re-free
        self.span_log.on_preempt(req.request_id, entry.preempt_time)
        self._tele(
            "record_preempt",
            request_id=req.request_id,
            reason=reason,
            blocks=entry.n_blocks,
            swap_bytes=nbytes,
            cache_len=entry.cache_len,
            priority=req.priority,
        )
        self.sampling.clear_slot(slot.index)
        self._tables[slot.index] = 0
        self._tables_dev = None
        self._slot_adapter[slot.index] = 0
        if self._proposer is not None:
            self._proposer.release(slot.index)
        if self.adapters is not None:
            self.adapters.release(req.adapter)
        self.scheduler.release(slot)  # frees the cow_spare, clears the seat

    def _try_resume(self) -> None:
        """Re-seat swapped requests, oldest first, while a free slot AND
        their block footprint are available. Resume never preempts —
        swapped work re-enters only on genuinely free capacity."""
        if not self._swapped_reqs:
            return
        free_slots = [s for s in self.scheduler.slots if not s.busy]
        while self._swapped_reqs and free_slots:
            entry = self._swapped_reqs[0]
            req = entry.request
            if self.adapters is not None and not self.adapters.resident(
                req.adapter
            ):
                break  # oldest-first: no resume reordering around tenants
            n = entry.n_blocks
            if self.scheduler.chunked_reserve:
                total = n  # grow on demand; growth has the preempt escape
            else:
                # full-reservation mode: restore the no-mid-flight-OOM
                # guarantee before the request decodes again
                total = max(n, self.pool.blocks_for_tokens(
                    len(req.prompt) + req.max_new_tokens
                ))
            if not self.pool.can_allocate(total):
                break
            slot = free_slots.pop(0)
            self._swapped_reqs.pop(0)
            self._resume(slot, entry, total - n)

    def _resume(
        self, slot: Slot, entry: _SwappedRequest, extra: int
    ) -> None:
        req = entry.request
        blocks = self.pool.swap_in(entry.n_blocks)
        self._restore_blocks(blocks, entry.data)
        if extra > 0:
            blocks = blocks + self.pool.allocate(extra)
        slot.clear()
        slot.request = req
        slot.blocks = blocks
        slot.cache_len = entry.cache_len
        slot.generated = list(entry.generated)
        slot.pending = entry.pending
        slot.chunks = entry.chunks
        slot.preempted_count = entry.preempted_count
        slot.resumed = True
        slot.cached_tokens = entry.cached_tokens
        slot.admit_time = entry.admit_time
        slot.first_token_time = entry.first_token_time
        # restored images live in different block ids than anything the
        # content index knows: keep every position out of it
        slot.cow_indices = set(range(len(blocks)))
        slot.lookahead = 0  # the draft cache was lost at swap-out
        if self.adapters is not None:
            self.adapters.acquire(req.adapter)
            self._slot_adapter[slot.index] = self.adapters.slot_of(req.adapter)
        self.sampling.set_slot(slot.index, req.temperature)
        self._tables[slot.index] = 0
        self._tables[slot.index, :len(blocks)] = blocks
        self._tables_dev = None
        self._swap_bytes_held -= entry.swap_bytes
        self._resumes_total += 1
        self.span_log.on_resume(req.request_id, self._now())

    def _maybe_preempt(self, blocked_before: dict, exclude=()) -> bool:
        """At most ONE head-funding preemption per step, and only when
        this step's admission actually blocked. Priority preemption
        victimizes any strictly-less-important seat; same-priority
        "pool" preemption fires only when a deadline exists and the
        head has burned half of it (pausing a seated request to seat an
        equal is otherwise pure churn)."""
        sched = self.scheduler
        if not sched.queue:
            return False
        br = sched.blocked_reasons
        seat_blocked = br["no_free_slot"] > blocked_before["no_free_slot"]
        pool_blocked = br["pool_exhausted"] > blocked_before["pool_exhausted"]
        if not (seat_blocked or pool_blocked):
            return False
        head = sched.queue[0]
        victim = sched.preempt_candidate(
            max_priority=head.priority - 1, exclude=exclude
        )
        if victim is not None:
            self._preempt(victim, "priority")
            return True
        if (
            pool_blocked
            and sched.max_queue_delay_s is not None
            and self._now() - head.submit_time
                > 0.5 * sched.max_queue_delay_s
        ):
            victim = sched.preempt_candidate(
                max_priority=head.priority, exclude=exclude
            )
            if victim is not None:
                self._preempt(victim, "pool")
                return True
        return False

    # ------------------------------------------------------------------ #
    # prefill/decode disaggregation (PR 19)
    # ------------------------------------------------------------------ #
    def _handoff_slot(self, slot: Slot) -> None:
        """Package a just-prefilled seat as a :class:`TransferManifest`
        and release it. The chain's block images leave through the SAME
        compiled swap gather the preemption path uses (int8 scale rows
        ride along), so the payload is bitwise what a colocated engine
        would have held; the chain keys make it content-addressed for
        decode-side dedup. The seat and its blocks free immediately —
        a prefill replica's pool only ever funds in-flight ingestion."""
        req = slot.request
        used = -(-slot.cache_len // self.block_size)
        data, nbytes = self._swap_out_blocks(slot.blocks[:used])
        keys = req.prefix_keys
        if keys is None:
            # admission only computes keys when the prefix cache is on;
            # the manifest needs them regardless (they are its address)
            keys = prefix_keys(
                self._model_fingerprint, req.adapter, req.prompt,
                self.block_size,
            )
        manifest = TransferManifest(
            request_id=req.request_id,
            prompt=tuple(req.prompt),
            max_new_tokens=req.max_new_tokens,
            temperature=req.temperature,
            eos_token_id=req.eos_token_id,
            adapter=req.adapter,
            priority=req.priority,
            keys=tuple(keys),
            fingerprint=self._model_fingerprint,
            block_size=self.block_size,
            n_blocks=used,
            cache_len=slot.cache_len,
            data=data,
            nbytes=nbytes,
            first_token=slot.pending,
            submit_time=req.submit_time,
            admit_time=slot.admit_time,
            first_token_time=slot.first_token_time,
            cached_tokens=slot.cached_tokens,
            prefill_chunks=slot.chunks,
        )
        if self._plane is not None:
            manifest = self._plane.stage(manifest)
        self._outbox.append(manifest)
        self._transfer_stats["manifests_out"] += 1
        # close the span here: this replica's part of the request's life
        # ends at hand-off (the decode replica opens its own)
        self.span_log.on_finish(
            req.request_id, self._now(), len(slot.generated),
            accept_rate=None,
        )
        self.sampling.clear_slot(slot.index)
        self._tables[slot.index] = 0
        self._tables_dev = None
        self._slot_adapter[slot.index] = 0
        if self._proposer is not None:
            self._proposer.release(slot.index)
        if self.adapters is not None:
            self.adapters.release(req.adapter)
        self.scheduler.release(slot)

    def pop_manifests(self) -> list[TransferManifest]:
        """Drain the prefill outbox (router transfer-pump API)."""
        out, self._outbox = self._outbox, []
        return out

    def acquire(self, manifest: TransferManifest) -> dict:
        """Accept a hand-off. Seats the request immediately when a free
        slot and its block footprint are available, else parks it in the
        inbox (seated at the next :meth:`step`, before admission).
        Returns the placement accounting: ``{"seated": bool}`` plus, when
        seated, the dedup split (``reused_blocks`` found warm in the
        local CACHED index vs ``moved_blocks`` scatter-restored from the
        manifest's host images and their ``moved_bytes``)."""
        self._regime.refuse("hand-off (acquire)")
        self._land_or_refuse("hand-off (acquire)")
        res = self._try_seat_manifest(manifest)
        if res is None:
            self._inbox.append(manifest)
            self._transfer_stats["seat_deferred"] += 1
            return {"seated": False}
        return res

    def _seat_manifests(self) -> None:
        while self._inbox:
            res = self._try_seat_manifest(self._inbox[0])
            if res is None:
                break  # FIFO: no reordering around a big chain
            self._inbox.pop(0)

    def _try_seat_manifest(self, m: TransferManifest) -> Optional[dict]:
        free = [s for s in self.scheduler.slots if not s.busy]
        if not free:
            return None
        if self.adapters is not None and not self.adapters.resident(m.adapter):
            return None
        used = m.n_blocks
        full = m.cache_len // self.block_size  # blocks with chain keys
        total = min(
            max(used, self.pool.blocks_for_tokens(
                len(m.prompt) + m.max_new_tokens
            )),
            self._max_table,
        )
        # warm-prefix dedup: chain-prefix blocks already in the CACHED
        # index are acquired (refcounted) instead of moved — the
        # content-addressed keys guarantee bitwise-identical contents,
        # so only the tail images scatter-restore
        hits = self.pool.lookup(list(m.keys)[:full])
        reused = len(hits)
        if hits:
            self.pool.acquire(hits)
        if not self.pool.can_allocate(total - reused):
            if hits:
                self.pool.free(hits)
            return None
        new = self.pool.allocate(total - reused)
        tail = used - reused
        moved_bytes = m.bytes_per_block() * tail
        if tail:
            self._restore_blocks(
                new[:tail], [d[reused:used] for d in m.data]
            )
        # index the freshly restored FULL prompt blocks: the next
        # manifest sharing this chain dedups against them (that is the
        # decode pool's entire warm set — it never prefills)
        published: set = set()
        if self.prefix_cache is not None:
            for i in range(reused, full):
                self.pool.publish(new[i - reused], m.keys[i])
                published.add(i)
        req = Request(
            prompt=list(m.prompt),
            max_new_tokens=m.max_new_tokens,
            temperature=m.temperature,
            eos_token_id=m.eos_token_id,
            request_id=m.request_id,
            adapter=m.adapter,
            priority=m.priority,
        )
        req.submit_time = m.submit_time
        req.prefix_keys = list(m.keys)
        slot = free[0]
        slot.clear()
        slot.request = req
        slot.blocks = list(hits) + new
        slot.cache_len = m.cache_len
        slot.generated = [m.first_token]
        slot.pending = m.first_token
        slot.chunks = m.prefill_chunks
        slot.cached_tokens = m.cached_tokens
        slot.admit_time = m.admit_time
        slot.first_token_time = m.first_token_time
        # shared = every position decode must copy-on-write before a
        # write: the acquired warm hits AND the just-published restores
        # (decode's first write lands at cache_len — beyond all of them
        # — so this is the same defensive posture as _decode_step's)
        slot.shared = set(range(reused)) | published
        if self.adapters is not None:
            self.adapters.acquire(req.adapter)
            self._slot_adapter[slot.index] = self.adapters.slot_of(req.adapter)
        self.sampling.set_slot(slot.index, req.temperature)
        self._tables[slot.index] = 0
        self._tables[slot.index, :len(slot.blocks)] = slot.blocks
        self._tables_dev = None
        # replay the lifecycle on this replica's span log with the
        # manifest's original stamps — queue/TTFT accounting stays
        # honest across the hop (finish closes the span normally)
        self.span_log.on_submit(
            req.request_id, m.submit_time, len(m.prompt),
            adapter_id=m.adapter,
        )
        self.span_log.on_admit(req.request_id, m.admit_time)
        self.span_log.on_prefill(
            req.request_id, m.first_token_time,
            cached_prefix_tokens=m.cached_tokens,
        )
        self.span_log.on_first_token(req.request_id, m.first_token_time)
        if m.eos_token_id is not None and m.first_token == m.eos_token_id:
            slot.done = True  # defensive: prefill keeps these local
            slot.finish_time = self._now()
        if m.max_new_tokens <= 1:
            slot.done = True
            slot.finish_time = self._now()
        stats = self._transfer_stats
        stats["manifests_in"] += 1
        stats["blocks_deduped"] += reused
        stats["blocks_moved"] += tail
        stats["bytes_moved"] += moved_bytes
        return {
            "seated": True,
            "reused_blocks": reused,
            "moved_blocks": tail,
            "moved_bytes": moved_bytes,
        }

    def transfer_gauges(self) -> dict:
        """Cumulative hand-off accounting (both directions)."""
        return dict(
            self._transfer_stats,
            transfer_inbox_depth=len(self._inbox),
            transfer_outbox_depth=len(self._outbox),
        )

    def _decode_step(self, active: list[Slot]) -> Callable:
        """The device half of one decode step over the seated batch: the
        inputs, the dispatch, the wait and the fetch of the sampled tokens.
        Returns the host half, ``emit(events)``, which :meth:`_step_inner`
        runs in its emit phase. With ``decode_ahead`` the step fetched here
        was dispatched during the step before, and the one after it goes to
        the device before the wait. ``decode_ahead`` is read here, at the
        step boundary: an engine that has landed since fetches the step in
        flight and dispatches none behind it."""
        ahead, self._ahead = self._ahead, None
        windows = self._regime.windows
        self._fetched += 1
        self._fetched_ahead += ahead is not None
        if ahead is None:
            (out, n), now = self._dispatch_decode(active), active
        else:
            # a slot of it may have ended since, by its eos, and its seat
            # may hold another request: the row it wrote is never read
            out, n = ahead[0]
            now = [s for s, req in ahead[1] if s.request is req and not s.done]
        if self.decode_ahead:
            # the step after this one: every decoding slot but those this
            # step ends (by their count) or brings to a window's end (the
            # roll-over runs in emit, before their next row)
            flying = {s.index for s in now}
            nxt = [
                s for s in active
                if s.index not in flying or (
                    len(s.generated) + 1 < s.request.max_new_tokens
                    and windows(s.cache_len + 1) <= s.windows_done
                )
            ]
            if nxt:
                self._ahead = (
                    self._dispatch_decode(nxt, prev=(out, flying)),
                    [(s, s.request) for s in nxt],
                )
        out = self._fetch(out, "jit__decode", n)

        def emit(events: list[TokenEvent]) -> None:
            for slot in now:
                token = int(out[slot.index])
                slot.cache_len += 1  # the fed token was written this step
                slot.pending = token
                slot.generated.append(token)
                self._note_token(slot, token, events)
                if not slot.done and windows(slot.cache_len) > slot.windows_done:
                    self._roll_over(slot)

        return emit

    def _dispatch(self, program: str, fn, *args, to_host: bool = False):
        """Hand one decode step to the device: ``fn`` (the compiled
        ``program``, as the device trace names it) over the pool and
        ``args``. Returns its sampled tokens, not fetched, and ``n``, how
        many dispatches of ``program`` this engine had made before: the
        span of the call and the span of the wait for it carry both, which
        is what pairs them with each other and with the program's n-th
        execution on the device."""
        n = self._dispatched[program]
        self._dispatched[program] = n + 1
        with annotate("atpu:serve.decode.dispatch", program=program, n=n):
            self.cache, out = fn(self.params, self.cache, *args)
            if to_host:
                out.copy_to_host_async()  # behind the step, no round trip
        return out, n

    def _fetch(self, out: jax.Array, program: str, n: int) -> np.ndarray:
        """The sampled tokens of dispatch ``n`` of ``program`` on the host:
        the wait for the device (it finishes, the runtime tells the host,
        the thread wakes), then the copy of an array that is ready."""
        with annotate("atpu:serve.decode.wait", program=program, n=n) as span:
            if span.is_enabled():
                out.block_until_ready()
        with annotate("atpu:serve.decode.fetch") as span:
            host = np.asarray(out)
            held = self._step_stats.pop(n, None) if program == "jit__decode" else None
            if held is not None:
                # the step that was fetched, in one place: what it held
                # (from its dispatch) and what its routing touched
                rows, seated, more = held
                span.set_metadata(
                    rows=rows, seated=seated,
                    experts_touched=int(host[self.max_slots]),
                    experts_held=self._experts_held, **more)
            return host

    def _dispatch_decode(self, slots: list[Slot], prev=None) -> tuple:
        """Put one decode step over ``slots`` on the device; returns its
        sampled tokens, not fetched, and the dispatch's count
        (:meth:`_dispatch`). ``prev`` (``decode_ahead``): the step
        before's tokens, on the device and not fetched either, and the
        indices of the slots it decodes: each of them stands one position
        past what the host has emitted, and feeds on that step's token."""
        prev_out, flying = prev if prev is not None else (None, ())
        # everything before the call: the rows, the puts, the fed tokens
        with annotate("atpu:serve.decode.inputs", seated=len(slots)) as phase:
            tokens = np.zeros((self.max_slots, 1), np.int32)
            cache_lens = np.zeros(self.max_slots, np.int32)
            lengths = np.zeros(self.max_slots, np.int32)
            from_prev = np.zeros(self.max_slots, bool)
            positions = self._regime.host_positions()
            rows_at = self._regime.rows
            at = 0
            for slot in slots:
                ahead = int(slot.index in flying)
                # shared blocks are immutable: a decode step about to write
                # into one (the pending token lands at cache_len) copies it
                # private first. Block-aligned hits mean this only fires when
                # generation flows into a still-shared block boundary case.
                t = slot.cache_len // self.block_size
                if t in slot.shared:
                    self._cow_block(slot, t)
                tokens[slot.index, 0] = slot.pending
                from_prev[slot.index] = ahead
                cache_lens[slot.index] = rows_at(slot.cache_len + ahead)
                lengths[slot.index] = 1
                at += slot.cache_len + ahead
                if positions is not None:
                    positions[slot.index] = slot.cache_len + ahead
            # what this step's attention reads: the rows the seated slots
            # hold, their new one included, and the positions they stand for
            rows = int(cache_lens.sum()) + len(slots)
            # what the cache's kind says of them beside that
            more = self._regime.step_stats(cache_lens, lengths)
            # and what those rows occupy in the pools as they are allocated
            phase.set_metadata(
                rows=rows, positions=at + len(slots),
                cache_bytes=int(rows * self.kv_bytes_per_token), **more)
            if self._counts_experts:
                self._step_stats[self._dispatched["jit__decode"]] = (
                    rows, len(slots), more)
            if self._feed_fn is None:
                fed = jnp.asarray(tokens)
            else:
                fed = self._feed_fn(
                    self._no_tokens if prev_out is None else prev_out,
                    tokens, from_prev,
                )
            args = self._regime.decode_args(
                fed, self._tables_device(), cache_lens, lengths,
                self.sampling.temperatures(), self._split_key(), positions,
                self._lora_call_args(self._slot_adapter),
            )
        return self._dispatch(
            "jit__decode", self._decode_fn, *args,
            to_host=self._feed_fn is not None,
        )

    def _roll_over(self, slot: Slot) -> None:
        """eva: ``slot`` has just filled a window. Its ``window_blocks``
        blocks of rows become ``summary_blocks`` blocks of summaries in the
        pool (one compiled program, built with the engine), written over
        the first of them: the table stands as it is and the window's other
        blocks take the next window's rows. After a request's last
        roll-over the blocks its remaining positions cannot need go back."""
        lay = self._eva
        first = slot.windows_done * lay.summary_blocks
        src = slot.blocks[first:first + lay.window_blocks]
        assert len(src) == lay.window_blocks, (len(src), slot.cache_len)
        with annotate("atpu:serve.roll_over", slot=slot.index,
                      window=slot.windows_done):
            self.cache = self._rollover_fn(
                self.params, self.cache, *self._regime.rollover_args(src))
        slot.windows_done += 1
        self._rollovers_total += 1
        req = slot.request
        keep = self._regime.footprint(
            len(req.prompt) + req.max_new_tokens, start=slot.cache_len)
        if keep < len(slot.blocks):
            self.pool.free(slot.blocks[keep:])
            del slot.blocks[keep:]
            self._tables[slot.index, keep:] = 0
            self._tables_dev = None

    def _spec_step(self, active: list[Slot]) -> Callable:
        """One speculative iteration: propose up to k tokens per slot,
        verify pending + drafts in ONE compiled pass at ``(max_slots,
        k + 1)``, commit the longest target-agreeing prefix host-side.
        Accepted drafts' KV was written BY the verify pass — commit is
        just cursor advancement; rejection leaves the cursor short of
        the stale writes, which the next round's position-addressed
        writes overwrite (no copies). The only blocks the verify writes
        can touch beyond plain decode's are the +lookahead reservation,
        so a SHARED (prefix-cached) block anywhere in that span is
        copied-on-write up front, before any speculative write."""
        k = self._spec.k
        width = k + 1
        with annotate("atpu:serve.decode.inputs", seated=len(active)):
            for slot in active:
                # COW the whole speculative write span [cache_len,
                # cache_len + lookahead]. Under block-aligned admission
                # shared blocks sit strictly below the cursor's block, so
                # this loop firing means a boundary case (full-prompt hit)
                # — same defensive posture as _decode_step, widened by the
                # lookahead.
                span = slot.lookahead
                hi = min(
                    (slot.cache_len + span) // self.block_size,
                    len(slot.blocks) - 1,
                )
                for t in range(slot.cache_len // self.block_size, hi + 1):
                    if t in slot.shared:
                        self._cow_block(slot, t)
            spec_slots = [s for s in active if s.lookahead > 0]
            drafts = self._proposer.propose(spec_slots, self._tables_device())
            drafted_any = any(drafts.values())
            if drafted_any:
                tokens = np.zeros((self.max_slots, width), np.int32)
                cache_lens = np.zeros(self.max_slots, np.int32)
                lengths = np.zeros(self.max_slots, np.int32)
                n_drafted = {}
                for slot in active:
                    d = drafts.get(slot.index, [])[: min(k, slot.lookahead)]
                    n_drafted[slot.index] = len(d)
                    tokens[slot.index, 0] = slot.pending
                    if d:
                        tokens[slot.index, 1:1 + len(d)] = d
                    cache_lens[slot.index] = slot.cache_len
                    lengths[slot.index] = 1 + len(d)
                vfn = self._verify_fns.get(width)
                if vfn is None:
                    vfn = self._verify_fns[width] = self._make_verify(width)
                # one host-side stack -> one device put (a per-key jnp.stack
                # would cost width+1 dispatches on the hottest loop in
                # serving)
                keys = np.stack(self._peek_keys(width))
                args = self._regime.verify_args(
                    tokens, self._tables_device(), cache_lens, lengths,
                    self.sampling.temperatures(), keys,
                    self._lora_call_args(self._slot_adapter),
                )
        if not drafted_any:
            # nothing proposed this round (n-gram miss everywhere): the
            # plain decode program is the cheaper identical-output path,
            # and it consumes one chain key exactly like a 0-draft verify
            # (its dispatch is jit__decode's and counts there)
            self._spec_rounds_total += 1
            return self._decode_step(active)
        out, n = self._dispatch("jit__verify", vfn, *args)
        out = self._fetch(out, "jit__verify", n)

        def emit(events: list[TokenEvent]) -> None:
            max_emitted = 1
            for slot in active:
                n = n_drafted[slot.index]
                drafted = tokens[slot.index, 1:1 + n]
                slot.cache_len += 1  # the pending token's write is always valid
                emitted = 0
                for j in range(n + 1):
                    token = int(out[slot.index, j])
                    accepted = j < n and token == int(drafted[j])
                    slot.pending = token
                    slot.generated.append(token)
                    emitted += 1
                    if accepted:
                        slot.spec_accepted += 1
                        self._spec_accepted_total += 1
                    self._note_token(slot, token, events)
                    if slot.done or not accepted:
                        break
                    # the matched draft was written at this position by the
                    # verify pass — committing it is pure cursor advancement
                    slot.cache_len += 1
                slot.spec_proposed += n
                self._spec_proposed_total += n
                max_emitted = max(max_emitted, emitted)
                self._proposer.commit(slot)
            self._spec_rounds_total += 1
            self._consume_keys(max_emitted)

        return emit

    def _note_token(self, slot: Slot, token: int,
                    events: list[TokenEvent]) -> None:
        req = slot.request
        done = (
            len(slot.generated) >= req.max_new_tokens
            or (req.eos_token_id is not None and token == req.eos_token_id)
        )
        if done:
            slot.done = True
            slot.finish_time = self._now()
        events.append(TokenEvent(req.request_id, token, done))

    def _finish(self, slot: Slot) -> None:
        req = slot.request
        n_new = len(slot.generated)
        decode_s = slot.finish_time - slot.first_token_time
        record = {
            "request_id": req.request_id,
            "adapter_id": req.adapter,
            "prompt_tokens": len(req.prompt),
            "cached_prefix_tokens": slot.cached_tokens,
            "new_tokens": n_new,
            "queue_s": slot.admit_time - req.submit_time,
            "ttft_s": slot.first_token_time - req.submit_time,
            "e2e_s": slot.finish_time - req.submit_time,
            "decode_tokens_per_s": (
                (n_new - 1) / decode_s if n_new > 1 and decode_s > 0 else None
            ),
            # speculation accounting (None accept_rate = request never
            # had a draft proposed: speculation off, or all-miss n-gram)
            "spec_proposed": slot.spec_proposed,
            "spec_accepted": slot.spec_accepted,
            "accept_rate": (
                slot.spec_accepted / slot.spec_proposed
                if slot.spec_proposed else None
            ),
            # PR 17: how turbulent this request's flight was
            "preempted_count": slot.preempted_count,
            "prefill_chunks": slot.chunks,
        }
        self.stats.add(record)
        self._tele("record_serve", **record)
        span = self.span_log.on_finish(
            req.request_id, slot.finish_time, n_new,
            accept_rate=record["accept_rate"],
        )
        if span is not None:
            self._tele("record_span", **span.to_record())
        if self.slo_tracker is not None:
            self.slo_tracker.observe(
                slot.finish_time, record["ttft_s"], record["e2e_s"]
            )
        self._results[req.request_id] = list(slot.generated)
        self._result_order.append(req.request_id)
        if self.max_retained_results is not None:
            while len(self._result_order) > self.max_retained_results:
                self._results.pop(self._result_order.popleft(), None)
        self.sampling.clear_slot(slot.index)
        self._tables[slot.index] = 0
        self._tables_dev = None
        self._slot_adapter[slot.index] = 0
        if self._proposer is not None:
            self._proposer.release(slot.index)
        if self.adapters is not None:
            self.adapters.release(req.adapter)
        self.scheduler.release(slot)

    def _shed(self, req: Request) -> None:
        """Terminal path for a refused/expired request: close its span
        as shed, record why (bounded history), and emit the
        ``kind="shed"`` + ``kind="span"`` records."""
        now = self._now()
        reason = req.shed_reason or "unknown"
        self.stats.add_shed(reason)
        self._shed_reasons[req.request_id] = reason
        self._shed_order.append(req.request_id)
        bound = self.span_log.closed.maxlen or 512
        while len(self._shed_order) > bound:
            self._shed_reasons.pop(self._shed_order.popleft(), None)
        span = self.span_log.on_shed(req.request_id, now, reason)
        self._tele(
            "record_shed",
            request_id=req.request_id,
            adapter_id=req.adapter,
            reason=reason,
            queue_s=now - req.submit_time,
            prompt_tokens=len(req.prompt),
            max_new_tokens=req.max_new_tokens,
        )
        if span is not None:
            self._tele("record_span", **span.to_record())

    def _tele(self, method: str, **fields) -> None:
        """Emit through the attached telemetry if it has the method —
        duck-typed/older collectors missing a record_* simply skip it."""
        if self._telemetry is None:
            return
        fn = getattr(self._telemetry, method, None)
        if fn is not None:
            fn(**fields)

    def _gauge_fields(self) -> dict:
        """The live-engine posture sampled into ``kind="serve_gauge"``
        records (host-side reads only — no device sync)."""
        now = self._now()
        sched = self.scheduler
        # the queue is FIFO over one monotonic clock, so ages are sorted
        # (oldest at the head) and the p95 reads straight off the index
        # within 5% of the head — no O(n) list build per gauge sample
        # (a 10k-deep backlog under soak made every sample an O(n) scan)
        n_queued = len(sched.queue)
        if n_queued:
            rank = 0.95 * (n_queued - 1)
            lo = int(rank)
            hi = min(lo + 1, n_queued - 1)
            a_lo = now - sched.queue[n_queued - 1 - lo].submit_time
            a_hi = now - sched.queue[n_queued - 1 - hi].submit_time
            queue_age_p95 = a_lo + (a_hi - a_lo) * (rank - lo)
        else:
            queue_age_p95 = 0.0
        pool = self.pool.stats()
        active = [s for s in sched.slots if s.busy]
        tokens_in_flight = sum(s.cache_len for s in active)
        fields = {
            "engine_steps": self._steps,
            "queue_depth": n_queued,
            "queue_age_p95_s": queue_age_p95,
            "slots_active": len(active),
            "slot_occupancy": len(active) / self.max_slots,
            "pool_blocks_free": pool["free"],
            "pool_blocks_allocated": pool["allocated"],
            "pool_blocks_cached": pool["cached"],
            "pool_utilization": pool["utilization"],
            "shared_blocks": pool["shared"],
            "prefix_cache_hit_rate": (
                self.prefix_cache.hit_rate
                if self.prefix_cache is not None else 0.0
            ),
            "cow_copies_total": (
                self.prefix_cache.cow_copies_total
                if self.prefix_cache is not None else 0
            ),
            "prefill_tokens_saved_total": (
                self.prefix_cache.tokens_saved_total
                if self.prefix_cache is not None else 0
            ),
            "tokens_in_flight": tokens_in_flight,
            # table entries that hold a position the next decode step
            # reads (cache_len included: its token is written first),
            # over the max_slots x max_blocks a gather reads: the share
            # of that read the decode kernel's live-block walk still makes
            "live_block_share": sum(
                self._regime.rows(s.cache_len) // self.block_size + 1
                for s in active
            ) / (self.max_slots * self._max_table),
            "admission_blocked_no_free_slot_total":
                sched.blocked_reasons["no_free_slot"],
            "admission_blocked_pool_exhausted_total":
                sched.blocked_reasons["pool_exhausted"],
            "admission_blocked_adapter_not_resident_total":
                sched.blocked_reasons["adapter_not_resident"],
            "adapters_resident": (
                len(self.adapters.resident_names())
                if self.adapters is not None else 0
            ),
            "shed_queue_full_total": sched.shed_counts["queue_full"],
            "shed_queue_deadline_total": sched.shed_counts["queue_deadline"],
            "spec_rounds": self._spec_rounds_total,
            "spec_tokens_proposed": self._spec_proposed_total,
            "spec_tokens_accepted": self._spec_accepted_total,
            "spec_accept_rate": (
                self._spec_accepted_total / self._spec_proposed_total
                if self._spec_proposed_total else 0.0
            ),
            # PR 17 capacity plane: swap ledger, preempt/resume rates,
            # chunk throughput, and the per-token KV cost int8 halves
            "swapped_blocks": pool["swapped"],
            "swapped_requests": len(self._swapped_reqs),
            "swap_bytes_held": self._swap_bytes_held,
            "preempts_total": sum(self._preempt_counts.values()),
            "preempts_priority_total": self._preempt_counts["priority"],
            "preempts_pool_total": self._preempt_counts["pool"],
            "preempts_growth_total": self._preempt_counts["growth"],
            "resumes_total": self._resumes_total,
            "prefill_chunks_total": self._prefill_chunks_total,
            # real tokens over bucket tokens of every prefill so far: what of
            # the prefill programs' width held a token
            "prefill_real_token_share": (
                self.prefill_real_tokens_total
                / self.prefill_bucket_tokens_total
                if self.prefill_bucket_tokens_total else 0.0
            ),
            "kv_bytes_per_token": self.kv_bytes_per_token,
            "state_bytes_per_slot": self.state_bytes_per_slot,
            # what the cache's kind adds (other kinds keep their schema)
            **self._regime.gauges(
                active, tokens_in_flight, self.kv_bytes_per_token,
                self._rollovers_total),
            "pool_alias_bytes": self.pool_alias_bytes,
            "decode_ahead_share": self.decode_ahead_share,
        }
        if self._role != "colocated":
            # PR 19 disaggregation plane: hand-off accounting only for
            # pool members — a colocated engine's gauge records stay
            # byte-identical to the pre-disagg schema
            fields["role"] = self._role
            fields.update(self.transfer_gauges())
        return fields

    def _sample_gauges(self) -> None:
        if self._telemetry is None:
            return  # no collector: no record is built
        self._tele("record_serve_gauge", **self._gauge_fields())
        # piggy-back the HBM census on the gauge cadence (the census's
        # own wall-clock throttle bounds the walk rate)
        self._tele("sample_memory")

    def _emit_slo(self) -> None:
        self._tele("record_slo", **self.slo_tracker.snapshot(self._now()))

    def _register_census_owners(self) -> None:
        """Point the telemetry's buffer census at this engine's resident
        pytrees. Providers re-read the live attributes at sample time, so
        cache churn / adapter swaps / speculation toggles stay correctly
        attributed without re-registration."""
        census = getattr(self._telemetry, "census", None)
        if census is None:
            return
        census.set_owner("params", lambda: self.params)
        census.set_owner("kv_cache", lambda: self.cache)
        census.set_owner(
            "adapter_stack",
            lambda: (
                (self.adapters.stacks(), self.adapters.scales())
                if self.adapters is not None else None
            ),
        )
        census.set_owner(
            "draft_pool",
            lambda: (
                (
                    getattr(self._proposer, "cache", None),
                    getattr(self._proposer, "params", None),
                )
                if self._proposer is not None else None
            ),
        )

    def _handle_oom(self, exc: BaseException, *, context: str) -> None:
        """RESOURCE_EXHAUSTED boundary: write the atomic autopsy from
        already-resident state (never a fresh census walk), dump the
        flight ring, return so the caller re-raises. Never raises."""
        try:
            from ..profiling.oom import is_resource_exhausted, write_oom_report

            if not is_resource_exhausted(exc):
                return
            census = getattr(self._telemetry, "census", None)
            diag = getattr(self._telemetry, "diagnostics", None)
            directory = diag.config.dir if diag is not None else None
            path = write_oom_report(
                exc,
                context=context,
                census=getattr(census, "last", None),
                pool_stats=self.pool.stats(),
                directory=directory,
                extra={"engine_steps": self._steps,
                       "slots_active": sum(
                           1 for s in self.scheduler.slots if s.busy
                       )},
            )
            if diag is not None:
                diag.recorder.event(
                    "oom", context=context, report_path=path,
                    error=str(exc)[:500],
                )
        except Exception:  # noqa: BLE001 — forensics never mask the OOM
            pass

    def capture_programs(self, registry: Any = None) -> list[str]:
        """Register every compiled serving program with the process-wide
        :class:`~accelerate_tpu.profiling.ProgramRegistry`.

        jit's call cache and the AOT ``lower().compile()`` cache are
        separate, so holding a ``Compiled`` in hand costs ONE explicit
        AOT compile per program — this is an explicit, once-per-topology
        call (after warmup), not something the hot path pays. Abstract
        specs are the shapes of what the live call sites' own argument
        builders (the regime's) return for empty inputs at the engine's
        shape contract (the fixed decode/verify batch shapes, every prefill
        bucket seen so far); the ``.lower()`` re-traces each closure, so
        the trace counters are snapshotted and restored — the
        zero-retrace contract's counters stay at their steady-state
        values. Returns the labels registered."""
        import time as _time

        from ..profiling.registry import get_program_registry

        # NOT `registry or ...`: an empty ProgramRegistry is falsy (len 0)
        registry = get_program_registry() if registry is None else registry

        regime, n = self._regime, self.max_slots
        temps = self.sampling.temperatures()
        tables = np.zeros((n, self._max_table), np.int32)
        none = np.zeros(n, np.int32)
        labels: list[str] = []
        snapshot = dict(self._traces)

        def _one(label, fn, *specs, **meta):
            compiled = self._captured_programs.get(label)
            t0 = _time.perf_counter()
            if compiled is None:
                try:
                    compiled = fn.lower(
                        *jax.eval_shape(lambda *a: a, *specs)).compile()
                except Exception as exc:  # noqa: BLE001 — partial > none
                    logger.debug(f"capture_programs({label}) failed: {exc}")
                    return
                self.capture_compile_count += 1
                self._captured_programs[label] = compiled
            registry.register_compiled(
                label, compiled, kind="serve",
                compile_seconds=_time.perf_counter() - t0, **meta,
            )
            labels.append(label)

        try:
            lora_n = self._lora_call_args(self._slot_adapter)
            for bucket in sorted(self._prefill_buckets):
                _one(
                    f"serve_prefill_b{bucket}", self._prefill_fn,
                    self.params, self.cache, *regime.prefill_args(
                        np.zeros((1, bucket), np.int32), tables[:1], 0, 0,
                        self._key, 0.0, 0, self._lora_call_args([0])),
                    bucket=bucket,
                )
            _one(
                "serve_decode", self._decode_fn, self.params, self.cache,
                *regime.decode_args(
                    none[:, None], tables, none, none, temps, self._key,
                    regime.host_positions(), lora_n),
            )
            if self._rollover_fn is not None:
                _one("serve_rollover", self._rollover_fn, self.params, self.cache,
                     *regime.rollover_args())
            for width, vfn in sorted(self._verify_fns.items()):
                _one(
                    f"serve_verify_w{width}", vfn, self.params, self.cache,
                    *regime.verify_args(
                        np.zeros((n, width), np.int32), tables, none, none,
                        temps, np.stack([self._key] * width), lora_n),
                    width=width,
                )
            zero = np.zeros((), np.int32)
            _one("serve_cow", self._cow_fn, self.cache, zero, zero)
            _one("serve_key_chain", self._key_chain_fn, self._key)
        finally:
            # .lower() above re-traced the closures; restore the
            # steady-state counters the zero-retrace assertions read
            self._traces.clear()
            self._traces.update(snapshot)
        self.pool_alias_bytes = int(min(
            (getattr(compiled.memory_analysis(), "alias_size_in_bytes", 0)
             for label, compiled in self._captured_programs.items()
             if label.startswith(
                 ("serve_prefill", "serve_decode", "serve_verify",
                  "serve_rollover"))),
            default=0,
        ))
        return labels

    def audit_programs(
        self,
        registry: Any = None,
        *,
        contract: Any = None,
        emit: bool = True,
    ) -> dict[str, Any]:
        """Sharding X-ray over every captured serving program: audit
        each memoized capture-time ``Compiled``'s HLO for collectives
        and check it against the expected-collective contract derived
        from how the engine's params are actually sharded (replicated
        params ⇒ decode/verify/COW/prefill expect ZERO cross-device
        collectives).

        Reuses the AOT artifacts :meth:`capture_programs` memoized — no
        second compile, ``trace_counts()`` untouched (capture_programs
        itself restores them). Returns ``{label: ProgramAudit}``; with
        ``emit=True`` each audit also flows out as a ``kind="audit"``
        telemetry record (flight ring, sinks, sharding_violation
        anomalies)."""
        from ..parallel.sharding import collective_contract_for_params
        from ..profiling.registry import get_program_registry

        registry = get_program_registry() if registry is None else registry
        if not self._captured_programs:
            self.capture_programs(registry)
        if contract is None:
            contract = collective_contract_for_params(
                self.params, family="serve",
            )
        audits: dict[str, Any] = {}
        for label, compiled in self._captured_programs.items():
            audit = registry.audit(label, compiled, contract=contract)
            if audit is None:
                continue
            audits[label] = audit
            if emit:
                self._tele("record_audit", **audit.to_record())
        return audits

    def audit_summary(self, registry: Any = None) -> dict:
        """Roll-up of the stored serving-program audits (ICI/DCN bytes,
        violation count + details) for soak reports.
        Empty dict when :meth:`audit_programs` has not run."""
        from ..profiling.registry import get_program_registry

        registry = get_program_registry() if registry is None else registry
        labels = [
            lbl for lbl in registry.audits() if lbl in self._captured_programs
        ]
        if not labels:
            return {}
        return registry.audit_summary(labels)

    # ------------------------------------------------------------------ #
    # observability surface
    # ------------------------------------------------------------------ #
    def set_observability(
        self,
        *,
        telemetry: Any = None,
        gauge_interval: int = 1,
        slo: Any = None,
        spans: bool = True,
    ) -> None:
        """(Re)attach or detach the observability plane at runtime on a
        WARM engine — an A/B toggle: the same compiled
        programs replay the same trace with observability off, then on,
        so the measured delta is purely span/gauge/SLO host work.
        ``slo`` accepts an :class:`SLOConfig` or an existing
        :class:`SloTracker` (pass the tracker to keep accumulating
        across toggles)."""
        self._telemetry = telemetry
        if gauge_interval < 0:
            raise ValueError("gauge_interval must be >= 0 (0 disables)")
        self.gauge_interval = gauge_interval
        if slo is None:
            self.slo_tracker = None
        elif isinstance(slo, SloTracker):
            self.slo_tracker = slo
        else:
            self.slo_tracker = SloTracker(slo)
        self.span_log.enabled = spans
        self._register_census_owners()

    def set_prefix_cache(
        self, enabled: bool, model_fingerprint: Optional[str] = None
    ) -> None:
        """Toggle prefix caching at runtime on a WARM engine. Caching is
        pure host policy — the compiled prefill/decode programs are
        identical either way — so a caller can A/B cold vs warm
        on one engine without a single retrace. Disabling clears the
        content index (cached LRU blocks return to the free list;
        in-flight shared blocks keep their refcounts and drain
        normally)."""
        if enabled:
            self._regime.refuse("prefix_cache")
            self._land_or_refuse("prefix_cache")
            if model_fingerprint is not None:
                self._model_fingerprint = model_fingerprint
            if self.prefix_cache is None:
                self.prefix_cache = PrefixCache(
                    self.pool, fingerprint=self._model_fingerprint
                )
        else:
            self.pool.clear_cache()
            self.prefix_cache = None
        self.scheduler.prefix_cache = self.prefix_cache

    def set_speculation(self, spec: Optional[SpecConfig]) -> None:
        """Toggle speculative decoding at runtime on a WARM engine.
        ``None`` (or ``k=0``) turns it off — the very next step runs the
        plain decode program, outputs unchanged. Turning it on affects
        only requests ADMITTED from now on (they get the +k block
        reservation); already-seated requests finish plainly, so an
        in-flight verify write can never outrun a reservation made
        before the toggle. Verify programs are cached per width and
        proposers per config instance: an off→on→off→on A/B replays warm traces — the
        zero-retrace-after-warmup contract extends to the toggle."""
        if spec is None or spec.k == 0:
            self._spec = spec
            self._proposer = None
            self.scheduler.lookahead_tokens = 0
            return
        self._regime.refuse("spec_decode")
        self._land_or_refuse("spec_decode")
        proposer = self._proposers.get(id(spec))
        if proposer is None:
            if spec.method == "draft_model":
                with self._placed():  # the draft's own KV pool
                    proposer = DraftModelProposer(
                        spec,
                        target_config=self.model.config,
                        num_blocks=self.num_blocks,
                        block_size=self.block_size,
                        max_table=self._max_table,
                        max_slots=self.max_slots,
                    )
            else:
                proposer = NGramProposer(spec)
            self._proposers[id(spec)] = proposer
        self._spec = spec
        self._proposer = proposer
        self.scheduler.lookahead_tokens = spec.k

    def export_trace(self, path: str) -> str:
        """Write the last ``span_history`` closed spans (plus any still
        open) as Chrome-trace/Perfetto JSON; returns ``path``. Load in
        https://ui.perfetto.dev or ``chrome://tracing``."""
        spans = list(self.span_log.closed) + self.span_log.open_spans
        return write_chrome_trace(path, spans)

    def drain(self) -> list:
        """Enter drain mode: admission stops (``/healthz`` reports
        ``draining``, new submits shed with reason ``"draining"``),
        seated requests keep decoding to completion, and the unadmitted
        queue is harvested and RETURNED for the caller (typically a
        :class:`~accelerate_tpu.router.FleetRouter`) to re-route —
        graceful replica rotation without losing queued work."""
        self.scheduler.draining = True
        return self.scheduler.harvest_queue()

    def undrain(self) -> None:
        """Leave drain mode: admission resumes."""
        self.scheduler.draining = False

    @property
    def draining(self) -> bool:
        return self.scheduler.draining

    def health(self) -> dict:
        """The ``/healthz`` body: ``ok`` stays true while draining (the
        process is healthy — it is just not taking traffic), and the
        ``state`` field is what routers key ejection/rotation off."""
        return {
            "ok": True,
            "state": "draining" if self.scheduler.draining else "serving",
        }

    def prefix_digest(self, max_entries: int = 512) -> dict:
        """The ``/debug/prefix`` body: a bounded digest of this
        replica's cached chain keys for router-side overlap scoring.
        Keys are the PR 13 rolling hashes — tenant-fingerprint-scoped
        and content-addressed, so the digest never exposes raw tokens
        and never matches across tenants/adapters."""
        digest = self.pool.cached_chain_digest(max_entries)
        digest["fingerprint"] = self._model_fingerprint
        digest["enabled"] = self.prefix_cache is not None
        return digest

    def start_http(self, port: int = 0, host: str = "127.0.0.1"):
        """Start the stdlib scrape endpoint (``/metrics`` Prometheus
        text, ``/healthz`` = :meth:`health` JSON, ``/debug/state`` =
        :meth:`summary` JSON, ``/debug/prefix`` = :meth:`prefix_digest`)
        on a background thread; returns the exporter (``.port`` carries
        the bound port when ``port=0``). Requires an attached telemetry
        with a :class:`~..telemetry.sinks.PrometheusTextSink` for
        /metrics — one is added in-memory if missing."""
        if self._http is not None:
            return self._http
        from ..telemetry.http_exporter import MetricsHTTPExporter
        from ..telemetry.sinks import PrometheusTextSink

        metrics_fn = None
        tele = self._telemetry
        if tele is not None:
            sinks = getattr(tele, "sinks", None) or []
            prom = next(
                (s for s in sinks if isinstance(s, PrometheusTextSink)), None
            )
            if prom is None and hasattr(tele, "add_sink"):
                prom = PrometheusTextSink(path=None)
                tele.add_sink(prom)
            if prom is not None:
                metrics_fn = prom.render
        self._http = MetricsHTTPExporter(
            metrics_fn=metrics_fn, state_fn=self.summary,
            health_fn=self.health, prefix_fn=self.prefix_digest,
            host=host, port=port,
        )
        self._http.start()
        return self._http

    def stop_http(self) -> None:
        """Shut the scrape endpoint down cleanly (idempotent)."""
        if self._http is not None:
            self._http.stop()
            self._http = None

    def summary(self) -> dict:
        """Aggregate serve metrics: the :class:`ServeStats` percentile
        block plus live pool/queue/slot posture, span counts, SLO
        attainment and compile counts."""
        out = {
            **self.stats.summary(),
            "pool": self.pool.stats(),
            "traces": self.trace_counts(),
            "gauges": self._gauge_fields(),
            "spans": self.span_log.summary(),
        }
        if self.slo_tracker is not None:
            out["slo"] = self.slo_tracker.snapshot(self._now())
        if self.prefix_cache is not None:
            out["prefix_cache"] = self.prefix_cache.stats()
        if self._proposer is not None or self._spec_rounds_total:
            proposed = self._spec_proposed_total
            out["speculation"] = {
                "enabled": self._proposer is not None,
                "method": self._spec.method if self._spec else None,
                "k": self._spec.k if self._spec else 0,
                "rounds": self._spec_rounds_total,
                "proposed": proposed,
                "accepted": self._spec_accepted_total,
                "accept_rate": (
                    self._spec_accepted_total / proposed if proposed else 0.0
                ),
            }
        return out

"""Speculative decoding: break the one-token-per-slot-per-step wall.

The serving engine's decode throughput is hard-capped at one token per
slot per compiled step. Speculation lifts that cap without touching the
static-shape XLA discipline: a cheap PROPOSER guesses ``k`` continuation
tokens per slot, ONE compiled target-model verification program at
``(max_slots, k + 1)`` scores the pending token plus every guess in a
single pass, and the engine commits the longest prefix the target agrees
with — emitting up to ``k + 1`` tokens per step for the price of one.
All policy (proposing, accept/reject, commit/rewind) is host-side; XLA
only ever sees the fixed verify shape with per-slot validity ``lengths``
as traced data, so the zero-retrace contract holds (the verify program
traces ONCE per ``k``; ``ServingEngine.trace_counts()["verify"]`` proves
it).

Correctness does not depend on proposer quality: the verify pass samples
the TARGET model at every candidate position (greedy argmax for
``temperature == 0`` slots, the per-slot temperature stream otherwise)
and only drafts matching the target's own sample are accepted — the
emitted stream is by construction exactly what non-speculative decode
would have produced, a bad proposer only lowers ``accept_rate``
(Leviathan et al. 2023 for the draft-model form; LLMA / prompt-lookup,
Yang et al. 2023, for the draft-free form).

Two proposers ship:

* :class:`NGramProposer` — self-drafting prompt-lookup: scans the
  slot's OWN prompt + emitted tokens host-side for the most recent
  earlier occurrence of the trailing n-gram and proposes the tokens
  that followed it. No draft checkpoint, no device work, and it nails
  the repetitive/templated tails (code, JSON, quoted context) where
  speculation pays most.
* :class:`DraftModelProposer` — a small draft ``CausalLM`` sharing the
  paged-KV idiom AND the engine's block tables: the draft keeps its own
  per-layer pools (same ``num_blocks``/``block_size``, so one block id
  addresses both caches) and runs ``k`` greedy ``(max_slots, 1)`` paged
  decode steps per round. Draft KV for rejected positions is simply
  overwritten on the next round — position-addressed writes need no
  rollback copies, the same rewind-by-cursor trick the target cache
  uses.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

__all__ = ["SpecConfig", "NGramProposer", "DraftModelProposer"]


@dataclass(eq=False)
class SpecConfig:
    """Speculation knobs for :class:`~.engine.ServingEngine`.

    ``k`` is the draft length per verify round (``k = 0`` disables
    speculation — the engine runs its plain decode step, token-for-token
    identical to a no-spec engine). ``method`` picks the proposer:
    ``"ngram"`` (default, self-drafting prompt lookup) or
    ``"draft_model"`` (requires ``draft_model`` + ``draft_params``; the
    draft must share the target's vocabulary).

    ``eq=False`` on purpose: ``draft_params`` is a pytree, so configs
    hash by identity — the engine caches warm proposers per config
    instance, which keeps ``set_speculation`` toggles retrace-free.
    """

    k: int = 4
    method: str = "ngram"
    # n-gram proposer: longest/shortest trailing n-gram searched for
    max_ngram: int = 3
    min_ngram: int = 1
    # draft-model proposer
    draft_model: Any = None
    draft_params: Any = None

    def __post_init__(self):
        if self.k < 0:
            raise ValueError("k must be >= 0 (0 disables speculation)")
        if self.method not in ("ngram", "draft_model"):
            raise ValueError(
                f"method must be 'ngram' or 'draft_model', got {self.method!r}"
            )
        if self.method == "draft_model" and self.k > 0 and (
            self.draft_model is None or self.draft_params is None
        ):
            raise ValueError(
                "method='draft_model' requires draft_model and draft_params"
            )
        if not 1 <= self.min_ngram <= self.max_ngram:
            raise ValueError("need 1 <= min_ngram <= max_ngram")


class NGramProposer:
    """Draft-free prompt-lookup speculation (LLMA-style).

    ``propose`` scans each slot's full context (prompt + generated,
    including the pending token) for the most recent PREVIOUS occurrence
    of its trailing n-gram — longest ``n`` first, down to ``min_ngram``
    — and proposes up to ``k`` tokens that followed that occurrence.
    Pure host work on a numpy view; no device programs, so attaching it
    adds zero traces.
    """

    def __init__(self, cfg: SpecConfig):
        self.cfg = cfg
        self.misses = 0  # rounds where a slot had no n-gram match

    def lookup(self, context: list[int], k: int) -> list[int]:
        """Proposed continuation of ``context`` (possibly empty)."""
        if k <= 0 or len(context) < self.cfg.min_ngram + 1:
            return []
        arr = np.asarray(context, dtype=np.int64)
        for n in range(min(self.cfg.max_ngram, len(arr) - 1),
                       self.cfg.min_ngram - 1, -1):
            pattern = arr[-n:]
            # candidate windows must END before the last position so at
            # least one follow-token exists
            windows = np.lib.stride_tricks.sliding_window_view(arr[:-1], n)
            hits = np.flatnonzero((windows == pattern).all(axis=1))
            if hits.size:
                start = int(hits[-1]) + n  # most recent occurrence wins
                follow = arr[start:start + k]
                if follow.size:
                    return [int(t) for t in follow]
        self.misses += 1
        return []

    def propose(self, slots, tables) -> dict[int, list[int]]:
        out = {}
        for slot in slots:
            k = min(self.cfg.k, slot.lookahead)
            out[slot.index] = self.lookup(
                slot.request.prompt + slot.generated, k
            )
        return out

    # stateless: the engine hooks below are no-ops (shared interface
    # with DraftModelProposer, which does keep per-slot cache state)
    def prefill_slot(self, slot) -> None:
        pass

    def commit(self, slot) -> None:
        pass

    def release(self, slot_index: int) -> None:
        pass

    def cow(self, cache_copy_fn, src, dst) -> None:
        pass

    def trace_counts(self) -> dict:
        return {}


class DraftModelProposer:
    """A small draft ``CausalLM`` proposing greedily through its own
    paged KV pools, addressed by the ENGINE's block tables.

    The draft cache is a second set of per-layer ``(num_blocks,
    block_size, kv_heads, head_dim)`` pools with the target pool's exact
    geometry, so the slot block tables the scheduler already maintains
    address both caches — no second allocator, and the engine's
    copy-on-write covers the draft rows through :meth:`cow`.

    Invariant (per slot, between rounds): the draft has written KV for
    ``draft_len`` token positions, with ``slot.cache_len - 1 <=
    draft_len <= slot.cache_len`` — full prompt at admission (see
    :meth:`prefill_slot`; draft KV content is a pure function of the
    token prefix, so re-writing a shared block's draft rows is a
    semantic no-op), then each round ingests the 1–2 committed tokens
    the draft hasn't seen (lag 2 only after a full-accept round, whose
    last proposal was never fed back) and rolls ``k - 1`` greedy decode
    steps forward. Rejected speculative draft writes are left in place:
    the next round's position-addressed writes overwrite them.

    Device work per round: one ``(max_slots, 2)`` ingest step + ``k - 1``
    ``(max_slots, 1)`` decode steps, all through ONE jitted function
    (two trace shapes, counted in ``trace_counts()["draft_step"]``).
    """

    def __init__(
        self,
        cfg: SpecConfig,
        *,
        target_config: Any,
        num_blocks: int,
        block_size: int,
        max_table: int,
        max_slots: int,
    ):
        import jax
        import jax.numpy as jnp

        from ..models.generation import init_cache
        from .cache_regime import CacheRegime

        self.cfg = cfg
        self.model = cfg.draft_model
        self.params = cfg.draft_params
        dcfg = self.model.config
        if dcfg.vocab_size != target_config.vocab_size:
            raise ValueError(
                f"draft vocab ({dcfg.vocab_size}) must match the target's "
                f"({target_config.vocab_size}) — proposals are target ids"
            )
        if dcfg.max_seq_len < target_config.max_seq_len:
            raise ValueError(
                f"draft max_seq_len ({dcfg.max_seq_len}) must cover the "
                f"target's ({target_config.max_seq_len})"
            )
        self.max_table = max_table
        self.max_slots = max_slots
        # tokens of draft KV written per slot; engine updates via
        # prefill_slot / commit / release
        self._draft_len = np.zeros(max_slots, np.int64)
        # slot.cache_len at the latest propose() — commit() derives the
        # new draft_len from it
        self._base = np.zeros(max_slots, np.int64)
        self._traces = {"draft_prefill": 0, "draft_step": 0}
        traces = self._traces
        model = self.model

        # the draft's own pool: native rows in the engine's block id space
        regime = CacheRegime(dcfg, block_size, max_slots, num_blocks=num_blocks)
        self.cache = init_cache(
            model.init, jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
            decode=True, paged=regime.state(
                jnp.zeros((1, max_table), jnp.int32),
                jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32)),
        )

        def _prefill(params, cache, ids, table, length, cached_len):
            traces["draft_prefill"] += 1  # trace-time counter
            _, mutated = model.apply(
                {"params": params, "cache": cache}, ids, decode=True,
                paged=regime.state(table, cached_len, length),
                mutable=["cache"],
            )
            return mutated["cache"]

        def _step(params, cache, tokens, tables, cache_lens, lengths):
            traces["draft_step"] += 1  # two shapes ever: (B, 2) and (B, 1)
            logits, mutated = model.apply(
                {"params": params, "cache": cache}, tokens, decode=True,
                paged=regime.state(tables, cache_lens, lengths),
                mutable=["cache"],
            )
            # greedy proposals from the last VALID position per slot;
            # rows with lengths == 0 are inert (writes routed to the
            # garbage block, output ignored host-side)
            last = jnp.take_along_axis(
                logits, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1
            )[:, 0]
            return mutated["cache"], jnp.argmax(last, axis=-1)

        # the draft pools are donated like the target's (engine.py):
        # every call site rebinds ``self.cache``
        self._prefill_fn = jax.jit(_prefill, donate_argnums=1)
        self._step_fn = jax.jit(_step, donate_argnums=1)

    # ------------------------------------------------------------------ #
    # engine hooks
    # ------------------------------------------------------------------ #
    def prefill_slot(self, slot) -> None:
        """Prefill the draft cache with the slot's FULL prompt (one
        pow2-bucketed call, same idiom as the target prefill). Cached
        prefix blocks are re-written on purpose: their draft rows may
        predate this proposer (chain published with speculation off),
        and identical-content writes cannot corrupt any other holder."""
        self._ingest(slot, slot.request.prompt, 0)

    def _ingest(self, slot, tokens: list[int], start: int) -> None:
        """One bucketed prefill of ``tokens`` at the slot's position
        ``start``: the whole prompt at admission, or the tokens the target
        wrote while this proposer wasn't running (``full[dl : cache_len]``,
        so the draft's lag returns to 1)."""
        import jax.numpy as jnp

        n = len(tokens)
        bucket = 1 << max(n - 1, 0).bit_length() if n > 1 else 1
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :n] = tokens
        table = np.zeros((1, self.max_table), np.int32)
        table[0, :len(slot.blocks)] = slot.blocks
        self.cache = self._prefill_fn(
            self.params, self.cache, jnp.asarray(ids), jnp.asarray(table),
            jnp.asarray([n], jnp.int32), jnp.asarray([start], jnp.int32),
        )
        self._draft_len[slot.index] = start + n

    def propose(self, slots, tables) -> dict[int, list[int]]:
        import jax.numpy as jnp

        B, k = self.max_slots, self.cfg.k
        tables_j = jnp.asarray(tables)
        # per-slot draft budget (lookahead can be clamped below k when a
        # request sits near the table-capacity edge)
        budget = {s.index: min(k, s.lookahead) for s in slots}
        ingest = np.zeros((B, 2), np.int32)
        lens = np.zeros(B, np.int32)
        clens = np.zeros(B, np.int32)
        for slot in slots:
            full = slot.request.prompt + slot.generated
            dl = int(self._draft_len[slot.index])
            lag = slot.cache_len + 1 - dl  # 1 normally, 2 after full accept
            if lag > 2:
                # the slot advanced without us (speculation was toggled
                # off mid-flight, or this proposer was attached late) —
                # catch the draft cache up with one bucketed prefill of
                # the gap, then proceed at lag 1
                self._ingest(slot, full[dl:slot.cache_len], dl)
                dl = int(self._draft_len[slot.index])
                lag = slot.cache_len + 1 - dl
            assert 1 <= lag <= 2, (slot.index, lag)
            ingest[slot.index, :lag] = full[dl:dl + lag]
            lens[slot.index] = lag
            clens[slot.index] = dl
            self._base[slot.index] = slot.cache_len
        self.cache, tok = self._step_fn(
            self.params, self.cache, jnp.asarray(ingest), tables_j,
            jnp.asarray(clens), jnp.asarray(lens),
        )
        tok = np.asarray(tok)
        drafts = {
            s.index: [int(tok[s.index])] for s in slots if budget[s.index] > 0
        }
        for r in range(1, k):
            # slots whose budget is exhausted stop feeding (their writes
            # would run past the reserved block span)
            live = [s for s in slots if budget[s.index] > r]
            if not live:
                break
            toks = np.zeros((B, 1), np.int32)
            lens1 = np.zeros(B, np.int32)
            clens1 = np.zeros(B, np.int32)
            for slot in live:
                toks[slot.index, 0] = drafts[slot.index][-1]
                lens1[slot.index] = 1
                clens1[slot.index] = slot.cache_len + r
            self.cache, tok = self._step_fn(
                self.params, self.cache, jnp.asarray(toks), tables_j,
                jnp.asarray(clens1), jnp.asarray(lens1),
            )
            tok = np.asarray(tok)
            for slot in live:
                drafts[slot.index].append(int(tok[slot.index]))
        return drafts

    def commit(self, slot) -> None:
        """Called after the engine commits a round for ``slot``
        (``slot.cache_len`` already advanced): the draft's valid prefix
        is whatever it wrote that the commit confirmed."""
        self._draft_len[slot.index] = min(
            slot.cache_len, int(self._base[slot.index]) + self.cfg.k
        )

    def release(self, slot_index: int) -> None:
        self._draft_len[slot_index] = 0

    def cow(self, cache_copy_fn, src, dst) -> None:
        """Mirror the engine's copy-on-write into the draft pools (the
        shared block id addresses both caches)."""
        self.cache = cache_copy_fn(self.cache, src, dst)

    def trace_counts(self) -> dict:
        return dict(self._traces)

"""What a request's cache IS, in one place: five regimes, told apart by the
model's configuration alone, and nothing else under ``serving/`` reads the
fields that tell them apart.

* ``rows``: one row a position in per-head K/V pools, native or int8 — what
  every block-list feature of the engine is written for;
* ``eva`` (``attention_class`` "eva", ops/eva_attention.py): a slot's position
  (``Slot.cache_len``, what rope turns by), its rows (the write offset, what a
  query sees) and its blocks part ways, the table and the pool are sized by
  the rows, and a slot gives blocks back before it ends (the roll-over);
* ``recurrent`` (``layer_types`` "linear_attention") and ``latent``
  (``kv_lora_rank``, models/transformer.LatentAttention): as the refusals
  below say;
* ``ring`` (``layer_types`` "sliding_attention" beside "full_attention"):
  layers of ONE stack hold caches of two sizes. A full layer keeps ``rows``'
  pools and block table; a window layer holds, a SLOT, a ring of
  ``sliding_window`` rows in pools of its own that no table hands out
  (ops/attention.py: ``PagedKVState.ring``), so the pool, the table and the
  scheduler count the full layers' blocks alone and a seat's rings are a
  state it holds whatever its length.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.transformer import qkv_in_place
from ..ops.attention import (
    PAGED_POOL_LEAVES,
    RING_POOL_LEAVES,
    SLOT_STATE_LEAVES,
    PagedKVState,
    block_write_eligible,
    decode_kernel_eligible,
    latent_kernel_eligible,
    pool_heads_first,
)
from ..ops.gated_delta import chunked_kernel_eligible

# Engine features that take a request's state for a list of blocks holding one
# row a position each (prefix cache, copy on write, preemption swap, hand-off,
# speculation, chunked prefill, int8 pools, adapters) are refused, by name,
# where it is not that: regime -> (ROADMAP Reach item, why)
_NOT_A_BLOCK_LIST = {
    "eva": ("A4", "attention_class 'eva': a request's cache is chunk summaries "
            "beside a window of rows, not one row a position"),
    "recurrent": ("A4", "a stack with 'linear_attention' layers: a request's "
                  "cache is a recurrent state a slot, overwritten in place, "
                  "beside the blocks of its attention layers"),
    "latent": ("A3", "latent attention: a request's cache is one latent row a "
               "position, which a prefill expands and never reads back and a "
               "decode step reads absorbed, one position a slot"),
    "ring": ("A2", "a stack with 'sliding_attention' layers: a request's "
             "cache is, beside the blocks of its full-attention layers, a "
             "ring of sliding_window rows a slot in each window layer, which "
             "no block list reaches and a later position overwrites"),
}

# An engine's trace-time counters, at zero, and what each counts
TRACE_COUNTS = {
    "prefill": 0, "decode": 0,
    # of the traced decode programs, how many took the Pallas paged-attention
    # kernel (the rest gather)
    "decode_attn_kernel": 0, "cow": 0, "verify": 0, "swap_out": 0, "swap_in": 0,
    # of the traced prefill, decode and verify programs, how many hold each
    # pool as ONE buffer from their (donated) input through the layer loop to
    # their output
    "kv_in_place": 0,
    # of the traced prefill, decode and roll-over programs, how many ran the
    # cache of summaries beside a window (all or none)
    "eva": 0,
    # of the traced decode programs, how many read each layer's q/k/v kernels
    # where they lie in the stacked parameters
    # (models/transformer.py::qkv_in_place: one position a slot)
    "qkv_in_place": 0,
    # of the traced prefill and decode programs, how many carry a per-slot
    # state beside the pools (all or none)
    "recurrent_state": 0,
    # latent attention: of the traced decode programs, how many read the
    # latent rows absorbed through the ``latent_decode`` kernel; of the traced
    # prefill programs, how many expanded the prompt's latent and attended
    # what they projected
    "mla_decode_kernel": 0, "mla_prefill_expanded": 0,
    # of the traced prefill programs, how many attend what they projected
    # (``fresh``) and so hand attention the prompt's real length: flash then
    # walks the real rows, not the bucket
    "flash_real_rows": 0,
    # of the traced prefill programs, how many write their rows into the
    # pools by the block they fill (a ring: one slice), not by the row
    "kv_block_write": 0,
    # of the traced prefill programs, how many run their DeltaNet layers'
    # chunked rule as the ``gdn_chunked`` kernel
    "gdn_kernel": 0,
    # of the traced prefill and decode programs, how many ran their window
    # layers through a ring a slot (all or none); of the traced decode
    # programs, how many read the rings through the ``paged_decode`` kernel
    "window_ring": 0, "window_decode_kernel": 0,
}


class CacheRegime:
    """The kind of one model's cache and what follows from it, for the engine,
    the scheduler, the draft proposer (its own pool's) and
    ``capture_programs`` to ask. Built once, never changed (but for a ring's
    count of its wraps)."""

    def __init__(self, config: Any, block_size: int, max_slots: int,
                 kv_dtype: str = "bf16", num_blocks: Optional[int] = None):
        # eva: where a request's rows lie by its position (an ``EvaLayout``)
        self.layout = None
        # ring: the rows a window layer holds a slot (0: no such layers), and
        # how often a seat's rings have wrapped so far (the one thing here
        # that moves: a counter for the gauge)
        self.ring = 0
        self.ring_wraps_total = 0
        types = getattr(config, "layer_types", None) or ()
        if getattr(config, "attention_class", None) == "eva":
            from ..ops.eva_attention import EvaLayout

            self.kind = "eva"
            self.layout = EvaLayout(
                config.window_size, config.chunk_size, block_size)
        elif "linear_attention" in types:
            self.kind = "recurrent"
        elif "sliding_attention" in types:
            self.kind = "ring"
            self.ring = config.sliding_window
            if self.ring % block_size:
                raise ValueError(
                    f"sliding_window {self.ring} must be whole blocks of "
                    f"block_size {block_size}: a window layer's ring holds "
                    "exactly the rows its band allows")
        elif getattr(config, "kv_lora_rank", None) is not None:
            self.kind = "latent"
        else:
            self.kind = "rows"
        self.block_size = block_size
        self.max_slots = max_slots
        # "bf16" keeps the pools at the model's native compute dtype
        self.kv_dtype = "int8" if kv_dtype == "int8" else "native"
        # a slot's table is as wide as max_seq_len's rows need, and the
        # default pool holds every slot's full table plus the garbage block
        self.max_table = self.footprint(config.max_seq_len)
        self.num_blocks = (
            max_slots * self.max_table + 1 if num_blocks is None else num_blocks)
        # how a block of the pools is laid out: a rule of the model's shapes
        # (the eva regime's own writes are written for one row a position and
        # head; a latent row has no heads)
        self.heads_first = self.kind in ("rows", "recurrent", "ring") and (
            pool_heads_first(config.num_kv_heads, config.head_dim))
        # a prefill that has nothing before it to see attends what it
        # projected (``PagedKVState.fresh``), by the prompt's real length
        self.fresh = self.kind in ("recurrent", "latent", "ring")
        # a state lives in the seat itself: a prefill is told which
        self._seated_state = self.kind in ("recurrent", "ring")
        # layers that hold a ring a slot, and those that hold every row
        self._window_layers = types.count("sliding_attention")
        self._full_layers = types.count("full_attention")
        self._config = config

    def state(self, block_table, cache_len, lengths, *, slot=None,
              positions=None, single_device: bool = False,
              prefill: bool = False) -> PagedKVState:
        """The paged state of one call. ``slot`` (1,): the seat whose state a
        recurrent stack's prefill fills, from zero (no other call is told
        one); ``positions``: what eva's rows stand for; ``single_device``: what
        the pools' owner knows and a trace cannot see, they sit on one device."""
        return PagedKVState(
            block_table=block_table, cache_len=cache_len, lengths=lengths,
            num_blocks=self.num_blocks, block_size=self.block_size,
            kv_dtype=self.kv_dtype, single_device=single_device,
            positions=positions, slot=slot,
            num_slots=self.max_slots if self._seated_state else 0,
            fresh=prefill and self.fresh, heads_first=self.heads_first,
            ring=self.ring,
        )

    def rows(self, cache_len: int) -> int:
        """Cache rows held at position ``cache_len``: the write offset, and
        the last row the next query sees. One a position, or the layout's."""
        if self.layout is None:
            return cache_len
        return int(self.layout.rows(cache_len))

    def footprint(self, tokens: int, start: int = 0) -> int:
        """The most blocks a request holds at once on its way from ``start``
        positions to ``tokens``."""
        if self.layout is None:
            return -(-max(tokens, 0) // self.block_size)
        return self.layout.peak_blocks(tokens, start)

    def windows(self, cache_len: int) -> int:
        """eva: the whole windows before position ``cache_len``. A prefill
        writes their summaries in place; a slot that stands past more than its
        ``Slot.windows_done`` has filled one still held as rows. 0 elsewhere."""
        return cache_len // self.layout.window if self.layout is not None else 0

    def leaves(self, cache: Any) -> tuple:
        """The leaves of an allocated ``cache``, by what the model declares
        each to be (its variable's name: ops/attention.py's two tables), never
        by shape. Returns (flat leaf index, block axis) of every pool and
        int8 scale array ((..., num_blocks, block_size, ...): what the COW
        copy, the swap and the hand-off address blocks through; no block list
        reaches the state leaves, (..., num_slots, ...), nor a window
        layer's rings), the pools' bytes, rings included, those of one cached
        token over every layer that holds a row a position (the headline int8
        halves), and those of what a seat holds whatever its length: its
        state, its rings."""
        info: list[tuple[int, int]] = []
        kv_bytes = state_bytes = ring_bytes = 0
        flat, _ = jax.tree_util.tree_flatten_with_path(cache)
        for i, (path, leaf) in enumerate(flat):
            name = next(k.key for k in reversed(path) if hasattr(k, "key"))
            if name in PAGED_POOL_LEAVES:
                axis = leaf.ndim - 1 - PAGED_POOL_LEAVES[name]
                assert leaf.shape[axis] == self.num_blocks and (
                    self.block_size in leaf.shape[axis + 1:axis + 3]
                ), (name, leaf.shape)
                info.append((i, axis))
                kv_bytes += leaf.nbytes
            elif name in SLOT_STATE_LEAVES:
                axis = leaf.ndim - 1 - SLOT_STATE_LEAVES[name]
                assert leaf.shape[axis] == self.max_slots, (name, leaf.shape)
                state_bytes += leaf.nbytes
            elif name in RING_POOL_LEAVES:
                blocks = leaf.shape[leaf.ndim - 1 - RING_POOL_LEAVES[name]]
                assert (blocks - 1) % self.max_slots == 0, (name, leaf.shape)
                ring_bytes += leaf.nbytes
                # the slots' own blocks: all but the garbage block
                state_bytes += leaf.nbytes // blocks * (blocks - 1)
            else:
                raise NotImplementedError(
                    f"cache leaf {name!r} {leaf.shape} is neither a paged "
                    "pool, a window layer's ring nor a per-slot state "
                    "(ops/attention.py: PAGED_POOL_LEAVES, RING_POOL_LEAVES, "
                    "SLOT_STATE_LEAVES)"
                )
        return (info, kv_bytes + ring_bytes,
                kv_bytes / (self.num_blocks * self.block_size),
                state_bytes / self.max_slots)

    def refuse(self, feature: str) -> None:
        """Raise, naming ``feature``, unless a request's cache is a list of
        blocks of one row a position. The ONE predicate."""
        if self.kind in _NOT_A_BLOCK_LIST:
            item, why = _NOT_A_BLOCK_LIST[self.kind]
            raise NotImplementedError(
                f"{feature} is not written for {why} (ROADMAP Reach {item})")

    def prefill_args(self, ids, table, tokens: int, cached: int, key,
                     temperature: float, slot: Optional[int], lora=()) -> tuple:
        """``slot``: the seat's index, told to the program only where a state
        lives there (None too for a chunk: a recurrent stack refuses them)."""
        return (
            jnp.asarray(ids), jnp.asarray(table),
            jnp.asarray([tokens], jnp.int32), jnp.asarray([cached], jnp.int32),
            key, jnp.asarray([temperature], jnp.float32),
            np.asarray([slot], np.int32)
            if self._seated_state and slot is not None else None,
            *lora,
        )

    def host_positions(self) -> Optional[np.ndarray]:
        """What a decode step fills beside its rows: eva's positions."""
        if self.layout is None:
            return None
        return np.zeros(self.max_slots, np.int32)

    def decode_args(self, tokens, tables, rows, lengths, temps, key,
                    positions, lora=()) -> tuple:
        return (
            tokens, tables, jnp.asarray(rows), jnp.asarray(lengths), temps,
            key, None if positions is None else jnp.asarray(positions), *lora,
        )

    def verify_args(self, tokens, tables, cache_lens, lengths, temps, keys,
                    lora=()) -> tuple:
        return (
            jnp.asarray(tokens), tables, jnp.asarray(cache_lens),
            jnp.asarray(lengths), temps, jnp.asarray(keys), *lora,
        )

    def rollover_args(self, window=None) -> tuple:
        """eva: the blocks of a full window's rows and those of them its
        summaries are written over (None: the garbage block throughout), as
        numpy rows: jnp.asarray of a list compiles a conversion, once a shape."""
        if window is None:
            window = np.zeros(self.layout.window_blocks, np.int32)
        src = np.asarray(window, np.int32)
        return src, src[:self.layout.summary_blocks]

    def mark(self, traces: dict, program: str, state=None, q_len: int = 1,
             pool=None) -> None:
        """Bump, at trace time, what one trace of ``program`` ("prefill",
        "decode", "verify", "rollover") counts. A decode trace says its state,
        tokens a slot and a pool leaf: the ops' predicates say which kernel."""
        eva, recurrent, latent = (
            self.kind == k for k in ("eva", "recurrent", "latent"))
        if program == "rollover":
            traces["eva"] += 1
            return
        traces[program] += 1  # the zero-retrace contract rides on these
        traces["kv_in_place"] += 1
        if program == "verify":
            return
        traces["eva"] += eva
        traces["recurrent_state"] += recurrent
        traces["window_ring"] += self.kind == "ring"
        if program == "prefill":
            traces["mla_prefill_expanded"] += latent
            traces["flash_real_rows"] += self.fresh
            traces["kv_block_write"] += block_write_eligible(state, q_len)
            traces["gdn_kernel"] += recurrent and chunked_kernel_eligible(
                self._config.gdn_head_k_dim, self._config.gdn_head_v_dim)
        else:
            kernel = (latent_kernel_eligible if latent
                      else decode_kernel_eligible)(state, q_len, pool)
            traces["decode_attn_kernel"] += kernel
            traces["mla_decode_kernel"] += latent and kernel
            # a ring's blocks are a pool's: the one predicate holds for both
            traces["window_decode_kernel"] += self.kind == "ring" and kernel
            traces["qkv_in_place"] += qkv_in_place(True, q_len)

    def count_wraps(self, start: int, end: int) -> None:
        """ring: a prefill took a seat from ``start`` positions to ``end``;
        count how often its rings wrapped (a position written over an earlier
        one's row) on the way. A decode step's are counted by
        :meth:`step_stats`."""
        if self.ring:
            self.ring_wraps_total += (
                max(0, (end - 1) // self.ring)
                - max(0, (start - 1) // self.ring))

    def step_stats(self, cache_lens: np.ndarray, lengths: np.ndarray) -> dict:
        """What a decode step's spans say beside ``rows``, the sum of the
        rows (positions, their new one included) its seated slots (``lengths``
        1) stand at — ring: ``window_rows``, what a window layer reads of
        them, and over every layer the rows read (``layer_rows``) beside the
        rows a cache of one row a position and layer would read
        (``layer_positions``)."""
        if not self.ring:
            return {}
        rows = (cache_lens + 1)[lengths > 0]
        # the step writes position ``rows - 1``: over ring row 0 again
        self.ring_wraps_total += int(
            ((rows > self.ring) & ((rows - 1) % self.ring == 0)).sum())
        total = int(rows.sum())
        window = int(np.minimum(rows, self.ring).sum())
        return {
            "window_rows": window,
            "layer_rows": (window * self._window_layers
                           + total * self._full_layers),
            "layer_positions": total * (
                self._window_layers + self._full_layers),
        }

    def gauges(self, active: list, tokens_in_flight: int,
               bytes_per_token: float, rollovers_total: int) -> dict:
        """The regime's own fields of a ``serve_gauge`` record, over the
        seated slots ``active`` and the positions they stand at
        (``rollovers_total``: eva's roll-overs)."""
        fields = {
            # what ONE position holds over the layers as the latent pool is
            # allocated, lanes of padding included (0: per-head K and V)
            "latent_row_bytes": bytes_per_token if self.kind == "latent" else 0,
        }
        if self.layout is not None:
            # a cache that is not one row a position: what it holds beside
            # what it stands for (other engines' records keep their schema)
            rows = sum(self.rows(s.cache_len) for s in active)
            summary = sum(
                s.windows_done * self.layout.summary_blocks for s in active)
            fields.update(
                cache_rows_live=rows,
                cache_rows_per_token=rows / max(1, tokens_in_flight),
                window_rollovers_total=rollovers_total,
                summary_blocks=summary,
                window_blocks=sum(
                    self.layout.blocks(s.cache_len) for s in active) - summary,
            )
        if self.ring:
            full = sum(s.cache_len for s in active)
            window = sum(min(s.cache_len, self.ring) for s in active)
            layers = self._window_layers + self._full_layers
            fields.update(
                # rows a window layer and a full layer hold for the seats
                window_rows_live=window, full_rows_live=full,
                # over every layer, rows held a position stood at: 1.0 where
                # every layer holds every row
                cache_rows_per_token=(
                    window * self._window_layers + full * self._full_layers
                ) / max(1, full * layers),
                ring_wraps_total=self.ring_wraps_total,
            )
        return fields

"""Attention ops with a single dispatch point.

The hot op of every model family. Three tiers, selected by
:func:`dot_product_attention`:

* ``xla`` — einsum softmax einsum; XLA fuses and tiles onto the MXU. Works
  everywhere (CPU tests, TPU), supports GQA and arbitrary masks/bias.
* ``flash`` — Pallas blockwise-softmax kernel (:mod:`.flash_attention`),
  O(seq) memory, TPU only.
* ``ring`` — sequence-parallel blockwise attention over the ``sp`` mesh axis
  (:mod:`.ring_attention`): each device holds a sequence shard, K/V blocks
  rotate around the ring via collective-permute. The long-context answer the
  reference lacks (SURVEY.md §5.7: no ring/Ulysses/context-parallel code
  exists there — Megatron-SP only).
"""

from __future__ import annotations

import functools
from typing import Optional

import flax.struct
import jax
import jax.numpy as jnp

# What a forward-only (serving) call may hold at once of temporaries that grow
# with its rows times something wide — a prefill's per-head q, k and v of
# latent attention (``models/transformer.py::LatentAttention``), the rows an
# expert layer gathers to its experts and back, one a choice
# (``ops/moe.py::moe_ragged``): beyond it the call walks them in equal parts,
# one after the other (:func:`forward_parts`). ONE budget, each user counting
# its own rows; a constant of the program, not read off the device: 1.5 GiB is
# what lets a 16,384-wide prefill at DeepSeek-V3's widths compile beside
# 12.5 GB of arguments on a v5e (heads whole, or the experts' rows in halves:
# refused by 0.2 GB) and cuts no program that ran before it (PERF.md, PR 40).
FORWARD_PART_BYTES = 3 << 29


def forward_parts(nbytes: int, of: int, budget: Optional[int] = None) -> int:
    """Into how many equal parts (a power of two that divides ``of``) a
    forward-only call cuts ``of`` things that together need ``nbytes`` of
    temporaries, so that a part needs at most ``budget``
    (:data:`FORWARD_PART_BYTES`); 1 where they fit whole."""
    budget = FORWARD_PART_BYTES if budget is None else budget
    parts = 1
    while nbytes > parts * budget and of % (2 * parts) == 0:
        parts *= 2
    return parts


@flax.struct.dataclass
class PagedKVState:
    """Per-call view of the paged KV cache (vLLM-style block tables in
    static-shape XLA form).

    The pools themselves live as flax ``cache`` variables inside the
    model ((num_blocks, block_size, kv_heads, head_dim) per layer — NO
    batch dim, so heterogeneous sequence lengths share HBM); this struct
    carries the per-slot indexing that routes each call into them:

    ``block_table``  (B, max_blocks) int32 — pool indices per slot, in
                     sequence order: table slot t holds global positions
                     [t*block_size, (t+1)*block_size). Unused tail
                     entries point at block 0, the RESERVED garbage
                     block the host allocator never hands out.
    ``cache_len``    (B,) int32 — tokens already written for the slot;
                     this call's token i lands at global position
                     cache_len + i.
    ``lengths``      (B,) int32 — valid tokens in THIS call (prefill:
                     the real prompt length inside the padded bucket;
                     decode: 1 for active slots, 0 for empty ones).
                     Writes beyond it are routed to the garbage block.

    ``num_blocks`` / ``block_size`` are static (pytree metadata): one
    engine → one compiled program shape.

    ``kv_dtype`` selects the pool storage format, also static (it picks
    the compiled program's dtype lattice): ``"native"`` stores K/V at
    the model's compute dtype; ``"int8"`` stores sym-quantized int8
    rows with one fp32 amax scale per written token slot — decode is
    HBM-bandwidth-bound, so the 2x (vs bf16) byte shrink is a direct
    capacity/throughput lever (:func:`paged_update` quantizes on
    write, :func:`paged_attention` dequantizes on gather).

    ``single_device`` (static) is what the pools' owner knows about
    where they live: True when every pool sits whole on ONE device (the
    engine sets it from where its weights are), False — the default —
    when they may be sharded over a mesh. A Mosaic kernel cannot be
    auto-partitioned, so only True lets :func:`paged_attention` take
    the decode kernel.

    A cache that is not one row a position (``attention_class`` "eva":
    :mod:`.eva_attention`) makes three things of ``cache_len``. It stays
    what the pools know — the slot's rows so far: the write offset, and
    the last row a query sees — and the position that rope turns by
    travels beside it: ``positions`` (B,), the position of this call's
    first token. None is ``cache_len`` where a row is a position; for such
    a cache it marks a call that starts a slot's cache from position 0 with
    nothing before it — a prefill, which writes other rows than it was
    given.

    A model may keep, beside the pools, a state a SLOT that is overwritten in
    place (a recurrent layer's: ``(num_slots, ...)`` leaves, named in
    :data:`SLOT_STATE_LEAVES`). ``num_slots`` (static) sizes those leaves and
    ``slot`` (B,) says which slot each row of the call writes; None is "row b
    is slot b" — a decode batch over every seat. ``fresh`` (static) marks a
    call that starts its rows' caches from position 0 with nothing before it:
    a recurrent layer then starts from a zero state, reads none, and
    attention attends what the call projected instead of gathering the table.

    ``heads_first`` (static): a block of the K/V pools is stored ``(Hkv,
    block_size, D)`` — each KV head's rows together — instead of
    ``(block_size, Hkv, D)``; whoever creates the pools decides it by
    :func:`pool_heads_first` and says so here, and every operation on them
    reads it here (a pool's shape cannot tell the two apart).

    ``ring`` (static, whole blocks; 0: none): a stack whose layers are full
    and SLIDING-WINDOW attention side by side keeps, for the window layers,
    pools of their own (:data:`RING_POOL_LEAVES`) in which a SLOT holds a
    ring of ``ring`` rows whatever its length — position ``p`` at row ``p %
    ring`` of the slot's ``ring // block_size`` blocks, which no table hands
    out: slot ``s`` owns blocks ``[1 + s * ring // block_size, 1 + (s + 1) *
    ring // block_size)`` of ``ring_blocks`` (block 0 is the garbage block
    there too), ``slot`` / ``num_slots`` as for a slot's state. Keys are
    stored rotated and a softmax does not ask in which order its rows lie,
    so a query at position ``p`` reads the ring's first ``min(p + 1, ring)``
    rows under no band at all: :func:`paged_update` and
    :func:`paged_attention` with ``ring=True``. ``block_table`` and
    ``num_blocks`` stay the full layers'.
    """

    block_table: jax.Array
    cache_len: jax.Array
    lengths: jax.Array
    num_blocks: int = flax.struct.field(pytree_node=False)
    block_size: int = flax.struct.field(pytree_node=False)
    kv_dtype: str = flax.struct.field(pytree_node=False, default="native")
    single_device: bool = flax.struct.field(pytree_node=False, default=False)
    positions: Optional[jax.Array] = None
    slot: Optional[jax.Array] = None
    num_slots: int = flax.struct.field(pytree_node=False, default=0)
    fresh: bool = flax.struct.field(pytree_node=False, default=False)
    heads_first: bool = flax.struct.field(pytree_node=False, default=False)
    ring: int = flax.struct.field(pytree_node=False, default=0)

    @property
    def ring_blocks(self) -> int:
        """Blocks of a window layer's pool: every slot's ring and block 0."""
        return self.num_slots * (self.ring // self.block_size) + 1


# What a model's ``cache`` collection may hold, by the variable's name: the
# engine asks these tables which leaf is what, never a leaf's shape. A paged
# pool's value is how many trailing axes are one block's own (its block axis
# stands before them); a slot-state leaf has its slot axis before the
# trailing axes the value counts.
# Three kinds of pool: per-head keys and values (``key_pool`` / ``value_pool``,
# (.., block_size, Hkv, D) or heads first; with int8 rows their ``key_scale`` /
# ``value_scale``, one float32 a position), and ``latent_pool``, (..,
# block_size, W): ONE row a position shared by all heads (latent attention),
# written by :func:`latent_update` and read by :func:`latent_attention`.
PAGED_POOL_LEAVES = {"key_pool": 3, "value_pool": 3, "key_scale": 1,
                     "value_scale": 1, "latent_pool": 2}
SLOT_STATE_LEAVES = {"state": 3, "taps": 2}
# a window layer's K/V rings (``PagedKVState.ring``): laid out as a K/V pool,
# ``ring_blocks`` blocks that no block list reaches
RING_POOL_LEAVES = {"key_ring": 3, "value_ring": 3}


def pool_heads_first(kv_heads: int, head_dim: int) -> bool:
    """Whether a model's K/V pools store a block ``(Hkv, block_size, D)``
    instead of ``(block_size, Hkv, D)``: a rule of the shapes, for whoever
    creates the pools (it then says ``PagedKVState.heads_first``).

    Where the decode kernel can read the pools (``head_dim`` whole lanes)
    and the KV heads do not fill a sublane tile (``Hkv % 8``), XLA:TPU tiles a
    pool's minor pair ``(Hkv, D)`` below (8, 128) and has to RELAY OUT the
    whole pool, every step, to hand the kernel its ``(block_size * Hkv, D)``
    rows: at 2 KV heads of 256 and 64 slots x 16,384 positions, 2 x 1.07 GB
    copied, 9.1 ms of a 35.8 ms decode step on the v5e (PERF.md, PR 38).
    Heads first, the minor pair is ``(block_size, D)``, whole tiles, and the
    same view is free. A block's rows are then ``h * block_size + t``, which
    the kernel's mask reads (``paged_decode_attention(heads_first=)``)."""
    return head_dim % 128 == 0 and kv_heads % 8 != 0


# floor on the per-token amax scale: keeps all-zero rows (garbage block,
# never-written slots) dividing to exact 0 instead of NaN
KV_SCALE_EPS = 1e-8


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-token int8 quantization of a K or V tensor.

    ``x``: (B, S, Hkv, D) -> int8 values of the same shape + (B, S)
    fp32 scales, one amax scale per token row (over all kv heads and
    head dims). Per-TOKEN (not per-whole-block) scales are what make
    incremental decode writes exact-cost: appending token t to a
    half-full block touches only slot t's row and scale — a true
    per-block amax would need requantizing every earlier row whenever
    the running amax grew. The scale arrays live beside the pools at
    (num_blocks, block_size), i.e. one fp32 per pool row: the
    "per-block scales stored beside the pool" layout at 4 bytes per
    token of overhead against ~2*Hkv*D quantized bytes saved.
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=(2, 3))
    scale = jnp.maximum(amax / 127.0, KV_SCALE_EPS)
    q = jnp.round(x.astype(jnp.float32) / scale[:, :, None, None])
    return jnp.clip(q, -127.0, 127.0).astype(jnp.int8), scale


def _flat_pool(pool: jax.Array, inner: int) -> jax.Array:
    """``pool`` with everything before its last ``inner`` axes (a block's
    own: 3 of a K/V pool, 1 of a scale array) merged into ONE block axis:
    a layer's own pool is returned as it is, a stack of pools (L,
    num_blocks, ...) becomes (L * num_blocks, ...), in which layer ``l``'s
    block ``b`` is row ``l * num_blocks + b``. The merged axes are major
    in every layout, so the reshape moves nothing."""
    return pool.reshape(-1, *pool.shape[pool.ndim - inner:])


def _first_block(state: "PagedKVState", layer):
    """Where layer ``layer``'s blocks start in a flattened stack of pools
    (0 for a pool of its own, ``layer`` None)."""
    return 0 if layer is None else layer * state.num_blocks


def _ring_view(state: "PagedKVState") -> "PagedKVState":
    """``state`` as a window layer's rings see it: the table is each row's
    slot's own blocks, in ring order, and the pool is ``ring_blocks`` long."""
    per_slot = state.ring // state.block_size
    slot = (jnp.arange(state.cache_len.shape[0], dtype=jnp.int32)
            if state.slot is None else state.slot)
    table = 1 + slot[:, None] * per_slot + jnp.arange(
        per_slot, dtype=jnp.int32)[None, :]
    return state.replace(block_table=table, num_blocks=state.ring_blocks)


def _write_rows(state: "PagedKVState", s: int, layer, ring: bool = False):
    """(block, offset in it) of each of a call's ``B * s`` positions, flat:
    token i of slot b belongs at global position ``cache_len[b] + i``, table
    slot ``pos // block_size``, offset ``pos % block_size``; at or beyond
    ``lengths[b]`` it goes to the reserved block 0. ``ring`` (``state`` a
    :func:`_ring_view`): the position's row is ``pos % state.ring``, and of a
    call longer than the ring only the last ``ring`` valid rows are kept."""
    bs = state.block_size
    max_blocks = state.block_table.shape[1]
    pos = state.cache_len[:, None] + jnp.arange(s)[None, :]  # (B, S) global
    valid = jnp.arange(s)[None, :] < state.lengths[:, None]
    if ring:
        valid &= jnp.arange(s)[None, :] >= state.lengths[:, None] - state.ring
        pos = pos % state.ring
    tbl = jnp.clip(pos // bs, 0, max_blocks - 1)
    blocks = jnp.take_along_axis(state.block_table, tbl, axis=1)
    blocks = jnp.where(valid, blocks, 0) + _first_block(state, layer)
    offsets = pos % bs
    return blocks.reshape(-1), offsets.reshape(-1)


def block_write_eligible(state: "PagedKVState", s: int) -> bool:
    """Whether a call of ``s`` positions writes its rows by the BLOCK they
    fill instead of by the row — decided at trace time from what the call can
    observe, never from a model's name or an option. ONE predicate: the
    serving engine asks it too, for its ``kv_block_write`` trace count.

    A ``fresh`` call starts every row's cache at position 0, so a call of
    whole blocks fills table entries ``0 .. s / block_size - 1`` in order and
    the unit of "put position p's row where the table says" can be the block
    (a slot's ring: one run of blocks). Everything else — a decode step, a
    prefill onto a ``cache_len`` that need not be block-aligned, a bucket
    narrower than a block, int8 pools with their scale arrays — keeps the row
    scatter of :func:`_write_rows`."""
    return (state.fresh and s % state.block_size == 0
            and state.kv_dtype == "native")


def _as_blocks(rows: jax.Array, state: "PagedKVState") -> jax.Array:
    """``rows`` (B, n, <a row>), ``n`` whole blocks -> (B, n / block_size, <a
    block>) as a pool stores one: rows of (Hkv, D) turned (Hkv, block_size,
    D) once where the pool is heads first (a latent row has no heads)."""
    b, n = rows.shape[:2]
    blocks = rows.reshape(b, n // state.block_size, state.block_size,
                          *rows.shape[2:])
    if rows.ndim == 4 and state.heads_first:
        blocks = jnp.swapaxes(blocks, 2, 3)
    return blocks


def _fill_blocks(pool: jax.Array, rows: jax.Array, state: "PagedKVState",
                 layer) -> jax.Array:
    """A fresh call's ``rows`` (B, S, <a row>) into ``pool``, each block WHOLE
    at its table entry: one update of a block's values a block, indexed on the
    pool's major axis alone — nothing asks XLA to relay the pool out. A block
    that starts at or past ``lengths[b]`` is dropped (an index past the
    pool, each its own: the indices are unique); the prompt's last, partial
    block is written whole, so its rows past the length hold the padded
    positions' values inside the slot's OWN block, where no read reaches
    (every read is bounded by ``cache_len``; decode overwrites them in
    order)."""
    blocks = _as_blocks(rows, state)
    b, n = blocks.shape[:2]
    flat = _flat_pool(pool, blocks.ndim - 2)
    j = jnp.arange(n, dtype=jnp.int32)
    table = state.block_table[
        :, jnp.minimum(j, state.block_table.shape[1] - 1)]
    live = j[None, :] * state.block_size < state.lengths[:, None]
    at = jnp.where(
        live, table + _first_block(state, layer),
        flat.shape[0] + jnp.arange(b * n, dtype=jnp.int32).reshape(b, n))
    return flat.at[at.reshape(-1)].set(
        blocks.reshape(b * n, *flat.shape[1:]).astype(pool.dtype),
        mode="drop", unique_indices=True).reshape(pool.shape)


def _fill_ring(pool: jax.Array, rows: jax.Array, state: "PagedKVState",
               layer) -> jax.Array:
    """A fresh call's ``rows`` (B, S, Hkv, D) into a window layer's rings
    (``state`` a :func:`_ring_view`): ring row ``r`` of a prompt of ``L``
    positions holds position ``r + ring * ((L - 1 - r) // ring)`` — the
    prompt's last ``ring`` rows, rotated — so ``min(S, ring)`` rows are
    gathered in ring order (a bucket no longer than the ring lies in ring
    order as it is), laid out as blocks and written with ONE slice at the
    slot's first block. Rows ``r >= L`` of a ring not yet wrapped are garbage
    inside the slot's own ring: a read takes ``min(position + 1, ring)``
    rows."""
    s, ring = rows.shape[1], state.ring
    if s > ring:
        r = jnp.arange(ring, dtype=jnp.int32)[None, :]
        at = r + ring * ((state.lengths[:, None] - 1 - r) // ring)
        rows = jnp.take_along_axis(
            rows, jnp.clip(at, 0, s - 1)[:, :, None, None], axis=1)
    blocks = _as_blocks(rows, state).astype(pool.dtype)
    flat = _flat_pool(pool, 3)
    first = state.block_table[:, 0] + _first_block(state, layer)
    for i in range(blocks.shape[0]):
        flat = jax.lax.dynamic_update_slice(
            flat, blocks[i], (first[i], 0, 0, 0))
    return flat.reshape(pool.shape)


@jax.named_scope("kv_write")
def paged_update(
    key_pool: jax.Array,
    value_pool: jax.Array,
    k: jax.Array,
    v: jax.Array,
    state: PagedKVState,
    key_scale: Optional[jax.Array] = None,
    value_scale: Optional[jax.Array] = None,
    layer=None,
    ring: bool = False,
) -> tuple[jax.Array, ...]:
    """Scatter one call's K/V into the block pools.

    ``ring``: the pools are a window layer's rings (``state.ring`` rows a
    slot, :class:`PagedKVState`): the call's last ``ring`` valid rows land at
    row ``position % ring`` of their slot's own blocks.

    The pools are one layer's own, (num_blocks, block_size, Hkv, D), or
    with ``layer`` (a traced index) the stack of every layer's, (L,
    num_blocks, ...): the rows then land in that layer's part of the
    stack, in place, and the stack is returned whole — never sliced out
    and written back.

    ``k``/``v``: (B, S, Hkv, D); token i of slot b belongs at global
    position ``cache_len[b] + i``, which lives in table slot
    ``pos // block_size`` at offset ``pos % block_size``. Positions at or
    beyond ``lengths[b]`` (bucket padding, inactive decode slots) are
    rerouted to reserved block 0 — real blocks are never handed out as 0,
    so garbage can never collide with live data. Static shapes: one
    compiled scatter regardless of how full any sequence is.

    One algorithm — "put position p's row where the table says" — in two
    units: where :func:`block_write_eligible` holds (a ``fresh`` call of
    whole blocks, native rows) the unit is the BLOCK (:func:`_fill_blocks`;
    a ring: one slice a slot, :func:`_fill_ring`), everywhere else the row
    (:func:`_write_rows`). Every row a read can reach holds the same bits
    either way.

    Because every write lands at ``cache_len + i``, a nonzero
    ``cache_len`` makes the SAME program a tail prefill: prefix caching
    passes the cached-token count as ``cache_len`` and only the uncached
    tail as ``k``/``v`` — the shared prefix blocks in ``block_table`` are
    read by attention but never written.

    With ``state.kv_dtype == "int8"`` the per-token amax scale arrays
    (``key_scale``/``value_scale``, (num_blocks, block_size) fp32) must
    ride along: K/V rows are quantized on the way in and the return
    grows to ``(key_pool, value_pool, key_scale, value_scale)``.
    """
    b, s = k.shape[:2]
    bs = state.block_size
    if ring:
        state = _ring_view(state)
    if block_write_eligible(state, s):
        fill = _fill_ring if ring else _fill_blocks
        return (fill(key_pool, k, state, layer),
                fill(value_pool, v, state, layer))
    bf, of = _write_rows(state, s, layer, ring)

    def put(pool, rows, inner):
        if inner == 3 and state.heads_first:
            # (blocks, Hkv, block_size, D): position ``of`` of KV head ``h``
            # of block ``bf`` is ROW (bf * Hkv + h) * block_size + of of the
            # pool seen as rows of D — one row a write, so that the scatter
            # keeps the pool's layout (indexed as [bf, :, of] XLA:TPU turns
            # the whole pool token-major for the write and back: four
            # passes over it a call)
            hkv, d = rows.shape[-2:]
            at = (bf[:, None] * hkv + jnp.arange(hkv)[None, :]) * bs + of[:, None]
            return pool.reshape(-1, d).at[at.reshape(-1)].set(
                rows.reshape(-1, d)).reshape(pool.shape)
        return _flat_pool(pool, inner).at[bf, of].set(rows).reshape(pool.shape)

    if state.kv_dtype == "int8":
        if key_scale is None or value_scale is None:
            raise ValueError(
                "kv_dtype='int8' needs the key_scale/value_scale arrays"
            )
        k, k_s = quantize_kv(k)
        v, v_s = quantize_kv(v)
        kf = k.reshape(b * s, *k.shape[2:])
        vf = v.reshape(b * s, *v.shape[2:])
        return (
            put(key_pool, kf, 3),
            put(value_pool, vf, 3),
            put(key_scale, k_s.reshape(-1), 1),
            put(value_scale, v_s.reshape(-1), 1),
        )
    kf = k.reshape(b * s, *k.shape[2:])
    vf = v.reshape(b * s, *v.shape[2:])
    return put(key_pool, kf, 3), put(value_pool, vf, 3)


def decode_kernel_eligible(state: PagedKVState, q_len: int, pool) -> bool:
    """Whether :func:`paged_attention` takes the Pallas decode kernel for
    a call of ``q_len`` query tokens against ``pool`` ((..., block_size,
    Hkv, D); only its shape and dtype are read) — decided at trace time
    from what the call can observe, never from a model's name or a user
    option. ONE predicate: the serving engine asks it too, for its
    ``decode_attn_kernel`` trace count. The last clause is the kernel's
    tiling rule: a block's ``block_size * Hkv`` rows fill whole sublane
    tiles of the pool's dtype."""
    kv_heads, head_dim = pool.shape[-2:]
    if state.heads_first:
        kv_heads = pool.shape[-3]
    return _kernel_takes(
        state, q_len, pool.dtype, head_dim, state.block_size * kv_heads)


def _kernel_takes(state: PagedKVState, q_len: int, dtype, lanes: int,
                  rows_a_block: int) -> bool:
    """What both decode kernels ask of a call: one position a slot, the pool
    native and whole on one device, its rows on whole lanes, a block's rows on
    whole sublane tiles of ``dtype``, a TPU or ``kernel_interpret_mode()``."""
    from .flash_attention import kernels_interpreted

    sublanes = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    return (
        q_len == 1
        and state.kv_dtype == "native"
        and state.single_device
        and lanes % 128 == 0
        and rows_a_block % sublanes == 0
        and (jax.default_backend() == "tpu" or kernels_interpreted())
    )


@jax.named_scope("paged_attention")
def paged_attention(
    q: jax.Array,
    key_pool: jax.Array,
    value_pool: jax.Array,
    state: PagedKVState,
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    window=None,
    key_scale: Optional[jax.Array] = None,
    value_scale: Optional[jax.Array] = None,
    layer=None,
    ring: bool = False,
) -> jax.Array:
    """Attention read through the block table, in one of two forms of
    one algorithm (grouped softmax over a slot's blocks, under one mask
    rule): query at global row r sees column c iff ``c <= r`` (and ``c >
    r - window`` under a sliding band). With ``layer`` the pools are the
    stack of every layer's (see :func:`paged_update`) and both forms read
    that layer's blocks straight out of the stack: the table is offset,
    the stack is never sliced.

    * The decode kernel (:mod:`.paged_attention`, where
      :func:`decode_kernel_eligible` holds: S == 1, native pools whole
      on one device, head_dim a multiple of 128, a TPU or
      ``kernel_interpret_mode()``): walks each slot's LIVE blocks only,
      straight out of the pools. Every live K/V byte moves once.
    * The gather form (everything else: prefill, chunked prefill,
      speculative verify, int8 pools, pools sharded over a mesh, CPU):
      gather each slot's blocks into a (B, max_blocks*block_size, Hkv,
      D) view and run the xla path over it. Because the table is indexed
      by ``pos // block_size``, gathered column j IS global position j,
      so the mask is the same globally-anchored band as the dense cache
      path. Table tail entries point at the garbage block, whose columns
      sit beyond every row and mask out. Its cost follows ``max_blocks``,
      not ``cache_len``: on the v5e at 16 slots x 1024 positions the
      gather and the two contractions over it were 28 % of the decode
      program even without the GQA repeat (PERF.md, PR 25).

    Under ``kv_dtype="int8"`` the gathered int8 rows are dequantized
    (row * its per-token scale) at the query's dtype before the math —
    the pools stay int8 in HBM, only the gathered working set widens.

    ``ring``: the pools are a window layer's rings (:class:`PagedKVState`).
    ONE position a slot, just written at row ``cache_len % ring``: it sees
    the first ``min(cache_len + 1, ring)`` rows of its slot's ring — every
    position the band allows and no other — in whatever order they lie,
    through either form, the table the slot's own blocks and no band.
    """
    if ring:
        if q.shape[1] != 1 or window is not None or state.kv_dtype == "int8":
            raise NotImplementedError(
                "a window layer's ring is read by one position a slot, "
                "native rows, under no band: a call of several tokens onto "
                "an existing ring (chunked or prefix-cached prefill, "
                "speculative verification) is not written")
        state = _ring_view(state).replace(
            cache_len=jnp.minimum(state.cache_len, state.ring - 1))
    if decode_kernel_eligible(state, q.shape[1], key_pool):
        from .paged_attention import paged_decode_attention

        return paged_decode_attention(
            q, key_pool, value_pool, state.block_table, state.cache_len,
            scale=scale, softcap=softcap, window=window, layer=layer,
            heads_first=state.heads_first,
        )
    b, s = q.shape[:2]
    bs = state.block_size
    max_blocks = state.block_table.shape[1]
    table = state.block_table + _first_block(state, layer)

    def gathered(pool):
        rows = _flat_pool(pool, 3)[table]  # (B, max_blocks, <a block>)
        if state.heads_first:
            rows = jnp.swapaxes(rows, 2, 3)  # -> (.., block_size, Hkv, D)
        return rows.reshape(b, max_blocks * bs, *rows.shape[-2:])

    k, v = gathered(key_pool), gathered(value_pool)
    if state.kv_dtype == "int8":
        if key_scale is None or value_scale is None:
            raise ValueError(
                "kv_dtype='int8' needs the key_scale/value_scale arrays"
            )
        k_s = _flat_pool(key_scale, 1)[table].reshape(b, max_blocks * bs)
        v_s = _flat_pool(value_scale, 1)[table].reshape(b, max_blocks * bs)
        k = (k.astype(jnp.float32) * k_s[:, :, None, None]).astype(q.dtype)
        v = (v.astype(jnp.float32) * v_s[:, :, None, None]).astype(q.dtype)
    rows = (state.cache_len[:, None] + jnp.arange(s)[None, :])[:, None, :, None]
    cols = jnp.arange(max_blocks * bs)[None, None, None, :]
    keep = cols <= rows  # (B, 1, S, K)
    if window is not None:
        keep = jnp.logical_and(keep, cols > rows - window)
    return xla_attention(
        q, k, v, mask=keep, causal=False, scale=scale, softcap=softcap
    )


def latent_row_width(kv_rank: int, rope_dim: int) -> int:
    """Lanes of a latent pool's row: ``[c_kv (kv_rank) | k_rope (rope_dim)]``
    and zeros up to whole 128-lane tiles (576 -> 640: Mosaic refuses a DMA of
    rows that are no whole number of lanes, and a pool whose minor pair is
    whole tiles is read where it lies: PERF.md, PR 38 and PR 40). A rule of
    the shapes, for whoever creates the pool."""
    return -(-(kv_rank + rope_dim) // 128) * 128


@jax.named_scope("kv_write")
def latent_update(pool: jax.Array, rows: jax.Array, state: PagedKVState,
                  layer=None) -> jax.Array:
    """:func:`paged_update` for a latent pool, (num_blocks, block_size, W) or
    with ``layer`` the stack of every layer's: ``rows`` (B, S, <= W), ONE row
    a position whatever the heads, zeros behind it up to the pool's width,
    land where :func:`paged_update` puts a position's K and V — by the block
    where :func:`block_write_eligible` holds, as there."""
    b, s, w = rows.shape
    width = pool.shape[-1]
    if w < width:
        rows = jnp.pad(rows, ((0, 0), (0, 0), (0, width - w)))
    if block_write_eligible(state, s):
        return _fill_blocks(pool, rows, state, layer)
    bf, of = _write_rows(state, s, layer)
    return _flat_pool(pool, 2).at[bf, of].set(
        rows.reshape(b * s, width).astype(pool.dtype)).reshape(pool.shape)


def latent_kernel_eligible(state: PagedKVState, q_len: int, pool) -> bool:
    """:func:`decode_kernel_eligible` for a latent pool ((.., block_size, W);
    only its shape and dtype are read): one position a slot, the pool native
    and whole on one device, its rows on whole lanes and a block on whole
    sublane tiles, a TPU or ``kernel_interpret_mode()``. The ONE predicate:
    the serving engine asks it for its ``mla_decode_kernel`` trace count."""
    return _kernel_takes(
        state, q_len, pool.dtype, pool.shape[-1], state.block_size)


@jax.named_scope("latent_attention")
def latent_attention(q: jax.Array, pool: jax.Array, state: PagedKVState,
                     value_width: int, scale: float, layer=None) -> jax.Array:
    """Absorbed latent attention through the block table. ``q`` (B, S, H, w):
    a head's query in the LATENT's own coordinates, ``[q_nope W_UK^T |
    q_rope]``, scored against each cached row's first ``w`` lanes — all heads
    against the same rows —, and the result (B, S, H, value_width) is the
    softmax-weighted sum of the rows' first ``value_width`` lanes (``c_kv``),
    for the caller to take through ``W_UV``. The mask rule is
    :func:`paged_attention`'s: the query at global row r sees column c iff
    ``c <= r``. Float32 scores and softmax statistics.

    Two forms, as there: the ``latent_decode`` kernel
    (:func:`latent_kernel_eligible`) walks each slot's live blocks and copies
    every live latent byte from HBM ONCE for both matmuls; the gather form
    (any ``S``, the CPU, a sharded pool — and the kernel's oracle) gathers
    ``max_blocks * block_size`` rows a slot."""
    b, s, h, w = q.shape
    if latent_kernel_eligible(state, s, pool):
        from .paged_attention import latent_decode_attention

        return latent_decode_attention(
            q, pool, state.block_table, state.cache_len,
            value_width=value_width, scale=scale, layer=layer)
    table = state.block_table + _first_block(state, layer)
    rows = _flat_pool(pool, 2)[table]  # (B, max_blocks, block_size, W)
    rows = rows.reshape(b, -1, rows.shape[-1])
    scores = jnp.einsum(
        "bshw,bkw->bhsk", q, rows[..., :w],
        preferred_element_type=jnp.float32) * scale
    at = (state.cache_len[:, None] + jnp.arange(s)[None, :])[:, None, :, None]
    keep = jnp.arange(rows.shape[1])[None, None, None, :] <= at
    scores = jnp.where(keep, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhsk,bkc->bshc", probs, rows[..., :value_width])


def make_causal_mask(
    q_len: int, kv_len: int, dtype=jnp.bool_, window: Optional[int] = None
) -> jax.Array:
    """Lower-triangular (q_len, kv_len) mask aligned at the end (supports
    decode where q_len < kv_len). ``window``: sliding-window band — query
    row r additionally sees only the last ``window`` keys (col > r -
    window, self included), the HF semantics
    (transformers masking_utils.sliding_window_overlay: ``kv_idx > q_idx -
    sliding_window`` AND causal)."""
    offset = kv_len - q_len
    rows = jnp.arange(q_len)[:, None]
    cols = jnp.arange(kv_len)[None, :]
    keep = cols <= rows + offset
    if window is not None:
        keep = jnp.logical_and(keep, cols > rows + offset - window)
    return keep.astype(dtype)


def lengths_to_mask(kv_lengths: jax.Array, kv_len: int) -> jax.Array:
    """(B,) valid-prefix lengths -> (B, 1, 1, kv_len) bool key mask."""
    cols = jnp.arange(kv_len)[None, :]
    return (cols < kv_lengths[:, None])[:, None, None, :]


def xla_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    causal: bool = False,
    kv_lengths: Optional[jax.Array] = None,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_lengths: Optional[jax.Array] = None,
) -> jax.Array:
    """Reference-path attention, shapes (B, S, H, D) / kv (B, Skv, Hkv, D).

    fp32 softmax regardless of input dtype (bf16-safe). GQA (``G = H //
    Hkv > 1``) takes one of two forms, chosen by shape, as the v5e
    measured them (PERF.md, PR 25):

    * ``S < Skv`` — a query block against a longer cache: paged prefill,
      speculative verify, every decode that is not the Pallas kernel —
      contracts per KV-head GROUP: ``q`` is viewed as (B, S, Hkv, G, D)
      and both einsums run against the un-repeated ``k``/``v``, so each
      K/V byte is read once. Until PR 25 K and V were repeated ``G``
      times first, on the belief that XLA fuses the broadcast away; for
      the decode shape it did not — the repeat was written to HBM and
      read back, 28 % of the decode program (16 x 1 x 1024: 1398 us a
      call repeated, 54 us grouped).
    * ``S == Skv`` — self-attention in training and evaluation — keeps
      the repeat: there it is a few MB, and forward + backward measured
      5 % faster with it (8 x 192 tokens, 32/8 heads: 218 us against 229).

    ``G == 1`` is the plain multi-head program either way.
    ``window`` (requires ``causal``): the Mistral/Qwen2 sliding-window
    band — each query sees at most the last ``window`` keys; a TRACED
    window (the per-layer Gemma-2 pattern riding the layer scan) is fine
    here — only this path, not flash/ring, accepts one. ``softcap``:
    Gemma-2 tanh soft-capping of the raw scores. ``q_lengths`` (B,): the
    rows at or past it come out as zeros, as the flash kernel leaves them.
    """
    if window is not None and not causal:
        raise ValueError("sliding window requires causal attention")
    orig_dtype = q.dtype
    b, s_q, h, d = q.shape
    s_kv, h_kv = k.shape[1], k.shape[2]
    g = h // h_kv
    if g > 1 and s_q == s_kv:
        k, v = (
            jnp.broadcast_to(
                x[:, :, :, None, :], (b, s_kv, h_kv, g, d)
            ).reshape(b, s_kv, h, d)
            for x in (k, v)
        )
        g = 1
    scale = scale if scale is not None else d ** -0.5
    if g == 1:
        logits = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
        )
    else:
        # H = Hkv * G is contiguous: the grouped scores fold back to
        # (B, H, S, Skv) for free, where masks and bias live
        logits = jnp.einsum(
            "bqhgd,bkhd->bhgqk", q.reshape(b, s_q, h_kv, g, d), k,
            preferred_element_type=jnp.float32,
        ).reshape(b, h, s_q, s_kv)
    logits = logits * scale
    if softcap is not None:
        # Gemma-2 tanh soft-capping, applied to raw scores BEFORE any
        # masking (transformers modeling_gemma2.py eager_attention_forward)
        logits = softcap * jnp.tanh(logits / softcap)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        cmask = make_causal_mask(s_q, s_kv, window=window)
        logits = jnp.where(cmask[None, None, :, :], logits, jnp.finfo(jnp.float32).min)
    if kv_lengths is not None:
        mask = (
            lengths_to_mask(kv_lengths, s_kv)
            if mask is None
            else jnp.logical_and(mask, lengths_to_mask(kv_lengths, s_kv))
        )
    if mask is not None:
        # mask: broadcastable to (B, H, Q, K); True = attend
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(orig_dtype)
    if g == 1:
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    else:
        out = jnp.einsum(
            "bhgqk,bkhd->bqhgd", probs.reshape(b, h_kv, g, s_q, s_kv), v
        ).reshape(b, s_q, h, d)
    if q_lengths is not None:
        real = lengths_to_mask(q_lengths, s_q)[:, 0, 0]  # (B, S)
        out = jnp.where(real[:, :, None, None], out, 0)
    return out


def flash_self_attention_eligible(seq_len: int) -> bool:
    """Would auto-dispatch pick the flash kernel for self-attention at
    this sequence length — the SHAPE/BACKEND part of the flash_ok
    predicate in :func:`dot_product_attention` (callers must separately
    rule out the flash-incompatible model switches: score soft-capping
    and traced per-layer windows). Models use it to decide whether to
    lower a right-padded attention mask to kv_lengths (flash fast path)
    or keep the exact dense key mask (xla path)."""
    from .flash_attention import DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, fit_block

    return (
        jax.default_backend() == "tpu"
        and seq_len >= 256
        and seq_len % 128 == 0
        and fit_block(seq_len, DEFAULT_BLOCK_Q) is not None
        and fit_block(seq_len, DEFAULT_BLOCK_K) is not None
    )


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    causal: bool = False,
    kv_lengths: Optional[jax.Array] = None,
    implementation: Optional[str] = None,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_lengths: Optional[jax.Array] = None,
) -> jax.Array:
    """Attention entry point, shapes (batch, seq, heads, head_dim).

    ``kv_lengths``: (B,) valid-prefix key lengths — the structured form of
    a right-padding key mask (HF tokenizer convention). Flash and xla both
    honor it; arbitrary (non-prefix) masks take the xla path.

    ``q_lengths``: (B,) how many of each row's queries are real, beside
    ``kv_lengths`` in causal self-attention (a prompt in a padded bucket).
    The rows past it are zeros out of either path, and the flash kernel does
    no work for the q blocks that hold none but them; ring attention takes
    no lengths.

    ``window``: causal sliding-window band (Mistral / sliding Qwen2).
    Supported by the xla and flash paths (the flash kernel additionally
    SKIPS kv blocks entirely below the band — work scales with
    S*window, not S^2); ring attention rejects it (a band crossing ring
    shards would need per-hop bounds — use flash/xla, which at
    window << S is the memory-frugal regime anyway). A TRACED window
    (Gemma-2's per-layer pattern riding the layer scan) routes to xla.

    ``softcap``: Gemma-2 tanh score soft-capping — xla path only (the
    flash online-softmax backward would need the tanh chain threaded
    through both passes).

    ``implementation``: None (auto) | "xla" | "flash" | "ring".
    Auto picks flash on TPU backends for causal or bidirectional
    self-attention with no custom mask/bias tensor (kv_lengths is fine —
    that's the padded-batch fast path), else xla.
    """
    window_static = window is None or isinstance(window, int)
    if q_lengths is not None and not (
            causal and kv_lengths is not None and q.shape[1] == k.shape[1]):
        raise ValueError(
            "q_lengths needs causal self-attention and kv_lengths beside it")
    if implementation is None:
        # trace-time decision: tracers have no .devices(), so the
        # eligibility helper keys off the default backend (correct under
        # jit on the target platform). ONE predicate — models route masks
        # based on flash_self_attention_eligible, so dispatch must agree.
        flash_ok = (
            bias is None and mask is None
            and softcap is None and window_static
            and q.shape[1] == k.shape[1]
            and flash_self_attention_eligible(q.shape[1])
        )
        implementation = "flash" if flash_ok else "xla"
    if implementation == "xla":
        return xla_attention(
            q, k, v, mask=mask, bias=bias, scale=scale, causal=causal,
            kv_lengths=kv_lengths, window=window, softcap=softcap,
            q_lengths=q_lengths,
        )
    if implementation == "flash":
        from .flash_attention import flash_attention

        if mask is not None or bias is not None:
            raise ValueError(
                "flash attention supports no dense mask/bias tensor — pass "
                "right-padding via kv_lengths, or implementation='xla' for "
                "arbitrary masks"
            )
        if softcap is not None or not window_static:
            raise ValueError(
                "flash attention supports neither score soft-capping nor "
                "traced per-layer windows — use implementation='xla'"
            )
        return flash_attention(
            q, k, v, scale=scale, causal=causal, kv_lengths=kv_lengths,
            window=window, q_lengths=q_lengths,
        )
    if implementation == "ring":
        from .ring_attention import ring_attention

        if mask is not None or bias is not None or kv_lengths is not None:
            raise ValueError("ring attention supports no custom mask/bias")
        if window is not None or softcap is not None:
            raise ValueError(
                "ring attention supports neither sliding windows nor score "
                "soft-capping — use implementation='flash' or 'xla' (at "
                "window << seq the flash band-skip already bounds memory "
                "and work)"
            )
        return ring_attention(q, k, v, scale=scale, causal=causal)
    raise ValueError(f"unknown attention implementation {implementation!r}")

"""Decoder-only transformer (Llama/GPT family) — the flagship model.

TPU-native design decisions:

* every parameter is created with ``nn.with_partitioning`` and a *logical*
  axis name (``embed/heads/kv/mlp/vocab/expert``); the mesh mapping lives in
  :mod:`accelerate_tpu.parallel.sharding`, so DP/FSDP/TP/EP are config, not
  model surgery (the reference needs Megatron for TP: utils/megatron_lm.py);
* layers run under ``nn.scan`` — one compiled block body iterated
  ``num_layers`` times, keeping XLA compile time flat in depth;
* optional ``nn.remat`` (activation checkpointing — the reference's FSDP
  ``activation_checkpointing`` flag, utils/dataclasses.py:1173) with
  MXU-friendly ``dots`` policies;
* attention dispatches to XLA / Pallas-flash / ring via
  :mod:`accelerate_tpu.ops.attention`;
* MoE layers (Mixtral family) route with a dense one-hot dispatch einsum
  whose expert dim carries the ``expert`` logical axis (GSPMD all-to-all).
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..ops.attention import (
    dot_product_attention, forward_parts, latent_attention, latent_row_width,
    latent_update, paged_attention, paged_update,
)
from .config import FF_LAYER_TYPES, TransformerConfig
from .config import rope_type as _rope_type

Dtype = Any


def _dtype(config: TransformerConfig) -> Dtype:
    return jnp.dtype(config.dtype)


def count_params(params: Any) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))


# ---------------------------------------------------------------------- #
# building blocks
# ---------------------------------------------------------------------- #
class RMSNorm(nn.Module):
    config: TransformerConfig
    # param_only: declare and RETURN the scale without normalizing — the
    # fused-prologue path (ops/fused.py) applies the norm inside its
    # kernel and only needs the raw scale. Keeps the param at the same
    # tree path either way, so checkpoints interchange with the flag off.
    param_only: bool = False

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        eps = cfg.rms_norm_eps
        # Gemma stores zero-centered scales and multiplies by (1 + w);
        # a zeros init keeps a fresh norm at identity either way
        init = (
            nn.initializers.zeros_init()
            if cfg.norm_offset
            else nn.initializers.ones_init()
        )
        scale = self.param(
            "scale",
            nn.with_partitioning(init, ("norm",)),
            (x.shape[-1],),
            jnp.float32,
        )
        if self.param_only:
            return scale
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
        mult = (1.0 + scale) if cfg.norm_offset else scale
        # a float32 residual stream ends here: what the norm hands on is an
        # operand of the layer's matmuls
        return (y * mult).astype(
            _dtype(cfg) if cfg.fp32_residual else x.dtype)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature ``0.1 * mscale * ln(factor) + 1`` (1 at
    ``factor <= 1``): cos and sin are multiplied by
    ``m(mscale) / m(mscale_all_dim)`` (``m(1)`` where the dict names neither),
    and latent attention multiplies its softmax scale by
    ``m(mscale_all_dim) ** 2``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_cos_sin_factor(scaling: dict) -> float:
    factor = float(scaling["factor"])
    if "mscale" in scaling and "mscale_all_dim" in scaling:
        return (yarn_mscale(factor, float(scaling["mscale"]))
                / yarn_mscale(factor, float(scaling["mscale_all_dim"])))
    return float(scaling.get("attention_factor") or yarn_mscale(factor))


def _scale_rope_freqs(freqs: jax.Array, scaling: Optional[dict],
                      theta: Optional[float] = None) -> jax.Array:
    """Apply HF-style rope frequency scaling to inverse frequencies.

    ``llama3`` mirrors transformers' ``_compute_llama3_parameters``
    (modeling_rope_utils.py): frequencies whose wavelength exceeds the
    original context keep full resolution divided by ``factor``, short
    wavelengths are untouched, and a smooth ramp interpolates between the
    two bands. ``linear`` is plain position-interpolation (freq/factor).
    ``yarn`` (transformers' ``_compute_yarn_parameters``; needs ``theta``):
    over the d/2 frequencies of a d-wide head, ``low`` / ``high`` the indices
    whose wave turns ``beta_fast`` / ``beta_slow`` times over the original
    context, frequency i is divided by ``factor`` by the share ``ramp_i =
    clip((i - low) / (high - low), 0, 1)``: the fast ones pass, the slow ones
    are interpolated.
    The parity anchor is reference utils/modeling.py:1608 — its loader is
    architecture-faithful to whatever rope the checkpoint declares.
    """
    rt = _rope_type(scaling)
    if rt == "default":
        return freqs
    factor = float(scaling["factor"])
    if rt == "linear":
        return freqs / factor
    if rt == "llama3":
        low = float(scaling["low_freq_factor"])
        high = float(scaling["high_freq_factor"])
        old_len = float(scaling["original_max_position_embeddings"])
        wavelen = 2.0 * jnp.pi / freqs
        # smooth ramp between the low/high frequency bands
        smooth = (old_len / wavelen - low) / (high - low)
        smoothed = (1.0 - smooth) * freqs / factor + smooth * freqs
        scaled = jnp.where(wavelen > old_len / low, freqs / factor, freqs)
        is_medium = (wavelen <= old_len / low) & (wavelen >= old_len / high)
        return jnp.where(is_medium, smoothed, scaled)
    if rt == "yarn":
        d = 2 * freqs.shape[0]
        old_len = float(scaling["original_max_position_embeddings"])

        def index_of(turns):  # where a wave turns that often over old_len
            return d * math.log(old_len / (2 * math.pi * turns)) / (
                2 * math.log(theta))

        low = max(math.floor(index_of(float(scaling.get("beta_fast", 32)))), 0)
        high = min(math.ceil(index_of(float(scaling.get("beta_slow", 1)))), d - 1)
        ramp = jnp.clip(
            (jnp.arange(d // 2, dtype=jnp.float32) - low)
            / (high - low if high != low else 0.001), 0.0, 1.0)
        return freqs / factor * ramp + freqs * (1.0 - ramp)
    raise ValueError(f"unsupported rope_scaling type {rt!r}")


def rope(
    x: jax.Array,
    positions: jax.Array,
    theta: float,
    scaling: Optional[dict] = None,
    rotary_dim: Optional[int] = None,
) -> jax.Array:
    """Rotary position embedding, x: (B, S, H, D), positions: (B, S).
    ``rotary_dim``: only the first that many elements of a head turn (with
    frequencies of a head that wide); the rest pass as they are."""
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        turned = rope(x[..., :rotary_dim], positions, theta, scaling)
        return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
    from ..parallel.sharding import live_mesh

    mesh = live_mesh()
    if mesh is not None:
        # The rotation pairs element i with element i + D/2 across the last
        # dim. When the qkv projection's output sharding propagates a
        # head_dim split into here (heuristic FSDP merging heads*head_dim),
        # XLA's SPMD partitioner produces numerically wrong attention
        # downstream of the split/concat (observed ~1e-2 logit divergence
        # vs the same weights replicated; q/k themselves and the attention
        # core are each exact in isolation). Pin head_dim unsplit through
        # the rotation; every other dim stays free for the partitioner.
        from jax.sharding import NamedSharding, PartitionSpec

        spec = PartitionSpec(
            *([PartitionSpec.UNCONSTRAINED] * (x.ndim - 1)), None
        )
        x = jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = _scale_rope_freqs(freqs, scaling, theta)
    angles = positions[:, :, None, None].astype(jnp.float32) * freqs  # (B,S,1,D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if _rope_type(scaling) == "yarn":
        stretch = _yarn_cos_sin_factor(scaling)
        if stretch != 1.0:
            cos, sin = cos * stretch, sin * stretch
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _make_proj(cfg: TransformerConfig, dtype):
    """The shared projection factory: nn.Dense, or Fp8Dense when
    ``cfg.fp8`` (the te.Linear swap, reference utils/transformer_engine.py:36)
    — same param layout either way, so checkpoints interchange. Biases are
    off except where an architecture convention turns them on per-proj
    (``use_bias``/``bias_axis`` — the Qwen2 q/k/v biases)."""

    def proj(name, out_features, axes, use_bias=False, bias_axis=None):
        kernel_init = nn.with_partitioning(nn.initializers.lecun_normal(), axes)
        kw = {}
        if use_bias:
            kw["bias_init"] = nn.with_partitioning(
                nn.initializers.zeros_init(), (bias_axis,)
            )
        if cfg.fp8:
            from ..ops.fp8 import Fp8Dense

            return Fp8Dense(
                out_features, dtype=dtype, param_dtype=jnp.float32,
                kernel_init=kernel_init, use_bias=use_bias, name=name, **kw,
            )
        return nn.Dense(
            out_features,
            use_bias=use_bias,
            dtype=dtype,
            param_dtype=jnp.float32,
            kernel_init=kernel_init,
            name=name,
            **kw,
        )

    return proj


class _ProjParams(nn.Module):
    """Declares exactly nn.Dense's param tree (kernel/bias names, shapes,
    init fns, partitioning, param_dtype) WITHOUT running the matmul — the
    fused prologue (ops/fused.py) consumes the raw arrays instead. Same
    module name => same tree paths AND same per-param init RNG streams,
    so checkpoints and init values interchange with the unfused path."""

    features: int
    axes: tuple
    use_bias: bool = False
    bias_axis: Optional[str] = None

    @nn.compact
    def __call__(self, in_features):
        kernel = self.param(
            "kernel",
            nn.with_partitioning(nn.initializers.lecun_normal(), self.axes),
            (in_features, self.features),
            jnp.float32,
        )
        if hasattr(kernel, "unbox"):
            kernel = kernel.unbox()
        bias = None
        if self.use_bias:
            bias = self.param(
                "bias",
                nn.with_partitioning(
                    nn.initializers.zeros_init(), (self.bias_axis,)
                ),
                (self.features,),
                jnp.float32,
            )
            if hasattr(bias, "unbox"):
                bias = bias.unbox()
        return kernel, bias


def _lora_delta_fn(module: nn.Module, lora, lora_stacks):
    """Per-projection LoRA delta closure for Attention/MLP.

    Returns ``delta(inp, name) -> array | None``: the gathered low-rank
    contribution for projection ``name`` (None when the adapter state
    doesn't target it). Dropout (training only) is applied to the delta's
    INPUT — the standard LoRA placement — via an nn.Dropout owned by the
    calling module, so it needs a "dropout" rng only when actually live.
    """
    if lora is None or lora_stacks is None:
        return lambda inp, name: None
    from ..adapters.runtime import lora_delta

    def delta(inp, name):
        pair = lora_stacks.get(name) if hasattr(lora_stacks, "get") else None
        if pair is None:
            return None
        z = inp
        if lora.dropout_rate > 0.0 and not lora.deterministic:
            z = nn.Dropout(lora.dropout_rate, name=f"lora_drop_{name}")(
                z, deterministic=False
            )
        return lora_delta(z, pair, lora.slot_ids, lora.scales)

    return delta


def qkv_in_place(decode: bool, q_len: int) -> bool:
    """Whether :class:`Attention` pins its three projection outputs
    two-dimensional (an ``optimization_barrier`` before the head split):
    a decode call of ONE position. XLA:TPU otherwise folds the split into
    the dot and asks for the layer's kernel transposed — a copy of every
    q/k/v weight, every layer of every step, for 8-16 rows; pinned, each
    projection reads its kernel out of the stacked parameter where it
    lies, as o_proj does. A wider call amortises the copy over its rows
    (and pinned would transpose activations that grow with them), so
    prefill, verify and training keep the text they had. ONE predicate:
    the serving engine asks it too, for its ``qkv_in_place`` trace count."""
    return decode and q_len == 1


class Attention(nn.Module):
    config: TransformerConfig
    decode: bool = False
    # a layer kind's own, where the stack has kinds that differ in them
    # (``layer_types`` "sliding_attention" beside "full_attention";
    # ``rope_layout``). None: the configuration's one value for the stack
    sliding: Optional[bool] = None
    rope: Optional[bool] = None

    @nn.compact
    def __call__(self, x, positions, mask=None, kv_lengths=None,
                 paged=None, layer_window=None, pre_norm_scale=None,
                 lora=None, lora_stacks=None, layer=None):
        decode = self.decode
        cfg = self.config
        if decode and not cfg.use_rope:
            raise NotImplementedError(
                "use_rope=False: every cache regime rotates q and k by the "
                "slot's position; attention without rope runs the training "
                "and evaluation path only (rope_layout says which layers of "
                "a served stack carry none)"
            )
        use_rope = cfg.use_rope if self.rope is None else self.rope
        delta = _lora_delta_fn(self, lora, lora_stacks)
        if self.sliding is None:
            # static homogeneous band, or the per-layer traced one (Gemma-2)
            window = cfg.sliding_window if layer_window is None else layer_window
        else:
            # the band of this layer's kind: a Python int, or none at all
            window = cfg.sliding_window if self.sliding else None
        # Gemma-2 decouples the attention scale from head_dim
        scale = (
            cfg.query_pre_attn_scalar ** -0.5
            if cfg.query_pre_attn_scalar is not None else None
        )
        dtype = _dtype(cfg)
        q_dim = cfg.num_heads * cfg.head_dim
        kv_dim = cfg.num_kv_heads * cfg.head_dim
        rotary_dim = (
            None if cfg.partial_rotary_factor == 1.0
            else int(cfg.head_dim * cfg.partial_rotary_factor)
        )

        def turn(a, at):
            if not use_rope:  # this attention carries no position
                return a
            return rope(a, at, cfg.rope_theta, cfg.rope_scaling, rotary_dim)

        proj = _make_proj(cfg, dtype)

        b, s = x.shape[:2]
        gate = None
        fused_qkv = False
        if pre_norm_scale is not None:
            # Block handed us the RAW residual stream + the norm scale:
            # the fused-kernels path. Fuse norm -> qkv -> rope when the
            # kernel supports the shape; otherwise apply the norm here
            # (exact RMSNorm math) and fall through unfused.
            from ..ops import fused as fused_ops

            # LoRA on q/k/v has to add its delta to the raw projection
            # outputs, which the fused kernel never materializes — force
            # the exact unfused fallback when any qkv target is adapted
            # (o_proj-only adapters keep the fused prologue)
            lora_on_qkv = lora_stacks is not None and any(
                t in lora_stacks for t in ("q_proj", "k_proj", "v_proj")
            )
            if (
                not self.decode
                and not cfg.fp8
                and not cfg.qk_norm  # the kernel ropes what it projects
                and not lora_on_qkv
                and fused_ops.prologue_supported(
                    cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                    b, s, x.shape[-1],
                )
            ):
                wq, bq = _ProjParams(
                    q_dim, ("embed", "heads"), cfg.qkv_bias, "heads",
                    name="q_proj",
                )(x.shape[-1])
                wk, bk = _ProjParams(
                    kv_dim, ("embed", "kv"), cfg.qkv_bias, "kv",
                    name="k_proj",
                )(x.shape[-1])
                wv, bv = _ProjParams(
                    kv_dim, ("embed", "kv"), cfg.qkv_bias, "kv",
                    name="v_proj",
                )(x.shape[-1])
                q, k, v = fused_ops.fused_qkv_prologue(
                    x, pre_norm_scale, wq, wk, wv, bq, bk, bv, positions,
                    eps=cfg.rms_norm_eps, norm_offset=cfg.norm_offset,
                    num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                    head_dim=cfg.head_dim, theta=cfg.rope_theta,
                    scaling=cfg.rope_scaling, dtype=dtype,
                )
                fused_qkv = True
            else:
                x = fused_ops.rms_norm_reference(
                    x, pre_norm_scale, eps=cfg.rms_norm_eps,
                    norm_offset=cfg.norm_offset,
                )
        if not fused_qkv:
            # with the output gate each head's columns are [q | gate]
            q = proj(
                "q_proj", q_dim * (2 if cfg.attn_output_gate else 1),
                ("embed", "heads"), use_bias=cfg.qkv_bias, bias_axis="heads",
            )(x)
            k = proj(
                "k_proj", kv_dim, ("embed", "kv"),
                use_bias=cfg.qkv_bias, bias_axis="kv",
            )(x)
            v = proj(
                "v_proj", kv_dim, ("embed", "kv"),
                use_bias=cfg.qkv_bias, bias_axis="kv",
            )(x)
            dq = delta(x, "q_proj")
            if dq is not None:
                q = q + dq
            dk = delta(x, "k_proj")
            if dk is not None:
                k = k + dk
            dv = delta(x, "v_proj")
            if dv is not None:
                v = v + dv
            if qkv_in_place(self.decode, s):
                q, k, v = jax.lax.optimization_barrier((q, k, v))
            if cfg.attn_output_gate:
                q, gate = jnp.split(
                    q.reshape(b, s, cfg.num_heads, 2 * cfg.head_dim), 2, axis=-1)
                gate = gate.reshape(b, s, q_dim)
            q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
            k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
            v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
            if cfg.qk_norm:
                # per-head RMSNorm over head_dim, one weight vector each,
                # BEFORE rope (every cache regime below ropes after this)
                with jax.named_scope("qk_norm"):
                    q = RMSNorm(cfg, name="q_norm")(q)
                    k = RMSNorm(cfg, name="k_norm")(k)

        eva = cfg.attention_class == "eva"
        if eva:
            # one learned vector a KV head for each of a chunk's two
            # summaries (ops/eva_attention.py). Unit normal: with unit-
            # variance keys a chunk's softmax logits then spread by about 1
            if mask is not None or kv_lengths is not None:
                raise NotImplementedError(
                    "attention_class 'eva' cuts windows and chunks by a "
                    "token's index in the call: no mask or kv_lengths"
                )
            mu, phi = (
                self.param(
                    name,
                    nn.with_partitioning(
                        nn.initializers.normal(1.0), (None, None)),
                    (cfg.num_kv_heads, cfg.head_dim), jnp.float32,
                )
                for name in ("mu", "phi")
            )
            mu, phi = (
                p.unbox() if hasattr(p, "unbox") else p for p in (mu, phi))

        use_paged = False
        if decode and paged is not None:
            # Paged decode (vLLM block tables, static-shape XLA form): the
            # K/V pools are cache variables with NO batch dim — every slot
            # and every prefill call shares ONE pool pytree, routed through
            # the per-call block tables in ``paged`` (ops/attention.py's
            # PagedKVState). The has_variable guard keeps the init pass on
            # the plain path (creation must not write). In a scanned stack
            # the live pools are the loop's carry (_apply_layer_stack):
            # the variables then hold every layer's pool, (L, num_blocks,
            # ...), and ``layer`` is this one's row — the three operations
            # below address that row inside the stack, never a copy of it.
            # int8 paged KV: pools store sym-quantized rows, one fp32
            # amax scale per token slot beside them ((num_blocks,
            # block_size) — ~4 bytes/token overhead vs the 2x row
            # shrink). kv_dtype is static PagedKVState metadata, so the
            # branch resolves at trace time: one engine, one lattice.
            kv_int8 = getattr(paged, "kv_dtype", "native") == "int8"
            pool_dtype = jnp.int8 if kv_int8 else k.dtype
            # a sliding layer beside full ones holds a RING a slot, in pools
            # of its own name and size (ops.attention.PagedKVState.ring)
            ring = bool(self.sliding) and paged.ring > 0
            key_name, value_name, pool_blocks = (
                ("key_ring", "value_ring", paged.ring_blocks) if ring
                else ("key_pool", "value_pool", paged.num_blocks))
            is_initialized = self.has_variable("cache", key_name)
            # a block's rows: one a position and KV head, or (the state
            # says so: ops.attention.pool_heads_first) each head's together
            a_block = (
                (cfg.num_kv_heads, paged.block_size) if paged.heads_first
                else (paged.block_size, cfg.num_kv_heads)
            )
            if paged.heads_first and eva:
                raise NotImplementedError(
                    "attention_class 'eva' over pools stored heads first")
            key_pool = self.variable(
                "cache", key_name,
                lambda: jnp.zeros(
                    (pool_blocks, *a_block, cfg.head_dim), pool_dtype),
            )
            value_pool = self.variable(
                "cache", value_name,
                lambda: jnp.zeros(
                    (pool_blocks, *a_block, cfg.head_dim), pool_dtype),
            )
            key_scale = value_scale = None
            if kv_int8:
                key_scale = self.variable(
                    "cache", "key_scale",
                    lambda: jnp.zeros(
                        (paged.num_blocks, paged.block_size), jnp.float32
                    ),
                )
                value_scale = self.variable(
                    "cache", "value_scale",
                    lambda: jnp.zeros(
                        (paged.num_blocks, paged.block_size), jnp.float32
                    ),
                )
            use_paged = is_initialized
            decode = False
        elif decode and eva:
            raise NotImplementedError(
                "attention_class 'eva' keeps chunk summaries beside a window "
                "of rows: the dense decode cache (models/generation.py) holds "
                "one row a position; serve it through ServingEngine's paged "
                "cache"
            )
        elif decode:
            # KV-cache decode (flax decode-cache pattern): a fixed-size
            # per-layer cache collection, updated in place at cache_index.
            # Static shapes throughout — XLA-friendly autoregression.
            # The has_variable guard keeps the init pass from running the
            # update body (it would advance cache_index on creation).
            max_len = cfg.max_seq_len
            is_initialized = self.has_variable("cache", "cached_key")
            cached_key = self.variable(
                "cache", "cached_key",
                lambda: jnp.zeros((b, max_len, cfg.num_kv_heads, cfg.head_dim), k.dtype),
            )
            cached_value = self.variable(
                "cache", "cached_value",
                lambda: jnp.zeros((b, max_len, cfg.num_kv_heads, cfg.head_dim), v.dtype),
            )
            cache_index = self.variable(
                "cache", "cache_index", lambda: jnp.asarray(0, jnp.int32)
            )
            decode = is_initialized
        if use_paged and eva:
            # the fourth cache regime: a slot at position n holds
            # EvaLayout.rows(n) rows, summaries first. ``cache_len`` is that
            # row count (write offset, last visible row) and the position
            # rope turns by travels beside it.
            from ..ops.eva_attention import eva_attention, eva_prefill_write

            if kv_int8:
                raise NotImplementedError(
                    "attention_class 'eva' over int8 KV pools")
            # no positions beside the rows: a call that starts its slot's
            # cache from position 0 (the engine refuses what would make a
            # prefill a continuation)
            fresh = paged.positions is None
            start = 0 if fresh else paged.positions[:, None]
            positions = jnp.broadcast_to(start + jnp.arange(s)[None, :], (b, s))
            q = turn(q, positions)
            k = turn(k, positions)
            if fresh:
                # prefill of a padded bucket from position 0: attends what
                # it projected, and leaves in the pool only the completed
                # windows' summaries and the last window's rows
                out, kt, vt = eva_attention(
                    q, k, v, mu, phi, chunk=cfg.chunk_size,
                    window=cfg.window_size, scale=scale,
                    kernel=paged.single_device,
                )
                new_k, new_v = eva_prefill_write(
                    key_pool.value, value_pool.value, k, v, kt, vt, paged,
                    chunk=cfg.chunk_size, window=cfg.window_size, layer=layer,
                )
            elif s != 1:
                raise NotImplementedError(
                    "attention_class 'eva': a call of several tokens onto an "
                    "existing cache (chunked or prefix-cached prefill, "
                    "speculative verification) may cross a window boundary "
                    "and is not written"
                )
            else:
                # decode: one row written at the slot's next row, one
                # softmax over its summaries and window rows
                new_k, new_v = paged_update(
                    key_pool.value, value_pool.value, k, v, paged, layer=layer)
                out = paged_attention(
                    q, new_k, new_v, paged, scale=scale, layer=layer)
            key_pool.value = new_k
            value_pool.value = new_v
        elif use_paged:
            # per-slot positions: slot b's token i sits at global position
            # cache_len[b] + i (heterogeneous across the batch — the dense
            # path's single scalar index cannot express a decode batch
            # whose members are at different depths)
            positions = paged.cache_len[:, None] + jnp.arange(s)[None, :]
            q = turn(q, positions)
            k = turn(k, positions)
            new_ks = new_vs = None
            # where layer kinds hold caches of two sizes, each kind's cache
            # write and read stand under its own scope in a device trace
            with (contextlib.nullcontext() if self.sliding is None
                  else jax.named_scope("window" if self.sliding else "full")):
                if kv_int8:
                    new_k, new_v, new_ks, new_vs = paged_update(
                        key_pool.value, value_pool.value, k, v, paged,
                        key_scale=key_scale.value,
                        value_scale=value_scale.value, layer=layer,
                    )
                    key_scale.value = new_ks
                    value_scale.value = new_vs
                else:
                    new_k, new_v = paged_update(
                        key_pool.value, value_pool.value, k, v, paged,
                        layer=layer, ring=ring,
                    )
                key_pool.value = new_k
                value_pool.value = new_v
                if paged.fresh:
                    # a prefill from position 0 (a stack with recurrent
                    # layers, or rings, never continues a cache by several
                    # tokens): what it projected is all there is to see, so
                    # the table is written and not gathered — flash over the
                    # prompt's real rows: the bucket's padded tail lies after
                    # them, is seen by none, and comes out as zeros that cost
                    # no work
                    out = dot_product_attention(
                        q, k, v, causal=True, scale=scale,
                        kv_lengths=paged.lengths, q_lengths=paged.lengths,
                        softcap=cfg.attn_softcap,
                        implementation=cfg.attention_impl, window=window,
                    )
                else:
                    # a ring holds what the band allows and no more: no band
                    out = paged_attention(
                        q, new_k, new_v, paged, scale=scale,
                        softcap=cfg.attn_softcap,
                        window=None if ring else window,
                        key_scale=new_ks, value_scale=new_vs, layer=layer,
                        ring=ring,
                    )
        elif decode:
            idx = cache_index.value
            positions = idx + jnp.arange(s)[None, :]  # (1, s) broadcasts over batch
            q = turn(q, positions)
            k = turn(k, positions)
            key_cache = jax.lax.dynamic_update_slice(
                cached_key.value, k, (0, idx, 0, 0)
            )
            value_cache = jax.lax.dynamic_update_slice(
                cached_value.value, v, (0, idx, 0, 0)
            )
            cached_key.value = key_cache
            cached_value.value = value_cache
            cache_index.value = idx + s
            # attend over the full cache, masking positions not yet written:
            # col j visible to query i (global pos idx+i) iff j <= idx+i —
            # and, under a sliding window, iff j > idx+i - window (rows
            # are GLOBAL positions, so the band is anchored at the true
            # decode position, not the cache buffer's end)
            cols = jnp.arange(max_len)[None, None, None, :]
            rows = (idx + jnp.arange(s))[None, None, :, None]
            dec_mask = cols <= rows  # (1,1,s,max_len)
            if window is not None:
                dec_mask = jnp.logical_and(dec_mask, cols > rows - window)
            out = dot_product_attention(
                q, key_cache, value_cache, mask=dec_mask, causal=False,
                scale=scale, softcap=cfg.attn_softcap,
                implementation="xla",
            )
        elif eva:
            from ..ops.eva_attention import eva_attention

            q = turn(q, positions)
            k = turn(k, positions)
            out, _, _ = eva_attention(
                q, k, v, mu, phi, chunk=cfg.chunk_size,
                window=cfg.window_size, scale=scale,
            )
        else:
            # the fused prologue already applied rope
            if not fused_qkv:
                q = turn(q, positions)
                k = turn(k, positions)
            out = dot_product_attention(
                q, k, v, mask=mask, causal=cfg.causal,
                kv_lengths=kv_lengths,
                scale=scale, softcap=cfg.attn_softcap,
                implementation=cfg.attention_impl,
                window=window,
            )
        # named residual: the "save_attn" remat policy keeps exactly these,
        # so backward never recomputes the attention kernel
        out = checkpoint_name(out, "attn_out")
        out = out.reshape(b, s, q_dim)
        if gate is not None:
            with jax.named_scope("gate"):
                out = out * jax.nn.sigmoid(gate)
        y = proj("o_proj", cfg.hidden_size, ("heads", "embed"))(out)
        do = delta(out, "o_proj")
        if do is not None:
            y = y + do
        return y


class LatentAttention(nn.Module):
    """Multi-head latent attention (``config.kv_lora_rank``; DeepSeek-V2/V3).

    ``c_q = norm(x W_DQ)``; ``q = c_q W_UQ`` as ``num_heads`` heads of
    ``[q_nope | q_rope]``, ``q_rope`` rotated. ``[c_kv | k_r] = x W_DKV``;
    ``c_kv = norm(c_kv)``, ``k_rope = rope(k_r)``, ONE for all heads: the
    latent row ``[c_kv | k_rope]`` is all a position keeps. ``kv_b_proj``
    (``W_UKV``, ONE leaf) gives head h its ``[k_nope | v]`` from ``c_kv``:

        score_h(t, s) = (q_nope_h(t) . k_nope_h(s) + q_rope_h(t) . k_rope(s)) * scale

    causal softmax in float32, ``o_h = sum_s P v_h(s)``, ``o_proj`` over the
    heads' outputs. ``scale`` is ``head_dim ** -0.5`` times YaRN's
    ``m(mscale_all_dim) ** 2`` where the rope scaling names one.

    Two forms of the one layer, on the same parameters:

    * **expanded** — a call that sees only what it projects: training and
      evaluation, and a serving PREFILL (``paged.fresh``). Every position's
      latent goes through ``kv_b_proj`` and attention runs over per-head keys
      ``head_dim`` wide and values ``v_head_dim`` wide (flash where the
      dispatch takes it). A serving call whose per-head q, k, v would pass
      ``ops.attention.FORWARD_PART_BYTES`` walks the heads in groups.
    * **absorbed** — a call against the paged latent cache (a decode step;
      the gather form takes any length): ``W_UK`` goes into the query,
      ``(q_nope_h W_UK^h^T) . c_kv(s)``, and ``W_UV`` behind the softmax,
      ``(sum_s P c_kv(s)) W_UV^h`` — equal to the above by associativity —,
      so a cached position is read once, as its latent row, for all heads
      (``ops.attention.latent_attention``; one position a slot: the
      ``latent_decode`` kernel). ``W_UK`` / ``W_UV`` are ``kv_b_proj``'s
      halves, read out of the one leaf.

    Both write the cache the same way: the latent normed BEFORE it is cached,
    ``k_rope`` rotated BEFORE it is cached (``ops.attention.latent_update``:
    variable ``latent_pool``, one row a position shared by all heads, its
    width on whole lanes; nothing a head is kept). The dense decode cache of
    ``models/generation.py`` is not written for it."""

    config: TransformerConfig
    decode: bool = False

    @nn.compact
    def __call__(self, x, positions, mask=None, kv_lengths=None, paged=None,
                 layer=None):
        cfg = self.config
        dtype = _dtype(cfg)
        heads, rank = cfg.num_heads, cfg.kv_lora_rank
        nope, rot, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        b, s = x.shape[:2]
        scale = (nope + rot) ** -0.5
        scaling = cfg.rope_scaling
        if _rope_type(scaling) == "yarn" and scaling.get("mscale_all_dim"):
            scale *= yarn_mscale(
                float(scaling["factor"]), float(scaling["mscale_all_dim"])) ** 2
        if self.decode and paged is None:
            raise NotImplementedError(
                "latent attention keeps one latent row a position: the dense "
                "decode cache (models/generation.py) holds per-head keys and "
                "values; serve it through ServingEngine's paged cache")
        use_paged = self.decode and self.has_variable("cache", "latent_pool")
        if self.decode:
            width = latent_row_width(rank, rot)
            pool = self.variable(
                "cache", "latent_pool", lambda: jnp.zeros(
                    (paged.num_blocks, paged.block_size, width), dtype))
        if use_paged:
            positions = paged.cache_len[:, None] + jnp.arange(s)[None, :]

        def turn(a):
            return rope(a, positions, cfg.rope_theta, scaling)

        proj = _make_proj(cfg, dtype)
        c_q = RMSNorm(cfg, name="q_a_norm")(
            proj("q_a_proj", cfg.q_lora_rank, ("embed", None))(x))
        down = proj("kv_a_proj", rank + rot, ("embed", None))(x)
        c_kv = RMSNorm(cfg, name="kv_a_norm")(down[..., :rank])
        with jax.named_scope("k_rope"):
            k_rope = turn(down[..., None, rank:])  # (b, s, 1, rot)
        w_uq, _ = _ProjParams(
            heads * (nope + rot), (None, "heads"), name="q_b_proj")(
                cfg.q_lora_rank)
        w_ukv, _ = _ProjParams(
            heads * (nope + dv), (None, "heads"), name="kv_b_proj")(rank)
        w_uq, w_ukv = w_uq.astype(dtype), w_ukv.astype(dtype)

        def queries(w):  # (q_lora_rank, n * (nope + rot)) -> the n heads' q
            with jax.named_scope("q_up"):
                q = jnp.dot(c_q, w)
                if qkv_in_place(self.decode, s):
                    q = jax.lax.optimization_barrier(q)
                q = q.reshape(b, s, -1, nope + rot)
                return q[..., :nope], turn(q[..., nope:])

        if use_paged:
            pool.value = latent_update(
                pool.value,
                jnp.concatenate([c_kv, k_rope[:, :, 0]], axis=-1), paged,
                layer=layer)
        if use_paged and not paged.fresh:
            w_ukv = w_ukv.reshape(rank, heads, nope + dv)
            q_nope, q_rope = queries(w_uq)
            with jax.named_scope("absorb_k"):
                q_lat = jnp.einsum("bshd,chd->bshc", q_nope, w_ukv[..., :nope])
            o_lat = latent_attention(
                jnp.concatenate([q_lat, q_rope], axis=-1), pool.value, paged,
                value_width=rank, scale=scale, layer=layer)
            with jax.named_scope("absorb_v"):
                out = jnp.einsum("bshc,chd->bshd", o_lat, w_ukv[..., nope:])
        else:
            def expanded(w_q, w_kv):
                q_nope, q_rope = queries(w_q)
                with jax.named_scope("kv_up"):
                    kv = jnp.dot(c_kv, w_kv).reshape(b, s, -1, nope + dv)
                    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
                        k_rope, q_rope.shape)], axis=-1)
                # a prefill says how many rows of its bucket are real: the
                # rest come out as zeros that cost no work
                real = paged.lengths if use_paged else None
                return dot_product_attention(
                    jnp.concatenate([q_nope, q_rope], axis=-1), k,
                    kv[..., nope:], mask=mask, causal=True,
                    kv_lengths=kv_lengths if real is None else real,
                    q_lengths=real, scale=scale,
                    implementation=cfg.attention_impl)

            # per-head q, k, the up-projection's output, v and the result
            groups = forward_parts(
                b * s * heads * (2 * (nope + rot) + (nope + dv) + 2 * dv)
                * dtype.itemsize, heads) if self.decode else 1
            if groups == 1:
                out = expanded(w_uq, w_ukv)
            else:
                def of_groups(w):  # (rank, heads * d) -> (groups, rank, .)
                    return jnp.moveaxis(w.reshape(w.shape[0], groups, -1), 1, 0)

                out = jax.lax.map(
                    lambda w: expanded(*w), (of_groups(w_uq), of_groups(w_ukv)))
                out = jnp.moveaxis(out, 0, 2)  # (b, s, groups, heads a group, dv)
        out = checkpoint_name(out.reshape(b, s, heads * dv), "attn_out")
        return proj("o_proj", cfg.hidden_size, ("heads", "embed"))(out)


class _CausalDepthwiseConv(nn.Module):
    """c_t = sum_j kernel[j] * z_{t-(L-1)+j}, z_{<0} = 0: one filter of
    ``length`` taps per channel, no activation; ``use_bias`` adds one bias a
    channel. Shifted multiply-adds — at L = 3 or 4 XLA fuses them into one
    pass over z."""

    length: int
    use_bias: bool = False

    @nn.compact
    def __call__(self, z):
        kernel = self.param(
            "kernel",
            nn.with_partitioning(
                nn.initializers.variance_scaling(1.0, "fan_in", "normal",
                                                 in_axis=0, out_axis=1),
                (None, "embed"),
            ),
            (self.length, z.shape[-1]),
            jnp.float32,
        )
        if hasattr(kernel, "unbox"):
            kernel = kernel.unbox()
        kernel = kernel.astype(z.dtype)
        s = z.shape[1]
        padded = jnp.pad(z, ((0, 0), (self.length - 1, 0), (0, 0)))
        out = sum(
            kernel[j] * jax.lax.slice_in_dim(padded, j, j + s, axis=1)
            for j in range(self.length)
        )
        if self.use_bias:
            bias = self.param(
                "bias",
                nn.with_partitioning(nn.initializers.zeros_init(), ("embed",)),
                (z.shape[-1],),
                jnp.float32,
            )
            if hasattr(bias, "unbox"):
                bias = bias.unbox()
            out = out + bias.astype(z.dtype)
        return out


class ShortConv(nn.Module):
    """The gated short convolution that stands where attention would:
    ``in_proj`` to 3 x hidden, split in this order into B, C, X;
    ``out_proj(C * conv(B * X))`` with a depthwise causal convolution of
    ``conv_L_cache`` taps over the sequence. No state is carried between
    calls: the training and evaluation path only (its decode state beside
    a KV cache is ROADMAP Reach A4)."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        proj = _make_proj(cfg, _dtype(cfg))
        h = cfg.hidden_size
        u = proj("in_proj", 3 * h, ("embed", "mlp"))(x)
        b_gate, c_gate, xs = jnp.split(u, 3, axis=-1)
        with jax.named_scope("gate"):
            z = b_gate * xs
        c = _CausalDepthwiseConv(cfg.conv_L_cache, name="conv1d")(z)
        with jax.named_scope("gate"):
            y = c_gate * c
        return proj("out_proj", h, ("mlp", "embed"))(y)


def _inverse_softplus_of_log_uniform(lo: float, hi: float, floor: float):
    """The Mamba family's ``dt_bias`` initialiser: softplus(bias) is
    log-uniform in [lo, hi], floored."""
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                     * (np.log(hi) - np.log(lo)) + np.log(lo))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    return init


class Mamba2(nn.Module):
    """The Mamba-2 operator (``nemotron_h``'s ``M`` layers), heads H of P
    channels (width d = H P), G groups of state size N:

        [z | xBC | dt] = in_proj(u)            d + (d + 2 G N) + H, no bias
        xBC <- silu(conv1d(xBC))               depthwise, causal, with bias
        [x | B | C]   = split(xBC)             d, G N, G N; head h reads
                                               group h // (H / G)
        delta = softplus(dt + dt_bias);  A = -exp(A_log)       float32
        H_t = exp(delta_t A) H_{t-1} + delta_t x_t B_t^T;  y_t = H_t C_t + D x_t
        out_proj(GroupRMSNorm(y * silu(z)))    the gate BEFORE the norm,
                                               G groups of d / G, one weight

    The recurrence starts from zero at the start of every row and runs in
    chunks (``ops.ssd.ssd_chunked``). No state is carried between calls: the
    training and evaluation path only. ``init`` draws ``A_log`` = log U(1, 16),
    ``D`` = 1 and ``dt_bias`` as the family does (softplus(dt_bias) log-uniform
    in [1e-3, 0.1], floored at 1e-4). Sown under ``intermediates``:
    ``ssm_delta_mean``, ``ssm_chunk_decay_min`` (the scan's underflow
    gauge, ``ops.ssd.chunk_decay_min``) and ``ssm_scan_kernel`` (1.0 where
    the scan lowered to the kernels, as ``ops.ssd.ssd_chunked`` says of the
    call itself; 0.0 where to the ``jax.numpy`` form)."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, u):
        from ..ops.ssd import chunk_decay_min, ssd_chunked

        cfg = self.config
        dtype = _dtype(cfg)
        proj = _make_proj(cfg, dtype)
        heads, p = cfg.mamba_num_heads, cfg.mamba_head_dim
        groups, n = cfg.mamba_n_groups, cfg.mamba_state_size
        d = heads * p
        bsz, s = u.shape[:2]

        def vector(name, init, size=heads):
            v = self.param(
                name, nn.with_partitioning(init, (None,)), (size,), jnp.float32)
            return v.unbox() if hasattr(v, "unbox") else v

        zxbcdt = proj("in_proj", 2 * d + 2 * groups * n + heads, ("embed", "mlp"))(u)
        z, xbc, dt = jnp.split(zxbcdt, [d, 2 * d + 2 * groups * n], axis=-1)
        xbc = nn.silu(_CausalDepthwiseConv(
            cfg.mamba_conv_kernel, use_bias=True, name="conv1d")(xbc))
        x, b_mat, c_mat = jnp.split(xbc, [d, d + groups * n], axis=-1)
        x = x.reshape(bsz, s, heads, p)
        dt_bias = vector("dt_bias", _inverse_softplus_of_log_uniform(1e-3, 0.1, 1e-4))
        a_log = vector("A_log", lambda key, shape, dtype: jnp.log(
            jax.random.uniform(key, shape, dtype, 1.0, 16.0)))
        skip = vector("D", nn.initializers.ones_init())
        with jax.named_scope("scan"):
            delta = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            a = -jnp.exp(a_log)
            y, kernels = ssd_chunked(
                x, delta, a, b_mat.reshape(bsz, s, groups, n),
                c_mat.reshape(bsz, s, groups, n), cfg.mamba_chunk_size,
                skip=skip, with_form=True)
        self.sow("intermediates", "ssm_delta_mean", jnp.mean(delta))
        self.sow("intermediates", "ssm_chunk_decay_min",
                 chunk_decay_min(delta, a, cfg.mamba_chunk_size))
        self.sow("intermediates", "ssm_scan_kernel", jnp.float32(kernels))
        with jax.named_scope("gate_norm"):
            scale = vector("norm", nn.initializers.ones_init(), d)
            g = (y.reshape(bsz, s, d) * nn.silu(z)).astype(jnp.float32)
            g = g.reshape(bsz, s, groups, d // groups)
            g = g * jax.lax.rsqrt(
                jnp.mean(jnp.square(g), axis=-1, keepdims=True) + cfg.rms_norm_eps)
            y = (g.reshape(bsz, s, d) * scale).astype(dtype)
        return proj("out_proj", cfg.hidden_size, ("mlp", "embed"))(y)


class GatedDeltaNet(nn.Module):
    """The Gated DeltaNet operator (``layer_types`` "linear_attention"), Hk
    query/key heads of Dk and Hv value heads of Dv (key head j // (Hv / Hk)
    serves value head j):

        [q | k | v | z] = in_proj_qkvz(x)       Hk Dk, Hk Dk, Hv Dv, Hv Dv
        [b | a]         = in_proj_ba(x)         Hv, Hv
        [q | k | v]    <- silu(conv1d([q | k | v]))   depthwise, causal, no bias
        beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)    float32
        o_t = the gated delta rule over (q, k, v, g, beta)  ops/gated_delta.py
        out_proj(o / rms(o) * norm * silu(z))   a head, plain weight

    The column layout of the two fused projections is head-contiguous blocks
    in the order written (a published checkpoint interleaves them by key
    head: a permutation of columns). Without a cache the recurrence starts
    from zero at the start of every row (training, evaluation). Under the
    serving engine's paged cache (``paged``: ops/attention.PagedKVState) a
    slot carries each value head's float32 state and the convolution's last
    taps beside the KV pools, ``cache`` variables ``state`` (num_slots, Hv,
    Dk, Dv) and ``taps`` (num_slots, taps - 1, width of [q | k | v]), written
    in place: a ``fresh`` call (a prefill) starts from zero, runs the chunked
    form and leaves in slot ``paged.slot`` the state and taps after the
    row's last real position; a call of one position (a decode step) runs
    the one-position form over every slot and leaves a row whose
    ``paged.lengths`` is 0 exactly as it was. Several positions onto an
    existing state (chunked prefill, speculative verification) are not
    written."""

    config: TransformerConfig
    decode: bool = False

    @nn.compact
    def __call__(self, x, paged=None, layer=None):
        from ..ops.gated_delta import gated_delta_chunked, gated_delta_step

        cfg = self.config
        dtype = _dtype(cfg)
        proj = _make_proj(cfg, dtype)
        hk, hv = cfg.gdn_num_k_heads, cfg.gdn_num_v_heads
        dk, dv = cfg.gdn_head_k_dim, cfg.gdn_head_v_dim
        key_dim, value_dim = hk * dk, hv * dv
        conv_dim, taps = 2 * key_dim + value_dim, cfg.gdn_conv_kernel - 1
        b, s = x.shape[:2]

        def vector(name, init, size=hv):
            v = self.param(
                name, nn.with_partitioning(init, (None,)), (size,), jnp.float32)
            return v.unbox() if hasattr(v, "unbox") else v

        with jax.named_scope("proj"):
            qkvz = proj("in_proj_qkvz", conv_dim + value_dim, ("embed", "mlp"))(x)
            ba = proj("in_proj_ba", 2 * hv, ("embed", None))(x)
        mixed, z = jnp.split(qkvz, [conv_dim], axis=-1)
        kernel = self.param(
            "conv1d",
            nn.with_partitioning(
                nn.initializers.variance_scaling(
                    1.0, "fan_in", "normal", in_axis=0, out_axis=1),
                (None, "mlp")),
            (taps + 1, conv_dim), jnp.float32)
        kernel = (kernel.unbox() if hasattr(kernel, "unbox") else kernel).astype(dtype)
        a_log = vector("A_log", lambda key, shape, dt: jnp.log(
            jax.random.uniform(key, shape, dt, 1e-3, 16.0)))
        dt_bias = vector("dt_bias", nn.initializers.ones_init())
        b_raw, a_raw = jnp.split(ba.astype(jnp.float32), 2, axis=-1)
        beta = jax.nn.sigmoid(b_raw)
        g = -jnp.exp(a_log) * jax.nn.softplus(a_raw + dt_bias)

        cached = False
        if self.decode:
            if paged is None or not paged.num_slots:
                raise NotImplementedError(
                    "a 'linear_attention' layer carries its state a serving "
                    "slot: the dense decode cache (models/generation.py) has "
                    "no place for it; serve it through ServingEngine"
                )
            cached = self.has_variable("cache", "state")
            state = self.variable(
                "cache", "state",
                lambda: jnp.zeros((paged.num_slots, hv, dk, dv), jnp.float32))
            tail = self.variable(
                "cache", "taps",
                lambda: jnp.zeros((paged.num_slots, taps, conv_dim), dtype))

        def rows(var):
            """This layer's (num_slots, ...) rows of a cache leaf: the leaf
            itself, or (``layer``) its row of a scanned segment's stack."""
            if layer is None:
                return var.value
            return jax.lax.dynamic_index_in_dim(var.value, layer, keepdims=False)

        def put(var, new, slot):
            """``new`` (n, ...) written over the rows from ``slot`` on, in
            place: the leaf, stacked or not, stays ONE buffer."""
            start = (slot,) + (0,) * (new.ndim - 1)
            if layer is not None:
                new, start = new[None], (layer,) + start
            var.value = jax.lax.dynamic_update_slice(
                var.value, new.astype(var.value.dtype), start)

        def conv(window):
            """``window`` (B, taps + S, C) -> silu of the causal convolution
            at its last S positions."""
            n = window.shape[1] - taps
            out = sum(kernel[j] * jax.lax.slice_in_dim(window, j, j + n, axis=1)
                      for j in range(taps + 1))
            return nn.silu(out)

        def heads(u):
            q, k, v = jnp.split(u, [key_dim, 2 * key_dim], axis=-1)
            return (q.reshape(*u.shape[:-1], hk, dk), k.reshape(*u.shape[:-1], hk, dk),
                    v.reshape(*u.shape[:-1], hv, dv))

        if cached and not paged.fresh:
            if s != 1:
                raise NotImplementedError(
                    "a 'linear_attention' layer: a call of several tokens onto "
                    "an existing state (chunked or prefix-cached prefill, "
                    "speculative verification) is not written"
                )
            old_tail, old_state = rows(tail), rows(state)
            live = paged.lengths > 0
            with jax.named_scope("conv"):
                window = jnp.concatenate([old_tail, mixed], axis=1)
                q, k, v = heads(conv(window)[:, 0])
                put(tail, jnp.where(live[:, None, None], window[:, 1:], old_tail), 0)
            with jax.named_scope("step"):
                o, new_state = gated_delta_step(
                    q, k, v, g[:, 0], beta[:, 0], old_state)
                put(state, jnp.where(
                    live[:, None, None, None], new_state, old_state), 0)
            o = o[:, None]
        else:
            lengths = paged.lengths if cached else None
            with jax.named_scope("conv"):
                window = jnp.pad(mixed, ((0, 0), (taps, 0), (0, 0)))
                q, k, v = heads(conv(window))
            with jax.named_scope("scan"):
                o, new_state = gated_delta_chunked(q, k, v, g, beta, lengths)
            if cached:
                if b != 1:
                    raise NotImplementedError(
                        "a prefill fills ONE slot's state a call")
                with jax.named_scope("conv"):
                    # the last taps inputs before ``lengths`` (zeros before
                    # the row's start): the padded window from lengths on
                    put(tail, jax.lax.dynamic_slice_in_dim(
                        window[0], paged.lengths[0], taps, axis=0)[None],
                        paged.slot[0])
                with jax.named_scope("scan"):
                    put(state, new_state, paged.slot[0])
        with jax.named_scope("gate_norm"):
            scale = vector("norm", nn.initializers.ones_init(), dv)
            o = o * jax.lax.rsqrt(
                jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.rms_norm_eps)
            zf = z.reshape(b, s, hv, dv).astype(jnp.float32)
            y = (o * scale * nn.silu(zf)).astype(dtype).reshape(b, s, value_dim)
        return proj("out_proj", cfg.hidden_size, ("mlp", "embed"))(y)


def _mlp_activation(cfg: TransformerConfig):
    return {
        "silu": nn.silu,
        "gelu_tanh": lambda z: nn.gelu(z, approximate=True),  # Gemma
        "relu2": lambda z: jnp.square(nn.relu(z)),  # Nemotron-H
        "relu": nn.relu,  # the ReGLU gate (SmallThinker)
    }[cfg.mlp_activation]


class MLP(nn.Module):
    """SwiGLU feed-forward (Llama family); ``mlp_gated`` off: down(act(up x)),
    no gate matrix. ``width``: the shared expert's own (None: the config's
    ``intermediate_size``)."""

    config: TransformerConfig
    width: Optional[int] = None

    @nn.compact
    def __call__(self, x, lora=None, lora_stacks=None):
        cfg = self.config
        dtype = _dtype(cfg)
        proj = _make_proj(cfg, dtype)
        delta = _lora_delta_fn(self, lora, lora_stacks)
        width = self.width or cfg.intermediate_size
        if not cfg.mlp_gated:
            if lora_stacks:
                raise NotImplementedError(
                    "mlp_gated=False: adapters on a feed-forward without a "
                    "gate matrix are not written")
            up = proj("up_proj", width, ("embed", "mlp"))(x)
            mid = _mlp_activation(cfg)(checkpoint_name(up, "mlp_up_out"))
            return proj("down_proj", cfg.hidden_size, ("mlp", "embed"))(mid)

        # named so the "save_mlp" remat policy can keep exactly these two
        # f-wide activations (the expensive recompute in backward) while
        # everything else recomputes — the long-context middle ground
        # between "full" (recomputes all matmuls) and "dots" (saves every
        # matmul output, OOM at S=8192 on 16G)
        gate = proj("gate_proj", width, ("embed", "mlp"))(x)
        dg = delta(x, "gate_proj")
        if dg is not None:
            gate = gate + dg
        gate = checkpoint_name(gate, "mlp_gate_out")
        up = proj("up_proj", width, ("embed", "mlp"))(x)
        du = delta(x, "up_proj")
        if du is not None:
            up = up + du
        up = checkpoint_name(up, "mlp_up_out")
        mid = _mlp_activation(cfg)(gate) * up
        y = proj("down_proj", cfg.hidden_size, ("mlp", "embed"))(mid)
        dd = delta(mid, "down_proj")
        if dd is not None:
            y = y + dd
        return y


class MoE(nn.Module):
    """Sparse mixture of experts.

    Routing (``config.moe_router``): "softmax" — Mixtral's: softmax over
    all experts, top-k, weights renormalised; "sigmoid" — independent
    sigmoid scores, the choice made on score + ``expert_bias`` (when
    ``moe_expert_bias``) while the combine weight stays the unbiased
    score, divided by (sum + ``moe_norm_topk_eps``) when
    ``moe_norm_topk_prob``, times ``moe_routed_scaling_factor``. The router
    works in float32.

    Experts are gated (``down(act(gate x) * up x)``, ``mlp_activation``
    "silu" or "relu") or, with ``mlp_gated``
    off, ``down(act(up x))`` under ``mlp_activation`` with no ``gate_proj``
    in the tree (the ragged and dense dispatches). With
    ``moe_shared_intermediate_size`` a shared expert of that width — a
    feed-forward of the same form that every token takes, ``shared`` — is
    added to the routed result; a layer that holds a share of the experts
    adds it whole, as every chip of its expert-parallel group does.

    The share: ``num_experts`` is how many experts this layer HOLDS, of a
    router ``moe_router_width`` wide (None: all of them), starting at
    ``moe_expert_offset``. The router chooses among all its outputs, the
    weights are normalised over all k choices, and the layer returns the
    part of the result that the experts held here give: a token with no
    choice here gets 0 and keeps its residual. No token is dropped at any
    imbalance (``ops.moe.moe_ragged``: the sorted choices are cut at a
    static row, twice the even share — not at all where the share is under
    an eighth of the router —; the first window always runs, with
    one more group, of zero weights, for the choices of absent experts in
    it; the rest runs under a ``cond`` only when a held choice lies there).
    What the absent experts would add lies on other chips; nothing here
    stands in for them.

    Expert weights are stacked on a leading ``expert`` logical axis; with
    ``ep_size > 1`` GSPMD shards experts across the ``ep`` mesh axis and the
    dispatch/combine lowers to all-to-all — the expert-parallel capability
    absent from the reference (SURVEY.md §2.4 EP row).

    Three dispatch modes (``config.moe_dispatch``): "ragged" — grouped
    matmuls via jax.lax.ragged_dot, exact at ep==1, shard-capacity
    schedule (moe_ragged_ep) under ep>1 — the default at every ep, and the
    one path of a layer that holds a share; "capacity" — the GShard-style
    static-shape schedule (ops/moe.py, FLOPs independent of E, the
    GSPMD-auto alternative and old-jax fallback); "dense" — every expert
    computes every token (O(E) FLOPs, exact math, the test oracle).

    Sown under ``intermediates`` (``CausalLM.loss_fn(model, with_aux=True)``
    returns their means over the expert layers): ``moe_aux_loss``, and from
    the ragged path ``moe_local_choice_share``,
    ``moe_expert_load_max_over_mean``, ``moe_rows_computed_over_needed``,
    ``moe_rest_window_share`` (the share of the expert layers whose
    rows past the first window ran: ``ops.moe.ragged_load_stats``) and
    ``moe_width_computed_over_published`` (the width the grouped matmuls
    are given over the experts' own: 1.0 unless ``ops.moe.
    padded_expert_shape`` puts it on whole tiles; 1.103 at 1856).
    """

    config: TransformerConfig
    # a serving call (the cache-carrying path, never differentiated): the
    # ragged dispatch leaves the choices of absent experts out of every group
    # instead of giving them a group of zero weights (``ops.moe.moe_ragged``
    # ``forward_only``), and every dispatch sows ``moe_experts_touched``: how
    # many of the experts held here at least one row of the call chose
    decode: bool = False

    @nn.compact
    def __call__(self, x, router_x=None):
        # ``router_x``: what the router reads where that is not the experts'
        # input (``moe_router_pre_attention``: the attention sublayer's
        # normed input); None: ``x``, every other configuration
        from ..ops.moe import (
            load_balancing_loss, moe_dispatch_combine, ragged_load_stats,
        )

        cfg = self.config
        dtype = _dtype(cfg)
        E, K = cfg.num_experts, cfg.num_experts_per_tok
        R = cfg.moe_router_width or E  # the router's published width
        share = R != E
        b, s, h = x.shape
        f = cfg.moe_intermediate_size or cfg.intermediate_size

        with jax.named_scope("route"):
            logits = nn.Dense(
                R,
                use_bias=False,
                dtype=jnp.float32,
                param_dtype=jnp.float32,
                kernel_init=nn.with_partitioning(
                    nn.initializers.lecun_normal(), ("embed", None)
                ),
                name="router",
            )((x if router_x is None else router_x).astype(jnp.float32))  # (B,S,R)
            if cfg.moe_router == "sigmoid":
                scores = jax.nn.sigmoid(logits)
                choice = scores
                if cfg.moe_expert_bias:
                    bias = self.param(
                        "expert_bias",
                        nn.with_partitioning(
                            nn.initializers.zeros_init(), (None,)
                        ),
                        (R,),
                        jnp.float32,
                    )
                    if hasattr(bias, "unbox"):
                        bias = bias.unbox()
                    # moves the choice and not the weight: top_k's indices
                    # carry no gradient, so the bias's is exactly zero
                    choice = scores + bias
                if cfg.moe_n_group > 1:
                    # group-limited: a group's score is the sum of its two
                    # largest choice scores, and the choice is made inside
                    # the ``moe_topk_group`` best groups alone
                    groups = cfg.moe_n_group
                    grouped = choice.reshape(b, s, groups, R // groups)
                    _, best = jax.lax.top_k(
                        jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1),
                        cfg.moe_topk_group)
                    kept = jnp.any(
                        best[..., None] == jnp.arange(groups), axis=-2)
                    choice = jnp.where(
                        kept[..., None], grouped, -jnp.inf).reshape(b, s, R)
                _, sel = jax.lax.top_k(choice, K)  # (B,S,K)
                weights = jnp.take_along_axis(scores, sel, axis=-1)
                if cfg.moe_norm_topk_prob:
                    weights = weights / (
                        jnp.sum(weights, -1, keepdims=True)
                        + cfg.moe_norm_topk_eps
                    )
                weights = weights * cfg.moe_routed_scaling_factor
            else:
                weights, sel = jax.lax.top_k(jax.nn.softmax(logits, -1), K)
                weights = weights / jnp.sum(weights, -1, keepdims=True)

        def epar(name, shape, axes):
            return self.param(
                name,
                nn.with_partitioning(nn.initializers.lecun_normal(), axes),
                shape,
                jnp.float32,
            )

        gated = cfg.mlp_gated
        w_gate = (epar("gate_proj", (E, h, f), ("expert", "embed", "mlp"))
                  if gated else None)
        w_up = epar("up_proj", (E, h, f), ("expert", "embed", "mlp"))
        w_down = epar("down_proj", (E, f, h), ("expert", "mlp", "embed"))
        act = _mlp_activation(cfg)  # the gate's, or the non-gated expert's

        xc = x.astype(dtype)
        from ..parallel.sharding import live_mesh

        mesh = live_mesh()
        ep_live = mesh is not None and mesh.shape.get("ep", 1) > 1
        dispatch = cfg.moe_dispatch
        if dispatch == "auto":
            # ragged everywhere: exact AND measured faster on a single
            # chip (ops/moe.py numbers); under ep>1 the shard-capacity EP
            # schedule (moe_ragged_ep) beats capacity on both measured
            # axes — at equal capacity_factor it drops 3-10x fewer tokens
            # under skewed routing and its compiled step moves ~2x fewer
            # collective bytes (dp=2 x ep=4 mesh; numbers in
            # moe_ragged_ep's docstring).
            dispatch = "ragged"
        if share and (dispatch != "ragged" or ep_live):
            raise ValueError(
                "a layer that holds a share of the experts is one chip's "
                "part of an expert-parallel deployment: ragged dispatch, no "
                "live ep axis"
            )
        if self.decode:  # whatever the dispatch: what the routing chose
            local = sel.reshape(-1) - cfg.moe_expert_offset
            chosen = jnp.zeros((E + 1,), bool).at[
                jnp.where((local >= 0) & (local < E), local, E)
            ].set(True)
            self.sow("intermediates", "moe_experts_touched",
                     jnp.sum(chosen[:E]).astype(jnp.int32))
        if dispatch == "ragged":
            from ..ops.moe import (
                moe_ragged, moe_ragged_ep, padded_expert_shape,
            )

            if ep_live and not (gated and cfg.mlp_activation == "silu"):
                raise NotImplementedError(
                    "mlp_gated=False or a gate that is not silu under a live "
                    "ep axis: moe_ragged_ep writes the silu-gated expert's "
                    "three grouped matmuls in"
                )
            if ep_live:
                # expert-parallel ragged: shard-capacity schedule — the
                # sorted rows' per-shard region runs through a static
                # window with ragged-packed local experts (ops/moe.py)
                out = moe_ragged_ep(
                    xc.reshape(b * s, h),
                    sel.reshape(b * s, K),
                    weights.reshape(b * s, K),
                    w_gate.astype(dtype),
                    w_up.astype(dtype),
                    w_down.astype(dtype),
                    mesh=mesh,
                    capacity_factor=cfg.moe_capacity_factor,
                ).reshape(b, s, h)
            else:
                out = moe_ragged(
                    xc.reshape(b * s, h),
                    sel.reshape(b * s, K),
                    weights.reshape(b * s, K),
                    w_gate.astype(dtype) if gated else None,
                    w_up.astype(dtype),
                    w_down.astype(dtype),
                    expert_offset=cfg.moe_expert_offset,
                    router_width=R,
                    activation=act,
                    forward_only=self.decode,
                ).reshape(b, s, h)
                for name, value in ragged_load_stats(
                    sel, E, cfg.moe_expert_offset, R
                ).items():
                    self.sow("intermediates", name, value)
                self.sow(
                    "intermediates", "moe_width_computed_over_published",
                    jnp.float32(padded_expert_shape(h, f)[1] / f))
        elif dispatch == "capacity":
            def experts_fn(buf):  # (E, C, h) -> (E, C, h)
                hidden = jnp.einsum("ech,ehf->ecf", buf, w_gate.astype(dtype))
                hidden = act(hidden) * jnp.einsum(
                    "ech,ehf->ecf", buf, w_up.astype(dtype)
                )
                return jnp.einsum("ecf,efh->ech", hidden, w_down.astype(dtype))

            out = moe_dispatch_combine(
                xc.reshape(b * s, h),
                sel.reshape(b * s, K),
                weights.reshape(b * s, K),
                experts_fn,
                E,
                capacity_factor=cfg.moe_capacity_factor,
            ).reshape(b, s, h)
        elif dispatch == "dense":
            # combine weights as dense (B,S,E): zero for unselected experts
            combine = jnp.zeros_like(logits).at[
                jnp.arange(b)[:, None, None],
                jnp.arange(s)[None, :, None],
                sel,
            ].add(weights)
            if gated:
                hidden = jnp.einsum("bsh,ehf->ebsf", xc, w_gate.astype(dtype))
                hidden = act(hidden) * jnp.einsum(
                    "bsh,ehf->ebsf", xc, w_up.astype(dtype)
                )
            else:
                hidden = act(jnp.einsum("bsh,ehf->ebsf", xc, w_up.astype(dtype)))
            expert_out = jnp.einsum("ebsf,efh->ebsh", hidden, w_down.astype(dtype))
            out = jnp.einsum("ebsh,bse->bsh", expert_out, combine.astype(dtype))
        else:
            raise ValueError(
                f"unknown moe_dispatch {cfg.moe_dispatch!r}; use 'auto', "
                "'ragged', 'capacity' or 'dense'"
            )
        self.sow(
            "intermediates", "moe_aux_loss", load_balancing_loss(logits, sel, R)
        )
        if cfg.moe_shared_intermediate_size is not None:
            shared = MLP(
                cfg, width=cfg.moe_shared_intermediate_size, name="shared")(xc)
            if cfg.moe_shared_gate:
                with jax.named_scope("shared_gate"):
                    w_s = self.param(
                        "shared_gate",
                        nn.with_partitioning(
                            nn.initializers.normal(h ** -0.5), ("embed",)),
                        (h,), jnp.float32)
                    w_s = w_s.unbox() if hasattr(w_s, "unbox") else w_s
                    shared = shared * jax.nn.sigmoid(
                        jnp.einsum("bsh,h->bs", xc, w_s.astype(dtype))
                    )[..., None]
            out = out + shared
        return out.astype(x.dtype)


class Block(nn.Module):
    """One decoder layer: x + operator(norm(x)), then + feed-forward(norm).
    ``mixer`` is the layer's operator (a ``layer_types`` entry) and ``ff``
    its feed-forward ("mlp" | "moe"; None: "moe" where the config has
    experts) — the defaults are the one kind every layer was before
    ``layer_types``, with the same parameter tree. Under
    ``config.single_sublayer`` the layer is ONE of the two, x + f(norm(x)):
    ``mixer`` alone (``ff`` None: no feed-forward) or, ``mixer`` None, the
    feed-forward ``ff`` alone (:func:`_one_sublayer`)."""

    config: TransformerConfig
    decode: bool = False
    mixer: Optional[str] = "full_attention"
    ff: Optional[str] = None
    # whether this layer's attention rotates q and k (``rope_layout``);
    # None: the configuration's ``use_rope``
    rope: Optional[bool] = None

    @nn.compact
    def __call__(self, x, positions, mask=None, kv_lengths=None,
                 paged=None, lora=None, scanned=None):
        from ..parallel.sharding import constrain_activations

        cfg = self.config
        if cfg.single_sublayer:
            return _one_sublayer(
                self, x, positions, mask, kv_lengths, paged, lora, scanned), None
        if self.mixer == "conv":
            return _conv_layer(self, x), None
        if self.mixer == "linear_attention":
            return _gdn_layer(self, x, paged, lora, scanned), None
        # ``scanned`` is this layer's slice of the per-layer traced data:
        # either the bare layer-window array (the pre-adapter form) or a
        # dict {"window": ..., "lora": {target: {lora_a, lora_b}}} — both
        # shapes ride nn.scan's in_axes=0 the same way, the dict just
        # scans every leaf
        if isinstance(scanned, dict):
            layer_window = scanned.get("window")
            lora_scan = scanned.get("lora")
            layer = scanned.get("layer")
        else:
            layer_window, lora_scan, layer = scanned, None, None
        attn_lora = mlp_lora = None
        if lora_scan is not None:
            attn_lora = {
                t: p for t, p in lora_scan.items()
                if t in ("q_proj", "k_proj", "v_proj", "o_proj")
            } or None
            mlp_lora = {
                t: p for t, p in lora_scan.items()
                if t in ("gate_proj", "up_proj", "down_proj")
            } or None
        if cfg.kv_lora_rank is not None:
            if lora is not None:
                raise NotImplementedError(
                    "adapters name per-head q/k/v projections: not written "
                    "for latent attention")
            attn_out = LatentAttention(cfg, decode=self.decode, name="attn")(
                RMSNorm(cfg, name="attn_norm")(x), positions, mask,
                kv_lengths, paged, layer=layer)
        elif cfg.fused_kernels:
            # fused prologue: hand Attention the raw residual stream plus
            # the norm scale so ops/fused.py can run norm -> qkv -> rope
            # as one kernel (it falls back to the exact unfused math for
            # shapes it can't tile, and for decode/fp8)
            attn_scale = RMSNorm(cfg, name="attn_norm", param_only=True)(x)
            attn_out = Attention(cfg, decode=self.decode, name="attn")(
                x, positions, mask, kv_lengths, paged, layer_window,
                pre_norm_scale=attn_scale, lora=lora, lora_stacks=attn_lora,
                layer=layer,
            )
        else:
            # a stack with "sliding_attention" layers: the band is theirs
            # alone, static (None elsewhere: ``sliding_window`` for all)
            sliding = (self.mixer == "sliding_attention"
                       if "sliding_attention" in (cfg.layer_types or ())
                       else None)
            attn_in = RMSNorm(cfg, name="attn_norm")(x)
            attn_out = Attention(cfg, decode=self.decode, sliding=sliding,
                                 rope=self.rope, name="attn")(
                attn_in, positions, mask, kv_lengths, paged, layer_window,
                lora=lora, lora_stacks=attn_lora, layer=layer,
            )
        if cfg.post_norms:
            # Gemma-2 block: a norm AFTER each sublayer too (pre + post,
            # 4 per block — transformers Gemma2DecoderLayer)
            attn_out = RMSNorm(cfg, name="post_attn_norm")(attn_out)
        h = checkpoint_name(x + attn_out, "attn_res")
        ff_out = _feed_forward(
            self, h, lora, mlp_lora,
            router_x=attn_in if cfg.moe_router_pre_attention else None)
        if cfg.post_norms:
            ff_out = RMSNorm(cfg, name="post_mlp_norm")(ff_out)
        # pin the residual stream's layout once per layer so GSPMD cannot
        # alternate it between batch-sharded and weight-following layouts
        # (each flip is a full resharding per layer)
        return constrain_activations(h + ff_out), None

def _feed_forward(block: Block, h, lora=None, mlp_lora=None, router_x=None):
    """``block``'s feed-forward on the residual stream ``h``. A function,
    not a method: a method of a module would put its own name into every
    operation's scope path, and ``layers/mlp/...`` is what the benchmark's
    metrics read. ``router_x``: what an expert layer's router reads instead
    of the experts' input (``moe_router_pre_attention``)."""
    cfg = block.config
    ff = block.ff or ("moe" if cfg.num_experts > 0 else "mlp")
    if ff == "moe":
        # MoE blocks don't take adapters (the expert weights are the
        # specialization mechanism there); attention adapters still apply
        return MoE(cfg, decode=block.decode, name="moe")(
            RMSNorm(cfg, name="mlp_norm")(h), router_x)
    return MLP(cfg, name="mlp")(
        RMSNorm(cfg, name="mlp_norm")(h), lora=lora, lora_stacks=mlp_lora,
    )


def _conv_layer(block: Block, x):
    """The layer whose operator is the gated short convolution. It has no
    state to decode from, takes no adapters and no fused prologue."""
    from ..parallel.sharding import constrain_activations

    if block.decode:
        raise NotImplementedError(
            "a convolution layer keeps no state between calls yet: "
            "serving a stack with convolution state beside KV is "
            "ROADMAP Reach A4"
        )
    cfg = block.config
    h = x + ShortConv(cfg, name="conv")(RMSNorm(cfg, name="conv_norm")(x))
    h = checkpoint_name(h, "attn_res")
    return constrain_activations(h + _feed_forward(block, h))


def _gdn_layer(block: Block, x, paged, lora, scanned):
    """The layer whose operator is Gated DeltaNet, then its feed-forward. It
    decodes from a state a slot (``GatedDeltaNet``); it takes no adapters and
    no fused prologue."""
    from ..parallel.sharding import constrain_activations

    cfg = block.config
    if lora is not None:
        raise NotImplementedError(
            "adapters ride a stack whose every layer is attention then "
            "feed-forward: not a 'linear_attention' layer")
    layer = scanned.get("layer") if isinstance(scanned, dict) else None
    h = x + GatedDeltaNet(cfg, decode=block.decode, name="gdn")(
        RMSNorm(cfg, name="gdn_norm")(x), paged, layer)
    h = checkpoint_name(h, "attn_res")
    return constrain_activations(h + _feed_forward(block, h))


def _one_sublayer(block: Block, x, positions, mask, kv_lengths, paged, lora,
                  scanned):
    """``x + f(norm(x))`` for the one sublayer ``block`` names: its operator
    under ``<operator>_norm`` or, with no operator, its feed-forward under
    ``mlp_norm``. State-space and convolution layers keep no state between
    calls; no sublayer here takes adapters."""
    from ..parallel.sharding import constrain_activations

    cfg, mixer = block.config, block.mixer
    if lora is not None:
        raise NotImplementedError(
            "single_sublayer: adapters ride a stack whose every layer is "
            "attention then feed-forward")
    if block.decode and mixer in ("mamba", "conv"):
        raise NotImplementedError(
            f"a {mixer!r} layer keeps no state between calls yet: serving a "
            "stack with recurrent state beside KV is ROADMAP Reach A4")
    if mixer is None:
        out = _feed_forward(block, x)
    elif mixer == "mamba":
        out = Mamba2(cfg, name="ssm")(RMSNorm(cfg, name="ssm_norm")(x))
    elif mixer == "conv":
        out = ShortConv(cfg, name="conv")(RMSNorm(cfg, name="conv_norm")(x))
    else:
        layer = scanned.get("layer") if isinstance(scanned, dict) else None
        out = Attention(cfg, decode=block.decode, name="attn")(
            RMSNorm(cfg, name="attn_norm")(x), positions, mask, kv_lengths,
            paged, layer=layer)
    return constrain_activations(x + out)


def _make_embed(cfg: TransformerConfig, dtype, name: Optional[str] = "embed") -> nn.Embed:
    kw = {"name": name} if name is not None else {}
    return nn.Embed(
        cfg.vocab_size,
        cfg.hidden_size,
        dtype=dtype,
        param_dtype=jnp.float32,
        # vocab dim carries BOTH tp and the ZeRO seat (("vocab","zero") ->
        # (tp, fsdp)); the feature dim stays replicated. Sharding the
        # feature dim (what the fsdp heuristic would pick) makes every
        # lookup hidden-sharded and triggers involuntary full reshards
        # against the batch-sharded activation layout, fwd and bwd.
        embedding_init=nn.with_partitioning(
            nn.initializers.normal(0.02), (("vocab", "zero"), "embed")
        ),
        **kw,
    )


_REMAT_POLICIES = {
    "full": lambda: None,
    "dots": lambda: jax.checkpoint_policies.checkpoint_dots,
    # "dots" + grouped-matmul outputs: checkpoint_dots matches only the
    # dot_general primitive, so under moe_dispatch="ragged" the backward
    # would re-run every ragged_dot (the expert FLOPs — the single biggest
    # matmul cost in an MoE block). Saving ragged_dot_general too keeps
    # the remat recompute down to elementwise ops, same as "dots" does
    # for dense blocks. A layer that holds a share of the experts saves
    # them for both of moe_ragged's windows: the rest window's leave its
    # cond as residuals, zeros when it was not taken — T*K rows in all.
    "dots_ragged": lambda: jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.checkpoint_dots,
        lambda prim, *_, **__: getattr(prim, "name", "")
        == "ragged_dot_general",
    ),
    "dots_with_no_batch_dims": (
        lambda: jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    ),
    "save_attn": lambda: jax.checkpoint_policies.save_only_these_names(
        "attn_out"
    ),
    # the long-context (S=8k, B=1) middle ground: keep the f-wide MLP
    # activations, the attention output, and the residual mid — backward
    # then recomputes only the attention path (norm+qkv+kernel, the small
    # fraction of layer FLOPs) instead of the whole layer ("full") while
    # saving far less than "dots" (which keeps every matmul output and
    # OOMs at S=8192 on 16G chips)
    "save_mlp": lambda: jax.checkpoint_policies.save_only_these_names(
        "attn_out", "attn_res", "mlp_gate_out", "mlp_up_out"
    ),
}


def _layer_windows_array(cfg: TransformerConfig):
    """The (num_layers,) int32 per-layer window array for
    ``cfg.layer_windows``, or None. Full-attention layers carry a
    sentinel wider than any sequence, so ONE traced band formula covers
    the whole scan (col > row - w is vacuous at w >= seq)."""
    if cfg.layer_windows is None:
        return None
    return jnp.asarray(
        [w if w is not None else (1 << 30) for w in cfg.layer_windows],
        jnp.int32,
    )


def layer_kinds(cfg: TransformerConfig, num_layers=None) -> list:
    """Per layer ``(mixer, ff)``: its operator from ``layer_types`` and its
    feed-forward — the dense MLP in the ``num_dense_layers`` leading layers
    and wherever the config has no experts, else the expert layer. Under
    ``single_sublayer`` one of the two is None."""
    n = num_layers or cfg.num_layers
    types = cfg.layer_types or ("full_attention",) * n
    if cfg.single_sublayer:
        # ONE sublayer a layer: (operator, None) or (None, feed-forward)
        return [(None, t) if t in FF_LAYER_TYPES else (t, None)
                for t in types[:n]]
    kinds = [
        (types[l],
         "moe" if cfg.num_experts > 0 and l >= cfg.num_dense_layers else "mlp")
        for l in range(n)
    ]
    if cfg.rope_layout is not None:
        # rope by layer is part of a layer's kind: (mixer, ff, rope)
        kinds = [kind + (cfg.rope_layout[l],) for l, kind in enumerate(kinds)]
    return kinds


def _kind_kwargs(kind) -> dict:
    """A :func:`layer_kinds` entry as :class:`Block`'s fields."""
    return {} if kind is None else dict(zip(("mixer", "ff", "rope"), kind))


def plan_layers(kinds: list) -> list:
    """Cut the list of layer kinds into ``(start, period, repeats)``
    segments, left to right: at each position the period (a tuple of kinds)
    whose consecutive repeats cover most layers, the shorter period on a
    tie; where nothing repeats, one layer alone (``repeats`` 1). A segment
    that repeats is scanned as one body of its period, the others are
    unrolled. A homogeneous list is one segment of period 1."""
    segments, i, n = [], 0, len(kinds)
    while i < n:
        period, repeats = 1, 1
        for p in range(1, (n - i) // 2 + 1):
            r = 1
            while kinds[i + r * p:i + (r + 1) * p] == kinds[i:i + p]:
                r += 1
            if r >= 2 and p * r > period * repeats:
                period, repeats = p, r
        segments.append((i, tuple(kinds[i:i + period]), repeats))
        i += period * repeats
    return segments


class _Period(nn.Module):
    """One period of a repeating layer pattern — the body of a scan over
    layers of different parameter shapes: blocks ``b0`` .. ``b{p-1}``."""

    config: TransformerConfig
    block_cls: Any
    kinds: tuple
    decode: bool = False

    @nn.compact
    def __call__(self, x, *args):
        for j, kind in enumerate(self.kinds):
            x, _ = self.block_cls(
                self.config, decode=self.decode, **_kind_kwargs(kind),
                name=f"b{j}",
            )(x, *args)
        return x, None


def _apply_layer_stack(cfg: TransformerConfig, x, *extra, decode=False,
                       block_cls=None, num_layers=None, per_layer=None,
                       carry_cache=False):
    """Run a block stack (scan or unrolled, optional remat) on hidden
    states. Must be called inside an ``nn.compact`` context — the created
    modules attach to the calling module's scope, so CausalLM,
    SequenceClassifier and the seq2seq decoder share one implementation.

    ``extra``: per-call broadcast arguments of the block (positions, mask,
    memory, ...). ``per_layer``: an optional pytree whose every leaf has a
    leading (num_layers, ...) axis, passed as the block's LAST positional
    argument and scanned over that axis — the Gemma-2 per-layer window
    array, or the adapters' {"window", "lora"} dict (nn.scan's in_axes
    applies per-ARGUMENT, so a dict of stacks scans exactly like a bare
    array). ``block_cls``: defaults to :class:`Block`; the seq2seq decoder
    passes :class:`~.seq2seq.DecoderBlock`. Blocks must return
    ``(x, None)``.

    ``carry_cache``: the ``cache`` collection of a scanned segment rides
    the layer loop as a CARRY — each body sees the whole stacked leaves,
    ``(repeats, ...)``, and is told its row as ``{"layer": index}`` in the
    per-layer argument — instead of being sliced in and stacked out along
    the scan axis. It is how live paged K/V pools go through the loop: the
    loop then holds ONE buffer a pool, written in place, where a scanned
    input and a scanned output are two, with a slice copied out and a
    slice copied back each layer (three passes over the pool to store a
    call's rows — a third of the v5e's decode step, PERF.md, PR 27).

    Layers of ONE kind (every configuration before ``layer_types``) are one
    scan named ``layers``. Layers of several kinds (:func:`layer_kinds`)
    follow :func:`plan_layers`: a segment that repeats is a scan named
    ``layers_<first layer>`` — over the :class:`Block` itself at period 1,
    over a :class:`_Period` of blocks ``b0..`` otherwise — and a layer that
    stands alone is a block named ``layer_<index>``, as every layer is
    with ``scan_layers`` off.
    """
    base_cls = block_cls or Block
    n = num_layers or cfg.num_layers

    def remat(cls, scanned: bool):
        if not cfg.remat:
            return cls
        return nn.remat(
            cls,
            policy=_REMAT_POLICIES[cfg.remat](),
            prevent_cse=not scanned,
            static_argnums=(),
        )

    kinds = layer_kinds(cfg, n) if base_cls is Block else [None] * n
    segments = plan_layers(kinds)
    mixed = len(segments) > 1 or len(segments[0][1]) > 1
    if mixed and per_layer is not None:
        raise NotImplementedError(
            "per-layer windows and adapter stacks ride ONE scan over layers "
            "of one kind; this stack has layers of several kinds"
        )

    def scan(body, length, in_axes):
        axes = {"params": 0, "intermediates": 0}
        if not carry_cache:
            axes["cache"] = 0
        return nn.scan(
            body,
            variable_axes=axes,
            variable_carry="cache" if carry_cache else False,
            # "dropout": LoRA delta dropout inside the scanned block —
            # the entry is inert unless a dropout rng is actually passed
            # to apply (adapter training with LoraConfig.dropout > 0)
            split_rngs={"params": True, "dropout": True},
            in_axes=in_axes,
            length=length,
            metadata_params={nn.PARTITION_NAME: "layers"},
        )

    if not cfg.scan_layers:
        cls = remat(base_cls, scanned=False)
        for i in range(n):
            if per_layer is None:
                args = extra
            else:
                # slice EVERY leaf's layer axis (per_layer may be a dict
                # of adapter stacks, not just the bare window array)
                args = extra + (jax.tree.map(lambda l: l[i], per_layer),)
            x, _ = cls(cfg, decode=decode, **_kind_kwargs(kinds[i]),
                       name=f"layer_{i}")(x, *args)
        return x

    def scan_args(per_layer, length):
        """``(in_axes, args)`` of a scan over ``length`` layers: ``extra``
        broadcast, then the per-layer pytree if there is one — with each
        body's row of a carried cache in it."""
        if carry_cache:
            per_layer = dict(
                per_layer or {}, layer=jnp.arange(length, dtype=jnp.int32))
        in_axes = tuple(nn.broadcast for _ in extra)
        if per_layer is None:
            return in_axes, extra
        return in_axes + (0,), extra + (per_layer,)

    if not mixed:
        in_axes, args = scan_args(per_layer, n)
        # a name for the loop's own copies and slices in a device trace,
        # not a Flax scope: the parameter tree is unchanged
        with jax.named_scope("layers"):
            x, _ = scan(remat(base_cls, scanned=True), n, in_axes)(
                cfg, decode=decode, **_kind_kwargs(kinds[0]), name="layers"
            )(x, *args)
        return x

    for start, period, repeats in segments:
        if repeats == 1:
            x, _ = remat(base_cls, scanned=False)(
                cfg, decode=decode, **_kind_kwargs(period[0]),
                name=f"layer_{start}",
            )(x, *extra)
            continue
        name = f"layers_{start}"
        in_axes, args = scan_args(None, repeats)
        if len(period) == 1:
            body = scan(remat(base_cls, scanned=True), repeats, in_axes)(
                cfg, decode=decode, **_kind_kwargs(period[0]), name=name)
        else:
            body = scan(_Period, repeats, in_axes)(
                cfg, remat(base_cls, scanned=True), period, decode, name=name)
        with jax.named_scope(name):
            x, _ = body(x, *args)
    return x


def eva_roll_over_cache(cfg: TransformerConfig, params, cache, src, dst):
    """A window one request has just filled, in every layer's pools: the
    rows of blocks ``src`` are summarised under that layer's ``mu``/``phi``
    and the summaries written into blocks ``dst``
    (``ops/eva_attention.eva_roll_over``). ``cache`` is the paged cache
    collection of a ``CausalLM`` of ``attention_class`` "eva" and is
    returned whole, its pools written in place when the caller donates it."""
    from ..ops.eva_attention import eva_roll_over

    scale = (cfg.query_pre_attn_scalar or cfg.head_dim) ** -0.5
    cache = dict(cache)
    for name, entry in cache.items():  # "layers", or "layer_<i>" unscanned
        pools = entry.get("attn") if hasattr(entry, "get") else None
        if pools is None or "key_pool" not in pools:
            continue
        learned = params[name]["attn"]
        new_k, new_v = eva_roll_over(
            pools["key_pool"], pools["value_pool"], learned["mu"],
            learned["phi"], src, dst, chunk=cfg.chunk_size, scale=scale,
        )
        cache[name] = dict(entry, attn=dict(
            pools, key_pool=new_k, value_pool=new_v))
    return cache


def _sown_means(sown) -> dict:
    """``{name: mean}`` over every layer's value of each scalar sowed under
    ``intermediates`` (a scan stacks them, ``sow`` wraps them in tuples)."""
    by_name: dict = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(sown)[0]:
        name = next(k.key for k in reversed(path) if hasattr(k, "key"))
        by_name.setdefault(name, []).append(jnp.ravel(leaf))
    return {k: jnp.mean(jnp.concatenate(v)) for k, v in by_name.items()}


class CausalLM(nn.Module):
    """The language model: embed -> scan(Block) -> norm -> lm_head.

    ``__call__(input_ids, positions=None, mask=None) -> logits``.
    """

    config: TransformerConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, mask=None, decode=False,
                 paged=None, lora=None, logits_at=None):
        # ``logits_at`` (B,): the head reads that row of each sequence alone
        # and the logits come back (B, 1, V) — a prefill that samples one
        # token of a padded bucket then never forms width x vocabulary
        # logits (16,384 x 75,968 float32 are 5 GB). None: every position.
        cfg = self.config
        dtype = _dtype(cfg)
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(input_ids.shape[1])[None, :], input_ids.shape
            )
        from ..parallel.sharding import constrain_activations

        embed = _make_embed(cfg, dtype)
        x = embed(input_ids)
        if cfg.embed_scale:  # Gemma scales embeddings by sqrt(hidden)
            x = x * jnp.asarray(np.sqrt(cfg.hidden_size), x.dtype)
        if cfg.fp32_residual:
            # the stream every layer adds into; each sublayer's output
            # (compute dtype) is promoted by the add
            x = x.astype(jnp.float32)
        x = constrain_activations(x)
        # the explicit Nones fill the block's kv_lengths/paged/lora slots
        # so the per-layer scanned pytree (window array and/or adapter
        # stacks) lands on the block's LAST positional argument. ``lora``
        # splits into a broadcast context (slot_ids/scales, shared by all
        # layers) and the per-layer stacks riding the scan axis.
        windows = _layer_windows_array(cfg)
        lora_ctx = lora.context() if lora is not None else None
        scanned = None
        if windows is not None or (lora is not None and lora.stacks is not None):
            scanned = {}
            if windows is not None:
                scanned["window"] = windows
            if lora is not None and lora.stacks is not None:
                scanned["lora"] = lora.stacks
        # Paged pools of a scanned stack are always carried through the
        # layer loop, written in place. ``init``, which creates them and
        # neither reads nor writes one, is the one pass that has them on
        # the scan axis: flax creates no variable inside a carry.
        x = _apply_layer_stack(
            cfg, x, positions, mask, None, paged, lora_ctx, decode=decode,
            per_layer=scanned,
            carry_cache=(
                decode and paged is not None and not self.is_initializing()
            ),
        )
        if logits_at is not None:
            x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
        x = constrain_activations(RMSNorm(cfg, name="final_norm")(x))
        # logits matmul stays in the compute dtype (bf16 on the MXU — fp32
        # here costs ~4x on the biggest matmul); the loss upcasts to fp32
        # before log_softmax, which is where precision actually matters
        if cfg.tie_embeddings:
            # the tied head opens no module of its own: name its matmul
            with jax.named_scope("tied_head"):
                logits = embed.attend(x)
        else:
            logits = nn.Dense(
                cfg.vocab_size * cfg.num_pred_heads,
                use_bias=False,
                dtype=dtype,
                param_dtype=jnp.float32,
                kernel_init=nn.with_partitioning(
                    nn.initializers.lecun_normal(), ("embed", "vocab")
                ),
                # the same operands into the MXU; its float32 accumulator
                # handed on as it is
                dot_general=functools.partial(
                    jax.lax.dot_general, preferred_element_type=jnp.float32,
                ) if cfg.fp32_logits else None,
                name="lm_head",
            )(x)
            if cfg.num_pred_heads > 1:
                # head-major: the first vocab_size columns are the next
                # token's; the further heads' are for multi-token decoding
                logits = logits[..., :cfg.vocab_size]
        if cfg.final_softcap is not None:
            # Gemma-2 final-logit soft-capping (in fp32: tanh saturates
            # quickly in bf16 and the caps exist to shape the tail)
            logits = (
                cfg.final_softcap
                * jnp.tanh(logits.astype(jnp.float32) / cfg.final_softcap)
            ).astype(logits.dtype)
        return logits

    # ------------------------------------------------------------------ #
    # convenience: init + loss
    # ------------------------------------------------------------------ #
    def init_params(self, rng, batch_size: int = 1, seq_len: Optional[int] = None):
        seq_len = seq_len or min(self.config.max_seq_len, 128)
        dummy = jnp.zeros((batch_size, seq_len), jnp.int32)
        return self.init(rng, dummy)["params"]

    @staticmethod
    def loss_fn(model: "CausalLM", with_aux: bool = False):
        """Next-token cross-entropy closure for Accelerator.unified_step:
        ``loss_fn(params, batch)`` with batch {input_ids, [loss_mask]}.
        ``with_aux`` (for ``unified_step(..., has_aux=True)``): returns
        ``(loss, aux)``, ``aux`` the mean over layers of every scalar the
        model sowed under ``intermediates`` (the MoE counters)."""

        def fn(params, batch):
            ids = batch["input_ids"]
            if with_aux:
                logits, sown = model.apply(
                    {"params": params}, ids, mutable=["intermediates"])
                return _next_token_loss(logits, ids, batch), _sown_means(
                    sown.get("intermediates", {}))
            logits = model.apply({"params": params}, ids)
            return _next_token_loss(logits, ids, batch)

        def _next_token_loss(logits, ids, batch):
            targets = ids[:, 1:]
            logits = logits[:, :-1]
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
            mask = batch.get("loss_mask")
            if mask is not None:
                mask = mask[:, 1:].astype(jnp.float32)
                return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
            return jnp.mean(nll)

        # telemetry: step records carry whether this step ran the fused
        # prologue (unified_step reads the attribute off the closure)
        fn.fused_kernels = bool(getattr(model.config, "fused_kernels", False))
        return fn


class SequenceClassifier(nn.Module):
    """Encoder classifier — the BERT-family fine-tune target (reference
    ``examples/nlp_example.py``: AutoModelForSequenceClassification on
    bert-base). Same Block stack as CausalLM with ``config.causal=False``
    (bidirectional self-attention); masked mean-pool + tanh pooler +
    classification head replace the lm_head.

    ``__call__(input_ids, attention_mask=None) -> (B, num_labels) logits``
    with ``attention_mask`` 1 = real token, 0 = padding.

    Attention-mask routing: where the flash kernel actually runs
    (``attention_impl="flash"``, or auto-dispatch selecting flash on TPU)
    the mask is treated as RIGHT padding and lowered to per-row valid
    lengths — the universal HF tokenizer convention (reference
    examples/nlp_example.py:83-96 pads right) — letting padded batches run
    the O(S)-memory flash kernel and skip fully-padded kv blocks. Every
    other path applies the exact dense (B,1,1,S) key mask, correct for ANY
    0/1 pattern. Non-prefix mask rows on the flash path are POISONED with
    NaN (loud failure, never silently-wrong logits) — left-padded or
    non-contiguous masks require ``attention_impl="xla"``.
    """

    config: TransformerConfig
    num_labels: int = 2

    @nn.compact
    def __call__(self, input_ids, attention_mask=None):
        cfg = self.config
        dtype = _dtype(cfg)
        b, s = input_ids.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        # Mask routing (see class docstring): lower the mask to
        # right-padding lengths ONLY where the flash kernel actually runs
        # (explicit "flash", or auto-dispatch selecting it); every other
        # path keeps the exact dense key mask, correct for ANY pattern.
        from ..ops.attention import flash_self_attention_eligible

        attn_mask4d = kv_lengths = is_prefix = None
        if attention_mask is not None:
            # softcap / per-layer windows force the xla path, which can
            # consume the exact dense mask — don't lower to kv_lengths
            # (the dispatch-must-agree contract of dot_product_attention)
            flash_compatible = (
                cfg.attn_softcap is None and cfg.layer_windows is None
            )
            use_flash = flash_compatible and (
                cfg.attention_impl == "flash" or (
                    cfg.attention_impl is None
                    and flash_self_attention_eligible(s)
                )
            )
            if use_flash:
                keep = attention_mask > 0
                kv_lengths = jnp.sum(keep, axis=-1).astype(jnp.int32)
                # lengths are only faithful for right-padded (prefix-form)
                # masks; a non-prefix row (e.g. padding_side="left") would
                # silently attend to pads and drop real tokens — poison
                # such rows with NaN so the loss screams instead
                is_prefix = jnp.all(keep[:, 1:] <= keep[:, :-1], axis=-1)
            else:
                # (B, S) keep-mask -> (B, 1, 1, S): padded keys invisible
                attn_mask4d = attention_mask[:, None, None, :] > 0
        x = _make_embed(cfg, dtype)(input_ids)
        # the explicit Nones fill the block's paged/lora slots so the
        # per-layer window dict (if any) lands on the scanned argument
        windows = _layer_windows_array(cfg)
        x = _apply_layer_stack(
            cfg, x, positions, attn_mask4d, kv_lengths, None, None,
            per_layer={"window": windows} if windows is not None else None,
        )
        if is_prefix is not None:
            x = jnp.where(is_prefix[:, None, None], x, jnp.nan)
        x = RMSNorm(cfg, name="final_norm")(x)

        if attention_mask is None:
            pooled = jnp.mean(x, axis=1)
        else:
            w = attention_mask[:, :, None].astype(x.dtype)
            pooled = jnp.sum(x * w, axis=1) / jnp.maximum(
                jnp.sum(w, axis=1), 1.0
            )
        pooled = nn.tanh(
            nn.Dense(
                cfg.hidden_size,
                dtype=dtype,
                param_dtype=jnp.float32,
                kernel_init=nn.with_partitioning(
                    # ("embed", None): a square kernel must not map one mesh
                    # axis to both dims (invalid PartitionSpec)
                    nn.initializers.lecun_normal(), ("embed", None)
                ),
                name="pooler",
            )(pooled)
        )
        # classifier logits in fp32: the softmax/CE is where precision matters
        return nn.Dense(
            self.num_labels,
            dtype=jnp.float32,
            param_dtype=jnp.float32,
            kernel_init=nn.with_partitioning(
                nn.initializers.lecun_normal(), ("embed", None)
            ),
            name="classifier",
        )(pooled)

    @staticmethod
    def loss_fn(model: "SequenceClassifier"):
        """Cross-entropy closure for Accelerator.unified_step; batch keys:
        {input_ids, labels, [attention_mask]}."""
        import optax

        def fn(params, batch):
            logits = model.apply(
                {"params": params},
                batch["input_ids"],
                batch.get("attention_mask"),
            )
            return optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), batch["labels"]
            ).mean()

        return fn

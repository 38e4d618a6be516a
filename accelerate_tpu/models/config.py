"""Model architecture configs + presets for the baseline families.

Parity note: the reference consumes HF ``transformers`` models as-is and
parses their configs into Megatron args (reference utils/megatron_lm.py:
1641-1771 — bert/gpt2/t5/llama parsers). Here the config is native and
presets mirror the BASELINE.md targets.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional


SUPPORTED_ROPE_TYPES = ("default", "llama3", "linear", "yarn")
# per-layer operators a ``layer_types`` entry may name (config.json names),
# and the feed-forwards it may name where a layer is ONE sublayer
# (``single_sublayer``: "mamba", "moe" and "mlp" stand in such a stack only)
LAYER_TYPES = ("full_attention", "sliding_attention", "conv", "mamba",
               "linear_attention")
FF_LAYER_TYPES = ("moe", "mlp")
MLP_ACTIVATIONS = ("silu", "gelu_tanh", "relu2", "relu")
# what a layer's attention computes: None is softmax over every earlier
# position (under ``sliding_window`` if set); "eva" is chunk summaries beside
# a window of exact keys and values (models/transformer.Attention)
ATTENTION_CLASSES = (None, "eva")
# required rope_scaling keys per type (beyond rope_type itself)
_ROPE_REQUIRED_KEYS = {
    "default": (),
    "linear": ("factor",),
    "llama3": (
        "factor",
        "low_freq_factor",
        "high_freq_factor",
        "original_max_position_embeddings",
    ),
    # beta_fast / beta_slow (32 / 1) and mscale / mscale_all_dim may follow
    "yarn": ("factor", "original_max_position_embeddings"),
}


def rope_type(scaling: Optional[dict]) -> str:
    """The rope_type of an HF-style ``rope_scaling`` dict (accepting the
    legacy ``type`` key), ``"default"`` when absent — the ONE place this
    extraction lives (used by config validation, hf interop, and the rope
    implementation)."""
    if not scaling:
        return "default"
    return scaling.get("rope_type", scaling.get("type", "default"))


def validate_rope_scaling(scaling: Optional[dict]) -> None:
    """Reject unsupported types AND missing parameters up front: a
    scaling dict that only fails at trace time (KeyError inside jit)
    would defeat the loader's fail-loudly contract."""
    rt = rope_type(scaling)
    if rt not in SUPPORTED_ROPE_TYPES:
        raise ValueError(
            f"unsupported rope_scaling type {rt!r}; "
            f"supported: {', '.join(SUPPORTED_ROPE_TYPES)}"
        )
    missing = [k for k in _ROPE_REQUIRED_KEYS[rt] if k not in (scaling or {})]
    if missing:
        raise ValueError(
            f"rope_scaling type {rt!r} requires keys {missing} "
            f"(got {sorted(scaling)})"
        )


@dataclass
class TransformerConfig:
    # model family: "llama" (the modern default — RMSNorm/rope/SwiGLU,
    # models/transformer.py) or "gpt2" (classic — LayerNorm/learned
    # positions/biases/GELU, models/gpt2.py). Selects the HF parameter
    # mapping in utils/hf_interop.py; build the matching module class
    # (CausalLM vs GPT2LM).
    arch: str = "llama"
    vocab_size: int = 32000
    hidden_size: int = 512
    intermediate_size: int = 1408
    num_layers: int = 4
    # encoder-decoder models (Seq2SeqLM): decoder depth; None -> num_layers
    num_decoder_layers: Optional[int] = None
    num_heads: int = 8
    num_kv_heads: Optional[int] = None  # None -> num_heads (MHA); < heads -> GQA
    # bias on the q/k/v projections ONLY (the Qwen2 family convention —
    # o_proj and the MLP stay bias-free); selects the matching HF mapping
    qkv_bias: bool = False
    head_dim: Optional[int] = None  # None -> hidden_size // num_heads
    max_seq_len: int = 2048
    rope_theta: float = 500000.0
    # HF-style rope frequency scaling (Llama-3.1+ ships
    # ``{"rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0,
    # "high_freq_factor": 4.0, "original_max_position_embeddings": 8192}``);
    # supported rope_types: "llama3", "linear", "default"/None. Applied in
    # models/transformer.rope — keep in sync with transformers'
    # _compute_llama3_parameters so HF checkpoints logits-match.
    rope_scaling: Optional[dict] = None
    rms_norm_eps: float = 1e-5
    # Gemma-family math switches (key layout is Llama's; only the math
    # differs — utils/hf_interop.py maps model_type "gemma" onto these):
    # RMSNorm multiplies by (1 + scale) with zero-init scales,
    norm_offset: bool = False
    # the MLP gate activation ("silu" = Llama/Mixtral, "gelu_tanh" =
    # Gemma's gelu_pytorch_tanh, "relu2" = relu(.)^2, Nemotron-H, "relu" =
    # the ReGLU gate, SmallThinker),
    mlp_activation: str = "silu"
    # False: no gate matrix, down(act(up x)) — the dense MLP, the shared
    # expert and (through the ragged and dense dispatches) the experts; their
    # parameter trees then hold no ``gate_proj``
    mlp_gated: bool = True
    # and embedding outputs scale by sqrt(hidden_size).
    embed_scale: bool = False
    tie_embeddings: bool = False
    # False -> bidirectional self-attention (BERT-family encoders)
    causal: bool = True
    # sliding-window attention band (Mistral / sliding Qwen2): each query
    # sees at most the last `sliding_window` keys, self included — HF
    # semantics (kv_idx > q_idx - sliding_window AND causal). Applies to
    # EVERY layer, unless ``layer_types`` names "sliding_attention" layers:
    # the band then belongs to those alone, a Python int where each is
    # traced, and the "full_attention" layers beside them see every earlier
    # position (a scanned period of both is one body of several blocks;
    # ServingEngine then holds a ring of ``sliding_window`` rows a slot for
    # the sliding layers, serving/cache_regime.py "ring"). xla and flash
    # attention honor it (flash skips below-band kv blocks: work scales
    # with S*window); ring attention rejects it.
    sliding_window: Optional[int] = None
    # Gemma-2 family switches (utils/hf_interop.py maps model_type
    # "gemma2" onto these, on top of the Gemma-1 trio above):
    # per-layer window pattern (tuple of int-or-None, len num_layers —
    # Gemma-2 alternates sliding/full). Heterogeneous patterns ride the
    # scan as a per-layer traced window, which only the xla attention
    # path supports; homogeneous patterns should use sliding_window.
    layer_windows: Optional[tuple] = None
    # attention scale = query_pre_attn_scalar**-0.5 (Gemma-2 sets 256,
    # decoupled from head_dim); None -> head_dim**-0.5
    query_pre_attn_scalar: Optional[float] = None
    # tanh soft-capping: s -> cap * tanh(s / cap) on attention scores
    # (before masking) and on final logits
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    # Gemma-2 block: norms AFTER attention and the MLP too (4 per block)
    post_norms: bool = False
    attention_impl: Optional[str] = None  # None=auto | xla | flash | ring
    # "eva" (EvaByte; Zheng et al., ICLR 2023): positions are cut into
    # windows of ``window_size`` and chunks of ``chunk_size``. A query sees
    # the keys and values of its own window up to itself, exactly, and of
    # every chunk in an EARLIER window one summary (k~, v~) — a softmax-
    # weighted mean of the chunk's keys, resp. values, under one learned
    # vector a head (``attn/mu``, ``attn/phi``) — all under ONE softmax. A
    # request's cache is then O(window_size + n / chunk_size) rows, not n:
    # ops/eva_attention.EvaLayout. Every layer is of this class.
    attention_class: Optional[str] = None
    chunk_size: int = 16
    window_size: int = 2048
    # the head predicts this many next tokens, head-major: columns
    # [j * vocab_size, (j + 1) * vocab_size) are the (j + 1)-th next token's.
    # The model returns the first head's logits, which is what sampling and
    # the loss read; the others are computed for multi-token decoding, which
    # nothing here runs yet.
    num_pred_heads: int = 1
    # where ``dtype`` is narrower than float32 (config.json names
    # ``fp32_skip_add``, ``fp32_logits``): the residual stream is carried in
    # float32 — every ``x + sublayer(norm(x))`` adds in float32 and stays
    # there, the norms hand the compute dtype to the matmuls —, resp. the
    # head's matmul keeps its float32 accumulator as the logits instead of
    # rounding them to ``dtype``. Off: one activation dtype throughout.
    fp32_residual: bool = False
    fp32_logits: bool = False
    # MoE (Mixtral family); 0 experts = dense MLP
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # "auto" (default): "ragged" at every ep. "ragged":
    # grouped-matmul dispatch (jax.lax.ragged_dot) — exact math at ep==1
    # (no padding, no drops), measured FASTER than capacity at bench
    # shapes (ops/moe.py docstring numbers); under ep>1 it runs the
    # shard-capacity EP schedule (ops/moe.moe_ragged_ep — ragged-packed
    # local experts, per-SHARD headroom: at equal capacity_factor it
    # drops 3-10x fewer tokens and moves ~2x fewer collective bytes than
    # "capacity", measured numbers in moe_ragged_ep's docstring).
    # "capacity": GShard-style static-shape dispatch — FLOPs scale with
    # K*capacity_factor, overflow tokens drop per expert. "dense": every
    # expert sees every token (the exact-math test oracle, O(E) FLOPs)
    moe_dispatch: str = "auto"
    moe_capacity_factor: float = 2.0
    # expert width; None -> intermediate_size (Mixtral: experts as wide as
    # the dense MLP would be)
    moe_intermediate_size: Optional[int] = None
    # leading layers that keep the dense MLP in a model whose other layers
    # are expert layers
    num_dense_layers: int = 0
    # how the router scores: "softmax" (Mixtral: softmax over all experts,
    # top-k, weights renormalised) or "sigmoid" (independent scores; the
    # three switches below apply)
    moe_router: str = "softmax"
    # a per-expert bias added to the scores for the CHOICE only: the
    # combine weight of a chosen expert is its unbiased score
    moe_expert_bias: bool = False
    # divide the k chosen scores by (their sum + moe_norm_topk_eps)
    moe_norm_topk_prob: bool = True
    moe_norm_topk_eps: float = 1e-6
    moe_routed_scaling_factor: float = 1.0
    # group-limited routing (config.json ``n_group`` / ``topk_group``): the
    # router's outputs in ``moe_n_group`` equal groups, a group's score the
    # sum of its two largest choice scores, the choice made inside the
    # ``moe_topk_group`` best groups alone. (1, 1): among all outputs
    moe_n_group: int = 1
    moe_topk_group: int = 1
    # a shared expert beside the routed ones: a plain feed-forward of this
    # width that every token takes, ``moe/shared``; a layer that holds a
    # share of the experts computes it whole, as every chip of the
    # expert-parallel group does. ``moe_shared_gate``: its output times
    # sigmoid(w_s . x), one learned vector ``moe/shared_gate``
    moe_shared_intermediate_size: Optional[int] = None
    moe_shared_gate: bool = False
    # the chip's share of an expert-parallel deployment: ``num_experts`` is
    # how many experts this layer HOLDS, the router keeps its published
    # width ``moe_router_width`` (None -> num_experts) and chooses among all
    # of them, and the layer computes the part of the result that experts
    # [moe_expert_offset, moe_expert_offset + num_experts) give — exact, no
    # token dropped; a token with no choice here gets 0 from the layer
    moe_router_width: Optional[int] = None
    moe_expert_offset: int = 0
    # per-layer operator, len num_layers: "full_attention" or "conv" (the
    # gated short convolution: in_proj to 3 x hidden, B * x, depthwise
    # causal conv of length conv_L_cache, C * ., out_proj); under
    # ``single_sublayer`` also "mamba", "moe", "mlp". None -> every
    # layer attends. Layers of different kinds have different parameter
    # shapes: models/transformer._plan_layers scans each maximal run of a
    # repeating period and unrolls what does not repeat.
    layer_types: Optional[tuple] = None
    conv_L_cache: int = 3
    # a layer is ONE sublayer, x + f(norm(x)) under one norm (Nemotron-H's
    # ``hybrid_override_pattern``): each ``layer_types`` entry then names the
    # layer's only sublayer — an operator ("mamba", "full_attention", "conv")
    # with no feed-forward after it, or a feed-forward ("moe", "mlp") with no
    # operator before it. Off: every layer is operator then feed-forward.
    single_sublayer: bool = False
    # the Mamba-2 operator (``layer_types`` "mamba"; models/transformer.Mamba2,
    # ops/ssd.py): ``mamba_num_heads`` heads of ``mamba_head_dim`` channels —
    # their product is the operator's width, whatever ``expand`` x hidden would
    # be —, ``mamba_n_groups`` groups of B and C of ``mamba_state_size`` each
    # (head h reads group h // (heads / groups)), a causal depthwise
    # convolution of ``mamba_conv_kernel`` taps with bias and silu over
    # [x | B | C], the recurrence computed in chunks of ``mamba_chunk_size``
    mamba_num_heads: int = 0
    mamba_head_dim: int = 64
    mamba_n_groups: int = 1
    mamba_state_size: int = 128
    mamba_conv_kernel: int = 4
    mamba_chunk_size: int = 128
    # the Gated DeltaNet operator (``layer_types`` "linear_attention";
    # models/transformer.GatedDeltaNet, ops/gated_delta.py): ``gdn_num_k_heads``
    # query/key heads of ``gdn_head_k_dim`` and ``gdn_num_v_heads`` value heads
    # of ``gdn_head_v_dim`` (key head j // (v heads / k heads) serves value
    # head j), a causal depthwise convolution of ``gdn_conv_kernel`` taps, no
    # bias, then silu over [q | k | v]; each value head keeps a float32 state
    # of head_k_dim x head_v_dim, which a serving slot carries beside the KV
    # pools of the attention layers
    gdn_num_k_heads: int = 0
    gdn_num_v_heads: int = 0
    gdn_head_k_dim: int = 128
    gdn_head_v_dim: int = 128
    gdn_conv_kernel: int = 4
    # False: attention carries no position (q and k are not rotated): the
    # order comes from elsewhere in the stack (state-space layers)
    use_rope: bool = True
    # rope by layer (config.json ``rope_layout``, one 0 / 1 a layer): layer l
    # rotates q and k iff ``rope_layout[l]``; None: ``use_rope`` for all
    rope_layout: Optional[tuple] = None
    # the router reads the normed input of the ATTENTION sublayer (the
    # SmallThinker family's "router placed before attention") while the
    # experts read the feed-forward's
    moe_router_pre_attention: bool = False
    # RMSNorm over head_dim on q and k (one weight vector each), before rope
    qk_norm: bool = False
    # rope turns the first ``partial_rotary_factor`` x head_dim elements of a
    # head (half-split pairing inside them); the rest pass unrotated
    partial_rotary_factor: float = 1.0
    # q_proj is twice as wide: each head's columns are [q | gate], and the
    # attention output is multiplied by sigmoid(gate) before o_proj
    attn_output_gate: bool = False
    # multi-head LATENT attention (config.json names; ``kv_lora_rank`` set
    # turns it on, in every layer: models/transformer.LatentAttention).
    # Queries come through a ``q_lora_rank`` bottleneck as ``num_heads`` heads
    # of [``qk_nope_head_dim`` | ``qk_rope_head_dim``]; a position's keys and
    # values are ONE latent row [c_kv (``kv_lora_rank``) | k_rope
    # (``qk_rope_head_dim``)] shared by all heads — what the cache holds —,
    # from which ``kv_b_proj`` gives each head ``qk_nope_head_dim`` of key and
    # ``v_head_dim`` of value. ``head_dim`` is then the score's width,
    # ``qk_nope_head_dim + qk_rope_head_dim``; ``num_kv_heads`` says nothing
    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # fp8 projections: e4m3 fwd / e5m2 bwd matmuls (ops/fp8.py) — the
    # TransformerEngine capability; pair with mixed_precision="fp8"
    fp8: bool = False
    # remat: None | "full" | "dots" — trades FLOPs for HBM
    remat: Optional[str] = None
    # fused Pallas step kernels (ops/fused.py): RMSNorm -> QKV -> rope in
    # one kernel per attention block. Param tree and checkpoints are
    # identical either way; numerics match the unfused chain to fp32
    # tolerance (exact-shape fallback to the unfused path when a shape the
    # kernel can't tile comes through, and interpret mode on CPU)
    fused_kernels: bool = False
    # scan over layers: one compiled layer body, num_layers iterations —
    # keeps compile time flat in depth (essential at 8B+)
    scan_layers: bool = True
    dtype: str = "float32"  # activation dtype at apply time

    def __post_init__(self):
        if self.arch not in ("llama", "gpt2"):
            raise ValueError(
                f"unknown arch {self.arch!r}; supported: llama, gpt2"
            )
        if self.mlp_activation not in MLP_ACTIVATIONS:
            raise ValueError(
                f"unknown mlp_activation {self.mlp_activation!r}; "
                f"supported: {', '.join(MLP_ACTIVATIONS)}"
            )
        # an unsupported/underspecified rope_scaling silently ignored (or
        # crashing only at trace time) would pass every weight check and
        # still diverge from the source model
        validate_rope_scaling(self.rope_scaling)
        if rope_type(self.rope_scaling) == "yarn" and self.fused_kernels:
            raise ValueError(
                "rope_scaling type 'yarn' scales cos and sin, which the fused "
                "norm -> qkv -> rope prologue (fused_kernels) does not")
        if self.sliding_window is not None:
            if self.sliding_window <= 0:
                raise ValueError(
                    f"sliding_window must be positive, got {self.sliding_window}"
                )
            if not self.causal:
                raise ValueError(
                    "sliding_window requires causal attention (the band is "
                    "a causal-mask refinement)"
                )
            if self.attention_impl == "ring":
                raise ValueError(
                    "sliding_window is not supported by ring attention — "
                    "use attention_impl 'flash'/'xla'/None (flash's "
                    "band-skip already bounds work and memory at "
                    "window << seq)"
                )
        if self.layer_windows is not None:
            self.layer_windows = tuple(self.layer_windows)
            if len(self.layer_windows) != self.num_layers:
                raise ValueError(
                    f"layer_windows has {len(self.layer_windows)} entries "
                    f"for {self.num_layers} layers"
                )
            if self.sliding_window is not None:
                raise ValueError(
                    "set either sliding_window (homogeneous) or "
                    "layer_windows (per-layer), not both"
                )
            if not self.causal:
                raise ValueError("layer_windows requires causal attention")
            if self.attention_impl in ("ring", "flash"):
                raise ValueError(
                    "per-layer windows ride the scan as traced values, "
                    "which only the xla attention path supports — use "
                    "attention_impl 'xla' or None"
                )
        if self.attention_class not in ATTENTION_CLASSES:
            raise ValueError(
                f"unknown attention_class {self.attention_class!r}; "
                f"supported: {', '.join(map(repr, ATTENTION_CLASSES))}"
            )
        if self.attention_class == "eva":
            if self.chunk_size < 1 or self.window_size % self.chunk_size:
                raise ValueError(
                    f"window_size {self.window_size} must be whole chunks of "
                    f"chunk_size {self.chunk_size}"
                )
            clash = [
                name for name, on in (
                    ("causal=False", not self.causal),
                    ("sliding_window", self.sliding_window is not None),
                    ("layer_windows", self.layer_windows is not None),
                    ("layer_types", self.layer_types is not None),
                    ("attn_softcap", self.attn_softcap is not None),
                    ("attention_impl='ring'", self.attention_impl == "ring"),
                    ("fused_kernels", self.fused_kernels),
                ) if on
            ]
            if clash:
                raise ValueError(
                    "attention_class 'eva' fixes what a query sees and how "
                    f"it is scored; it cannot be combined with {clash}"
                )
        if self.num_pred_heads < 1 or (
            self.num_pred_heads > 1 and self.tie_embeddings
        ):
            raise ValueError(
                f"num_pred_heads {self.num_pred_heads}: at least 1, and more "
                "than one only with an untied head"
            )
        if self.fp32_residual and self.fused_kernels:
            raise ValueError(
                "fp32_residual: the fused norm -> qkv prologue (fused_kernels) "
                "reads a residual stream of the compute dtype"
            )
        if self.fp32_logits and self.tie_embeddings:
            raise ValueError(
                "fp32_logits is written for an untied head (lm_head); the "
                "tied head's matmul is the embedding's own"
            )
        if self.layer_types is not None:
            self.layer_types = tuple(self.layer_types)
            if len(self.layer_types) != self.num_layers:
                raise ValueError(
                    f"layer_types has {len(self.layer_types)} entries for "
                    f"{self.num_layers} layers"
                )
            unknown = set(self.layer_types) - set(LAYER_TYPES + FF_LAYER_TYPES)
            if unknown:
                raise ValueError(
                    f"unknown layer_types {sorted(unknown)}; supported: "
                    f"{', '.join(LAYER_TYPES + FF_LAYER_TYPES)}"
                )
            alone = set(self.layer_types) & {"mamba", *FF_LAYER_TYPES}
            if alone and not self.single_sublayer:
                raise ValueError(
                    f"layer_types {sorted(alone)} name a layer that is one "
                    "sublayer: set single_sublayer"
                )
            if self.layer_windows is not None:
                raise ValueError(
                    "layer_windows and layer_types cannot be combined: the "
                    "per-layer window rides ONE homogeneous scan as a traced "
                    "value; layer_types 'sliding_attention' gives the "
                    "sliding layers sliding_window as a static band"
                )
            if ("sliding_attention" in self.layer_types) != (
                    self.sliding_window is not None):
                raise ValueError(
                    "layer_types 'sliding_attention' layers take their band "
                    "from sliding_window, and beside layer_types "
                    "sliding_window belongs to them alone: set both or "
                    "neither"
                )
            if self.conv_L_cache < 1:
                raise ValueError(
                    f"conv_L_cache must be >= 1, got {self.conv_L_cache}"
                )
        self._validate_single_sublayer()
        self._validate_gated_layers()
        self._validate_latent_attention()
        if self.rope_layout is not None:
            self.rope_layout = tuple(bool(r) for r in self.rope_layout)
            clash = [
                name for name, on in (
                    ("use_rope=False", not self.use_rope),
                    ("attention_class", self.attention_class is not None),
                    ("fused_kernels", self.fused_kernels),
                    ("kv_lora_rank", self.kv_lora_rank is not None),
                    ("single_sublayer", self.single_sublayer),
                ) if on
            ]
            if len(self.rope_layout) != self.num_layers or clash:
                raise ValueError(
                    f"rope_layout: one entry a layer ({self.num_layers}), got "
                    f"{len(self.rope_layout)}; it cannot be combined with "
                    f"{clash}")
        if self.moe_router_pre_attention and (
                self.num_experts == 0 or self.single_sublayer
                or self.kv_lora_rank is not None or self.fused_kernels
                or set(self.layer_types or ()) - {
                    "full_attention", "sliding_attention"}):
            raise ValueError(
                "moe_router_pre_attention: the router of an expert layer "
                "reads its attention sublayer's input; it needs experts, "
                "layers of per-head attention then feed-forward and no "
                "fused_kernels")
        if not self.use_rope:
            clash = [
                name for name, on in (
                    ("attention_class", self.attention_class is not None),
                    ("fused_kernels", self.fused_kernels),
                    ("rope_scaling", bool(self.rope_scaling)),
                ) if on
            ]
            if clash:
                raise ValueError(
                    "use_rope=False: attention that carries no position "
                    f"cannot be combined with {clash}, which rotate q and k"
                )
        if not 0 <= self.num_dense_layers <= self.num_layers:
            raise ValueError(
                f"num_dense_layers {self.num_dense_layers} outside "
                f"[0, {self.num_layers}]"
            )
        if self.moe_router not in ("softmax", "sigmoid"):
            raise ValueError(
                f"unknown moe_router {self.moe_router!r}; supported: "
                "softmax, sigmoid"
            )
        if self.num_experts > 0:
            width = self.moe_router_width or self.num_experts
            if not (0 <= self.moe_expert_offset
                    and self.moe_expert_offset + self.num_experts <= width):
                raise ValueError(
                    f"experts [{self.moe_expert_offset}, "
                    f"{self.moe_expert_offset + self.num_experts}) do not lie "
                    f"inside the router's {width} outputs"
                )
            if self.num_experts_per_tok > width:
                raise ValueError(
                    f"num_experts_per_tok {self.num_experts_per_tok} exceeds "
                    f"the router's {width} outputs"
                )
            if width != self.num_experts and self.moe_dispatch not in (
                "auto", "ragged"
            ):
                raise ValueError(
                    "a layer that holds a share of the experts computes it "
                    "through the ragged dispatch only (moe_dispatch 'auto' "
                    "or 'ragged')"
                )
            groups, kept = self.moe_n_group, self.moe_topk_group
            if (groups < 1 or width % groups or not 1 <= kept <= groups
                    or self.num_experts_per_tok > kept * (width // groups)
                    or (groups > 1 and (width // groups < 2
                                        or self.moe_router != "sigmoid"))):
                raise ValueError(
                    f"moe_n_group {groups} / moe_topk_group {kept}: the "
                    f"router's {width} outputs in equal groups of at least "
                    "two, 1 <= moe_topk_group <= moe_n_group, the kept groups "
                    f"hold the {self.num_experts_per_tok} choices, and the "
                    "group limit is written for moe_router 'sigmoid'"
                )
            if not self.mlp_gated and self.moe_dispatch == "capacity":
                raise ValueError(
                    "mlp_gated=False: experts without a gate matrix run "
                    "through moe_dispatch 'auto', 'ragged' or 'dense'"
                )
            if self.mlp_gated and self.mlp_activation == "relu2":
                raise ValueError(
                    "mlp_activation 'relu2' with gated experts: the gated "
                    "expert's activation is silu; relu2 experts are "
                    "mlp_gated=False"
                )
            if (self.moe_shared_gate
                    and self.moe_shared_intermediate_size is None):
                raise ValueError(
                    "moe_shared_gate gates a shared expert: set "
                    "moe_shared_intermediate_size"
                )
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads
        if self.head_dim is None:
            assert self.hidden_size % self.num_heads == 0
            self.head_dim = self.hidden_size // self.num_heads
        assert self.num_heads % self.num_kv_heads == 0

    def _validate_single_sublayer(self):
        """``single_sublayer`` and the Mamba-2 sizes, each clash by name."""
        types = self.layer_types or ()
        if self.single_sublayer:
            clash = [
                name for name, on in (
                    ("layer_types=None", self.layer_types is None),
                    ("num_dense_layers", self.num_dense_layers > 0),
                    ("post_norms", self.post_norms),
                    ("fused_kernels", self.fused_kernels),
                    ("attention_class", self.attention_class is not None),
                ) if on
            ]
            if clash:
                raise ValueError(
                    "single_sublayer: every layer is the one sublayer its "
                    f"layer_types entry names; it cannot be combined with {clash}"
                )
            if "moe" in types and self.num_experts < 1:
                raise ValueError(
                    "layer_types names a 'moe' layer and num_experts is 0")
        if "mamba" in types:
            if (self.mamba_num_heads < 1 or self.mamba_n_groups < 1
                    or self.mamba_num_heads % self.mamba_n_groups):
                raise ValueError(
                    f"mamba_num_heads {self.mamba_num_heads} must be a "
                    f"positive multiple of mamba_n_groups {self.mamba_n_groups}"
                )
            if self.mamba_conv_kernel < 1 or self.mamba_chunk_size < 1:
                raise ValueError(
                    f"mamba_conv_kernel {self.mamba_conv_kernel} and "
                    f"mamba_chunk_size {self.mamba_chunk_size} must be >= 1"
                )

    def _validate_gated_layers(self):
        """The Gated DeltaNet sizes, partial rotary and the attention output
        gate, each clash by name."""
        if "linear_attention" in (self.layer_types or ()):
            if (self.gdn_num_k_heads < 1 or self.gdn_num_v_heads < 1
                    or self.gdn_num_v_heads % self.gdn_num_k_heads):
                raise ValueError(
                    f"gdn_num_v_heads {self.gdn_num_v_heads} must be a "
                    f"positive multiple of gdn_num_k_heads {self.gdn_num_k_heads}"
                )
            if (self.gdn_head_k_dim < 1 or self.gdn_head_v_dim < 1
                    or self.gdn_conv_kernel < 2):
                raise ValueError(
                    f"gdn_head_k_dim {self.gdn_head_k_dim} and gdn_head_v_dim "
                    f"{self.gdn_head_v_dim} must be >= 1, gdn_conv_kernel "
                    f"{self.gdn_conv_kernel} >= 2"
                )
            if self.single_sublayer:
                raise ValueError(
                    "layer_types 'linear_attention' names an operator that a "
                    "feed-forward follows: not with single_sublayer"
                )
        if not 0.0 < self.partial_rotary_factor <= 1.0:
            raise ValueError(
                f"partial_rotary_factor {self.partial_rotary_factor} outside (0, 1]"
            )
        for name, on in (
            ("partial_rotary_factor", self.partial_rotary_factor != 1.0),
            ("attn_output_gate", self.attn_output_gate),
        ):
            clash = [
                other for other, live in (
                    ("fused_kernels", self.fused_kernels),
                    ("attention_class", self.attention_class is not None),
                ) if on and live
            ]
            if clash:
                raise ValueError(
                    f"{name} is written for plain softmax attention, unfused: "
                    f"it cannot be combined with {clash}"
                )

    def _validate_latent_attention(self):
        """Latent attention's sizes, and each thing written for per-head K
        and V that it cannot be combined with, by name."""
        if self.kv_lora_rank is None:
            if self.q_lora_rank is not None:
                raise ValueError(
                    "q_lora_rank is latent attention's query bottleneck: set "
                    "kv_lora_rank too")
            return
        sizes = (self.q_lora_rank, self.kv_lora_rank, self.qk_nope_head_dim,
                 self.qk_rope_head_dim, self.v_head_dim)
        if None in sizes or min(sizes) < 1 or self.qk_rope_head_dim % 2:
            raise ValueError(
                f"latent attention: q_lora_rank, kv_lora_rank, qk_nope_head_dim,"
                f" qk_rope_head_dim (even) and v_head_dim must be set and "
                f"positive, got {sizes}")
        width = self.qk_nope_head_dim + self.qk_rope_head_dim
        clash = [
            name for name, on in (
                ("num_kv_heads", self.num_kv_heads not in (None, self.num_heads)),
                ("head_dim", self.head_dim not in (None, width)),
                ("qkv_bias", self.qkv_bias),
                ("qk_norm", self.qk_norm),
                ("attn_output_gate", self.attn_output_gate),
                ("partial_rotary_factor", self.partial_rotary_factor != 1.0),
                ("use_rope=False", not self.use_rope),
                ("causal=False", not self.causal),
                ("sliding_window", self.sliding_window is not None),
                ("layer_windows", self.layer_windows is not None),
                ("attn_softcap", self.attn_softcap is not None),
                ("query_pre_attn_scalar", self.query_pre_attn_scalar is not None),
                ("attention_class", self.attention_class is not None),
                ("attention_impl='ring'", self.attention_impl == "ring"),
                ("fused_kernels", self.fused_kernels),
                ("fp8", self.fp8),
                ("single_sublayer", self.single_sublayer),
                ("layer_types", any(
                    t != "full_attention" for t in self.layer_types or ())),
            ) if on
        ]
        if clash:
            raise ValueError(
                "latent attention (kv_lora_rank) shares one latent row a "
                "position among all heads and rotates a part of the key of "
                f"its own; it cannot be combined with {clash}")
        self.head_dim = width

    # ------------------------------------------------------------------ #
    # presets (BASELINE.md model families)
    # ------------------------------------------------------------------ #
    @classmethod
    def tiny(cls, **kw) -> "TransformerConfig":
        kw.setdefault("vocab_size", 1024)
        kw.setdefault("hidden_size", 128)
        kw.setdefault("intermediate_size", 352)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_heads", 4)
        kw.setdefault("max_seq_len", 256)
        return cls(**kw)

    @classmethod
    def bert_base(cls, **kw) -> "TransformerConfig":
        """BERT-base shape (the reference's nlp_example.py fine-tune target,
        examples/nlp_example.py: bert-base-cased). Bidirectional attention;
        rope replaces learned positions — the TPU build's encoder idiom."""
        kw.setdefault("vocab_size", 30522)
        kw.setdefault("hidden_size", 768)
        kw.setdefault("intermediate_size", 3072)
        kw.setdefault("num_layers", 12)
        kw.setdefault("num_heads", 12)
        kw.setdefault("max_seq_len", 512)
        kw.setdefault("causal", False)
        kw.setdefault("tie_embeddings", True)
        return cls(**kw)

    @classmethod
    def gpt2(cls, **kw) -> "TransformerConfig":
        """The FAITHFUL classic architecture (models/gpt2.GPT2LM):
        learned positions, LayerNorm, biases, GELU — real ``gpt2`` hub
        checkpoints load with matching logits."""
        kw.setdefault("arch", "gpt2")
        kw.setdefault("vocab_size", 50257)
        kw.setdefault("hidden_size", 768)
        kw.setdefault("intermediate_size", 3072)
        kw.setdefault("num_layers", 12)
        kw.setdefault("num_heads", 12)
        kw.setdefault("max_seq_len", 1024)
        kw.setdefault("rms_norm_eps", 1e-5)
        kw.setdefault("tie_embeddings", True)
        return cls(**kw)

    @classmethod
    def llama3_8b(cls, **kw) -> "TransformerConfig":
        kw.setdefault("vocab_size", 128256)
        kw.setdefault("hidden_size", 4096)
        kw.setdefault("intermediate_size", 14336)
        kw.setdefault("num_layers", 32)
        kw.setdefault("num_heads", 32)
        kw.setdefault("num_kv_heads", 8)
        kw.setdefault("max_seq_len", 8192)
        return cls(**kw)

    @classmethod
    def llama3_70b(cls, **kw) -> "TransformerConfig":
        kw.setdefault("vocab_size", 128256)
        kw.setdefault("hidden_size", 8192)
        kw.setdefault("intermediate_size", 28672)
        kw.setdefault("num_layers", 80)
        kw.setdefault("num_heads", 64)
        kw.setdefault("num_kv_heads", 8)
        kw.setdefault("max_seq_len", 8192)
        return cls(**kw)

    @classmethod
    def qwen2_7b(cls, **kw) -> "TransformerConfig":
        """Qwen2-7B shape (the qkv-bias interop family)."""
        kw.setdefault("vocab_size", 152064)
        kw.setdefault("hidden_size", 3584)
        kw.setdefault("intermediate_size", 18944)
        kw.setdefault("num_layers", 28)
        kw.setdefault("num_heads", 28)
        kw.setdefault("num_kv_heads", 4)
        kw.setdefault("max_seq_len", 32768)
        kw.setdefault("rope_theta", 1000000.0)
        kw.setdefault("qkv_bias", True)
        return cls(**kw)

    @classmethod
    def t5_base(cls, **kw) -> "TransformerConfig":
        """T5-base shape family (reference megatron t5 parser
        utils/megatron_lm.py:1717): 12+12 layers, 768 hidden. SwiGLU/rope
        replace relu/relative-bias — capability parity, modernized arch."""
        kw.setdefault("vocab_size", 32128)
        kw.setdefault("hidden_size", 768)
        kw.setdefault("intermediate_size", 2048)
        kw.setdefault("num_layers", 12)
        kw.setdefault("num_decoder_layers", 12)
        kw.setdefault("num_heads", 12)
        kw.setdefault("max_seq_len", 512)
        kw.setdefault("tie_embeddings", True)
        return cls(**kw)

    @classmethod
    def mixtral_8x7b(cls, **kw) -> "TransformerConfig":
        kw.setdefault("vocab_size", 32000)
        kw.setdefault("hidden_size", 4096)
        kw.setdefault("intermediate_size", 14336)
        kw.setdefault("num_layers", 32)
        kw.setdefault("num_heads", 32)
        kw.setdefault("num_kv_heads", 8)
        kw.setdefault("num_experts", 8)
        kw.setdefault("num_experts_per_tok", 2)
        kw.setdefault("max_seq_len", 4096)
        return cls(**kw)

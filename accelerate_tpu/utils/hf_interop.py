"""HF-checkpoint interop: bidirectional name mapping between HF-format
safetensors checkpoints (Llama / Mixtral key conventions) and this
package's native pytrees (the stacked ``nn.scan`` layout).

This is the capability behind the reference's whole raison d'être —
running *real* pretrained models: ``load_checkpoint_in_model``
(reference utils/modeling.py:1608) and ``load_checkpoint_and_dispatch``
(reference big_modeling.py:499) consume actual HF hub safetensors. The
TPU-native twist is the *layout* translation, not hooks:

* per-layer HF keys (``model.layers.{i}.self_attn.q_proj.weight``) map
  onto ONE stacked leaf per projection (``layers//attn//q_proj//kernel``
  with a leading ``num_layers`` dim) — the ``nn.scan`` layout that keeps
  XLA compile time flat in depth;
* torch ``nn.Linear`` stores kernels ``(out, in)``; flax ``nn.Dense``
  stores ``(in, out)`` — every projection transposes;
* Mixtral's per-expert modules (``block_sparse_moe.experts.{e}.w1``) map
  onto expert-stacked leaves ``(L, E, H, F)`` whose leading expert axis
  carries the ``expert`` logical name (GSPMD expert parallelism);
* tied embeddings follow the HF convention: ``lm_head.weight`` is
  omitted on save when ``config.tie_embeddings`` and re-tied on load.

GQA needs no re-packing: HF stores q/k/v separately with head-major
feature order, which is exactly the transposed native kernel layout.

Rope compatibility: both sides use the GPT-NeoX-style half-split
rotation (HF ``rotate_half`` == models/transformer.rope), so weights
interchange without any permutation of head dims.

Architectures covered: the Llama family (Llama-2/3/3.1+ incl. GQA,
llama3/linear rope scaling, tied or untied heads), Mistral (the Llama
layout + every-layer sliding window — ``TransformerConfig.sliding_window``
— incl. NeMo's decoupled head_dim), Qwen2 (the Llama layout plus q/k/v
biases — ``TransformerConfig.qkv_bias``; sliding window incl. per-layer
mixes via ``layer_windows``), Gemma v1 (offset RMSNorm / tanh-GELU gate /
scaled embeddings — ``norm_offset``/``mlp_activation``/``embed_scale``),
Gemma-2 (the v1 trio plus ``post_norms`` 4-norm blocks,
``query_pre_attn_scalar``, ``attn_softcap``/``final_softcap`` tanh
capping, and the alternating sliding/full pattern as ``layer_windows``;
Gemma-3 rejected),
Mixtral-style MoE (``sliding_window`` honored) — the BASELINE.md targets
(Llama-3-8B FSDP, Mixtral 8x7B EP,
Llama-3-70B device_map="auto") — and classic GPT-2 via the faithful
:class:`~...models.gpt2.GPT2LM` (learned positions, LayerNorm, biases,
fused c_attn; HF Conv1D already stores ``(in, out)`` so that mapping has
no transposes).
BERT/T5 checkpoints do NOT map: this package's encoder/seq2seq are
modernized architectures (RMSNorm + rope + SwiGLU, no biases) with no
faithful parameter correspondence; they train from scratch or load
native checkpoints. README.md carries the user-facing compatibility
matrix.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Callable, Iterator

import numpy as np

from ..logging import get_logger

logger = get_logger(__name__)

# HF's file-naming convention happens to equal this package's native one
# (constants.SAFE_WEIGHTS_*): both write model.safetensors(+index). Format
# is therefore detected from tensor KEYS, never file names.
from .constants import SAFE_WEIGHTS_INDEX_NAME as _HF_INDEX_NAME
from .constants import SAFE_WEIGHTS_NAME as _HF_WEIGHTS_NAME


# ---------------------------------------------------------------------- #
# checkpoint introspection
# ---------------------------------------------------------------------- #
def list_hf_checkpoint_files(checkpoint: str) -> list[str]:
    """Safetensors files making up ``checkpoint`` (dir or single file)."""
    if os.path.isdir(checkpoint):
        index_path = os.path.join(checkpoint, _HF_INDEX_NAME)
        if os.path.isfile(index_path):
            with open(index_path) as f:
                weight_map = json.load(f)["weight_map"]
            return [
                os.path.join(checkpoint, f) for f in sorted(set(weight_map.values()))
            ]
        single = os.path.join(checkpoint, _HF_WEIGHTS_NAME)
        if os.path.isfile(single):
            return [single]
        raise FileNotFoundError(f"no safetensors files under {checkpoint}")
    return [checkpoint]


def list_checkpoint_keys(checkpoint: str) -> list[str]:
    """All tensor names in the checkpoint without loading any data
    (reads only safetensors headers / the index json)."""
    if os.path.isdir(checkpoint):
        for index_name in (_HF_INDEX_NAME,):
            index_path = os.path.join(checkpoint, index_name)
            if os.path.isfile(index_path):
                with open(index_path) as f:
                    return sorted(json.load(f)["weight_map"])
    from safetensors import safe_open

    keys: list[str] = []
    for path in list_hf_checkpoint_files(checkpoint):
        with safe_open(path, framework="numpy") as f:
            keys.extend(f.keys())
    return sorted(keys)


# The canonical hub GPT-2 checkpoints (gpt2, gpt2-medium, ...) store the
# BASE model's keys unprefixed (``wte.weight``, ``h.0.attn.c_attn.weight``);
# transformers re-prefixes them via ``base_model_prefix`` at load. A local
# ``GPT2LMHeadModel.save_pretrained`` writes the prefixed layout. Both are
# real-world GPT-2 checkpoints; both must detect and load.
def _is_unprefixed_gpt2_key(k: str) -> bool:
    return (
        k in ("wte.weight", "wpe.weight")
        or k.startswith("ln_f.")
        or re.match(r"h\.\d+\.", k) is not None
    )


def is_hf_checkpoint(checkpoint: str) -> bool:
    """True when the checkpoint uses HF transformers key conventions
    (``model.embed_tokens.weight`` / ``model.layers.{i}...`` for the
    Llama family, ``transformer.wte.weight`` / ``transformer.h.{i}...``
    — or the hub's unprefixed base-model layout ``wte.weight`` /
    ``h.{i}...`` — for GPT-2) rather than this package's native
    ``//``-joined pytree paths."""
    try:
        keys = list_checkpoint_keys(checkpoint)
    except (FileNotFoundError, OSError):
        return False
    return any(
        k == "model.embed_tokens.weight"
        or k.startswith("model.layers.")
        or k == "transformer.wte.weight"
        or k.startswith("transformer.h.")
        or _is_unprefixed_gpt2_key(k)
        for k in keys
    )




def infer_config_from_hf(checkpoint: str, **overrides) -> "Any":
    """Build a :class:`TransformerConfig` from an HF ``config.json`` living
    next to the weights (the reference reads the same file through
    ``AutoConfig``; utils/modeling.py consumes its dtype/shape fields)."""
    from ..models.config import TransformerConfig

    cfg_path = os.path.join(checkpoint, "config.json")
    if not os.path.isfile(cfg_path):
        raise FileNotFoundError(
            f"{cfg_path} not found — pass a TransformerConfig explicitly"
        )
    with open(cfg_path) as f:
        hf = json.load(f)
    model_type = hf.get("model_type", "llama")
    if model_type == "gpt2":
        act = hf.get("activation_function", "gelu_new")
        if act not in ("gelu_new", "gelu_pytorch_tanh"):
            # the native GPT2LM hard-codes tanh-GELU; a relu/gelu-exact
            # checkpoint would load every tensor and still diverge
            raise ValueError(
                f"GPT-2 activation_function {act!r} is not the tanh GELU "
                "the native GPT2LM implements"
            )
        # attention-math variants with IDENTICAL tensor layouts: every
        # weight would map and logits would silently diverge — same
        # rejection class as activation_function above
        if (
            not hf.get("scale_attn_weights", True)
            or hf.get("scale_attn_by_inverse_layer_idx", False)
            or hf.get("reorder_and_upcast_attn", False)
        ):
            raise ValueError(
                "GPT-2 checkpoints with scale_attn_weights=False, "
                "scale_attn_by_inverse_layer_idx or reorder_and_upcast_attn "
                "use attention math the native GPT2LM does not implement"
            )
        kw = dict(
            arch="gpt2",
            vocab_size=hf["vocab_size"],
            hidden_size=hf["n_embd"],
            intermediate_size=hf.get("n_inner") or 4 * hf["n_embd"],
            num_layers=hf["n_layer"],
            num_heads=hf["n_head"],
            max_seq_len=hf.get("n_positions", hf.get("n_ctx", 1024)),
            rms_norm_eps=float(hf.get("layer_norm_epsilon", 1e-5)),
            tie_embeddings=True,  # GPT-2 always ties
        )
        kw.update(overrides)
        return TransformerConfig(**kw)
    # rope_scaling (llama3 / linear / yarn applied natively; others rejected)
    # is validated by TransformerConfig.__post_init__ — the construction
    # below fails loudly, including on parameter keys missing for the
    # declared type, so nothing can only blow up at trace time.
    rope_scaling = hf.get("rope_scaling")
    # sliding-window resolution (transformers semantics): Mistral and
    # Mixtral apply the band to EVERY layer when config.sliding_window is
    # set (modeling_mistral.py:355, modeling_mixtral.py:448); Qwen2
    # zeroes it unless use_sliding_window
    # (configuration_qwen2.py:181) and then derives per-layer layer_types
    # with layers >= max_window_layers sliding (:204-209); Gemma-2
    # alternates sliding/full every other layer
    # (configuration_gemma2.py:176-179). Homogeneous patterns collapse to
    # ``sliding_window``; genuine mixes ride the scan as per-layer
    # ``layer_windows``.
    def _resolve_layer_types(layer_types, w):
        kinds = set(layer_types)
        if kinds == {"full_attention"}:
            return None, None
        if w is None:
            # a null/absent band with sliding layers declared would load
            # every tensor and silently run full attention — same loud-
            # rejection class as the semantics-changing fields above
            raise ValueError(
                "layer_types declares 'sliding_attention' layers but "
                "config sliding_window is null/absent; refusing to load "
                "the checkpoint as full attention"
            )
        if kinds == {"sliding_attention"}:
            return w, None
        return None, tuple(
            w if t == "sliding_attention" else None for t in layer_types
        )

    sliding_window = layer_windows = None
    if model_type in ("mistral", "mixtral"):
        sliding_window = hf.get("sliding_window")
    elif model_type == "qwen2" and hf.get("use_sliding_window", False):
        w = hf.get("sliding_window")
        layer_types = hf.get("layer_types")
        if layer_types is None and w is not None:
            n = hf["num_hidden_layers"]
            layer_types = [
                "sliding_attention"
                if i >= hf.get("max_window_layers", 28)
                else "full_attention"
                for i in range(n)
            ]
        if layer_types is not None:
            sliding_window, layer_windows = _resolve_layer_types(
                layer_types, w
            )
    elif model_type == "gemma2":
        w = hf.get("sliding_window", 4096)
        n = hf["num_hidden_layers"]
        layer_types = hf.get("layer_types") or [
            "sliding_attention" if (i + 1) % 2 else "full_attention"
            for i in range(n)
        ]
        sliding_window, layer_windows = _resolve_layer_types(layer_types, w)
    if model_type in ("gemma3", "gemma3_text"):
        # Gemma-3 adds q/k norms and per-layer-type rope bases — math the
        # native model does not implement; every tensor of the shared
        # keys would load and logits would silently diverge
        raise ValueError(
            f"HF model_type {model_type!r} is not supported: Gemma-3 "
            "qk-norms / dual rope bases are not implemented (Gemma v1 "
            "loads via model_type 'gemma', Gemma-2 via 'gemma2')"
        )
    if model_type not in (
        "llama", "mistral", "mixtral", "qwen2", "gemma", "gemma2"
    ):
        # Phi/... share the model.layers.* key convention and every
        # config field this mapping reads, but differ in parameters the
        # plan would silently drop — loading them would succeed and
        # generate garbage.
        raise ValueError(
            f"HF model_type {model_type!r} is not supported by the "
            "parameter mappings; supported: llama, mistral, mixtral, "
            "qwen2, gemma, gpt2"
        )
    kw = dict(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        max_seq_len=hf.get("max_position_embeddings", 2048),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rope_scaling=rope_scaling,
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        sliding_window=sliding_window,
        layer_windows=layer_windows,
        # the Qwen2 convention: biases on q/k/v only (hard-wired in the
        # arch, not a config.json field)
        qkv_bias=model_type == "qwen2",
    )
    if model_type == "mistral" and hf.get("head_dim"):
        # Mistral-NeMo decouples head_dim from hidden/num_heads
        kw["head_dim"] = hf["head_dim"]
    if model_type in ("gemma", "gemma2"):
        act = hf.get("hidden_activation") or hf.get("hidden_act")
        if act not in (None, "gelu", "gelu_pytorch_tanh"):
            raise ValueError(
                f"Gemma hidden_activation {act!r} is not the tanh GELU "
                "the native model implements"
            )
        # Gemma v1: Llama's key layout, different math — offset RMSNorm,
        # tanh-GELU gate, sqrt(h)-scaled embeddings, always-tied heads,
        # and an explicit head_dim decoupled from hidden/num_heads
        kw.update(
            norm_offset=True,
            mlp_activation="gelu_tanh",
            embed_scale=True,
            tie_embeddings=True,
            head_dim=hf.get("head_dim"),
        )
    if model_type == "gemma2":
        # Gemma-2 on top of the v1 trio: 4 norms per block, decoupled
        # attention scale, tanh soft-capping on scores and final logits
        # (transformers modeling_gemma2.py:185-189,566-569)
        kw.update(
            post_norms=True,
            query_pre_attn_scalar=float(
                hf.get("query_pre_attn_scalar", 256)
            ),
            # transformers defaults the caps to 50/30
            # (configuration_gemma2.py:143-144) — a config.json omitting
            # the keys still soft-caps there, so it must here too
            attn_softcap=hf.get("attn_logit_softcapping", 50.0),
            final_softcap=hf.get("final_logit_softcapping", 30.0),
        )
    if hf.get("num_local_experts"):
        kw["num_experts"] = hf["num_local_experts"]
        kw["num_experts_per_tok"] = hf.get("num_experts_per_tok", 2)
    kw.update(overrides)
    return TransformerConfig(**kw)


# ---------------------------------------------------------------------- #
# native name -> HF key plan
# ---------------------------------------------------------------------- #
_ATTN = {"q_proj": "q_proj", "k_proj": "k_proj", "v_proj": "v_proj", "o_proj": "o_proj"}
_MLP = {"gate_proj": "gate_proj", "up_proj": "up_proj", "down_proj": "down_proj"}
_NORMS = {"attn_norm": "input_layernorm", "mlp_norm": "post_attention_layernorm"}
# Gemma-2's 4-norm block: HF's post_attention_layernorm is the norm AFTER
# attention (native post_attn_norm), and the pre-MLP norm is
# pre_feedforward_layernorm (native mlp_norm)
_NORMS_POST = {
    "attn_norm": "input_layernorm",
    "post_attn_norm": "post_attention_layernorm",
    "mlp_norm": "pre_feedforward_layernorm",
    "post_mlp_norm": "post_feedforward_layernorm",
}
# Mixtral expert weights: w1 = gate, w3 = up, w2 = down (transposed)
_MOE_EXPERT = {"gate_proj": "w1", "up_proj": "w3", "down_proj": "w2"}


def _normalize(name: str) -> tuple[str, ...]:
    """Native flat name -> path parts, dropping the trailing ``value``
    that boxed (nn.Partitioned) trees carry."""
    from ..checkpointing import _SEP

    parts = tuple(name.split(_SEP))
    if parts and parts[-1] == "value":
        parts = parts[:-1]
    return parts


class _HfPlanEntry:
    """How to assemble one native leaf from HF tensors.

    ``keys``: HF tensor names, one per (layer[, expert]) slice; ``stack``
    0 = single tensor, 1 = stack over layers, 2 = stack layers x experts;
    ``transpose``: apply ``.T`` to each 2-D HF tensor before stacking.
    """

    __slots__ = ("keys", "stack", "transpose")

    def __init__(self, keys, stack: int, transpose: bool):
        self.keys, self.stack, self.transpose = keys, stack, transpose


# GPT-2 maps: native (sub-)path -> HF suffix. Conv1D stores (in, out) =
# the flax kernel layout, so NOTHING transposes.
_GPT2_TOP = {
    ("wte", "embedding"): "transformer.wte.weight",
    ("wpe", "embedding"): "transformer.wpe.weight",
    ("ln_f", "scale"): "transformer.ln_f.weight",
    ("ln_f", "bias"): "transformer.ln_f.bias",
}
_GPT2_PARAM = {"kernel": "weight", "scale": "weight", "bias": "bias"}
_GPT2_INNER = {
    ("ln_1",): "ln_1",
    ("ln_2",): "ln_2",
    ("attn", "c_attn"): "attn.c_attn",
    ("attn", "c_proj"): "attn.c_proj",
    ("mlp", "c_fc"): "mlp.c_fc",
    ("mlp", "c_proj"): "mlp.c_proj",
}


def _plan_for_gpt2(parts: tuple[str, ...], config) -> _HfPlanEntry:
    """GPT-2 assembly plan (classic-arch interop, models/gpt2.py):
    ``transformer.h.{i}.*`` per-layer keys stack onto the scan layout, no
    transposes (HF Conv1D already stores ``(in, out)``)."""
    if parts in _GPT2_TOP:
        return _HfPlanEntry([_GPT2_TOP[parts]], 0, False)
    first = parts[0]
    if first == "layers":
        idxs: list[int] = list(range(config.num_layers))
    else:
        m = re.fullmatch(r"layer_(\d+)", first)
        if not m:
            raise KeyError(f"no GPT-2 HF mapping for native path {parts}")
        idxs = [int(m.group(1))]
    inner, param = parts[1:-1], parts[-1]
    if inner in _GPT2_INNER and param in _GPT2_PARAM:
        suffix = f"{_GPT2_INNER[inner]}.{_GPT2_PARAM[param]}"
        return _HfPlanEntry(
            [f"transformer.h.{i}.{suffix}" for i in idxs], 1, False
        )
    raise KeyError(f"no GPT-2 HF mapping for native path {parts}")


def _plan_for(parts: tuple[str, ...], config) -> _HfPlanEntry:
    """Assembly plan for one native param path; raises KeyError for paths
    with no HF counterpart."""
    if getattr(config, "arch", "llama") == "gpt2":
        return _plan_for_gpt2(parts, config)
    L = config.num_layers

    def layer_indices(first: str) -> tuple[list[int], tuple[str, ...]]:
        # scan layout: ("layers", rest...) covers all L layers at once;
        # unrolled layout: ("layer_{i}", rest...) covers exactly one.
        if first == "layers":
            return list(range(L)), parts[1:]
        m = re.fullmatch(r"layer_(\d+)", first)
        if m:
            return [int(m.group(1))], parts[1:]
        raise KeyError(f"unrecognized native param path {parts}")

    if parts == ("embed", "embedding"):
        return _HfPlanEntry(["model.embed_tokens.weight"], 0, False)
    if parts == ("final_norm", "scale"):
        return _HfPlanEntry(["model.norm.weight"], 0, False)
    if parts == ("lm_head", "kernel"):
        return _HfPlanEntry(["lm_head.weight"], 0, True)
    if parts[0] == "layers" or parts[0].startswith("layer_"):
        idxs, rest = layer_indices(parts[0])
        prefix = [f"model.layers.{i}" for i in idxs]
        if len(rest) == 3 and rest[0] == "attn" and rest[1] in _ATTN and rest[2] == "kernel":
            return _HfPlanEntry(
                [f"{p}.self_attn.{_ATTN[rest[1]]}.weight" for p in prefix], 1, True
            )
        if (
            len(rest) == 3 and rest[0] == "attn" and rest[2] == "bias"
            and rest[1] in ("q_proj", "k_proj", "v_proj")
            and getattr(config, "qkv_bias", False)
        ):
            # Qwen2-family q/k/v biases (1-D: no transpose applies)
            return _HfPlanEntry(
                [f"{p}.self_attn.{_ATTN[rest[1]]}.bias" for p in prefix], 1, False
            )
        norms = _NORMS_POST if getattr(config, "post_norms", False) else _NORMS
        if len(rest) == 2 and rest[0] in norms and rest[1] == "scale":
            return _HfPlanEntry(
                [f"{p}.{norms[rest[0]]}.weight" for p in prefix], 1, False
            )
        if len(rest) == 3 and rest[0] == "mlp" and rest[1] in _MLP and rest[2] == "kernel":
            return _HfPlanEntry(
                [f"{p}.mlp.{_MLP[rest[1]]}.weight" for p in prefix], 1, True
            )
        if len(rest) == 3 and rest[0] == "moe" and rest[1] == "router" and rest[2] == "kernel":
            return _HfPlanEntry(
                [f"{p}.block_sparse_moe.gate.weight" for p in prefix], 1, True
            )
        if len(rest) == 2 and rest[0] == "moe" and rest[1] in _MOE_EXPERT:
            E = config.num_experts
            w = _MOE_EXPERT[rest[1]]
            return _HfPlanEntry(
                [
                    [f"{p}.block_sparse_moe.experts.{e}.{w}.weight" for e in range(E)]
                    for p in prefix
                ],
                2,
                True,
            )
    raise KeyError(f"no HF mapping for native param path {parts}")


def hf_native_reader(
    checkpoint: str, config
) -> Callable[[str], np.ndarray]:
    """Adapter with the signature of ``_lazy_checkpoint_reader``: native
    flat name -> assembled numpy array, reading HF safetensors lazily.

    Peak host memory is ONE assembled native leaf (the stacked projection
    being built) plus one HF tensor — the streaming property the
    reference's shard-by-shard ``load_checkpoint_in_model`` has
    (utils/modeling.py:1692-1712).

    The returned callable additionally exposes ``unconsumed()`` — the
    checkpoint tensors never requested (minus known-inert keys like
    rotary inv_freq buffers, and ``lm_head.weight`` under tied
    embeddings). A non-empty result after a full load means the mapping
    dropped real parameters; :func:`...big_modeling.load_checkpoint_and_dispatch`
    raises on it.
    """
    from safetensors import safe_open

    key_to_file: dict[str, str] = {}
    index_path = (
        os.path.join(checkpoint, _HF_INDEX_NAME)
        if os.path.isdir(checkpoint)
        else None
    )
    if index_path and os.path.isfile(index_path):
        # the index already maps key -> file; avoid opening every shard
        with open(index_path) as f:
            for k, fname in json.load(f)["weight_map"].items():
                key_to_file[k] = os.path.join(checkpoint, fname)
    else:
        for path in list_hf_checkpoint_files(checkpoint):
            with safe_open(path, framework="numpy") as f:
                for k in f.keys():
                    key_to_file[k] = path
    if getattr(config, "arch", "llama") == "gpt2":
        # hub gpt2/gpt2-medium/... store the BASE model's keys unprefixed
        # (wte.weight, h.0.attn.c_attn.weight — transformers re-prefixes
        # via base_model_prefix at load); normalize to the prefixed layout
        # the plan emits so both real-world layouts load identically
        stored_name = {
            (f"transformer.{k}" if _is_unprefixed_gpt2_key(k) else k): k
            for k in key_to_file
        }
        key_to_file = {
            new: key_to_file[old] for new, old in stored_name.items()
        }
    else:
        stored_name = {}
    consumed: set[str] = set()

    def read_hf(key: str) -> np.ndarray:
        consumed.add(key)
        if key not in key_to_file:
            raise KeyError(
                f"HF checkpoint {checkpoint} has no tensor {key!r} "
                f"(available e.g. {sorted(key_to_file)[:4]}...)"
            )
        with safe_open(key_to_file[key], framework="numpy") as f:
            return f.get_tensor(stored_name.get(key, key))

    def maybe_t(a: np.ndarray, transpose: bool) -> np.ndarray:
        return a.T if transpose and a.ndim == 2 else a

    def read_native(name: str) -> np.ndarray:
        parts = _normalize(name)
        if parts == ("lm_head", "kernel") and "lm_head.weight" not in key_to_file:
            # HF tied checkpoints omit lm_head; re-tie from the embedding
            return read_hf("model.embed_tokens.weight").T
        plan = _plan_for(parts, config)
        if plan.stack == 0:
            return np.ascontiguousarray(maybe_t(read_hf(plan.keys[0]), plan.transpose))
        # preallocate the assembled leaf and fill slice-by-slice, so peak
        # host memory really is ONE assembled leaf + one HF tensor (a
        # build-list-then-np.stack would transiently hold ~2x the leaf)
        if plan.stack == 1:
            first = maybe_t(read_hf(plan.keys[0]), plan.transpose)
            out = np.empty((len(plan.keys),) + first.shape, first.dtype)
            out[0] = first
            del first
            for i, k in enumerate(plan.keys[1:], start=1):
                out[i] = maybe_t(read_hf(k), plan.transpose)
        else:  # layers x experts
            first = maybe_t(read_hf(plan.keys[0][0]), plan.transpose)
            out = np.empty(
                (len(plan.keys), len(plan.keys[0])) + first.shape, first.dtype
            )
            out[0, 0] = first
            del first
            for li, expert_keys in enumerate(plan.keys):
                for ei, k in enumerate(expert_keys):
                    if li or ei:
                        out[li, ei] = maybe_t(read_hf(k), plan.transpose)
        # unrolled (layer_{i}) paths carry no leading layer dim
        return out[0] if parts[0].startswith("layer_") else out

    def unconsumed() -> list[str]:
        inert = {"lm_head.weight"} if config.tie_embeddings else set()
        return sorted(
            k
            for k in key_to_file
            if k not in consumed
            and k not in inert
            and not k.endswith(".rotary_emb.inv_freq")
            # GPT-2 causal-mask buffers (older transformers persisted them)
            and not k.endswith(".attn.bias")
            and not k.endswith(".attn.masked_bias")
        )

    read_native.unconsumed = unconsumed
    return read_native


# ---------------------------------------------------------------------- #
# export: native pytree -> HF-format safetensors
# ---------------------------------------------------------------------- #
def native_to_hf(params: Any, config) -> Iterator[tuple[str, np.ndarray]]:
    """Yield ``(hf_key, array)`` pairs for every native leaf, unstacking
    layer (and expert) dims back into per-layer HF keys. Tied embeddings
    follow the HF convention: no ``lm_head.weight`` is emitted."""
    from ..checkpointing import flatten_tree

    named = flatten_tree(params)
    for name, leaf in sorted(named.items()):
        parts = _normalize(name)
        arr = np.asarray(
            leaf.value if hasattr(leaf, "value") else leaf
        )
        plan = _plan_for(parts, config)
        if plan.stack == 0:
            yield plan.keys[0], (arr.T if plan.transpose else arr)
            continue
        if parts[0].startswith("layer_"):  # unrolled: single layer slice
            arr = arr[None]
        if plan.stack == 1:
            for key, sl in zip(plan.keys, arr):
                yield key, np.ascontiguousarray(sl.T if plan.transpose else sl)
        else:
            for expert_keys, layer_slice in zip(plan.keys, arr):
                for key, sl in zip(expert_keys, layer_slice):
                    yield key, np.ascontiguousarray(
                        sl.T if plan.transpose else sl
                    )


def _hf_emission_sizes(params: Any, config) -> list[int]:
    """Per-emitted-HF-tensor byte sizes in :func:`native_to_hf` order,
    computed from shapes only — no data is touched. Stacked leaves split
    uniformly across their emitted per-layer(/expert) keys."""
    from ..checkpointing import flatten_tree

    sizes: list[int] = []
    for name, leaf in sorted(flatten_tree(params).items()):
        arr = leaf.value if hasattr(leaf, "value") else leaf
        plan = _plan_for(_normalize(name), config)
        n_keys = (
            1 if plan.stack == 0
            else sum(len(k) if isinstance(k, list) else 1 for k in plan.keys)
        )
        nbytes = int(np.prod(arr.shape)) * np.dtype(arr.dtype).itemsize
        sizes.extend([nbytes // n_keys] * n_keys)
    return sizes


def _export_arch(config) -> tuple[str, str]:
    """The HF (architecture, model_type) an exported config maps to —
    rejecting any switch combination NO HF model_type represents. A
    mislabeled export is the silent-divergence failure mode this module
    exists to prevent: transformers would load every matching tensor,
    drop/ignore the rest (qkv biases under Gemma/Mixtral labels), and the
    round-trip would re-infer different math (partial Gemma switch sets,
    Mixtral labels carrying none of the offset-norm/gelu/embed-scale
    semantics)."""
    gemma_flags = (
        getattr(config, "norm_offset", False),
        getattr(config, "mlp_activation", "silu") == "gelu_tanh",
        getattr(config, "embed_scale", False),
    )
    is_gemma = all(gemma_flags)
    if any(gemma_flags) and not is_gemma:
        raise ValueError(
            "partial Gemma switch set (norm_offset/mlp_activation="
            "'gelu_tanh'/embed_scale must all be on or all off) matches "
            "no HF model_type; save a native checkpoint instead"
        )
    kinds = [
        name for name, on in (
            ("layer_types", getattr(config, "layer_types", None) is not None),
            ("rope_layout", getattr(config, "rope_layout", None) is not None),
            ("moe_router_pre_attention",
             getattr(config, "moe_router_pre_attention", False)),
        ) if on
    ]
    if kinds:
        # the mappings here write ONE kind of layer: a stack whose layers
        # differ (``layer_types`` operators, sliding beside full attention
        # with rope by layer) would come back as that one kind, every tensor
        # loaded and the logits silently another model's
        raise ValueError(
            f"no HF model_type this module maps carries {kinds} (layer_types "
            "names per-layer operators and windows the parameter mappings "
            "do not write); save a native checkpoint instead"
        )
    qkv = getattr(config, "qkv_bias", False)
    moe = bool(config.num_experts)
    post = getattr(config, "post_norms", False)
    sw = getattr(config, "sliding_window", None) is not None
    lw = getattr(config, "layer_windows", None) is not None
    if sum((is_gemma, qkv, moe)) > 1:
        raise ValueError(
            "no HF model_type represents this switch combination "
            f"(gemma-math={is_gemma}, qkv_bias={qkv}, moe={moe}); "
            "save a native checkpoint instead"
        )
    if post and not is_gemma:
        raise ValueError(
            "post_norms without the Gemma math trio matches no HF "
            "model_type; save a native checkpoint instead"
        )
    if (sw or lw) and is_gemma and not post:
        # GemmaConfig (v1) has no sliding_window field — transformers
        # would drop the band silently on reload (Gemma-2, post_norms,
        # DOES carry one)
        raise ValueError(
            "no HF model_type represents Gemma-v1 math with a sliding "
            "window; save a native checkpoint instead"
        )
    if lw and not (post or qkv):
        # only Gemma2Config/Qwen2Config carry per-layer layer_types
        raise ValueError(
            "no HF model_type represents per-layer windows outside the "
            "Gemma-2/Qwen2 families; save a native checkpoint instead"
        )
    if lw:
        widths = {w for w in config.layer_windows if w is not None}
        if len(widths) > 1:
            raise ValueError(
                "HF configs carry ONE sliding_window; per-layer windows "
                f"with mixed widths {sorted(widths)} cannot round-trip — "
                "save a native checkpoint instead"
            )
    if is_gemma and not config.tie_embeddings:
        raise ValueError(
            "Gemma checkpoints are always tied; an untied lm_head would "
            "be silently dropped by transformers — tie_embeddings=True "
            "or save a native checkpoint"
        )
    if moe:
        return "MixtralForCausalLM", "mixtral"
    if is_gemma and post:
        return "Gemma2ForCausalLM", "gemma2"
    if is_gemma:
        return "GemmaForCausalLM", "gemma"
    if qkv:
        return "Qwen2ForCausalLM", "qwen2"
    if sw:
        # LlamaConfig has no sliding_window; the Llama layout + band IS
        # Mistral
        return "MistralForCausalLM", "mistral"
    return "LlamaForCausalLM", "llama"


def save_hf_checkpoint(
    params: Any,
    config,
    save_directory: str,
    max_shard_size: "str | int" = "5GB",
) -> None:
    """Write an HF-layout safetensors checkpoint (+ index when sharded)
    that ``transformers`` can load directly — the reverse interop of
    :func:`hf_native_reader` (reference save path accelerator.py:2712).
    Also writes a minimal ``config.json`` so :func:`infer_config_from_hf`
    round-trips.

    Streaming: shard boundaries are planned from shapes alone, then each
    shard is written (and freed) as soon as it fills — peak host memory is
    the source params + ONE shard (max_shard_size), matching the
    one-leaf-at-a-time property of the load path, not 2x the model.

    Addressability: every leaf must be host-readable from process 0 —
    single-host (sharded or not) or fully-replicated params. Params
    sharded ACROSS hosts (a multi-host pod mesh) cannot be np.asarray'd
    here; gather them first (``accelerator.get_state_dict(params)``, or
    re-shard via ``dist_checkpoint`` save+merge). This function checks
    and raises rather than letting jax surface a cryptic
    'non-addressable devices' error mid-write.
    """
    import jax

    from ..checkpointing import _save_named, flatten_tree, parse_size

    # checked BEFORE any shard is written — a big-model export is hours
    # of I/O and a late failure would leave orphaned shards on disk
    _export_arch(config)
    for name, leaf in flatten_tree(params).items():
        arr = leaf.value if hasattr(leaf, "value") else leaf
        if (
            hasattr(arr, "is_fully_addressable")
            and not arr.is_fully_addressable
            # fully-replicated multi-host arrays np.asarray fine from any
            # process (jax reads the local copy) — only CROSS-host shards
            # are unexportable from process 0
            and not getattr(arr, "is_fully_replicated", False)
        ):
            raise ValueError(
                f"param {name!r} is sharded across hosts (not fully "
                "addressable); gather before export — e.g. "
                "accelerator.get_state_dict(params), or save with "
                "dist_checkpoint and merge-weights"
            )
    os.makedirs(save_directory, exist_ok=True)
    if jax.process_index() != 0:
        return
    limit = parse_size(max_shard_size)

    # plan shard assignment without materializing any tensor
    sizes = _hf_emission_sizes(params, config)
    shard_of: list[int] = []
    shard_idx, acc = 0, 0
    for nbytes in sizes:
        if shard_of and acc + nbytes > limit:
            shard_idx, acc = shard_idx + 1, 0
        shard_of.append(shard_idx)
        acc += nbytes
    n_shards = (shard_of[-1] + 1) if shard_of else 1

    stem, ext = os.path.splitext(_HF_WEIGHTS_NAME)

    def shard_name(i: int) -> str:
        if n_shards == 1:
            return _HF_WEIGHTS_NAME
        return f"{stem}-{i + 1:05d}-of-{n_shards:05d}{ext}"

    weight_map: dict[str, str] = {}
    total = 0
    shard: dict[str, np.ndarray] = {}
    current = 0
    for i, (key, arr) in enumerate(native_to_hf(params, config)):
        if shard_of[i] != current:
            _save_named(shard, os.path.join(save_directory, shard_name(current)), True)
            shard, current = {}, shard_of[i]
        shard[key] = arr
        weight_map[key] = shard_name(shard_of[i])
        total += arr.nbytes
    _save_named(shard, os.path.join(save_directory, shard_name(current)), True)
    if n_shards > 1:
        with open(os.path.join(save_directory, _HF_INDEX_NAME), "w") as f:
            json.dump(
                {"metadata": {"total_size": total}, "weight_map": weight_map},
                f,
                indent=2,
                sort_keys=True,
            )
    if getattr(config, "arch", "llama") == "gpt2":
        hf_cfg = {
            "architectures": ["GPT2LMHeadModel"],
            "model_type": "gpt2",
            "vocab_size": config.vocab_size,
            "n_embd": config.hidden_size,
            "n_inner": config.intermediate_size,
            "n_layer": config.num_layers,
            "n_head": config.num_heads,
            "n_positions": config.max_seq_len,
            "n_ctx": config.max_seq_len,
            "layer_norm_epsilon": config.rms_norm_eps,
            "activation_function": "gelu_new",
            "tie_word_embeddings": True,
        }
        with open(os.path.join(save_directory, "config.json"), "w") as f:
            json.dump(hf_cfg, f, indent=2, sort_keys=True)
        return
    arch_name, mt = _export_arch(config)
    hf_cfg = {
        "architectures": [arch_name],
        "model_type": mt,
        "vocab_size": config.vocab_size,
        "hidden_size": config.hidden_size,
        "intermediate_size": config.intermediate_size,
        "num_hidden_layers": config.num_layers,
        "num_attention_heads": config.num_heads,
        "num_key_value_heads": config.num_kv_heads,
        "max_position_embeddings": config.max_seq_len,
        "rope_theta": config.rope_theta,
        "rms_norm_eps": config.rms_norm_eps,
        "tie_word_embeddings": config.tie_embeddings,
    }
    if config.rope_scaling:
        hf_cfg["rope_scaling"] = config.rope_scaling
    if mt in ("gemma", "gemma2"):
        hf_cfg["head_dim"] = config.head_dim
        hf_cfg["hidden_activation"] = "gelu_pytorch_tanh"
    sw = getattr(config, "sliding_window", None)
    lw = getattr(config, "layer_windows", None)
    lw_width = next((w for w in (lw or ()) if w is not None), None)
    layer_types = (
        ["sliding_attention" if w is not None else "full_attention"
         for w in lw]
        if lw is not None else None
    )
    if mt in ("mistral", "mixtral"):
        hf_cfg["sliding_window"] = sw  # None -> full attention, HF default
        if mt == "mistral":
            hf_cfg["head_dim"] = config.head_dim
    elif mt == "qwen2" and (sw is not None or lw is not None):
        hf_cfg["use_sliding_window"] = True
        hf_cfg["sliding_window"] = sw if sw is not None else lw_width
        if layer_types is not None:
            hf_cfg["layer_types"] = layer_types
        else:
            # every layer slides (infer_config_from_hf round-trips this
            # via the derived layer_types)
            hf_cfg["max_window_layers"] = 0
    elif mt == "gemma2":
        hf_cfg["query_pre_attn_scalar"] = config.query_pre_attn_scalar
        hf_cfg["attn_logit_softcapping"] = config.attn_softcap
        hf_cfg["final_logit_softcapping"] = config.final_softcap
        if lw is not None:
            hf_cfg["sliding_window"] = lw_width
            hf_cfg["layer_types"] = layer_types
        else:
            hf_cfg["sliding_window"] = sw
            hf_cfg["layer_types"] = [
                "sliding_attention" if sw is not None else "full_attention"
            ] * config.num_layers
    if config.num_experts:
        hf_cfg["num_local_experts"] = config.num_experts
        hf_cfg["num_experts_per_tok"] = config.num_experts_per_tok
    with open(os.path.join(save_directory, "config.json"), "w") as f:
        json.dump(hf_cfg, f, indent=2, sort_keys=True)


# ---------------------------------------------------------------------- #
# PEFT adapter interop: native LoRA trees <-> PEFT key/layout conventions
# ---------------------------------------------------------------------- #
# PEFT (HF peft) names adapter tensors
#   base_model.model.<module path>.lora_A.weight
# where <module path> is the wrapped transformers module — for a Llama
# CausalLM: model.layers.{i}.self_attn.q_proj (attention) or
# model.layers.{i}.mlp.gate_proj (MLP). torch nn.Linear layout applies:
# lora_A stores (r, in) and lora_B stores (out, r) — each the transpose
# of the native flax (in, r)/(r, out) — and the leading layer axis of the
# native scan-stacked leaves unstacks into per-layer keys.
_PEFT_ATTN = ("q_proj", "k_proj", "v_proj", "o_proj")
_PEFT_PREFIX = "base_model.model.model.layers"


def _peft_module_path(layer: int, target: str) -> str:
    group = "self_attn" if target in _PEFT_ATTN else "mlp"
    return f"{_PEFT_PREFIX}.{layer}.{group}.{target}"


def adapter_to_peft(
    adapter_params: Any, lora_config, model_config
) -> dict[str, np.ndarray]:
    """Native adapter tree -> flat PEFT-named dict (torch layouts).

    The result's keys/shapes are exactly what ``peft``'s
    ``set_peft_model_state_dict`` expects for a Llama-family base model,
    so a tree trained here exports into the HF adapter ecosystem the way
    :func:`save_hf_checkpoint` exports base weights.
    """
    from ..adapters.runtime import A_KEY, B_KEY

    L = model_config.num_layers
    out: dict[str, np.ndarray] = {}
    for target in lora_config.target_modules:
        pair = adapter_params[target]
        a = np.asarray(pair[A_KEY])  # (L, in, r)
        b = np.asarray(pair[B_KEY])  # (L, r, out)
        if a.shape[0] != L or b.shape[0] != L:
            raise ValueError(
                f"adapter leaf for {target!r} has layer dim "
                f"{a.shape[0]}/{b.shape[0]}, model has {L} layers"
            )
        for i in range(L):
            mod = _peft_module_path(i, target)
            out[f"{mod}.lora_A.weight"] = np.ascontiguousarray(a[i].T)
            out[f"{mod}.lora_B.weight"] = np.ascontiguousarray(b[i].T)
    return out


def peft_to_adapter(
    state_dict: dict, lora_config, model_config
) -> dict:
    """Flat PEFT-named dict -> native adapter tree (the inverse of
    :func:`adapter_to_peft`; re-stacks per-layer keys onto the leading
    scan axis and transposes back to flax layouts)."""
    from ..adapters.runtime import A_KEY, B_KEY

    L = model_config.num_layers
    adapter: dict = {}
    for target in lora_config.target_modules:
        a_slices, b_slices = [], []
        for i in range(L):
            mod = _peft_module_path(i, target)
            a_slices.append(np.asarray(state_dict[f"{mod}.lora_A.weight"]).T)
            b_slices.append(np.asarray(state_dict[f"{mod}.lora_B.weight"]).T)
        adapter[target] = {
            A_KEY: np.stack(a_slices),
            B_KEY: np.stack(b_slices),
        }
    return adapter

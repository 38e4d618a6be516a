"""Model zoo: TPU-native implementations of the reference's benchmark model
families (BASELINE.md: BERT MRPC, GPT-2, Llama-3, Mixtral-MoE).

Models are flax.linen modules annotated with *logical* axis names
(``nn.with_partitioning``); :mod:`accelerate_tpu.parallel.sharding` maps the
names onto the device mesh, so the same model definition runs pure-DP, FSDP,
TP, SP or EP without edits — the whole point of the GSPMD redesign.
"""

from .._lazy import lazy_exports

# name -> submodule, imported on first access: importing this package
# must not import jax (a parent that spawns chip children stays off it)
_EXPORTS = {
    "TransformerConfig": ".config",
    "GPT2LM": ".gpt2",
    "Seq2SeqLM": ".seq2seq",
    "CausalLM": ".transformer",
    "SequenceClassifier": ".transformer",
    "count_params": ".transformer",
}
__getattr__, __dir__ = lazy_exports(__name__, _EXPORTS)

__all__ = [
    "TransformerConfig",
    "CausalLM",
    "GPT2LM",
    "SequenceClassifier",
    "Seq2SeqLM",
    "causal_model_for",
    "count_params",
]


def causal_model_for(config: "TransformerConfig"):
    """The decoder-LM module class instance matching ``config.arch`` —
    lets arch-agnostic call sites (examples, estimate-memory, interop
    tests) mirror the reference's AutoModel dispatch."""
    if config.arch == "gpt2":
        from .gpt2 import GPT2LM

        return GPT2LM(config)
    from .transformer import CausalLM

    return CausalLM(config)

"""The seam between the serving engine and what a request's cache IS
(``serving/cache_regime.py``): the features x regimes refusals as one table,
the source rules that keep the cache's kind known in one module, and
``capture_programs`` through the regime's argument builders, on the tiny
configurations of ``test_eva_attention.py``, ``test_qwen3_next.py``,
``test_deepseek_v3.py`` and ``test_smallthinker.py``."""

import ast
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "tests"))

import tiny_deepseek_v3  # noqa: E402
import tiny_eva  # noqa: E402
import tiny_qwen3_next  # noqa: E402
import tiny_smallthinker  # noqa: E402
from harness import common  # noqa: E402
from harness import (  # noqa: E402
    deepseek_v3_weights, evabyte_weights, qwen3_next_weights, smallthinker_weights)

from accelerate_tpu.models import CausalLM, TransformerConfig  # noqa: E402
from accelerate_tpu.profiling.registry import ProgramRegistry  # noqa: E402
from accelerate_tpu.serving import ServingEngine, SpecConfig  # noqa: E402
from accelerate_tpu.serving.cache_regime import CacheRegime  # noqa: E402

SERVING = os.path.join(ROOT, "accelerate_tpu", "serving")

# regime -> (tiny configuration, seeded weights, seed, block size)
TINY = {
    "eva": (tiny_eva, evabyte_weights, 2**31 + 5, 4),
    "recurrent": (tiny_qwen3_next, qwen3_next_weights, 2**31 + 38, 4),
    "latent": (tiny_deepseek_v3, deepseek_v3_weights, 2**31 + 40, 8),
    "ring": (tiny_smallthinker, smallthinker_weights, 2**31 + 45, 4),
}


def _model(regime):
    if regime == "rows":
        return CausalLM(TransformerConfig.tiny(max_seq_len=64))
    cfg = TINY[regime][0].config()
    return CausalLM(common.program_config(
        cfg, max_seq_len=cfg["max_position_embeddings"], dtype="float32"))


# --------------------------------------------------------------------------- #
# features x regimes: what is refused, letter for letter
# --------------------------------------------------------------------------- #
WHY = {
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
FEATURES = {
    "prefix_cache": dict(prefix_cache=True),
    "spec_decode": dict(spec_decode=SpecConfig(k=2)),
    "prefill_chunk_tokens": dict(prefill_chunk_tokens=16),
    "preemption": dict(preemption=True),
    "role 'prefill'": dict(role="prefill"),
    "role 'decode'": dict(role="decode"),
    "adapters": dict(adapters=object()),  # refused before it is looked at
    "kv_dtype 'int8'": dict(kv_dtype="int8"),
}


@pytest.mark.parametrize("feature", sorted(FEATURES))
@pytest.mark.parametrize("regime", sorted(WHY))
def test_a_feature_that_takes_the_cache_for_a_list_of_blocks_is_refused(
        regime, feature):
    """Prefix cache, speculation, chunked prefill, preemption swap, both
    hand-off roles, adapters and int8 pools all take a request's state for
    blocks of one row a position: each is refused, by name and with the
    regime's reason and ROADMAP item, when an engine of a model whose cache is
    not that is built (the weights are never looked at)."""
    item, why = WHY[regime]
    with pytest.raises(NotImplementedError) as err:
        ServingEngine(_model(regime), {}, max_slots=2,
                      block_size=TINY[regime][3], **FEATURES[feature])
    assert str(err.value) == (
        f"{feature} is not written for {why} (ROADMAP Reach {item})")


def test_one_row_a_position_refuses_nothing():
    regime = CacheRegime(_model("rows").config, 8, 2, "int8")
    assert regime.kind == "rows" and regime.kv_dtype == "int8"
    for feature in FEATURES:
        regime.refuse(feature)
    assert [CacheRegime(_model(r).config, TINY[r][3], 2).kind
            for r in sorted(TINY)] == sorted(TINY)


# --------------------------------------------------------------------------- #
# the source: the cache's kind is known in one module
# --------------------------------------------------------------------------- #
def _sources():
    for name in sorted(os.listdir(SERVING)):
        if name.endswith(".py"):
            with open(os.path.join(SERVING, name)) as f:
                yield name, ast.parse(f.read())


def _functions_calling(tree, callee):
    """Names of the innermost functions of ``tree`` that call ``callee``."""
    found = []

    def walk(node, inside):
        for child in ast.iter_child_nodes(node):
            here = child.name if isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef)) else inside
            if isinstance(child, ast.Call) and (
                    getattr(child.func, "id", None) == callee
                    or getattr(child.func, "attr", None) == callee):
                found.append(here)
            walk(child, here)

    walk(tree, None)
    return found


def test_the_paged_state_is_built_in_one_function_under_serving():
    built = {name: _functions_calling(tree, "PagedKVState")
             for name, tree in _sources()}
    assert {k: v for k, v in built.items() if v} == {
        "cache_regime.py": ["state"]}


def test_only_the_regime_reads_the_fields_that_say_what_the_cache_is():
    fields = {"attention_class", "layer_types", "kv_lora_rank"}
    readers = set()
    for name, tree in _sources():
        for node in ast.walk(tree):
            if (isinstance(node, ast.Attribute) and node.attr in fields) or (
                    isinstance(node, ast.Constant) and node.value in fields):
                readers.add(name)
    assert readers == {"cache_regime.py"}


def test_the_engine_asks_the_regime_and_branches_on_no_kind_itself():
    with open(os.path.join(SERVING, "engine.py")) as f:
        tree = ast.parse(f.read())
    # the regime's kind is never read outside its module
    assert not [n for n in ast.walk(tree)
                if isinstance(n, ast.Attribute) and n.attr == "kind"]
    # the scheduler is told the regime and the chunking when it is built
    assigned = [t.attr for n in ast.walk(tree) if isinstance(n, ast.Assign)
                for t in n.targets if isinstance(t, ast.Attribute)
                and getattr(t.value, "attr", None) == "scheduler"]
    assert not {"layout", "regime", "chunk_tokens", "chunked_reserve"} & set(
        assigned), assigned
    # capture_programs writes no shape of its own: its specs are the shapes
    # of what the regime's argument builders return (``jax.eval_shape``)
    capture = next(n for n in ast.walk(tree) if isinstance(
        n, ast.FunctionDef) and n.name == "capture_programs")
    attrs = {n.attr for n in ast.walk(capture) if isinstance(n, ast.Attribute)}
    assert "ShapeDtypeStruct" not in attrs and "eval_shape" in attrs


# --------------------------------------------------------------------------- #
# capture_programs: every regime's programs, through the regime's arguments
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("regime,kw", [
    ("rows", {}), ("rows", {"kv_dtype": "int8"}), ("eva", {}),
    ("recurrent", {}), ("latent", {}), ("ring", {}),
], ids=["rows-bf16", "rows-int8", "eva", "recurrent", "latent", "ring"])
def test_capture_programs_registers_each_regimes_programs(regime, kw):
    model = _model(regime)
    if regime == "rows":
        params = nn.unbox(model.init(
            jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"])
        block = 8
    else:
        tiny, weights, seed, block = TINY[regime]
        params = weights.make_tree(tiny.config(), seed, jnp.float32)
    eng = ServingEngine(model, params, max_slots=2, block_size=block, **kw)
    prompt = np.random.default_rng(3).integers(
        0, model.config.vocab_size, 11).astype(np.int32)
    rid = eng.add_request(prompt, max_new_tokens=3)
    while eng.has_work:
        eng.step()
    assert len(eng.result(rid)) == 3
    traced = eng.trace_counts()
    assert traced["prefill"] == traced["decode"] == 1
    assert traced["eva"] == (3 if regime == "eva" else 0)  # + the roll-over
    assert traced["recurrent_state"] == (2 if regime == "recurrent" else 0)
    assert traced["mla_prefill_expanded"] == (regime == "latent")
    assert traced["flash_real_rows"] == (regime in ("recurrent", "latent", "ring"))
    assert traced["window_ring"] == (2 if regime == "ring" else 0)
    labels = eng.capture_programs(ProgramRegistry())
    assert labels == ["serve_prefill_b16", "serve_decode"] + (
        ["serve_rollover"] if regime == "eva" else []) + [
        "serve_cow", "serve_key_chain"]
    assert eng.trace_counts() == traced  # the re-traces are not counted
    compiled = eng.capture_compile_count
    assert eng.capture_programs(ProgramRegistry()) == labels
    assert eng.capture_compile_count == compiled == len(labels)

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
    # the bucket of 16 is whole blocks, and only those three start from nothing
    assert traced["kv_block_write"] == (regime in ("recurrent", "latent", "ring"))
    assert traced["window_ring"] == (2 if regime == "ring" else 0)
    labels = eng.capture_programs(ProgramRegistry())
    assert labels == ["serve_prefill_b16", "serve_decode"] + (
        ["serve_rollover"] if regime == "eva" else []) + [
        "serve_cow", "serve_key_chain"]
    assert eng.trace_counts() == traced  # the re-traces are not counted
    compiled = eng.capture_compile_count
    assert eng.capture_programs(ProgramRegistry()) == labels
    assert eng.capture_compile_count == compiled == len(labels)


# --------------------------------------------------------------------------- #
# a fresh prefill writes its rows by the block (PR 46)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("fresh,width,kv_dtype,by_block", [
    (True, 16, "native", True), (True, 64, "native", True),
    (True, 2, "native", False),    # a bucket narrower than a block
    (True, 12, "native", False),   # no whole number of blocks
    (False, 16, "native", False),  # onto a cache_len that need not be aligned
    (True, 16, "int8", False),     # the scale arrays keep the row scatter
    (False, 1, "native", False),   # a decode step
], ids=["whole-blocks", "wide", "under-a-block", "ragged", "not-fresh", "int8",
        "decode"])
def test_the_block_write_asks_what_the_call_can_observe(
        fresh, width, kv_dtype, by_block):
    from accelerate_tpu.ops.attention import PagedKVState, block_write_eligible

    state = PagedKVState(
        block_table=jnp.zeros((1, 8), jnp.int32),
        cache_len=jnp.zeros(1, jnp.int32), lengths=jnp.ones(1, jnp.int32),
        num_blocks=9, block_size=8, kv_dtype=kv_dtype, fresh=fresh)
    assert block_write_eligible(state, width) == by_block


def _served_logits(regime, monkeypatch, by_block):
    """The logits an engine's own prefill and decode programs sampled from
    over a prompt that ends inside a block, and its trace counts — with the
    block write as the predicate says, or with every call on the row
    scatter."""
    from accelerate_tpu.ops import attention
    from accelerate_tpu.serving import cache_regime, engine as engine_module

    if not by_block:
        for module in (attention, cache_regime):
            monkeypatch.setattr(
                module, "block_write_eligible", lambda state, s: False)
    seen, real = [], engine_module.sample_tokens

    def sample(logits, *a, **kw):
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), logits,
                           ordered=True)
        return real(logits, *a, **kw)

    monkeypatch.setattr(engine_module, "sample_tokens", sample)
    tiny, weights, seed, block = TINY[regime]
    params = weights.make_tree(tiny.config(), seed, jnp.float32)
    eng = ServingEngine(_model(regime), params, max_slots=2, block_size=block)
    prompt = np.random.default_rng(5).integers(
        0, eng.model.config.vocab_size, 2 * block + 3).astype(np.int32)
    rid = eng.add_request(prompt, max_new_tokens=block + 2)
    while eng.has_work:
        eng.step()
    jax.effects_barrier()
    assert len(eng.result(rid)) == block + 2
    return [x[0] for x in seen], eng.trace_counts()


@pytest.mark.parametrize("regime", ["recurrent", "latent", "ring"])
def test_decode_after_a_block_written_prompt_reads_no_padded_row(
        regime, monkeypatch):
    """A prompt of ``2 blocks + 3`` in a bucket of 4: the block write leaves
    the bucket's padded positions in the rest of the slot's third block. The
    decode steps that follow, across that block's end, sample from the logits
    of the tree in which every call keeps the row scatter."""
    got, counts = _served_logits(regime, monkeypatch, True)
    assert counts["kv_block_write"] == counts["prefill"] == 1
    want, rows = _served_logits(regime, monkeypatch, False)
    assert rows["kv_block_write"] == 0 and rows["prefill"] == 1
    assert len(got) == len(want) > TINY[regime][3]
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)

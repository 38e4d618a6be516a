"""Autoregressive generation with a static-shape KV cache.

The inference counterpart of the reference's big-model benchmark surface
(BASELINE.md measures s/token generation; reference drives HF
``model.generate``). TPU-native design: prefill is one forward over the
prompt; the decode loop is a single ``lax.scan`` over token steps — one
compiled program for the whole generation, no per-token dispatch.

Sampling: greedy, temperature, top-k, top-p (nucleus).
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from .transformer import CausalLM


def _filter_logits(logits, top_k, top_p):
    """(B, V) fp32 logits -> same, with everything outside the top-k /
    nucleus set at -inf. Shared by batch sampling here and the per-slot
    serving sampler (:mod:`accelerate_tpu.serving.sampling`)."""
    if top_k is not None and top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None and top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest set with cumulative prob >= top_p; cutoff is the logit of
        # the last token inside that set
        include = cum - probs < top_p
        cutoff = jnp.min(
            jnp.where(include, sorted_logits, jnp.inf), axis=-1, keepdims=True
        )
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


def _sample_logits(logits, key, temperature, top_k, top_p):
    """(B, V) logits -> (B,) token ids."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = _filter_logits(logits.astype(jnp.float32) / temperature, top_k, top_p)
    return jax.random.categorical(key, logits, axis=-1)


def init_cache(model_init, *init_args, device=None, **init_kwargs):
    """Zeroed decode-cache template via eval_shape: a full ``model.init``
    here would materialize (and randomly initialize) an entire spare
    parameter tree just to learn the cache shapes — pure HBM/time waste at
    8B+ scale. Shared by CausalLM and Seq2SeqLM generation. ``device``
    allocates the zeros there (None: the default device, uncommitted)."""
    cache_shapes = jax.eval_shape(
        lambda: model_init(*init_args, **init_kwargs)["cache"]
    )
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype, device=device), cache_shapes
    )


def generate(
    model: CausalLM,
    params: Any,
    input_ids: jax.Array,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    eos_token_id: Optional[int] = None,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """Generate continuations; returns (B, prompt_len + max_new_tokens).

    The prompt must fit ``config.max_seq_len - max_new_tokens``. After an
    EOS, positions are padded with EOS (finished sequences stop changing).
    """
    B, prompt_len = input_ids.shape
    if prompt_len + max_new_tokens > model.config.max_seq_len:
        raise ValueError(
            f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) "
            f"exceeds max_seq_len ({model.config.max_seq_len})"
        )
    key = key if key is not None else jax.random.PRNGKey(0)
    cache = init_cache(
        model.init, jax.random.PRNGKey(0), jnp.zeros((B, 1), jnp.int32),
        decode=True,
    )

    # prefill the whole prompt in one forward
    logits, mutated = model.apply(
        {"params": params, "cache": cache}, input_ids, decode=True,
        mutable=["cache"],
    )
    cache = mutated["cache"]
    first = _sample_logits(logits[:, -1], key, temperature, top_k, top_p)

    def step(carry, _):
        cache, token, k, done = carry
        k, sub = jax.random.split(k)
        logits, mutated = model.apply(
            {"params": params, "cache": cache}, token[:, None], decode=True,
            mutable=["cache"],
        )
        nxt = _sample_logits(logits[:, -1], sub, temperature, top_k, top_p)
        if eos_token_id is not None:
            nxt = jnp.where(done, eos_token_id, nxt)
            done = done | (nxt == eos_token_id)
        return (mutated["cache"], nxt, k, done), nxt

    done = (
        (first == eos_token_id)
        if eos_token_id is not None
        else jnp.zeros((B,), bool)
    )
    if max_new_tokens > 1:
        (_, _, _, _), rest = jax.lax.scan(
            step, (cache, first, key, done), None, length=max_new_tokens - 1
        )
        new_tokens = jnp.concatenate([first[:, None], rest.T], axis=1)
    else:
        new_tokens = first[:, None]
    return jnp.concatenate([input_ids, new_tokens], axis=1)


def _prompt_chunks(prompt_len: int) -> list[int]:
    """Descending power-of-two decomposition of a prompt length (13 ->
    [8, 4, 1]): the chunk widths every prompt can be prefilled with."""
    chunks, width = [], 1 << (max(prompt_len, 1).bit_length() - 1)
    while prompt_len:
        if width <= prompt_len:
            chunks.append(width)
            prompt_len -= width
        width >>= 1
    return chunks


def make_generate_fn(
    model: CausalLM,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    eos_token_id: Optional[int] = None,
):
    """A compiled generate closure: ``fn(params, input_ids, key) -> ids``.

    The old closure jitted the WHOLE generate, so every distinct prompt
    length retraced prefill + decode scan — a serving workload with mixed
    prompts recompiled per length (the retrace trap). Here prefill runs
    as descending power-of-two CHUNKS through one shared jitted apply
    (13 tokens -> chunks of 8, 4, 1 written at their true cache offsets —
    the dense decode branch anchors masks at the global position, so the
    math is EXACT, not bucket-padded), and the decode scan is jitted once
    per batch size. Across any mix of prompt lengths at most
    ``log2(max_seq_len)`` prefill programs ever compile.

    ``fn.trace_counts()`` exposes ``{"prefill": n, "decode": m}`` (Python
    trace-time counters) so tests can assert the bound.
    """
    traces = {"prefill": 0, "decode": 0}

    @jax.jit
    def _prefill_chunk(params, cache, chunk):
        traces["prefill"] += 1
        logits, mutated = model.apply(
            {"params": params, "cache": cache}, chunk, decode=True,
            mutable=["cache"],
        )
        return mutated["cache"], logits[:, -1]

    @jax.jit
    def _decode(params, cache, last_logits, key):
        traces["decode"] += 1
        # sampling order matches generate() exactly: first token from the
        # caller's key, scan steps split from it — same key math, same
        # tokens, so the two APIs are interchangeable
        first = _sample_logits(last_logits, key, temperature, top_k, top_p)
        done = (
            (first == eos_token_id)
            if eos_token_id is not None
            else jnp.zeros(first.shape, bool)
        )

        def step(carry, _):
            cache, token, k, done = carry
            k, sub = jax.random.split(k)
            logits, mutated = model.apply(
                {"params": params, "cache": cache}, token[:, None],
                decode=True, mutable=["cache"],
            )
            nxt = _sample_logits(logits[:, -1], sub, temperature, top_k, top_p)
            if eos_token_id is not None:
                nxt = jnp.where(done, eos_token_id, nxt)
                done = done | (nxt == eos_token_id)
            return (mutated["cache"], nxt, k, done), nxt

        if max_new_tokens > 1:
            _, rest = jax.lax.scan(
                step, (cache, first, key, done), None,
                length=max_new_tokens - 1,
            )
            return jnp.concatenate([first[:, None], rest.T], axis=1)
        return first[:, None]

    def fn(params, input_ids, key=None):
        B, prompt_len = input_ids.shape
        if prompt_len + max_new_tokens > model.config.max_seq_len:
            raise ValueError(
                f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_seq_len ({model.config.max_seq_len})"
            )
        key = key if key is not None else jax.random.PRNGKey(0)
        cache = init_cache(
            model.init, jax.random.PRNGKey(0), jnp.zeros((B, 1), jnp.int32),
            decode=True,
        )
        offset = 0
        for width in _prompt_chunks(prompt_len):
            cache, last = _prefill_chunk(
                params, cache, input_ids[:, offset:offset + width]
            )
            offset += width
        new_tokens = _decode(params, cache, last, key)
        return jnp.concatenate([input_ids, new_tokens], axis=1)

    fn.trace_counts = lambda: dict(traces)
    return fn

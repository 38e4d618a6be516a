"""Faults planted UNDER the timed path, for the tests alone: each patches the
program (or the optimizer the runner hands it) in place, so that the runners
carry no test hook, and a run driven over it has to come out ``correct``
false. ``plant(name)`` patches and returns the call that undoes it; the CPU
rehearsals and ``limits_on_chip.py`` (at the cells' own sizes) both use it.

train: half_batch (a part of the batch left out), frozen_state (a step that
returns its state unchanged), compile_in_window, lr_off_1pct (the learning
rate 1 % high: a wrong optimizer update far milder than the int8 control).
serve: wrong_token (every decode token altered where it is produced),
one_token (ONE token of ONE slot, once, while other slots are live),
int8_kv (the program's own lower-precision path: an int8 KV pool).
"""

from __future__ import annotations

TRAIN = ("half_batch", "frozen_state", "compile_in_window", "lr_off_1pct")
SERVE = ("wrong_token", "one_token", "int8_kv")


_UNDO: list = []


def _set(obj, attr, value):
    _UNDO.append((obj, attr, getattr(obj, attr)))
    setattr(obj, attr, value)


def undo() -> None:
    while _UNDO:
        obj, attr, was = _UNDO.pop()
        setattr(obj, attr, was)


class _Step:
    """The program's step with its call replaced; ``warm``, ``jitted`` and
    ``aot_fallbacks`` stay the real step's."""

    def __init__(self, real, call):
        self._real, self._call = real, call

    def __call__(self, carry, batch):
        return self._call(self._real, carry, batch)

    def __getattr__(self, name):
        return getattr(self._real, name)


def _patch_step(call):
    from accelerate_tpu import Accelerator

    build = Accelerator.unified_step
    _set(Accelerator, "unified_step",
         lambda self, *a, **kw: _Step(build(self, *a, **kw), call))


def _patch_decode(alter):
    """``alter(engine, tokens) -> tokens`` on what the decode program hands
    back, before the engine reads it."""
    from accelerate_tpu import ServingEngine

    init = ServingEngine.__init__

    def patched(self, *a, **kw):
        init(self, *a, **kw)
        real = self._decode_fn

        def decode(*args, **kwargs):
            cache, tokens = real(*args, **kwargs)
            return cache, alter(self, tokens)
        self._decode_fn = decode
    _set(ServingEngine, "__init__", patched)


def plant(name: str):
    import jax
    import jax.numpy as jnp

    if name == "half_batch":
        def call(step, carry, batch):
            ids = batch["input_ids"]
            half = ids.shape[0] // 2
            ids = jnp.concatenate([ids[:half], ids[:half]], axis=0)
            return step(carry, {**batch, "input_ids": ids})
        _patch_step(call)
    elif name == "frozen_state":
        _patch_step(lambda step, carry, batch: (carry, {"loss": jnp.float32(6.0)}))
    elif name == "compile_in_window":
        calls = {"n": 0}

        def call(step, carry, batch):
            calls["n"] += 1
            if calls["n"] == 6:  # after the three checked steps
                jax.jit(lambda x: x * 2.5 + 1.25)(jnp.ones((7, 3)))
            return step(carry, batch)
        _patch_step(call)
    elif name == "lr_off_1pct":
        import optax

        adamw = optax.adamw
        _set(optax, "adamw", lambda lr, *a, **kw: adamw(lr * 1.01, *a, **kw))
    elif name == "wrong_token":
        _patch_decode(lambda eng, tok: (tok + 1) % eng.model.config.vocab_size)
    elif name == "one_token":
        state = {"calls": 0, "done": False}

        def alter(eng, tok):
            state["calls"] += 1
            busy = [s.index for s in eng.scheduler.slots if s.busy]
            # once, after the warm-up's few decode calls (its requests are not
            # compared), with other slots live beside the victim
            if state["done"] or state["calls"] < 24 or len(busy) < 2:
                return tok
            state["done"] = True
            victim = busy[-1]
            print(f"fault one_token: decode call {state['calls']}, slot {victim} "
                  f"of {len(busy)} live", flush=True)
            return tok.at[victim].set((tok[victim] + 1) % eng.model.config.vocab_size)
        _patch_decode(alter)
    elif name == "int8_kv":
        from accelerate_tpu import ServingEngine

        init = ServingEngine.__init__
        _set(ServingEngine, "__init__", lambda self, *a, **kw: init(
            self, *a, **{**kw, "kv_dtype": "int8"}))
    else:
        raise KeyError(name)
    return undo

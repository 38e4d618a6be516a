"""Test environment: a virtual 8-device CPU backend.

This is the TPU build's equivalent of the reference's gloo/CPU debug
launcher (reference launchers.py:263, SURVEY §4 pattern 2): real XLA
collectives over 8 fake host devices so every sharding/mesh/collective path
runs anywhere. The platform and device count are pinned through
``jax.config`` before any backend initializes (``JAX_PLATFORMS=cpu`` in the
environment does the same for the platform).
"""

import os

os.environ.setdefault("ACCELERATE_TPU_TEST_NUM_DEVICES", "8")

import jax

if os.environ.get("ACCELERATE_TPU_TEST_ON_TPU", "0") != "1":
    jax.config.update("jax_platforms", "cpu")
    jax.config.update(
        "jax_num_cpu_devices", int(os.environ["ACCELERATE_TPU_TEST_NUM_DEVICES"])
    )

# Persistent XLA compilation cache: nearly all of a tier-1 run is XLA:CPU
# compiles of programs that do not change between runs. Same resolver as
# every other entry point (JAX_COMPILATION_CACHE_DIR, else the in-checkout
# .jax_compile_cache); the key includes the program, the 8-device topology
# and the compile options, so hits are exact. The 0.3 s floor keeps the
# thousands of trivial programs off the disk.
from accelerate_tpu.compilation import activate_persistent_cache
from accelerate_tpu.utils.dataclasses import CompilePlugin

activate_persistent_cache(CompilePlugin(
    cache_min_compile_time_secs=0.3,
    # XLA:CPU is not in the default allowlist; opt it in explicitly
    cache_enable_xla_caches="all",
))

import pytest


@pytest.fixture(autouse=True)
def reset_singletons():
    """Reference AccelerateTestCase (test_utils/testing.py:429) resets
    singleton state between tests; we do it for every test."""
    yield
    from accelerate_tpu.profiling import reset_program_registry
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    reset_program_registry()


@pytest.fixture
def row_scatters():
    """``(text, width) -> [(operand, update)]``: the types of every
    ``stablehlo.scatter`` of a lowered program whose update is a block of
    rows at least ``width`` wide (``bincount``'s and ``take_along_axis``'s
    scalar updates are not among them)."""
    import re

    def find(text, width):
        found = re.findall(
            r'"stablehlo\.scatter"\(.*?\}\) : \(tensor<([^>]*)>, tensor<[^>]*>, '
            r'tensor<([^>]*)>\)', text, flags=re.DOTALL)
        wide = []
        for operand, update in found:
            dims = [int(d) for d in update.split("x")[:-1]]
            if len(dims) >= 2 and dims[-1] >= width:
                wide.append((operand, update))
        return wide

    return find

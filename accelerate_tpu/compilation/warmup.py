"""AOT warmup: compile the step before the first batch arrives.

A cold training loop serializes two slow phases: the input pipeline's
first batch and XLA's first compile. Both are knowable ahead of time —
the prepared dataloader pads every batch to one fixed shape, and jit
only needs *abstract* values to lower — so the compile can start from
``ShapeDtypeStruct`` specs while the host is still reading data.

:func:`warm_step` drives ``jitted.lower(*specs).compile()`` and
returns the compiled executable plus timing; the Accelerator wires it as
``step_fn.warm(...)`` / ``accelerator.warmup(...)`` and routes matching
real calls straight to the compiled executable (true AOT dispatch: the
first real step neither traces nor compiles).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Optional

import jax
import numpy as np

from ..logging import get_logger

logger = get_logger(__name__)


def spec_like(tree: Any) -> Any:
    """Concrete pytree -> ``ShapeDtypeStruct`` pytree, shardings kept.

    Leaves that already are specs pass through; committed ``jax.Array``
    leaves keep their sharding so the AOT lowering sees the same
    in_shardings the real call will. Non-array leaves (python scalars)
    pass through unchanged — jit treats them as weak-typed values either
    way.
    """

    def _one(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        if isinstance(x, jax.Array):
            try:
                # uncommitted arrays (fresh jnp literals) report a
                # SingleDeviceSharding that would CONFLICT with multi-device
                # operands at lower time; jit is free to place them, so the
                # spec must stay placement-free too
                sharding = x.sharding if x.committed else None
            except Exception:
                sharding = None
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
        if isinstance(x, np.ndarray):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    return jax.tree.map(_one, tree)


def batch_spec_of(source: Any) -> Any:
    """Batch spec from a prepared dataloader (or any batch-like pytree).

    A ``DataLoaderShard`` knows its fixed padded global batch shape
    (``.batch_spec()``) — in superbatch mode that spec is the stacked
    ``[K, global_batch, ...]`` shape the fused-accumulation step consumes,
    so warming from the loader covers the fused program too; a concrete
    batch (the output of one loader step, or a hand-built pytree of
    arrays) is abstracted leaf-by-leaf.
    """
    spec_fn = getattr(source, "batch_spec", None)
    if callable(spec_fn):
        return spec_fn()
    return spec_like(source)


def warm_step(
    jitted: Callable,
    *arg_specs: Any,
    static_kwargs: Optional[dict] = None,
    traced_kwargs: Optional[dict] = None,
) -> tuple[Any, float]:
    """Lower and compile ``jitted`` from abstract specs.

    ``static_kwargs`` are keyword arguments declared static on the jit
    (passed concrete — they select the program); ``traced_kwargs`` are
    ordinary traced keywords (abstracted via :func:`spec_like`).
    Compiler options belong on the ``jax.jit`` itself (where
    ``unified_step`` puts ``CompilePlugin.compiler_options``), so this
    compile and a plain call of ``jitted`` share one cache key.

    Returns ``(compiled, seconds)`` where ``seconds`` is the wall time
    of lower+compile (with the persistent cache warm this is mostly
    deserialize time).
    """
    kwargs = dict(static_kwargs or {})
    kwargs.update(spec_like(traced_kwargs or {}))
    specs = tuple(spec_like(a) for a in arg_specs)
    t0 = time.perf_counter()
    lowered = jitted.lower(*specs, **kwargs)
    compiled = lowered.compile()
    seconds = time.perf_counter() - t0
    return compiled, seconds

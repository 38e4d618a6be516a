"""Pytree utilities + host-level collectives.

Parity: reference ``src/accelerate/utils/operations.py`` (848 LoC) — the
communication façade (`gather`:425, `broadcast`:545, `reduce`:727,
`pad_across_processes`:634, `gather_object`:451, debug checker
`verify_operation`:370).

TPU-native split of responsibilities:

* **Inside jit** there are no explicit collectives to call — arrays carry
  `NamedSharding`s and GSPMD emits all-reduce/all-gather/reduce-scatter on
  ICI. Nothing in this module is used in the hot path.
* **Outside jit** (metrics, logging, object sync, uneven eval tails) these
  functions provide the reference's cross-*process* semantics over
  ``jax.experimental.multihost_utils``. On a single process they degrade to
  cheap local ops, exactly like the reference on one GPU.

Every function takes arbitrary pytrees (the reference's
``recursively_apply``:84 is jax.tree.map here, which already walks
list/tuple/dict/namedtuple).
"""

from __future__ import annotations

import pickle
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class DistributedOperationException(Exception):
    """Raised by the debug-mode operational checker when process inputs to a
    collective disagree (reference utils/operations.py:370)."""


def nested_manual_mesh() -> Optional[Any]:
    """The tracing context's abstract mesh when any of its axes is already
    Manual — i.e. we are INSIDE a shard_map body (a pipeline stage) and a
    nested shard_map must be built on this mesh, not the concrete one.
    Returns None at top level.

    Compares against ``jax.sharding.AxisType.Manual`` — not the enum's
    repr, which a jax upgrade could change silently, disabling the
    context-mesh path and surfacing only as an obscure mesh-mismatch
    error under pp x sp / pp x ep.
    """
    ctx = jax.sharding.get_abstract_mesh()
    manual = jax.sharding.AxisType.Manual
    if any(t == manual for t in ctx.axis_types):
        return ctx
    return None


def is_tensor(x: Any) -> bool:
    return isinstance(x, (jax.Array, np.ndarray))


def recursively_apply(
    func: Callable,
    data: Any,
    *args,
    test_type: Callable[[Any], bool] = is_tensor,
    error_on_other_type: bool = False,
    **kwargs,
) -> Any:
    """Apply ``func`` to all leaves of ``data`` passing ``test_type``
    (reference utils/operations.py:84)."""

    def _apply(x):
        if test_type(x):
            return func(x, *args, **kwargs)
        if error_on_other_type:
            raise TypeError(
                f"Unsupported type {type(x)} passed to {getattr(func, '__name__', func)}."
            )
        return x

    return jax.tree.map(_apply, data, is_leaf=lambda x: test_type(x))


def send_to_device(
    data: Any,
    device: Any = None,
    non_blocking: bool = True,
    skip_keys: Optional[list[str]] = None,
) -> Any:
    """Move a pytree onto a device or sharding (reference
    utils/operations.py:135). ``device`` may be a jax.Device, a
    ``Sharding``, or None (default device). jax.device_put is always
    asynchronous; ``non_blocking=False`` waits for the transfer."""
    if isinstance(data, dict) and skip_keys:
        data = {
            k: (v if k in skip_keys else send_to_device(v, device, non_blocking))
            for k, v in data.items()
        }
        return data

    def _put(x):
        y = jax.device_put(x, device)
        if not non_blocking and isinstance(y, jax.Array):
            y.block_until_ready()
        return y

    return recursively_apply(_put, data)


def get_data_structure(data: Any) -> Any:
    """Shape/dtype skeleton of a pytree (reference utils/operations.py:195)."""
    from .dataclasses import TensorInformation

    def _info(x):
        return TensorInformation(shape=tuple(x.shape), dtype=x.dtype)

    return recursively_apply(_info, data)


def initialize_tensors(data_structure: Any) -> Any:
    """Materialize empty arrays from a skeleton (reference :231)."""
    from .dataclasses import TensorInformation

    def _init(info):
        return jnp.zeros(info.shape, dtype=info.dtype)

    return recursively_apply(
        _init, data_structure, test_type=lambda x: isinstance(x, TensorInformation)
    )


def find_batch_size(data: Any) -> Optional[int]:
    """Leading dimension of the first array leaf (reference :245)."""
    leaves = jax.tree.leaves(data, is_leaf=is_tensor)
    for leaf in leaves:
        if is_tensor(leaf) and leaf.ndim > 0:
            return int(leaf.shape[0])
    return None


def find_device(data: Any) -> Optional[Any]:
    """First device found in a pytree (reference :830)."""
    for leaf in jax.tree.leaves(data):
        if isinstance(leaf, jax.Array):
            devs = leaf.devices()
            if devs:
                return next(iter(devs))
    return None


def slice_tensors(data: Any, tensor_slice: slice) -> Any:
    """Slice every array leaf (reference :587)."""
    return recursively_apply(lambda t: t[tensor_slice], data)


def concatenate(data: list[Any], dim: int = 0) -> Any:
    """Concatenate a list of same-structure pytrees leafwise (reference :607)."""
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=dim), *data)


def convert_to_fp32(data: Any) -> Any:
    """Upcast floating leaves to fp32 (reference :768) — the analogue of
    ConvertOutputsToFp32 for bf16/fp16 step outputs."""

    def _upcast(x):
        if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != jnp.float32:
            return x.astype(jnp.float32)
        return x

    return recursively_apply(_upcast, data)


def _multiprocess() -> bool:
    return jax.process_count() > 1


def _to_local(x: Any) -> np.ndarray:
    """Fully materialize a (possibly sharded) array on host."""
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    return np.asarray(x)


# --------------------------------------------------------------------------- #
# collectives (host-level, cross-process)
# --------------------------------------------------------------------------- #
def gather(tensor: Any) -> Any:
    """All-gather per-process tensors along dim 0 (reference :425).

    Semantics table (matching the reference's ``_tpu_gather``/``_gpu_gather``):

    * multi-process, host-local leaf value -> every process returns the
      concatenation over processes (``process_allgather`` tiled).
    * globally-sharded jax.Array -> returns the full array, replicated and
      addressable everywhere (the SPMD equivalent: the data was already
      global, gather just makes every host see all of it).
    * single process -> identity (after de-sharding).
    """

    def _gather_one(x):
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            from jax.experimental import multihost_utils

            return jnp.asarray(multihost_utils.process_allgather(x, tiled=True))
        if _multiprocess():
            from jax.experimental import multihost_utils

            return jnp.asarray(
                multihost_utils.process_allgather(np.asarray(x), tiled=True)
            )
        return jnp.asarray(x)

    return recursively_apply(_gather_one, tensor)


def gather_object(object: Any) -> list[Any]:
    """Gather arbitrary picklable objects from all processes into a list
    (reference :451). Single process returns ``[object]``."""
    if not _multiprocess():
        return [object]
    from jax.experimental import multihost_utils

    payload = np.frombuffer(pickle.dumps(object), dtype=np.uint8)
    sizes = multihost_utils.process_allgather(np.array([payload.size]))
    max_size = int(np.max(sizes))
    padded = np.zeros(max_size, dtype=np.uint8)
    padded[: payload.size] = payload
    all_payloads = multihost_utils.process_allgather(padded)  # [P, max_size]
    out = []
    for i in range(all_payloads.shape[0]):
        size = int(np.asarray(sizes).reshape(-1)[i])
        out.append(pickle.loads(all_payloads[i, :size].tobytes()))
    return out


def broadcast(tensor: Any, from_process: int = 0) -> Any:
    """Broadcast array pytree from one process to all (reference :545)."""
    if not _multiprocess():
        return tensor
    from jax.experimental import multihost_utils

    return recursively_apply(
        lambda x: jnp.asarray(
            multihost_utils.broadcast_one_to_all(
                np.asarray(x), is_source=jax.process_index() == from_process
            )
        ),
        tensor,
    )


def broadcast_object_list(object_list: list[Any], from_process: int = 0) -> list[Any]:
    """Broadcast a list of picklable objects (reference :566). In-place-style:
    returns the source's list contents on every process."""
    if not _multiprocess():
        return object_list
    from jax.experimental import multihost_utils

    is_source = jax.process_index() == from_process
    payload = np.frombuffer(pickle.dumps(list(object_list)), dtype=np.uint8)
    size = multihost_utils.broadcast_one_to_all(
        np.array([payload.size]), is_source=is_source
    )
    buf = np.zeros(int(size[0]), dtype=np.uint8)
    if is_source:
        buf[:] = payload
    data = multihost_utils.broadcast_one_to_all(buf, is_source=is_source)
    result = pickle.loads(np.asarray(data).tobytes())
    object_list[:] = result
    return object_list


def reduce(tensor: Any, reduction: str = "mean", scale: float = 1.0) -> Any:
    """Elementwise cross-process reduce of same-shape per-process tensors
    (reference :727)."""

    def _reduce_one(x):
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            # globally sharded: data is already one logical array; reduce is
            # identity (matches reference semantics where the "copies" being
            # reduced are the DP replicas — GSPMD already summed grads).
            return x * scale
        if _multiprocess():
            from jax.experimental import multihost_utils

            stacked = multihost_utils.process_allgather(np.asarray(x))
            out = stacked.sum(axis=0) * scale
            if reduction == "mean":
                out = out / jax.process_count()
            return jnp.asarray(out)
        return jnp.asarray(x) * scale

    return recursively_apply(_reduce_one, tensor)


def pad_across_processes(
    tensor: Any, dim: int = 0, pad_index: int = 0, pad_first: bool = False
) -> Any:
    """Pad each process's tensor along ``dim`` to the max size across
    processes so a fixed-shape gather can follow (reference :634)."""
    if not _multiprocess():
        return tensor

    def _pad_one(x):
        x = np.asarray(x)
        if dim >= x.ndim:
            return x
        from jax.experimental import multihost_utils

        sizes = multihost_utils.process_allgather(np.array([x.shape[dim]]))
        max_size = int(np.max(sizes))
        if max_size == x.shape[dim]:
            return jnp.asarray(x)
        new_shape = list(x.shape)
        new_shape[dim] = max_size
        out = np.full(new_shape, pad_index, dtype=x.dtype)
        idx = [slice(None)] * x.ndim
        if pad_first:
            idx[dim] = slice(max_size - x.shape[dim], max_size)
        else:
            idx[dim] = slice(0, x.shape[dim])
        out[tuple(idx)] = x
        return jnp.asarray(out)

    return recursively_apply(_pad_one, tensor)


def pad_input_tensors(tensor: Any, batch_size: int, num_processes: int, dim: int = 0):
    """Pad the batch so it divides evenly across processes (reference :686)."""
    remainder = batch_size % num_processes
    if remainder == 0:
        return tensor
    pad = num_processes - remainder

    def _pad_one(x):
        if dim >= x.ndim or x.shape[dim] != batch_size:
            return x
        reps = jnp.concatenate([x] + [x[-1:]] * pad, axis=dim)
        return reps

    return recursively_apply(_pad_one, tensor)


# --------------------------------------------------------------------------- #
# debug-mode operational checker
# --------------------------------------------------------------------------- #
def verify_operation(function: Callable) -> Callable:
    """Decorator: in debug mode, gather every process's input pytree shapes
    and raise DistributedOperationException on mismatch *before* running the
    collective (reference utils/operations.py:370) — the collective
    sanitizer that turns silent hangs into errors."""
    import functools

    @functools.wraps(function)
    def wrapper(*args, **kwargs):
        from ..state import PartialState

        state = PartialState()
        if not getattr(state, "debug", False) or state.num_processes == 1:
            return function(*args, **kwargs)
        tensor = kwargs.get("tensor", args[0] if args else None)
        shapes = jax.tree.map(
            lambda x: tuple(x.shape) if is_tensor(x) else None, tensor
        )
        all_shapes = gather_object(shapes)
        if not all(s == all_shapes[0] for s in all_shapes):
            raise DistributedOperationException(
                f"Cannot apply desired operation due to shape mismatches. "
                f"All shapes across devices must be valid.\n\nOperation: `{function.__name__}`\n"
                f"Input shapes:\n  - "
                + "\n  - ".join(
                    f"Process {i}: {s}" for i, s in enumerate(all_shapes)
                )
            )
        return function(*args, **kwargs)

    return wrapper


# Apply the sanitizer to the shape-sensitive collectives, like the reference
# does. pad_across_processes is deliberately NOT wrapped: mismatched shapes
# are its job (reference wraps it with chained_operation, :633).
gather = verify_operation(gather)
broadcast = verify_operation(broadcast)
reduce = verify_operation(reduce)

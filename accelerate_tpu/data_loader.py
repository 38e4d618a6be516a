"""Data pipeline: shard host data across processes, land it in HBM as
globally-sharded arrays, prefetch ahead of the step.

Parity: reference ``src/accelerate/data_loader.py`` (1149 LoC):
``SeedableRandomSampler``:67, ``BatchSamplerShard``:100,
``IterableDatasetShard``:256, ``DataLoaderShard``:391 (one-batch-lookahead
iter :445-476), ``MpDeviceLoaderWrapper``:521, ``DataLoaderDispatcher``:562,
``prepare_data_loader``:797, ``skip_first_batches``:1082.

TPU-native redesign:

* Batches are **global jax.Arrays** with a ``NamedSharding`` over the data
  axes of the mesh — on multi-host, each process contributes its local
  shard via ``jax.make_array_from_process_local_data`` and XLA sees ONE
  logical batch; there is no per-rank tensor juggling above this module.
* Device placement is double-buffered by a background prefetch thread (the
  seat of torch-xla's ``MpDeviceLoader`` per-core prefetch :521), so the
  H2D copy of batch N+1 overlaps step N.
* XLA needs static shapes: the uneven tail batch is padded (and recorded in
  ``remainder``) instead of shipped ragged; ``gather_for_metrics`` uses the
  remainder to drop the padding — the fixed-shape answer to the reference's
  ``even_batches``/``join_uneven_inputs`` machinery.
"""

from __future__ import annotations

import math
import threading
import time
import queue as queue_mod
from typing import Any, Callable, Iterable, Iterator, Optional

import jax
import numpy as np

from .logging import get_logger
from .parallel.sharding import batch_sharding
from .state import AcceleratorState, GradientState
from .utils.dataclasses import DataLoaderConfiguration
from .utils.operations import broadcast_object_list, find_batch_size, recursively_apply

logger = get_logger(__name__)


def _to_numpy(batch: Any) -> Any:
    """Convert a host batch (torch tensors / lists / scalars) to numpy."""

    def _is_convertible(x):
        if isinstance(x, np.ndarray):
            return True
        # torch tensor without importing torch eagerly
        return type(x).__module__.startswith("torch") and hasattr(x, "numpy")

    def _conv(x):
        if isinstance(x, np.ndarray):
            return x
        return x.detach().cpu().numpy() if hasattr(x, "detach") else np.asarray(x)

    return recursively_apply(_conv, batch, test_type=_is_convertible)


class SeedableRandomSampler:
    """Deterministic epoch-seeded permutation sampler (reference
    data_loader.py:67): every process computes the identical shuffle from
    (seed, epoch) — no RNG-state broadcast needed, unlike the reference."""

    def __init__(self, data_source_len: int, seed: int = 0, epoch: int = 0):
        self.length = data_source_len
        self.seed = seed
        self.epoch = epoch

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch

    def __len__(self) -> int:
        return self.length

    def __iter__(self) -> Iterator[int]:
        rng = np.random.default_rng(self.seed + self.epoch)
        yield from rng.permutation(self.length).tolist()


class RandomSampler:
    """Non-seedable shuffle drawing from the process-global numpy RNG
    (reference RandomSampler path when use_seedable_sampler=False); identical
    shuffles across processes then rely on synchronize_rng_states."""

    def __init__(self, data_source_len: int):
        self.length = data_source_len

    def set_epoch(self, epoch: int) -> None:
        pass

    def __len__(self) -> int:
        return self.length

    def __iter__(self) -> Iterator[int]:
        yield from np.random.permutation(self.length).tolist()


class SequentialSampler:
    def __init__(self, data_source_len: int):
        self.length = data_source_len

    def set_epoch(self, epoch: int) -> None:
        pass

    def __len__(self) -> int:
        return self.length

    def __iter__(self) -> Iterator[int]:
        yield from range(self.length)


class BatchSamplerShard:
    """Yield this process's slice of each global batch of indices
    (reference data_loader.py:100).

    ``even_batches=True`` wraps around to complete the tail batch
    (reference _iter_with_split:186 wraparound); ``False`` yields the short
    tail — DataLoaderShard then pads it for XLA and records the remainder.
    """

    def __init__(
        self,
        sampler,
        batch_size: int,
        drop_last: bool = False,
        num_processes: int = 1,
        process_index: int = 0,
        split_batches: bool = False,
        even_batches: bool = True,
    ):
        if split_batches and batch_size % num_processes != 0:
            raise ValueError(
                f"batch_size {batch_size} must be divisible by num_processes "
                f"{num_processes} when split_batches=True"
            )
        self.sampler = sampler
        self.batch_size = batch_size
        self.drop_last = drop_last
        self.num_processes = num_processes
        self.process_index = process_index
        self.split_batches = split_batches
        self.even_batches = even_batches

    @property
    def global_batch_size(self) -> int:
        return (
            self.batch_size
            if self.split_batches
            else self.batch_size * self.num_processes
        )

    @property
    def local_batch_size(self) -> int:
        return self.global_batch_size // self.num_processes

    def __len__(self) -> int:
        n = len(self.sampler)
        if self.drop_last:
            return n // self.global_batch_size
        return math.ceil(n / self.global_batch_size)

    def set_epoch(self, epoch: int) -> None:
        if hasattr(self.sampler, "set_epoch"):
            self.sampler.set_epoch(epoch)

    def __iter__(self) -> Iterator[tuple[list[int], int]]:
        """Yields (local_indices, global_valid_count) pairs."""
        indices = list(self.sampler)
        gbs = self.global_batch_size
        for start in range(0, len(indices), gbs):
            batch = indices[start : start + gbs]
            if len(batch) < gbs:
                if self.drop_last:
                    return
                valid = len(batch)
                if self.even_batches:
                    # wrap around the dataset to fill (reference :186-207)
                    while len(batch) < gbs:
                        batch += indices[: gbs - len(batch)]
                else:
                    # short tail: repeat last index to keep shapes static;
                    # remainder tracking drops the padding in metrics.
                    batch = batch + [batch[-1]] * (gbs - len(batch))
                local = batch[
                    self.process_index * self.local_batch_size : (self.process_index + 1)
                    * self.local_batch_size
                ]
                yield local, valid
            else:
                local = batch[
                    self.process_index * self.local_batch_size : (self.process_index + 1)
                    * self.local_batch_size
                ]
                yield local, gbs


class IterableDatasetShard:
    """Shard an iterable (no len / no random access) across processes
    (reference data_loader.py:256): collect global batches from the stream,
    each process keeps its slice; tail padded + remainder reported."""

    def __init__(
        self,
        iterable: Iterable,
        batch_size: int,
        num_processes: int = 1,
        process_index: int = 0,
        drop_last: bool = False,
        even_batches: bool = True,
    ):
        self.iterable = iterable
        self.batch_size = batch_size
        self.num_processes = num_processes
        self.process_index = process_index
        self.drop_last = drop_last
        self.even_batches = even_batches

    def __iter__(self) -> Iterator[tuple[list[Any], int]]:
        gbs = self.batch_size * self.num_processes
        buffer: list[Any] = []
        first_batch: Optional[list[Any]] = None
        for item in self.iterable:
            buffer.append(item)
            if len(buffer) == gbs:
                if first_batch is None:
                    first_batch = list(buffer)
                yield buffer[
                    self.process_index * self.batch_size : (self.process_index + 1)
                    * self.batch_size
                ], gbs
                buffer = []
        if buffer and not self.drop_last:
            valid = len(buffer)
            pad_src = buffer if not self.even_batches else (buffer + (first_batch or buffer))
            while len(buffer) < gbs:
                buffer.append(pad_src[len(buffer) % len(pad_src)] if self.even_batches else buffer[-1])
            yield buffer[
                self.process_index * self.batch_size : (self.process_index + 1)
                * self.batch_size
            ], valid


def _sharding_data_degree(sharding) -> int:
    """Number of shards the batch dim is split into under ``sharding``."""
    spec0 = sharding.spec[0] if len(sharding.spec) else None
    if spec0 is None:
        return 1
    axes = spec0 if isinstance(spec0, tuple) else (spec0,)
    degree = 1
    for a in axes:
        degree *= sharding.mesh.shape[a]
    return degree


def _stack_superbatches(
    source: Iterator[tuple[Any, int]], k: int
) -> Iterator[tuple[Any, int]]:
    """Collate every ``k`` consecutive microbatches into ONE stacked
    ``[k, micro, ...]`` host batch — the input contract of the fused
    gradient-accumulation step (``unified_step(fused_accumulation=True)``),
    which ``lax.scan``s over the leading axis instead of being dispatched
    ``k`` times.

    A partial final group is padded by repeating its last microbatch so
    the stacked shape stays static for XLA; ``valid`` carries the TRUE
    global sample count summed across the k slots, so remainder tracking
    and loss masking can drop the padding.
    """
    group: list[Any] = []
    valid_total = 0
    for host_batch, valid in source:
        group.append(_to_numpy(host_batch))
        valid_total += valid
        if len(group) == k:
            yield _stack_group(group), valid_total
            group, valid_total = [], 0
    if group:
        # pad-and-mask: repeat the last microbatch to fill the stack
        while len(group) < k:
            group.append(group[-1])
        yield _stack_group(group), valid_total


def _stack_group(group: list[Any]) -> Any:
    return jax.tree.map(lambda *xs: np.stack(xs), *group)


def _default_collate(items: list[Any]) -> Any:
    """Stack a list of samples into a batch pytree."""
    first = items[0]
    if isinstance(first, dict):
        return {k: _default_collate([it[k] for it in items]) for k in first}
    if isinstance(first, (tuple, list)):
        return type(first)(
            _default_collate([it[i] for it in items]) for i in range(len(first))
        )
    return np.stack([np.asarray(it) for it in items])


class DataLoaderStateMixin:
    """begin/end hooks wiring GradientState (reference data_loader.py:355)."""

    def begin(self):
        self.end_of_dataloader = False
        self.remainder = -1
        GradientState()._add_dataloader(self)

    def end(self):
        GradientState()._remove_dataloader(self)


class DataLoaderShard(DataLoaderStateMixin):
    """The prepared training dataloader: yields globally-sharded device
    batches with background prefetch (reference data_loader.py:391 +
    MpDeviceLoaderWrapper:521 in one object)."""

    def __init__(
        self,
        batch_iter_factory: Callable[[], Iterator[tuple[Any, int]]],
        num_batches: Optional[int],
        sharding,
        global_batch_size: int,
        prefetch_size: int = 2,
        rng_synchronizer: Optional[Callable[[], None]] = None,
        sampler=None,
        superbatch: int = 1,
        _skip_batches: int = 0,
    ):
        self._factory = batch_iter_factory
        self._num_batches = num_batches
        self.sharding = sharding
        self.global_batch_size = global_batch_size
        # superbatch=K: stack K consecutive microbatches into one
        # [K, micro, ...] device batch for the fused-accumulation step.
        # The K axis is replicated; the batch axis (now axis 1) keeps the
        # data sharding. global_batch_size stays the per-MICROBATCH size.
        self.superbatch = max(1, int(superbatch))
        self.prefetch_size = max(1, prefetch_size)
        self._rng_synchronizer = rng_synchronizer
        self.sampler = sampler
        self.epoch = 0
        self._skip_batches = _skip_batches
        self._batches_yielded = 0  # position within the current epoch
        self.end_of_dataloader = False
        self.remainder = -1
        # set by Accelerator.prepare_data_loader: a StepTelemetry that gets
        # told how long the loop blocked waiting for each batch, so step
        # records separate input starvation from compute
        self.telemetry = None

    def _timed_get(self, q: "queue_mod.Queue") -> Any:
        """q.get() that reports blocking time to the telemetry collector.

        The producer thread prefetches, so in a healthy pipeline the queue
        is non-empty and this is ~0; sustained dataloader_wait_s means the
        input pipeline — not the TPU — is the bottleneck."""
        tel = self.telemetry
        if tel is None or not tel.enabled:
            return q.get()
        t0 = time.perf_counter()
        item = q.get()
        tel.record_dataloader_wait(time.perf_counter() - t0, source="shard")
        return item

    @property
    def total_batch_size(self) -> int:
        return self.global_batch_size

    def _stacked_sharding(self):
        """Sharding of a [K, micro, ...] superbatch: leading K axis
        replicated (every device scans all K slots), batch axis keeps the
        data-parallel split — GSPMD then propagates it through lax.scan."""
        return jax.sharding.NamedSharding(
            self.sharding.mesh,
            jax.sharding.PartitionSpec(None, *tuple(self.sharding.spec)),
        )

    def batch_spec(self) -> Any:
        """Abstract spec of one global device batch: a pytree of
        ``jax.ShapeDtypeStruct`` with the shardings :meth:`__iter__` would
        commit — the AOT-warmup contract (``accelerator.warmup``). Every
        batch is padded to one fixed shape, so the first batch's spec is
        THE spec. In superbatch mode the spec gains the leading stacked
        ``K`` axis (the shape the fused step is compiled for).

        Collates one host batch from a fresh iterator to read the shapes
        (no device transfer, no training-iterator state touched)."""
        source = self._factory()
        try:
            host_batch, _valid = next(iter(source))
        except StopIteration:
            raise ValueError("empty dataloader: no batch to derive a spec from")
        finally:
            close = getattr(source, "close", None)
            if close is not None:
                close()
        host_batch = _to_numpy(host_batch)
        num_processes = jax.process_count()
        data_degree = _sharding_data_degree(self.sharding)
        k = self.superbatch

        def _spec(x):
            # mirror _device_put's placement decisions exactly; the factory
            # yields microbatches, so in superbatch mode prepend the K axis
            x = np.asarray(x)
            if x.ndim == 0 or (x.shape[0] * num_processes) % data_degree != 0:
                replicated = jax.sharding.NamedSharding(
                    self.sharding.mesh, jax.sharding.PartitionSpec()
                )
                shape = (k,) + x.shape if k > 1 else x.shape
                return jax.ShapeDtypeStruct(shape, x.dtype, sharding=replicated)
            if k > 1:
                global_shape = (k, x.shape[0] * num_processes) + x.shape[1:]
                return jax.ShapeDtypeStruct(
                    global_shape, x.dtype, sharding=self._stacked_sharding()
                )
            global_shape = (x.shape[0] * num_processes,) + x.shape[1:]
            return jax.ShapeDtypeStruct(global_shape, x.dtype, sharding=self.sharding)

        return recursively_apply(
            _spec, host_batch, test_type=lambda x: isinstance(x, np.ndarray)
        )

    def __len__(self) -> int:
        if self._num_batches is None:
            raise TypeError("this dataloader has no length")
        n = self._num_batches
        if self.superbatch > 1:
            # the factory counts microbatches; we yield stacked superbatches
            n = math.ceil(n / self.superbatch)
        return max(0, n - self._skip_batches)

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch
        if self.sampler is not None and hasattr(self.sampler, "set_epoch"):
            self.sampler.set_epoch(epoch)

    def state_dict(self) -> dict:
        """Checkpointable cursor: epoch + intra-epoch position, plus the
        global batch size the position was counted under so a restore on a
        different topology can re-derive it by samples seen."""
        return {
            "epoch": self.epoch,
            "batches_yielded": self._batches_yielded,
            "global_batch_size": self.global_batch_size * self.superbatch,
        }

    def load_state_dict(self, state: dict) -> None:
        """Restore the cursor. Same global batch size: skip exactly the
        yielded batches. Different (a reshaped restore whose per-process
        count changed the effective global batch): re-derive the position
        from SAMPLES seen — ``batches * saved_gbs // live_gbs`` — rounded
        DOWN to a whole live batch, so no sample is skipped unseen (a few
        may repeat; the conservative side of the trade)."""
        self.set_epoch(int(state.get("epoch", 0)))
        seen = int(state.get("batches_yielded", 0))
        saved_gbs = int(state.get("global_batch_size", 0) or 0)
        live_gbs = self.global_batch_size * self.superbatch
        if saved_gbs and live_gbs and saved_gbs != live_gbs:
            samples = seen * saved_gbs
            seen = samples // live_gbs
            logger.warning(
                "dataloader cursor re-derived for a changed global batch "
                "size (%d -> %d): %d samples seen -> resume at batch %d",
                saved_gbs,
                live_gbs,
                samples,
                seen,
            )
        self._skip_batches = seen
        self._batches_yielded = seen

    def _device_put(self, host_batch: Any, valid: int) -> Any:
        """Host numpy pytree -> global sharded jax.Array pytree.

        In superbatch mode ``host_batch`` arrives already stacked
        ``[K, micro, ...]`` (the producer ran :func:`_stack_superbatches`),
        so the batch dim is axis 1 and the K axis is replicated."""
        num_processes = jax.process_count()
        data_degree = _sharding_data_degree(self.sharding)
        batch_axis = 1 if self.superbatch > 1 else 0

        def _make(x):
            x = np.asarray(x)
            sharding = self.sharding
            if (
                x.ndim <= batch_axis
                or (x.shape[batch_axis] * num_processes) % data_degree != 0
            ):
                # batch not divisible over the data axes: replicate (correct,
                # just not parallel) rather than crash mid-epoch.
                logger.warning_once(
                    "batch dim %s not divisible by data-parallel degree %s; "
                    "replicating this input",
                    x.shape[batch_axis] if x.ndim > batch_axis else 0,
                    data_degree,
                )
                sharding = jax.sharding.NamedSharding(
                    self.sharding.mesh, jax.sharding.PartitionSpec()
                )
                return jax.device_put(x, sharding)
            if batch_axis == 1:
                sharding = self._stacked_sharding()
                if num_processes > 1:
                    global_shape = (
                        x.shape[0],
                        x.shape[1] * num_processes,
                    ) + x.shape[2:]
                    return jax.make_array_from_process_local_data(
                        sharding, x, global_shape
                    )
                return jax.device_put(x, sharding)
            if num_processes > 1:
                return jax.make_array_from_process_local_data(sharding, x)
            return jax.device_put(x, sharding)

        batch = recursively_apply(
            _make, host_batch, test_type=lambda x: isinstance(x, np.ndarray)
        )
        return batch

    def __iter__(self) -> Iterator[Any]:
        if self._rng_synchronizer is not None:
            self._rng_synchronizer()
        self.begin()
        q: queue_mod.Queue = queue_mod.Queue(maxsize=self.prefetch_size)
        stop = object()
        cancelled = threading.Event()
        try:
            source = self._factory()
            if self.superbatch > 1:
                # the generator is consumed by the producer thread, so the
                # K-way stacking (host collate) happens off the step loop
                source = _stack_superbatches(source, self.superbatch)

            def _put(item) -> bool:
                """put that gives up when the consumer is gone (break/GC) —
                otherwise the producer thread would block forever on a full
                queue and pin prefetched device batches."""
                while not cancelled.is_set():
                    try:
                        q.put(item, timeout=0.2)
                        return True
                    except queue_mod.Full:
                        continue
                return False

            def _producer():
                try:
                    skipped = 0
                    for host_batch, valid in source:
                        if cancelled.is_set():
                            return
                        if skipped < self._skip_batches:
                            skipped += 1
                            continue
                        # host work only in this thread: collate/convert.
                        # The device_put happens on the consumer thread —
                        # concurrent jax dispatch from two threads can wedge
                        # XLA:CPU collective rendezvous, and on TPU
                        # device_put is async so the consumer-side put still
                        # overlaps H2D with the running step.
                        host_batch = _to_numpy(host_batch)
                        if not _put((host_batch, valid)):
                            return
                    _put(stop)
                except BaseException as e:  # surface producer errors
                    _put(e)

            thread = threading.Thread(target=_producer, daemon=True)
            thread.start()

            # skipped batches count as consumed positions in the cursor
            self._batches_yielded = self._skip_batches
            current = self._timed_get(q)
            if isinstance(current, BaseException):
                raise current
            while current is not stop:
                nxt = self._timed_get(q)
                if isinstance(nxt, BaseException):
                    raise nxt
                host_batch, valid = current
                batch = self._device_put(host_batch, valid)
                if self.global_batch_size == 0:
                    # iterable-of-batches path: learn the batch size from the
                    # first batch so the tail's remainder is detected
                    self.global_batch_size = valid // self.superbatch
                # a full superbatch carries K microbatches' worth of samples
                gbs = self.global_batch_size * self.superbatch
                if nxt is stop:
                    # one-batch lookahead: mark last batch before yielding it
                    # (reference data_loader.py:445-476)
                    self.end_of_dataloader = True
                    self.remainder = valid if valid != gbs else 0
                yield batch
                self._batches_yielded += 1
                current = nxt
        finally:
            cancelled.set()
            # drain so a blocked producer can observe the cancel promptly
            try:
                while True:
                    q.get_nowait()
            except queue_mod.Empty:
                pass
            self.end()
            self._skip_batches = 0
            if self.end_of_dataloader:
                self._batches_yielded = 0  # full epoch consumed


class DataLoaderDispatcher(DataLoaderShard):
    """Process 0 reads the dataset and broadcasts each global batch to all
    processes (reference data_loader.py:562) — for datasets only rank 0 can
    see. On TPU the broadcast is a host-level object collective; prefer
    DataLoaderShard when every host can read its shard."""

    def __iter__(self) -> Iterator[Any]:
        if jax.process_count() == 1:
            yield from super().__iter__()
            return
        self.begin()
        try:
            is_main = jax.process_index() == 0
            source = self._factory() if is_main else None
            if source is not None and self.superbatch > 1:
                # stack before broadcast so every process receives the
                # ready-made [K, micro, ...] superbatch
                source = _stack_superbatches(source, self.superbatch)
            skipped = 0

            def _next_payload():
                nonlocal skipped
                if is_main:
                    while True:
                        try:
                            host_batch, valid = next(source)  # type: ignore[arg-type]
                        except StopIteration:
                            payload = [None, 0, True]
                            break
                        if skipped < self._skip_batches:
                            skipped += 1
                            continue
                        payload = [_to_numpy(host_batch), valid, False]
                        break
                else:
                    payload = [None, 0, True]
                return broadcast_object_list(payload, from_process=0)

            def _next_payload_timed():
                # no prefetch thread on this path: the whole read+broadcast
                # blocks the loop, so all of it is dataloader wait
                tel = self.telemetry
                if tel is None or not tel.enabled:
                    return _next_payload()
                t0 = time.perf_counter()
                payload = _next_payload()
                tel.record_dataloader_wait(
                    time.perf_counter() - t0, source="dispatcher"
                )
                return payload

            def _to_batch(payload):
                host_batch, valid, _ = payload
                num = jax.process_count()
                idx = jax.process_index()

                def _slice(x):
                    # superbatch payloads carry the batch dim at axis 1
                    axis = 1 if self.superbatch > 1 and x.ndim > 1 else 0
                    local = x.shape[axis] // num
                    if axis == 1:
                        return x[:, idx * local : (idx + 1) * local]
                    return x[idx * local : (idx + 1) * local]

                local_batch = recursively_apply(
                    _slice, host_batch, test_type=lambda x: isinstance(x, np.ndarray)
                )
                return self._device_put(local_batch, valid), valid

            # one-payload lookahead so the last batch is marked before yield
            self._batches_yielded = self._skip_batches
            current = _next_payload_timed()
            while not current[2]:
                nxt = _next_payload_timed()
                batch, valid = _to_batch(current)
                if nxt[2]:
                    self.end_of_dataloader = True
                    full = self.global_batch_size * self.superbatch
                    self.remainder = valid if valid != full else 0
                yield batch
                self._batches_yielded += 1
                current = nxt
        finally:
            self.end()
            self._skip_batches = 0
            if self.end_of_dataloader:
                self._batches_yielded = 0  # full epoch consumed


def prepare_data_loader(
    dataloader: Any,
    state: Optional[AcceleratorState] = None,
    config: Optional[DataLoaderConfiguration] = None,
    seed: int = 0,
    skip_batches: int = 0,
    superbatch: int = 1,
) -> DataLoaderShard:
    """Turn a host dataloader into a DataLoaderShard (reference
    data_loader.py:797 decision tree).

    Accepts:
    * our :class:`DataLoader` (or anything exposing ``dataset``,
      ``batch_size``, ``shuffle``/``sampler``, ``drop_last``, ``collate_fn``)
      — includes torch.utils.data.DataLoader;
    * a bare iterable of already-batched pytrees (treated as an iterable
      dataset of batches on every process).

    The incoming ``batch_size`` is the **per-process** batch; the prepared
    loader yields the global batch (``batch_size * num_processes``) as one
    sharded array (``split_batches=True``: the incoming batch is already the
    global batch and is split).

    ``superbatch=K`` (K > 1) puts the loader in stacked mode for fused
    gradient accumulation: each yielded device batch stacks K consecutive
    microbatches as ``[K, micro, ...]`` (K axis replicated, batch axis
    data-sharded); a partial final group is padded by repeating its last
    microbatch with the true sample count recorded in ``remainder``.
    """
    state = state or AcceleratorState()
    config = config or getattr(state, "dataloader_config", None) or DataLoaderConfiguration()
    mesh = state.mesh
    sharding = batch_sharding(mesh)
    num_processes = state.num_processes
    process_index = state.process_index

    dataset = getattr(dataloader, "dataset", None)
    batch_size = getattr(dataloader, "batch_size", None)

    if dataset is not None and batch_size is not None and hasattr(dataset, "__len__"):
        # map-style dataset: shard by sampler
        collate = getattr(dataloader, "collate_fn", None) or _default_collate
        shuffle = _loader_shuffles(dataloader)
        if not shuffle:
            sampler = SequentialSampler(len(dataset))
        elif config.use_seedable_sampler:
            sampler = SeedableRandomSampler(len(dataset), seed=seed)
        else:
            sampler = RandomSampler(len(dataset))
        drop_last = bool(getattr(dataloader, "drop_last", False) or config.drop_last)
        shard = BatchSamplerShard(
            sampler,
            batch_size,
            drop_last=drop_last,
            num_processes=num_processes,
            process_index=process_index,
            split_batches=config.split_batches,
            even_batches=config.even_batches,
        )

        dispatching = bool(config.dispatch_batches) and num_processes > 1
        # Dispatcher mode: ONLY rank 0 runs the factory and must produce the
        # whole GLOBAL batch (the dispatcher slices per process afterwards)
        # — a per-process shard here would get sliced twice, silently
        # dropping (num_processes-1)/num_processes of every batch.
        factory_shard = (
            BatchSamplerShard(
                sampler,
                shard.global_batch_size,
                drop_last=drop_last,
                num_processes=1,
                process_index=0,
                split_batches=False,
                even_batches=config.even_batches,
            )
            if dispatching
            else shard
        )

        def factory():
            for local_indices, valid in iter(factory_shard):
                items = [dataset[i] for i in local_indices]
                yield collate(items), valid

        global_bs = shard.global_batch_size
        data_degree = _sharding_data_degree(sharding)
        if global_bs % data_degree != 0:
            raise ValueError(
                f"global batch size {global_bs} (batch_size x num_processes) must be "
                f"divisible by the data-parallel device count {data_degree} so XLA can "
                f"shard the batch. Increase batch_size, or reduce the dp/fsdp mesh axes."
            )
        num_batches = len(factory_shard)
        cls = DataLoaderDispatcher if dispatching else DataLoaderShard
        batch_sampler = factory_shard
        out = cls(
            factory,
            num_batches,
            sharding,
            global_bs,
            prefetch_size=config.prefetch_size,
            sampler=sampler,
            superbatch=superbatch,
            _skip_batches=skip_batches,
        )
        # exposed for join_uneven_inputs: flipping .even_batches takes
        # effect on the next epoch's iter(factory_shard)
        out.batch_sampler = batch_sampler
        return out

    # iterable of pre-batched pytrees
    def factory():
        for batch in dataloader:
            batch = _to_numpy(batch)
            bs = find_batch_size(batch) or 0
            yield batch, bs

    try:
        num_batches = len(dataloader)
    except TypeError:
        num_batches = None
    return DataLoaderShard(
        factory,
        num_batches,
        sharding,
        global_batch_size=getattr(dataloader, "global_batch_size", 0) or 0,
        prefetch_size=config.prefetch_size,
        superbatch=superbatch,
        _skip_batches=skip_batches,
    )


def _loader_shuffles(dataloader: Any) -> bool:
    """Best-effort detection of shuffling on the incoming loader."""
    if getattr(dataloader, "shuffle", None) is not None:
        return bool(dataloader.shuffle)
    sampler = getattr(dataloader, "sampler", None)
    if sampler is not None:
        return type(sampler).__name__ in ("RandomSampler", "SeedableRandomSampler")
    return False


class DataLoader:
    """Minimal torch-free host dataloader: map-style dataset + batch/shuffle/
    collate. Exists so the framework has no torch dependency; torch loaders
    are also accepted by prepare_data_loader directly."""

    def __init__(
        self,
        dataset: Any,
        batch_size: int = 1,
        shuffle: bool = False,
        drop_last: bool = False,
        collate_fn: Optional[Callable] = None,
        seed: int = 0,
    ):
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.collate_fn = collate_fn or _default_collate
        self.seed = seed
        self._epoch = 0

    def set_epoch(self, epoch: int) -> None:
        self._epoch = epoch

    def __len__(self) -> int:
        n = len(self.dataset)
        return n // self.batch_size if self.drop_last else math.ceil(n / self.batch_size)

    def __iter__(self) -> Iterator[Any]:
        indices = (
            np.random.default_rng(self.seed + self._epoch).permutation(len(self.dataset))
            if self.shuffle
            else np.arange(len(self.dataset))
        )
        for start in range(0, len(indices), self.batch_size):
            chunk = indices[start : start + self.batch_size]
            if self.drop_last and len(chunk) < self.batch_size:
                return
            yield self.collate_fn([self.dataset[int(i)] for i in chunk])


def skip_first_batches(dataloader: DataLoaderShard, num_batches: int = 0):
    """Resume mid-epoch: a view of the loader that skips the first
    ``num_batches`` (reference data_loader.py:1082)."""
    if isinstance(dataloader, DataLoaderShard):
        dataloader._skip_batches = num_batches
        return dataloader
    raise TypeError(
        "skip_first_batches expects a loader returned by prepare()/prepare_data_loader()"
    )

"""Process/device state singletons.

Parity: reference ``src/accelerate/state.py`` — ``PartialState``:110,
``AcceleratorState``:805, ``GradientState``:1082, including the shared-dict
singleton trick (:78-107). TPU-native redesign: ``torch.distributed.
init_process_group`` / backend selection (:708-760) becomes
``jax.distributed.initialize`` (one process per host, single-controller
SPMD), and the device mesh — absent in the reference, where topology hides
inside NCCL process groups — is a first-class member here.
"""

from __future__ import annotations

import logging
import os
from contextlib import contextmanager
from functools import wraps
from typing import Any, Callable, Iterable, Optional

import jax

from .parallel.mesh import build_mesh, data_axes, mesh_axis_size
from .utils.constants import ENV_PREFIX
from .utils.dataclasses import (
    DataLoaderConfiguration,
    DistributedInitKwargs,
    DistributedType,
    GradientAccumulationPlugin,
    MixedPrecisionPolicy,
    ParallelismPlugin,
    PrecisionType,
)
from .utils.environment import parse_flag_from_env

logger = logging.getLogger(__name__)


def _maybe_init_distributed(kwargs: Optional[DistributedInitKwargs]) -> None:
    """Bring up the multi-process JAX runtime when the launcher asked for it.

    The launcher (commands/launch.py) sets ACCELERATE_TPU_NUM_PROCESSES /
    COORDINATOR_ADDRESS / PROCESS_ID; on GCE TPU pods jax.distributed can
    also self-discover from metadata. Idempotent.

    ORDER MATTERS: this must not touch any backend-initializing JAX API
    (jax.process_count(), jax.devices(), ...) before calling
    jax.distributed.initialize — doing so pins the single-process backend
    and makes initialize() raise unconditionally. All the pre-checks below
    are env/kwargs reads only.
    """
    num = kwargs.num_processes if kwargs and kwargs.num_processes else None
    if num is None:
        env = os.environ.get(ENV_PREFIX + "NUM_PROCESSES")
        num = int(env) if env else None
    coord = (kwargs.coordinator_address if kwargs else None) or os.environ.get(
        ENV_PREFIX + "COORDINATOR_ADDRESS"
    )
    if not coord and (num is None or num <= 1):
        return
    from jax._src import distributed as _jax_distributed

    if _jax_distributed.global_state.client is not None:
        return  # already initialized by someone else
    pid = kwargs.process_id if kwargs and kwargs.process_id is not None else None
    if pid is None:
        env = os.environ.get(ENV_PREFIX + "PROCESS_ID")
        pid = int(env) if env else None
    extra = {}
    if kwargs and kwargs.local_device_ids is not None:
        extra["local_device_ids"] = kwargs.local_device_ids
    if kwargs and kwargs.initialization_timeout is not None:
        extra["initialization_timeout"] = int(
            kwargs.initialization_timeout.total_seconds()
        )
    if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu" or (
        os.environ.get("JAX_PLATFORM_NAME", "").strip() == "cpu"
    ):
        # XLA:CPU has no native cross-process collectives ("Multiprocess
        # computations aren't implemented on the CPU backend"); the gloo
        # transport must be selected BEFORE initialize() or every
        # multi-process debug/elastic run dies at its first collective.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    # the launcher ASKED for a multi-process run: a failure here raises —
    # N ranks each training alone would look like a healthy job
    jax.distributed.initialize(
        coordinator_address=coord, num_processes=num, process_id=pid, **extra
    )


class PartialState:
    """Singleton holding process topology + collective entry points
    (reference state.py:110). One instance per python process; in JAX's
    single-controller model one process drives all local devices, so the
    reference's per-GPU ranks map to (process_index, local devices)."""

    _shared_state: dict[str, Any] = {}

    def __init__(self, cpu: bool = False, **kwargs):
        self.__dict__ = self._shared_state
        if self.initialized:
            return
        init_kwargs = kwargs.get("init_kwargs")
        if cpu:
            # Force the CPU backend (reference semantics: cpu=True debugs on
            # CPU even on an accelerator host). Only possible before the XLA
            # backend initializes; best-effort otherwise.
            try:
                jax.config.update("jax_platforms", "cpu")
            except Exception:
                logger.warning("could not force CPU backend; it is already live")
        else:
            _maybe_init_distributed(init_kwargs)
        self.fork_launched = parse_flag_from_env("FORK_LAUNCHED", False)
        backend = jax.default_backend()
        self.backend = backend
        self.num_processes = jax.process_count()
        self.process_index = jax.process_index()
        self.local_process_index = int(
            os.environ.get(ENV_PREFIX + "LOCAL_PROCESS_INDEX", 0)
        )
        self.device = jax.local_devices()[0]
        self.num_devices = jax.device_count()
        self.num_local_devices = jax.local_device_count()
        if backend == "tpu":
            self.distributed_type = (
                DistributedType.MULTI_TPU
                if self.num_processes > 1
                else (DistributedType.TPU if self.num_devices > 1 else DistributedType.NO)
            )
        else:
            self.distributed_type = (
                DistributedType.MULTI_CPU
                if self.num_processes > 1
                else (DistributedType.CPU if self.num_devices > 1 else DistributedType.NO)
            )
        self.debug = parse_flag_from_env(ENV_PREFIX + "DEBUG_MODE")

    @property
    def initialized(self) -> bool:
        return "distributed_type" in self.__dict__

    @staticmethod
    def _reset_state():
        """Wipe the singleton (test isolation; reference state.py:105)."""
        PartialState._shared_state.clear()

    def __repr__(self) -> str:
        return (
            f"Distributed environment: {self.distributed_type}\n"
            f"Num processes: {self.num_processes}\n"
            f"Process index: {self.process_index}\n"
            f"Local devices: {self.num_local_devices} / {self.num_devices} global\n"
            f"Device: {self.device}\n"
        )

    # ------------------------------------------------------------------ #
    # process predicates
    # ------------------------------------------------------------------ #
    @property
    def use_distributed(self) -> bool:
        return self.num_processes > 1 or self.num_devices > 1

    @property
    def is_main_process(self) -> bool:
        return self.process_index == 0

    @property
    def is_local_main_process(self) -> bool:
        return self.local_process_index == 0

    @property
    def is_last_process(self) -> bool:
        return self.process_index == self.num_processes - 1

    # ------------------------------------------------------------------ #
    # process control
    # ------------------------------------------------------------------ #
    def wait_for_everyone(self) -> None:
        """Cross-process barrier (reference state.py:347). Single-process is
        a no-op; multi-process syncs all hosts via a tiny global collective."""
        if self.num_processes > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("accelerate_tpu.wait_for_everyone")

    @contextmanager
    def main_process_first(self):
        """Main process runs the body before others (reference state.py:481)
        — e.g. dataset download/tokenization caches."""
        if not self.is_main_process:
            self.wait_for_everyone()
        yield
        if self.is_main_process:
            self.wait_for_everyone()

    @contextmanager
    def local_main_process_first(self):
        if not self.is_local_main_process:
            self.wait_for_everyone()
        yield
        if self.is_local_main_process:
            self.wait_for_everyone()

    def on_main_process(self, function: Callable) -> Callable:
        @wraps(function)
        def wrapper(*args, **kwargs):
            if self.is_main_process:
                return function(*args, **kwargs)

        return wrapper

    def on_local_main_process(self, function: Callable) -> Callable:
        @wraps(function)
        def wrapper(*args, **kwargs):
            if self.is_local_main_process:
                return function(*args, **kwargs)

        return wrapper

    def on_process(self, function: Callable, process_index: int = 0) -> Callable:
        @wraps(function)
        def wrapper(*args, **kwargs):
            if self.process_index == process_index:
                return function(*args, **kwargs)

        return wrapper

    @contextmanager
    def split_between_processes(
        self, inputs: Any, apply_padding: bool = False
    ):
        """Split a list/dict/tuple evenly across processes (reference
        state.py:392). With ``apply_padding`` the last items are repeated so
        every process gets the same count (for fixed-shape collectives)."""
        if self.num_processes == 1:
            yield inputs
            return
        if isinstance(inputs, dict):
            split = {}
            with self.split_between_processes(
                list(zip(*inputs.values())), apply_padding
            ) as rows:
                for i, key in enumerate(inputs.keys()):
                    split[key] = [row[i] for row in rows]
            yield split
            return
        length = len(inputs)
        num = self.num_processes
        base, extra = divmod(length, num)
        # first `extra` processes get one more element
        start = self.process_index * base + min(self.process_index, extra)
        end = start + base + (1 if self.process_index < extra else 0)
        chunk = inputs[start:end]
        if apply_padding and extra != 0:
            target = base + 1
            if len(chunk) < target and length:
                pad = inputs[-1:] * (target - len(chunk))
                chunk = list(chunk) + pad
        yield chunk

    def print(self, *args, **kwargs) -> None:
        """Print once (main process only) — reference state.py:561."""
        if self.is_main_process:
            print(*args, **kwargs)

    def destroy_process_group(self) -> None:
        if self.num_processes > 1:
            try:
                jax.distributed.shutdown()
            except Exception:
                pass

    @property
    def local_devices(self) -> list[jax.Device]:
        return jax.local_devices()


class AcceleratorState:
    """Full accelerator-level state: PartialState + precision + parallelism
    mesh + plugins (reference state.py:805)."""

    _shared_state: dict[str, Any] = {}

    def __init__(
        self,
        mixed_precision: Optional[str] = None,
        cpu: bool = False,
        parallelism_plugin: Optional[ParallelismPlugin] = None,
        gradient_accumulation_plugin: Optional[GradientAccumulationPlugin] = None,
        dataloader_config: Optional[DataLoaderConfiguration] = None,
        compile_plugin=None,
        **kwargs,
    ):
        self.__dict__ = self._shared_state
        if self.initialized:
            if mixed_precision is not None and str(mixed_precision) != str(
                self.mixed_precision
            ):
                logger.warning(
                    "AcceleratorState already initialized with mixed_precision=%s; "
                    "ignoring new value %s",
                    self.mixed_precision,
                    mixed_precision,
                )
            return
        self.partial_state = PartialState(cpu, **kwargs)
        self.gradient_accumulation_plugin = gradient_accumulation_plugin
        if mixed_precision is None:
            mixed_precision = os.environ.get(ENV_PREFIX + "MIXED_PRECISION", "no")
        self.mixed_precision = PrecisionType(str(mixed_precision))
        self.mixed_precision_policy = MixedPrecisionPolicy.from_precision(
            self.mixed_precision
        )
        self.parallelism_plugin = parallelism_plugin or ParallelismPlugin.pure_dp()
        self.dataloader_config = dataloader_config or DataLoaderConfiguration()
        # Persistent XLA compilation cache: activated here — the same
        # once-per-process seat that builds the mesh — so every jit in the
        # process (user code included, not just the unified step) reuses
        # compiles across restarts. The directory is resolved by the one
        # rule in compilation/cache.py.
        from .compilation import activate_persistent_cache

        self.compile_plugin = compile_plugin
        self.compile_cache_dir = activate_persistent_cache(compile_plugin)
        self.mesh = build_mesh(self.parallelism_plugin)
        self.data_axis_names = data_axes(self.mesh)
        self.data_parallel_size = mesh_axis_size(self.mesh, *self.data_axis_names)

    def reform_mesh(self, devices: Optional[Iterable[jax.Device]] = None):
        """Rebuild the device mesh from an explicit device set (the elastic
        survivor path: after a relaunch at a smaller world size, or — in
        tests — to model a shrunken fleet on a device subset). ``-1`` auto
        axes in the parallelism plugin re-resolve against the new device
        count; fixed axes that no longer divide it raise, same as at init.
        Returns the new mesh; derived data-axis bookkeeping is refreshed."""
        devices = list(devices) if devices is not None else None
        self.mesh = build_mesh(self.parallelism_plugin, devices=devices)
        self.data_axis_names = data_axes(self.mesh)
        self.data_parallel_size = mesh_axis_size(self.mesh, *self.data_axis_names)
        return self.mesh

    @property
    def initialized(self) -> bool:
        return "partial_state" in self.__dict__

    @staticmethod
    def _reset_state(reset_partial_state: bool = False):
        AcceleratorState._shared_state.clear()
        if reset_partial_state:
            PartialState._reset_state()

    def __getattr__(self, name: str):
        # Delegate process topology to PartialState (reference state.py:1070).
        if name in ("partial_state", "initialized") or name.startswith("__"):
            raise AttributeError(name)
        ps = self.__dict__.get("partial_state")
        if ps is not None and hasattr(ps, name):
            return getattr(ps, name)
        raise AttributeError(
            f"'AcceleratorState' object has no attribute '{name}'"
        )

    def __repr__(self) -> str:
        return (
            repr(self.partial_state)
            + f"Mixed precision: {self.mixed_precision}\n"
            + f"Mesh: {dict(self.mesh.shape)}\n"
        )


class GradientState:
    """Gradient-accumulation bookkeeping shared between Accelerator,
    dataloaders and wrapped optimizer (reference state.py:1082).

    On TPU the *arithmetic* of accumulation runs inside the compiled step
    (carried grad buffer + lax.cond apply); this singleton tracks the
    host-side schedule — whether the *current* host step is an optimizer
    boundary — which gates scheduler stepping and `sync_gradients` parity
    semantics, plus dataloader end/remainder state for gather_for_metrics.
    """

    _shared_state: dict[str, Any] = {}

    def __init__(
        self, gradient_accumulation_plugin: Optional[GradientAccumulationPlugin] = None
    ):
        self.__dict__ = self._shared_state
        if not self.initialized:
            self.sync_gradients = True
            self.active_dataloader = None
            self.dataloader_references: list[Any] = [None]
            self.plugin_kwargs = (
                gradient_accumulation_plugin.to_kwargs()
                if gradient_accumulation_plugin is not None
                else {}
            )
            self._num_steps = (
                gradient_accumulation_plugin.num_steps
                if gradient_accumulation_plugin is not None
                else 1
            )
        elif gradient_accumulation_plugin is not None:
            self.plugin_kwargs = gradient_accumulation_plugin.to_kwargs()
            self._num_steps = gradient_accumulation_plugin.num_steps

    @property
    def initialized(self) -> bool:
        return "sync_gradients" in self.__dict__

    @property
    def num_steps(self) -> int:
        return self._num_steps

    @num_steps.setter
    def num_steps(self, value: int):
        self._num_steps = value

    @property
    def sync_with_dataloader(self) -> bool:
        return self.plugin_kwargs.get("sync_with_dataloader", True)

    @property
    def adjust_scheduler(self) -> bool:
        return self.plugin_kwargs.get("adjust_scheduler", True)

    @property
    def sync_each_batch(self) -> bool:
        return self.plugin_kwargs.get("sync_each_batch", False)

    @property
    def fused(self) -> bool:
        """Whether accumulation runs fused: one compiled step per optimizer
        step, scanning over a stacked ``[num_steps, micro, ...]`` batch.

        Falls back to the ``ACCELERATE_TPU_FUSED_ACCUM`` env flag: the
        plugin's ``to_kwargs`` keeps only non-default fields, and with the
        env set a default-constructed plugin ALSO has fused=True, so the
        knob would otherwise vanish from ``plugin_kwargs``."""
        if "fused" in self.plugin_kwargs:
            return self.plugin_kwargs["fused"]
        return parse_flag_from_env(ENV_PREFIX + "FUSED_ACCUM")

    @property
    def end_of_dataloader(self) -> bool:
        return (
            self.active_dataloader is not None
            and getattr(self.active_dataloader, "end_of_dataloader", False)
        )

    @property
    def remainder(self) -> int:
        return (
            getattr(self.active_dataloader, "remainder", -1)
            if self.active_dataloader is not None
            else -1
        )

    @property
    def in_dataloader(self) -> bool:
        return self.active_dataloader is not None

    def _add_dataloader(self, dataloader):
        self.dataloader_references.append(dataloader)
        self.active_dataloader = dataloader

    def _remove_dataloader(self, dataloader):
        if dataloader in self.dataloader_references:
            self.dataloader_references.remove(dataloader)
        self.active_dataloader = self.dataloader_references[-1]

    @staticmethod
    def _reset_state():
        GradientState._shared_state.clear()

    def __repr__(self) -> str:
        return (
            f"Sync gradients: {self.sync_gradients}\n"
            f"Accumulation steps: {self.num_steps}\n"
            f"At end of dataloader: {self.end_of_dataloader}\n"
            f"Remainder: {self.remainder}\n"
        )

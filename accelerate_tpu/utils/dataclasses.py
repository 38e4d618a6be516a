"""Config dataclasses, enums and kwargs handlers.

Parity: reference ``src/accelerate/utils/dataclasses.py`` (1919 LoC) — the
whole config/flag surface. The deepest redesign in the codebase lives here:
the reference's per-engine plugins (``DeepSpeedPlugin``:739,
``FullyShardedDataParallelPlugin``:1075, ``MegatronLMPlugin``:1311) collapse
into ONE declarative :class:`ParallelismPlugin`, because on TPU every
parallelism flavor — DDP, ZeRO-1/2/3, FSDP, TP, SP, EP — is the same
mechanism: a sharding annotation over a named device mesh, lowered by GSPMD
to collectives on ICI/DCN. Compatibility shims with the reference plugin
names are provided in :mod:`accelerate_tpu.utils.compat`.

Like the reference, every plugin reads ``ACCELERATE_TPU_*`` env vars in
``__post_init__`` so launcher -> worker config flows through the environment.
"""

from __future__ import annotations

import copy
import enum
import functools
import os
import warnings
from dataclasses import dataclass, field, fields
from datetime import timedelta
from typing import Any, Callable, Iterable, Optional

import jax.numpy as jnp

from .constants import (
    ENV_PREFIX,
    MESH_AXIS_DATA,
    MESH_AXIS_EXPERT,
    MESH_AXIS_FSDP,
    MESH_AXIS_SEQUENCE,
    MESH_AXIS_TENSOR,
)
from .environment import parse_flag_from_env


class KwargsHandler:
    """Base mixin for objects that feed kwargs into Accelerator internals
    (reference utils/dataclasses.py:39)."""

    def to_dict(self) -> dict[str, Any]:
        return copy.deepcopy(self.__dict__)

    def to_kwargs(self) -> dict[str, Any]:
        """Only the values that differ from the dataclass defaults."""
        default = self.__class__()
        return {
            k: v for k, v in self.to_dict().items() if getattr(default, k) != v
        }


class EnumWithContains(enum.EnumMeta):
    def __contains__(cls, item):  # noqa: N805
        try:
            cls(item)
        except ValueError:
            return False
        return True


class BaseEnum(str, enum.Enum, metaclass=EnumWithContains):
    def __str__(self) -> str:
        return self.value

    @classmethod
    def list(cls) -> list[str]:
        return [e.value for e in cls]


class DistributedType(BaseEnum):
    """Process/topology type (reference utils/dataclasses.py:377).

    The CUDA-era zoo (MULTI_GPU/NPU/MLU/XPU, DEEPSPEED, FSDP, MEGATRON_LM)
    collapses: on TPU, multi-device within one process is plain SPMD and the
    only real boundary is single-process vs multi-process (pod slices).
    """

    NO = "NO"  # single device, single process
    TPU = "TPU"  # single process, >=1 TPU devices (SPMD)
    MULTI_TPU = "MULTI_TPU"  # multi-process TPU pod slice
    CPU = "CPU"  # single process CPU (possibly faked multi-device)
    MULTI_CPU = "MULTI_CPU"  # multi-process CPU (tests / debug launcher)


class ComputeEnvironment(BaseEnum):
    """Reference utils/dataclasses.py:425."""

    LOCAL_MACHINE = "LOCAL_MACHINE"
    TPU_POD = "TPU_POD"
    CLOUD_BATCH = "CLOUD_BATCH"


class PrecisionType(BaseEnum):
    """Reference utils/dataclasses.py:510 {no,fp8,fp16,bf16}."""

    NO = "no"
    FP8 = "fp8"
    FP16 = "fp16"
    BF16 = "bf16"


class RNGType(BaseEnum):
    """Reference utils/dataclasses.py:526 — JAX key threading replaces
    torch/cuda/xla generator state."""

    JAX = "jax"
    NUMPY = "numpy"
    PYTHON = "python"
    GENERATOR = "generator"  # alias of JAX key for API familiarity


class LoggerType(BaseEnum):
    """Reference utils/dataclasses.py:488."""

    ALL = "all"
    TENSORBOARD = "tensorboard"
    WANDB = "wandb"
    MLFLOW = "mlflow"
    COMETML = "comet_ml"
    AIM = "aim"
    CLEARML = "clearml"
    DVCLIVE = "dvclive"
    JSONL = "jsonl"  # TPU-native zero-dependency tracker


@dataclass
class MixedPrecisionPolicy(KwargsHandler):
    """What dtype each tensor class uses inside the jitted step.

    TPU-native replacement for AutocastKwargs + GradScalerKwargs + FP8 recipe
    (reference utils/dataclasses.py:84,203,271): instead of an autocast
    context, JAX threads explicit dtypes — params stay fp32 master copies,
    compute runs in ``compute_dtype`` (bf16 on the MXU), gradients/psums in
    ``grad_dtype`` (the analogue of DDP bf16-compression comm hooks).
    """

    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    output_dtype: Any = jnp.float32
    grad_dtype: Any = None  # accumulation-buffer dtype; None -> float32
    # fp8 projections requested (reference FP8RecipeKwargs): matmuls run
    # e4m3-fwd/e5m2-bwd (ops/fp8.py) in models built with
    # ``TransformerConfig(fp8=True)``; non-matmul compute stays bf16.
    fp8: bool = False
    # fp16 only: dynamic loss scaling (GradScaler parity).
    loss_scale_init: float = 2.0**15
    loss_scale_growth_interval: int = 2000
    loss_scale_factor: float = 2.0

    @classmethod
    def from_precision(cls, precision: str | PrecisionType) -> "MixedPrecisionPolicy":
        precision = PrecisionType(precision)
        if precision == PrecisionType.NO:
            return cls()
        if precision == PrecisionType.BF16:
            return cls(compute_dtype=jnp.bfloat16)
        if precision == PrecisionType.FP16:
            return cls(compute_dtype=jnp.float16)
        if precision == PrecisionType.FP8:
            # fp8 matmul inputs, bf16 accumulate/everything-else. The
            # matmul swap itself lives in the model (TransformerConfig.fp8
            # -> ops/fp8.Fp8Dense); custom models use Fp8Dense directly.
            return cls(compute_dtype=jnp.bfloat16, fp8=True)
        raise ValueError(f"unknown precision {precision}")

    @property
    def uses_loss_scaling(self) -> bool:
        return self.compute_dtype == jnp.float16


@dataclass
class DistributedInitKwargs(KwargsHandler):
    """Multi-process bring-up knobs — replaces InitProcessGroupKwargs
    (reference utils/dataclasses.py:234): jax.distributed.initialize instead
    of torch.distributed.init_process_group."""

    coordinator_address: Optional[str] = None
    num_processes: Optional[int] = None
    process_id: Optional[int] = None
    local_device_ids: Optional[list[int]] = None
    initialization_timeout: timedelta = timedelta(minutes=5)


@dataclass
class GradientAccumulationPlugin(KwargsHandler):
    """Reference utils/dataclasses.py:654. On TPU, accumulation happens
    *inside* the compiled step via a carried grad buffer, so `sync_gradients`
    is a traced predicate rather than a Python flag.

    ``fused=True`` (env: ``ACCELERATE_TPU_FUSED_ACCUM``) selects the fused
    execution mode: one compiled step per OPTIMIZER step that takes a
    stacked ``[num_steps, micro_batch, ...]`` batch and runs the microbatch
    loop under ``lax.scan`` — one dispatch per optimizer step instead of
    ``num_steps``, no carried accumulation buffer in HBM between calls."""

    num_steps: int = 1
    adjust_scheduler: bool = True
    sync_with_dataloader: bool = True
    sync_each_batch: bool = False
    fused: bool = False

    def __post_init__(self):
        env = os.environ.get(ENV_PREFIX + "GRADIENT_ACCUMULATION_STEPS")
        if env is not None and self.num_steps == 1:
            self.num_steps = int(env)
        if self.num_steps < 1:
            raise ValueError("num_steps must be >= 1")
        if not self.fused:
            from .environment import parse_flag_from_env

            self.fused = parse_flag_from_env(ENV_PREFIX + "FUSED_ACCUM")
        if self.fused and self.sync_each_batch:
            raise ValueError(
                "fused accumulation folds every microbatch into one optimizer "
                "step; sync_each_batch=True contradicts that — use the "
                "unfused path for per-microbatch sync"
            )


@dataclass
class DataLoaderConfiguration(KwargsHandler):
    """Reference utils/dataclasses.py:556."""

    split_batches: bool = False
    dispatch_batches: Optional[bool] = None
    even_batches: bool = True
    use_seedable_sampler: bool = True
    non_blocking: bool = True
    prefetch_size: int = 2
    drop_last: bool = False


@dataclass
class ProjectConfiguration(KwargsHandler):
    """Reference utils/dataclasses.py:606."""

    project_dir: Optional[str] = None
    logging_dir: Optional[str] = None
    automatic_checkpoint_naming: bool = False
    total_limit: Optional[int] = None
    iteration: int = 0
    save_on_each_node: bool = False

    def set_directories(self, project_dir: Optional[str] = None):
        self.project_dir = project_dir
        if self.logging_dir is None:
            self.logging_dir = project_dir

    def __post_init__(self):
        self.set_directories(self.project_dir)


class ShardingStrategy(BaseEnum):
    """How far parameter/optimizer/grad sharding goes — the union of the
    reference's FSDP sharding strategies (utils/dataclasses.py:1075) and
    DeepSpeed ZeRO stages (:739), expressed as what actually gets sharded."""

    NO_SHARD = "no_shard"  # pure DP (DDP / ZeRO-0)
    SHARD_OPT = "shard_opt"  # optimizer state only (ZeRO-1)
    SHARD_GRAD_OP = "shard_grad_op"  # + gradients (ZeRO-2)
    FULL_SHARD = "full_shard"  # + parameters (ZeRO-3 / FSDP)
    HYBRID_SHARD = "hybrid_shard"  # FULL_SHARD inside a slice, DP across


@dataclass
class ParallelismPlugin(KwargsHandler):
    """THE parallelism config — the TPU-native collapse of DeepSpeedPlugin,
    FullyShardedDataParallelPlugin and MegatronLMPlugin (reference
    utils/dataclasses.py:739,1075,1311).

    Degrees multiply up the mesh: ``dp * fsdp * ep * sp * tp`` must divide
    the device count. ``-1`` for exactly one axis means "absorb all remaining
    devices" (``dp_size`` does by default, and yields when another axis asks). GSPMD turns the per-axis shardings into reduce-scatter /
    all-gather / all-to-all over ICI; nothing here spawns wrappers or
    engines.
    """

    dp_size: int = -1
    fsdp_size: int = 1
    tp_size: int = 1
    sp_size: int = 1  # sequence/context parallel degree (ring attention)
    ep_size: int = 1  # expert parallel degree (MoE)
    pp_size: int = 1  # pipeline stages (shard_map microbatch loop)

    sharding_strategy: ShardingStrategy = ShardingStrategy.FULL_SHARD
    # Minimum parameter size (elements) worth sharding on the fsdp axis;
    # small arrays replicate (reference FSDP min_num_params auto-wrap:1234).
    min_weight_size: int = 2**12
    # Extra logical-axis sharding rules appended to the model's defaults:
    # list of (logical_axis_name, mesh_axis | None).
    sharding_rules: Optional[list[tuple[str, Optional[str]]]] = None
    # Number of microbatches for the pipeline-parallel stage loop
    # (parallel/pipeline.py); must be >= pp_size for full utilization.
    # NOTE deliberately absent (each had no honest mechanism here):
    #  * reduce_dtype — gradients already communicate in the mixed-precision
    #    compute dtype (XLA places the backward all-reduce before any cast we
    #    could add), which IS the bf16 comm-hook behavior; use
    #    MixedPrecisionPolicy.grad_dtype for accumulation-buffer dtype.
    #  * remat_policy — rematerialisation is a model-definition concern
    #    (TransformerConfig.remat); the plugin cannot reach into user models.
    num_micro_batches: int = 1

    def __post_init__(self):
        # Env fills *defaults* only — an explicitly-passed value wins over
        # the launcher's env transport.
        defaults = {f.name: f.default for f in fields(self.__class__)}
        for name in ("dp_size", "fsdp_size", "tp_size", "sp_size", "ep_size",
                     "pp_size", "num_micro_batches"):
            env = os.environ.get(ENV_PREFIX + name.upper())
            if env is not None and getattr(self, name) == defaults[name]:
                setattr(self, name, int(env))
        env = os.environ.get(ENV_PREFIX + "SHARDING_STRATEGY")
        if env is not None and self.sharding_strategy == defaults["sharding_strategy"]:
            self.sharding_strategy = ShardingStrategy(env)
        others = [self.pp_size, self.fsdp_size, self.tp_size, self.sp_size,
                  self.ep_size]
        if self.dp_size == -1 and -1 in others:
            # dp's -1 is only the default "absorb what is left": an axis the
            # caller marks auto takes its place, so the documented
            # ParallelismPlugin(fsdp_size=-1) means ZeRO-3 over every chip
            self.dp_size = 1
        sizes = [self.dp_size, *others]
        if sizes.count(-1) > 1:
            raise ValueError("at most one mesh axis may be -1 (auto)")
        for s in sizes:
            if s == 0 or s < -1:
                raise ValueError(f"invalid mesh degree {s}")

    @property
    def mesh_shape(self) -> dict[str, int]:
        """Axis-name -> degree mapping (auto axes still -1 here; resolved
        against the real device count in parallel/mesh.py)."""
        from .constants import MESH_AXIS_PIPELINE

        return {
            MESH_AXIS_DATA: self.dp_size,
            MESH_AXIS_PIPELINE: self.pp_size,
            MESH_AXIS_FSDP: self.fsdp_size,
            MESH_AXIS_EXPERT: self.ep_size,
            MESH_AXIS_SEQUENCE: self.sp_size,
            MESH_AXIS_TENSOR: self.tp_size,
        }

    @property
    def shards_parameters(self) -> bool:
        return (
            self.sharding_strategy
            in (ShardingStrategy.FULL_SHARD, ShardingStrategy.HYBRID_SHARD)
            and self.fsdp_size != 1
        ) or self.tp_size != 1

    @classmethod
    def pure_dp(cls) -> "ParallelismPlugin":
        return cls(dp_size=-1, fsdp_size=1, sharding_strategy=ShardingStrategy.NO_SHARD)


@dataclass
class CompilePlugin(KwargsHandler):
    """jit/compile knobs — the seat held by TorchDynamoPlugin in the
    reference (utils/dataclasses.py:703). XLA always compiles; this only
    tunes how."""

    donate_state: bool = True  # donate params/opt-state buffers to the step
    # kwargs of the user loss_fn to treat as compile-time constants in the
    # unified step (jax.jit static_argnames)
    static_argnames: tuple[str, ...] = ()
    # XLA backend options, threaded into .lower().compile(...) by warmup
    compiler_options: Optional[dict[str, Any]] = None
    # collective/compute overlap (compilation/overlap.py): None = auto
    # (emit the async-collective + latency-hiding-scheduler options when
    # the backend is TPU and the sharding layout issues per-step
    # collectives), False = never, True = always-on-TPU regardless of
    # sharding. Always a no-op on non-TPU backends. Explicit keys in
    # ``compiler_options`` win over the emitted defaults.
    overlap_collectives: Optional[bool] = None
    # persistent compilation cache directory. None = the one rule in
    # compilation/cache.py (JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.jax_compile_cache); ignored when that variable is set
    cache_dir: Optional[str] = None
    # Persistence floors: JAX persists only compiles >1s / >4KiB, which
    # suits a cache that is always on. 0.0 / -1 persist everything (what a
    # bench sweep of small programs wants). None leaves them untouched.
    cache_min_compile_time_secs: Optional[float] = None
    cache_min_entry_size_bytes: Optional[int] = None
    # cache-key scope: "all" folds the per-backend XLA autotune/kernel
    # caches into the same dir; "none" keeps only the executable cache
    cache_enable_xla_caches: Optional[str] = None
    # diagnostics: log WHY a lookup missed (first differing key field)
    explain_cache_misses: bool = False

    def __post_init__(self):
        if isinstance(self.static_argnames, str):
            self.static_argnames = (self.static_argnames,)
        else:
            self.static_argnames = tuple(self.static_argnames)


@dataclass
class TensorInformation:
    """Reference utils/dataclasses.py:550 — used by object-collectives."""

    shape: tuple[int, ...]
    dtype: Any


def add_model_config_to_megatron_parser(*args, **kwargs):  # pragma: no cover
    raise NotImplementedError(
        "Megatron-LM config parsing does not exist on TPU; use ParallelismPlugin"
    )

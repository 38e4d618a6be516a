"""The compilation subsystem: compile as a first-class, cached,
observable phase.

XLA always compiles; untuned, it compiles *repeatedly* — every process,
every restart, every benchmark run pays the full lowering + backend
compile again. Production JAX trainers (MaxText/T5X-style AOT compile,
JAX's persistent compilation cache) treat compile as a cached, warmed,
measured resource. This package gives the Accelerator the same three
levers:

* :mod:`cache` — the one rule for where JAX's persistent compilation
  cache lives (``JAX_COMPILATION_CACHE_DIR``, else
  ``CompilePlugin.cache_dir``, else ``<checkout>/.jax_compile_cache``),
  so identical programs compile once per *cache*, not once per process;
* :mod:`monitor` — attribute compile cost: per-step-fn compile seconds
  and persistent-cache hit/miss counts, collected from
  ``jax.monitoring`` events and exposed to the telemetry sinks;
* :mod:`warmup` — ahead-of-time lower+compile a built step fn from
  ``ShapeDtypeStruct`` specs (derived from the prepared dataloader's
  fixed padded batch shape), so host data loading and XLA compilation
  overlap instead of serialize;
* :mod:`overlap` — XLA async-collective + latency-hiding-scheduler
  options for the ZeRO/FSDP paths (threaded through
  ``CompilePlugin.compiler_options``, no-op on CPU) and the
  profile-based collective/compute overlap report backing the
  ``overlap_pct`` telemetry field.
"""

from .._lazy import lazy_exports

# name -> submodule, imported on first access: importing this package
# must not import jax (a parent that spawns chip children stays off it)
_EXPORTS = {
    "activate_persistent_cache": ".cache",
    "persistent_cache_dir": ".cache",
    "persistent_cache_entries": ".cache",
    "resolve_cache_dir": ".cache",
    "CompileMonitor": ".monitor",
    "get_compile_monitor": ".monitor",
    "DEFAULT_OVERLAP_OPTIONS": ".overlap",
    "assert_overlap": ".overlap",
    "collective_compute_overlap": ".overlap",
    "merge_compiler_options": ".overlap",
    "overlap_from_spans": ".overlap",
    "overlap_options": ".overlap",
    "top_self_time_ops": ".overlap",
    "batch_spec_of": ".warmup",
    "spec_like": ".warmup",
    "warm_step": ".warmup",
}
__getattr__, __dir__ = lazy_exports(__name__, _EXPORTS)

__all__ = [
    "activate_persistent_cache",
    "persistent_cache_dir",
    "persistent_cache_entries",
    "resolve_cache_dir",
    "CompileMonitor",
    "get_compile_monitor",
    "DEFAULT_OVERLAP_OPTIONS",
    "assert_overlap",
    "collective_compute_overlap",
    "merge_compiler_options",
    "overlap_from_spans",
    "overlap_options",
    "top_self_time_ops",
    "batch_spec_of",
    "spec_like",
    "warm_step",
]

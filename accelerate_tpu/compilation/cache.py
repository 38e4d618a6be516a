"""Persistent XLA compilation cache: one rule for where it lives.

JAX ships a content-addressed on-disk compilation cache (keyed on the
optimized HLO + compile options + backend version); pointing every
process of a run at one directory turns the second-and-later compiles of
an identical program into a fast deserialize. The directory is part of
nothing's key but must not move, or nothing ever hits — so it is resolved
in exactly one place, :func:`resolve_cache_dir`:

1. ``JAX_COMPILATION_CACHE_DIR`` set: JAX itself already uses that
   directory and this package sets no other (an explicit
   ``CompilePlugin.cache_dir`` is ignored with one log line);
2. else an explicit ``CompilePlugin.cache_dir``;
3. else ``<checkout>/.jax_compile_cache`` — a fixed path beside the
   package, never one built from ``tempfile``, a pid or the time.

The names a program gives its operations (``jax.named_scope``, module
names) ARE part of the key here, source lines are not: a trace of a
cached program shows the scopes of the tree that runs it.

:func:`activate_persistent_cache` applies the rule and is what
``AcceleratorState``, ``ServingEngine``, the graft entry and
``tests/conftest.py`` all call. It is idempotent.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Optional

from ..logging import get_logger

logger = get_logger(__name__)

ENV_JAX_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
#: the fixed default: beside the package, i.e. the root of the checkout
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_compile_cache",
)

_lock = threading.Lock()
_active_dir: Optional[str] = None
_warned_ignored = False


def resolve_cache_dir(plugin: Any = None) -> str:
    """Where the persistent cache lives for this process (see module
    docstring for the rule). Pure: touches neither JAX nor the disk."""
    global _warned_ignored
    explicit = getattr(plugin, "cache_dir", None)
    env = os.environ.get(ENV_JAX_CACHE_DIR)
    if env:
        if explicit and not _warned_ignored:
            _warned_ignored = True
            logger.warning(
                "%s=%s is set: CompilePlugin.cache_dir=%s is ignored",
                ENV_JAX_CACHE_DIR, env, explicit,
            )
        return env
    if explicit:
        return os.path.abspath(os.path.expanduser(str(explicit)))
    return DEFAULT_CACHE_DIR


def activate_persistent_cache(plugin: Any = None) -> str:
    """Point JAX's persistent compilation cache at the resolved directory
    and apply the plugin's persistence knobs. Re-activation with the same
    directory is free; switching directories mid-process resets JAX's
    in-memory handle so the new location takes effect.

    Returns the cache directory.
    """
    global _active_dir
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    path = resolve_cache_dir(plugin)
    with _lock:
        os.makedirs(path, exist_ok=True)
        # The previously active dir may have been configured OUTSIDE this
        # module (JAX reading the env var, a direct jax.config call) —
        # JAX's lazily-initialized cache handle stays bound to it, so
        # detect the switch from the config value, not our own state.
        prev = jax.config.jax_compilation_cache_dir
        if prev != path:
            jax.config.update("jax_compilation_cache_dir", path)
            if prev:
                compilation_cache.reset_cache()
        # Persistence floors: JAX's defaults (1 s compile, 4 KiB entry)
        # suit an always-on cache; None leaves them (or whatever the
        # caller configured) untouched.
        if getattr(plugin, "cache_min_compile_time_secs", None) is not None:
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs",
                float(plugin.cache_min_compile_time_secs),
            )
        if getattr(plugin, "cache_min_entry_size_bytes", None) is not None:
            jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes",
                int(plugin.cache_min_entry_size_bytes),
            )
        # Cache-key knobs: fold the per-backend XLA autotune/kernel caches
        # into the same dir, and (diagnostics) log why a lookup missed.
        if getattr(plugin, "cache_enable_xla_caches", None) is not None:
            jax.config.update(
                "jax_persistent_cache_enable_xla_caches",
                str(plugin.cache_enable_xla_caches),
            )
        if getattr(plugin, "explain_cache_misses", None):
            jax.config.update("jax_explain_cache_misses", True)
        # Names are part of the program: JAX's default key leaves an
        # operation's metadata out, so an executable compiled from ANOTHER
        # tree answers the lookup and a profile then shows that tree's
        # scope paths (the per-layer metrics read them). With metadata in
        # the key and no traceback frames in it, the key holds the scope
        # paths and neither source lines, checkout path nor call site.
        jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
        jax.config.update("jax_traceback_in_locations_limit", 0)
        if _active_dir != path:
            logger.info("persistent XLA compilation cache: %s", path)
        _active_dir = path
    return path


def persistent_cache_dir() -> Optional[str]:
    """The directory activated this process (None before activation)."""
    return _active_dir


def persistent_cache_entries(path: Optional[str] = None) -> int:
    """Count cache entries on disk — a cheap proxy for 'did anything
    persist' in smoke tests."""
    path = path or _active_dir
    if not path or not os.path.isdir(path):
        return 0
    n = 0
    for _root, _dirs, files in os.walk(path):
        n += len(files)
    return n

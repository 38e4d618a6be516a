"""PEP 562 lazy re-exports for package ``__init__`` files.

Importing ``accelerate_tpu`` (or ``.utils`` / ``.models`` / ``.profiling``)
must not import jax: a process that only SPAWNS chip children —
``accelerate-tpu launch`` — has to stay off JAX entirely, and Python runs
every parent package's ``__init__`` on the way to
``accelerate_tpu.commands.launch``. So those packages declare their
re-exports as a ``name -> submodule`` table and resolve them on first
access.
"""

from __future__ import annotations

import importlib
import sys


def lazy_exports(package: str, exports: dict[str, str]):
    """``(__getattr__, __dir__)`` for ``package``: ``exports`` maps each
    public name to the relative submodule that defines it. Unlisted names
    fall back to a submodule of that name, so ``pkg.sub`` keeps working
    after a bare ``import pkg``."""

    def __getattr__(name: str):
        module = exports.get(name)
        if module is not None:
            value = getattr(importlib.import_module(module, package), name)
        else:
            try:
                value = importlib.import_module("." + name, package)
            except ModuleNotFoundError as exc:
                if exc.name != f"{package}.{name}":
                    raise  # a real missing dependency inside the submodule
                raise AttributeError(
                    f"module {package!r} has no attribute {name!r}"
                ) from None
        setattr(sys.modules[package], name, value)
        return value

    def __dir__():
        return sorted(set(vars(sys.modules[package])) | set(exports))

    return __getattr__, __dir__

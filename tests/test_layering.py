"""The package's arrows point down.

``ORDER`` is the one statement of which part of ``accelerate_tpu`` may know
which: a unit (a top-level package or module) imports from its own level and
from the levels before it. ``KNOWN_UPWARD`` lists the imports that break that
today, each with the ROADMAP debt that names where the thing should live.
The test fails on an upward import that is not listed AND on a listed one that
is gone, so the list only shrinks. It reads source with ``ast`` (imports
inside functions too); only ``test_lazy_exports_resolve`` imports the package.
"""

import ast
import importlib
import os

import pytest

PACKAGE = "accelerate_tpu"
ROOT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    PACKAGE)

# lowest first; units of one level may import each other
ORDER = (
    ("_lazy", "utils", "logging", "state", "parallel"),
    ("compilation",),
    ("ops",),
    ("models",),
    ("optimizer", "scheduler", "data_loader", "checkpointing",
     "dist_checkpoint", "checkpoint_async"),
    ("profiling", "telemetry"),
    ("diagnostics", "tracking"),
    ("accelerator", "serving", "adapters", "big_modeling"),
    ("router", "loadgen", "fault_tolerance", "local_sgd", "launchers"),
    ("commands", "test_utils"),
)
LEVEL = {unit: i for i, level in enumerate(ORDER) for unit in level}

# (file under accelerate_tpu/, unit it reaches up to): the debt that names it
KNOWN_UPWARD = {
    ("parallel/sharding.py", "profiling"):
        "ROADMAP Design 11a: the sharding contract's audit belongs to its caller",
    ("state.py", "compilation"):
        "ROADMAP Design 11b: the Accelerator activates the compile cache, not the state",
    ("ops/fused.py", "models"):
        "ROADMAP Design 11c (goes with Design 5): rope belongs in ops",
    ("models/transformer.py", "adapters"):
        "ROADMAP Design 11d: lora_delta is an op the model calls",
    ("utils/hf_interop.py", "models"):
        "ROADMAP Design 11e: HF interop is a layer above models, adapters, checkpointing",
    ("utils/hf_interop.py", "adapters"): "ROADMAP Design 11e",
    ("utils/hf_interop.py", "checkpointing"): "ROADMAP Design 11e",
    ("utils/quantization.py", "big_modeling"):
        "ROADMAP Design 11f: the streamed loader calls quantization, not the reverse",
    ("utils/quantization.py", "checkpointing"):
        "ROADMAP Design 11g: naming a pytree's leaves (_path_str) belongs in utils",
    ("telemetry/config.py", "diagnostics"):
        "ROADMAP Design 11h (goes with Design 8): telemetry and diagnostics know each other",
    ("telemetry/collector.py", "diagnostics"): "ROADMAP Design 11h",
    ("diagnostics/diagnose.py", "loadgen"):
        "ROADMAP Design 11i: the soak report's reader belongs with diagnose",
    ("loadgen/chaos.py", "test_utils"):
        "ROADMAP Design 11j: fault_injection is product code living under test_utils",
    ("loadgen/harness.py", "test_utils"): "ROADMAP Design 11j",
}


def _units():
    found = set()
    for name in os.listdir(ROOT):
        if name.endswith(".py") and name != "__init__.py":
            found.add(name[:-3])
        elif os.path.isfile(os.path.join(ROOT, name, "__init__.py")):
            found.add(name)
    return sorted(found)


def _root_exports():
    """``from accelerate_tpu import Accelerator`` reaches ``accelerator``:
    the root's lazy table, read without importing it."""
    with open(os.path.join(ROOT, "__init__.py")) as f:
        tree = ast.parse(f.read())
    for node in tree.body:
        if isinstance(node, ast.Assign) and node.targets[0].id == "_EXPORTS":
            return {k: v.lstrip(".").split(".")[0]
                    for k, v in ast.literal_eval(node.value).items()}
    raise AssertionError("accelerate_tpu/__init__.py has no _EXPORTS table")


def _files_of(unit):
    single = os.path.join(ROOT, unit + ".py")
    if os.path.isfile(single):
        return [single]
    return sorted(os.path.join(d, f) for d, _, files in
                  os.walk(os.path.join(ROOT, unit)) for f in files
                  if f.endswith(".py"))


def _reached(path, exports):
    """Units of the package that the file at ``path`` imports, with lines."""
    rel = os.path.relpath(path, ROOT)
    package = [PACKAGE] + rel.split(os.sep)[:-1]
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            modules = [(a.name.split("."), ()) for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] if node.level else []
            parts = base + (node.module.split(".") if node.module else [])
            modules = [(parts, [a.name for a in node.names])]
        else:
            continue
        for parts, names in modules:
            if parts[:1] != [PACKAGE]:
                continue
            if len(parts) > 1:
                yield parts[1], node.lineno
            else:  # from accelerate_tpu import X / from .. import X
                for name in names:
                    yield exports.get(name, name), node.lineno


def test_the_order_names_every_unit_once():
    listed = [unit for level in ORDER for unit in level]
    assert sorted(listed) == _units()
    assert {target for _, target in KNOWN_UPWARD} <= set(listed)


@pytest.mark.parametrize("unit", _units())
def test_imports_point_down(unit):
    exports = _root_exports()
    upward = {}
    for path in _files_of(unit):
        rel = os.path.relpath(path, ROOT).replace(os.sep, "/")
        for target, line in _reached(path, exports):
            assert target in LEVEL, f"{rel}:{line} imports unknown {target!r}"
            if LEVEL[target] > LEVEL[unit]:
                upward.setdefault((rel, target), []).append(line)
    known = {key for key in KNOWN_UPWARD
             if key[0] == unit + ".py" or key[0].startswith(unit + "/")}
    new = {f"{rel}:{lines[0]} -> {target}"
           for (rel, target), lines in upward.items()
           if (rel, target) not in known}
    assert not new, (
        f"{unit} (level {LEVEL[unit]}) imports from a level above it: "
        f"{sorted(new)}; move the thing down, do not list the import")
    gone = known - set(upward)
    assert not gone, (
        f"no longer upward, delete from KNOWN_UPWARD (and from ROADMAP "
        f"Design 11): {sorted(gone)}")


def _lazy_tables():
    tables = []
    for d, _, files in os.walk(ROOT):
        if "__init__.py" in files:
            with open(os.path.join(d, "__init__.py")) as f:
                if "\n_EXPORTS = {" in f.read():
                    tables.append(os.path.relpath(d, os.path.dirname(ROOT))
                                  .replace(os.sep, "."))
    return sorted(tables)


@pytest.mark.parametrize("package", _lazy_tables())
def test_lazy_exports_resolve(package):
    """Every name a lazy ``_EXPORTS`` table promises is an object of the
    submodule it names (a deleted or renamed module breaks exactly this,
    and only at first use)."""
    module = importlib.import_module(package)
    assert module._EXPORTS
    missing = []
    for name, sub in sorted(module._EXPORTS.items()):
        try:
            getattr(module, name)
        except (ImportError, AttributeError) as exc:
            missing.append(f"{name} ({sub}): {exc}")
    assert not missing, missing
    assert set(getattr(module, "__all__", ())) <= set(dir(module))

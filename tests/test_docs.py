"""Docs-check: the public API surface stays documented.

Imports the package's public modules and fails on any exported name
(``__all__``) whose class/function docstring is empty — the CI
``docs-check`` step runs exactly this file, so a PR that adds an
undocumented export fails before review.  Constants (tuples, frozen
preset instances) are exempt: they carry their type's docstring.
"""

import importlib
import inspect

import pytest

#: Modules whose ``__all__`` is the public API surface (README/docs
#: entry points: the planning subsystem, the Fabric API, serving, and
#: the training-side sync).
PUBLIC_MODULES = (
    "repro.planning",
    "repro.fabric",
    "repro.serving",
    "repro.core.sync",
)

#: Modules that must carry a module-level docstring (the docs/ tree
#: links into these as subsystem entry points).
DOCUMENTED_MODULES = PUBLIC_MODULES + (
    "repro",
    "repro.launch.compile_cache",
    "repro.planning.serve",
    "repro.planning.tuner",
    "repro.fabric.measured",
    "repro.serving.engine",
    "repro.serving.sharded",
    "repro.core.sync",
    "repro.core.bucketing",
)


@pytest.mark.parametrize("modname", PUBLIC_MODULES)
def test_every_export_has_a_docstring(modname):
    mod = importlib.import_module(modname)
    assert hasattr(mod, "__all__"), f"{modname} must declare __all__"
    missing = []
    for name in mod.__all__:
        obj = getattr(mod, name)
        if not (inspect.isclass(obj) or inspect.isroutine(obj)):
            continue  # constants/preset instances document via their type
        doc = inspect.getdoc(obj)
        if not doc or not doc.strip():
            missing.append(name)
    assert not missing, (
        f"{modname} exports without docstrings: {missing} — every public "
        f"name needs a one-line summary (see docs/architecture.md)"
    )


@pytest.mark.parametrize("modname", DOCUMENTED_MODULES)
def test_module_docstring(modname):
    mod = importlib.import_module(modname)
    assert mod.__doc__ and mod.__doc__.strip(), f"{modname} needs a module docstring"

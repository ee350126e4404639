"""Where JAX keeps its persistent compilation cache.

A compiled step of a full-width model takes tens of seconds to build;
the persistent cache lets the next process on the same machine load it
instead.  The directory must not move between runs: it is either the
one the environment names or one fixed path inside this checkout —
never a temporary or per-process name.

A Pallas TPU kernel carries its own MLIR, source locations included,
inside the custom call that the cache key covers.  With full Python
tracebacks in those locations the key names every frame that led to the
compile, so the same step compiled from two call sites (say an
ahead-of-time memory check, then the launcher's own jit) never hits.
The helper therefore keeps only the innermost user frame in locations
(a traceback limit of one frame).  It leaves tracebacks in locations
on: with them off, XLA drops the ``jax.named_scope`` path from each
instruction's ``op_name``, and the device-trace readers join on it.
For the same reason the key covers that metadata: by default JAX strips
it from the key, and a step that differs from a cached one only in its
scopes would load with the cached step's names.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: The in-checkout default (listed in ``.gitignore``).
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.  Call before the first compile.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it: no
    directory is set in code.  Otherwise the cache goes to
    ``DEFAULT_DIR``.  Either way kernel locations stop naming the caller.
    """
    jax.config.update("jax_traceback_in_locations_limit", 1)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)

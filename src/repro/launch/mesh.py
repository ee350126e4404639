"""Production meshes.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — required because the dry-run must
set XLA_FLAGS before any jax initialization.
"""

from __future__ import annotations

import os

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    """A mesh over the first ``prod(shape)`` devices, every axis Auto-typed
    (``jax.make_mesh`` defaults to Explicit axes)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def ensure_virtual_devices(n: int = 8) -> None:
    """Force ``n`` virtual CPU devices if no device count is set yet.

    Appends ``--xla_force_host_platform_device_count=n`` to ``XLA_FLAGS``
    unless one is already present.  Must run before jax initializes its
    backend (importing jax is fine — the flag is read on first device
    use).  The flag only shapes the CPU backend: it never stands in for
    accelerator devices.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}"
    ).strip()

"""The arena wire kernels compile for a TPU v5e chip.

Interpret mode cannot see what the chip's compiler refuses (tile
alignment, VMEM limits), so these tests compile ``pack_arena_pallas`` and
``unpack_arena_pallas`` with the installed TPU compiler for a described
``v5e:2x2`` topology — no chip attached — at tinyllama-1.1b's real group
sizes (the largest and smallest group of the plan ``chip_smoke.py``
trains under) and at one ragged group, and check that the compiled
module holds the Mosaic kernel (``tpu_custom_call``).

The topology is described only inside a fixture: one process at a time
may load the TPU library, so describing it while a module is imported
would break parallel test workers.  All such tests live in this file.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.bucketing import tree_get
from repro.kernels.comm_pack import pack_arena_pallas, unpack_arena_pallas
from repro.launch import train
from repro.launch.specs import param_specs

#: The one-chip training configuration of chip_smoke.py.
SMOKE_ARGV = ["--arch", "tinyllama-1.1b", "--policy", "mg_wfbp", "--fuse", "arena",
              "--issue-order", "dag", "--optimizer", "adamw", "--batch", "1",
              "--seq", "2048"]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def groups():
    """(size, gradient dtype) of every part of the smoke plan's largest and
    smallest group, and of a ragged group (odd tails, mid-tile offsets)."""
    ts = train.setup(train.parse_args(SMOKE_ARGV))
    shapes = param_specs(ts.cfg)
    arenas = sorted(ts.engine().plan.group_arenas(shapes, jnp.float32), key=lambda a: a.size)

    def parts(arena):
        return [(s.size, tree_get(shapes, s.path).dtype) for s in arena.slots]

    ragged = [(45056, jnp.float32), (129, jnp.float32), (7, jnp.float32)]
    return {"largest": parts(arenas[-1]), "smallest": parts(arenas[0]), "ragged": ragged}


@pytest.mark.parametrize(
    "group,wire,ef",
    [
        ("largest", jnp.float32, False),
        ("largest", jnp.bfloat16, False),
        ("smallest", jnp.float32, False),
        ("smallest", jnp.bfloat16, False),
        ("ragged", jnp.bfloat16, False),
        ("ragged", jnp.bfloat16, True),
    ],
)
def test_arena_kernels_compile_for_v5e(one_chip, groups, group, wire, ef):
    sizes = [n for n, _ in groups[group]]
    dtypes = [dt for _, dt in groups[group]]
    offsets = [sum(sizes[:i]) for i in range(len(sizes))]
    total = sum(sizes)

    def spec(n, dt):
        return jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)

    parts = [spec(n, dt) for n, dt in groups[group]]
    if ef:
        res = [spec(n, jnp.float32) for n in sizes]
        pack = jax.jit(lambda p, r: pack_arena_pallas(p, offsets, total, wire, r))
        compiled = pack.lower(parts, res).compile()
    else:
        pack = jax.jit(lambda p: pack_arena_pallas(p, offsets, total, wire)[0])
        compiled = pack.lower(parts).compile()
    assert "tpu_custom_call" in compiled.as_text()

    unpack = jax.jit(lambda a, s: unpack_arena_pallas(
        a, list(zip(offsets, sizes)), dtypes, s))
    compiled = unpack.lower(spec(total, wire), spec(1, jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_kernel_payload_independent_of_call_site(one_chip, tmp_path, monkeypatch):
    """Under the compile-cache helper's settings, the same kernel lowered
    from two call sites carries the same Mosaic payload, so the
    persistent cache can hit across them."""
    from repro.launch.compile_cache import enable_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))  # no dir set in code
    parts = [jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip) for n in (8192, 4096)]

    def lower():
        return jax.jit(lambda p: pack_arena_pallas(p, [0, 8192], 12288, jnp.bfloat16)[0]
                       ).lower(parts).as_text()

    was = jax.config.jax_include_full_tracebacks_in_locations
    try:
        enable_compile_cache()
        first = lower()
        assert (lambda: lower())() == first
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", was)

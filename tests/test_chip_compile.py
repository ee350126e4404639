"""The arena wire kernels compile for a TPU v5e chip.

Interpret mode cannot see what the chip's compiler refuses (tile
alignment, VMEM limits), so these tests compile ``pack_arena_pallas`` and
``unpack_arena_pallas`` with the installed TPU compiler for a described
``v5e:2x2`` topology — no chip attached — at tinyllama-1.1b's real group
sizes (the largest and smallest group of the plan ``chip_smoke.py``
trains under) and at one ragged group, and check that the compiled
module holds the Mosaic kernel (``tpu_custom_call``).  The flash
attention kernels compile, forward and backward, at the starcoder2-3b
benchmark cell's attention shape, and the sdar-30b-a3b cell's step with
its grouped-matmul kernels.  A data-parallel
train step compiled for the four chips keeps one all-reduce per schedule
group: the TPU's all-reduce combiner would merge them all into one.

The topology is described only inside a fixture: one process at a time
may load the TPU library, so describing it while a module is imported
would break parallel test workers.  All such tests live in this file.
"""

import os
import pathlib
import re
import sys

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro import scopes
from repro.core import profiler
from repro.core.bucketing import tree_get
from repro.kernels.comm_pack import pack_arena_pallas, unpack_arena_pallas
from repro.kernels.flash_attention import flash_attention_train
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
def four_chips(one_chip):
    """A ``data`` mesh over the described v5e:2x2 topology's four chips."""
    import numpy as np
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    return jax.sharding.Mesh(np.array(topo.devices).reshape(4), ("data",))


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

    was = jax.config.jax_traceback_in_locations_limit
    was_key = jax.config.jax_compilation_cache_include_metadata_in_key
    try:
        enable_compile_cache()
        first = lower()
        assert (lambda: lower())() == first
    finally:
        jax.config.update("jax_traceback_in_locations_limit", was)
        jax.config.update("jax_compilation_cache_include_metadata_in_key", was_key)


def test_dp_step_keeps_one_allreduce_per_group_on_four_chips(four_chips, monkeypatch):
    """The DAG step of a reduced tinyllama over four v5e chips compiles to
    one all-reduce per schedule group plus the loss's; with the combiner
    left on, XLA merges them into fewer, after the last gradient."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_reduced
    from repro.core import trainer
    from repro.core.comm_model import AllReduceModel
    from repro.core.sync import SyncConfig
    from repro.models.transformer import init_params
    from repro.optim import make_optimizer

    mesh = four_chips
    cfg = get_reduced("tinyllama-1.1b")
    eng = trainer.MGWFBPEngine.build(
        cfg, param_specs(cfg), dp_axes=("data",), ar_model=AllReduceModel(a=5e-5, b=1e-9),
        tokens_per_device=1024, method="wfbp", sync_config=SyncConfig(fuse="arena"))
    opt = make_optimizer("sgd", momentum=0.9)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    rep = NamedSharding(mesh, P())

    def described(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep), tree)

    tok = jax.ShapeDtypeStruct((8, 64), jnp.int32, sharding=NamedSharding(mesh, P("data", None)))
    args = (described(params), described(jax.eval_shape(opt.init, params)),
            {"tokens": tok, "targets": tok})

    def n_allreduce():
        step = eng.make_train_step(opt, mesh, lr=1e-2, issue="dag")
        with jax.set_mesh(mesh):
            text = step.lower(*args).compile().as_text()
        return len(re.findall(r" all-reduce(-start)?\(", text))

    n_groups = len(eng.schedule.groups)
    assert n_allreduce() == n_groups + 1
    monkeypatch.setattr(trainer, "KEEP_GROUPS_APART", None)
    assert n_allreduce() < n_groups


def test_flash_kernels_compile_for_v5e_at_the_cell_shape(one_chip):
    """Forward and backward of ``flash_attention_train`` in bf16 at the
    sc2-3b.train.dp1 cell's attention (1 x 4096 tokens, 24 query heads over
    2 KV heads of 128): Mosaic takes the bf16 tiles and their VMEM, the
    module holds the three named kernels, and none of them looks like a
    comm_pack kernel to the benchmark's trace reader."""
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from bench.trace import Op, is_comm_pack

    q = jax.ShapeDtypeStruct((1, 4096, 24, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4096, 2, 128), jnp.bfloat16, sharding=one_chip)

    def fwd_bwd(q, k, v):
        def attend(*a):
            with jax.named_scope(scopes.ATTENTION):  # as models/layers names it
                return flash_attention_train(*a, True, None, None)

        o, pullback = jax.vjp(attend, q, k, v)
        return o, pullback(o)

    text = jax.jit(fwd_bwd).lower(q, kv, kv).compile().as_text()
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    paths = [re.search(r'op_name="([^"]*)"', line).group(1) for line in calls]
    assert sorted(next(c for c in p.split("/") if c in scopes.FLASH_KERNELS) for p in paths) == [
        "flash_dkv", "flash_dq", "flash_fwd"]
    assert all(profiler.is_attention(p) and profiler.is_flash(p) for p in paths)
    assert not any(is_comm_pack(Op(0, 1, line.strip())) for line in calls)


def test_sdar_cell_step_compiles_with_named_moe_kernels(one_chip, monkeypatch):
    """The sdar-30b-a3b.train.dp1 cell's train step, built as the
    benchmark builds it (2 x 8192 tokens, 16 of 128 experts at the
    published widths) with its depth cut to one layer, compiles for a v5e
    with the grouped-matmul kernels: ``moe_gmm`` three times forward, three
    times in the recompute and three times against the weights transposed,
    ``moe_tgmm`` three times,
    each under the ``moe`` scope, and the row kernels that move the routed
    rows in dispatch, combine and their gradients; none of them looks like
    a comm_pack kernel to the benchmark's trace reader.  The described chip is not
    the process's backend, so the kernels' dispatch is told it runs on a
    TPU."""
    import dataclasses
    import functools

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import repro.kernels.grouped_matmul.ops as gmm_ops
    import repro.models.layers as layers

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from bench import harness
    from bench.trace import Op, is_comm_pack

    monkeypatch.setattr(layers, "_on_tpu", lambda: True)
    monkeypatch.setattr(gmm_ops, "_on_tpu", lambda: True)
    cell = harness.load_cell("sdar-30b-a3b.train.dp1")
    cfg = {**cell.config, "num_hidden_layers": 1}
    seq, rows = int(cell.traffic["seq"]), cell.rows
    ts = train.setup(train.parse_args([
        "--arch", cfg["arch"], "--batch", str(rows), "--seq", str(seq), "--lr", "0.1",
        "--replan-every", "0", *cell.spec["launcher"]]))
    mesh = Mesh(np.array([one_chip.device_set.pop()]).reshape(1, 1), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    ts = dataclasses.replace(ts, cfg=cell.family.program_config(cfg, ts.cfg), mesh=mesh)
    step = ts.train_step(ts.engine())
    rep = NamedSharding(mesh, P())
    specs = jax.eval_shape(functools.partial(cell.family.init, cfg), jax.random.PRNGKey(0))
    on = lambda t: jax.tree.map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep), t)
    tok = jax.ShapeDtypeStruct((rows, seq), jnp.int32, sharding=NamedSharding(mesh, P("data")))
    with jax.set_mesh(mesh):
        text = step.lower(on(specs), on(jax.eval_shape(ts.opt.init, specs)),
                          {"tokens": tok, "targets": tok}).compile().as_text()
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    paths = [re.search(r'op_name="([^"]*)"', line).group(1) for line in calls]
    moe = [p for p in paths if profiler.is_moe_gmm(p)]
    kernels = sorted(next(c for c in p.split("/") if c in scopes.MOE_KERNELS) for p in moe)
    assert kernels == ["moe_gmm"] * 9 + ["moe_tgmm"] * 3
    assert all(profiler.is_moe(p) for p in moe)
    moe_calls = [line for line, p in zip(calls, paths) if profiler.is_moe_gmm(p)]
    assert not any(is_comm_pack(Op(0, 1, line.strip())) for line in moe_calls)
    # the row kernels: dispatch forward and in the recompute (a pack and a
    # gather each), combine forward (a pack and a sum; the recompute does
    # not need it), and their gradients (a pack and a gather for the
    # combine's, a pack and a sum for the dispatch's)
    rows = [p for p in paths if profiler.is_moe_rows(p)]
    names = sorted(next(c for c in p.split("/") if c in scopes.MOE_ROW_KERNELS) for p in rows)
    assert names == ["moe_rows_gather"] * 3 + ["moe_rows_pack"] * 5 + ["moe_rows_sum"] * 2
    assert all(profiler.is_moe(p) and not profiler.is_moe_gmm(p) for p in rows)
    row_calls = [line for line, p in zip(calls, paths) if profiler.is_moe_rows(p)]
    assert not any(is_comm_pack(Op(0, 1, line.strip())) for line in row_calls)

"""Arena wire path + measured-comm fitting.

Covers the PR-2 tentpole end to end:

  * pack→unpack numeric round-trip (oracle vs Pallas-interpret), incl.
    scan-stacked slices, odd-sized tails, and the multi-chunk DMA
    pipeline in both wire dtypes;
  * the plan-time ``group_arenas`` layout: exact packing (zero padding),
    offsets/sizes, scan-slice shapes;
  * lowered-HLO invariants of the arena wire: exact all-reduce op AND
    byte counts (``profiler.parse_collectives`` on stablehlo), zero
    concatenate ops, bf16 halving wire bytes;
  * seeded ``MeasuredComm`` α–β fit recovery;
  * plan-aware checkpointing: the plan JSON rides beside the weights.
"""

import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from _env import REPO_ROOT, SUBPROC_ENV

import jax
import jax.numpy as jnp

from repro.core import (
    AllReduceModel,
    fit_affine,
    group_arenas,
    parse_collectives,
    stacked_lm_layout,
)
from repro.kernels.comm_pack import pack_arena, unpack_arena
from repro.planning import MeasuredComm, build_schedule


def _parts(seed=0, shapes=((3, 5), (7,), (2, 2, 3), (1,), (11,))):
    rng = np.random.default_rng(seed)
    parts = [jnp.asarray(rng.standard_normal(s), jnp.float32) for s in shapes]
    sizes = [int(np.prod(s)) for s in shapes]
    offsets = np.concatenate([[0], np.cumsum(sizes)[:-1]]).tolist()
    return parts, offsets, sizes, sum(sizes)


class TestPackUnpack:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_round_trip_ref_vs_pallas(self, dtype):
        parts, offsets, sizes, total = self._setup()
        a_ref = pack_arena(parts, offsets, total, dtype, use_pallas=False)
        a_pal = pack_arena(parts, offsets, total, dtype, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(a_ref, np.float32), np.asarray(a_pal, np.float32)
        )
        slots = list(zip(offsets, sizes))
        shapes = [p.shape for p in parts]
        dts = [p.dtype for p in parts]
        out_r = unpack_arena(a_ref, slots, shapes, dts, scale=0.25, use_pallas=False)
        out_p = unpack_arena(a_pal, slots, shapes, dts, scale=0.25, interpret=True)
        for r, p, orig in zip(out_r, out_p, parts):
            assert r.shape == orig.shape and r.dtype == orig.dtype
            np.testing.assert_array_equal(np.asarray(r), np.asarray(p))
        if dtype == jnp.float32:  # lossless: unpack(pack(x)) * 4 == x
            for r, orig in zip(out_r, parts):
                np.testing.assert_allclose(
                    np.asarray(r) * 4.0, np.asarray(orig), rtol=1e-6
                )

    @staticmethod
    def _setup():
        # 3072 and 2048 start on HBM tiles (the kernel moves them); the
        # odd 7, 1, 11 behind them go through the oracle's encode/decode
        return _parts(shapes=((3, 1024), (2, 2, 512), (7,), (1,), (11,)))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_multi_chunk_pipeline_matches_oracle(self, dtype):
        """Shrinking ``chunk`` below the part sizes forces the
        double-buffered DMA pipeline (warm-up + cross-chunk slot reuse, a
        last chunk pulled back onto its predecessor, ragged tails, parts
        at mid-tile offsets) — results must stay bit-identical to the
        oracle, on the f32 wire the cells run and on the bf16 one."""
        parts, offsets, sizes, total = _parts(
            seed=3, shapes=((5, 1024), (2500,), (37,), (3, 1024), (1,), (1001,))
        )
        a_ref = pack_arena(parts, offsets, total, dtype, use_pallas=False)
        # chunk=2048: the 5120-part takes chunks at 0, 2048 and 3072; the
        # 2500-part one chunk and a 452 tail; the rest start mid-tile
        a_pal = pack_arena(parts, offsets, total, dtype, interpret=True, chunk=2048)
        assert a_ref.dtype == a_pal.dtype == dtype
        np.testing.assert_array_equal(
            np.asarray(a_ref, np.float32), np.asarray(a_pal, np.float32)
        )
        slots = list(zip(offsets, sizes))
        shapes = [p.shape for p in parts]
        dts = [p.dtype for p in parts]
        o_ref = unpack_arena(a_ref, slots, shapes, dts, scale=0.5, use_pallas=False)
        o_pal = unpack_arena(a_pal, slots, shapes, dts, scale=0.5,
                             interpret=True, chunk=2048)
        for r, p in zip(o_ref, o_pal):
            np.testing.assert_array_equal(np.asarray(r), np.asarray(p))


def _toy_layout(n_stages=4):
    shapes = {
        "embed": {"tok": jnp.zeros((32, 16))},
        "stages": {
            "w1": jnp.zeros((n_stages, 16, 16)),
            "w2": jnp.zeros((n_stages, 16)),
        },
        "final_norm": {"scale": jnp.zeros((16,))},
        "head": {"w": jnp.zeros((16, 33))},  # odd tail
    }
    return shapes, stacked_lm_layout(shapes, n_stages)


class TestGroupArenas:
    def test_exact_packing_and_scan_slices(self):
        shapes, layout = _toy_layout()
        costs = layout.layer_costs(1024, None)
        # merge everything -> one arena with leaf + multi-stage slice slots
        sched = build_schedule("single", costs, AllReduceModel(a=1e-3, b=1e-9))
        (arena,) = group_arenas(layout, sched, shapes, jnp.bfloat16)
        assert arena.comm_dtype == "bfloat16"
        # exact packing: no padding, contiguous offsets
        off = 0
        for slot in arena.slots:
            assert slot.offset == off
            assert slot.size == int(np.prod(slot.shape))
            off += slot.size
        assert arena.size == off
        assert arena.nbytes == arena.size * 2
        total_params = 32 * 16 + 4 * (16 * 16 + 16) + 16 + 16 * 33
        assert arena.size == total_params
        # the scan slice spans all four stages with the sliced leading axis
        slices = [s for s in arena.slots if s.kind == "slice"]
        assert {s.stack_range for s in slices} == {(0, 4)}
        assert {s.shape[0] for s in slices} == {4}

    def test_plan_exposes_arena_layout(self):
        from repro.planning import build_plan

        shapes, layout = _toy_layout()
        costs = layout.layer_costs(1024, None)
        plan = build_plan(
            layout, costs, AllReduceModel(a=1e-3, b=1e-9), n_scan_stages=4
        )
        via_plan = plan.group_arenas(shapes, jnp.bfloat16)
        direct = group_arenas(layout, plan.schedule, shapes, jnp.bfloat16)
        assert via_plan == direct
        assert len(via_plan) == len(plan.schedule.groups)

    def test_shapeless_leaves_rejected(self):
        shapes, layout = _toy_layout()
        costs = layout.layer_costs(1024, None)
        sched = build_schedule("single", costs, AllReduceModel(a=1e-3, b=1e-9))
        bad = jax.tree.map(lambda x: tuple(x.shape), shapes)  # tuples, not arrays
        with pytest.raises(TypeError, match="has no .shape"):
            group_arenas(layout, sched, bad)

    def test_per_group_arenas_cover_per_tensor_schedule(self):
        shapes, layout = _toy_layout()
        costs = layout.layer_costs(1024, None)
        sched = build_schedule("per_tensor", costs, AllReduceModel(a=1e-9, b=1e-12))
        arenas = layout.group_arenas(sched, shapes)  # ParamLayout method
        assert len(arenas) == len(sched.groups)
        assert sum(a.size for a in arenas) == 32 * 16 + 4 * (16 * 16 + 16) + 16 + 16 * 33
        # stage groups are singleton slices [i, i+1)
        stage_arenas = [a for a in arenas if a.slots[0].kind == "slice"]
        assert len(stage_arenas) == 4
        for a in stage_arenas:
            assert all(s.stack_range[1] - s.stack_range[0] == 1 for s in a.slots)


ARENA_LOWERING_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.core import (
        AllReduceModel, SyncConfig, make_gradient_sync, parse_collectives,
        stacked_lm_layout,
    )
    from repro.planning import build_schedule

    n_stages = 4
    shapes = {
        "embed": {"tok": jnp.zeros((32, 16))},
        "stages": {"w1": jnp.zeros((n_stages, 16, 16)), "w2": jnp.zeros((n_stages, 16))},
        "final_norm": {"scale": jnp.zeros((16,))},
        "head": {"w": jnp.zeros((16, 33))},
    }
    layout = stacked_lm_layout(shapes, n_stages)
    costs = layout.layer_costs(1024, None)
    mesh = make_mesh((8,), ("data",))
    key = jax.random.PRNGKey(0)
    grads = jax.tree.map(
        lambda s: jax.random.normal(jax.random.fold_in(key, s.size), s.shape), shapes
    )
    n_elems = sum(x.size for x in jax.tree.leaves(grads))

    out = []
    for policy in ("per_tensor", "single", "bucketed"):
        sched = build_schedule(policy, costs, AllReduceModel(a=1e-3, b=1e-9))
        rec = {"policy": policy, "n_groups": len(sched.groups)}
        for comp, dtype in (("f32", jnp.float32), ("bf16", jnp.bfloat16)):
            sync = make_gradient_sync(layout, sched, ("data",), SyncConfig(comm_dtype=dtype))

            def body(g):
                r = jax.lax.axis_index("data").astype(jnp.float32)
                return sync(jax.tree.map(lambda x: x * (r + 1.0), g))

            f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                                  axis_names={"data"}, check_vma=False))
            stats = parse_collectives(f.lower(grads).as_text())
            got = f(grads)
            expect = jax.tree.map(lambda x: 4.5 * x, grads)
            diff = max(jax.tree.leaves(jax.tree.map(
                lambda a, b: float(jnp.max(jnp.abs(a - b))), got, expect)))
            rec[comp] = {
                "allreduce_ops": stats.counts.get("all-reduce", 0),
                "wire_bytes": stats.bytes_by_kind.get("all-reduce", 0),
                "concat_ops": stats.concat_ops,
                "max_diff": diff,
            }
        rec["n_elems"] = int(n_elems)
        out.append(rec)
    print(json.dumps(out))
""")


def test_arena_lowering_op_and_byte_counts():
    """Acceptance: the arena wire lowers to exactly one all-reduce HLO op
    per schedule group with ZERO concatenate ops, at exactly the group's
    gradient elements in wire bytes (half of them under bf16) — per
    policy, via ``profiler.parse_collectives``."""
    out = subprocess.run(
        [sys.executable, "-c", ARENA_LOWERING_SCRIPT],
        capture_output=True, text=True, timeout=900,
        env=SUBPROC_ENV, cwd=REPO_ROOT,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    cases = json.loads(out.stdout.strip().splitlines()[-1])
    assert {r["policy"] for r in cases} == {"per_tensor", "single", "bucketed"}
    for rec in cases:
        n_groups, n_elems = rec["n_groups"], rec["n_elems"]
        for comp, itemsize in (("f32", 4), ("bf16", 2)):
            arena = rec[comp]
            # one op per group, zero concatenates, exact bytes
            assert arena["allreduce_ops"] == n_groups, rec
            assert arena["concat_ops"] == 0, rec
            assert arena["wire_bytes"] == n_elems * itemsize, rec
            tol = 1e-4 if comp == "f32" else 0.1
            assert arena["max_diff"] < tol, (comp, rec)
        # bf16 halves the wire exactly
        assert rec["bf16"]["wire_bytes"] * 2 == rec["f32"]["wire_bytes"], rec


class TestMeasuredComm:
    def test_fit_recovers_synthetic_alpha_beta(self):
        rng = np.random.default_rng(42)
        a, b = 4.5e-5, 1.0 / 1.07e9  # the paper's 10GbE constants
        sizes = tuple(4096 * 8**i for i in range(6))
        times = tuple(a + b * s + float(rng.normal(0, 2e-7)) for s in sizes)
        fit = MeasuredComm(sizes_bytes=sizes, times_s=times, axes=("data",)).fit()
        assert fit.a == pytest.approx(a, rel=0.05)
        assert fit.b == pytest.approx(b, rel=0.05)
        assert fit.name == "measured_comm[data]"
        # merge gain is the recovered α (Eq. 10)
        assert fit.merged_gain(1e6, 2e6) == pytest.approx(fit.a)

    def test_fit_clamps_negative_intercept(self):
        m = fit_affine([100, 200, 300], [1e-6, 3e-6, 5e-6])
        assert m.a >= 0.0 and m.b > 0.0

    def test_fit_rejects_degenerate_sweep(self):
        with pytest.raises(ValueError, match="pairs"):
            fit_affine([100], [1e-6])

    def test_live_sweep_on_host_mesh(self):
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((1,), ("data",))
        m = MeasuredComm.time_psums(
            mesh, ("data",), sizes_bytes=(4096, 65536, 1 << 20), repeats=1
        )
        assert len(m.times_s) == 3 and all(t > 0 for t in m.times_s)
        fit = m.fit()  # fits and is a usable AllReduceModel
        assert fit(1 << 20) >= fit(4096) >= 0.0

    def test_measured_model_drives_planning_transparently(self):
        _, layout = _toy_layout()
        costs = layout.layer_costs(1024, None)
        fit = fit_affine(
            [4096, 65536, 1 << 20], [5e-5 + s / 1e9 for s in (4096, 65536, 1 << 20)],
            name="measured_comm[data]",
        )
        sched = build_schedule("mg_wfbp", costs, fit)
        assert sched.result is not None and len(sched.groups) >= 1


class TestPlanAwareCheckpoint:
    def test_plan_rides_beside_weights(self, tmp_path):
        from repro.checkpoint import load_plan, restore, save
        from repro.core import layout_for_stacked_lm
        from repro.planning import build_plan

        layout = layout_for_stacked_lm(4, 5000, 3000, 7000)
        costs = layout.layer_costs(tokens_per_chip=64, hw=None)
        plan = build_plan(
            layout, costs, AllReduceModel(a=1e-3, b=1e-9), n_scan_stages=4
        )
        tree = {"w": np.arange(6, dtype=np.float32)}
        save(tmp_path, 7, tree, extra={"k": 1}, plan=plan)
        got = load_plan(tmp_path, 7)
        assert got == plan
        restored, extra = restore(tmp_path, 7, tree)
        assert extra == {"k": 1}
        np.testing.assert_array_equal(restored["w"], tree["w"])

    def test_missing_plan_is_none(self, tmp_path):
        from repro.checkpoint import load_plan, save

        save(tmp_path, 3, {"w": np.zeros(2, np.float32)})
        assert load_plan(tmp_path, 3) is None

    def test_async_checkpointer_snapshots_plan(self, tmp_path):
        from repro.checkpoint import AsyncCheckpointer, load_plan
        from repro.core import layout_for_stacked_lm
        from repro.planning import build_plan

        layout = layout_for_stacked_lm(2, 100, 100, 100)
        costs = layout.layer_costs(tokens_per_chip=8, hw=None)
        plan = build_plan(layout, costs, AllReduceModel(a=1e-4, b=1e-9))
        ck = AsyncCheckpointer(tmp_path)
        ck.save(5, {"w": np.ones(3, np.float32)}, plan=plan)
        ck.wait()
        assert load_plan(tmp_path, 5) == plan


def _cell_argvs():
    """Each benchmark cell's launcher command line, as the harness builds
    it: ``--arch`` from the cell's config file, then the cell's flags."""
    manifest = json.loads((REPO_ROOT / "BENCHMARK.json").read_text())
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    out = {}
    for w in manifest["workloads"]:
        arch = json.loads((REPO_ROOT / files[w["config"]]).read_text())["arch"]
        spec = json.loads((REPO_ROOT / "bench" / "cells" / f"{w['name']}.json").read_text())
        out[w["name"]] = ["--arch", arch, *spec["launcher"]]
    return out


class TestLauncherWire:
    @pytest.mark.parametrize("cell", sorted(_cell_argvs()))
    def test_cell_command_line_parses_to_the_f32_arena(self, cell):
        from repro.core.sync import SyncConfig
        from repro.launch import train

        args = train.parse_args(_cell_argvs()[cell])
        assert args.comm_dtype == "f32"
        assert train.setup(args).sync_cfg == SyncConfig(comm_dtype=jnp.float32)

    @pytest.mark.parametrize("flags", [
        ["--fuse", "concat"], ["--fuse", "variadic"], ["--compression", "bf16"],
    ])
    def test_removed_wire_options_are_refused(self, flags, capsys):
        from repro.launch import train

        with pytest.raises(SystemExit) as exc:
            train.parse_args(["--arch", "tinyllama-1.1b", *flags])
        assert exc.value.code == 2  # argparse's usage error
        assert flags[0] in capsys.readouterr().err

    def test_autotune_run_scores_the_arena_at_the_wire_dtype(self, tmp_path, monkeypatch):
        """``--autotune`` builds its Tuner from the launcher's SyncConfig and
        trains under the plan it picks."""
        from repro.launch import train

        seen = []

        class Recording(train.Tuner):
            def __init__(self, **kw):
                seen.append(kw["wire_dtype"])
                super().__init__(**kw)

        monkeypatch.setattr(train, "Tuner", Recording)
        res = train.main(["--arch", "tinyllama-1.1b", "--reduced", "--steps", "2",
                          "--batch", "2", "--seq", "64", "--autotune",
                          "--ckpt-dir", str(tmp_path)])
        assert seen == ["float32"]
        assert len(res.losses) == 2 and all(np.isfinite(res.losses))

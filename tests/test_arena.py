"""Arena wire path + measured-comm fitting.

Covers the PR-2 tentpole end to end:

  * pack→unpack numeric round-trip (oracle vs Pallas-interpret), incl.
    scan-stacked slices, odd-sized tails, and the fused error-feedback
    residual;
  * the plan-time ``group_arenas`` layout: exact packing (zero padding),
    offsets/sizes, scan-slice shapes;
  * lowered-HLO invariants for all three fuse modes: exact all-reduce op
    AND byte counts (``profiler.parse_collectives`` on stablehlo), zero
    concatenate ops on the arena path, bf16 halving wire bytes;
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
from repro.core.sync import SyncConfig, make_gradient_sync
from repro.kernels.comm_pack import pack_arena, unpack_arena
from repro.planning import MeasuredComm, build_schedule
from repro.runtime import bf16_ef_encode


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
        a_ref, _ = pack_arena(parts, offsets, total, dtype, use_pallas=False)
        a_pal, _ = pack_arena(parts, offsets, total, dtype, interpret=True)
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

    @pytest.mark.parametrize("ef", [False, True])
    def test_multi_chunk_pipeline_matches_oracle(self, ef):
        """Shrinking ``chunk`` below the part sizes forces the
        double-buffered DMA pipeline (warm-up + cross-chunk slot reuse, a
        last chunk pulled back onto its predecessor, ragged tails, parts
        at mid-tile offsets) — results must stay bit-identical to the
        oracle."""
        parts, offsets, sizes, total = _parts(
            seed=3, shapes=((5, 1024), (2500,), (37,), (3, 1024), (1,), (1001,))
        )
        rng = np.random.default_rng(4)
        res = (
            [jnp.asarray(rng.standard_normal(p.shape) * 1e-3, jnp.float32)
             for p in parts]
            if ef else None
        )
        a_ref, r_ref = pack_arena(
            parts, offsets, total, jnp.bfloat16, residuals=res, use_pallas=False
        )
        # chunk=2048: the 5120-part takes chunks at 0, 2048 and 3072; the
        # 2500-part one chunk and a 452 tail; the rest start mid-tile
        a_pal, r_pal = pack_arena(
            parts, offsets, total, jnp.bfloat16, residuals=res,
            interpret=True, chunk=2048,
        )
        np.testing.assert_array_equal(
            np.asarray(a_ref, np.float32), np.asarray(a_pal, np.float32)
        )
        if ef:
            for rr, rp in zip(r_ref, r_pal):
                np.testing.assert_array_equal(np.asarray(rr), np.asarray(rp))
        slots = list(zip(offsets, sizes))
        shapes = [p.shape for p in parts]
        dts = [p.dtype for p in parts]
        o_ref = unpack_arena(a_ref, slots, shapes, dts, scale=0.5, use_pallas=False)
        o_pal = unpack_arena(a_pal, slots, shapes, dts, scale=0.5,
                             interpret=True, chunk=2048)
        for r, p in zip(o_ref, o_pal):
            np.testing.assert_array_equal(np.asarray(r), np.asarray(p))

    def test_error_feedback_matches_compression_oracle(self):
        parts, offsets, sizes, total = self._setup()
        rng = np.random.default_rng(1)
        res = [jnp.asarray(rng.standard_normal(p.shape) * 1e-3, jnp.float32)
               for p in parts]
        for kw in ({"use_pallas": False}, {"interpret": True}):
            arena, new_res = pack_arena(
                parts, offsets, total, jnp.bfloat16, residuals=res, **kw
            )
            for p, r0, r1, off, n in zip(parts, res, new_res, offsets, sizes):
                wire_want, res_want = bf16_ef_encode(p, r0)
                np.testing.assert_array_equal(
                    np.asarray(arena[off : off + n], np.float32),
                    np.asarray(wire_want, np.float32).reshape(-1),
                )
                assert r1.shape == p.shape
                np.testing.assert_allclose(
                    np.asarray(r1), np.asarray(res_want), atol=1e-7
                )
                # EF identity: wire + residual reconstructs the accumulator
                np.testing.assert_allclose(
                    np.asarray(arena[off : off + n], np.float32).reshape(p.shape)
                    + np.asarray(r1),
                    np.asarray(p) + np.asarray(r0),
                    atol=1e-7,
                )


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
        AllReduceModel, SyncConfig, count_expected_allreduces,
        make_gradient_sync, parse_collectives, stacked_lm_layout,
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
        for fuse in ("concat", "variadic", "arena"):
            for comp in (None, "bf16"):
                cfgs = SyncConfig(fuse=fuse, compression=comp)
                sync = make_gradient_sync(layout, sched, ("data",), cfgs)

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
                rec[f"{fuse}_{comp or 'f32'}"] = {
                    "allreduce_ops": stats.counts.get("all-reduce", 0),
                    "expected": count_expected_allreduces(sched, cfgs, layout),
                    "wire_bytes": stats.bytes_by_kind.get("all-reduce", 0),
                    "concat_ops": stats.concat_ops,
                    "max_diff": diff,
                }
        rec["n_elems"] = int(n_elems)
        out.append(rec)

    # stateful error-feedback arena mode
    sched = build_schedule("bucketed", costs, AllReduceModel(a=1e-3, b=1e-9))
    cfgs = SyncConfig(fuse="arena", compression="bf16_ef")
    sync = make_gradient_sync(layout, sched, ("data",), cfgs)
    res0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), grads)

    def body_ef(g, r):
        return sync(g, r)

    f = jax.jit(jax.shard_map(body_ef, mesh=mesh, in_specs=(P(), P()),
                          out_specs=(P(), P()), axis_names={"data"}, check_vma=False))
    stats = parse_collectives(f.lower(grads, res0).as_text())
    o, r1 = f(grads, res0)
    # identical ranks: avg == bf16 value, so out + residual == grads exactly
    rec_ef = {
        "allreduce_ops": stats.counts.get("all-reduce", 0),
        "n_groups": len(sched.groups),
        "concat_ops": stats.concat_ops,
        "recon_diff": max(jax.tree.leaves(jax.tree.map(
            lambda a, b, c: float(jnp.max(jnp.abs(a + b - c))), o, r1, grads))),
    }
    print(json.dumps({"cases": out, "ef": rec_ef}))
""")


def test_arena_lowering_op_and_byte_counts():
    """Acceptance: ``fuse='arena'`` lowers to exactly one all-reduce HLO op
    per schedule group with ZERO concatenate ops, at exactly the concat
    layout's wire bytes (half of them under bf16) — per policy, via
    ``profiler.parse_collectives``."""
    out = subprocess.run(
        [sys.executable, "-c", ARENA_LOWERING_SCRIPT],
        capture_output=True, text=True, timeout=900,
        env=SUBPROC_ENV, cwd=REPO_ROOT,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    payload = json.loads(out.stdout.strip().splitlines()[-1])
    for rec in payload["cases"]:
        n_groups, n_elems = rec["n_groups"], rec["n_elems"]
        for comp, itemsize in (("f32", 4), ("bf16", 2)):
            arena = rec[f"arena_{comp}"]
            concat = rec[f"concat_{comp}"]
            variadic = rec[f"variadic_{comp}"]
            # arena: one op per group, zero concatenates, exact bytes
            assert arena["allreduce_ops"] == n_groups, rec
            assert arena["expected"] == n_groups, rec
            assert arena["concat_ops"] == 0, rec
            assert arena["wire_bytes"] == n_elems * itemsize, rec
            # byte parity with concat, and bf16 halves the wire exactly
            assert arena["wire_bytes"] <= concat["wire_bytes"], rec
            assert concat["allreduce_ops"] == n_groups, rec
            # variadic stays zero-copy but op counts are version-dependent
            assert variadic["concat_ops"] == 0, rec
            assert variadic["allreduce_ops"] == variadic["expected"], rec
            for fuse in ("arena", "concat", "variadic"):
                tol = 1e-4 if comp == "f32" else 0.1
                assert rec[f"{fuse}_{comp}"]["max_diff"] < tol, (fuse, comp, rec)
        assert rec["arena_bf16"]["wire_bytes"] * 2 == rec["concat_f32"]["wire_bytes"], rec
    ef = payload["ef"]
    assert ef["allreduce_ops"] == ef["n_groups"]
    assert ef["concat_ops"] == 0
    assert ef["recon_diff"] == pytest.approx(0.0, abs=1e-6)


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


class TestCompatProbe:
    def test_variadic_probe_cached_and_consistent(self):
        from repro.core.sync import variadic_psum_is_single_op

        first = variadic_psum_is_single_op()
        assert variadic_psum_is_single_op() is first  # functools.cache
        assert variadic_psum_is_single_op.cache_info().hits >= 1
        assert isinstance(first, bool)

    def test_sync_rejects_bad_modes(self):
        shapes, layout = _toy_layout()
        costs = layout.layer_costs(1024, None)
        sched = build_schedule("single", costs, AllReduceModel(a=1e-3, b=1e-9))
        with pytest.raises(ValueError, match="unknown fuse"):
            make_gradient_sync(layout, sched, ("data",), SyncConfig(fuse="nope"))
        with pytest.raises(ValueError, match="requires fuse='arena'"):
            make_gradient_sync(
                layout, sched, ("data",),
                SyncConfig(fuse="concat", compression="bf16_ef"),
            )

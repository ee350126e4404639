"""Fabric API: typed-collective cost algebra, the backend-preset registry,
back-compat shims over core.comm_model, the ServePlan lifecycle, and the
serve-side lowering invariant (one collective HLO op per scheduled group)."""

import dataclasses
import json
import subprocess
import sys
import textwrap

import pytest

from _env import REPO_ROOT, SUBPROC_ENV

from repro.core.comm_model import (
    AllReduceModel,
    TPU_V5E as TPU_V5E_SHIM,
    TpuInterconnect,
    fit_affine,
    paper_cluster_model,
    tpu_psum_model,
)
from repro.fabric import (
    Collective,
    Fabric,
    MeasuredFabric,
    RingInterconnect,
    available_fabrics,
    get_fabric,
    register_fabric,
)

PRESETS = ("tpu_v5e", "gpu_nccl", "dcn_only", "paper_10gbe")
#: Hierarchical tree/pipeline presets (Wang & Vuduc): covered by the
#: preset-wide invariants but not the ring-phase algebra tests (a tree
#: all-reduce is not rs ∘ ag, and its startup can undercut a ring
#: all_gather's — that asymmetry is the point of the presets).
HIER_PRESETS = ("tree_10gbe", "pipeline_10gbe", "tpu_v5e_tree_dcn")
ALL_PRESETS = PRESETS + HIER_PRESETS
#: Representative psum axis sets (single-axis, multi-ICI, cross-pod).
AXIS_CASES = (
    {"data": 8},
    {"data": 32},
    {"pod": 2, "data": 16},
    {"data": 16, "model": 4},
)


class TestFabricAlgebra:
    def test_rs_plus_ag_equals_all_reduce_per_axis(self):
        """One ring phase each way: reduce_scatter ∘ all_gather == all_reduce."""
        for preset in PRESETS:
            f = get_fabric(preset)
            for n in (2, 8, 16):
                rs = f.cost("reduce_scatter", {"data": n})
                ag = f.cost("all_gather", {"data": n})
                ar = f.cost("all_reduce", {"data": n})
                assert rs.a + ag.a == pytest.approx(ar.a, rel=1e-12), preset
                assert rs.b + ag.b == pytest.approx(ar.b, rel=1e-12), preset

    def test_hierarchical_composition_matches_psum_model(self):
        """Satellite: rs(ici) + cross-pod ar on 1/ici of the message +
        ag(ici) composed through the fabric == TpuInterconnect.psum_model."""
        f = get_fabric("tpu_v5e")
        for ici, pods in ((16, 2), (8, 4), (32, 2)):
            rs = f.cost(Collective.REDUCE_SCATTER, {"data": ici})
            ar = f.cost(Collective.ALL_REDUCE, {"pod": pods})
            ag = f.cost(Collective.ALL_GATHER, {"data": ici})
            ref = tpu_psum_model({"pod": pods, "data": ici})
            assert rs.a + ar.a + ag.a == pytest.approx(ref.a, rel=1e-12)
            assert rs.b + ag.b + ar.b / ici == pytest.approx(ref.b, rel=1e-12)

    def test_paper_preset_reproduces_paper_cluster(self):
        """paper_10gbe all_reduce == Table II ring at the paper's constants."""
        f = get_fabric("paper_10gbe")
        for n in (2, 4, 8):
            got = f.cost("all_reduce", {"data": n})
            ref = paper_cluster_model(n, algorithm="ring")
            assert got.a == pytest.approx(ref.a, rel=1e-12)
            assert got.b == pytest.approx(ref.b, rel=1e-12)

    def test_gather_cheaper_than_reduce(self):
        """all_gather ships bytes without reducing: b strictly below
        all_reduce's, a strictly below (one phase vs two)."""
        for preset in PRESETS:
            f = get_fabric(preset)
            ag = f.cost("all_gather", {"data": 8})
            ar = f.cost("all_reduce", {"data": 8})
            assert ag.b < ar.b and ag.a < ar.a, preset

    def test_all_to_all_prices_full_volume_per_tier(self):
        """Hierarchical all-to-all reshuffles the full local volume on
        every tier — no reduce-scatter shrink factor on the slow tier."""
        f = get_fabric("tpu_v5e")
        both = f.cost("all_to_all", {"data": 8, "pod": 4})
        ici = f.cost("all_to_all", {"data": 8})
        pod = f.cost("all_to_all", {"pod": 4})
        assert both.b == pytest.approx(ici.b + pod.b, rel=1e-12)

    def test_trivial_axes_are_free(self):
        f = get_fabric("tpu_v5e")
        for op in Collective:
            m = f.cost(op, {"data": 1})
            assert (m.a, m.b) == (0.0, 0.0)

    def test_every_preset_prices_every_op(self):
        for preset in ALL_PRESETS:
            f = get_fabric(preset)
            for op in Collective:
                for axes in AXIS_CASES:
                    m = f.cost(op, axes)
                    assert m.a > 0 and m.b > 0, (preset, op, axes)
                    # Eq. 10: merging recovers exactly the startup
                    assert m.merged_gain(1 << 20, 1 << 20) == pytest.approx(m.a)


class TestRegistry:
    def test_round_trip_and_protocol(self):
        for preset in ALL_PRESETS:
            f = get_fabric(preset)
            assert isinstance(f, Fabric)
            assert f.name == preset
        assert set(ALL_PRESETS) <= set(available_fabrics())

    def test_available_fabrics_is_sorted_list(self):
        """The registry listing is a sorted list — stable display order,
        directly usable as argparse choices."""
        names = available_fabrics()
        assert isinstance(names, list)
        assert names == sorted(names)

    def test_unknown_name_errors_with_known_list(self):
        with pytest.raises(KeyError, match="tpu_v5e"):
            get_fabric("infiniband_9000")

    def test_instance_passthrough(self):
        custom = RingInterconnect(ici_link_bw=1e9, name="custom")
        assert get_fabric(custom) is custom
        with pytest.raises(TypeError):
            get_fabric(object())  # no .cost

    def test_register_measured_round_trip(self):
        fit = AllReduceModel(a=3e-5, b=2e-9, name="fit")
        mf = MeasuredFabric(models={"data": fit})
        register_fabric("measured", mf, overwrite=True)
        got = get_fabric("measured")
        assert got is mf
        m = got.cost("all_reduce", {"data": 8})
        assert (m.a, m.b) == (fit.a, fit.b)

    def test_duplicate_registration_raises(self):
        with pytest.raises(ValueError):
            register_fabric("tpu_v5e", RingInterconnect())


class TestMeasuredFabric:
    def test_from_comm_fit_slots_into_cost(self):
        """A MeasuredComm-style sweep drives the same cost() surface."""
        from repro.planning import MeasuredComm

        true = AllReduceModel(a=5e-5, b=1.5e-9)
        sizes = tuple(4096 * 8**i for i in range(5))
        comm = MeasuredComm(sizes_bytes=sizes,
                            times_s=tuple(true(s) for s in sizes),
                            axes=("data",))
        mf = MeasuredFabric.from_comm(comm)
        ar = mf.cost("all_reduce", {"data": 32})
        assert ar.a == pytest.approx(true.a, rel=1e-6)
        assert ar.b == pytest.approx(true.b, rel=1e-6)
        # derived single-phase ops: half the ring each way
        ag = mf.cost("all_gather", {"data": 32})
        assert ag.a == pytest.approx(true.a / 2, rel=1e-6)
        assert ag.b == pytest.approx(true.b / 2, rel=1e-6)

    def test_op_override_and_missing_axes(self):
        mf = MeasuredFabric(models={
            "data": AllReduceModel(a=1e-5, b=1e-9),
            "all_gather@data": AllReduceModel(a=9e-6, b=3e-10),
        })
        ag = mf.cost("all_gather", {"data": 8})
        assert (ag.a, ag.b) == (9e-6, 3e-10)  # direct fit wins
        with pytest.raises(KeyError, match="model"):
            mf.cost("all_reduce", {"model": 4})


class TestCommModelShim:
    def test_shim_names_are_the_preset(self):
        """Satellite: core.comm_model keeps the TPU names as re-exports of
        the tpu_v5e fabric preset."""
        assert TPU_V5E_SHIM is get_fabric("tpu_v5e")
        assert TpuInterconnect is RingInterconnect
        assert isinstance(TPU_V5E_SHIM, TpuInterconnect)

    def test_shim_and_preset_identical_ab(self):
        """Satellite: identical (a, b) through both surfaces for
        representative axis sizes."""
        preset = get_fabric("tpu_v5e")
        for axes in AXIS_CASES:
            shim = tpu_psum_model(axes)
            direct = preset.cost("all_reduce", axes)
            assert (shim.a, shim.b) == (direct.a, direct.b), axes
            legacy = TpuInterconnect().psum_model(axes)
            assert (shim.a, shim.b) == (legacy.a, legacy.b), axes

    def test_core_package_reexports(self):
        import repro.core as core

        assert core.TPU_V5E_ICI is get_fabric("tpu_v5e")
        assert core.tpu_psum_model is tpu_psum_model


def _serve_inputs(arch="tinyllama-1.1b", batch_rows=16):
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch.specs import param_specs

    cfg = get_config(arch)
    return cfg, param_specs(cfg)


class TestServePlan:
    def test_json_round_trip_exact(self):
        from repro.planning import ServePlan, build_serve_plan

        cfg, shapes = _serve_inputs()
        plan = build_serve_plan(cfg, shapes, "tpu_v5e", {"model": 8},
                                batch_rows=16)
        rt = ServePlan.from_json(plan.to_json())
        assert rt == plan
        # and through a dict cycle that simulates a file on disk
        rt2 = ServePlan.from_json_dict(json.loads(plan.to_json()))
        assert rt2.schedule.result.t_iter == plan.schedule.result.t_iter

    def test_save_load(self, tmp_path):
        from repro.planning import ServePlan, build_serve_plan

        cfg, shapes = _serve_inputs()
        plan = build_serve_plan(cfg, shapes, "gpu_nccl", {"model": 8},
                                batch_rows=16)
        p = plan.save(tmp_path / "serve_plan.json")
        assert ServePlan.load(p) == plan

    def test_bad_format_rejected(self):
        from repro.planning import ServePlan, build_serve_plan

        cfg, shapes = _serve_inputs()
        d = build_serve_plan(cfg, shapes, "tpu_v5e", {"model": 8},
                             batch_rows=16).to_json_dict()
        d["format"] = 99
        with pytest.raises(ValueError, match="format"):
            ServePlan.from_json_dict(d)

    def test_moe_arch_schedules_all_to_all(self):
        from repro.configs import get_config
        from repro.launch.specs import param_specs
        from repro.planning import build_serve_plan, decode_unit_costs

        cfg = get_config("mixtral-8x7b")
        plan = build_serve_plan(cfg, param_specs(cfg), "tpu_v5e",
                                {"model": 8}, batch_rows=16)
        assert plan.op == "all_to_all"
        assert plan.provenance["fabric"] == "tpu_v5e"
        # 'moe' blocks carry an attention sublayer: the per-stage payload
        # must include the fresh KV rows on top of the expert dispatch
        costs = decode_unit_costs(cfg, param_specs(cfg), 16)
        kv = 2 * 16 * cfg.attention.n_kv_heads * cfg.attention.head_dim * 2
        a2a = 2 * 16 * cfg.moe.top_k * cfg.d_model * 2 * len(cfg.pattern)
        assert costs[0].grad_bytes == kv + a2a

    def test_recurrent_stages_ship_no_kv(self):
        from repro.configs import get_config
        from repro.launch.specs import param_specs
        from repro.planning import decode_unit_costs

        cfg = get_config("rwkv6-7b")  # pattern ('rwkv',): no KV cache
        costs = decode_unit_costs(cfg, param_specs(cfg), 16)
        assert costs[0].grad_bytes == 1  # clamped empty payload

    def test_fabric_moves_the_merge_set(self):
        """Same cost vector, different fabric -> different schedule: the
        NCCL-class launch overhead merges what TPU ICI keeps separate."""
        from repro.planning import build_serve_plan

        cfg, shapes = _serve_inputs()
        tpu = build_serve_plan(cfg, shapes, "tpu_v5e", {"model": 8},
                               batch_rows=16)
        nccl = build_serve_plan(cfg, shapes, "gpu_nccl", {"model": 8},
                                batch_rows=16)
        assert len(tpu.schedule.groups) > len(nccl.schedule.groups)

    def test_all_presets_yield_valid_plans(self):
        from repro.planning import build_serve_plan

        cfg, shapes = _serve_inputs()
        for preset in ALL_PRESETS:
            plan = build_serve_plan(cfg, shapes, preset, {"model": 8},
                                    batch_rows=16)
            assert plan.schedule.groups[0][0] == 1
            assert plan.schedule.groups[-1][1] == cfg.n_stages
            assert plan.schedule.result.t_iter > 0
            if plan.model.a > 0:
                assert plan.model.merged_gain(1, 1) > 0

    def test_engine_carries_plan(self):
        import jax
        import jax.numpy as jnp

        from repro.configs import get_reduced
        from repro.launch.specs import param_specs
        from repro.models.transformer import init_params
        from repro.planning import build_serve_plan
        from repro.serving import Request, ServingEngine

        cfg = dataclasses.replace(get_reduced("tinyllama-1.1b"),
                                  param_dtype=jnp.float32)
        plan = build_serve_plan(cfg, param_specs(cfg), "tpu_v5e",
                                {"model": 4}, batch_rows=2)
        params = init_params(jax.random.PRNGKey(0), cfg)
        eng = ServingEngine(cfg, params, slots=2, max_seq=32, plan=plan)
        assert eng.plan is plan
        assert eng.predicted_step_time() == plan.schedule.result.t_iter
        import numpy as np

        eng.submit(Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                           max_new_tokens=3))
        done = eng.run_to_completion()
        assert len(done) == 1 and len(done[0].generated) == 3


SERVE_LOWERING_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.configs import get_config
    from repro.core.profiler import parse_collectives
    from repro.launch.specs import param_specs
    from repro.planning import build_serve_plan, make_group_collective

    cfg = get_config("tinyllama-1.1b")
    shapes = param_specs(cfg)
    mesh = make_mesh((8,), ("model",))
    out = []
    # tpu_v5e @ 16 rows -> many groups; gpu_nccl -> one merged group;
    # wfbp pins the one-op-per-group invariant at the other extreme.
    for fabric, policy in (("tpu_v5e", "mg_wfbp"), ("gpu_nccl", "mg_wfbp"),
                           ("tpu_v5e", "wfbp")):
        plan = build_serve_plan(cfg, shapes, fabric, {"model": 8},
                                batch_rows=16, policy=policy)
        gather = make_group_collective(plan)
        stacked = jnp.ones((cfg.n_stages, 16, 64), jnp.float32)

        f = jax.shard_map(gather, mesh=mesh, in_specs=(P(),),
                      out_specs=[P(None, "model") for _ in plan.schedule.groups],
                      axis_names={"model"}, check_vma=False)
        stats = parse_collectives(jax.jit(f).lower(stacked).as_text())
        outs = jax.jit(f)(stacked)
        ok = all(float(jnp.max(jnp.abs(o - 1.0))) == 0.0 for o in outs)
        out.append({
            "fabric": fabric,
            "policy": policy,
            "op": plan.op,
            "n_groups": len(plan.schedule.groups),
            "collective_ops": stats.counts.get("all-gather", 0),
            "total_collectives": stats.total_ops,
            "values_ok": ok,
        })
    print(json.dumps(out))
""")


def test_serve_lowering_one_collective_per_group():
    """Acceptance: exactly one collective HLO op per scheduled serve group
    — the decode-side analogue of the training sync's lowering invariant."""
    out = subprocess.run(
        [sys.executable, "-c", SERVE_LOWERING_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env=SUBPROC_ENV, cwd=REPO_ROOT,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    recs = json.loads(out.stdout.strip().splitlines()[-1])
    by = {(r["fabric"], r["policy"]): r for r in recs}
    # the fabrics picked different merge sets from the same cost vector
    assert by[("tpu_v5e", "mg_wfbp")]["n_groups"] > by[("gpu_nccl", "mg_wfbp")]["n_groups"]
    assert by[("tpu_v5e", "wfbp")]["n_groups"] == get_config_n_stages()
    for r in recs:
        assert r["op"] == "all_gather", r
        assert r["collective_ops"] == r["n_groups"], r
        assert r["total_collectives"] == r["n_groups"], r  # nothing extra
        assert r["values_ok"], r


def get_config_n_stages():
    from repro.configs import get_config

    return get_config("tinyllama-1.1b").n_stages

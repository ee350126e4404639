"""Planning subsystem: policy registry, Plan artifact round-trip, cost
sources + measured-profile re-planning, scan-bucket edge cases, and the
sync lowering invariant (exactly one all-reduce per schedule group)."""

import json
import os
import random
import subprocess
import sys
import textwrap
from _env import REPO_ROOT, SUBPROC_ENV  # shared subprocess env

import pytest

from repro.core import (
    AllReduceModel,
    Hardware,
    LayerCost,
    layer_buckets_for_scan,
    layout_for_stacked_lm,
    wfbp_schedule,
)
from repro.core.schedule import (
    Schedule,
    dp_optimal_schedule,
    evaluate,
    mg_wfbp_schedule,
    optimal_schedule,
)
from repro.planning import (
    MEASURED_HW,
    MeasuredCosts,
    Plan,
    available_policies,
    build_plan,
    build_schedule,
    cost_drift,
    get_policy,
    register_policy,
    replan_if_drifted,
    resolve_policy_name,
)

HW = Hardware(name="unit", peak_flops=1.0, hbm_bw=1.0, mxu_eff=1.0, hbm_eff=1.0)


def mk_costs(tb, nbytes, tf=0.0):
    return [
        LayerCost(
            name=f"l{i + 1}", params=n, grad_bytes=n, bwd_flops=t, fwd_flops=tf / len(tb)
        )
        for i, (t, n) in enumerate(zip(tb, nbytes))
    ]


class TestRegistry:
    def test_builtins_registered(self):
        names = available_policies()
        for p in ("wfbp", "synceasgd", "fixed", "mg_wfbp", "dp_optimal", "optimal"):
            assert p in names

    def test_strategy_aliases(self):
        """The old SyncConfig.strategy vocabulary resolves to policies."""
        assert resolve_policy_name("per_tensor") == "wfbp"
        assert resolve_policy_name("single") == "synceasgd"
        assert resolve_policy_name("bucketed") == "mg_wfbp"

    def test_unknown_policy_raises(self):
        with pytest.raises(KeyError, match="unknown scheduler policy"):
            get_policy("definitely_not_a_policy")

    def test_duplicate_registration_raises(self):
        with pytest.raises(ValueError, match="already registered"):
            register_policy("mg_wfbp")(lambda *a, **k: None)

    def test_register_custom_policy(self):
        @register_policy("_test_pairs", overwrite=True)
        def pairs(costs, ar_model, hw=HW, t_f=None, **opts):
            L = len(costs)
            groups = tuple((l, min(l + 1, L)) for l in range(1, L + 1, 2))
            return Schedule(groups=groups, method="_test_pairs")

        costs = mk_costs([0.1] * 5, [100] * 5, tf=0.1)
        s = build_schedule("_test_pairs", costs, AllReduceModel(a=0.01, b=1e-6), hw=HW)
        assert s.groups == ((1, 2), (3, 4), (5, 5))
        assert s.result is not None  # registry evaluated it

    def test_all_builtin_policies_run_and_evaluate(self):
        costs = mk_costs([0.01] * 6, [1000] * 6, tf=0.05)
        ar = AllReduceModel(a=1e-3, b=1e-8)
        for name in ("wfbp", "synceasgd", "fixed", "mg_wfbp", "dp_optimal", "optimal"):
            s = build_schedule(name, costs, ar, hw=HW)
            assert s.result is not None and s.result.t_iter > 0, name
            assert s.groups[0][0] == 1 and s.groups[-1][1] == 6, name


class TestSchedulingEquivalence:
    """Seeded-random coverage (runs without hypothesis): the exact DP never
    loses to the paper's greedy and always matches exhaustive search."""

    def test_dp_le_greedy_and_eq_exhaustive_random(self):
        rng = random.Random(1234)
        for trial in range(40):
            L = rng.randint(2, 12)
            tb = [rng.uniform(1e-4, 1.0) for _ in range(L)]
            nb = [rng.randint(1, 10_000_000) for _ in range(L)]
            costs = mk_costs(tb, nb, tf=rng.uniform(0.0, 1.0))
            ar = AllReduceModel(a=rng.uniform(1e-6, 0.5), b=rng.uniform(1e-12, 1e-6))
            dp = dp_optimal_schedule(costs, ar, HW)
            greedy = mg_wfbp_schedule(costs, ar, HW)
            exact = optimal_schedule(costs, ar, HW)
            assert dp.result.t_iter <= greedy.result.t_iter + 1e-9, (trial, L)
            assert dp.result.t_iter == pytest.approx(
                exact.result.t_iter, rel=1e-9, abs=1e-12
            ), (trial, L)


class TestScanBuckets:
    def test_group_spanning_embed_boundary(self):
        # units: 1=embed, 2..5=stages, 6=head; group [1..3] spans embed+2 stages
        s = Schedule(groups=((1, 3), (4, 6)), method="manual")
        assert layer_buckets_for_scan(s, 4) == ((0, 2), (2, 4))

    def test_group_spanning_head_boundary(self):
        s = Schedule(groups=((1, 1), (2, 6)), method="manual")
        assert layer_buckets_for_scan(s, 4) == ((0, 4),)

    def test_single_group_covers_all(self):
        s = Schedule(groups=((1, 6),), method="manual")
        assert layer_buckets_for_scan(s, 4) == ((0, 4),)

    def test_singletons_give_per_stage_segments(self):
        s = wfbp_schedule(6)
        assert layer_buckets_for_scan(s, 4) == ((0, 1), (1, 2), (2, 3), (3, 4))

    def test_coverage_mismatch_raises(self):
        with pytest.raises(ValueError, match="do not cover"):
            layer_buckets_for_scan(wfbp_schedule(4), 4)


def small_plan(policy="mg_wfbp"):
    layout = layout_for_stacked_lm(4, embed_params=5000, layer_params=3000, head_params=7000)
    costs = layout.layer_costs(tokens_per_chip=64, hw=HW)
    ar = AllReduceModel(a=1e-3, b=1e-9)
    return build_plan(
        layout, costs, ar, policy=policy, hw=HW, n_scan_stages=4,
        provenance={"arch": "unit-test"},
    )


class TestPlanArtifact:
    def test_json_round_trip_exact(self):
        plan = small_plan()
        clone = Plan.from_json(plan.to_json())
        assert clone == plan
        # and the serialized form itself is stable
        assert clone.to_json() == plan.to_json()

    def test_save_load(self, tmp_path):
        plan = small_plan("dp_optimal")
        path = plan.save(tmp_path / "plans" / "p.json")
        loaded = Plan.load(path)
        assert loaded == plan
        assert loaded.policy == "dp_optimal"
        assert loaded.segments == plan.segments

    def test_provenance_and_describe(self):
        plan = small_plan()
        assert plan.provenance["policy"] == "mg_wfbp"
        assert plan.provenance["cost_source"] == "analytic"
        assert plan.provenance["arch"] == "unit-test"
        assert "mg_wfbp" in plan.describe()

    def test_bad_format_rejected(self):
        plan = small_plan()
        d = plan.to_json_dict()
        d["format"] = 99
        with pytest.raises(ValueError, match="unsupported plan format"):
            Plan.from_json_dict(d)

    def test_build_plan_validates_cost_length(self):
        layout = layout_for_stacked_lm(2, 10, 10, 10)
        costs = mk_costs([0.1] * 3, [10] * 3)  # layout has 4 units
        with pytest.raises(ValueError, match="cost vector"):
            build_plan(layout, costs, AllReduceModel(a=1e-3, b=1e-9), hw=HW)


class TestMeasuredReplan:
    """Acceptance: MeasuredCosts -> replan_if_drifted yields a different
    (better-modeled) schedule than the analytic plan on a skewed-cost
    instance — the journal version's online re-planning."""

    def skewed_setup(self):
        # Analytic belief: tiny uniform backward times + large startup α
        # => comm-bound => Algorithm 1 merges everything into one message.
        layout = layout_for_stacked_lm(6, 1_000_000, 1_000_000, 1_000_000)
        analytic = mk_costs([0.01] * 8, [1_000_000] * 8, tf=0.01)
        ar = AllReduceModel(a=0.5, b=1e-9)
        plan = build_plan(layout, analytic, ar, policy="mg_wfbp", hw=HW, n_scan_stages=6)
        # Reality: backward is ~200x slower than believed => comm hides
        # behind compute and merging everything is pessimal.
        measured = MeasuredCosts.from_unit_times(
            analytic, [2.0] * 8, name="measured_skew"
        )
        return plan, measured

    def test_replan_changes_schedule_and_improves_model(self):
        plan, measured = self.skewed_setup()
        assert plan.schedule.groups == ((1, 8),)  # analytic merged everything
        drift = cost_drift(plan, measured)
        assert drift > 1.0  # 200x skew
        new_plan, replanned = replan_if_drifted(plan, measured, threshold=0.25)
        assert replanned
        assert new_plan.schedule.groups != plan.schedule.groups
        # better-modeled: under measured costs, the re-planned schedule's
        # t_iter beats the stale analytic schedule's.
        stale = evaluate(
            list(plan.schedule.groups), measured.layer_costs(), plan.ar_model, MEASURED_HW
        )
        assert new_plan.schedule.result.t_iter < stale.t_iter - 1e-9
        # provenance records the hand-off
        assert new_plan.provenance["cost_source"] == "measured_skew"
        assert new_plan.provenance["replanned_from"] == "analytic"
        assert float(new_plan.provenance["drift"]) == pytest.approx(drift, rel=1e-3)
        # segments follow the new schedule
        assert new_plan.segments != plan.segments

    def test_below_threshold_keeps_plan(self):
        plan, _ = self.skewed_setup()
        near = MeasuredCosts.from_unit_times(
            list(plan.costs), [c.t_b(HW) * 1.05 for c in plan.costs]
        )
        same, replanned = replan_if_drifted(plan, near, threshold=0.25)
        assert not replanned and same is plan

    def test_zero_drift_on_identical(self):
        plan, _ = self.skewed_setup()
        identical = MeasuredCosts.from_unit_times(
            list(plan.costs), [c.t_b(HW) for c in plan.costs]
        )
        assert cost_drift(plan, identical) == pytest.approx(0.0, abs=1e-12)

    def test_step_timing_calibration(self):
        plan, _ = self.skewed_setup()
        modeled = plan.schedule.result.t_iter
        m = MeasuredCosts.from_step_timing(list(plan.costs), HW, 2 * modeled, modeled)
        # uniform 2x scale on every unit
        for c, base in zip(m.layer_costs(), plan.costs):
            assert c.t_b(MEASURED_HW) == pytest.approx(2 * base.t_b(HW), rel=1e-9)
            assert c.grad_bytes == base.grad_bytes

    def test_unit_count_mismatch_raises(self):
        plan, _ = self.skewed_setup()
        with pytest.raises(ValueError):
            MeasuredCosts.from_unit_times(list(plan.costs), [1.0] * 3)


def test_moe_stage_costs_its_expected_held_share():
    """A MoE stage is priced by the weights a token multiplies through:
    all but the held experts', and of these top_k·held/E experts' worth
    (reduced SDAR: 8 x 16 / 128 = one expert of the 16 held)."""
    from repro.configs import get_reduced
    from repro.core.trainer import lm_unit_costs
    from repro.launch.specs import param_specs

    cfg = get_reduced("sdar-30b-a3b")
    shapes = param_specs(cfg)
    stage = next(c for c in lm_unit_costs(cfg, shapes, tokens_per_device=64)
                 if c.name == "stage_0")
    moe = shapes["stages"]["moe_0"]["moe"]
    experts = sum(moe[k].size for k in ("w_gate", "w_up", "w_down")) // cfg.n_stages
    active = stage.params - experts + experts / cfg.moe.n_held * 8 * 16 / 128
    assert stage.bwd_flops == pytest.approx(4.0 * active * 64)


class TestEnginePlan:
    """MGWFBPEngine accepts/produces a Plan and rebuilds identically from
    the serialized artifact."""

    def test_engine_round_trips_plan(self):
        from repro.configs import get_reduced
        from repro.core.trainer import MGWFBPEngine
        from repro.launch.specs import param_specs

        cfg = get_reduced("tinyllama-1.1b")
        shapes = param_specs(cfg)
        ar = AllReduceModel(a=5e-5, b=1e-9)
        eng = MGWFBPEngine.build(
            cfg, shapes, dp_axes=("data",), ar_model=ar,
            tokens_per_device=1024, policy="mg_wfbp",
        )
        assert eng.plan.provenance["policy"] == "mg_wfbp"
        assert eng.schedule is eng.plan.schedule
        assert eng.segments == eng.plan.segments

        clone = Plan.from_json(eng.plan.to_json())
        eng2 = MGWFBPEngine.build(cfg, None, dp_axes=("data",), plan=clone)
        assert eng2.plan == eng.plan
        assert eng2.schedule.groups == eng.schedule.groups

    def test_engine_replan_rebuilds_sync(self):
        from repro.configs import get_reduced
        from repro.core.trainer import MGWFBPEngine
        from repro.launch.specs import param_specs

        cfg = get_reduced("tinyllama-1.1b")
        shapes = param_specs(cfg)
        # comm-bound analytic belief: merge everything
        ar = AllReduceModel(a=0.5, b=1e-9)
        eng = MGWFBPEngine.build(
            cfg, shapes, dp_axes=("data",), ar_model=ar,
            tokens_per_device=1024, policy="mg_wfbp",
        )
        measured = MeasuredCosts.from_unit_times(
            list(eng.plan.costs), [10.0] * len(eng.plan.costs)
        )
        eng2, replanned = eng.replan(measured, threshold=0.25)
        assert replanned
        assert eng2.plan.schedule.groups != eng.plan.schedule.groups
        assert eng2.sync is not eng.sync


SYNC_LOWERING_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, re
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.core import AllReduceModel, make_gradient_sync, stacked_lm_layout
    from repro.planning import build_schedule

    n_stages = 4
    shapes = {
        "embed": {"tok": jnp.zeros((32, 16))},
        "stages": {"w1": jnp.zeros((n_stages, 16, 16)), "w2": jnp.zeros((n_stages, 16))},
        "final_norm": {"scale": jnp.zeros((16,))},
        "head": {"w": jnp.zeros((16, 32))},
    }
    layout = stacked_lm_layout(shapes, n_stages)
    costs = layout.layer_costs(1024, None)
    mesh = make_mesh((8,), ("data",))
    key = jax.random.PRNGKey(0)
    grads = jax.tree.map(
        lambda s: jax.random.normal(jax.random.fold_in(key, s.size), s.shape), shapes
    )

    # α picked so mg_wfbp lands between the two extremes on these costs.
    CASES = [
        ("per_tensor", AllReduceModel(a=1e-3, b=1e-9)),
        ("single", AllReduceModel(a=1e-3, b=1e-9)),
        ("bucketed", AllReduceModel(a=1e-3, b=1e-9)),
        ("fixed", AllReduceModel(a=1e-3, b=1e-9)),
        ("dp_optimal", AllReduceModel(a=1e-3, b=1e-9)),
    ]
    out = []
    for policy, ar in CASES:
        opts = {"bucket_bytes": 3000} if policy == "fixed" else {}
        sched = build_schedule(policy, costs, ar, **opts)
        sync = make_gradient_sync(layout, sched, ("data",))

        def body(g):
            # distinct per-device values: rank r contributes (r+1)*g,
            # so the averaged result must equal 4.5*g exactly.
            r = jax.lax.axis_index("data").astype(jnp.float32)
            scaled = jax.tree.map(lambda x: x * (r + 1.0), g)
            return sync(scaled)

        f = jax.shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                      axis_names={"data"}, check_vma=False)
        text = jax.jit(f).lower(grads).as_text()
        got = jax.jit(f)(grads)
        expect = jax.tree.map(lambda x: 4.5 * x, grads)
        diff = max(
            jax.tree.leaves(
                jax.tree.map(
                    lambda a, b: float(jnp.max(jnp.abs(a - b))), got, expect
                )
            )
        )
        out.append({
            "policy": policy,
            "n_groups": len(sched.groups),
            "hlo_allreduces": len(re.findall(r"stablehlo\\.all_reduce", text)),
            "hlo_concats": len(re.findall(r"stablehlo\\.concatenate", text)),
            "max_diff": diff,
        })
    print(json.dumps(out))
""")


def test_sync_lowering_allreduce_counts():
    """Satellite: the sync under shard_map lowers to exactly
    len(schedule.groups) all-reduce ops per policy — the merged message
    of Definition 1 — with zero concatenates, and averages exactly."""
    out = subprocess.run(
        [sys.executable, "-c", SYNC_LOWERING_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env=SUBPROC_ENV,
        cwd=REPO_ROOT,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    recs = json.loads(out.stdout.strip().splitlines()[-1])
    assert {r["policy"] for r in recs} == {
        "per_tensor", "single", "bucketed", "fixed", "dp_optimal"
    }
    by = {r["policy"]: r for r in recs}
    assert by["per_tensor"]["n_groups"] == 6  # embed + 4 stages + head
    assert by["single"]["n_groups"] == 1
    assert 1 < by["fixed"]["n_groups"] < 6  # genuinely intermediate
    for r in recs:
        assert r["hlo_allreduces"] == r["n_groups"], r
        assert r["hlo_concats"] == 0, r
        assert r["max_diff"] < 1e-4, r

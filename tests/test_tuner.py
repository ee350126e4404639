"""Closed-loop auto-tuner: registry-wide sweep determinism and argmin
guarantee, comm-drift re-fitting (threshold-exact firing, α×10 injection),
tuner-state checkpoint round-trip, predicted-vs-observed provenance,
per-unit probe non-uniformity, and the bf16_ef residual threading through
the train step + checkpoints."""

import json
import subprocess
import sys

import pytest
from _env import REPO_ROOT, SUBPROC_ENV

from repro.core import AllReduceModel, Hardware, layout_for_stacked_lm
from repro.planning import (
    DEFAULT_COMM_SWEEP,
    MEASURED_HW,
    SLIM_COMM_SWEEP,
    CommRefitter,
    MeasuredComm,
    MeasuredCosts,
    SweepRecord,
    Tuner,
    available_policies,
    build_plan,
    comm_drift,
    default_policies,
    replan_if_comm_drifted,
)

HW = Hardware(name="unit", peak_flops=1.0, hbm_bw=1.0, mxu_eff=1.0, hbm_eff=1.0)


def small_setup(n_layers=6, seed_skew=False):
    layout = layout_for_stacked_lm(
        n_layers, embed_params=5_000_000, layer_params=1_000_000,
        head_params=7_000_000,
    )
    costs = layout.layer_costs(tokens_per_chip=64, hw=HW)
    if seed_skew:
        costs = MeasuredCosts.from_unit_times(
            costs, [0.01 * (i + 1) for i in range(len(costs))], name="skew"
        ).layer_costs()
    ar = AllReduceModel(a=1e-3, b=1e-9)
    return layout, costs, ar


class TestSweep:
    def test_deterministic(self):
        """Same layout × costs × model -> byte-identical chosen plan and
        candidate table, across independent Tuner instances."""
        layout, costs, ar = small_setup(seed_skew=True)
        t1 = Tuner(layout=layout, n_scan_stages=6)
        t2 = Tuner(layout=layout, n_scan_stages=6)
        p1 = t1.sweep(costs, ar, MEASURED_HW, cost_source="skew")
        p2 = t2.sweep(costs, ar, MEASURED_HW, cost_source="skew")
        assert p1.to_json() == p2.to_json()
        assert t1.last_record.to_json_dict() == t2.last_record.to_json_dict()
        # and policy iteration order is the sorted registry, not dict order
        assert list(t1.policies) == sorted(t1.policies)

    def test_argmin_and_per_tensor_bound(self):
        """Acceptance: chosen plan's predicted t_iter ≤ EVERY candidate's,
        in particular ≤ the per_tensor (wfbp) baseline's."""
        layout, costs, ar = small_setup()
        tuner = Tuner(layout=layout, n_scan_stages=6)
        plan = tuner.sweep(costs, ar, MEASURED_HW)
        rec = tuner.last_record
        by_policy = {c.policy: c for c in rec.candidates}
        assert "wfbp" in by_policy  # per_tensor alias target swept
        for c in rec.candidates:
            assert rec.predicted_t_iter <= c.predicted_t_iter + 1e-12, c
        assert rec.predicted_t_iter <= by_policy["wfbp"].predicted_t_iter
        assert plan.schedule.result.t_iter == pytest.approx(rec.predicted_t_iter)

    def test_sweeps_whole_registry(self):
        layout, costs, ar = small_setup()
        tuner = Tuner(layout=layout, n_scan_stages=6)
        tuner.sweep(costs, ar, MEASURED_HW)
        swept = {c.policy for c in tuner.last_record.candidates}
        # 8 units: small enough that even exhaustive 'optimal' is included
        assert swept == set(available_policies())

    def test_exhaustive_dropped_for_large_layouts(self):
        assert "optimal" not in default_policies(40)
        assert "optimal" in default_policies(8)

    def test_arena_bytes_scored_when_shapes_given(self):
        import jax.numpy as jnp

        n_stages = 4
        shapes = {
            "embed": {"tok": jnp.zeros((64, 32))},
            "stages": {"w": jnp.zeros((n_stages, 32, 32))},
            "final_norm": {"scale": jnp.zeros((32,))},
            "head": {"w": jnp.zeros((32, 65))},
        }
        from repro.core.bucketing import stacked_lm_layout

        layout = stacked_lm_layout(shapes, n_stages)
        costs = layout.layer_costs(1 << 20, None)
        tuner = Tuner(layout=layout, n_scan_stages=n_stages, shapes=shapes)
        tuner.sweep(costs, AllReduceModel(a=5e-5, b=1e-9), MEASURED_HW)
        total_elems = 64 * 32 + n_stages * 32 * 32 + 32 + 32 * 65
        for c in tuner.last_record.candidates:
            # exact packing: arena bytes == payload bytes on every candidate
            assert c.arena_bytes == total_elems * 4, c

    def test_provenance_records_search(self):
        layout, costs, ar = small_setup()
        tuner = Tuner(layout=layout, n_scan_stages=6)
        plan = tuner.sweep(
            costs, ar, MEASURED_HW, cost_source="probe_segments",
            comm_source="measured", trigger="startup",
        )
        assert plan.provenance["tuner"] == "startup"
        assert plan.provenance["cost_source"] == "probe_segments"
        assert plan.provenance["comm_source"] == "measured"
        assert float(plan.provenance["predicted_t_iter"]) == pytest.approx(
            tuner.last_record.predicted_t_iter
        )
        assert int(plan.provenance["candidates"]) == len(tuner.last_record.candidates)

    def test_observed_vs_predicted(self):
        layout, costs, ar = small_setup()
        tuner = Tuner(layout=layout, n_scan_stages=6)
        with pytest.raises(ValueError, match="before any sweep"):
            tuner.observe(1.0)
        tuner.sweep(costs, ar, MEASURED_HW)
        rec = tuner.observe(0.042)
        assert rec.observed_t_iter == pytest.approx(0.042)
        assert rec.predicted_t_iter > 0
        # the pair survives serialization
        clone = SweepRecord.from_json_dict(rec.to_json_dict())
        assert clone.observed_t_iter == rec.observed_t_iter


class TestTunerStateCheckpoint:
    def test_round_trip_through_checkpoint(self, tmp_path):
        import numpy as np

        from repro.checkpoint import load_tuner_state, save

        layout, costs, ar = small_setup()
        tuner = Tuner(layout=layout, n_scan_stages=6)
        tuner.sweep(costs, ar, MEASURED_HW, trigger="startup")
        tuner.observe(0.5)
        tuner.sweep(costs, AllReduceModel(a=1e-2, b=1e-9), MEASURED_HW,
                    trigger="comm_drift")

        save(tmp_path, 7, {"x": np.zeros(3)}, tuner=tuner)
        state = load_tuner_state(tmp_path, 7)
        assert state is not None
        restored = Tuner(layout=layout, n_scan_stages=6).load_state(state)
        assert len(restored.history) == 2
        assert [r.trigger for r in restored.history] == ["startup", "comm_drift"]
        assert restored.history[0].observed_t_iter == pytest.approx(0.5)
        assert (
            restored.history[0].to_json_dict() == tuner.history[0].to_json_dict()
        )

    def test_absent_for_untuned_checkpoints(self, tmp_path):
        import numpy as np

        from repro.checkpoint import load_tuner_state, save

        save(tmp_path, 3, {"x": np.zeros(2)})
        assert load_tuner_state(tmp_path, 3) is None

    def test_bad_format_rejected(self):
        layout, _, _ = small_setup()
        with pytest.raises(ValueError, match="tuner state format"):
            Tuner(layout=layout).load_state({"format": 99, "history": []})


class TestCommDrift:
    def test_drift_metric(self):
        a = AllReduceModel(a=1e-3, b=1e-9)
        assert comm_drift(a, a) == 0.0
        assert comm_drift(a, AllReduceModel(a=1e-2, b=1e-9)) == pytest.approx(9.0)
        assert comm_drift(a, AllReduceModel(a=1e-3, b=2e-9)) == pytest.approx(1.0)

    def test_replan_fires_exactly_at_threshold(self):
        """Below/at the (α, β) delta threshold nothing happens; past it the
        policy reruns under the fresh model."""
        layout, costs, ar = small_setup()
        plan = build_plan(layout, costs, ar, policy="mg_wfbp", hw=MEASURED_HW,
                          n_scan_stages=6)
        # drift exactly == threshold: keeps the plan (strict inequality)
        at = AllReduceModel(a=ar.a * 1.25, b=ar.b)
        same, replanned = replan_if_comm_drifted(plan, at, threshold=0.25)
        assert not replanned and same is plan
        # just past it: re-plans
        past = AllReduceModel(a=ar.a * 1.2501, b=ar.b)
        new_plan, replanned = replan_if_comm_drifted(plan, past, threshold=0.25)
        assert replanned
        assert new_plan.ar_model == past
        assert new_plan.provenance["replanned_from_comm"] == ar.name
        assert float(new_plan.provenance["comm_drift"]) == pytest.approx(
            0.2501, rel=1e-3
        )
        # costs and layout are untouched — only the wire model moved
        assert new_plan.costs == plan.costs

    def test_alpha_x10_schedule_actually_changes(self):
        """α×10 congestion makes merging strictly more attractive: the
        re-planned schedule has fewer groups."""
        layout, costs, ar = small_setup(seed_skew=True)
        plan = build_plan(layout, costs, ar, policy="mg_wfbp", hw=MEASURED_HW,
                          n_scan_stages=6)
        congested = AllReduceModel(a=ar.a * 10, b=ar.b, name="congested")
        new_plan, replanned = replan_if_comm_drifted(plan, congested, threshold=0.5)
        assert replanned
        assert len(new_plan.schedule.groups) <= len(plan.schedule.groups)

    def test_measured_comm_ewma_update(self):
        base = MeasuredComm(sizes_bytes=(100, 200), times_s=(1.0, 2.0))
        up = base.update([200, 400], [4.0, 8.0], weight=0.5)
        assert up.sizes_bytes == (100, 200, 400)
        assert up.times_s == (1.0, 3.0, 8.0)  # 200: (2+4)/2; 400: fresh
        with pytest.raises(ValueError, match="EWMA weight"):
            base.update([100], [1.0], weight=0.0)

    def test_refitter_alpha_x10_fires_within_one_check(self):
        """Acceptance: an injected α×10 perturbation triggers a re-fit on
        the FIRST slim-sweep check after the event — i.e. within
        --comm-refit-every steps of the congestion starting."""
        model = AllReduceModel(a=5e-5, b=1e-9)
        base = MeasuredComm(
            sizes_bytes=DEFAULT_COMM_SWEEP,
            times_s=tuple(model(s) for s in DEFAULT_COMM_SWEEP),
        )
        ref = CommRefitter(base=base, threshold=0.5, weight=0.5)
        # healthy probes: no drift, no refit
        _, drift, drifted = ref.check(lambda n: model(n))
        assert not drifted and drift < 0.05
        # congestion event: α jumps ×10
        congested = AllReduceModel(a=model.a * 10, b=model.b)
        fit, drift, drifted = ref.check(lambda n: congested(n))
        assert drifted and ref.refits == 1
        assert drift > 0.5
        # the EWMA'd fit moved toward the congested α (≥2x the baseline)
        assert fit.a > 2 * model.a
        # after the refit the reference follows the new regime: steady
        # congestion does not keep re-firing
        _, _, drifted2 = ref.check(lambda n: congested(n))
        assert ref.checks == 3

    def test_refitter_state_round_trip(self, tmp_path):
        model = AllReduceModel(a=5e-5, b=1e-9)
        base = MeasuredComm(
            sizes_bytes=SLIM_COMM_SWEEP,
            times_s=tuple(model(s) for s in SLIM_COMM_SWEEP),
        )
        ref = CommRefitter(base=base, threshold=0.4, weight=0.25)
        ref.check(lambda n: model(n))
        blob = json.dumps(ref.state_dict())
        clone = CommRefitter.from_state_dict(json.loads(blob))
        assert clone.checks == 1 and clone.threshold == 0.4
        assert clone.base.times_s == ref.base.times_s
        assert clone.reference.a == pytest.approx(ref.reference.a)


class TestUnitProbes:
    """Per-unit segment probes: genuinely non-uniform measured drift —
    the thing the whole-step uniform rescale can never produce."""

    @pytest.fixture(scope="class")
    def profile_and_costs(self):
        import dataclasses

        import jax
        import jax.numpy as jnp

        from repro.configs import get_reduced
        from repro.core.bucketing import stacked_lm_layout
        from repro.core.cost_model import TPU_V5E
        from repro.core.trainer import lm_unit_costs
        from repro.launch.specs import param_specs
        from repro.models.transformer import init_params
        from repro.runtime.timeline import probe_unit_times

        cfg = dataclasses.replace(
            get_reduced("tinyllama-1.1b"), param_dtype=jnp.float32
        )
        shapes = param_specs(cfg)
        layout = stacked_lm_layout(shapes, cfg.n_stages)
        analytic = lm_unit_costs(cfg, shapes, tokens_per_device=64)
        params = init_params(jax.random.PRNGKey(0), cfg)
        key = jax.random.PRNGKey(1)
        batch = {
            "targets": jax.random.randint(key, (2, 32), 0, cfg.vocab),
            "tokens": jax.random.randint(key, (2, 32), 0, cfg.vocab),
        }
        profile = probe_unit_times(cfg, params, batch, layout)
        return profile, analytic, layout

    def test_covers_every_unit(self, profile_and_costs):
        profile, _, layout = profile_and_costs
        assert set(profile.unit_seconds) == {u.name for u in layout.units}
        assert all(t > 0 for t in profile.unit_seconds.values())

    def test_nonuniform_across_units(self, profile_and_costs):
        """Acceptance: the measured/analytic ratio differs across units —
        proof the cost vector is NOT a uniform whole-step rescale."""
        profile, analytic, _ = profile_and_costs
        from repro.core.cost_model import TPU_V5E

        ratios = profile.ratios(analytic, TPU_V5E)
        assert len(set(f"{r:.3e}" for r in ratios.values())) > 1
        assert profile.nonuniformity(analytic, TPU_V5E) > 1.05

    def test_feeds_measured_costs(self, profile_and_costs):
        profile, analytic, _ = profile_and_costs
        from repro.core.cost_model import TPU_V5E

        measured = MeasuredCosts.from_segment_times(
            analytic, TPU_V5E, profile.unit_seconds, name="probe_segments"
        )
        for c, base in zip(measured.layer_costs(), analytic):
            assert c.t_b(MEASURED_HW) == pytest.approx(
                profile.unit_seconds[base.name]
            )
            assert c.grad_bytes == base.grad_bytes  # payloads never move


class TestStepTimer:
    def test_skips_compile_steps_and_medians(self):
        from repro.runtime import StepTimer

        t = StepTimer(window=10, skip_first=2)
        assert t.median() is None
        for dt in (9.0, 9.0, 1.0, 2.0, 3.0):  # two compile steps discarded
            t.observe(dt)
        assert len(t) == 3
        assert t.median() == pytest.approx(2.0)
        t.skip(1)
        t.observe(50.0)  # recompile after re-plan: discarded
        assert t.median() == pytest.approx(2.0)
        t.reset()
        assert t.median() is None


EF_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_mesh
from repro.configs import get_reduced
from repro.core.comm_model import AllReduceModel
from repro.core.sync import SyncConfig
from repro.core.trainer import MGWFBPEngine
from repro.launch.specs import param_specs
from repro.models.transformer import init_params
from repro.optim import make_optimizer
from repro.runtime import RunState
from repro.checkpoint import save, restore
import dataclasses, sys, tempfile

cfg = dataclasses.replace(get_reduced("tinyllama-1.1b"), param_dtype=jnp.float32)
n_dev = jax.device_count()
mesh = make_mesh((n_dev, 1), ("data", "model"))
eng = MGWFBPEngine.build(
    cfg, param_specs(cfg), dp_axes=("data",),
    ar_model=AllReduceModel(a=5e-5, b=1e-9), tokens_per_device=64,
    sync_config=SyncConfig(compression="bf16_ef", fuse="arena"),
)
assert eng.stateful
opt = make_optimizer("sgd")
step = eng.make_train_step(opt, mesh, lr=1e-2)
params = init_params(jax.random.PRNGKey(0), cfg)
residual = eng.init_residual(params, mesh)
assert residual is not None
# per-device state: every leaf carries a leading DP axis of the world size
assert all(x.shape[0] == n_dev for x in jax.tree.leaves(residual))
opt_state = opt.init(params)
key = jax.random.PRNGKey(1)
batch = {
    "targets": jax.random.randint(key, (4, 32), 0, cfg.vocab),
    "tokens": jax.random.randint(key, (4, 32), 0, cfg.vocab),
}
with jax.set_mesh(mesh):
    p1, o1, r1, m1 = step(params, opt_state, residual, batch)
    p2, o2, r2, m2 = step(p1, o1, r1, batch)
res_norm = float(sum(jnp.sum(jnp.abs(x)) for x in jax.tree.leaves(r2)))
# distinct data shards -> distinct local quantization errors: the
# per-device slices must NOT be copies of device 0's residual
big = max(jax.tree.leaves(r2), key=lambda x: x.size)
slice_diff = float(max(
    jnp.max(jnp.abs(big[i] - big[0])) for i in range(1, n_dev)
)) if n_dev > 1 else -1.0

# checkpoint round-trip with the residual in the tree
state = RunState(step=2, params=p2, opt_state=o2, residual=r2)
d = tempfile.mkdtemp()
save(d, 2, state.checkpoint_tree())
fresh = RunState(
    step=0,
    params=init_params(jax.random.PRNGKey(0), cfg),
    opt_state=opt.init(params),
    residual=eng.init_residual(params, mesh),
)
tree, _ = restore(d, 2, fresh.checkpoint_tree())
diff = max(
    float(jnp.max(jnp.abs(a - b)))
    for a, b in zip(jax.tree.leaves(tree["residual"]), jax.tree.leaves(r2))
)
print(json.dumps({
    "n_dev": n_dev,
    "residual_norm": res_norm,
    "slice_diff": slice_diff,
    "restore_diff": diff,
    "loss1": float(m1["loss"]),
    "loss2": float(m2["loss"]),
}))
"""


def test_bf16_ef_residual_threads_and_checkpoints():
    """Satellite: compression='bf16_ef' threads the error-feedback residual
    through the engine's train step on a 4-device DP mesh, the residual is
    genuinely per-device (leading DP axis, distinct slices — not device
    0's copy), and the full per-device state round-trips through the
    checkpoint tree."""
    out = subprocess.run(
        [sys.executable, "-c", EF_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env=SUBPROC_ENV, cwd=REPO_ROOT,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["n_dev"] == 4
    assert rec["residual_norm"] > 0  # the cast error is actually carried
    assert rec["slice_diff"] > 0  # per-device state, not a broadcast
    assert rec["restore_diff"] == 0.0
    assert rec["loss2"] <= rec["loss1"] + 1.0  # training is sane


def test_benchmarks_only_rejects_unknown_tables():
    """Satellite: a typo'd --only exits non-zero and names the known
    tables instead of silently running nothing."""
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--only", "plannin_sweep"],
        capture_output=True, text=True, timeout=300,
        env=SUBPROC_ENV, cwd=REPO_ROOT,
    )
    assert out.returncode != 0
    err = out.stderr + out.stdout
    assert "plannin_sweep" in err  # names the offender
    assert "planning_sweep" in err and "tuner" in err  # lists known tables

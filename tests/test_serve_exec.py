"""Serve execution: the ServePlan driven inside ``ServingEngine.step`` —
sharded-vs-unsharded decode numerics, the engine-step lowering invariant
(one fused collective per scheduled serve group), measured serve fabrics
(op-specific fits round-tripping through ``MeasuredFabric``), and the
reviewable ``ServePlan.describe()`` output."""

import dataclasses
import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _env import REPO_ROOT, SUBPROC_ENV

from repro.launch.mesh import make_mesh
from repro.configs import get_reduced
from repro.core.comm_model import AllReduceModel, fit_affine
from repro.fabric import MeasuredFabric
from repro.launch.specs import param_specs
from repro.models.transformer import init_caches, init_params
from repro.planning import (
    build_serve_plan,
    measure_serve_comm,
    serve_fabric_fits,
)
from repro.serving import (
    ServeTimer,
    serving_cache_pspecs,
    serving_param_pspecs,
    stack_fresh_rows,
    write_fresh_rows,
)


def _reduced_cfg(arch="tinyllama-1.1b"):
    return dataclasses.replace(get_reduced(arch), param_dtype=jnp.float32)


class TestFreshRows:
    def test_stack_write_round_trip(self):
        """write(stack(caches)) is the identity: the wire payload covers
        exactly the rows it is spliced back into."""
        cfg = _reduced_cfg()
        caches = init_caches(cfg, batch=2, max_seq=16, dtype=jnp.float32)
        # make the cache contents distinctive
        caches = jax.tree.map(
            lambda x: x + jnp.arange(x.size, dtype=x.dtype).reshape(x.shape)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            caches,
        )
        pos = jnp.asarray(3, jnp.int32)
        stacked = stack_fresh_rows(cfg, caches, pos)
        att = cfg.attention
        assert stacked.shape == (cfg.n_stages,
                                 2 * 2 * att.n_kv_heads * att.head_dim)
        rt = write_fresh_rows(cfg, caches, stacked, pos)
        for a, b in zip(jax.tree.leaves(caches), jax.tree.leaves(rt)):
            assert jnp.array_equal(a, b)

    def test_recurrent_arch_has_no_payload(self):
        cfg = _reduced_cfg("rwkv6-7b")
        caches = init_caches(cfg, batch=2, max_seq=16, dtype=jnp.float32)
        assert stack_fresh_rows(cfg, caches, jnp.asarray(0, jnp.int32)) is None


class TestServeTimer:
    def test_skip_then_median(self):
        t = ServeTimer(skip_first=2)
        for dt in (9.0, 9.0, 1.0, 2.0, 3.0):
            t.observe(dt)
        assert len(t) == 3
        assert t.median() == 2.0
        assert t.group_times == ()
        t.group_times = (1e-4, 2e-4)
        assert t.group_times == (1e-4, 2e-4)


class TestDescribe:
    def test_describe_includes_group_times_and_bytes(self):
        """Satellite fix: --plan-out artifacts are reviewable without
        loading JSON — per-group predicted time + wire bytes."""
        cfg = _reduced_cfg()
        plan = build_serve_plan(cfg, param_specs(cfg), "tpu_v5e",
                                {"model": 4}, batch_rows=2, policy="wfbp")
        text = plan.describe()
        lines = text.splitlines()
        assert len(lines) == 1 + len(plan.schedule.groups)
        for g, line in zip(plan.group_summaries(), lines[1:]):
            lo, hi = g["stages"]
            assert f"group[{lo}..{hi}]" in line
            assert f"wire={g['nbytes']}B" in line
            assert "t_pred=" in line
        # summaries price each group at the plan's affine model
        for g in plan.group_summaries():
            assert g["t_pred_s"] == pytest.approx(plan.model(g["nbytes"]))


class TestMeasuredServeFabric:
    def test_fit_round_trip_through_measured_fabric(self):
        """Acceptance: an 'all_gather@model' override recovered from
        synthetic timings prices the plan with the injected constants."""
        true = AllReduceModel(a=3e-5, b=2e-9)
        sizes = tuple(4096 * 8**i for i in range(5))
        fit = fit_affine(sizes, tuple(true(s) for s in sizes),
                         name="all_gather@model")
        fab = MeasuredFabric(models={"all_gather@model": fit},
                             name="measured_serve")
        got = fab.cost("all_gather", {"model": 8})
        assert got.a == pytest.approx(true.a, rel=1e-6)
        assert got.b == pytest.approx(true.b, rel=1e-6)
        cfg = _reduced_cfg()
        plan = build_serve_plan(cfg, param_specs(cfg), fab, {"model": 8},
                                batch_rows=2)
        assert plan.fabric == "measured_serve"
        assert plan.model.a == pytest.approx(true.a, rel=1e-6)
        assert plan.model.b == pytest.approx(true.b, rel=1e-6)

    def test_with_fits_overrides(self):
        base = MeasuredFabric(models={"model": AllReduceModel(a=1e-5, b=1e-9)})
        override = AllReduceModel(a=9e-6, b=3e-10)
        fab = base.with_fits({"all_gather@model": override})
        assert fab.cost("all_gather", {"model": 8}).a == override.a
        # base untouched (frozen dataclass semantics)
        assert "all_gather@model" not in base.models

    def test_measure_serve_comm_runs_on_trivial_mesh(self):
        """The timing path itself needs no virtual devices: a 1-wide
        model axis still times the jitted collective."""
        mesh = make_mesh((1,), ("model",))
        mc = measure_serve_comm(mesh, "all_gather", ("model",),
                                sizes_bytes=(4096, 65536), repeats=1)
        assert mc.sizes_bytes == (4096, 65536)
        assert all(t > 0 and np.isfinite(t) for t in mc.times_s)
        fit = mc.fit()
        assert np.isfinite(fit.a) and np.isfinite(fit.b)
        fits = serve_fabric_fits(mesh, ops=("all_gather",),
                                 sizes_bytes=(4096, 65536), repeats=1)
        assert set(fits) == {"all_gather@model"}

    def test_measure_serve_comm_rejects_multi_axis(self):
        mesh = make_mesh((1,), ("model",))
        with pytest.raises(ValueError, match="one axis"):
            measure_serve_comm(mesh, "all_gather", ("model", "data"))


class TestAtRestLayout:
    def test_param_pspecs_follow_megatron_dims(self):
        cfg = _reduced_cfg()
        specs = serving_param_pspecs(param_specs(cfg))
        stages = specs["stages"]["attn_0"]
        # stacked stage leaves: (n_stages, in, out)
        assert tuple(stages["attn"]["wq"]) == (None, None, "model")
        assert tuple(stages["attn"]["wo"]) == (None, "model", None)
        assert tuple(stages["mlp"]["w_gate"]) == (None, None, "model")
        assert tuple(stages["mlp"]["w_down"]) == (None, "model", None)
        assert tuple(specs["embed"]) == ()
        assert tuple(specs["final_norm"]["scale"]) == ()

    def test_cache_pspecs_shard_head_dim(self):
        cfg = _reduced_cfg()
        caches = init_caches(cfg, batch=2, max_seq=16, dtype=jnp.float32)
        specs = serving_cache_pspecs(cfg, caches)
        k_spec, v_spec, kpos_spec = specs["stages"]["attn_0"]
        assert tuple(k_spec) == (None, None, None, None, "model")
        assert tuple(v_spec) == (None, None, None, None, "model")
        assert tuple(kpos_spec) == ()


SHARDED_EXEC_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses, json
    import jax, jax.numpy as jnp, numpy as np

    from repro.launch.mesh import make_mesh
    from repro.configs import get_reduced
    from repro.core.profiler import parse_collectives
    from repro.launch.specs import param_specs
    from repro.models.transformer import init_caches, init_params
    from repro.planning import build_serve_plan
    from repro.serving import Request, ServingEngine, shard_serving_state

    mesh = make_mesh((4,), ("model",))
    out = {"cells": []}

    cfg = dataclasses.replace(get_reduced("tinyllama-1.1b"),
                              param_dtype=jnp.float32)
    shapes = param_specs(cfg)
    params = init_params(jax.random.PRNGKey(0), cfg)

    def run(mesh_arg, policy, fabric):
        plan = build_serve_plan(cfg, shapes, fabric, {"model": 4},
                                batch_rows=2, policy=policy)
        eng = ServingEngine(cfg, params, slots=2, max_seq=20, plan=plan,
                            mesh=mesh_arg)
        rng = np.random.default_rng(0)
        for rid in range(3):  # 3 requests on 2 slots: slot reuse rides along
            eng.submit(Request(
                rid=rid,
                prompt=rng.integers(0, cfg.vocab, size=8, dtype=np.int32),
                max_new_tokens=6,
            ))
        done = eng.run_to_completion()
        return {r.rid: r.generated for r in done}, eng, plan

    base, _, _ = run(None, "mg_wfbp", "gpu_nccl")
    # the fabrics/policies pick different merge sets; every one must pin
    # exactly one fused collective per group INSIDE the engine's one
    # jitted step, donate its DecodeState buffers, decode token-for-token
    # identically to the unsharded engine, and never retrace the decode
    # executable across joins, leaves, and slot reuse
    # donation shows as tf.aliasing_output (single-device) or
    # jax.buffer_donor (sharded args) in the lowered StableHLO
    def donated(text):
        return "tf.aliasing_output" in text or "jax.buffer_donor" in text

    for policy, fabric in (("mg_wfbp", "gpu_nccl"), ("wfbp", "gpu_nccl"),
                           ("synceasgd", "tpu_v5e")):
        toks, eng, plan = run(mesh, policy, fabric)
        text = eng._step_fn.lower(eng.params, eng._state).as_text()
        stats = parse_collectives(text)
        try:  # a decode step moves no weights or state between devices
            with jax.transfer_guard("disallow"):
                eng._step_fn(eng.params, eng._state)
            no_transfer = True
        except Exception as e:
            no_transfer = repr(e)[:300]
        out["cells"].append({
            "policy": policy, "fabric": fabric, "op": plan.op,
            "n_groups": len(plan.schedule.groups),
            "gather_ops": stats.counts.get("all-gather", 0),
            "total_collectives": stats.total_ops,
            "tokens_match": toks == base,
            "donated": donated(text),
            "decode_execs": eng.compile_stats()["decode"],
            "no_transfer": no_transfer,
        })

    # MoE: the plan schedules the expert all-to-all; same invariant
    moe_cfg = dataclasses.replace(get_reduced("mixtral-8x7b"),
                                  param_dtype=jnp.float32)
    moe_params = init_params(jax.random.PRNGKey(0), moe_cfg)
    moe_plan = build_serve_plan(moe_cfg, param_specs(moe_cfg), "tpu_v5e",
                                {"model": 4}, batch_rows=2, policy="wfbp")
    eng = ServingEngine(moe_cfg, moe_params, slots=2, max_seq=16,
                        plan=moe_plan, mesh=mesh)
    text = eng._step_fn.lower(eng.params, eng._state).as_text()
    stats = parse_collectives(text)
    out["moe"] = {
        "op": moe_plan.op,
        "n_groups": len(moe_plan.schedule.groups),
        "a2a_ops": stats.counts.get("all-to-all", 0),
        "total_collectives": stats.total_ops,
        "donated": donated(text),
    }

    # at-rest layout: sharded leaves really live in 1/N-size shards
    sp, sc = shard_serving_state(
        params, init_caches(cfg, batch=2, max_seq=20, dtype=jnp.float32),
        cfg, mesh,
    )
    wq = sp["stages"]["attn_0"]["attn"]["wq"]
    shard = wq.sharding.shard_shape(wq.shape)
    out["wq_shard_fraction"] = (np.prod(shard) / np.prod(wq.shape)).item()
    print(json.dumps(out))
""")


def test_engine_step_lowers_one_collective_per_group():
    """Acceptance: the engine's ONE jitted step on a virtual TP mesh
    lowers to exactly one fused collective per ServePlan group, donates
    its ``DecodeState`` buffers (``tf.aliasing_output``/``jax.buffer_donor``
    in the lowered text — the cache arena is updated in place), compiles
    exactly one
    decode executable across joins/leaves/slot reuse, moves no weights or
    state between devices in a step, and the sharded engine decodes
    token-for-token what the unsharded engine decodes."""
    out = subprocess.run(
        [sys.executable, "-c", SHARDED_EXEC_SCRIPT],
        capture_output=True, text=True, timeout=900,
        env=SUBPROC_ENV, cwd=REPO_ROOT,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    by = {(c["policy"], c["fabric"]): c for c in rec["cells"]}
    # different merge sets from the same cost vector across the cells
    assert by[("wfbp", "gpu_nccl")]["n_groups"] > by[("mg_wfbp", "gpu_nccl")]["n_groups"]
    for c in rec["cells"]:
        assert c["op"] == "all_gather", c
        assert c["gather_ops"] == c["n_groups"], c
        assert c["total_collectives"] == c["n_groups"], c  # nothing extra
        assert c["tokens_match"], c
        assert c["donated"], c  # the DecodeState buffers alias outputs
        assert c["decode_execs"] == 1, c  # zero steady-state retraces
        assert c["no_transfer"] is True, c  # weights placed once, not per step
    moe = rec["moe"]
    assert moe["op"] == "all_to_all"
    assert moe["a2a_ops"] == moe["n_groups"]
    assert moe["total_collectives"] == moe["n_groups"]
    assert moe["donated"]
    # at-rest Megatron layout really shards the projection weights
    assert rec["wq_shard_fraction"] == pytest.approx(0.25)


class TestStepFixedModel:
    """The honest compute+dispatch cost model: ``t_step_fixed`` rides the
    plan, survives JSON, and folds into ``predicted_step_time``."""

    def _plan(self):
        cfg = _reduced_cfg()
        return build_serve_plan(cfg, param_specs(cfg), "tpu_v5e",
                                {"model": 4}, batch_rows=2)

    def test_with_step_fixed_and_prediction(self):
        plan = self._plan()
        assert plan.t_step_fixed == 0.0
        assert plan.predicted_step_time() == plan.schedule.result.t_iter
        cal = plan.with_step_fixed(1.5e-3)
        assert cal.t_step_fixed == 1.5e-3
        assert cal.predicted_step_time() == pytest.approx(
            plan.schedule.result.t_iter + 1.5e-3)
        assert cal.provenance["t_step_fixed_source"] == "probe"
        # the original plan is untouched (frozen-value semantics)
        assert plan.t_step_fixed == 0.0

    def test_json_round_trip_and_legacy_load(self):
        from repro.planning import ServePlan

        cal = self._plan().with_step_fixed(2e-4)
        rt = ServePlan.from_json_dict(json.loads(cal.to_json()))
        assert rt.t_step_fixed == pytest.approx(2e-4)
        assert rt.predicted_step_time() == pytest.approx(cal.predicted_step_time())
        # artifacts written before the fixed-term model load as 0.0
        d = json.loads(self._plan().to_json())
        d.pop("t_step_fixed")
        legacy = ServePlan.from_json_dict(d)
        assert legacy.t_step_fixed == 0.0

    def test_describe_and_group_summaries_carry_fixed(self):
        from repro.planning import group_comparison_lines

        cal = self._plan().with_step_fixed(1e-3)
        assert "step=fixed" in cal.describe()
        for g in cal.group_summaries():
            assert g["t_fixed_s"] == pytest.approx(1e-3)
        lines = group_comparison_lines(
            cal, tuple(0.0 for _ in cal.schedule.groups))
        assert lines[0].startswith("step: fixed=")
        assert len(lines) == 1 + len(cal.schedule.groups)
        # an uncalibrated plan keeps the legacy table shape
        plain = group_comparison_lines(
            self._plan(), tuple(0.0 for _ in self._plan().schedule.groups))
        assert len(plain) == len(self._plan().schedule.groups)


class TestServePlanCapacityModel:
    """Direct contracts for ``predicted_completion_s`` /
    ``capacity_tok_per_s`` — the terms fleet admission and the what-if
    simulator price ETAs and scale decisions with."""

    def _plan(self):
        cfg = _reduced_cfg()
        return build_serve_plan(cfg, param_specs(cfg), "tpu_v5e",
                                {"model": 4}, batch_rows=2)

    def test_completion_scales_linearly_in_tokens(self):
        plan = self._plan()
        step = plan.predicted_step_time()
        assert plan.predicted_completion_s(1) == pytest.approx(step)
        assert plan.predicted_completion_s(17) == pytest.approx(17 * step)

    def test_completion_zero_and_negative_tokens_clamp_to_zero(self):
        plan = self._plan()
        assert plan.predicted_completion_s(0) == 0.0
        assert plan.predicted_completion_s(-5) == 0.0

    def test_capacity_is_rows_per_step(self):
        plan = self._plan()
        step = plan.predicted_step_time()
        assert plan.capacity_tok_per_s(1) == pytest.approx(1.0 / step)
        assert plan.capacity_tok_per_s(8) == pytest.approx(8.0 / step)

    def test_capacity_zero_rows_is_zero_not_none(self):
        """An idle replica has zero capacity — a priced answer, not a
        missing one (None is reserved for un-evaluated schedules)."""
        plan = self._plan()
        assert plan.capacity_tok_per_s(0) == 0.0

    def test_unevaluated_schedule_prices_nothing(self):
        """Gate-empty plan: no evaluated timeline => both terms are None
        (admission must refuse to price, not price garbage)."""
        plan = self._plan()
        gutted = dataclasses.replace(
            plan, schedule=dataclasses.replace(plan.schedule, result=None))
        assert gutted.predicted_step_time() is None
        assert gutted.predicted_completion_s(4) is None
        assert gutted.capacity_tok_per_s(4) is None

    def test_step_fixed_feeds_both_terms(self):
        """The calibrated fixed term moves completion and capacity
        together — they stay mutually consistent views of one step."""
        cal = self._plan().with_step_fixed(1e-2)
        step = cal.predicted_step_time()
        assert cal.predicted_completion_s(3) == pytest.approx(3 * step)
        assert cal.capacity_tok_per_s(5) == pytest.approx(5.0 / step)

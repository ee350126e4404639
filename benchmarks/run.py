"""Benchmark harness entry point: one function per paper table/figure plus
the roofline summary assembled from dry-run records.

Prints ``name,us_per_call,derived`` CSV lines per the harness contract:
each table reports its wall time and emits its rows beneath it.

``--only planning_sweep,wire_layout`` restricts to named tables (CI runs
exactly that pair in smoke mode and uploads the BENCH_*.json artifacts).
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

# The wire-layout and serve-exec sweeps run shard_map over 8 virtual
# devices; flags must land before jax initializes its backend.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, "src")

from repro.launch.mesh import ensure_virtual_devices

ensure_virtual_devices(8)


def write_bench(name: str, record, rows: list[str], gate=None) -> pathlib.Path:
    """Publish one suite's record: the shared stash-record-and-compare
    tail every table used to hand-roll.

    Writes ``benchmarks/results/BENCH_<name>.json`` and appends the
    ``wrote ...`` row.  When ``gate`` is given and ``BENCH_BASELINE_DIR``
    points at a stash of previously-committed records (the CI smoke jobs
    stash the checked-in JSON there before re-running a suite), the gate
    runs as ``gate(record, baseline_record)`` BEFORE the new record is
    written — a regressed run raises and never publishes, so the
    committed trajectory only ever moves forward.

    Records are serialized with sorted keys so a committed BENCH file
    round-trips byte-identically through ``json.loads`` + this writer —
    the schema test (tests/test_bench_records.py) pins that."""
    out = pathlib.Path(__file__).parent / "results" / f"BENCH_{name}.json"
    out.parent.mkdir(parents=True, exist_ok=True)
    base_dir = os.environ.get("BENCH_BASELINE_DIR")
    if gate is not None and base_dir:
        base_path = pathlib.Path(base_dir) / out.name
        if base_path.exists():
            gate(record, json.loads(base_path.read_text()))
            rows.append(f"gate vs {base_path}: ok")
        else:
            rows.append(f"gate skipped: no baseline at {base_path}")
    out.write_text(json.dumps(record, indent=1, sort_keys=True) + "\n")
    rows.append(f"wrote {out}")
    return out


def roofline_summary() -> list[str]:
    """Per-(arch x shape x mesh) roofline terms from the dry-run records."""
    rows = ["table=roofline_summary"]
    results = pathlib.Path(__file__).parent / "results" / "dryrun"
    if not results.exists():
        rows.append("no dry-run records yet; run python -m repro.launch.dryrun --all")
        return rows
    for f in sorted(results.glob("*.json")):
        rec = json.loads(f.read_text())
        t = rec.get("totals")
        mem = rec["memory"]["peak_per_device_gib"]
        if not t:
            rows.append(f"{rec['arch']},{rec['shape']},{rec['mesh']},mem_gib={mem},segments=skipped")
            continue
        rows.append(
            f"{rec['arch']},{rec['shape']},{rec['mesh']},mem_gib={mem},"
            f"compute_s={t['compute_term_s']:.4f},memory_s={t['memory_term_s']:.4f},"
            f"collective_s={t['collective_term_s']:.4f},dominant={t['dominant']},"
            f"useful_ratio={t['useful_flops_ratio']:.3f},"
            f"roofline_fraction={t['roofline_fraction']:.4f}"
        )
    return rows


def _arch_sweep_inputs(arch: str):
    """(layout, analytic costs, measured_3x costs, n_scan_stages) for one
    arch — the shared setup of the planning/tuner sweeps."""
    from repro.configs import get_config
    from repro.core.bucketing import stacked_lm_layout
    from repro.core.cost_model import TPU_V5E
    from repro.core.trainer import lm_unit_costs
    from repro.launch.specs import param_specs
    from repro.planning import MeasuredCosts

    cfg = get_config(arch)
    shapes = param_specs(cfg)
    layout = stacked_lm_layout(shapes, cfg.n_stages, model_shards=16)
    analytic = lm_unit_costs(cfg, shapes, tokens_per_device=8192, model_shards=16)
    # Skewed measured profile: compute 3x the analytic belief — the
    # regime where re-planning pays (comm hides behind backward).
    measured = MeasuredCosts.from_unit_times(
        analytic,
        [c.t_b(TPU_V5E) * 3.0 for c in analytic],
        [c.t_f(TPU_V5E) * 3.0 for c in analytic],
        name="measured_3x",
    )
    return layout, analytic, measured, cfg.n_stages


def planning_sweep() -> list[str]:
    """Sweep scheduler policies × cost sources through the ``Tuner`` —
    the same registry-wide argmin-t_iter search the ``--autotune`` train
    loop runs (the sweep is load-bearing, not a report); rows go to
    stdout and the full records to
    ``benchmarks/results/BENCH_planning.json`` so future PRs have a perf
    trajectory (t_iter, exposed comm, group count per policy)."""
    from repro.core import tpu_psum_model
    from repro.core.cost_model import TPU_V5E
    from repro.planning import MEASURED_HW, Tuner

    rows = ["table=planning_sweep"]
    records = []
    ar = tpu_psum_model({"pod": 2, "data": 16})
    for arch in ("tinyllama-1.1b", "mixtral-8x7b", "recurrentgemma-9b"):
        layout, analytic, measured, n_scan = _arch_sweep_inputs(arch)
        tuner = Tuner(layout=layout, n_scan_stages=n_scan)
        sources = {
            "analytic": (analytic, TPU_V5E),
            "measured_3x": (measured.layer_costs(), MEASURED_HW),
        }
        for src, (costs, hw) in sources.items():
            tuner.sweep(costs, ar, hw, cost_source=src, trigger="bench")
            rec = tuner.last_record
            for c in rec.candidates:
                records.append(
                    {
                        "arch": arch,
                        "policy": c.policy,
                        "cost_source": src,
                        "chosen": c.policy == rec.chosen,
                        "n_groups": c.n_groups,
                        "t_iter_s": c.predicted_t_iter,
                        "t_comm_exposed_s": c.t_comm_exposed,
                    }
                )
                rows.append(
                    f"{arch},{c.policy},{src},groups={c.n_groups},"
                    f"t_iter_ms={c.predicted_t_iter * 1e3:.3f},"
                    f"exposed_ms={c.t_comm_exposed * 1e3:.3f}"
                    + (",chosen" if c.policy == rec.chosen else "")
                )
    write_bench("planning", records, rows)
    return rows


def tuner() -> list[str]:
    """Closed-loop auto-tuner acceptance table -> BENCH_tuner.json.

    Three cells, matching the PR's acceptance criteria:

      * ``sweep``        — registry-wide search per arch on measured
        costs; records every candidate and pins chosen ≤ per_tensor
        (wfbp) and ≤ every other candidate;
      * ``unit_profile`` — real per-unit segment probes on a CPU-mesh
        reduced arch; records measured-vs-analytic ratios per unit and
        their non-uniformity (a uniform whole-step rescale would be 1.0);
      * ``comm_drift``   — injected α×10 congestion into the CommRefitter
        (EWMA slim-sweep re-fit) and the checks-to-refit count, plus the
        re-plan the fresh fit triggers.
    """
    import jax
    from repro.configs import get_reduced
    from repro.core import tpu_psum_model
    from repro.core.comm_model import AllReduceModel
    from repro.core.cost_model import TPU_V5E
    from repro.models.transformer import init_params
    from repro.planning import (
        DEFAULT_COMM_SWEEP,
        MEASURED_HW,
        CommRefitter,
        MeasuredComm,
        MeasuredCosts,
        Tuner,
        build_plan,
        replan_if_comm_drifted,
    )
    from repro.runtime.timeline import probe_unit_times

    rows = ["table=tuner"]
    record: dict = {"sweeps": [], "unit_profile": None, "comm_drift": None}

    # -- 1. registry-wide sweep: chosen plan beats every candidate --------
    ar = tpu_psum_model({"pod": 2, "data": 16})
    for arch in ("tinyllama-1.1b", "mixtral-8x7b"):
        layout, _, measured, n_scan = _arch_sweep_inputs(arch)
        tun = Tuner(layout=layout, n_scan_stages=n_scan)
        tun.sweep(
            measured.layer_costs(), ar, MEASURED_HW,
            cost_source="measured_3x", trigger="bench",
        )
        rec = tun.last_record
        by_policy = {c.policy: c for c in rec.candidates}
        assert all(
            rec.predicted_t_iter <= c.predicted_t_iter for c in rec.candidates
        ), rec
        assert rec.predicted_t_iter <= by_policy["wfbp"].predicted_t_iter
        record["sweeps"].append(rec.to_json_dict() | {"arch": arch})
        rows.append(
            f"sweep,{arch},chosen={rec.chosen},"
            f"t_iter_ms={rec.predicted_t_iter * 1e3:.3f},"
            f"vs_per_tensor_ms={by_policy['wfbp'].predicted_t_iter * 1e3:.3f}"
        )

    # -- 2. per-unit measured profile: non-uniform drift (CPU mesh) -------
    cfg = get_reduced("tinyllama-1.1b")
    import dataclasses as _dc
    import jax.numpy as jnp
    cfg = _dc.replace(cfg, param_dtype=jnp.float32)
    from repro.core.bucketing import stacked_lm_layout
    from repro.core.trainer import lm_unit_costs
    from repro.launch.specs import param_specs

    shapes = param_specs(cfg)
    layout = stacked_lm_layout(shapes, cfg.n_stages)
    analytic = lm_unit_costs(cfg, shapes, tokens_per_device=128)
    params = init_params(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(1)
    batch = {"targets": jax.random.randint(key, (2, 64), 0, cfg.vocab)}
    if cfg.input_mode == "embeds":
        batch["embeds"] = jax.random.normal(key, (2, 64, cfg.d_model))
    else:
        batch["tokens"] = jax.random.randint(key, (2, 64), 0, cfg.vocab)
    profile = probe_unit_times(cfg, params, batch, layout)
    ratios = profile.ratios(analytic, TPU_V5E)
    nonuni = profile.nonuniformity(analytic, TPU_V5E)
    record["unit_profile"] = {
        "arch": cfg.name,
        "unit_seconds": profile.unit_seconds,
        "measured_over_analytic": ratios,
        "nonuniformity": nonuni,
    }
    rows.append(f"unit_profile,{cfg.name},nonuniformity={nonuni:.2f},"
                f"units={len(profile.unit_seconds)}")

    # -- 3. injected α×10 congestion -> re-fit + re-plan ------------------
    base_model = AllReduceModel(a=5e-5, b=1e-9, name="baseline")
    base = MeasuredComm(
        sizes_bytes=DEFAULT_COMM_SWEEP,
        times_s=tuple(base_model(s) for s in DEFAULT_COMM_SWEEP),
        name="baseline",
    )
    refitter = CommRefitter(base=base, threshold=0.5, weight=0.5)
    comm_refit_every = 5  # drift checked every N train steps
    congested = AllReduceModel(a=base_model.a * 10.0, b=base_model.b, name="congested")
    checks = 0
    drifted = False
    while not drifted and checks < 10:
        _fit, drift, drifted = refitter.check(lambda n: congested(n))
        checks += 1
    # the re-plan the fresh fit triggers on a plan built at baseline α
    measured = MeasuredCosts.from_unit_times(
        analytic, [c.t_b(TPU_V5E) for c in analytic],
        [c.t_f(TPU_V5E) for c in analytic],
    )
    plan = build_plan(
        layout, measured.layer_costs(), base_model,
        policy="mg_wfbp", hw=MEASURED_HW, n_scan_stages=cfg.n_stages,
    )
    new_plan, replanned = replan_if_comm_drifted(plan, refitter.reference, threshold=0.5)
    record["comm_drift"] = {
        "alpha_injection": 10.0,
        "comm_refit_every": comm_refit_every,
        "checks_to_refit": checks,
        "steps_to_refit": checks * comm_refit_every,
        "drift_at_refit": drift,
        "replanned": replanned,
        "groups_before": len(plan.schedule.groups),
        "groups_after": len(new_plan.schedule.groups),
    }
    assert drifted and checks == 1, (checks, drifted)  # fires on the first check
    assert replanned, "α×10 must trigger a comm re-plan"
    rows.append(f"comm_drift,alpha_x10,checks_to_refit={checks},"
                f"steps_to_refit={checks * comm_refit_every},replanned={replanned}")

    write_bench("tuner", record, rows)
    return rows


def fabric_sweep() -> list[str]:
    """One registry, all backends: t_iter per fabric preset × arch, the
    plan each fabric's (α, β) selects, and the decode-side serve plan —
    written to ``benchmarks/results/BENCH_fabric.json``.

    The sweep is load-bearing acceptance, not a report: every preset must
    yield a valid plan (schedule covers all units, evaluated timeline),
    and wherever a preset's startup cost is positive the merge gain of
    Eq. 10 must be positive too (the gain IS ``a``) — asserted per cell,
    and re-checked by the ``fabric-smoke`` CI job.
    """
    from repro.configs import get_reduced
    from repro.core.cost_model import TPU_V5E
    from repro.fabric import available_fabrics, get_fabric
    from repro.launch.specs import param_specs
    from repro.planning import Tuner, build_serve_plan

    rows = ["table=fabric_sweep"]
    records = []
    axis_sizes = {"pod": 2, "data": 16}
    serve_axis_sizes = {"model": 16}
    for arch in ("tinyllama-1.1b", "mixtral-8x7b"):
        layout, analytic, _, n_scan = _arch_sweep_inputs(arch)
        serve_cfg = get_reduced(arch)
        for preset in available_fabrics():
            fab = get_fabric(preset)
            ar = fab.cost("all_reduce", axis_sizes)
            tuner = Tuner(layout=layout, n_scan_stages=n_scan)
            plan = tuner.sweep_fabric(
                analytic, fab, axis_sizes, TPU_V5E,
                cost_source="analytic", trigger="fabric_bench",
            )
            rec_t = tuner.last_record
            res = plan.schedule.result
            assert res is not None and res.t_iter > 0, (preset, arch)
            assert plan.schedule.groups[-1][1] == layout.num_layers, (preset, arch)
            merge_gain = ar.merged_gain(1 << 20, 1 << 20)
            if ar.a > 0:
                assert merge_gain > 0, (preset, ar)  # Eq. 10: the gain IS a
            serve = build_serve_plan(
                serve_cfg, param_specs(serve_cfg), fab, serve_axis_sizes,
                batch_rows=16,
            )
            records.append(
                {
                    "arch": arch,
                    "fabric": preset,
                    "a": ar.a,
                    "b": ar.b,
                    "merge_gain_s": merge_gain,
                    "chosen": rec_t.chosen,
                    "comm_source": rec_t.comm_source,
                    "n_groups": len(plan.schedule.groups),
                    "t_iter_s": res.t_iter,
                    "t_comm_exposed_s": res.t_comm_exposed,
                    "serve_op": serve.op,
                    "serve_groups": len(serve.schedule.groups),
                    "serve_t_step_s": serve.schedule.result.t_iter,
                }
            )
            rows.append(
                f"{arch},{preset},a={ar.a:.2e},b={ar.b:.2e},"
                f"chosen={rec_t.chosen},groups={len(plan.schedule.groups)},"
                f"t_iter_ms={res.t_iter * 1e3:.3f},"
                f"serve={serve.op}/{len(serve.schedule.groups)}g"
            )
    write_bench("fabric", records, rows)
    return rows


def serve_exec() -> list[str]:
    """Executed-ServePlan acceptance -> ``BENCH_serve_exec.json``.

    Runs the plan-driven sharded decode (``serving.sharded``) on a
    virtual TP mesh and closes the serve measurement loop:

      * sharded-vs-unsharded token equality (the same requests decoded
        both ways must match token-for-token);
      * predicted (``ServePlan.predicted_step_time()``: probed fixed
        compute+dispatch term + wire timeline) vs observed (``ServeTimer``
        median) step time, gated at ``ratio_budget`` = 3x — the honest
        cost model must stay honest;
      * per-group measured collective seconds at the plan's exact wire
        payloads — the merged schedule's total must not exceed the
        per-stage (wfbp) baseline's on the same mesh (Eq. 10 executed,
        not just priced);
      * op-specific measured fits (``'all_gather@model'``) from real
        decode-gather sweeps, served back through a ``MeasuredFabric``.
    """
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.mesh import make_mesh
    from repro.configs import get_reduced
    from repro.fabric import MeasuredFabric
    from repro.launch.specs import param_specs
    from repro.models.transformer import init_params
    from repro.planning import build_serve_plan, serve_fabric_fits, time_serve_groups
    from repro.serving import Request, ServeTimer, ServingEngine

    rows = ["table=serve_exec"]
    tp = min(8, jax.device_count())
    mesh = make_mesh((tp,), ("model",))
    cfg = _dc.replace(get_reduced("tinyllama-1.1b"), param_dtype=jnp.float32)
    shapes = param_specs(cfg)
    slots, prompt_len, n_tokens = 2, 8, 6
    params = init_params(jax.random.PRNGKey(0), cfg)
    # fp32 engine caches: price the wire at the bytes the step ships
    wire_bytes = {"cache_dtype_bytes": 4, "act_dtype_bytes": 4}
    merged = build_serve_plan(cfg, shapes, "gpu_nccl", {"model": tp},
                              batch_rows=slots, policy="mg_wfbp", **wire_bytes)
    per_stage = build_serve_plan(cfg, shapes, "gpu_nccl", {"model": tp},
                                 batch_rows=slots, policy="wfbp", **wire_bytes)

    def run_engine(mesh_arg, plan):
        timer = ServeTimer(skip_first=2)
        eng = ServingEngine(cfg, params, slots=slots,
                            max_seq=prompt_len + n_tokens + 1,
                            plan=plan, mesh=mesh_arg, timer=timer)
        # compile + probe outside the timed region: the published
        # observed/predicted ratio must compare steady-state dispatch,
        # not XLA compile time
        eng.warmup()
        cal = eng.calibrate_plan()
        rng = np.random.default_rng(0)
        for rid in range(slots + 1):
            eng.submit(Request(
                rid=rid,
                prompt=rng.integers(0, cfg.vocab, size=prompt_len, dtype=np.int32),
                max_new_tokens=n_tokens,
            ))
        t0 = time.perf_counter()
        done = eng.run_to_completion()
        dt = time.perf_counter() - t0
        return {r.rid: r.generated for r in done}, timer, cal, dt

    base_tokens, _, _, _ = run_engine(None, merged)
    sharded_tokens, timer, cal_plan, wall_s = run_engine(mesh, merged)
    tokens_match = base_tokens == sharded_tokens
    observed = timer.median()
    predicted = cal_plan.predicted_step_time()
    ratio = observed / predicted
    ratio_budget = 3.0
    n_generated = sum(len(g) for g in sharded_tokens.values())
    tokens_per_s = n_generated / max(wall_s, 1e-9)

    # min-of-7 per group: the merged-vs-per-stage comparison below is a
    # hard acceptance gate, so squeeze scheduler jitter out of the samples
    merged_group_s = time_serve_groups(merged, mesh, repeats=7)
    per_stage_group_s = time_serve_groups(per_stage, mesh, repeats=7)
    fits = serve_fabric_fits(mesh, ops=("all_gather",), axes=("model",))
    fab = MeasuredFabric(models=fits, name="measured_serve")
    measured_plan = build_serve_plan(cfg, shapes, fab, {"model": tp},
                                     batch_rows=slots, **wire_bytes)

    assert tokens_match, "sharded decode diverged from unsharded"
    assert observed is not None and np.isfinite(ratio) and ratio > 0, (observed, ratio)
    assert ratio <= ratio_budget, (
        f"observed/predicted = {ratio:.1f}x exceeds the {ratio_budget:.0f}x "
        f"budget — the compute+dispatch cost model is no longer honest")
    assert sum(merged_group_s) <= sum(per_stage_group_s), (
        merged_group_s, per_stage_group_s)

    record = {
        "arch": cfg.name,
        "tp": tp,
        "slots": slots,
        "fabric": "gpu_nccl",
        "tokens_match": tokens_match,
        "predicted_step_s": predicted,
        "t_step_fixed_s": cal_plan.t_step_fixed,
        "t_wire_s": cal_plan.schedule.result.t_iter,
        "observed_step_s": observed,
        "observed_over_predicted": ratio,
        "ratio_budget": ratio_budget,
        "tokens_per_s": tokens_per_s,
        "merged": {
            "policy": merged.policy,
            "n_groups": len(merged.schedule.groups),
            "groups": [
                dict(g, measured_s=t)
                for g, t in zip(merged.group_summaries(), merged_group_s)
            ],
            "measured_total_s": sum(merged_group_s),
        },
        "per_stage": {
            "policy": per_stage.policy,
            "n_groups": len(per_stage.schedule.groups),
            "measured_total_s": sum(per_stage_group_s),
        },
        "measured_fits": {
            k: {"a": m.a, "b": m.b} for k, m in fits.items()
        },
        "measured_plan": {
            "fabric": measured_plan.fabric,
            "n_groups": len(measured_plan.schedule.groups),
            "t_iter_s": measured_plan.schedule.result.t_iter,
        },
    }
    rows.append(f"{cfg.name},tp={tp},tokens_match={tokens_match},"
                f"pred_ms={predicted * 1e3:.3f},obs_ms={observed * 1e3:.3f},"
                f"ratio={ratio:.2f},fixed_ms={cal_plan.t_step_fixed * 1e3:.3f},"
                f"tok_per_s={tokens_per_s:.1f}")
    rows.append(f"merged({merged.policy}),groups={len(merged.schedule.groups)},"
                f"gather_total_us={sum(merged_group_s) * 1e6:.1f}")
    rows.append(f"per_stage(wfbp),groups={len(per_stage.schedule.groups)},"
                f"gather_total_us={sum(per_stage_group_s) * 1e6:.1f}")
    for key, m in fits.items():
        rows.append(f"fit,{key},a={m.a:.3e},b={m.b:.3e}")
    def gate(rec, base):
        floor = 0.8 * base["tokens_per_s"]
        assert rec["tokens_per_s"] >= floor, (
            f"serve_exec throughput regressed: {rec['tokens_per_s']:.1f} "
            f"tok/s < 0.8x committed baseline {base['tokens_per_s']:.1f}")

    write_bench("serve_exec", record, rows, gate=gate)
    return rows


def wire_layout() -> list[str]:
    """Wire-layout sweep: concat vs variadic vs arena × fp32 vs bf16.

    Lowers + compiles the bucketed sync for each (fuse, comm dtype) cell
    under shard_map on 8 virtual devices, then reads the truth out of the
    compiled HLO with ``profiler.parse_collectives``: all-reduce op
    count, all-reduce payload bytes (bytes moved per device per step),
    and concatenate op count (the copy tax of the concat layout, zero on
    the arena path).  A numeric check (distinct per-rank scaling, exact
    expected average) rides along so a cell that mis-packs can never
    publish.  Full records go to
    ``benchmarks/results/BENCH_wire_layout.json``.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.core import (
        AllReduceModel,
        SyncConfig,
        count_expected_allreduces,
        group_arenas,
        make_gradient_sync,
        parse_collectives,
        stacked_lm_layout,
    )
    from repro.planning import build_schedule

    n_stages = 4
    shapes = {
        "embed": {"tok": jnp.zeros((64, 32))},
        "stages": {"w1": jnp.zeros((n_stages, 32, 32)), "w2": jnp.zeros((n_stages, 32))},
        "final_norm": {"scale": jnp.zeros((32,))},
        "head": {"w": jnp.zeros((32, 65))},  # odd tail exercises exact packing
    }
    layout = stacked_lm_layout(shapes, n_stages)
    costs = layout.layer_costs(1 << 20, None)
    # α tuned so mg_wfbp lands on an intermediate grouping for these costs:
    # ((1,1), (2,6)) — a lone embed message plus a merged stages+head arena
    # whose slots include a [0:4) scan slice and an odd-sized head tail
    schedule = build_schedule("mg_wfbp", costs, AllReduceModel(a=5e-5, b=1e-9))
    # honor a pre-existing --xla_force_host_platform_device_count (the
    # module-top guard never overrides one): size the mesh to what exists
    n_dev = jax.device_count()
    mesh = make_mesh((n_dev,), ("data",))
    key = jax.random.PRNGKey(0)
    grads = jax.tree.map(
        lambda s: jax.random.normal(jax.random.fold_in(key, s.size), s.shape), shapes
    )

    rows = ["table=wire_layout"]
    records = []
    for fuse in ("concat", "variadic", "arena"):
        for comp in (None, "bf16"):
            cfg = SyncConfig(fuse=fuse, compression=comp)
            sync = make_gradient_sync(layout, schedule, ("data",), cfg)

            def body(g):
                r = jax.lax.axis_index("data").astype(jnp.float32)
                return sync(jax.tree.map(lambda x: x * (r + 1.0), g))

            f = jax.jit(
                jax.shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                          axis_names={"data"}, check_vma=False)
            )
            # lowered (stablehlo) text: the wire dtype is truthful there
            # (compiled CPU modules upcast bf16 collectives to f32)
            stats = parse_collectives(f.lower(grads).as_text())
            got = f(grads)
            # rank r ships (r+1)·g, so the average is mean(1..n_dev)·g
            expect = jax.tree.map(lambda x: (n_dev + 1) / 2 * x, grads)
            max_diff = max(
                jax.tree.leaves(
                    jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), got, expect)
                )
            )
            rec = {
                "fuse": fuse,
                "comm_dtype": "bf16" if comp else "f32",
                "n_groups": len(schedule.groups),
                "allreduce_ops": stats.counts.get("all-reduce", 0),
                "expected_allreduce_ops": count_expected_allreduces(schedule, cfg, layout),
                "wire_bytes": stats.bytes_by_kind.get("all-reduce", 0),
                "concat_ops": stats.concat_ops,
                "max_diff": max_diff,
            }
            if fuse == "arena":
                rec["arena_bytes"] = sum(
                    a.nbytes
                    for a in group_arenas(
                        layout, schedule, shapes,
                        jnp.bfloat16 if comp else jnp.float32,
                    )
                )
            records.append(rec)
            rows.append(
                f"{fuse},{rec['comm_dtype']},groups={rec['n_groups']},"
                f"allreduce_ops={rec['allreduce_ops']},"
                f"wire_bytes={rec['wire_bytes']},concat_ops={rec['concat_ops']},"
                f"max_diff={max_diff:.2e}"
            )
    write_bench("wire_layout", records, rows)
    return rows


def overlap() -> list[str]:
    """Measured DAG-overlap acceptance -> ``BENCH_overlap.json``.

    Runs the same reduced arch through both communication issue orders —
    ``post`` (every merged all-reduce issued after the whole backward) and
    ``dag`` (each group's all-reduce at its last-gradient event inside
    backward) — one steady step of each traced with ``jax.profiler``, and
    prices the contrast from the ``bwd_*`` scopes and the ``wfbp_group*``
    all-reduces of that DEVICE TRACE (``profiler.scope_spans``), not the
    timeline model:

      * ``overlap_fraction`` (comm inside the backward window) and the
        overlapped starts must be > 0 for dag, and post must start fewer
        all-reduces inside backward than dag — re-asserted by the
        ``overlap-smoke`` CI job and the baseline gate.  Post is not
        zero on XLA:CPU, which runs each op once its operands are ready:
        the head's group is ready before the scan's backward ends.  The
        fractions are not compared: on XLA:CPU a device's all-reduce
        span includes its wait for the slowest device, so post's early
        group reads anywhere from 0.2 to 0.7 between runs;
      * every group has one all-reduce span on every device, carrying
        its group's exact wire bytes;
      * both steps must compile to ONE all-reduce per schedule group
        (small slack for the loss pmean etc.): the train step keeps
        XLA's all-reduce combiner from merging them;
      * dag and post losses must agree bit-exactly — reordering the
        issue points must not change the arithmetic.
    """
    import dataclasses as _dc
    import glob as _glob
    import re as _re
    import tempfile as _tempfile

    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_mesh
    from repro.configs import get_reduced
    from repro.core.comm_model import AllReduceModel
    from repro.core.profiler import GROUP_SPAN_RE, overlap_report, scope_spans
    from repro.core.sync import SyncConfig
    from repro.core.trainer import MGWFBPEngine
    from repro.launch.specs import param_specs
    from repro.models.transformer import init_params
    from repro.optim import make_optimizer

    rows = ["table=overlap"]
    n_dev = jax.device_count()
    mesh = make_mesh((n_dev,), ("data",))
    cfg = _dc.replace(get_reduced("tinyllama-1.1b"), param_dtype=jnp.float32)
    eng = MGWFBPEngine.build(
        cfg, param_specs(cfg), dp_axes=("data",),
        ar_model=AllReduceModel(a=5e-5, b=1e-9),
        tokens_per_device=1024, method="wfbp",  # one group per unit
        sync_config=SyncConfig(fuse="arena"),
    )
    n_groups = len(eng.schedule.groups)
    opt = make_optimizer("sgd", momentum=0.9)
    params = init_params(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(1)
    batch = {"targets": jax.random.randint(key, (8, 64), 0, cfg.vocab)}
    if cfg.input_mode == "embeds":
        batch["embeds"] = jax.random.normal(key, (8, 64, cfg.d_model))
    else:
        batch["tokens"] = jax.random.randint(key, (8, 64), 0, cfg.vocab)

    record: dict = {
        "arch": cfg.name,
        "policy": "wfbp",
        "fuse": "arena",
        "n_groups": n_groups,
        "n_devices": n_dev,
        "group_wire_bytes": [int(b) for b in eng.sync.group_wire_bytes],
    }
    reports = {}
    for issue in ("post", "dag"):
        step = eng.make_train_step(opt, mesh, lr=1e-2, issue=issue)
        with jax.set_mesh(mesh):
            compiled = step.lower(params, opt.init(params), batch).compile()
        hlo = compiled.as_text()
        n_ar = len(_re.findall(r" all-reduce\(", hlo))

        def call(compiled=compiled):  # the step donates params/opt_state buffers
            p0 = jax.tree.map(jnp.array, params)
            with jax.set_mesh(mesh):
                return compiled(p0, opt.init(p0), batch)

        # steady-state trace: the first run stays out of it
        jax.block_until_ready(call())
        with _tempfile.TemporaryDirectory() as tdir:
            jax.profiler.start_trace(tdir)
            p, o, m = call()
            jax.block_until_ready(p)
            jax.profiler.stop_trace()
            (xplane,) = _glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
            spans = scope_spans(xplane, hlo, eng.sync.group_wire_bytes)
        rep = overlap_report(spans)
        reports[issue] = rep
        covered: dict[int, set] = {}  # group -> devices with its all-reduce span
        for s in spans:
            g = GROUP_SPAN_RE.match(s.name)
            if g:
                covered.setdefault(int(g.group(1)), set()).add(s.device)
        assert covered == {gi: set(range(n_dev)) for gi in range(n_groups)}, (issue, covered)
        record[issue] = {
            "loss": float(m["loss"]),
            "allreduce_ops": n_ar,
            **{k: rep[k] for k in (
                "n_comm_spans", "n_bwd_spans", "total_comm_us",
                "windowed_comm_us", "hidden_comm_us", "overlap_fraction",
                "hidden_fraction", "n_overlapped_starts",
            )},
            "groups": rep["groups"],
        }
        rows.append(
            f"{issue},groups={n_groups},allreduce_ops={n_ar},"
            f"overlap_fraction={rep['overlap_fraction']:.3f},"
            f"overlapped_starts={rep['n_overlapped_starts']}/{rep['n_comm_spans']},"
            f"loss={float(m['loss']):.6f}"
        )

    # trace-proved acceptance: the wire moved inside backward under dag
    assert record["dag"]["overlap_fraction"] > 0.0, record["dag"]
    assert record["dag"]["n_overlapped_starts"] > 0, record["dag"]
    assert record["post"]["n_overlapped_starts"] < record["dag"]["n_overlapped_starts"]
    # one merged all-reduce per group (slack: loss pmean & friends)
    for issue in ("post", "dag"):
        assert n_groups <= record[issue]["allreduce_ops"] <= n_groups + 4, record
    # per-group spans carry the exact wire bytes of their arena
    by_group: dict[int, int] = {}
    for g in reports["dag"]["groups"]:
        by_group.setdefault(g["group"], g["bytes"])
        assert g["bytes"] == by_group[g["group"]]
    assert sorted(by_group) == list(range(n_groups)), by_group
    for gi, nbytes in by_group.items():
        assert nbytes == record["group_wire_bytes"][gi], (gi, nbytes)
    # issue order must not change the arithmetic
    assert record["dag"]["loss"] == record["post"]["loss"], record
    record["loss_bit_identical"] = True
    rows.append(f"loss_bit_identical=True,"
                f"wire_bytes={sum(record['group_wire_bytes'])}")

    def gate(rec, base):
        assert rec["dag"]["overlap_fraction"] > 0.0
        assert rec["dag"]["n_overlapped_starts"] > rec["post"]["n_overlapped_starts"]
        assert rec["loss_bit_identical"]

    write_bench("overlap", record, rows, gate=gate)
    return rows


def serve_resilience() -> list[str]:
    """Chaos-injected serving acceptance -> ``BENCH_serve_resilience.json``.

    Exercises the whole resilience layer end to end on the reduced arch:

      * seeded kill sweep (``kill_every`` in 0/5/3): every chaos run must
        finish with tokens bit-identical to the uninterrupted baseline,
        and records restarts, mean recovery seconds (backoff + snapshot
        restore + step re-warm) and goodput tok/s — the goodput floor is
        asserted by the ``serve-chaos-smoke`` CI job;
      * deadline cells on a fake clock (deterministic): one run that
        sheds unmeetable requests at admission, one whose in-flight
        request expires mid-generation with partial output;
      * degraded-fabric replan: sustained injected slowdown drives the
        StragglerMonitor -> serve (α, β) refit -> plan rebuild, and the
        full-size planning cell pins that the degraded constants change
        the merge decision itself (fewer, larger serve groups).
    """
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config, get_reduced
    from repro.core.comm_model import AllReduceModel
    from repro.launch.specs import param_specs
    from repro.models.transformer import init_params
    from repro.planning import build_serve_plan, rebuild_serve_plan
    from repro.runtime import StragglerMonitor
    from repro.serving import (
        ChaosConfig,
        ChaosInjector,
        Request,
        ServingEngine,
        resilient_serve_loop,
    )

    rows = ["table=serve_resilience"]
    records = []
    cfg = _dc.replace(get_reduced("tinyllama-1.1b"), param_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    slots, prompt_len, n_tokens, n_requests = 2, 8, 8, 4
    max_seq = prompt_len + n_tokens + 1

    def make_engine(**kw):
        kw.setdefault("slots", slots)
        kw.setdefault("max_seq", max_seq)
        return ServingEngine(cfg, params, **kw)

    def submit_all(eng, deadlines=None):
        rng = np.random.default_rng(0)
        for rid in range(n_requests):
            eng.submit(Request(
                rid=rid,
                prompt=rng.integers(0, cfg.vocab, size=prompt_len, dtype=np.int32),
                max_new_tokens=n_tokens,
                deadline_s=None if deadlines is None else deadlines[rid],
            ))

    import tempfile

    # -- seeded kill sweep: recovery must be token-identical ---------------
    baseline_tokens = None
    for kill_every in (0, 5, 3):
        eng = make_engine()
        eng.warmup()
        submit_all(eng)
        chaos = (ChaosInjector(ChaosConfig(seed=7, kill_every=kill_every))
                 if kill_every else None)
        with tempfile.TemporaryDirectory() as snap_dir:
            report = resilient_serve_loop(
                eng, snapshot_dir=snap_dir, snapshot_every=2,
                backoff_base_s=0.0, chaos=chaos,
            )
        tokens = {r.rid: r.generated for r in report.completed}
        if baseline_tokens is None:
            baseline_tokens = tokens
        match = tokens == baseline_tokens
        assert match, f"kill_every={kill_every}: tokens diverged after recovery"
        mean_rec = (sum(report.recovery_times_s) / len(report.recovery_times_s)
                    if report.recovery_times_s else 0.0)
        records.append({
            "case": "kill_sweep", "kill_every": kill_every,
            "restarts": report.restarts,
            "recovery_time_s": mean_rec,
            "goodput_tok_s": report.goodput_tok_per_s,
            "tokens_match": match,
        })
        rows.append(
            f"kill_every={kill_every},restarts={report.restarts},"
            f"recovery_s={mean_rec:.3f},"
            f"goodput_tok_s={report.goodput_tok_per_s:.1f},tokens_match={match}"
        )

    # -- deadline shed/expire on a deterministic fake clock ----------------
    class FakeClock:
        def __init__(self, dt):
            self.t, self.dt = 0.0, dt

        def __call__(self):
            self.t += self.dt
            return self.t

    # shed: deadlines already in the past at admission
    eng = make_engine()
    submit_all(eng, deadlines=[-1.0] * n_requests)
    with tempfile.TemporaryDirectory() as snap_dir:
        report = resilient_serve_loop(
            eng, snapshot_dir=snap_dir, snapshot_every=100,
            backoff_base_s=0.0, clock=FakeClock(0.25),
        )
    assert report.shed == n_requests
    records.append({"case": "deadline_shed", "shed": report.shed,
                    "expired": report.expired,
                    "goodput_tokens": report.goodput_tokens})
    rows.append(f"deadline_shed,shed={report.shed},expired={report.expired}")

    # expire: one request's deadline lands mid-generation -> partial output
    eng = make_engine()
    submit_all(eng, deadlines=[1000.0, 4.0, 1000.0, 1000.0])
    with tempfile.TemporaryDirectory() as snap_dir:
        report = resilient_serve_loop(
            eng, snapshot_dir=snap_dir, snapshot_every=100,
            backoff_base_s=0.0, clock=FakeClock(0.25),
        )
    expired = [r for r in report.completed if r.expired]
    assert len(expired) == 1 and 0 < len(expired[0].generated) < n_tokens
    records.append({"case": "deadline_expire", "expired": report.expired,
                    "partial_tokens": len(expired[0].generated),
                    "max_new_tokens": n_tokens})
    rows.append(f"deadline_expire,expired={report.expired},"
                f"partial_tokens={len(expired[0].generated)}/{n_tokens}")

    # -- degraded-fabric replan: loop-level + full-size merge shift --------
    plan = build_serve_plan(cfg, param_specs(cfg), "tpu_v5e", {"model": 8},
                            batch_rows=slots)
    eng = make_engine(max_seq=128, plan=plan)
    for rid in range(slots):
        eng.submit(Request(rid=rid,
                           prompt=np.arange(4, dtype=np.int32) + 1,
                           max_new_tokens=40))
    chaos = ChaosInjector(ChaosConfig(seed=3, slow_factor=30.0, slow_after=12))
    with tempfile.TemporaryDirectory() as snap_dir:
        report = resilient_serve_loop(
            eng, snapshot_dir=snap_dir, snapshot_every=50,
            backoff_base_s=0.0, chaos=chaos,
            straggler=StragglerMonitor(window=16, factor=2.0, patience=2),
        )
    assert report.replans >= 1 and eng.plan.model.a > plan.model.a
    records.append({
        "case": "degraded_replan", "replans": report.replans,
        "a_before": plan.model.a, "a_after": eng.plan.model.a,
        "pred_step_before_s": plan.predicted_step_time(),
        "pred_step_after_s": eng.plan.predicted_step_time(),
    })
    rows.append(f"degraded_replan,replans={report.replans},"
                f"a={plan.model.a:.2e}->{eng.plan.model.a:.2e}")

    # full-size arch, analytic only: the degraded wire changes the merge
    # decision itself — MG-WFBP's merge set is a function of (a, b)
    cfg_full = get_config("tinyllama-1.1b")
    full = build_serve_plan(cfg_full, param_specs(cfg_full), "tpu_v5e",
                            {"model": 8}, batch_rows=64)
    degraded_model = AllReduceModel(a=full.model.a * 50, b=full.model.b * 10,
                                    name="degraded")
    shifted = rebuild_serve_plan(full, degraded_model)
    assert len(shifted.schedule.groups) < len(full.schedule.groups)
    records.append({
        "case": "merge_shift", "arch": cfg_full.name,
        "groups_before": len(full.schedule.groups),
        "groups_after": len(shifted.schedule.groups),
        "pred_step_before_s": full.predicted_step_time(),
        "pred_step_after_s": shifted.predicted_step_time(),
    })
    rows.append(f"merge_shift,groups={len(full.schedule.groups)}->"
                f"{len(shifted.schedule.groups)},"
                f"pred_s={full.predicted_step_time():.2e}->"
                f"{shifted.predicted_step_time():.2e}")

    write_bench("serve_resilience", records, rows)
    return rows


def serve_fleet() -> list[str]:
    """Fleet-under-chaos acceptance -> ``BENCH_serve_fleet.json``.

    Drives the 4-replica serving fleet (``serving.fleet``) through the
    SAME seeded offered load with and without kill chaos and publishes
    p50/p99 latency and goodput vs offered load:

      * ``fault_free``  — 4 replicas, seeded Poisson load, no faults;
      * ``kill_chaos``  — identical load, replica 0's fault domain kills
        it with no restore budget, so its in-flight requests fail over.
        Hard acceptance (re-asserted by the ``serve-fleet-smoke`` CI
        job): goodput ≥ 70% of the fault-free run and ZERO failed-over
        requests whose final tokens diverge from their partial prefix;
      * ``load_sweep``  — offered rate × replica count grid: p50/p99
        latency and goodput per cell, the saturation curve;
      * ``slo_shed``    — a deadline no plan-priced replica can meet:
        everything sheds at admission, costing zero decode steps.
    """
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    from repro.configs import get_reduced
    from repro.launch.specs import param_specs
    from repro.models.transformer import init_params
    from repro.planning import build_serve_plan
    from repro.serving import (
        ChaosConfig,
        FleetConfig,
        FleetController,
        LoadGenerator,
        LoadSpec,
        ServingEngine,
    )

    rows = ["table=serve_fleet"]
    record: dict = {}
    cfg = _dc.replace(get_reduced("tinyllama-1.1b"), param_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    slots, prompt_len, n_tokens = 2, 8, 8
    max_seq = prompt_len + n_tokens + 1
    plan = build_serve_plan(cfg, param_specs(cfg), "tpu_v5e", {"model": 8},
                            batch_rows=slots, cache_dtype_bytes=4,
                            act_dtype_bytes=4)

    def factory(rid: int) -> ServingEngine:
        eng = ServingEngine(cfg, params, slots=slots, max_seq=max_seq,
                            plan=plan)
        eng.warmup()
        return eng

    import tempfile

    def run_cell(*, replicas, n_requests, rate=1e6, deadline_s=None,
                 chaos=None, chaos_replicas=None, seed=0):
        load = LoadGenerator(LoadSpec(
            n_requests=n_requests, prompt_len=prompt_len,
            max_new_tokens=n_tokens, rate_rps=rate, deadline_s=deadline_s,
            seed=seed, vocab=cfg.vocab,
        ))
        with tempfile.TemporaryDirectory() as snap_root:
            fleet = FleetController(
                engine_factory=factory,
                config=FleetConfig(replicas=replicas, snapshot_every=4,
                                   max_restores=0, backoff_base_s=0.0),
                snapshot_root=snap_root,
                chaos=chaos, chaos_replicas=chaos_replicas,
            )
            return fleet.run(load)

    # -- fault-free vs single-replica kill chaos, same seeded load ---------
    n_requests = 16
    ff = run_cell(replicas=4, n_requests=n_requests)
    ko = run_cell(replicas=4, n_requests=n_requests,
                  chaos=ChaosConfig(seed=7, kill_at=(2,)),
                  chaos_replicas=(0,))
    goodput_ratio = ko.goodput_tokens / max(ff.goodput_tokens, 1)
    record["fault_free"] = ff.summary()
    record["kill_chaos"] = ko.summary() | {
        "goodput_ratio_vs_fault_free": goodput_ratio,
    }
    assert ff.failover_token_mismatches == 0
    assert ko.replica_deaths == 1 and ko.failovers >= 1
    assert ko.failover_token_mismatches == 0, (
        "failed-over requests diverged from their partial prefix")
    assert goodput_ratio >= 0.7, (
        f"kill chaos retained only {goodput_ratio:.0%} of fault-free goodput")
    for name, rep in (("fault_free", ff), ("kill_chaos", ko)):
        s = rep.summary()
        rows.append(
            f"{name},replicas=4,offered={s['offered']},"
            f"completed={s['completed']},p50_ms={s['p50_latency_s'] * 1e3:.1f},"
            f"p99_ms={s['p99_latency_s'] * 1e3:.1f},"
            f"goodput_tokens={s['goodput_tokens']},"
            f"failovers={s['failovers']},"
            f"mismatches={s['failover_token_mismatches']}"
        )
    rows.append(f"kill_chaos_goodput_ratio={goodput_ratio:.3f} (floor 0.7)")

    # -- p50/p99/goodput vs offered load -----------------------------------
    record["load_sweep"] = []
    for replicas in (1, 2):
        for rate in (50.0, 400.0):
            rep = run_cell(replicas=replicas, n_requests=8, rate=rate, seed=1)
            cell = rep.summary() | {"replicas": replicas, "rate_rps": rate}
            record["load_sweep"].append(cell)
            rows.append(
                f"load,replicas={replicas},rate={rate:.0f},"
                f"p50_ms={cell['p50_latency_s'] * 1e3:.1f},"
                f"p99_ms={cell['p99_latency_s'] * 1e3:.1f},"
                f"goodput_tok_s={cell['goodput_tok_per_s']:.1f}"
            )

    # -- SLO shed: no replica's plan can meet the deadline -----------------
    shed = run_cell(replicas=2, n_requests=6, deadline_s=1e-9, seed=2)
    assert shed.shed == 6 and shed.goodput_tokens == 0
    record["slo_shed"] = shed.summary()
    rows.append(f"slo_shed,offered=6,shed={shed.shed},"
                f"goodput_tokens={shed.goodput_tokens}")

    def gate(rec, base):
        ratio = rec["kill_chaos"]["goodput_ratio_vs_fault_free"]
        assert ratio >= 0.7, f"chaos goodput ratio {ratio:.2f} < 0.7 floor"
        assert rec["kill_chaos"]["failover_token_mismatches"] == 0
        base_ratio = base["kill_chaos"]["goodput_ratio_vs_fault_free"]
        assert ratio >= 0.9 * base_ratio, (
            f"chaos goodput ratio regressed: {ratio:.2f} vs committed "
            f"{base_ratio:.2f}")

    write_bench("serve_fleet", record, rows, gate=gate)
    return rows


def sim() -> list[str]:
    """Fleet-scale what-if simulator suite (``repro.sim``): calibration
    against the committed BENCH records, the paper's Figs. 6-8 scaling
    ordering on the simulated 10GbE cluster, a policies x fleets x
    fabrics what-if sweep to 512 hosts, straggler/elastic/serve replay
    cells, and a two-run byte-determinism check.  Record goes to
    ``benchmarks/results/BENCH_sim.json``.

    Paper-ordering note: at the paper's own batches (googlenet 64,
    resnet50 32) the 64-node WFBP cell falls *below* SyncEASGD — per-layer
    ring startup 2(N-1)α dominates at N=64, the exact crossover MG-WFBP
    exists to fix (and MG-WFBP stays on top).  Those cells are recorded
    unasserted; the strict MG-WFBP > WFBP > SyncEASGD chain is asserted
    at 8 nodes with paper batches and at 64 nodes in the compute-balanced
    regime (googlenet 256 / resnet50 128)."""
    import hashlib

    from repro.configs.cnn_profiles import cnn_layer_costs
    from repro.core.cost_model import K80_CALIBRATED
    from repro.serving.fleet import LoadSpec
    from repro.sim import (
        ClusterEvent,
        ClusterSpec,
        SimReport,
        calibrate_serve,
        calibrate_train,
        replay_serve,
        replay_train,
        row_from_replay,
    )

    rows = ["table=sim"]
    record = {}
    POLICIES = ("synceasgd", "wfbp", "mg_wfbp")

    # -- calibration: the simulator must reproduce the committed records ---
    cal = {}
    for rep in (calibrate_train(), calibrate_serve()):
        cal[rep.kind] = rep.to_json_dict()
        assert rep.ok, (
            f"calibration/{rep.kind}: max ratio {rep.max_ratio:.4f} blew "
            f"the {rep.budget}x budget — what-ifs would be untrustworthy")
        rows.append(
            f"calibration,{rep.kind},rows={len(rep.rows)},"
            f"max_ratio={rep.max_ratio:.6f},budget={rep.budget}"
        )
    record["calibration"] = cal

    # -- paper reproduction: Figs. 6-8 scaling-efficiency ordering ---------
    def eff_cells(arch: str, batch: int, n: int) -> dict:
        cluster = ClusterSpec(n_hosts=n, fabric="paper_10gbe")
        costs = cnn_layer_costs(arch, batch)
        return {
            p: row_from_replay(
                replay_train(cluster, list(costs), p, hw=K80_CALIBRATED),
                arch, "paper_10gbe", n,
            ).to_json_dict()
            for p in POLICIES
        }

    paper = {"asserted": [], "crossover_unasserted": []}
    for arch, batch, n in (
        ("googlenet", 64, 8), ("resnet50", 32, 8),       # paper batches
        ("googlenet", 256, 64), ("resnet50", 128, 64),   # compute-balanced
    ):
        cells = eff_cells(arch, batch, n)
        effs = {p: cells[p]["efficiency"] for p in POLICIES}
        assert effs["mg_wfbp"] > effs["wfbp"] > effs["synceasgd"], (
            f"{arch} b{batch} n={n}: MG-WFBP > WFBP > SyncEASGD ordering "
            f"broken: {effs}")
        paper["asserted"].append(
            {"arch": arch, "batch": batch, "n_hosts": n, "cells": cells})
        rows.append(
            f"paper,{arch},b{batch},n={n},"
            + ",".join(f"{p}={effs[p]:.4f}" for p in POLICIES)
            + ",ordering=ok"
        )
    for arch, batch in (("googlenet", 64), ("resnet50", 32)):
        cells = eff_cells(arch, batch, 64)
        effs = {p: cells[p]["efficiency"] for p in POLICIES}
        assert effs["mg_wfbp"] == max(effs.values())  # MG-WFBP still wins
        paper["crossover_unasserted"].append(
            {"arch": arch, "batch": batch, "n_hosts": 64, "cells": cells})
        rows.append(
            f"paper_crossover,{arch},b{batch},n=64,"
            + ",".join(f"{p}={effs[p]:.4f}" for p in POLICIES)
            + ",wfbp_startup_bound=unasserted"
        )
    record["paper"] = paper

    # -- what-if sweep: policies x fleets x fabrics, run twice for the -----
    # -- byte-determinism contract -----------------------------------------
    FABRICS = ("paper_10gbe", "tree_10gbe", "pipeline_10gbe", "tpu_v5e_tree_dcn")
    HOSTS = (8, 64, 512)
    wcosts = cnn_layer_costs("googlenet", 64)

    def build_report() -> SimReport:
        srows = []
        for fabric in FABRICS:
            ici = 16 if fabric == "tpu_v5e_tree_dcn" else 0
            for n in HOSTS:
                cluster = ClusterSpec(n_hosts=n, ici_size=ici, fabric=fabric)
                for p in POLICIES:
                    res = replay_train(cluster, list(wcosts), p,
                                       hw=K80_CALIBRATED)
                    srows.append(row_from_replay(res, "googlenet", fabric, n))
        return SimReport(
            rows=tuple(srows),
            calibration=cal,
            provenance={"arch": "googlenet", "batch": "64",
                        "source": "benchmarks.run/sim"},
        )

    report, report2 = build_report(), build_report()
    j1, j2 = report.to_json(), report2.to_json()
    assert j1 == j2, "identical specs produced different SimReport bytes"
    record["whatif"] = [r.to_json_dict() for r in report.rows]
    record["determinism"] = {
        "identical": j1 == j2,
        "sha256": hashlib.sha256(j1.encode()).hexdigest(),
    }
    for fabric in FABRICS:
        for n in HOSTS:
            best = report.best_policy(fabric=fabric, n_hosts=n)
            eff = report.select(fabric=fabric, n_hosts=n, policy=best)[0].efficiency
            rows.append(f"whatif,{fabric},n={n},best={best},eff={eff:.4f}")
    rows.append(f"determinism,two_runs,identical=True,"
                f"sha256={record['determinism']['sha256'][:16]}")

    # -- stragglers: heterogeneous fleets can only get slower --------------
    strag = []
    for spread in (0.0, 0.2, 0.5):
        cluster = ClusterSpec(n_hosts=64, fabric="paper_10gbe",
                              straggler_spread=spread, seed=3)
        res = replay_train(cluster, list(wcosts), "mg_wfbp", hw=K80_CALIBRATED)
        strag.append({"spread": spread, "t_iter_s": res.mean_t_iter,
                      "efficiency": res.mean_efficiency})
    assert strag[0]["t_iter_s"] <= strag[1]["t_iter_s"] <= strag[2]["t_iter_s"], (
        f"t_iter must be monotone in straggler spread: {strag}")
    record["straggler"] = strag
    rows.append("straggler,n=64,"
                + ",".join(f"spread{s['spread']}={s['t_iter_s'] * 1e3:.3f}ms"
                           for s in strag) + ",monotone=ok")

    # -- elastic fleet: shrink/grow/kill re-plans the merge set ------------
    elastic_cluster = ClusterSpec(
        n_hosts=64, fabric="paper_10gbe",
        events=(ClusterEvent(at_iter=2, kind="shrink", count=32),
                ClusterEvent(at_iter=4, kind="grow", count=32),
                ClusterEvent(at_iter=6, kind="kill", count=8)),
    )
    el = replay_train(elastic_cluster, list(wcosts), "mg_wfbp",
                      hw=K80_CALIBRATED, n_iters=8)
    assert el.n_replans == 3 and el.n_kills == 8, (el.n_replans, el.n_kills)
    alive = [it["n_alive"] for it in el.iterations]
    assert alive == [64, 64, 32, 32, 64, 64, 56, 56], alive
    record["elastic"] = {"n_replans": el.n_replans, "n_kills": el.n_kills,
                         "iterations": list(el.iterations)}
    rows.append(f"elastic,n0=64,replans={el.n_replans},kills={el.n_kills},"
                f"alive={'/'.join(map(str, alive))}")

    # -- serve replay: min-ETA routing, kill failover, SLO shed ------------
    load = LoadSpec(n_requests=12, prompt_len=1, max_new_tokens=16,
                    kind="trace", trace_arrivals_s=(0.0,) * 12, seed=0)
    sv = replay_serve(load, 0.01, n_replicas=2, slots=4,
                      kill_at_s={0: 0.05})
    assert sv.failovers >= 1 and sv.lost == 0 and sv.completed == 12, (
        sv.to_json_dict())
    shed = replay_serve(
        LoadSpec(n_requests=6, prompt_len=1, max_new_tokens=16, kind="trace",
                 trace_arrivals_s=(0.0,) * 6, deadline_s=1e-9, seed=0),
        0.01, n_replicas=2, slots=4,
    )
    assert shed.shed == 6 and shed.completed == 0, shed.to_json_dict()
    record["serve_sim"] = {"kill_failover": sv.to_json_dict(),
                           "slo_shed": shed.to_json_dict()}
    rows.append(f"serve,kill_failover,completed={sv.completed},"
                f"failovers={sv.failovers},tok_s={sv.tokens_per_s:.1f},"
                f"p99_ms={sv.latency_percentile(99) * 1e3:.1f}")
    rows.append(f"serve,slo_shed,offered=6,shed={shed.shed}")

    def gate(rec, base):
        for kind in ("train", "serve"):
            c = rec["calibration"][kind]
            assert c["ok"], f"calibration/{kind} out of budget: {c['max_ratio']}"
            b = base["calibration"][kind]
            assert c["max_ratio"] <= max(b["max_ratio"] * 1.05, 1.0 + 1e-9), (
                f"calibration/{kind} regressed: {c['max_ratio']:.4f} vs "
                f"committed {b['max_ratio']:.4f}")
        assert rec["determinism"]["identical"]
        for cell in rec["paper"]["asserted"]:
            effs = {p: cell["cells"][p]["efficiency"] for p in POLICIES}
            assert effs["mg_wfbp"] > effs["wfbp"] > effs["synceasgd"], (
                f"paper ordering broken in {cell['arch']} b{cell['batch']} "
                f"n={cell['n_hosts']}: {effs}")

    write_bench("sim", record, rows, gate=gate)
    return rows


def main() -> None:
    from benchmarks.paper_tables import ALL_TABLES

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated table names (default: all)")
    args = ap.parse_args()

    tables = list(ALL_TABLES) + [
        planning_sweep, wire_layout, tuner, fabric_sweep, serve_exec,
        overlap, serve_resilience, serve_fleet, sim, roofline_summary,
    ]
    if args.only:
        wanted = {n.strip() for n in args.only.split(",")}
        unknown = wanted - {fn.__name__ for fn in tables}
        if unknown:
            raise SystemExit(f"unknown tables {sorted(unknown)}; "
                             f"have {[fn.__name__ for fn in tables]}")
        tables = [fn for fn in tables if fn.__name__ in wanted]
    for fn in tables:
        t0 = time.perf_counter()
        rows = fn()
        dt_us = (time.perf_counter() - t0) * 1e6
        print(f"{fn.__name__},{dt_us:.0f},rows={len(rows) - 1}")
        for r in rows:
            print("  " + r)
        print()


if __name__ == "__main__":
    main()

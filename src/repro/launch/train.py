"""Production training launcher: MG-WFBP Tier-2 engine + data pipeline +
fault-tolerant loop + async checkpointing, driven by --arch configs.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \\
        --reduced --steps 100 --batch 8 --seq 256 --policy mg_wfbp

On a real TPU slice the same entry point runs under `jax.distributed`
(one process per host); this container runs it single-process.

Planning lifecycle wiring (journal MG-WFBP's online re-planning):

  * the engine builds (or loads, ``--plan-in``) a frozen ``Plan``;
  * ``--autotune`` closes the loop: per-unit segment probes
    (``runtime/timeline.py``) feed ``MeasuredCosts.from_segment_times``
    and a registry-wide ``planning.Tuner`` sweep picks the argmin
    predicted-t_iter plan — at startup, on drift, and on restart;
  * every ``--replan-every`` steps the measured profile (per-unit probe
    times under --autotune, else the median step time's uniform rescale)
    drives ``replan_if_drifted`` / a tuner sweep (threshold
    ``--replan-threshold``); a re-plan rebuilds the train step;
  * every ``--comm-refit-every`` steps a slim timed-psum sweep is
    exponentially weighted into the (α, β) fit (``CommRefitter``); when
    the fitted constants drift past ``--comm-drift-threshold`` the plan
    search reruns under the fresh comm model — the journal version's
    online comm loop;
  * fault-tolerant restarts restore the plan AND the tuner state saved
    beside the latest checkpoint, or re-enter the plan search when none
    is stored, through the ``resilient_loop`` hooks;
  * ``--plan-out`` additionally serializes the final plan for elastic
    restarts, dry-runs, and benchmarks to reuse;
  * ``--fuse arena`` ships gradients over the packed-arena wire path and
    ``--compression bf16_ef`` threads the error-feedback residual through
    the train step and checkpoints (EF survives restarts).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..checkpoint import latest_step, load_plan, load_tuner_state
from ..configs import ARCH_NAMES, get_config, get_reduced
from ..core.cost_model import Hardware, hardware_for
from ..core.sync import SyncConfig
from ..core.trainer import MGWFBPEngine
from ..data import DataConfig, make_stream
from ..fabric import MeasuredFabric, available_fabrics, get_fabric
from ..launch.compile_cache import enable_compile_cache
from ..launch.mesh import make_mesh
from ..launch.specs import param_specs
from ..models.common import ArchConfig
from ..models.transformer import init_params
from ..optim import make_optimizer
from ..optim.optimizers import Optimizer
from ..planning import (
    CommRefitter,
    DEFAULT_COMM_SWEEP,
    MeasuredComm,
    MeasuredCosts,
    Plan,
    Tuner,
    available_policies,
    cost_drift,
    psum_time_fn,
)
from ..runtime import RunState, StragglerMonitor, StepTimer, resilient_loop
from ..runtime.timeline import make_unit_probes, probe_unit_times


@dataclasses.dataclass
class TrainSetup:
    """What a parsed command line resolves to before any step is built:
    the model, the mesh, the wire and the comm model that prices plans."""

    args: argparse.Namespace
    cfg: ArchConfig
    mesh: jax.sharding.Mesh
    sync_cfg: SyncConfig
    hw: Hardware
    ar_model: Any
    comm_obs: MeasuredComm
    opt: Optimizer

    def engine(self, plan: Plan | None = None, from_tuner: bool = False) -> MGWFBPEngine:
        """The engine for ``plan``, or for a fresh plan of ``--policy``."""
        args = self.args
        return MGWFBPEngine.build(
            self.cfg,
            param_specs(self.cfg),
            dp_axes=("data",),
            ar_model=self.ar_model,
            tokens_per_device=args.batch * args.seq // self.mesh.size,
            hw=self.hw,
            # a loaded plan carries its own policy; an explicitly requested
            # one is forwarded so the engine can reject a mismatch instead
            # of silently losing it.  Tuner-chosen plans own their policy.
            policy=(None if from_tuner else args.policy)
            if plan is not None
            else (args.policy or "mg_wfbp"),
            sync_config=self.sync_cfg,
            plan=plan,
        )

    def train_step(self, eng: MGWFBPEngine):
        """The jitted train step of ``eng`` under this command line."""
        return eng.make_train_step(
            self.opt, self.mesh, lr=self.args.lr, issue=self.args.issue_order,
        )


@dataclasses.dataclass
class TrainResult:
    """What ``main`` returns: the final state and every step's loss."""

    final: RunState
    losses: list[float]


def _dryrun(args, eng, step_fn, init_state, data, mesh) -> None:
    """Trace-first smoke: compile the step, run one warm-up step, trace the
    other ``args.dryrun - 1`` with ``jax.profiler`` (into ``--trace-out``,
    else a temporary directory), and report how much of the wire the chosen
    issue order hides under backward — from the ``bwd_*`` / ``wfbp_group*``
    scopes of the device trace (``profiler.scope_spans``), not the model —
    and the device time per step of each layer (``profiler.scope_layers``)."""
    import glob
    import os
    import shutil
    import tempfile

    from ..core.profiler import GROUP_SPAN_RE, overlap_report, scope_layers, scope_spans

    state = init_state()
    batch = jax.tree.map(jnp.asarray, data.batch_at(0))

    def inputs(state):
        if eng.stateful:
            return state.params, state.opt_state, state.residual, batch
        return state.params, state.opt_state, batch

    with jax.set_mesh(mesh):
        compiled = step_fn.lower(*inputs(state)).compile()

    def one(state):
        with jax.set_mesh(mesh):
            out = compiled(*inputs(state))
        p, o, m = out[0], out[1], out[-1]
        res = out[2] if eng.stateful else state.residual
        return RunState(step=state.step + 1, params=p, opt_state=o,
                        residual=res), m

    if args.dryrun > 1:  # steady state: the trace holds no first-run work
        state, m = one(state)
        jax.block_until_ready(state.params)
    tdir = args.trace_out or tempfile.mkdtemp(prefix="dryrun_trace_")
    jax.profiler.start_trace(tdir)
    for _ in range(max(1, args.dryrun - 1)):
        state, m = one(state)
    jax.block_until_ready(state.params)
    jax.profiler.stop_trace()
    xplane = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True),
                    key=os.path.getmtime)[-1]
    hlo = compiled.as_text()
    spans = scope_spans(xplane, hlo, eng.sync.group_wire_bytes)
    layers = scope_layers(xplane, hlo)
    if not args.trace_out:
        shutil.rmtree(tdir, ignore_errors=True)

    last = max((s.args["step"] for s in spans), default=0)
    spans = [s for s in spans if s.args["step"] == last]
    report = overlap_report(spans)
    covered: dict[int, list[int]] = {}  # group -> devices with its all-reduce span
    for s in spans:
        g = GROUP_SPAN_RE.match(s.name)
        if g:
            covered.setdefault(int(g.group(1)), []).append(s.device)
    sched = eng.plan.schedule
    print(f"[dryrun] issue={args.issue_order} loss={float(m['loss']):.4f} "
          f"groups={list(sched.groups)}")
    print(f"[dryrun] overlap fraction {report['overlap_fraction']:.3f} "
          f"({report['windowed_comm_us']:.0f}us of {report['total_comm_us']:.0f}us "
          f"comm inside the backward window; strict concurrent overlap "
          f"{report['hidden_fraction']:.3f}; {report['n_overlapped_starts']}/"
          f"{report['n_comm_spans']} comm spans start inside backward; "
          f"last of {last + 1} traced steps)")
    print("[dryrun] " + json.dumps(
        {k: report[k] for k in ("n_devices", "n_comm_spans", "n_bwd_spans",
                                "total_comm_us", "windowed_comm_us",
                                "hidden_comm_us", "overlap_fraction",
                                "hidden_fraction", "n_overlapped_starts")}
        | {"comm_span_devices": {str(g): sorted(d) for g, d in sorted(covered.items())}}
    ))
    print("[dryrun] layers " + json.dumps(layers))
    if args.trace_out:
        print(f"[dryrun] profiler trace written under {args.trace_out}")


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_NAMES)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the initial weights and the data stream")
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "sgd"])
    ap.add_argument("--policy", "--method", dest="policy", default=None,
                    choices=list(available_policies()),
                    help="scheduler policy (planning registry; default mg_wfbp). "
                         "With --plan-in, only valid if it matches the plan's policy; "
                         "ignored under --autotune (the sweep picks).")
    ap.add_argument("--comm-dtype", default="f32", choices=["f32", "bf16"])
    ap.add_argument("--compression", default=None,
                    choices=["bf16", "bf16_ef"],
                    help="wire compression (default: follows --comm-dtype). "
                         "bf16_ef carries the error-feedback residual through "
                         "the train step and checkpoints (requires --fuse arena)")
    ap.add_argument("--fuse", default="concat",
                    choices=["concat", "variadic", "arena"],
                    help="wire layout: concat (one flat buffer, copy each way), "
                         "variadic (zero-copy tuple psum), arena (packed flat "
                         "buffer via kernels/comm_pack — one all-reduce per "
                         "group AND no concatenate copies)")
    ap.add_argument("--virtual-dp", type=int, default=32,
                    help="DP size assumed by the α–β schedule model")
    ap.add_argument("--fabric", default="tpu_v5e",
                    choices=available_fabrics(),
                    help="interconnect preset pricing the DP all-reduce: "
                         f"{', '.join(available_fabrics())} "
                         "(tpu_v5e matches the historical analytic TPU "
                         "model; tree_10gbe / pipeline_10gbe / "
                         "tpu_v5e_tree_dcn are the hierarchical Wang-Vuduc "
                         "reductions)")
    ap.add_argument("--measure-comm", action="store_true",
                    help="fit (α, β) from timed psums on the live mesh "
                         "(a MeasuredFabric, journal §V-A) instead of the "
                         "--fabric preset at --virtual-dp")
    ap.add_argument("--autotune", action="store_true",
                    help="closed-loop auto-tuner: per-unit segment probes feed "
                         "MeasuredCosts, and a registry-wide Tuner sweep picks "
                         "the argmin predicted-t_iter plan at startup, on "
                         "drift, and on restart")
    ap.add_argument("--comm-refit-every", type=int, default=0,
                    help="steps between slim timed-psum (α, β) re-fits "
                         "(EWMA into the stored sweep; 0 = off)")
    ap.add_argument("--comm-drift-threshold", type=float, default=0.25,
                    help="relative (α, β) drift that triggers a comm re-plan")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--max-restarts", type=int, default=5)
    ap.add_argument("--plan-in", default=None,
                    help="load a serialized Plan instead of planning")
    ap.add_argument("--plan-out", default=None,
                    help="write the final Plan JSON here")
    ap.add_argument("--replan-every", type=int, default=25,
                    help="steps between measured-profile drift checks (0 = off)")
    ap.add_argument("--replan-threshold", type=float, default=0.25,
                    help="relative per-unit backward-time drift that triggers a re-plan")
    ap.add_argument("--issue-order", default="post", choices=["post", "dag"],
                    help="when each schedule group's merged all-reduce issues: "
                         "after the whole backward (post) or at the group's "
                         "last-gradient event inside backward (dag) — the "
                         "WFBP overlap path (requires scan segments)")
    ap.add_argument("--dryrun", type=int, default=0, metavar="N",
                    help="trace-first smoke: run N steps, the last N-1 under "
                         "jax.profiler, print the measured overlap report "
                         "(comm hidden under backward, from the traced "
                         "wfbp_group*/bwd_* scopes), and exit — no "
                         "checkpoints, no resilience loop")
    ap.add_argument("--trace-out", default=None,
                    help="with --dryrun: the jax.profiler output directory "
                         "(kept; default a temporary one)")
    return ap


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    """The launcher's command line (default: ``sys.argv``), validated."""
    ap = _parser()
    args = ap.parse_args(argv)
    if args.plan_in and args.autotune:
        ap.error("--plan-in and --autotune are mutually exclusive: the "
                 "tuner's sweep picks the plan (drop --autotune to pin a "
                 "serialized plan)")
    if args.compression is None and args.comm_dtype == "bf16":
        args.compression = "bf16"
    if args.compression == "bf16_ef" and args.fuse != "arena":
        ap.error("--compression bf16_ef requires --fuse arena")
    return args


def setup(args: argparse.Namespace) -> TrainSetup:
    """Resolve a parsed command line: model, data-parallel mesh over every
    visible device, wire config, and the comm model pricing the plan."""
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if args.reduced:
        cfg = dataclasses.replace(cfg, param_dtype=jnp.float32)
    hw = hardware_for(jax.devices()[0])
    n_dev = jax.device_count()
    mesh = make_mesh((n_dev, 1), ("data", "model"))
    sync_cfg = SyncConfig(
        comm_dtype=jnp.bfloat16 if args.comm_dtype == "bf16" else jnp.float32,
        compression=args.compression,
        fuse=args.fuse,
    )

    if args.measure_comm:
        comm_obs = MeasuredComm.time_psums(mesh, ("data",))
        fabric = MeasuredFabric.from_comm(comm_obs)
        ar_model = fabric.cost("all_reduce", {"data": n_dev})
        print(f"[train] measured comm fit: α={ar_model.a:.3e}s β={ar_model.b:.3e}s/B")
    else:
        fabric = get_fabric(args.fabric)
        ar_model = fabric.cost("all_reduce", {"data": args.virtual_dp})
        # analytic prior sampled on the standard sweep, so the online
        # EWMA re-fit has observations to blend fresh probes into
        comm_obs = MeasuredComm(
            sizes_bytes=DEFAULT_COMM_SWEEP,
            times_s=tuple(ar_model(s) for s in DEFAULT_COMM_SWEEP),
            name="analytic_prior",
        )
    return TrainSetup(args=args, cfg=cfg, mesh=mesh, sync_cfg=sync_cfg, hw=hw,
                      ar_model=ar_model, comm_obs=comm_obs,
                      opt=make_optimizer(args.optimizer))


def main(argv: list[str] | None = None) -> TrainResult | None:
    """Run the launcher on ``argv`` (default: the command line).  Returns
    the final state and per-step losses (None after ``--dryrun``)."""
    args = parse_args(argv)
    print(f"[train] compile cache: {enable_compile_cache()}")
    ts = setup(args)
    cfg, mesh, sync_cfg = ts.cfg, ts.mesh, ts.sync_cfg
    ar_model, comm_obs = ts.ar_model, ts.comm_obs
    build_engine = ts.engine

    plan_in = Plan.load(args.plan_in) if args.plan_in else None
    state_box = {"eng": build_engine(plan_in)}

    opt = ts.opt
    data = make_stream(
        DataConfig(
            vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
            seed=args.seed, input_mode=cfg.input_mode, d_model=cfg.d_model,
        )
    )
    monitor = StragglerMonitor()
    timer = StepTimer(window=max(8, args.replan_every or 8))

    make_step = ts.train_step

    tuner: Tuner | None = None
    if args.autotune:
        tuner = Tuner(
            layout=state_box["eng"].plan.layout,
            n_scan_stages=cfg.n_stages,
            shapes=param_specs(cfg),
            wire_dtype=jnp.dtype(sync_cfg.wire_dtype).name,
            provenance={"arch": cfg.name},
        )
        # probe inputs are only materialized (and their jitted probes only
        # built) when the tuner actually needs them — a plain run must not
        # pin a second copy of the parameters
        probe_batch = jax.tree.map(jnp.asarray, data.batch_at(0))
        probe_params = init_params(jax.random.PRNGKey(args.seed), cfg)
        state_box["probes"] = make_unit_probes(cfg, probe_params, probe_batch)
    if args.comm_refit_every:
        state_box["refitter"] = CommRefitter(
            base=comm_obs, threshold=args.comm_drift_threshold,
        )
        state_box["psum_time"] = psum_time_fn(mesh, ("data",))

    def measured_unit_costs() -> MeasuredCosts:
        """Per-unit probe times -> measured cost vector (non-uniform drift,
        unlike the whole-step rescale)."""
        eng = state_box["eng"]
        profile = probe_unit_times(
            cfg, probe_params, probe_batch, eng.plan.layout,
            probes=state_box["probes"],
        )
        return MeasuredCosts.from_segment_times(
            list(eng.plan.costs), eng.plan.hw, profile.unit_seconds,
            name="probe_segments",
        )

    def adopt_plan(plan: Plan, why: str) -> None:
        state_box["eng"] = build_engine(plan, from_tuner=True)
        state_box["step_fn"] = make_step(state_box["eng"])
        timer.reset()
        print(f"[train] {why} -> {state_box['eng'].plan.describe()}")

    def tuner_sweep(costs: MeasuredCosts, model, comm_source: str, trigger: str) -> Plan:
        assert tuner is not None
        return tuner.sweep(
            costs.layer_costs(), model, costs.hw,
            cost_source=costs.name, comm_source=comm_source, trigger=trigger,
        )

    if args.autotune:
        measured = measured_unit_costs()
        plan = tuner_sweep(
            measured, ar_model,
            "measured" if args.measure_comm else "analytic", "startup",
        )
        adopt_plan(plan, "autotune startup sweep "
                         f"({tuner.last_record.chosen}, "
                         f"{len(tuner.last_record.candidates)} candidates)")
    else:
        state_box["step_fn"] = make_step(state_box["eng"])
        print(f"[train] {state_box['eng'].plan.describe()}")
    print(f"[train] scan segments: {state_box['eng'].segments}")

    def init_state() -> RunState:
        # one replica per device, committed once: every step then takes
        # the shardings its outputs come back with, and compiles once.
        # Each device draws its own replica, so no device holds an extra
        # unreplicated copy or the draw's f32 temporaries.
        eng = state_box["eng"]
        replicated = NamedSharding(mesh, P())
        params = jax.jit(init_params, static_argnums=1, out_shardings=replicated)(
            jax.random.PRNGKey(args.seed), cfg)
        residual = eng.init_residual(params, mesh)
        return RunState(
            step=0, params=params,
            opt_state=jax.device_put(opt.init(params), replicated),
            residual=None if residual is None
            else jax.device_put(residual, NamedSharding(mesh, P(eng.dp_axes))),
        )

    if args.dryrun:
        _dryrun(args, state_box["eng"], state_box["step_fn"], init_state, data, mesh)
        return None

    def maybe_replan(step: int) -> None:
        """Measured-profile drift check (journal MG-WFBP online re-plan)."""
        eng = state_box["eng"]
        modeled = eng.plan.schedule.result
        measured_t = timer.median()
        if modeled is None or measured_t is None or len(timer) < 5:
            return
        if tuner is not None:
            tuner.observe(measured_t)
            measured = measured_unit_costs()
            drift = cost_drift(eng.plan, measured)
            if drift > args.replan_threshold:
                plan = tuner_sweep(
                    measured, eng.plan.ar_model,
                    eng.plan.provenance.get("comm_source", "analytic"),
                    "cost_drift",
                )
                adopt_plan(plan, f"step {step}: cost drift {drift:.3f} re-sweep")
            return
        measured = MeasuredCosts.from_step_timing(
            list(eng.plan.costs), eng.plan.hw, measured_t, modeled.t_iter
        )
        new_eng, replanned = eng.replan(measured, threshold=args.replan_threshold)
        if replanned:
            state_box["eng"] = new_eng
            state_box["step_fn"] = make_step(new_eng)
            # The rebuilt step recompiles and the old engine's samples no
            # longer describe the new segmentation — restart the window.
            timer.reset()
            print(f"[train] step {step}: re-planned "
                  f"(drift {new_eng.plan.provenance['drift']}) -> "
                  f"{new_eng.plan.schedule.describe()}")

    def maybe_refit_comm(step: int) -> None:
        """Amortized comm-side drift check: slim psum sweep -> EWMA ->
        (α, β) re-fit -> re-plan past the threshold."""
        refitter = state_box.get("refitter")
        if refitter is None:
            return
        fit, drift, drifted = refitter.check(state_box["psum_time"])
        if not drifted:
            return
        eng = state_box["eng"]
        if tuner is not None:
            plan = tuner_sweep(
                MeasuredCosts(costs=tuple(eng.plan.costs), hw=eng.plan.hw,
                              name=eng.plan.provenance.get("cost_source", "analytic")),
                fit, "measured_comm_refit", "comm_drift",
            )
            adopt_plan(plan, f"step {step}: comm drift {drift:.3f} "
                             f"(α={fit.a:.3e} β={fit.b:.3e}) re-sweep")
        else:
            new_plan, replanned = refitter.replan(eng.plan, fit)
            if replanned:
                adopt_plan(new_plan, f"step {step}: comm drift {drift:.3f} re-plan")

    track_time = bool(args.replan_every or args.comm_refit_every or args.autotune)
    losses: dict[int, jax.Array] = {}  # by step: a restart overwrites its redo

    def do_step(state: RunState, step: int) -> RunState:
        # host spans on the profiler's clock: a trace puts each device
        # idle gap down to what the loop was doing (a few µs untraced)
        with jax.profiler.StepTraceAnnotation("train", step_num=step):
            return _do_step(state, step)

    def _do_step(state: RunState, step: int) -> RunState:
        with jax.profiler.TraceAnnotation("train.batch"):
            batch = jax.tree.map(jnp.asarray, data.batch_at(step))
        eng = state_box["eng"]
        timer.start()
        with jax.set_mesh(mesh), jax.profiler.TraceAnnotation("train.dispatch"):
            if eng.stateful:
                p, o, res, m = state_box["step_fn"](
                    state.params, state.opt_state, state.residual, batch
                )
            else:
                p, o, m = state_box["step_fn"](state.params, state.opt_state, batch)
                res = state.residual
        if track_time:
            # timing needs a host-device sync; skip both when every online
            # check is off so the dispatch pipeline stays async
            with jax.profiler.TraceAnnotation("train.wait"):
                jax.block_until_ready(p)
            timer.stop()
            if args.replan_every and step and step % args.replan_every == 0:
                with jax.profiler.TraceAnnotation("train.replan"):
                    maybe_replan(step)
            if args.comm_refit_every and step and step % args.comm_refit_every == 0:
                with jax.profiler.TraceAnnotation("train.refit"):
                    maybe_refit_comm(step)
        losses[step] = m["loss"]
        if step % 10 == 0:
            with jax.profiler.TraceAnnotation("train.log"):
                print(f"[train] step {step} loss {float(m['loss']):.4f}")
        return RunState(step=state.step, params=p, opt_state=o,
                        restarts=state.restarts, residual=res)

    def on_restart(state: RunState) -> RunState:
        # Same-shape restart: resume under the exact plan the checkpoint
        # was trained with (saved beside the weights), and under --autotune
        # resume the tuner's sweep history too; elastic restarts (no stored
        # plan / different N) re-enter the plan search instead.
        plan = None
        how = "re-planned"
        ck = latest_step(args.ckpt_dir)
        if ck is not None:
            try:
                plan = load_plan(args.ckpt_dir, ck)
                if plan is not None:
                    state_box["eng"] = build_engine(plan, from_tuner=args.autotune)
                    how = "restored plan"
            except Exception as e:  # corrupt/foreign/mismatched plan -> re-plan
                print(f"[train] stored plan unusable ({e}); re-planning")
                plan = None
            if tuner is not None:
                try:
                    st = load_tuner_state(args.ckpt_dir, ck)
                    if st is not None:
                        tuner.load_state(st)
                        if st.get("comm_refitter") and "refitter" in state_box:
                            state_box["refitter"] = CommRefitter.from_state_dict(
                                st["comm_refitter"]
                            )
                except Exception as e:
                    print(f"[train] stored tuner state unusable ({e}); starting fresh")
        if plan is None:
            if tuner is not None:
                plan = tuner_sweep(
                    measured_unit_costs(), ar_model,
                    "measured" if args.measure_comm else "analytic", "restart",
                )
                state_box["eng"] = build_engine(plan, from_tuner=True)
                how = "restart sweep"
            else:
                state_box["eng"] = build_engine()
        state_box["step_fn"] = make_step(state_box["eng"])
        timer.reset()
        print(f"[train] restart at step {state.step}: {how} -> "
              f"{state_box['eng'].plan.schedule.describe()}")
        return state

    def tuner_state() -> dict:
        """Checkpointed tuner state: sweep history + the comm refitter's
        EWMA'd observations, so BOTH online loops resume after a restart."""
        st = tuner.state_dict()
        if state_box.get("refitter") is not None:
            st["comm_refitter"] = state_box["refitter"].state_dict()
        return st

    t0 = time.time()
    final = resilient_loop(
        num_steps=args.steps,
        init_state=init_state,
        train_step=do_step,
        checkpoint_dir=args.ckpt_dir,
        checkpoint_every=args.ckpt_every,
        max_restarts=args.max_restarts,
        straggler=monitor,
        on_restart=on_restart,
        # every checkpoint carries the live plan (--plan-out made automatic)
        # and, when auto-tuning, the tuner's sweep history + comm observations
        plan_provider=lambda: state_box["eng"].plan,
        tuner_provider=tuner_state if tuner is not None else None,
    )
    if tuner is not None and timer.median() is not None and tuner.history:
        rec = tuner.observe(timer.median())
        print(f"[train] tuner: chosen={rec.chosen} "
              f"predicted_t_iter={rec.predicted_t_iter:.3e}s "
              f"observed_t_iter={rec.observed_t_iter:.3e}s "
              f"over {len(rec.candidates)} candidates")
    print(f"[train] done: {final.step} steps, {final.restarts} restarts, "
          f"{time.time() - t0:.1f}s, {monitor.remediations} straggler remediations")
    if args.plan_out:
        path = state_box["eng"].plan.save(args.plan_out)
        print(f"[train] plan written to {path}")
    return TrainResult(final=final, losses=[float(losses[k]) for k in sorted(losses)])


if __name__ == "__main__":
    main()

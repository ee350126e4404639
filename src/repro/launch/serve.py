"""Serving launcher: continuous batching through ``serving.ServingEngine``
under a fabric-priced ``ServePlan`` — and, with ``--sharded``, the plan
*executed* on a virtual TP mesh.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --reduced \\
        --slots 4 --requests 8 --prompt-len 32 --tokens 16 \\
        --fabric gpu_nccl --plan-out /tmp/serve_plan.json

    # execute the plan: sharded decode over a virtual TP mesh, measured
    # serve fabrics, predicted-vs-observed per group
    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \\
        --reduced --virtual-tp 4 --sharded --measure-comm

There is ONE serving code path: this launcher builds the decode-side
``ServePlan`` (the same merge math as training, priced by the selected
fabric preset — KV all-gathers for dense archs, expert all-to-alls for
MoE), hands it to the ``ServingEngine`` (continuous batching: requests
join free slots, finished rows free them immediately), and reports
throughput against the plan's predicted step time.  ``--sharded`` runs
the engine's decode under ``shard_map`` on a ``--virtual-tp``-wide mesh
where every scheduled serve group issues exactly one fused collective
(``serving.sharded``); ``--measure-comm`` times the real per-group
collectives, fits op-specific (α, β) constants into a ``MeasuredFabric``
(``'all_gather@model'``-style keys), and prints the predicted-vs-measured
per-group table.
"""

from __future__ import annotations

import os
import sys


def _requested_virtual_tp() -> int:
    """Pre-argparse scan for ``--virtual-tp N`` / ``--virtual-tp=N``."""
    for i, arg in enumerate(sys.argv):
        try:
            if arg == "--virtual-tp":
                return int(sys.argv[i + 1])
            if arg.startswith("--virtual-tp="):
                return int(arg.split("=", 1)[1])
        except (IndexError, ValueError):
            break
    return 8


if os.environ.get("JAX_PLATFORMS") == "cpu" and (
    "--sharded" in sys.argv or "--measure-comm" in sys.argv
):
    # CPU rehearsal only: the TP mesh needs virtual CPU devices before jax
    # initializes.  Anywhere else the mesh takes real devices or fails.
    from .mesh import ensure_virtual_devices

    ensure_virtual_devices(_requested_virtual_tp())

import argparse
import dataclasses
import signal
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_NAMES, get_config, get_reduced
from ..core.cost_model import hardware_for
from ..fabric import MeasuredFabric, available_fabrics
from ..launch.compile_cache import enable_compile_cache
from ..launch.mesh import make_mesh
from ..launch.specs import param_specs
from ..models.transformer import init_params
from ..planning import (
    available_policies,
    build_serve_plan,
    group_comparison_lines,
    serve_fabric_fits,
    time_serve_groups,
)
from ..runtime import StragglerMonitor
from ..serving import (
    ChaosConfig,
    ChaosInjector,
    Request,
    ServeTimer,
    ServingEngine,
    resilient_serve_loop,
)


def main(argv: list[str] | None = None) -> list[Request]:
    """Run the launcher on ``argv`` (default: the command line) and
    return the completed requests."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_NAMES)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode batch slots (continuous batching)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights and the request prompts")
    ap.add_argument("--fabric", default="tpu_v5e",
                    choices=available_fabrics(),
                    help="interconnect preset pricing the decode collectives: "
                         f"{', '.join(available_fabrics())}")
    ap.add_argument("--policy", default="mg_wfbp",
                    choices=list(available_policies()),
                    help="scheduler policy for the serve plan")
    ap.add_argument("--virtual-tp", type=int, default=8,
                    help="TP size of the serve-plan collective model (and of "
                         "the mesh under --sharded, which needs that many "
                         "devices)")
    ap.add_argument("--sharded", action="store_true",
                    help="execute the plan: sharded decode on a "
                         "--virtual-tp-wide mesh, one fused collective per "
                         "serve group")
    ap.add_argument("--measure-comm", action="store_true",
                    help="time the real per-group collectives, fit a "
                         "MeasuredFabric, and print predicted-vs-measured "
                         "(implies --sharded's mesh)")
    ap.add_argument("--plan-out", default=None,
                    help="write the ServePlan JSON here")
    # resilience: any of these routes the run through resilient_serve_loop
    ap.add_argument("--chaos-kill-every", type=int, default=0,
                    help="inject a deterministic kill every N serve steps "
                         "(0 = off); the loop must recover token-identically")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed for the chaos fault schedule")
    ap.add_argument("--chaos-slow-factor", type=float, default=1.0,
                    help="multiply observed step/collective times by this "
                         "once --chaos-slow-after is reached (degraded wire)")
    ap.add_argument("--chaos-slow-after", type=int, default=None,
                    help="serve step after which the injected slowdown starts")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request SLO: deadline = now + this; expired "
                         "requests retire with partial output, unmeetable "
                         "waiting requests are shed")
    ap.add_argument("--snapshot-every", type=int, default=8,
                    help="serve snapshot cadence in steps")
    ap.add_argument("--snapshot-dir", default=None,
                    help="serve snapshot directory (temp dir when resilience "
                         "is active and this is unset)")
    ap.add_argument("--max-restarts", type=int, default=5,
                    help="restart budget for the resilient serve loop")
    args = ap.parse_args(argv)

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if args.reduced:
        cfg = dataclasses.replace(cfg, param_dtype=jnp.float32)
    hw = hardware_for(jax.devices()[0])
    mesh = None
    tp = args.virtual_tp
    if args.sharded or args.measure_comm:
        if jax.device_count() < tp:
            ap.error(f"--virtual-tp {tp} needs {tp} devices; "
                     f"{jax.device_count()} {jax.devices()[0].platform} "
                     "device(s) are visible")
        mesh = make_mesh((tp,), ("model",))
    print(f"[serve] compile cache: {enable_compile_cache()}")
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    max_seq = args.prompt_len + args.tokens + 1

    # ServingEngine allocates fp32 decode caches, so the executed wire
    # ships 4-byte elements — price the plan at what the step ships
    cache_bytes = 4
    plan = build_serve_plan(
        cfg, param_specs(cfg), args.fabric, {"model": tp},
        batch_rows=args.slots, policy=args.policy, hw=hw,
        cache_dtype_bytes=cache_bytes, act_dtype_bytes=cache_bytes,
    )
    print(f"[serve] {plan.describe()}")

    sample = None
    if args.temperature > 0:
        # two-arg (logits, key) form: the key threads through the jitted
        # step's donated state, so sampling never forces a host round-trip
        def sample(logits, key):
            return jax.random.categorical(key, logits / args.temperature, axis=-1)

    timer = ServeTimer()
    engine = ServingEngine(
        cfg, params, slots=args.slots, max_seq=max_seq, sample=sample,
        sample_seed=2, plan=plan, mesh=mesh if args.sharded else None,
        timer=timer,
    )

    engine.warmup()  # compile the full-batch step before anything is timed
    if plan.schedule.result is not None:
        plan = engine.calibrate_plan()
        wire = plan.schedule.result.t_iter
        print(f"[serve] calibrated step: fixed={plan.t_step_fixed * 1e6:.1f}us"
              f" + wire={wire * 1e6:.1f}us"
              f" = {(plan.t_step_fixed + wire) * 1e6:.1f}us")
    resilient = (
        args.chaos_kill_every > 0
        or args.chaos_slow_factor != 1.0
        or args.deadline_ms is not None
        or args.snapshot_dir is not None
    )

    def submit_all(eng, deadline_s=None):
        rng = np.random.default_rng(args.seed)
        for rid in range(args.requests):
            eng.submit(Request(
                rid=rid,
                prompt=rng.integers(0, cfg.vocab, size=args.prompt_len,
                                    dtype=np.int32),
                max_new_tokens=args.tokens,
                deadline_s=deadline_s,
            ))

    if resilient:
        baseline_tokens = None
        if args.chaos_kill_every > 0 and args.deadline_ms is None:
            # uninterrupted reference run: the chaos run must reproduce it
            ref = ServingEngine(
                cfg, params, slots=args.slots, max_seq=max_seq, sample=sample,
                sample_seed=2, plan=plan, mesh=mesh if args.sharded else None,
            )
            submit_all(ref)
            baseline_tokens = {
                r.rid: r.generated for r in ref.run_to_completion()
            }

        chaos = ChaosInjector(ChaosConfig(
            seed=args.chaos_seed,
            kill_every=args.chaos_kill_every,
            slow_factor=args.chaos_slow_factor,
            slow_after=args.chaos_slow_after,
        ))
        straggler = (
            StragglerMonitor(window=16, factor=2.0, patience=2)
            if args.chaos_slow_factor != 1.0 else None
        )
        snap_dir = args.snapshot_dir or tempfile.mkdtemp(prefix="serve_snap_")
        deadline_s = (
            time.monotonic() + args.deadline_ms / 1e3
            if args.deadline_ms is not None else None
        )
        submit_all(engine, deadline_s=deadline_s)

        # graceful SIGINT: first ^C snapshots and exits cleanly; the
        # loop's own handler re-raises a second one immediately
        stop = {"flag": False}

        def _sigint(signum, frame):
            print("[serve] SIGINT: snapshotting before exit...")
            stop["flag"] = True

        prev_handler = signal.signal(signal.SIGINT, _sigint)
        t0 = time.time()
        try:
            report = resilient_serve_loop(
                engine,
                snapshot_dir=snap_dir,
                snapshot_every=args.snapshot_every,
                max_restarts=args.max_restarts,
                chaos=chaos,
                straggler=straggler,
                stop_flag=lambda: stop["flag"],
            )
        finally:
            signal.signal(signal.SIGINT, prev_handler)
        dt = time.time() - t0
        completed = report.completed

        mean_rec = (
            sum(report.recovery_times_s) / len(report.recovery_times_s)
            if report.recovery_times_s else 0.0
        )
        tokens_match = ""
        if baseline_tokens is not None:
            got = {r.rid: r.generated for r in completed}
            tokens_match = f" tokens_match={got == baseline_tokens}"
        print(f"[serve] resilience: restarts={report.restarts} "
              f"recovery_mean_s={mean_rec:.3f} snapshots={report.snapshots} "
              f"fallbacks={report.snapshot_fallbacks} shed={report.shed} "
              f"expired={report.expired} replans={report.replans} "
              f"interrupted={report.interrupted} "
              f"goodput_tok_s={report.goodput_tok_per_s:.1f}"
              f"{tokens_match} (snapshots in {snap_dir})")
    else:
        submit_all(engine)
        t0 = time.time()
        completed = engine.run_to_completion()
        dt = time.time() - t0
    n_tok = sum(len(r.generated) for r in completed)
    dev = jax.devices()[0]
    mode = (f"sharded TP={tp}" if args.sharded else "unsharded") + \
        f" on {dev.platform} {dev.device_kind}"
    print(f"[serve] {len(completed)} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok / max(dt, 1e-9):.1f} tok/s, {args.slots} slots, {mode})")
    predicted = engine.predicted_step_time()
    observed = engine.observed_step_time()
    if predicted is not None:
        print(f"[serve] plan predicted step: {predicted * 1e3:.3f}ms "
              f"({plan.op} over {plan.axis_sizes} on {plan.fabric})")
    if observed is not None:
        print(f"[serve] observed step: {observed * 1e3:.3f}ms "
              f"(observed/predicted = {observed / predicted:.1f}x)"
              if predicted else
              f"[serve] observed step: {observed * 1e3:.3f}ms")

    if args.measure_comm:
        assert mesh is not None
        fits = serve_fabric_fits(mesh, ops=(plan.op,), axes=("model",))
        fab = MeasuredFabric(models=fits, name="measured_serve")
        for key, fit in fits.items():
            print(f"[serve] measured fit {key}: a={fit.a:.3e}s b={fit.b:.3e}s/B")
        measured_plan = build_serve_plan(
            cfg, param_specs(cfg), fab, {"model": tp},
            batch_rows=args.slots, policy=args.policy, op=plan.op, hw=hw,
            cache_dtype_bytes=cache_bytes, act_dtype_bytes=cache_bytes,
        )
        print(f"[serve] measured-fabric plan: {measured_plan.describe()}")
        group_s = time_serve_groups(plan, mesh)
        timer.group_times = group_s
        print("[serve] per-group predicted vs measured:")
        for line in group_comparison_lines(plan, group_s):
            print("  " + line)

    if args.plan_out:
        path = plan.save(args.plan_out)
        print(f"[serve] serve plan written to {path}")
    return completed


if __name__ == "__main__":
    main()

"""Serving example: fabric-priced decode plans, continuous batching, and
the plan *executed* — the same prompts decoded sharded and unsharded.

Builds the decode-side ServePlan for two interconnect presets on one
arch and prints how the chosen fabric moves the merge set — the TPU's
microsecond startup keeps per-stage KV all-gathers separate, while
NCCL-class launch overhead merges them (Eq. 10: the merge gain IS α) —
then runs the request batch through the one serving code path
(``serving.ServingEngine``) twice: unsharded, and sharded over a virtual
TP mesh where every scheduled serve group issues exactly one fused
collective.  The tokens must match exactly; the closing table leads
with the calibrated fixed-vs-wire step decomposition (probed
compute+dispatch + plan wire timeline — the honest predicted step) and
shows each group's predicted collective time next to a real measured
one (``planning.time_serve_groups``) — see docs/fabrics.md.

    PYTHONPATH=src python examples/serve_decode.py --arch tinyllama-1.1b \\
        --fabric gpu_nccl --tokens 12
"""

import argparse
import sys
import time

sys.path.insert(0, "src")

# the sharded half of the demo wants a few virtual CPU devices; the flag
# must land before jax initializes its backend
from repro.launch.mesh import ensure_virtual_devices

ensure_virtual_devices(4)

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_mesh
from repro.configs import ARCH_NAMES, get_config, get_reduced
from repro.launch.specs import param_specs
from repro.models.transformer import init_params
from repro.planning import (
    build_serve_plan,
    group_comparison_lines,
    time_serve_groups,
)
from repro.serving import Request, ServeTimer, ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=ARCH_NAMES)
    ap.add_argument("--fabric", default="tpu_v5e",
                    help="fabric preset the engine's plan is priced on")
    ap.add_argument("--compare", default="gpu_nccl",
                    help="second preset for the plan-difference table")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds params, prompts, and the engine's sampling "
                         "key — the whole demo is reproducible per seed")
    args = ap.parse_args()

    # Plan differences are shown at the FULL arch scale (per-stage decode
    # compute large enough that fabric startup moves the merge set); the
    # engine then runs the reduced config so the demo stays CPU-friendly.
    full_cfg = get_config(args.arch)
    full_shapes = param_specs(full_cfg)
    print(f"== decode plans, {args.arch} @ 16 rows, TP=8 ==")
    plans = {}
    for preset in dict.fromkeys((args.fabric, args.compare, "tpu_v5e")):
        plan = build_serve_plan(full_cfg, full_shapes, preset, {"model": 8},
                                batch_rows=16)
        plans[preset] = plan
        r = plan.schedule.result
        print(f"  {preset:12s} α={plan.model.a:.2e}s  "
              f"{len(plan.schedule.groups):2d} groups  "
              f"t_step={r.t_iter * 1e6:7.1f}µs  "
              f"exposed_comm={r.t_comm_exposed * 1e6:6.1f}µs  ({plan.op})")
    a, b = args.fabric, args.compare
    if len(plans[a].schedule.groups) != len(plans[b].schedule.groups):
        print(f"  -> {a} and {b} pick different merge sets from the SAME "
              f"cost vector: only the fabric's (α, β) moved.")

    cfg = dataclasses.replace(get_reduced(args.arch), param_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    tp = min(4, jax.device_count())
    mesh = make_mesh((tp,), ("model",))
    # the reduced engine runs fp32 caches: price the wire at 4 bytes/elem
    # so the measured group collectives ship exactly the predicted bytes
    plan = build_serve_plan(cfg, param_specs(cfg), args.fabric, {"model": tp},
                            batch_rows=args.slots,
                            cache_dtype_bytes=4, act_dtype_bytes=4)

    def run(mesh_arg):
        engine = ServingEngine(
            cfg, params, slots=args.slots,
            max_seq=args.prompt_len + args.tokens + 1, plan=plan,
            sample_seed=args.seed, mesh=mesh_arg,
            timer=ServeTimer(skip_first=1),
        )
        # compile + probe before the timed loop: the printed tok/s and
        # step times are steady-state dispatch, never compilation
        engine.warmup()
        engine.calibrate_plan()
        rng = np.random.default_rng(args.seed)
        for rid in range(args.requests):
            engine.submit(Request(
                rid=rid,
                prompt=rng.integers(0, cfg.vocab, size=args.prompt_len, dtype=np.int32),
                max_new_tokens=args.tokens,
            ))
        t0 = time.time()
        completed = engine.run_to_completion()
        return completed, time.time() - t0, engine

    for label, mesh_arg in (("unsharded", None), (f"sharded TP={tp}", mesh)):
        completed, dt, engine = run(mesh_arg)
        n_tok = sum(len(r.generated) for r in completed)
        print(f"\n== engine, {label} ({args.fabric} plan, reduced arch) ==")
        print(f"{len(completed)} requests, {n_tok} tokens in {dt:.2f}s "
              f"({n_tok / max(dt, 1e-9):.1f} tok/s)")
        print("sample request 0:", completed[0].generated)
        if mesh_arg is None:
            base = {r.rid: r.generated for r in completed}
        else:
            match = base == {r.rid: r.generated for r in completed}
            print(f"tokens match unsharded run: {match}")
            obs = engine.observed_step_time()
            cal = engine.plan  # calibrated copy: wire + probed fixed term
            pred = cal.predicted_step_time()
            wire = cal.schedule.result.t_iter
            print(f"step decomposition: fixed {cal.t_step_fixed * 1e3:.3f}ms "
                  f"(compute+dispatch, probed) + wire {wire * 1e3:.3f}ms "
                  f"(plan timeline) = {pred * 1e3:.3f}ms predicted")
            if obs is not None:
                print(f"observed step: {obs * 1e3:.3f}ms "
                      f"(observed/predicted = {obs / pred:.2f}x)")
            print("per-group predicted vs measured collective:")
            for line in group_comparison_lines(cal, time_serve_groups(cal, mesh)):
                print("  " + line)


if __name__ == "__main__":
    main()

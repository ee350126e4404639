import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell, prove memory fits, and extract the roofline terms.

For each cell this script:

  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. lowers and compiles the full train_step / serve_step with production
     shardings (chunk 'map' mode -> realistic buffer reuse), printing
     ``compiled.memory_analysis()`` and ``compiled.cost_analysis()``,
  3. lowers the cost segments ('unroll' mode) and recomposes exact
     per-device FLOPs / bytes / collective traffic (see segments.py),
  4. derives the three roofline terms (compute / memory / collective)
     with the v5e constants, and
  5. appends a JSON record under benchmarks/results/dryrun/.

Usage:
  python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--skip-segments]
"""

import argparse
import dataclasses
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES, get_config
from repro.configs.shapes import LONG_CONTEXT_SKIP, SHAPES, applicable_shapes
from repro.core.profiler import parse_collectives
from repro.launch.mesh import make_production_mesh
from repro.launch.segments import (
    head_fwd_segment,
    head_train_segment,
    stage_fwd_segment,
    stage_train_segment,
)
from repro.launch.specs import (
    arch_config_for_shape,
    batch_input_specs,
    cache_specs,
    decode_input_specs,
    opt_state_specs,
    param_specs,
)
from repro.launch.steps import make_decode_step, make_prefill_step, make_train_step
from repro.optim import make_optimizer
from repro.parallel.sharding import (
    batch_specs,
    cache_pspecs,
    named,
    param_pspecs,
    rules_for_arch,
)

# v5e constants (per chip) — the roofline denominators
PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"


def model_flops_per_step(cfg, shape) -> float:
    """Paper-style useful flops: 6·N_active·tokens (train), 2·N_active·tokens (serve)."""
    import numpy as np

    shapes = param_specs(cfg)
    n_total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    if cfg.moe is not None:
        stages = shapes["stages"]
        n_exp = sum(
            int(np.prod(x.shape))
            for k, x in jax.tree_util.tree_leaves_with_path(stages)
            if any(str(getattr(p, "key", "")) in ("w_gate", "w_up", "w_down") for p in k)
        )
        n_active = n_total - n_exp + n_exp * cfg.moe.top_k / cfg.moe.n_experts
    else:
        n_active = n_total
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch  # decode: one token per row


def lower_cell(arch: str, shape_name: str, multi_pod: bool, fsdp_data=True,
               n_microbatches: int = 1, skip_segments: bool = False,
               overrides: dict | None = None, comm_fit: dict | None = None,
               fabric: str = "tpu_v5e") -> dict:
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = arch_config_for_shape(arch, shape_name, cost_mode=False)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    rules = rules_for_arch(cfg, mesh, fsdp_data=fsdp_data)
    n_dev = mesh.devices.size

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev,
        "fsdp_data": fsdp_data,
        "n_microbatches": n_microbatches,
    }

    p_shapes = param_specs(cfg)
    p_sh = named(param_pspecs(p_shapes, rules), mesh)

    t0 = time.time()
    if shape.kind == "train":
        from repro.optim.optimizers import OptState

        opt = make_optimizer("adamw")
        o_shapes = opt_state_specs(cfg, opt)
        # optimizer state shards like its parameter (FSDP/ZeRO for free)
        o_sh = OptState(
            step=jax.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            m=p_sh,
            v=p_sh,
        )
        b_specs = batch_input_specs(cfg, shape)
        b_sh = named(batch_specs(cfg, rules, shape.global_batch, shape.seq_len), mesh)
        step = make_train_step(cfg, rules, opt, n_microbatches=n_microbatches)
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                step, in_shardings=(p_sh, o_sh, b_sh), out_shardings=(p_sh, o_sh, None),
                donate_argnums=(0, 1),
            ).lower(p_shapes, o_shapes, b_specs)
            compiled = lowered.compile()
    elif shape.kind == "prefill":
        b_specs = batch_input_specs(cfg, shape)
        b_specs.pop("targets")
        bsp = batch_specs(cfg, rules, shape.global_batch, shape.seq_len)
        bsp.pop("targets")
        b_sh = named(bsp, mesh)
        step = make_prefill_step(cfg, rules, max_seq=shape.seq_len)
        with jax.set_mesh(mesh):
            lowered = jax.jit(step, in_shardings=(p_sh, b_sh)).lower(p_shapes, b_specs)
            compiled = lowered.compile()
    else:  # decode
        c_shapes = cache_specs(cfg, batch=shape.global_batch, max_seq=shape.seq_len)
        c_sh = named(cache_pspecs(cfg, rules, c_shapes, shape.global_batch), mesh)
        b_specs = decode_input_specs(cfg, shape)
        bsp = batch_specs(cfg, rules, shape.global_batch, 1)
        bsp.pop("targets")
        b_sh = named(bsp, mesh)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        step = make_decode_step(cfg, rules)
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                step, in_shardings=(p_sh, c_sh, b_sh, None), donate_argnums=(1,),
            ).lower(p_shapes, c_shapes, b_specs, pos)
            compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 1)

    ma = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_per_device_gib": round(
            (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2**30, 3
        ),
    }
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, list):  # jax 0.4.x: one dict per computation
        ca = ca[0] if ca else {}
    rec["whole_program"] = {
        "flops_per_device": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "collectives": dataclasses.asdict(parse_collectives(compiled.as_text())),
    }
    print(f"[{arch} x {shape_name} x {rec['mesh']}] compile {rec['compile_s']}s")
    print("  memory_analysis:", ma)
    print("  cost_analysis flops/device:", rec["whole_program"]["flops_per_device"])

    if not skip_segments:
        rec["segments"] = segment_costs(arch, shape_name, mesh, rules, overrides)
        rec["totals"] = recompose(cfg, shape, rec, n_dev)
    if shape.kind == "train":
        rec["plan"] = plan_record(cfg, shape, rec.get("segments"), mesh, n_dev,
                                  comm_fit=comm_fit, fabric=fabric)
    elif shape.kind == "decode":
        rec["serve_plan"] = serve_plan_record(cfg, shape, mesh, fabric=fabric)
    return rec


def serve_plan_record(cfg, shape, mesh, fabric: str = "tpu_v5e") -> dict:
    """Serialized decode-side ServePlan for this cell: the same merge math
    as the train plan, pricing the decode collective (KV all-gather /
    expert all-to-all) on the selected fabric over the mesh's model axis."""
    from repro.launch.specs import param_specs
    from repro.planning import build_serve_plan

    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    plan = build_serve_plan(
        cfg, param_specs(cfg), fabric,
        {"model": axis_sizes.get("model", 1)},
        batch_rows=shape.global_batch,
        provenance={"shape": shape.name},
    )
    import textwrap

    print(textwrap.indent(plan.describe(), "  "))
    return plan.to_json_dict()


def plan_record(cfg, shape, segs, mesh, n_dev, comm_fit=None,
                fabric: str = "tpu_v5e") -> dict:
    """Serialized MG-WFBP plan(s) for this train cell.

    The analytic plan comes from Eq. 18 costs priced by the selected
    ``--fabric`` preset; when HLO segments were profiled, a measured plan
    re-runs the policy on per-unit segment times
    (``MeasuredCosts.from_segment_times``) — the dry-run analogue of the
    journal version's online re-plan.  ``comm_fit`` (a serialized
    ``MeasuredComm`` sweep, --comm-fit) swaps the analytic α–β model for
    a measured fit.  Restarts and benchmarks reload these records
    instead of recomputing Algorithm 1; each plan carries its per-group
    arena wire layout (the packed buffer sizes).
    """
    from repro.core.bucketing import stacked_lm_layout
    from repro.fabric import get_fabric
    from repro.core.cost_model import TPU_V5E as HW_V5E
    from repro.core.trainer import lm_unit_costs
    from repro.planning import MeasuredComm, MeasuredCosts, build_plan, replan_if_drifted

    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model_shards = axis_sizes.get("model", 1)
    dp_axes = {k: v for k, v in axis_sizes.items() if k in ("pod", "data")}
    shapes_tree = param_specs(cfg)
    costs = lm_unit_costs(
        cfg, shapes_tree,
        tokens_per_device=shape.global_batch * shape.seq_len // n_dev,
        model_shards=model_shards,
    )
    layout = stacked_lm_layout(shapes_tree, cfg.n_stages, model_shards=model_shards)
    if comm_fit is not None:
        ar_model = MeasuredComm(
            sizes_bytes=tuple(comm_fit["sizes_bytes"]),
            times_s=tuple(comm_fit["times_s"]),
            axes=tuple(comm_fit.get("axes", ("data",))),
        ).fit()
        comm_source = "measured_comm"
    else:
        ar_model = get_fabric(fabric).cost("all_reduce", dp_axes)
        comm_source = fabric
    plan = build_plan(
        layout, costs, ar_model,
        policy="mg_wfbp", n_scan_stages=cfg.n_stages,
        provenance={"arch": cfg.name, "comm_source": comm_source},
    )
    out = {"analytic": plan.to_json_dict()}
    out["arena"] = [
        {"nbytes": a.nbytes, "n_slots": len(a.slots)}
        for a in plan.group_arenas(shapes_tree)
    ]
    if segs:
        # Segment roofline time covers fwd+bwd of a train segment; split
        # it 1/3 fwd + 2/3 bwd (the 2:4 flops ratio of Eq. 17/18).
        def seg_t(s):
            return max(s["flops"] / PEAK_FLOPS, s["bytes_accessed"] / HBM_BW)

        unit_seconds = {f"stage_{i}": 2 / 3 * seg_t(segs["stage"])
                        for i in range(cfg.n_stages)}
        if "tail" in segs:
            unit_seconds["tail"] = 2 / 3 * seg_t(segs["tail"])
        unit_seconds["head"] = 2 / 3 * seg_t(segs["head"])
        measured = MeasuredCosts.from_segment_times(
            costs, HW_V5E, unit_seconds, name="hlo_segments"
        )
        mplan, replanned = replan_if_drifted(plan, measured, threshold=0.05)
        out["measured"] = mplan.to_json_dict()
        out["replanned"] = replanned
    return out


def segment_costs(arch: str, shape_name: str, mesh, rules, overrides=None) -> dict:
    shape = SHAPES[shape_name]
    cfg = arch_config_for_shape(arch, shape_name, cost_mode=True)
    if overrides:
        cfg = dataclasses.replace(cfg, **{k: v for k, v in overrides.items()
                                          if k != "chunk_impl"})
    B, S = shape.global_batch, shape.seq_len
    out = {}
    if shape.kind == "train":
        st = stage_train_segment(cfg, rules, mesh, B, S)
        out["stage"] = dataclasses.asdict(st)
        if cfg.tail_pattern:
            out["tail"] = dataclasses.asdict(
                stage_train_segment(cfg, rules, mesh, B, S, pattern=cfg.tail_pattern)
            )
        out["head"] = dataclasses.asdict(head_train_segment(cfg, rules, mesh, B, S))
    elif shape.kind == "prefill":
        out["stage"] = dataclasses.asdict(stage_fwd_segment(cfg, rules, mesh, B, S))
        if cfg.tail_pattern:
            out["tail"] = dataclasses.asdict(
                stage_fwd_segment(cfg, rules, mesh, B, S, pattern=cfg.tail_pattern)
            )
        out["head"] = dataclasses.asdict(head_fwd_segment(cfg, rules, mesh, B, S))
    else:  # decode: one stage with caches
        c_shapes = cache_specs(cfg, batch=B, max_seq=S)
        c_sh_all = named(cache_pspecs(cfg, rules, c_shapes, B), mesh)
        one_stage_c = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), c_shapes["stages"]
        )
        one_stage_sh = jax.tree.map(
            lambda s: jax.NamedSharding(
                mesh, jax.sharding.PartitionSpec(*tuple(s.spec)[1:])
            ),
            c_sh_all["stages"],
            is_leaf=lambda x: isinstance(x, jax.NamedSharding),
        )
        out["stage"] = dataclasses.asdict(
            stage_fwd_segment(
                cfg, rules, mesh, B, 1,
                caches=one_stage_c, cache_sh=one_stage_sh, pos_value=S - 2,
            )
        )
        if cfg.tail_pattern:
            tail_c = c_shapes["tail"]
            tail_sh = c_sh_all["tail"]
            out["tail"] = dataclasses.asdict(
                stage_fwd_segment(
                    cfg, rules, mesh, B, 1,
                    caches=tail_c, cache_sh=tail_sh, pos_value=S - 2,
                    pattern=cfg.tail_pattern,
                )
            )
        out["head"] = dataclasses.asdict(head_fwd_segment(cfg, rules, mesh, B, 1))
    return out


def recompose(cfg, shape, rec, n_dev) -> dict:
    segs = rec["segments"]
    n_stages = cfg.n_stages

    def total(field):
        t = segs["head"][field] + segs["stage"][field] * n_stages
        if "tail" in segs:
            t += segs["tail"][field]
        return t

    flops_dev = total("flops")
    bytes_dev = total("bytes_accessed")
    coll_bytes_dev = (
        sum(segs["head"]["coll_bytes"].values())
        + sum(segs["stage"]["coll_bytes"].values()) * n_stages
        + (sum(segs["tail"]["coll_bytes"].values()) if "tail" in segs else 0)
    )
    mf = model_flops_per_step(cfg, shape)
    compute_t = flops_dev / PEAK_FLOPS
    memory_t = bytes_dev / HBM_BW
    coll_t = coll_bytes_dev / LINK_BW
    dom = max(("compute", compute_t), ("memory", memory_t), ("collective", coll_t),
              key=lambda kv: kv[1])[0]
    return {
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "collective_bytes_per_device": coll_bytes_dev,
        "model_flops_total": mf,
        "model_flops_per_device": mf / n_dev,
        "useful_flops_ratio": (mf / n_dev) / flops_dev if flops_dev else 0.0,
        "compute_term_s": compute_t,
        "memory_term_s": memory_t,
        "collective_term_s": coll_t,
        "dominant": dom,
        "roofline_bound_s": max(compute_t, memory_t, coll_t),
        "ideal_compute_s": mf / n_dev / PEAK_FLOPS,
        "roofline_fraction": (mf / n_dev / PEAK_FLOPS) / max(compute_t, memory_t, coll_t)
        if max(compute_t, memory_t, coll_t) > 0
        else 0.0,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-fsdp-data", action="store_true",
                    help="paper-faithful baseline: params replicated over data")
    ap.add_argument("--remat", default=None, choices=["full", "dots", "none"])
    ap.add_argument("--qchunk", type=int, default=None)
    ap.add_argument("--serve-sharding", default="experts_only",
                    choices=["experts_only", "full", "model_only"],
                    help="decode/prefill param sharding override")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--skip-segments", action="store_true")
    ap.add_argument("--comm-fit", default=None,
                    help="JSON file with a serialized MeasuredComm sweep "
                         "({sizes_bytes, times_s[, axes]}); plan records use "
                         "its α–β fit instead of the analytic fabric model")
    from repro.fabric import available_fabrics
    ap.add_argument("--fabric", default="tpu_v5e",
                    choices=list(available_fabrics()),
                    help="interconnect preset pricing the plan records "
                         "(train plans AND decode serve plans)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    comm_fit = json.loads(pathlib.Path(args.comm_fit).read_text()) if args.comm_fit else None

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    cells = []
    if args.all:
        for arch in ARCH_NAMES:
            for shp in applicable_shapes(arch):
                cells.append((arch, shp))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        if args.shape == "long_500k" and args.arch in LONG_CONTEXT_SKIP:
            print(f"SKIP {args.arch} x long_500k (pure full-attention; DESIGN.md §4)")
            return
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    ok, failed = 0, []
    for arch, shp in cells:
        for mp in meshes:
            tag = f"{arch}__{shp}__{'2x16x16' if mp else '16x16'}"
            try:
                overrides = {}
                if args.remat:
                    overrides["remat"] = args.remat
                if args.qchunk:
                    overrides["q_chunk"] = args.qchunk
                fsdp = not args.no_fsdp_data
                if args.serve_sharding and SHAPES[shp].kind == "decode":
                    # experts_only only matters (and only helps) for MoE
                    # archs — non-MoE decode keeps full ZeRO-3 sharding
                    from repro.configs import get_config as _gc
                    if _gc(arch).moe is not None or args.serve_sharding != "experts_only":
                        fsdp = {"experts_only": "experts_only", "full": True,
                                "model_only": False}[args.serve_sharding]
                rec = lower_cell(
                    arch, shp, mp,
                    fsdp_data=fsdp,
                    n_microbatches=args.microbatches,
                    skip_segments=args.skip_segments,
                    overrides=overrides or None,
                    comm_fit=comm_fit,
                    fabric=args.fabric,
                )
                out = pathlib.Path(args.out) if args.out else RESULTS_DIR / f"{tag}.json"
                out.write_text(json.dumps(rec, indent=1))
                ok += 1
            except Exception as e:
                failed.append((tag, repr(e)))
                print(f"FAILED {tag}: {e}")
                traceback.print_exc()
    print(f"\ndry-run complete: {ok} ok, {len(failed)} failed")
    for tag, err in failed:
        print(" FAIL:", tag, err[:200])
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

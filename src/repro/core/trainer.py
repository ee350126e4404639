"""The MG-WFBP training engine (Tier 2): explicit, scheduled DP gradient
communication inside ``shard_map``.

Pipeline (paper Algorithm 2, compiler-expressed):

  1. cost     — per-unit gradient sizes + backward times from a
                ``planning.CostSource`` (analytic Eq. 18 by default, or a
                measured wall-clock / HLO-segment profile);
  2. plan     — a ``planning.registry`` policy (Algorithm 1 ``mg_wfbp``,
                the exact DP ``dp_optimal``, or the WFBP / SyncEASGD /
                fixed-bucket baselines) turns the cost vector into a
                frozen, JSON-serializable ``Plan``;
  3. execute  — the layer scan is segmented on the plan's bucket
                boundaries and gradients are reduced with one all-reduce
                per bucket, all inside ``shard_map`` with the DP axes
                manual and the model axis left to GSPMD.

The engine is re-plannable: ``replan_if_drifted`` (journal MG-WFBP's
online re-planning) swaps in a successor plan built from measured costs,
and elastic restarts rebuild the plan for the new N — plans are cheap
pure functions of (arch, mesh, α–β model) and serialize to JSON so
restarts and dry-runs can reuse them instead of recomputing.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .. import scopes
from ..models import loss_fn, staged_loss_fns
from ..models.common import ArchConfig
from ..models.moe import sum_aux
from ..optim.optimizers import Optimizer
from ..planning import AnalyticCosts, CostSource, build_plan, replan_if_drifted
from ..planning import build_schedule as _registry_build_schedule
from ..planning.plan import Plan
from .bucketing import stacked_lm_layout
from .comm_model import AllReduceModel
from .cost_model import Hardware, LayerCost, TPU_V5E
from . import profiler
from .schedule import Schedule
from .sync import SyncConfig, make_gradient_sync

Pytree = Any

#: XLA's all-reduce combiners (the TPU's pass and XLA:CPU's) merge
#: independent all-reduces into one.  Over the train step that is every
#: schedule group's, run after the last gradient lands: it undoes the
#: plan's merging and the DAG issue order.  The plan owns merging, so the
#: step turns them off wherever it has more than one DP replica.
KEEP_GROUPS_APART = {"xla_disable_hlo_passes": "all-reduce-combiner,cpu-all-reduce-combiner"}


def _tree_size(tree: Pytree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def lm_unit_costs(
    cfg: ArchConfig,
    param_shapes: Pytree,
    tokens_per_device: int,
    hw: Hardware = TPU_V5E,
    comm_dtype_bytes: int = 4,
    model_shards: int = 1,
) -> list[LayerCost]:
    """Per-unit LayerCost for the stacked LM layout (paper Eq. 17/18).

    Units in paper order (gradient of unit 1 lands last):
    [embed, stage_1..stage_n, (tail), head+final_norm]."""
    embed_p = _tree_size(param_shapes["embed"])
    stage_p = _tree_size(param_shapes["stages"]) // cfg.n_stages
    norm_p = _tree_size(param_shapes["final_norm"])
    head_p = norm_p + (0 if cfg.tie_embeddings else _tree_size(param_shapes["head"]))
    tail_p = _tree_size(param_shapes["tail"]) if "tail" in param_shapes else 0

    def cost(name, p, bwd, fwd):
        return LayerCost(
            name=name,
            params=p,
            grad_bytes=max(1, p * comm_dtype_bytes // model_shards),
            bwd_flops=bwd,
            fwd_flops=fwd,
        )

    t = tokens_per_device
    units = [cost("embed", embed_p, 2.0 * t * cfg.d_model, 2.0 * t * cfg.d_model)]
    # weights a token multiplies through in one stage: all of them, but of
    # the held experts only the expected share, top_k·held/E experts' worth
    # (top_k/E of the held experts' weights)
    active_p = stage_p
    if cfg.moe is not None:
        expert_p = sum(_tree_size(w) for sub in param_shapes["stages"].values() if "moe" in sub
                       for name, w in sub["moe"].items() if name != "router") // cfg.n_stages
        active_p = stage_p - expert_p + expert_p * cfg.moe.top_k / cfg.moe.n_experts
    for i in range(cfg.n_stages):
        units.append(cost(f"stage_{i}", stage_p, 4.0 * active_p * t, 2.0 * active_p * t))
    if tail_p:
        units.append(cost("tail", tail_p, 4.0 * tail_p * t, 2.0 * tail_p * t))
    head_flops_p = norm_p + cfg.d_model * cfg.vocab  # tied: head matmul still runs
    units.append(cost("head", head_p, 4.0 * head_flops_p * t, 2.0 * head_flops_p * t))
    return units


def build_schedule(
    method: str,
    costs: list[LayerCost],
    ar_model: AllReduceModel,
    hw: Hardware = TPU_V5E,
    bucket_bytes: int = 25 * 2**20,
) -> Schedule:
    """Compatibility shim over the planning registry.

    Scheduler selection lives in ``planning.registry`` — new code should
    call ``planning.build_schedule(policy, ...)`` / ``get_policy`` directly.
    """
    return _registry_build_schedule(
        method, costs, ar_model, hw=hw, bucket_bytes=bucket_bytes
    )


def group_issue_events(
    schedule: Schedule,
    n_stages: int,
    segments: tuple[tuple[int, int], ...],
    has_tail: bool,
) -> dict[Any, tuple[int, ...]]:
    """Map each backward event to the schedule groups it completes.

    Events key the DAG step's backward walk: ``"head"``, ``"tail"``,
    ``("seg", j)`` (the ``j``-th scan segment), ``"embed"`` — in that
    execution order.  Group ``gi`` (backward issue order) appears under
    the event that computes the gradient of its *lowest* unit ``lo``
    (the last member gradient to land, paper Eq. 6): the embed event for
    ``lo == 1``, the segment containing stage ``lo - 2`` for stage
    units, the tail/head events otherwise.  Every group appears exactly
    once — the partition covers all units.
    """
    group_spans = tuple(reversed(schedule.groups))
    n_units = schedule.num_layers
    tail_unit = n_stages + 2 if has_tail else None
    out: dict[Any, list[int]] = {}
    for gi, (lo, _hi) in enumerate(group_spans):
        if lo == 1:
            event: Any = "embed"
        elif lo <= 1 + n_stages:
            s = lo - 2
            event = None
            for j, (start, stop) in enumerate(segments):
                if start <= s < stop:
                    event = ("seg", j)
                    break
            if event is None:
                raise ValueError(
                    f"group {group_spans[gi]} starts at stage {s} but no scan "
                    f"segment in {segments} contains it"
                )
        elif tail_unit is not None and lo == tail_unit:
            event = "tail"
        elif lo == n_units:
            event = "head"
        else:
            raise ValueError(f"group {group_spans[gi]} has no issue event")
        out.setdefault(event, []).append(gi)
    assert sum(len(v) for v in out.values()) == len(group_spans)
    return {k: tuple(v) for k, v in out.items()}


@dataclasses.dataclass
class MGWFBPEngine:
    """Plan + sync bundle for one (arch, mesh) pair.

    The schedule, scan segmentation, cost vector, and provenance all live
    in the frozen ``plan``; the engine adds the executable pieces (the
    bucketed sync closure and the shard_map train step).
    """

    cfg: ArchConfig
    plan: Plan
    sync: Any
    dp_axes: tuple[str, ...]
    sync_config: SyncConfig = SyncConfig()

    @property
    def schedule(self) -> Schedule:
        return self.plan.schedule

    @property
    def segments(self) -> tuple[tuple[int, int], ...]:
        return self.plan.segments

    def dp_world(self, mesh) -> int:
        return int(np.prod([mesh.shape[ax] for ax in self.dp_axes]))

    def _moe_counters(self, metrics: dict) -> dict:
        """An MoE model's step counters over all replicas: the rows routed
        to held experts, summed over layers (``moe_held_rows``), and the
        most rows one held expert took (``moe_max_expert_rows``).  None
        for a dense model."""
        if self.cfg.moe is None:
            return {}
        return {"moe_held_rows": jax.lax.psum(metrics["moe_held_rows"], self.dp_axes),
                "moe_max_expert_rows": jax.lax.pmax(metrics["moe_max_expert_rows"],
                                                    self.dp_axes)}

    @classmethod
    def build(
        cls,
        cfg: ArchConfig,
        param_shapes: Pytree,
        *,
        dp_axes: tuple[str, ...],
        ar_model: AllReduceModel | None = None,
        tokens_per_device: int | None = None,
        hw: Hardware = TPU_V5E,
        policy: str | None = None,
        method: str | None = None,  # legacy alias for ``policy``
        sync_config: SyncConfig = SyncConfig(),
        model_shards: int = 1,
        plan: Plan | None = None,
        cost_source: CostSource | None = None,
    ) -> "MGWFBPEngine":
        """Build from an existing ``plan``, or derive one from a cost
        source + policy (the planning lifecycle's first three legs)."""
        if plan is not None:
            requested = policy or method
            if requested is not None:
                from ..planning import resolve_policy_name

                if resolve_policy_name(requested) != plan.policy:
                    raise ValueError(
                        f"plan was built with policy {plan.policy!r}; drop the "
                        f"policy argument to reuse it, or re-plan with {requested!r}"
                    )
        if plan is None:
            if ar_model is None:
                raise ValueError("either a plan or an ar_model is required")
            comm_bytes = jnp.dtype(sync_config.comm_dtype).itemsize
            layout = stacked_lm_layout(
                param_shapes, cfg.n_stages,
                comm_dtype_bytes=comm_bytes, model_shards=model_shards,
            )
            if cost_source is None:
                if tokens_per_device is None:
                    raise ValueError("tokens_per_device is required for analytic costs")
                cost_source = AnalyticCosts(
                    costs=tuple(
                        lm_unit_costs(
                            cfg, param_shapes, tokens_per_device,
                            hw=hw, model_shards=model_shards,
                            comm_dtype_bytes=comm_bytes,
                        )
                    ),
                    hw=hw,
                )
            plan = build_plan(
                layout,
                cost_source.layer_costs(),
                ar_model,
                policy=policy or method or "mg_wfbp",
                hw=cost_source.hw,
                n_scan_stages=cfg.n_stages,
                cost_source=cost_source.name,
                provenance={"arch": cfg.name},
            )
        if plan.n_scan_stages not in (None, cfg.n_stages):
            raise ValueError(
                f"plan was built for {plan.n_scan_stages} scan stages, "
                f"arch {cfg.name} has {cfg.n_stages}"
            )
        sync = make_gradient_sync(plan.layout, plan.schedule, dp_axes, sync_config)
        return cls(
            cfg=cfg, plan=plan, sync=sync, dp_axes=dp_axes, sync_config=sync_config
        )

    def with_plan(self, plan: Plan) -> "MGWFBPEngine":
        """Same engine, different plan (rebuilds the sync closure)."""
        return MGWFBPEngine.build(
            self.cfg, None, dp_axes=self.dp_axes,
            sync_config=self.sync_config, plan=plan,
        )

    def replan(
        self,
        measured: CostSource,
        threshold: float = 0.15,
        policy: str | None = None,
    ) -> tuple["MGWFBPEngine", bool]:
        """Online re-planning hook: returns (engine, replanned).

        When measured costs drift beyond ``threshold`` the policy reruns
        and a new engine (new sync + segments) is returned; the caller
        must rebuild its train step (the scan segmentation changed).
        """
        new_plan, changed = replan_if_drifted(
            self.plan, measured, threshold=threshold, policy=policy
        )
        if not changed:
            return self, False
        return self.with_plan(new_plan), True

    def make_train_step(
        self, optimizer: Optimizer, mesh, *, lr: float = 3e-4,
        issue: str = "post",
    ):
        """Shard-map train step: manual DP axes, auto model axis,
        ``step(params, opt_state, batch) -> (params, opt_state, metrics)``
        in either issue order.

        ``issue`` selects the communication issue order
        (``core.timeline.MODES`` maps onto it: ``'dag'`` executes what
        ``mode='overlap'`` prices, ``'post'`` what ``'serialized'``
        prices):

        * ``'post'`` — the historical step: one ``value_and_grad`` over
          the whole model, then every group's all-reduce;
        * ``'dag'`` — the WFBP DAG step: the forward records one
          ``jax.vjp`` pullback per unit event (embed / scan segment /
          tail / head), backward walks them in reverse, and each
          schedule group's merged all-reduce is issued *at the event
          where its last gradient lands* — program order, not compiler
          luck, puts the wire inside backward.  Group ``g``'s psum
          depends only on gradients already computed when it issues, so
          its wire time hides behind the backward of groups ``g+1..``.

        Both orders name their layers with the ``repro.scopes`` names
        (``fwd_*``, ``bwd_*``, ``optimizer``; each group's reduction is
        ``wfbp_group*``): ``profiler.scope_spans`` reads them back from a
        device trace.
        """
        if issue not in ("post", "dag"):
            raise ValueError(f"unknown issue order {issue!r}; known: ('post', 'dag')")
        cfg = self.cfg
        P = jax.sharding.PartitionSpec

        batch_spec = {"targets": P(self.dp_axes, None)}
        if cfg.input_mode == "embeds":
            batch_spec["embeds"] = P(self.dp_axes, None, None)
        else:
            batch_spec["tokens"] = P(self.dp_axes, None)

        if issue == "dag":
            grads_and_loss = self._dag_grads()
        else:
            def grads_and_loss(params, batch):
                def loss(p):
                    with jax.named_scope(scopes.FWD_MODEL):
                        return loss_fn(p, batch, cfg, segments=self.segments)

                (l, metrics), grads = jax.value_and_grad(loss, has_aux=True)(params)
                return self.sync(grads), l, metrics

        def body(params, opt_state, batch):
            grads, l, metrics = grads_and_loss(params, batch)
            with jax.named_scope(scopes.OPTIMIZER):
                new_params, new_opt = optimizer.update(grads, opt_state, params, lr)
            l = jax.lax.pmean(l, self.dp_axes)
            return new_params, new_opt, {"loss": l, **self._moe_counters(metrics)}

        smapped = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), P(), batch_spec),
            out_specs=(P(), P(), P()),
            axis_names=set(self.dp_axes),
            check_vma=False,
        )
        # the all-reduce combiners off (KEEP_GROUPS_APART) where there is
        # more than one replica to reduce over
        opts = KEEP_GROUPS_APART if self.dp_world(mesh) > 1 else None
        return jax.jit(smapped, donate_argnums=(0, 1), compiler_options=opts)

    def _dag_grads(self):
        """The DAG order's ``(params, batch) -> (reduced_grads, loss,
        metrics)`` (see ``make_train_step``)."""
        cfg = self.cfg
        segments = self.segments
        if segments is None:
            raise ValueError("issue='dag' needs a plan with scan segments")
        events = group_issue_events(
            self.schedule, cfg.n_stages, segments, has_tail=bool(cfg.tail_pattern)
        )
        sync = self.sync

        def dag_grads(params, batch):
            """Staged fwd -> backward walk with in-backward group issue."""
            embed_fn, seg_fns, tail_fn, head_fn = staged_loss_fns(cfg, batch, segments)

            # ---- forward: one vjp pullback per unit event --------------
            with jax.named_scope(scopes.FWD_EMBED):
                x, pb_embed = jax.vjp(embed_fn, params["embed"])
            seg_pbs, aux_parts = [], []
            for j, ((start, stop), seg_fn) in enumerate(zip(segments, seg_fns)):
                with jax.named_scope(scopes.fwd_seg(j)):
                    seg_p = jax.tree.map(lambda a: a[start:stop], params["stages"])
                    (x, aux), pb = jax.vjp(seg_fn, seg_p, x)
                seg_pbs.append(pb)
                aux_parts.append(aux)
            pb_tail = None
            if tail_fn is not None:
                with jax.named_scope(scopes.FWD_TAIL):
                    (x, aux), pb_tail = jax.vjp(tail_fn, params["tail"], x)
                aux_parts.append(aux)
            with jax.named_scope(scopes.FWD_HEAD):
                aux_total = sum_aux(aux_parts)
                head_p = {"final_norm": params["final_norm"]}
                if not cfg.tie_embeddings:
                    head_p["head"] = params["head"]
                l, pb_head, metrics = jax.vjp(
                    head_fn, head_p, params["embed"], x, aux_total, has_aux=True
                )

            # ---- backward: walk pullbacks in reverse, issuing each
            # group's all-reduce the moment its last gradient lands.
            # ``acc`` collects raw per-event gradients (group psums read
            # only already-computed paths — the issue point is program
            # order, not a compiler artifact); ``out`` collects the
            # reduced write-backs (every path is covered by exactly one
            # group, so starting from zeros is fully overwritten).
            acc = dict(jax.tree.map(jnp.zeros_like, params))
            out = jax.tree.map(jnp.zeros_like, params)

            def issue_ready(event, out):
                for gi in events.get(event, ()):
                    out = sync.sync_group(gi, acc, out)
                return out

            with jax.named_scope(scopes.BWD_HEAD):
                d_head_p, d_embed_head, dx, daux = pb_head(jnp.ones_like(l))
                acc["final_norm"] = d_head_p["final_norm"]
                if not cfg.tie_embeddings:
                    acc["head"] = d_head_p["head"]
            out = issue_ready("head", out)

            if pb_tail is not None:
                with jax.named_scope(scopes.BWD_TAIL):
                    d_tail_p, dx = pb_tail((dx, daux))
                    acc["tail"] = d_tail_p
                out = issue_ready("tail", out)

            for j in range(len(segments) - 1, -1, -1):
                start, stop = segments[j]
                with jax.named_scope(scopes.bwd_seg(j)):
                    d_seg_p, dx = seg_pbs[j]((dx, daux))
                    acc["stages"] = jax.tree.map(
                        lambda g, d: g.at[start:stop].set(d), acc["stages"], d_seg_p
                    )
                out = issue_ready(("seg", j), out)

            with jax.named_scope(scopes.BWD_EMBED):
                (d_embed_lookup,) = pb_embed(dx)
                acc["embed"] = d_embed_head + d_embed_lookup
            out = issue_ready("embed", out)
            return out, l, metrics

        return dag_grads

"""Sharded TP decode: the ``ServePlan`` executed inside the engine step.

``planning.serve`` prices the decode-side collectives — the fresh KV rows
every attention sublayer must publish across the tensor-parallel group,
the expert all-to-all of MoE archs — and merges them with the paper's
Eq. 9/10 math.  This module is the executable counterpart: a decode step
that runs under ``shard_map`` on a TP mesh and issues **exactly one fused
collective per scheduled serve group** (``make_group_collective``), the
decode analogue of ``core.sync``'s one-all-reduce-per-group invariant.

Execution model: mirror-compute / sliced-wire
---------------------------------------------
Decode is latency-bound and sequentially dependent: stage ``i+1``'s input
is stage ``i``'s full output, so a collective whose result feeds the next
stage (the Megatron output-combine psum) can never be deferred, let alone
merged across stages.  The collectives MG-WFBP *can* merge are the ones
whose results are only needed by **future** steps — exactly the KV-cache
coherence traffic ``ServePlan`` prices: the fresh rows written at step
``t`` are not read again until step ``t+1``.

The step therefore runs **mirror-compute / sliced-wire** TP:

  * every rank computes the full decode locally (the current token's
    self-attention reads the fresh row from registers — no blocking
    collective on the critical path);
  * each rank *owns* a ``1/N`` feature slice of every stage's fresh KV
    row; the cache receives the other ``N-1`` slices **only off the
    wire** — one fused all-gather per scheduled group, so in the lowered
    HLO the written cache rows genuinely flow through the collectives;
  * MoE archs issue the plan's expert all-to-all per group instead (the
    dispatch traffic the plan priced); its outputs ride along as live
    step outputs.

The wire traffic — op type, op count, group membership, payload bytes,
issue order — is exactly what the plan scheduled and what a production
TP serving mesh ships for KV coherence; the mirrored dense compute is
the virtual-mesh stand-in for sharded projections (whose blocking
combines are out of merge scope by the argument above).  Numerics are
bit-identical to the unsharded engine: the gathered slices are the same
deterministic values every rank computed, reassembled in rank order.

``serving_param_pspecs`` / ``serving_cache_pspecs`` give the matching
at-rest GSPMD layout (Megatron column/row shards for attention + MLP
weights, head-dim shards for the KV caches) used to report per-device
memory; ``ServeTimer`` owns the step wall-clock and per-group measured
comm samples that close the predicted-vs-observed loop.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Any, Callable

import jax
import jax.numpy as jnp

from ..models.transformer import _window_for
from ..runtime.timeline import StepTimer

if TYPE_CHECKING:
    from ..planning.serve import ServePlan

Pytree = Any

#: Param-leaf names sharded on their LAST axis (Megatron column shards:
#: q/k/v projections and the MLP up/gate matrices) vs their FIRST axis
#: (row shards: the output projections whose contraction dim is sharded).
_COL_SHARD_KEYS = ("wq", "wk", "wv", "w_gate", "w_up")
_ROW_SHARD_KEYS = ("wo", "w_down")


def _attn_sublayers(cfg) -> tuple[str, ...]:
    """Cache keys of the pattern's attention-bearing sublayers, in order."""
    return tuple(
        f"{kind}_{i}"
        for i, kind in enumerate(cfg.pattern)
        if kind not in ("rwkv", "rec")
    )


def _write_index(cfg, kind: str, cache_len: int, pos):
    """Ring-buffer write index for this sublayer's cache at ``pos`` —
    the same rule ``models.layers.attention_block`` applies on decode."""
    return pos % cache_len if _window_for(cfg, kind) else pos


def stack_fresh_rows(cfg, caches: Pytree, pos) -> jax.Array | None:
    """The step's wire payload: ``(n_stages, F)`` fresh K/V rows.

    Reads the rows the decode step just wrote at ``pos`` out of every
    attention-bearing sublayer's stacked stage cache (K then V, pattern
    order) and flattens them per stage — the exact per-stage payload
    ``planning.serve.decode_unit_costs`` prices.  Returns ``None`` for
    recurrent-only archs (nothing on the serve wire).
    """
    parts = []
    for i, kind in enumerate(cfg.pattern):
        if kind in ("rwkv", "rec"):
            continue
        k, v, _ = caches["stages"][f"{kind}_{i}"]
        idx = _write_index(cfg, kind, k.shape[2], pos)
        for arr in (k, v):
            row = jax.lax.dynamic_index_in_dim(arr, idx, axis=2, keepdims=False)
            parts.append(row.reshape(row.shape[0], -1))
    if not parts:
        return None
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]


def write_fresh_rows(cfg, caches: Pytree, stacked: jax.Array, pos) -> Pytree:
    """Inverse of ``stack_fresh_rows``: splice ``(n_stages, F)`` rows back
    into the stage caches at ``pos``.

    On the sharded path ``stacked`` is the reassembled all-gather output,
    so the written rows flow through the plan's collectives in the
    lowered HLO — the wire is load-bearing, not decorative.
    """
    new_stages = dict(caches["stages"])
    off = 0
    for i, kind in enumerate(cfg.pattern):
        if kind in ("rwkv", "rec"):
            continue
        key = f"{kind}_{i}"
        k, v, kpos = new_stages[key]
        idx = _write_index(cfg, kind, k.shape[2], pos)
        written = []
        for arr in (k, v):
            n_stages, b, _, h, hd = arr.shape
            width = b * h * hd
            row = stacked[:, off : off + width]
            row = row.reshape(n_stages, b, 1, h, hd).astype(arr.dtype)
            written.append(
                jax.lax.dynamic_update_slice_in_dim(arr, row, idx, axis=2)
            )
            off += width
        new_stages[key] = (written[0], written[1], kpos)
    return {**caches, "stages": new_stages}


def make_sharded_decode_step(cfg, plan: "ServePlan", *, tp_axis: str = "model"):
    """Build the per-rank body of the plan-driven sharded decode step.

    Returns ``step(params, caches, batch, pos) -> (logits, caches, wire)``
    meant to run inside ``shard_map`` over ``tp_axis`` (see
    ``sharded_decode_fn`` for the jitted wrapper).  The body runs the
    ordinary decode (``launch.steps.make_decode_step``), cuts this rank's
    owned ``1/N`` slice out of the stacked fresh-row payload, and drives
    ``planning.serve.make_group_collective`` — one fused collective per
    scheduled serve group.  For the plan's ``all_gather`` op the gathered
    full rows are written back into the caches (``wire`` is empty); for
    ``all_to_all`` (MoE) the shuffled dispatch buffers are returned as
    live outputs and the locally written rows stand.
    """
    from ..launch.steps import make_decode_step
    from ..planning.serve import make_group_collective

    base = make_decode_step(cfg, None)
    wire = make_group_collective(plan, tp_axis)
    groups = plan.schedule.groups
    is_gather = plan.op == "all_gather"

    def step(params, caches, batch, pos):
        logits, caches = base(params, caches, batch, pos)
        stacked = stack_fresh_rows(cfg, caches, pos)
        if stacked is None:  # recurrent-only arch: nothing to cohere
            return logits, caches, ()

        n = jax.lax.axis_size(tp_axis)
        r = jax.lax.axis_index(tp_axis)
        n_stages, full = stacked.shape
        width = -(-full // n)  # ceil: every rank ships an equal slice
        pad = width * n - full
        padded = jnp.pad(stacked, ((0, 0), (0, pad))) if pad else stacked
        local = jax.lax.dynamic_slice_in_dim(padded, r * width, width, axis=1)
        outs = wire(local)
        if not is_gather:
            return logits, caches, tuple(outs)
        rows = []
        for (lo, hi), out in zip(groups, outs):
            g = hi - lo + 1
            # (n, g·width) gather -> rank-major slices back to (g, n·width)
            rows.append(out.reshape(n, g, width).transpose(1, 0, 2).reshape(g, n * width))
        gathered = jnp.concatenate(rows, axis=0)[:, :full]
        caches = write_fresh_rows(cfg, caches, gathered, pos)
        return logits, caches, ()

    return step


def sharded_decode_core(cfg, plan: "ServePlan", mesh, *, tp_axis: str = "model"):
    """Unjitted ``shard_map``-ped plan-driven decode step on a TP ``mesh``.

    ``fn(params, caches, batch, pos) -> (logits, caches, wire)`` — the
    collective-issuing core ``ServingEngine`` embeds inside its ONE
    jitted, buffer-donating step (so sampling and the masked state
    updates trace into the same executable as the plan's collectives).
    Engine state rides in replicated (the mirrored compute needs full
    values per rank; see the module docstring), and the lowered HLO of
    any step containing this core has exactly
    ``len(plan.schedule.groups)`` collective ops — pinned by the engine
    lowering test.
    """

    P = jax.sharding.PartitionSpec
    step = make_sharded_decode_step(cfg, plan, tp_axis=tp_axis)
    n_wire = 0 if plan.op == "all_gather" else len(plan.schedule.groups)
    if not _attn_sublayers(cfg):
        n_wire = 0
    out_specs = (P(), P(), tuple(P(tp_axis) for _ in range(n_wire)))
    return jax.shard_map(
        step, mesh=mesh, in_specs=(P(), P(), P(), P()),
        out_specs=out_specs, axis_names={tp_axis}, check_vma=False,
    )


def sharded_decode_fn(cfg, plan: "ServePlan", mesh, *, tp_axis: str = "model"):
    """``jax.jit(sharded_decode_core(...))`` — the standalone jitted
    sharded decode step, for callers that want the plan-driven step
    outside a ``ServingEngine`` (the engine itself jits the core inside
    its donated whole-step function instead)."""
    return jax.jit(sharded_decode_core(cfg, plan, mesh, tp_axis=tp_axis))


# ---------------------------------------------------------------------------
# At-rest GSPMD layout (what a production engine holds its state in)
# ---------------------------------------------------------------------------


def serving_param_pspecs(params: Pytree, *, tp_axis: str = "model") -> Pytree:
    """Megatron at-rest ``PartitionSpec`` tree for the decode weights.

    q/k/v and MLP up/gate projections shard their output (last) axis over
    ``tp_axis``; the output projections (``wo``/``w_down``) shard their
    contraction (first non-stage) axis; everything else (norms, embed,
    head) stays replicated.  Stacked stage leaves keep the leading stage
    axis unsharded.  Pair with ``shard_serving_state`` to place, or with
    ``jax.sharding.NamedSharding.shard_shape`` to report the per-device
    memory of a sharded deployment.
    """
    P = jax.sharding.PartitionSpec

    def spec_for(path, leaf) -> jax.sharding.PartitionSpec:
        names = [str(getattr(p, "key", "")) for p in path]
        ndim = getattr(leaf, "ndim", len(getattr(leaf, "shape", ())))
        if any(n in _COL_SHARD_KEYS for n in names) and ndim >= 2:
            return P(*([None] * (ndim - 1) + [tp_axis]))
        if any(n in _ROW_SHARD_KEYS for n in names) and ndim >= 2:
            # stacked stage leaves: (n_stages, in, out) -> shard 'in'
            row_axis = ndim - 2
            spec = [None] * ndim
            spec[row_axis] = tp_axis
            return P(*spec)
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, params)


def serving_cache_pspecs(cfg, caches: Pytree, *, tp_axis: str = "model") -> Pytree:
    """At-rest ``PartitionSpec`` tree sharding every K/V cache leaf's
    head_dim (last) axis over ``tp_axis`` — the decode-side memory win
    (the KV cache is the serving bottleneck); recurrent state and the
    ``kpos`` ring indices stay replicated."""
    P = jax.sharding.PartitionSpec

    def spec_for(path, leaf) -> jax.sharding.PartitionSpec:
        ndim = getattr(leaf, "ndim", len(getattr(leaf, "shape", ())))
        # K/V leaves: (..., B, T, n_kv_heads, head_dim) float arrays
        if ndim >= 4 and jnp.issubdtype(getattr(leaf, "dtype", jnp.int32), jnp.floating):
            names = [str(getattr(p, "key", "")) for p in path]
            if any("_" in n and n.split("_")[0] not in ("rwkv", "rec") for n in names):
                return P(*([None] * (ndim - 1) + [tp_axis]))
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, caches)


def shard_serving_state(
    params: Pytree, caches: Pytree, cfg, mesh, *, tp_axis: str = "model"
) -> tuple[Pytree, Pytree]:
    """``device_put`` the engine state into the at-rest TP layout.

    Leaves whose shard axis does not divide by the ``tp_axis`` size fall
    back to replicated (small reduced configs).  The mirror-compute step
    consumes replicated values, so use this for at-rest storage /
    memory reporting, not as the step's input sharding.
    """
    size = dict(zip(mesh.axis_names, mesh.devices.shape))[tp_axis]

    def place(specs, tree):
        def put(spec, leaf):
            for ax, name in enumerate(tuple(spec)):
                if name is not None and leaf.shape[ax] % size != 0:
                    spec = jax.sharding.PartitionSpec()
                    break
            return jax.device_put(leaf, jax.NamedSharding(mesh, spec))

        return jax.tree.map(
            put, specs, tree,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
        )

    return (
        place(serving_param_pspecs(params, tp_axis=tp_axis), params),
        place(serving_cache_pspecs(cfg, caches, tp_axis=tp_axis), caches),
    )


class ServeTimer(StepTimer):
    """Decode-step wall-clock window + per-group measured comm seconds.

    The serving analogue of ``runtime.timeline.StepTimer``: the engine
    feeds ``observe(dt)`` per decode step (first samples skipped — they
    include compilation), ``median()`` is the observed step time that
    ``ServePlan.predicted`` (``schedule.result.t_iter``) is compared
    against, and ``group_times`` holds the per-scheduled-group measured
    collective seconds filled by ``planning.serve.time_serve_groups``.
    """

    def __init__(self, window: int = 200, skip_first: int = 2):
        super().__init__(window=window, skip_first=skip_first)
        self.group_times: tuple[float, ...] = ()

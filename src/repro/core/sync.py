"""Gradient synchronization engine (paper Algorithm 2, TPU-native).

The paper's Algorithm 2 runs a background communication thread that pops
layer indices from a queue and calls ``SynchronizedAllReduce`` on merged
buffers.  In JAX the same structure is expressed to the compiler instead:

  * the train step runs inside ``shard_map`` with the data-parallel mesh
    axes **manual** and the model axes **auto** (GSPMD), so the DP
    gradient reduction is written explicitly by us — one all-reduce per
    schedule group;
  * XLA's latency-hiding scheduler overlaps each group's all-reduce with
    the backward computation of earlier layers, because the groups are
    independent ops — structurally the same overlap WFBP gets from its
    background thread.

There is exactly ONE bucketed reducer, ``make_gradient_sync``, driven by
a ``ParamLayout``'s communication units.  Both unit kinds flow through
the same path: ``leaf`` units contribute whole pytree leaves, ``stacked``
units contribute contiguous slices of scan-stacked leaves (a group
spanning stages [a, b) ships ``leaf[a:b]``; XLA folds
slice-of-assembled-grad back to the per-segment gradient value, so each
group's all-reduce depends only on its own scan segment's backward).
The WFBP / SyncEASGD / MG-WFBP distinction is *entirely* in the schedule
a policy produced — there is no separate strategy switch (the old
``SyncConfig.strategy`` is absorbed by ``planning.registry`` aliases).

One wire layout, the packed arena: each group's leaves are packed into a
preallocated flat arena by the ``kernels/comm_pack`` pack kernel (the
wire-dtype cast fused in), reduced with one ``psum``, and unpacked
(decompress + DP average fused).  That is the merged message of
Definition 1 at one all-reduce HLO op per group, with zero concatenate
ops: the only copies are the cast the wire needed anyway.  The wire
carries f32, or bf16 to halve DP traffic (``SyncConfig.comm_dtype``).

Every gradient reduction is issued through the typed collective seam
(``fabric.ops.issue(Collective.ALL_REDUCE, ...)``) — the same vocabulary
the planner's fabric cost models price and the serve wire path uses.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

# submodule imports (not the fabric package) — core and fabric import each
# other's leaves, and the package __init__s would cycle
from .. import scopes
from ..fabric.model import Collective
from ..fabric.ops import issue
from ..kernels.comm_pack import pack_arena, unpack_arena
from .bucketing import (
    ParamLayout,
    WireEntry,
    bucket_assignment,
    tree_get as _get,
    tree_set as _set,
    wire_entries,
)
from .schedule import Schedule

Pytree = Any

__all__ = [
    "SyncConfig",
    "WireEntry",
    "make_gradient_sync",
    "wire_entries",
]


@dataclasses.dataclass(frozen=True)
class SyncConfig:
    """How DP gradients are reduced.

    comm_dtype : dtype gradients are cast to on the wire (uniform per
                 arena — how real systems ship grads anyway): f32, or
                 bf16 to halve the DP traffic.

    Which layers ride together is NOT configured here — that is the
    schedule, produced by a ``planning.registry`` policy.
    """

    comm_dtype: Any = jnp.float32


def make_gradient_sync(
    layout: ParamLayout,
    schedule: Schedule,
    dp_axes: tuple[str, ...],
    config: SyncConfig = SyncConfig(),
) -> Callable[[Pytree], Pytree]:
    """Build ``sync_fn(grads) -> reduced_grads`` for use inside shard_map:
    one all-reduce per schedule group, averaged over the DP world.

    The returned closure exposes the per-group seam the DAG train step
    issues through: ``sync.sync_group(gi, grads, out) -> out`` reduces
    backward-order group ``gi`` alone, reading that group's gradient
    paths from ``grads`` and writing the reduced values into ``out`` —
    so a caller that knows *when* group ``gi``'s last gradient lands can
    place the all-reduce at exactly that event.  ``sync(grads)`` is
    simply all groups in backward order.

    Each group runs under the named scope ``wfbp_group{gi}_l{lo}_{hi}``:
    the spans ``profiler.scope_spans`` reads from a device trace.
    """
    group_entries = wire_entries(layout, schedule)
    # (lo, hi) layer spans in backward issue order — names the profiler
    # scopes below and lets the timeline layer know what group i is.
    group_spans = tuple(reversed(schedule.groups))
    # Per-group wire payload (per device): CommUnit.grad_bytes already
    # carries the model-shard division and the wire dtype the layout was
    # built with — the same "p" vector the schedule was optimized over.
    group_wire_bytes = tuple(
        sum(u.grad_bytes for u in units)
        for units in reversed(bucket_assignment(layout, schedule))
    )

    def sync_group(gi: int, grads: Pytree, out: Pytree) -> Pytree:
        """Reduce group ``gi`` (backward issue order) only."""
        lo, hi = group_spans[gi]
        world = 1.0
        for ax in dp_axes:
            world *= jax.lax.axis_size(ax)
        with jax.named_scope(scopes.wfbp_group(gi, lo, hi)):
            return _arena_group(group_entries[gi], grads, out, dp_axes, world, config)

    def sync(grads: Pytree) -> Pytree:
        out = grads
        # Issue groups in backward order (layer-L group first), matching the
        # availability order the schedule was optimized for.  Each group is
        # wrapped in a named scope so device profiles (and the timeline
        # layer's per-group comm attribution) see the schedule boundaries.
        for gi in range(len(group_entries)):
            out = sync_group(gi, grads, out)
        return out

    # Metadata for the instrumentation layer (runtime/timeline.py): the
    # per-group wire payloads, in the same backward issue order the groups
    # execute in — what time_group_comm probes one psum per.
    sync.schedule = schedule
    sync.group_spans = group_spans
    sync.group_wire_bytes = group_wire_bytes
    sync.sync_group = sync_group
    sync.n_groups = len(group_entries)
    return sync


def _arena_group(
    entries: list[WireEntry],
    grads: Pytree,
    out: Pytree,
    dp_axes: tuple[str, ...],
    world,
    config: SyncConfig,
) -> Pytree:
    """One group over the arena wire: pack(+cast) -> one psum ->
    unpack(+decompress+average).  The arena layout is the plan-time
    ``bucketing.group_arenas`` layout, re-derived here from the traced
    gradient shapes (identical by construction — ``test_arena`` pins it).
    """
    parts, metas = [], []
    off = 0
    for kind, path, ab in entries:
        g = _get(grads, path)
        if kind == "slice":
            g = g[ab[0] : ab[1]]
        n = int(np.prod(g.shape)) if g.shape else 1
        metas.append((kind, path, ab, g.dtype, g.shape, off, n))
        parts.append(g)
        off += n
    arena = pack_arena(parts, [m[5] for m in metas], off, config.comm_dtype)
    red = issue(Collective.ALL_REDUCE, arena, dp_axes)
    unpacked = unpack_arena(
        red,
        [(m[5], m[6]) for m in metas],
        [m[4] for m in metas],
        [m[3] for m in metas],
        scale=1.0 / world,
    )
    for (kind, path, ab, _, _, _, _), r in zip(metas, unpacked):
        out = _write_back(out, kind, path, ab, r)
    return out


def _write_back(tree: Pytree, kind: str, path, ab, value: jax.Array) -> Pytree:
    if kind == "leaf":
        return _set(tree, path, value)
    cur = _get(tree, path)
    return _set(tree, path, cur.at[ab[0] : ab[1]].set(value.astype(cur.dtype)))

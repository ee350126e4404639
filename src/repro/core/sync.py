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

Three wire layouts:

  ``concat``    — each group's encoded leaves are flattened into one
                  buffer and reduced with a single ``psum``: the merged
                  message of Definition 1, guaranteed one all-reduce HLO
                  op per group on every jax/XLA version — but the merge
                  is paid for with a full extra round-trip of gradient
                  memory traffic (concatenate in, split out).
  ``variadic``  — one ``psum`` over the tuple of leaves (zero-copy);
                  whether XLA lowers this to a single variadic
                  all-reduce is probed once
                  (``variadic_psum_is_single_op``); otherwise it emits
                  one op per leaf and relies on the combiner.
  ``arena``     — the merged buffer without the merge tax: each group's
                  leaves are packed into a preallocated flat arena by the
                  ``kernels/comm_pack`` pack kernel (wire-dtype cast and
                  optional error-feedback residual fused in), reduced
                  with one ``psum``, and unpacked (decompress + DP
                  average fused).  One all-reduce HLO op per group on
                  every jax version, zero concatenate ops, and the only
                  copies are the cast the wire needed anyway.

Every gradient reduction is issued through the typed collective seam
(``fabric.ops.issue(Collective.ALL_REDUCE, ...)``) — the same vocabulary
the planner's fabric cost models price and the serve wire path uses.

``compression='bf16'`` halves fp32 wire traffic on any layout;
``'bf16_ef'`` (arena only) additionally carries the rounding error in a
local error-feedback residual — the EF-SGD trick of
``runtime/compression.py`` fused into the pack —  at which point the
sync is stateful: ``sync(grads, residual) -> (grads, residual)``.
"""

from __future__ import annotations

import dataclasses
import functools
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
    group_arenas,
    tree_get as _get,
    tree_set as _set,
    wire_entries,
)
from .schedule import Schedule

Pytree = Any

__all__ = [
    "SyncConfig",
    "WireEntry",
    "count_expected_allreduces",
    "make_gradient_sync",
    "wire_entries",
]


@dataclasses.dataclass(frozen=True)
class SyncConfig:
    """How DP gradients are reduced.

    comm_dtype  : dtype gradients are cast to on the wire (uniform per
                  bucket — required for the merged buffer, and how real
                  systems ship grads anyway).
    average     : divide by the DP world size after summing.
    compression : None | 'bf16' | 'bf16_ef' (arena only; int8 lives in
                  ``runtime/compression.py``).
    fuse        : 'concat' (one flat buffer per group, exactly one
                  all-reduce op, copy each way) | 'variadic' (tuple psum,
                  zero-copy, op count is version-dependent) | 'arena'
                  (packed flat buffer via kernels/comm_pack: one op per
                  group AND no concatenate copies).

    Which layers ride together is NOT configured here — that is the
    schedule, produced by a ``planning.registry`` policy.
    """

    comm_dtype: Any = jnp.float32
    average: bool = True
    compression: str | None = None
    fuse: str = "concat"

    @property
    def wire_dtype(self) -> Any:
        if self.compression in ("bf16", "bf16_ef"):
            return jnp.bfloat16
        return self.comm_dtype


def make_gradient_sync(
    layout: ParamLayout,
    schedule: Schedule,
    dp_axes: tuple[str, ...],
    config: SyncConfig = SyncConfig(),
) -> Callable[..., Pytree]:
    """Build ``sync_fn(grads) -> reduced_grads`` for use inside shard_map.

    One all-reduce is issued per schedule group (``fuse='concat'`` /
    ``'arena'``); ``count_expected_allreduces`` states the invariant and
    the tier-1 suite pins it against lowered HLO.  With
    ``compression='bf16_ef'`` the returned function is stateful:
    ``sync_fn(grads, residual) -> (reduced_grads, new_residual)`` where
    ``residual`` is an f32 pytree of ``grads``' structure (zeros to
    start) carrying each device's local quantization error.

    The returned closure exposes the per-group seam the DAG train step
    issues through: ``sync.sync_group(gi, grads, out, residual=None) ->
    (out, residual)`` reduces backward-order group ``gi`` alone, reading
    that group's gradient paths from ``grads`` and writing the reduced
    values into ``out`` — so a caller that knows *when* group ``gi``'s
    last gradient lands can place the all-reduce at exactly that event.
    ``sync(grads)`` is simply all groups in backward order.

    Each group runs under the named scope ``wfbp_group{gi}_l{lo}_{hi}``:
    the spans ``profiler.scope_spans`` reads from a device trace.
    """
    if config.fuse not in ("concat", "variadic", "arena"):
        raise ValueError(f"unknown fuse mode {config.fuse!r}")
    if config.compression == "bf16_ef" and config.fuse != "arena":
        raise ValueError("error-feedback compression requires fuse='arena'")
    group_entries = wire_entries(layout, schedule)
    stateful = config.compression == "bf16_ef"
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

    def sync_group(gi: int, grads: Pytree, out: Pytree, residual: Pytree | None = None):
        """Reduce group ``gi`` (backward issue order) only."""
        entries = group_entries[gi]
        lo, hi = group_spans[gi]
        world = 1.0
        for ax in dp_axes:
            world *= jax.lax.axis_size(ax)
        name = scopes.wfbp_group(gi, lo, hi)
        with jax.named_scope(name):
            if config.fuse == "arena":
                return _arena_group(entries, grads, out, residual, dp_axes, world, config)
            vals, metas = [], []
            for kind, path, ab in entries:
                g = _get(grads, path)
                if kind == "slice":
                    g = g[ab[0] : ab[1]]
                metas.append((kind, path, ab, g.dtype, g.shape))
                vals.append(_encode(g, config))
            if config.fuse == "concat":
                flat = (
                    jnp.concatenate([v.reshape(-1) for v in vals])
                    if len(vals) > 1
                    else vals[0].reshape(-1)
                )
                red = issue(Collective.ALL_REDUCE, flat, dp_axes)
                parts, off = [], 0
                for _, _, _, _, shp in metas:
                    n = int(np.prod(shp)) if shp else 1
                    parts.append(red[off : off + n].reshape(shp))
                    off += n
            else:
                parts = list(issue(Collective.ALL_REDUCE, tuple(vals), dp_axes))
            for (kind, path, ab, dt, _), r in zip(metas, parts):
                r = r.astype(dt)
                if config.average:
                    r = (r.astype(jnp.float32) / world).astype(dt)
                out = _write_back(out, kind, path, ab, r)
        return out, residual

    def sync(grads: Pytree, residual: Pytree | None = None):
        if stateful and residual is None:
            raise ValueError("compression='bf16_ef' needs the residual pytree")
        out = grads
        res_out = residual
        # Issue groups in backward order (layer-L group first), matching the
        # availability order the schedule was optimized for.  Each group is
        # wrapped in a named scope so device profiles (and the timeline
        # layer's per-group comm attribution) see the schedule boundaries.
        for gi in range(len(group_entries)):
            out, res_out = sync_group(gi, grads, out, res_out)
        return (out, res_out) if stateful else out

    # Metadata for the instrumentation layer (runtime/timeline.py): the
    # per-group wire payloads, in the same backward issue order the groups
    # execute in — what time_group_comm probes one psum per.
    sync.schedule = schedule
    sync.group_spans = group_spans
    sync.group_wire_bytes = group_wire_bytes
    sync.stateful = stateful
    sync.sync_group = sync_group
    sync.n_groups = len(group_entries)
    return sync


def _arena_group(
    entries: list[WireEntry],
    grads: Pytree,
    out: Pytree,
    residual: Pytree | None,
    dp_axes: tuple[str, ...],
    world,
    config: SyncConfig,
) -> tuple[Pytree, Pytree | None]:
    """One group over the arena wire path: pack(+cast[+EF]) -> one psum
    -> unpack(+decompress+average).  The arena layout is the plan-time
    ``bucketing.group_arenas`` layout, re-derived here from the traced
    gradient shapes (identical by construction — ``test_arena`` pins it).
    """
    parts, resid, metas = [], [], []
    off = 0
    for kind, path, ab in entries:
        g = _get(grads, path)
        if kind == "slice":
            g = g[ab[0] : ab[1]]
        if residual is not None:
            r = _get(residual, path)
            resid.append(r[ab[0] : ab[1]] if kind == "slice" else r)
        n = int(np.prod(g.shape)) if g.shape else 1
        metas.append((kind, path, ab, g.dtype, g.shape, off, n))
        parts.append(g)
        off += n
    arena, new_res = pack_arena(
        parts, [m[5] for m in metas], off, config.wire_dtype,
        residuals=resid if residual is not None else None,
    )
    red = issue(Collective.ALL_REDUCE, arena, dp_axes)
    scale = (1.0 / world) if config.average else 1.0
    unpacked = unpack_arena(
        red,
        [(m[5], m[6]) for m in metas],
        [m[4] for m in metas],
        [m[3] for m in metas],
        scale=scale,
    )
    for (kind, path, ab, _, _, _, _), r in zip(metas, unpacked):
        out = _write_back(out, kind, path, ab, r)
    if new_res is not None:
        for (kind, path, ab, _, _, _, _), r in zip(metas, new_res):
            residual = _write_back(residual, kind, path, ab, r)
    return out, residual


def _write_back(tree: Pytree, kind: str, path, ab, value: jax.Array) -> Pytree:
    if kind == "leaf":
        return _set(tree, path, value)
    cur = _get(tree, path)
    return _set(tree, path, cur.at[ab[0] : ab[1]].set(value.astype(cur.dtype)))


def _encode(g: jax.Array, config: SyncConfig) -> jax.Array:
    """Cast to the wire dtype.  'bf16' compression halves DP traffic for
    fp32 grads.  Sub-16-bit wire formats are not expressible through a TPU
    psum (the switch reduces in-flight); the int8 error-feedback path lives
    in ``runtime/compression.py`` and uses a reduce-scatter + quantized
    all-gather decomposition instead of this hook."""
    return g.astype(config.wire_dtype)


@functools.cache
def variadic_psum_is_single_op() -> bool:
    """Whether ``psum`` over a tuple lowers to ONE variadic all-reduce op.

    Answered by lowering ``psum((a, b), axis)`` on a one-device mesh once
    and counting the all-reduce ops; cached, so the cost is one tiny
    lowering per process.
    """
    mesh = jax.make_mesh((1,), ("_probe",), axis_types=(jax.sharding.AxisType.Auto,))
    P = jax.sharding.PartitionSpec

    def body(x, y):
        return jax.lax.psum((x, y), "_probe")

    f = jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        axis_names={"_probe"}, check_vma=False,
    )
    text = jax.jit(f).lower(jnp.zeros((8,)), jnp.zeros((4,))).as_text()
    return text.count("all_reduce") + text.count("all-reduce") <= 1


def count_expected_allreduces(
    schedule: Schedule,
    config: SyncConfig = SyncConfig(),
    layout: ParamLayout | None = None,
) -> int:
    """Gradient all-reduce ops the sync lowers to.

    'concat' and 'arena' reduce one flat buffer per group — exactly one
    op per group.  'variadic' issues one psum per group, which lowers to
    one variadic op per group where ``variadic_psum_is_single_op`` holds
    and to one op per operand otherwise — the honest expectation there
    needs the layout (wire-leaf count per group).
    """
    if (
        config.fuse in ("concat", "arena")
        or layout is None
        or variadic_psum_is_single_op()
    ):
        return len(schedule.groups)
    return sum(len(entries) for entries in wire_entries(layout, schedule))

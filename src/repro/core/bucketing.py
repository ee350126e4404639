"""Mapping between model parameter pytrees and schedule buckets.

The scheduler (``core.schedule``) works on the paper's flat layer list
``1..L``.  Real models are pytrees.  This module defines the bridge:

  * a ``ParamLayout`` names every *communication unit* in
    backward-availability order, with its gradient message size — the
    ``p`` vector of the paper.  Two unit kinds exist:

      - ``leaf``    — the unit owns whole pytree leaves (its ``paths``);
      - ``stacked`` — the unit is one index of a scan-stacked subtree:
        ``paths`` name the stacked leaves and ``stack_index`` selects the
        slice along their leading axis.  Contiguous stacked units in one
        schedule group collapse into a single ``[a:b]`` slice on the wire.

  * ``bucket_assignment`` groups the units according to a ``Schedule`` so
    the sync engine can issue exactly one all-reduce per group;
  * ``wire_entries`` flattens those groups into the per-group wire plan
    (leaf entries + contiguous ``[a:b)`` scan-slice entries) and
    ``group_arenas`` lays each group out as a flat **arena** — exact
    element offset/size per unit, zero padding, so the arena wire buffer
    holds exactly the group's gradient elements and is packed and
    unpacked in place (kernels/comm_pack);
  * stacked-layer models re-bucket by slicing the leading axis, which is
    also how checkpoints are converted when the schedule changes between
    runs (elastic restarts — a different N gives a different α–β model,
    hence a different optimal 𝕄).

Paths are stored as plain ``str``/``int`` tuples (jax key objects are
normalized away) so a ``ParamLayout`` serializes losslessly into the
``planning.Plan`` JSON artifact.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import numpy as np

from .cost_model import LayerCost
from .schedule import Schedule

LEAF = "leaf"
STACKED = "stacked"


def tree_get(tree: Any, path: tuple[Any, ...]) -> Any:
    """Indexed lookup on nested dict/list pytrees (jax key objects ok)."""
    for p in path:
        if hasattr(p, "key"):
            tree = tree[p.key]
        elif hasattr(p, "idx"):
            tree = tree[p.idx]
        else:
            tree = tree[p]
    return tree


def tree_set(tree: Any, path: tuple[Any, ...], value: Any) -> Any:
    """Functional set on nested dict/list pytrees."""
    if not path:
        return value
    p = path[0]
    key = p.key if hasattr(p, "key") else p.idx if hasattr(p, "idx") else p
    if isinstance(tree, dict):
        new = dict(tree)
        new[key] = tree_set(tree[key], path[1:], value)
        return new
    if isinstance(tree, (list, tuple)):
        new_l = list(tree)
        new_l[key] = tree_set(tree[key], path[1:], value)
        return type(tree)(new_l)
    raise TypeError(f"unsupported container {type(tree)} at {path}")


def normalize_path(path: tuple[Any, ...]) -> tuple[Any, ...]:
    """jax key-path entries -> plain str/int keys (JSON-serializable)."""
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(p.key)
        elif hasattr(p, "idx"):
            out.append(p.idx)
        elif hasattr(p, "name"):
            out.append(p.name)
        else:
            out.append(p)
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class CommUnit:
    """One schedulable gradient message (paper: one 'layer' l with p^(l))."""

    name: str
    index: int  # 1-based position in backward-forward layer order
    grad_bytes: int
    params: int
    # paths into the gradient pytree whose leaves belong to this unit
    # (kind == 'stacked': the stacked leaves, sliced at stack_index)
    paths: tuple[tuple[Any, ...], ...]
    kind: str = LEAF
    stack_index: int = -1


@dataclasses.dataclass(frozen=True)
class ParamLayout:
    """Ordered communication units for a model's gradient pytree.

    ``units[0]`` is layer 1 in the paper's numbering — the *first* forward
    layer, whose gradient lands *last* during backward.
    """

    units: tuple[CommUnit, ...]

    @property
    def num_layers(self) -> int:
        return len(self.units)

    def group_arenas(
        self, schedule: Schedule, shapes: Any, comm_dtype: Any = "float32"
    ) -> "list[GroupArena]":
        """Per-group flat wire layout of the arena wire (see
        ``group_arenas`` below for the shape-source contract)."""
        return group_arenas(self, schedule, shapes, comm_dtype)

    def layer_costs(
        self,
        tokens_per_chip: int,
        hw,
        bwd_flops_fn: Callable[[CommUnit], float] | None = None,
        fwd_flops_fn: Callable[[CommUnit], float] | None = None,
    ) -> list[LayerCost]:
        """LayerCost list in paper order, with pluggable flops models."""
        out = []
        for u in self.units:
            bwd = bwd_flops_fn(u) if bwd_flops_fn else 4.0 * u.params * tokens_per_chip
            fwd = fwd_flops_fn(u) if fwd_flops_fn else 2.0 * u.params * tokens_per_chip
            out.append(
                LayerCost(
                    name=u.name,
                    params=u.params,
                    grad_bytes=u.grad_bytes,
                    bwd_flops=bwd,
                    fwd_flops=fwd,
                )
            )
        return out


def _subtree_paths(tree: Any, prefix: tuple[Any, ...]) -> list[tuple[tuple[Any, ...], Any]]:
    """(full normalized path, leaf) pairs for every leaf under ``tree``."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [(prefix + normalize_path(tuple(p)), leaf) for p, leaf in flat]


def _leaf_size(leaf: Any) -> int:
    shape = getattr(leaf, "shape", ())
    return int(np.prod(shape)) if shape else 1


def layout_from_params(
    params: Any,
    comm_dtype_bytes: int = 4,
    model_shards: int = 1,
    order_key: Callable[[str], float] | None = None,
) -> ParamLayout:
    """Build a per-leaf ParamLayout from a parameter pytree.

    Leaves are ordered by ``order_key`` over their dot-joined path name
    (default: pytree order).  ``model_shards`` divides the DP message size
    (FSDP/TP/EP shrink the data-parallel all-reduce payload).
    """
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    named = []
    for path, leaf in leaves:
        name = jax.tree_util.keystr(path).strip("[].'\"").replace("']['", ".")
        named.append((name, normalize_path(tuple(path)), leaf))
    if order_key is not None:
        named.sort(key=lambda t: order_key(t[0]))
    units = []
    for i, (name, path, leaf) in enumerate(named):
        size = _leaf_size(leaf)
        units.append(
            CommUnit(
                name=name,
                index=i + 1,
                grad_bytes=max(1, size * comm_dtype_bytes // model_shards),
                params=size,
                paths=(path,),
            )
        )
    return ParamLayout(units=tuple(units))


def stacked_lm_layout(
    param_shapes: Any,
    n_stages: int,
    comm_dtype_bytes: int = 4,
    model_shards: int = 1,
) -> ParamLayout:
    """ParamLayout for the stacked-scan LM param pytree.

    ``param_shapes`` is the model's parameter (shape) pytree with top-level
    subtrees ``embed``, ``stages`` (leaves stacked on a leading axis of
    length ``n_stages``), ``final_norm``, optional ``tail`` and optional
    ``head`` (absent when embeddings are tied).

    Units in paper order (gradient of unit 1 lands last):
      unit 1             = embed                       (leaf kind)
      units 2..n+1       = scan stages                 (stacked kind)
      unit n+2 (if tail) = tail stage                  (leaf kind)
      last unit          = head + final_norm           (leaf kind)
    """

    def leaf_unit(name: str, idx: int, pairs: list[tuple[tuple[Any, ...], Any]]) -> CommUnit:
        size = sum(_leaf_size(leaf) for _, leaf in pairs)
        return CommUnit(
            name=name,
            index=idx,
            grad_bytes=max(1, size * comm_dtype_bytes // model_shards),
            params=size,
            paths=tuple(p for p, _ in pairs),
        )

    units = [leaf_unit("embed", 1, _subtree_paths(param_shapes["embed"], ("embed",)))]

    stage_pairs = _subtree_paths(param_shapes["stages"], ("stages",))
    stage_params = sum(_leaf_size(leaf) for _, leaf in stage_pairs) // n_stages
    stage_paths = tuple(p for p, _ in stage_pairs)
    for i in range(n_stages):
        units.append(
            CommUnit(
                name=f"stage_{i}",
                index=i + 2,
                grad_bytes=max(1, stage_params * comm_dtype_bytes // model_shards),
                params=stage_params,
                paths=stage_paths,
                kind=STACKED,
                stack_index=i,
            )
        )

    idx = n_stages + 2
    if "tail" in param_shapes:
        units.append(leaf_unit("tail", idx, _subtree_paths(param_shapes["tail"], ("tail",))))
        idx += 1

    head_pairs = _subtree_paths(param_shapes["final_norm"], ("final_norm",))
    if "head" in param_shapes:
        head_pairs += _subtree_paths(param_shapes["head"], ("head",))
    units.append(leaf_unit("head", idx, head_pairs))
    return ParamLayout(units=tuple(units))


def layout_for_stacked_lm(
    num_layers: int,
    embed_params: int,
    layer_params: int,
    head_params: int,
    comm_dtype_bytes: int = 4,
    model_shards: int = 1,
) -> ParamLayout:
    """Synthetic ParamLayout for a stacked-scan LM: [embed, layer×L, head].

    Cost-model-only variant (no real pytree behind it); see
    ``stacked_lm_layout`` for the executable one.
    """

    def unit(name: str, idx: int, p: int) -> CommUnit:
        return CommUnit(
            name=name,
            index=idx,
            grad_bytes=max(1, p * comm_dtype_bytes // model_shards),
            params=p,
            paths=((name,),),
        )

    units = [unit("embed", 1, embed_params)]
    units += [unit(f"layer_{i}", i + 2, layer_params) for i in range(num_layers)]
    units += [unit("head", num_layers + 2, head_params)]
    return ParamLayout(units=tuple(units))


def bucket_assignment(layout: ParamLayout, schedule: Schedule) -> list[list[CommUnit]]:
    """Units grouped per schedule group, ascending (layer-1 group first)."""
    if schedule.num_layers != layout.num_layers:
        raise ValueError(
            f"schedule covers {schedule.num_layers} layers, layout has {layout.num_layers}"
        )
    groups = []
    for lo, hi in schedule.groups:
        groups.append([layout.units[i - 1] for i in range(lo, hi + 1)])
    return groups


# One wire entry: ('leaf', path, None) or ('slice', path, (a, b)).
WireEntry = tuple[str, tuple[Any, ...], tuple[int, int] | None]


def wire_entries(layout: ParamLayout, schedule: Schedule) -> list[list[WireEntry]]:
    """Per-group wire plan in backward issue order (layer-L group first).

    Leaf units contribute one entry per leaf path; contiguous stacked
    units collapse into one ``[a:b)`` slice entry per stacked leaf path.
    """
    groups: list[list[WireEntry]] = []
    for units in reversed(bucket_assignment(layout, schedule)):
        entries: list[WireEntry] = []
        runs: dict[tuple, list[int]] = {}
        for u in units:
            if u.kind == LEAF:
                entries.extend(("leaf", p, None) for p in u.paths)
            else:
                runs.setdefault(u.paths, []).append(u.stack_index)
        for paths, idxs in runs.items():
            a, b = min(idxs), max(idxs) + 1
            if sorted(idxs) != list(range(a, b)):
                raise ValueError(f"stacked units in one group must be contiguous: {idxs}")
            entries.extend(("slice", p, (a, b)) for p in paths)
        groups.append(entries)
    return groups


@dataclasses.dataclass(frozen=True)
class ArenaSlot:
    """One wire entry's span inside its group's flat arena."""

    kind: str  # 'leaf' | 'slice'
    path: tuple[Any, ...]
    stack_range: tuple[int, int] | None  # [a, b) over the scan axis
    offset: int  # element offset into the arena
    size: int  # elements
    shape: tuple[int, ...]  # shape of the packed value


@dataclasses.dataclass(frozen=True)
class GroupArena:
    """Flat wire layout of one schedule group.

    Offsets are exact-packed (no per-slot padding): ``size`` equals the
    sum of slot sizes, so the arena's psum payload is exactly the group's
    gradient elements in the wire dtype — no padding on the wire.
    """

    slots: tuple[ArenaSlot, ...]
    size: int  # total elements
    comm_dtype: str

    @property
    def nbytes(self) -> int:
        return self.size * np.dtype(self.comm_dtype).itemsize


def group_arenas(
    layout: ParamLayout,
    schedule: Schedule,
    shapes: Any,
    comm_dtype: Any = "float32",
) -> list[GroupArena]:
    """Plan-time arena layouts, one per schedule group (backward order).

    ``shapes`` is either the parameter (shape) pytree or a callable
    ``path -> shape`` — only leaf shapes are consulted, so abstract
    ``ShapeDtypeStruct`` trees and live gradient trees both work.
    """
    if callable(shapes):
        shape_of = shapes
    else:
        def shape_of(p):
            leaf = tree_get(shapes, p)
            shape = getattr(leaf, "shape", None)
            if shape is None:
                raise TypeError(
                    f"leaf at {p} has no .shape ({type(leaf).__name__}); pass "
                    "arrays / ShapeDtypeStructs or a path->shape callable"
                )
            return tuple(shape)
    dtype_name = np.dtype(comm_dtype).name if not isinstance(comm_dtype, str) else comm_dtype
    arenas = []
    for entries in wire_entries(layout, schedule):
        slots, off = [], 0
        for kind, path, ab in entries:
            shape = tuple(shape_of(path))
            if kind == "slice":
                shape = (ab[1] - ab[0],) + shape[1:]
            n = int(np.prod(shape)) if shape else 1
            slots.append(
                ArenaSlot(
                    kind=kind, path=path, stack_range=ab,
                    offset=off, size=n, shape=shape,
                )
            )
            off += n
        arenas.append(GroupArena(slots=tuple(slots), size=off, comm_dtype=dtype_name))
    return arenas


def layer_buckets_for_scan(schedule: Schedule, num_scan_layers: int) -> tuple[tuple[int, int], ...]:
    """Translate a [embed, L layers, head] schedule into scan segments.

    Returns (start, stop) ranges over the stacked layer axis.  The embed and
    head units are handled separately by the sync engine; groups that span
    the embed/head boundary keep the layer slice only.
    """
    segs = []
    for lo, hi in schedule.groups:
        # schedule indices: 1 = embed, 2..L+1 = layers, L+2 = head
        start = max(lo - 2, 0)
        stop = min(hi - 1, num_scan_layers)
        if stop > start:
            segs.append((start, stop))
    # Ensure full coverage of the scan axis.
    covered = sum(b - a for a, b in segs)
    if covered != num_scan_layers:
        raise ValueError(f"scan segments {segs} do not cover {num_scan_layers} layers")
    return tuple(segs)
